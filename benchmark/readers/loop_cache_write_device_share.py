"""``loop_cache_write_device_share``: share of device busy time in operations written
under ``core_loop`` and ``core_cache_write`` (a step's rows scattered into the ring of each
layer and loop step), mean over chips; 0 where a program has no such scopes."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_loop", "core_cache_write"))
