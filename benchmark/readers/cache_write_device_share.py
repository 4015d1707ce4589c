"""``cache_write_device_share``: share of device busy time in operations written under
``core_cache_write`` (``models/afmoe.py``, inside ``policy_core``), forward and
transposed, mean over chips; 0 where a program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_cache_write"))
