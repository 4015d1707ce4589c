"""``sdar_commit_device_share``: share of device busy time in operations written under
``core_commit`` (``models/sdar.py decode``: the pass that runs a step's finished block once more
and writes its keys and values to the rings), mean over chips; 0 where a program has no such scope."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "core_commit"))
