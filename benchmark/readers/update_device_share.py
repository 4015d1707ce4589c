"""``update_device_share``: share of device busy time under ``phase_update``
(loss forward and backward, optimizer, probes), mean over chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "phase_update"))
