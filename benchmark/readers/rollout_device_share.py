"""``rollout_device_share``: share of device busy time under ``phase_rollout``
(the sixteen rollout steps of both teams and the chunk's assembly), mean over
chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "phase_rollout"))
