"""``unscoped_device_share``: share of device busy time under neither
``phase_rollout`` nor ``phase_update``: the remainder, which grows when someone
adds unscoped work to the fused program. With the other two it adds up to
100."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: not any(_scopes.under(op, p) for p in _scopes.PHASES))
