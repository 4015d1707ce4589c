"""``optimizer_device_share``: share of device busy time under
``update_optimizer`` (warm-up masking, clipping, Adam, ``apply_updates``),
mean over chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "update_optimizer"))
