"""``update_loss_device_share``: share of device busy time under
``update_loss`` and none of the policy's scopes: GAE, log-probabilities,
entropy, value loss, and their backward, mean over chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "update_loss") and not _scopes.in_policy(op))
