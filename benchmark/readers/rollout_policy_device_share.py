"""``rollout_policy_device_share``: share of device busy time under
``phase_rollout`` and one of the policy's scopes: both teams' forward passes
in step mode, mean over chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "phase_rollout") and _scopes.in_policy(op))
