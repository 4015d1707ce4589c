"""``sampling_device_share``: share of device busy time under
``rollout_sample`` (key split, masked sampling and log-probabilities, packing,
``actions_to_sim``), mean over chips."""

from benchmark.readers import _scopes


def read(record):
    return _scopes.share(record, lambda op: _scopes.under(op, "rollout_sample"))
