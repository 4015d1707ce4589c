"""``nonpolicy_device_share``: share of device busy time under no
``policy_*`` scope (simulation, featurizer, sampling, GAE, loss, Adam,
collectives), mean over chips."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, lambda op: not trace.scope_of(op).startswith("policy_"))
