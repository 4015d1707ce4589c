"""``policy_core_share``: share of device busy time under ``policy_core``
(``policy_core_scan`` included, forward and transposed), mean over chips."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, lambda op: trace.scope_of(op) == "policy_core")
