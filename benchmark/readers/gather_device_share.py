"""``gather_device_share``: share of device busy time in operations built
around a ``gather`` (the last part of the scoped name), mean over chips."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, lambda op: op.scope.rstrip(":").rsplit("/", 1)[-1] == "gather")
