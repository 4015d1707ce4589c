"""``featurizer_device_share``: share of device busy time in operations
whose source is under ``dotaclient_tpu/features/``, mean over chips."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, lambda op: "dotaclient_tpu/features/" in op.source)
