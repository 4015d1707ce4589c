"""``sim_device_share``: share of device busy time in operations whose
source is ``envs/jax_lane_sim.py``, mean over chips."""

from benchmark.harness import result, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    return trace.mean_share_where(*tw, lambda op: "envs/jax_lane_sim.py" in op.source)
