"""``lfm2moe_train_mfu``: required forward and backward products per trained frame
(``harness/flops_lfm2moe.py``: held experts only, the ring rows a query sees) x frames/s of the
traced dispatches, over chips x the peak FLOP/s of the type the configuration multiplies in. An
end-to-end utilisation on the host's clock, not a roofline share."""

from benchmark.harness import flops, flops_lfm2moe
from benchmark.readers import _afmoe


def read(record):
    w = record["window"]
    position = _afmoe.traced_position(record)
    # pairs that landed on a held expert in the logged update: 0 is a count (no held expert was
    # chosen), a missing gauge is not
    pairs = record["counters"]["after"].get("moe/local_assignments")
    if not w.get("traced_dispatches") or not w.get("traced_seconds") or position is None or pairs is None:
        return None
    pairs = pairs / (record["lanes"] * (record["rollout_len"] + 1))
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    per_frame = flops_lfm2moe.train_flops_per_frame(
        record["run_config"], record["lanes"], record["opp_lanes"], record["rollout_len"], position, pairs,
    )
    frames_per_s = w["traced_dispatches"] * w["frames_per_dispatch"] / w["traced_seconds"]
    peak = flops.peak_flops_per_s(peaks, record["run_config"]["model"]["dtype"])
    return 100.0 * per_frame * frames_per_s / (record["chips"] * peak)
