"""``train_mfu``: required forward and backward matrix-multiplication
operations per trained frame x frames/s of the traced dispatches, over
chips x the peak FLOP/s of the type the configuration multiplies in. An
end-to-end utilisation on the host's clock, not a roofline share."""

from benchmark.harness import flops


def read(record):
    w = record["window"]
    if not w.get("traced_dispatches") or not w.get("traced_seconds"):
        return None
    shape = flops.PolicyShape.from_run_config(record["run_config"])
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    per_frame = flops.train_flops_per_frame(
        shape, record["lanes"], record["opp_lanes"], record["rollout_len"]
    )
    frames_per_s = w["traced_dispatches"] * w["frames_per_dispatch"] / w["traced_seconds"]
    peak = flops.peak_flops_per_s(peaks, shape.dtype)
    return 100.0 * per_frame * frames_per_s / (record["chips"] * peak)
