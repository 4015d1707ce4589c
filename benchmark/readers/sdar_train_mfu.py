"""``sdar_train_mfu``: required forward and backward products per trained frame
(``harness/flops_sdar.py``: every pass of the decode and the learner's copies, held experts only,
the ring rows a query sees) x frames/s of the traced dispatches, over chips x the peak FLOP/s of the
type the configuration multiplies in. An end-to-end utilisation on the host's clock, not a roofline
share."""

from benchmark.harness import flops, flops_sdar
from benchmark.readers import _sdar


def read(record):
    w = record["window"]
    step, pairs = _sdar.traced_step(record), _sdar.pairs_per_row(record)
    if not w.get("traced_dispatches") or not w.get("traced_seconds") or step is None or pairs is None:
        return None
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    per_frame = flops_sdar.train_flops_per_frame(
        record["run_config"], record["lanes"], record["opp_lanes"], record["rollout_len"], step, pairs,
    )
    frames_per_s = w["traced_dispatches"] * w["frames_per_dispatch"] / w["traced_seconds"]
    peak = flops.peak_flops_per_s(peaks, record["run_config"]["model"]["dtype"])
    return 100.0 * per_frame * frames_per_s / (record["chips"] * peak)
