"""``policy_core_roofline``: the least time one chip could take for the
LSTM core's operations and bytes (``harness/flops.py``: per-chip lanes,
peaks by ``device_kind``) over the traced time under ``policy_core*``, per
dispatch, mean over chips. Which bound applies goes to ``record["notes"]``."""

from benchmark.harness import flops, result, trace


def read(record):
    tw = result.traced_window(record)
    n = record["window"].get("traced_dispatches")
    if tw is None or not n:
        return None
    tr, lo, hi = tw
    chips = len(tr.devices)
    shape = flops.PolicyShape.from_run_config(record["run_config"])
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    work = flops.core_dispatch_work(
        shape, record["lanes"] // chips,
        record["opp_lanes"] // chips, record["rollout_len"],
    )
    least = flops.roofline_seconds(work, peaks, shape.dtype)
    core = [
        trace.scope_seconds(p, lo, hi).get("policy_core", 0.0) / n
        for p in tr.devices
    ]
    core = [c for c in core if c > 0]
    if not core:
        return None
    record.setdefault("notes", {})["policy_core_roofline"] = {
        "bound": least["bound"], "least_s_per_dispatch": least["seconds"],
        "traced_s_per_dispatch": sum(core) / len(core),
        "flops_per_dispatch_per_chip": work["flops"],
        "bytes_per_dispatch_per_chip": work["bytes"],
    }
    return 100.0 * least["seconds"] / (sum(core) / len(core))
