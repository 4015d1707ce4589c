"""``looplm_core_roofline``: the least time one chip could take for the looped core's
operations and bytes (``harness/flops_looplm.py``, peaks by ``device_kind``) over the
traced time under ``policy_core``, per dispatch, mean over chips. Which bound applies goes
to ``record["notes"]``."""

from benchmark.harness import flops, flops_looplm, result, trace
from benchmark.readers import _afmoe


def read(record):
    tw = result.traced_window(record)
    n = record["window"].get("traced_dispatches")
    position = _afmoe.traced_position(record)
    if tw is None or not n or position is None:
        return None
    tr, lo, hi = tw
    chips = len(tr.devices)
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    work = flops_looplm.core_dispatch_work(
        record["run_config"], record["lanes"] // chips, record["opp_lanes"] // chips,
        record["rollout_len"], position,
    )
    least = flops.roofline_seconds(work, peaks, record["run_config"]["model"]["dtype"])
    core = [trace.scope_seconds(p, lo, hi).get("policy_core", 0.0) / n for p in tr.devices]
    core = [c for c in core if c > 0]
    if not core:
        return None
    record.setdefault("notes", {})["looplm_core_roofline"] = {
        "bound": least["bound"], "least_s_per_dispatch": least["seconds"],
        "compute_s": least["compute_s"], "memory_s": least["memory_s"],
        "traced_s_per_dispatch": sum(core) / len(core),
        "flops_per_dispatch_per_chip": work["flops"], "bytes_per_dispatch_per_chip": work["bytes"],
        "weight_bytes_a_pass": work["weight_bytes_a_pass"],
        "seen_cache_bytes_per_lane": work["seen_cache_bytes_per_lane"], "mean_position": position,
    }
    return 100.0 * least["seconds"] / (sum(core) / len(core))
