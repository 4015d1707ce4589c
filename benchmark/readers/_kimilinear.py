"""What ``kda_state_roofline`` and ``latent_attend_roofline`` share: the least time for a
scope's work (``harness/flops_kimilinear.py``, peaks by ``device_kind``) over the traced
time of the operations written under that scope, per dispatch, mean over chips."""

from benchmark.harness import flops, result, trace
from benchmark.readers import _scopes


def scope_roofline(record, metric, scope, work):
    """``work(chips) -> {"flops", "bytes", ...}`` for one chip's share of a dispatch;
    ``None`` untraced or where no operation carries ``scope`` (a program without it).
    Which bound applies goes to ``record["notes"][metric]``."""
    tw = result.traced_window(record)
    n = record["window"].get("traced_dispatches")
    if tw is None or not n:
        return None
    tr, lo, hi = tw
    keep = lambda op: _scopes.under(op, scope)
    # self times of one chip's operations add up to its busy time, so a share of it is a time
    scoped = [
        (trace.share_where(p, lo, hi, keep) or 0.0) * trace.busy_seconds(p, lo, hi) / n for p in tr.devices
    ]
    scoped = [s for s in scoped if s > 0]
    if not scoped:
        return None
    per_chip = work(len(tr.devices))
    peaks = flops.peaks_for(record["devices"][0].device_kind)
    least = flops.roofline_seconds(per_chip, peaks, record["run_config"]["model"]["dtype"])
    traced = sum(scoped) / len(scoped)
    record.setdefault("notes", {})[metric] = {
        "bound": least["bound"], "least_s_per_dispatch": least["seconds"],
        "compute_s": least["compute_s"], "memory_s": least["memory_s"],
        "traced_s_per_dispatch": traced, **per_chip,
    }
    return 100.0 * least["seconds"] / traced
