"""``dispatch_gap_ms``: median idle gap between consecutive executions of
the cell's main program on the fullest chip's ``XLA Modules`` line."""

from benchmark.harness import result, stats, trace


def read(record):
    tw = result.traced_window(record)
    if tw is None:
        return None
    tr, lo, hi = tw
    plane = max(tr.devices, key=lambda d: trace.busy_seconds(d, lo, hi))
    module = trace.dominant_module(plane, lo, hi)
    if module is None:
        return None
    gaps = trace.module_gaps(plane, lo, hi, module)
    return stats.median(gaps) * 1e3 if gaps else None
