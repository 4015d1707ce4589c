"""``loop_host_ms_per_dispatch``: the loop's host milliseconds a dispatch outside the dispatch
call and the log boundary, over the window: (``learner/iteration`` - ``learner/dispatch`` -
``learner/boundary``) over ``learner/dispatches_total``. ``learner/dispatch`` is left out
because under the benchmark it holds the meter's wait for dispatch i-2 and, traced, the
profiler's start and stop. ``None`` without ``counters``, without a dispatch, or where the
difference is negative (a registry cleared between the snapshots)."""

from benchmark.readers import boundary_host_ms
from benchmark.tools import host_spans


def read(record):
    table = boundary_host_ms.window_table(record)
    ms = table["per_dispatch_ms"] if table is not None else {}     # empty without a dispatch
    if host_spans.ITERATION not in ms:
        return None
    host = ms[host_spans.ITERATION] - ms.get("learner/dispatch", 0.0) - ms.get(host_spans.BOUNDARY, 0.0)
    return host if host >= 0 else None
