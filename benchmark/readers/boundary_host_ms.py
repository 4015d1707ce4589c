"""``boundary_host_ms``: host milliseconds a log boundary, the span ``learner/boundary`` over
``learner/boundaries_total`` in the window; ``None`` without ``counters`` or without a
boundary."""

from benchmark.tools import host_spans


def window_table(record):
    """``host_spans.host_table`` between the registry's two snapshots (before and after the
    window), ``None`` for a record without ``counters`` or with an empty snapshot. The other
    ``learner_loop`` readers read through this and ``per_boundary``."""
    counters = record.get("counters") or {}
    first, last = counters.get("before"), counters.get("after")
    if not first or not last:
        return None
    return host_spans.host_table(first, last)


def per_boundary(record, span):
    """Milliseconds a log boundary in ``span``; ``None`` where the window passed no boundary,
    the span did not run in it, or a timer or the dispatch counter went backwards (a registry
    cleared between the snapshots)."""
    table = window_table(record)
    if table is None or not table["dispatches"] > 0 or not table["count"].get(span, 0.0) > 0:
        return None
    ms = table["per_boundary_ms"].get(span)
    return ms if ms is not None and ms >= 0 else None


def read(record):
    return per_boundary(record, host_spans.BOUNDARY)
