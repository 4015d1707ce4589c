"""``league_fetch_ms``: host milliseconds a log boundary in ``learner/boundary/league_fetch``
(the train thread's fetch of the league's pending reports); ``None`` without ``counters``,
without a boundary or without a league."""

from benchmark.readers import boundary_host_ms
from benchmark.tools import host_spans


def read(record):
    return boundary_host_ms.per_boundary(record, host_spans.BOUNDARY + "/league_fetch")
