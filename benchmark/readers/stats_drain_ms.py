"""``stats_drain_ms``: host milliseconds a log boundary in ``learner/boundary/stats_drain``
(``DeviceActor.begin_drain`` and the stats job's submission); ``None`` without ``counters``
or without a boundary."""

from benchmark.readers import boundary_host_ms
from benchmark.tools import host_spans


def read(record):
    return boundary_host_ms.per_boundary(record, host_spans.BOUNDARY + "/stats_drain")
