"""``startup_fused_build_s``: the span ``fused/build`` before the window, summed over the kinds
of a donated fused program (its children ``lower`` and ``compile`` are in the program's
JSONL); ``None`` where the program is not donated, or the record has no ``counters``."""

from benchmark.readers import startup_process_s


def read(record):
    return startup_process_s.before(record, "span/fused/build/total_s")
