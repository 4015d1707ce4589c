"""``startup_warmup_s``: the span ``learner/train`` before the window, which is the warm-up
call (the first dispatch's build or load of the fused program in it); ``None`` for a record
without ``counters`` or a program without the span."""

from benchmark.readers import startup_process_s


def read(record):
    return startup_process_s.before(record, "span/learner/train/total_s")
