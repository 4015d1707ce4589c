"""``startup_learner_init_s``: the span ``startup/learner_init``, the whole ``Learner``
constructor (its children ``startup/learner_init/<stage>`` are in the program's JSONL);
``None`` for a record without ``counters`` or a program without the span."""

from benchmark.readers import startup_process_s


def read(record):
    return startup_process_s.before(record, "span/startup/learner_init/total_s")
