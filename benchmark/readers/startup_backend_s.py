"""``startup_backend_s``: the program's counter ``compile/backend_s_total`` before the window
(XLA compiles or, on a persistent-cache hit, loads; summed over threads); ``None`` for a
record without ``counters`` or a program without the counter."""

from benchmark.readers import startup_process_s


def read(record):
    return startup_process_s.before(record, "compile/backend_s_total")
