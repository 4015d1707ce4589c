"""``startup_trace_lower_s``: the program's counters ``compile/trace_s_total`` +
``compile/lower_s_total`` before the window (JAX's own events, every program of the process,
summed over threads); ``None`` for a record without ``counters`` or a program without them."""

from benchmark.readers import startup_process_s


def read(record):
    trace = startup_process_s.before(record, "compile/trace_s_total")
    if trace is None:
        return None
    return trace + (startup_process_s.before(record, "compile/lower_s_total") or 0.0)
