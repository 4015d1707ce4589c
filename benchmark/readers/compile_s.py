"""``compile_s``: seconds of tracing, lowering and compiling (or cache
loading) before the window, from ``jax.monitoring`` events."""


def read(record):
    return record["setup"].get("compile_s")
