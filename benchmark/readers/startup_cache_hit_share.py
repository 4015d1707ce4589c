"""``startup_cache_hit_share``: persistent-cache hits over hits + misses before the window,
per cent, from the program's counters; ``None`` where nothing asked the cache, the record
has no ``counters`` or the program has no such counters."""

from benchmark.readers import startup_process_s


def read(record):
    hits = startup_process_s.before(record, "compile/cache_hits_total") or 0.0
    asked = hits + (startup_process_s.before(record, "compile/cache_misses_total") or 0.0)
    return 100.0 * hits / asked if asked > 0 else None
