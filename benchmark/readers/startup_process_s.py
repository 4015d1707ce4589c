"""``startup_process_s``: the program's gauge ``startup/process_age_at_init_s``, seconds from
the kernel's record of the process start to the first line of ``Learner.__init__``; ``None``
for a record without ``counters`` or a program without the gauge (or a kernel without the
record, where the gauge reads 0)."""


def before(record, key):
    """``key`` in the registry's snapshot before the window, ``None`` where the record has no
    ``counters``, the snapshot is empty or lacks the key, or the value is not above 0 (an
    eager-created key that nothing fed). The other ``startup_*`` readers read through this."""
    value = ((record.get("counters") or {}).get("before") or {}).get(key)
    return value if isinstance(value, (int, float)) and value > 0 else None


def read(record):
    return before(record, "startup/process_age_at_init_s")
