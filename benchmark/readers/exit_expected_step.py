"""``exit_expected_step``: the program's own gauge ``looplm/expected_exit_step`` as the
window left it (the mean loop step of exit, counted from 1, of the last logged update);
``None`` where the program has no such gauge or passed no log boundary."""


def read(record):
    return record["counters"]["after"].get("looplm/expected_exit_step") or None
