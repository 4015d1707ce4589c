"""``expert_load_max_over_mean``: the program's own gauge
``moe/max_over_mean_expert_load`` as the window left it; ``None`` where the
program has no such gauge or passed no log boundary."""


def read(record):
    return record["counters"]["after"].get("moe/max_over_mean_expert_load") or None
