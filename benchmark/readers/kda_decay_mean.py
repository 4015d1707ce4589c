"""``kda_decay_mean``: the program's own gauge ``kda/decay_mean`` as the window left it (the
mean of alpha, what a step keeps of a state's channel, over the last logged update's lanes,
steps, heads and channels); ``None`` where the program has no such gauge or passed no log
boundary."""


def read(record):
    return record["counters"]["after"].get("kda/decay_mean") or None
