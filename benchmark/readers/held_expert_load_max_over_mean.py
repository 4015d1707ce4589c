"""``held_expert_load_max_over_mean``: the program's own gauge
``moe/max_over_mean_expert_load`` as the window left it, in a cell whose router has 256 outputs
and 8 held. Where no pair of the logged update landed on a held expert (early in training the
lanes choose like experts, and the chosen 8 of 256 need not include one of the held 8: my chip
run, PR 32) the gauge reads 0.0 and so does this, where ``expert_load_max_over_mean`` would
leave its metric out; ``None`` where the program has no such gauge or passed no log boundary."""


def read(record):
    return record["counters"]["after"].get("moe/max_over_mean_expert_load")
