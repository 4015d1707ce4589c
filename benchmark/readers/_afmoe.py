"""What ``afmoe_core_roofline`` and ``afmoe_train_mfu`` share: the two things
a run counted that the counts in ``harness/flops_afmoe.py`` depend on."""


def pairs_per_token(record):
    """Token-expert pairs a token that landed on a held expert, all expert
    layers, from the last logged update's ``moe/local_assignments`` (one
    learner pass over lanes x (T + 1) tokens); ``None`` where the program
    counted none."""
    pairs = record["counters"]["after"].get("moe/local_assignments")
    if not pairs:
        return None
    return pairs / (record["lanes"] * (record["rollout_len"] + 1))


def traced_position(record):
    """Mean position in its episode of a step inside the traced dispatches:
    the runner notes the lanes' mean position when the window ends
    (``core_position_at_end``) and every dispatch moved it by T."""
    w = record["window"]
    end = record.get("core_position_at_end")
    if end is None or not w.get("traced_dispatches"):
        return None
    after = w["dispatches"] - w["traced_until_dispatch"]
    middle = end - (after + w["traced_dispatches"] / 2.0) * record["rollout_len"]
    return max(middle, 0.0)
