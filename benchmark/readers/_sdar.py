"""What the SDAR cell's readers share: the two things a run counted that the
counts in ``harness/flops_sdar.py`` depend on."""

from benchmark.harness import flops_sdar


def traced_step(record):
    """Mean step of its episode of a rollout step inside the traced
    dispatches: the runner notes the lanes' mean ``pos`` when the window ends
    (``core_position_at_end``: positions, six a step) and every dispatch moved
    it by T steps."""
    w = record["window"]
    end = record.get("core_position_at_end")
    if end is None or not w.get("traced_dispatches"):
        return None
    after = w["dispatches"] - w["traced_until_dispatch"]
    middle = end / flops_sdar.ROWS - (after + w["traced_dispatches"] / 2.0) * record["rollout_len"]
    return max(middle, 0.0)


def pairs_per_row(record):
    """Token-expert pairs a row that landed on a held expert, all layers, from
    the last logged update's ``moe/local_assignments`` (one learner pass over
    lanes x its rows); ``None`` where the program counted none."""
    pairs = record["counters"]["after"].get("moe/local_assignments")
    if pairs is None:
        return None
    rows = flops_sdar.learner_rows(record["run_config"]["model"], record["rollout_len"])
    return pairs / (record["lanes"] * rows)
