"""``diffusion_passes_per_step``: the core passes a rollout step ran in the window, from the
program's own counters: ``diffusion/passes_total`` over ``learner/dispatches_total`` x T (moved
together in the fused loop, so whole dispatches). ``None`` without ``counters``, without a
dispatch, or where the program has no such counter."""


def read(record):
    before, after = record.get("counters", {}).get("before"), record.get("counters", {}).get("after")
    if not before or not after:
        return None
    key, per = "diffusion/passes_total", "learner/dispatches_total"
    if key not in after or per not in after:
        return None
    dispatches = after[per] - before.get(per, 0.0)
    passes = after[key] - before.get(key, 0.0)
    if dispatches <= 0 or passes < 0:
        return None
    return passes / (dispatches * record["rollout_len"])
