"""``exit_mix_device_share``: what leaving after any loop step costs. Share of device
busy time under ``core_exit_gate`` (``models/looplm.py``) or ``update_exit_mix``
(``train/ppo.exit_weighted_loss``: the exit distribution, the mix and its entropy), plus
(R - 1) / R of the update's ``policy_heads``: the learner's pass puts the heads on all R
loop steps' outputs in ONE call, of which a stack that could not leave early would need
the last alone (the rollout's heads read only that one and are not counted). Mean over
chips; 0 where a program has no such scopes."""

from benchmark.readers import _scopes


def read(record):
    gate = _scopes.share(
        record, lambda op: _scopes.under(op, "core_exit_gate") or _scopes.under(op, "update_exit_mix")
    )
    heads = _scopes.share(record, lambda op: _scopes.under(op, "phase_update", "policy_heads"))
    if gate is None or heads is None:
        return None
    loops = record["run_config"]["model"].get("loop_steps", 1)
    return gate + heads * (loops - 1) / loops
