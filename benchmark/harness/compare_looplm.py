"""The comparison that decides ``correct`` for a policy with the looped core.

On the run's own device, at the cell's widths and with the run's trained
parameters, a seeded sample of lanes (``compare_afmoe.sample``: episodes end
at a thousandth of the steps, once inside the compared chunk, and one lane
runs its last 2,100 steps unbroken) is fed ``history_steps`` observations in
chunks of ``steps`` through the program's ``Policy.sequence`` (a reset
through the core between chunks, ``dones`` inside them), and the LAST chunk
once more step by step through ``Policy.step`` from the same carry. Compared
with ``reference/looplm_ref.py`` over each lane's WHOLE history, computed a
lane at a time so that it fits:

* ``*_sequence``: logits and values of EVERY loop step and the exit gates'
  logits, as ``sequence`` hands them to the loss;
* ``*_step``: logits and values of the last loop step, as the rollout acts.

Differences are relative to the outputs' size, as in ``harness/compare.py``.
The program's lanes go ``LANE_BLOCK`` at a time: a lane's rings are 403 MB
in bfloat16 and twice that in float32, and sixteen lanes' do not fit beside
the parameters. The step-by-step pass starts from the learner's view of the
chunk (``Policy.chunk_start_carry``: the start's counters beside the END's
rings), as the fused program hands it over.

What a wrong loop would show: a loop step skipped, two loop steps in one
ring, or weights that are not one set each move every later loop step's
outputs by more than any limit here (``tests/test_looplm.py`` makes the
reference wrong in each way, and ``benchmark/tests/test_looplm_cell.py``
the program).

Two numbers a precision, two limits, each set between two readings on the
v5e at the cell's widths (my chip runs, PR 30: twelve runs of the cell, twelve
seeds, trained parameters; the readings' origin in full in PERF.md section 6):

* ``TOL_EXACT`` (2e-4): the program with every product in float32 at
  "highest" precision. Same arithmetic as the reference in another order
  (rings, a two-part softmax, every head's scores in one block-diagonal
  product), so what is left is float32 accumulation over up to 5,632 terms
  through sixteen layer passes: 9.5e-8 to 5.8e-7 on the v5e, 1.6e-7 at the
  rehearsal's widths on the CPU. The program as stated (bfloat16) reads
  6.4e-4 to 5.6e-3 against the same reference and fails this limit: it pins
  the mathematics.
* ``TOL_STATED["bfloat16"]`` (0.04): the policy as the configuration states
  it, bfloat16 products and caches, float32 parameters, residual stream,
  softmax and gate: 6.4e-4 to 5.6e-3 over the twelve seeds (the norm between
  loop steps keeps sixteen layer passes from compounding: the afmoe cell's
  five read 7.2e-3). The same reference with every product's operands
  rounded to bfloat16 reads 0.0101 against itself in float32 (seeded initial
  weights, whose outputs are smaller), and with them rounded to 8-bit floats
  (unscaled e4m3, the nearest precision below;
  ``benchmark/tools/looplm_precision_below.py``) 0.447: not correct. The
  limit is 7 times the program's worst reading, 4 times the reference's own
  bfloat16 reading and 11 times under the 8-bit one, near the geometric
  middle of 5.6e-3 and 0.447 (0.05). A float32-stated configuration is held
  to the exact limit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping

import numpy as np

from benchmark.harness.compare_afmoe import sample
from benchmark.reference import looplm_ref, policy_ref

TOL_EXACT = 2e-4
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.04}
LANE_BLOCK = 2


def make_program(policy: Any):
    """The program's two jitted ways through a chunk, built once a module:
    ``advance`` (``Policy.sequence`` over a chunk: its outputs, the exit
    gates' logits, the counters the chunk started from and the carry it
    left) and ``one_step`` (``Policy.step``). Both donate the carry: a
    block's rings are held once."""
    import functools

    import jax

    @functools.partial(jax.jit, donate_argnums=(3,))
    def advance(p, o, d, carry, ended):
        carry = policy.reset_carry(carry, 1.0 - ended)
        start = {"pos": carry["pos"], "cursor": carry["cursor"]}
        (lg, v, carry), mut = policy.apply(p, o, carry, d, method="sequence", mutable=["losses"])
        return lg, v, mut["losses"]["core"]["exit_logits"][0], start, carry

    @functools.partial(jax.jit, donate_argnums=(2,))
    def one_step(p, o, carry, ended):
        carry = policy.reset_carry(carry, 1.0 - ended)
        return policy.apply(p, o, carry, method="step")

    return policy, advance, one_step


def program_outputs(program: Any, params: Any, obs, dones, steps: int, precision: str):
    """``make_program``'s functions over the histories of one block of
    lanes: (sequence outputs, step outputs) of the last chunk: (logits [R, B,
    T, n], values [R, B, T], gate logits [B, T, R]) and (logits [B, T, n],
    values [B, T])."""
    import jax
    import jax.numpy as jnp

    policy, advance, one_step = program
    lanes, hist = dones.shape

    def chunk(c0):
        return {k: v[:, c0:c0 + steps] for k, v in obs.items()}, dones[:, c0:c0 + steps]

    with jax.default_matmul_precision(precision):
        carry = policy.initial_state(lanes)
        ended = np.zeros((lanes,), np.float32)
        for c0 in range(0, hist, steps):
            o, d = chunk(c0)
            lg, v, gates, start, carry = advance(params, o, d, carry, ended)
            ended = d[:, -1]
        seq = (lg, v, gates)
        # the same chunk, one step at a time, from the same start as the
        # fused program hands it to the learner: the start's counters (already
        # reset) beside the rings as the chunk left them
        o, d = chunk(hist - steps)
        carry, ended = policy.chunk_start_carry(start, carry), np.zeros((lanes,), np.float32)
        lgs, vs = [], []
        for t in range(steps):
            lg_t, v_t, carry = one_step(params, {k: x[:, t] for k, x in o.items()}, carry, ended)
            lgs.append(lg_t), vs.append(v_t)
            ended = d[:, t]
        stack = lambda xs: jnp.stack(xs, axis=1)
        step = (jax.tree.map(lambda *xs: stack(xs), *lgs), stack(vs))
    return seq, step


def reference_outputs(params: Any, obs, dones, model: Mapping[str, Any], steps: int):
    """The reference over whole histories, a lane at a time: (logits [R, B,
    T, n], values [R, B, T], gate logits [B, T, R]) of the last ``steps``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lane_of(p, o, d):
        logits, values, gates = looplm_ref.history(p, o, d, model)
        return {k: v[:, :, -steps:] for k, v in logits.items()}, values[:, :, -steps:], gates[:, -steps:]

    outs = [
        lane_of(params, {k: v[b:b + 1] for k, v in obs.items()}, dones[b:b + 1])
        for b in range(dones.shape[0])
    ]
    logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *[o[0] for o in outs])
    values = jnp.concatenate([o[1] for o in outs], axis=1)
    return logits, values, jnp.concatenate([o[2] for o in outs], axis=0)


def policy_agreement(
    policy: Any, params: Any, rc: Mapping[str, Mapping[str, Any]], seed: int,
    lanes: int, steps: int, history_steps: int,
) -> Dict[str, Any]:
    """Compare ``policy`` (the program's module, as configured) with the
    reference. Returns the four worst relative differences, the limits, the
    outputs' size and ``ok``."""
    import jax
    import jax.numpy as jnp

    stated = rc["model"]["dtype"]
    model = dict(rc["model"])
    obs, dones = sample(rc, seed, lanes, steps, history_steps)
    exact = policy.clone(model=dataclasses.replace(policy.model, dtype="float32"))
    report: Dict[str, Any] = {
        "lanes": lanes, "steps": steps, "history_steps": history_steps,
        "episode_ends": int(dones.sum()), "stated_dtype": stated,
        "loop_steps": model["loop_steps"],
        "tol_exact": TOL_EXACT, "tol_stated": TOL_STATED[stated],
    }
    want_logits, want_values, want_gates = reference_outputs(params, obs, dones, model, steps)
    want_seq = {"l": want_logits, "v": want_values, "g": want_gates}
    want_step = {"l": {k: v[-1] for k, v in want_logits.items()}, "v": want_values[-1]}
    scale = max(1.0, policy_ref.max_abs_diff(want_seq, jax.tree.map(jnp.zeros_like, want_seq)))
    report["output_scale"] = scale
    limits = {"exact": TOL_EXACT, "stated": TOL_STATED[stated]}
    ok = True
    for name, module, precision in (("exact", exact, "highest"), ("stated", policy, "default")):
        program = make_program(module)
        diffs: Dict[str, list] = {"sequence": [], "step": []}
        for b0 in range(0, lanes, LANE_BLOCK):
            cut = slice(b0, b0 + LANE_BLOCK)
            (lg, v, g), (lg_s, v_s) = program_outputs(
                program, params, {k: x[cut] for k, x in obs.items()}, dones[cut], steps, precision
            )
            block = {"l": {k: x[:, cut] for k, x in want_logits.items()}, "v": want_values[:, cut], "g": want_gates[cut]}
            diffs["sequence"].append(policy_ref.max_abs_diff({"l": lg, "v": v, "g": g}, block) / scale)
            diffs["step"].append(
                policy_ref.max_abs_diff({"l": lg_s, "v": v_s}, jax.tree.map(lambda x: x[cut], want_step)) / scale
            )
        for mode, blocks in diffs.items():
            report[f"{name}_{mode}"] = float(np.max(blocks))              # a NaN stays one
            # each compared on its own: a NaN compares false
            ok = ok and all(diff <= limits[name] for diff in blocks)
    report["ok"] = bool(ok)
    return report
