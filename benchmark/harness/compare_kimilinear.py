"""The comparison that decides ``correct`` for a policy with the Kimi-Linear
core.

On the run's own device, at the cell's widths and with the run's trained
parameters, a seeded sample of lanes (``compare_afmoe.sample``: episodes end
at a thousandth of the steps, once inside the compared chunk, and one lane
runs its last 2,100 steps unbroken) is fed ``history_steps`` observations in
chunks of ``steps`` through the program's ``Policy.sequence`` (a reset
through the core between chunks, ``dones`` inside them: the delta rule in
its closed form over each chunk, the states handed from chunk to chunk), and
the LAST chunk once more step by step through ``Policy.step`` (the
recurrence a position at a time) from the learner's view of the chunk's
start (``Policy.chunk_start_carry``: the start's counters, states and
convolution rows beside the END's latent ring), as the fused program hands
it over. Logits and values of that chunk are compared with
``reference/kimilinear_ref.py`` over each lane's WHOLE history (the
recurrence from the lane's first step, latent attention expanded to keys and
values a head), computed a lane at a time. Differences are relative to the
outputs' size, as in ``harness/compare.py``.

**Which experts**: as ``compare_afmoe`` (its docstring says why): the
reference is given the experts the program took and computes everything else
itself, and ``*_routing_margin`` is how far below the reference's own cut
line the program's lowest pick lies, over 256 scores a token here.

What a wrong core would show: no decay, no beta, a tap shifted, a state kept
across an episode's end, a latent stored unnormalised or a rotation applied
each move the outputs by a tenth and more of their size
(``tests/test_kimilinear.py`` makes the reference wrong in each way,
``benchmark/tests/test_kimilinear_cell.py`` the program).

Four output numbers and two margins, two pairs of limits, each set between
two readings on the v5e at the cell's widths (my chip runs, PR 32; the
readings' origin in full in PERF.md section 6):

* ``TOL_EXACT`` (2e-4, outputs) and ``MARGIN_EXACT`` (1e-4, scores in (0,
  1)): the program with every product in float32 at "highest" precision.
  Same arithmetic as the reference in another order (the closed form over a
  chunk against the recurrence, the absorbed products against the expanded
  ones, a ring, a two-part softmax, weighted experts): what is left is
  float32 accumulation, 5.0e-7 to 4.3e-5 (outputs) and 8.9e-7 to 3.1e-5
  (margin) over seven seeds on the v5e, 3.8e-6 at toy widths on the CPU. The
  program as stated (bfloat16) reads 2.3e-3 to 6.9e-3 and 1.2e-3 to 2.1e-3
  against the same reference and fails both: the pair pins the mathematics.
* ``TOL_STATED["bfloat16"]`` (0.025, outputs) and ``MARGIN_STATED`` (0.01):
  the policy as the configuration states it (bfloat16 products and latent
  ring; float32 parameters, stream, softmax, router and KDA state): 0.0023,
  0.0039, 0.0046, 0.0049, 0.0055, 0.0064, 0.0069 and margins 0.0012 to 0.0021
  over seven seeds. The
  same reference with every product's operands rounded to 8-bit floats
  (unscaled e4m3, the nearest precision below;
  ``benchmark/tools/kimilinear_precision_below.py``) reads 0.335 and 0.149:
  not correct by either limit; with them rounded to bfloat16 it reads 0.0164
  and 0.0087 (harsher than the program: it rounds the state into every
  product and its router rounds too). The output limit is 3.6 times the
  program's worst reading and 13 times under the 8-bit one; the margin's 4.8
  times and 15 times: each below the geometric middle of its pair (0.048,
  0.016), on the side of the program's readings. A float32-stated
  configuration is held to the exact limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping

import numpy as np

from benchmark.harness.compare_afmoe import _routes, sample
from benchmark.reference import kimilinear_ref, policy_ref

TOL_EXACT, MARGIN_EXACT = 2e-4, 1e-4
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.025}
MARGIN_STATED = {"float32": MARGIN_EXACT, "bfloat16": 0.01}


def program_outputs(policy: Any, params: Any, obs, dones, steps: int, precision: str):
    """The program over the histories: (sequence outputs, step outputs) of
    the last chunk, each (logits, values, routes over the WHOLE history)."""
    import jax
    import jax.numpy as jnp

    lanes, hist = dones.shape

    def chunk(c0):
        return {k: v[:, c0:c0 + steps] for k, v in obs.items()}, dones[:, c0:c0 + steps]

    @jax.jit
    def advance(p, o, d, carry, ended):
        carry = policy.reset_carry(carry, 1.0 - ended)
        (lg, v, end), mut = policy.apply(p, o, carry, d, method="sequence", mutable=["routing"])
        return lg, v, carry, end, _routes(mut)

    @jax.jit
    def one_step(p, o, carry, ended):
        carry = policy.reset_carry(carry, 1.0 - ended)
        (lg, v, carry), mut = policy.apply(p, o, carry, method="step", mutable=["routing"])
        return lg, v, carry, _routes(mut)

    with jax.default_matmul_precision(precision):
        carry = policy.initial_state(lanes)
        ended = np.zeros((lanes,), np.float32)
        taken: List[List[Any]] = []
        for c0 in range(0, hist, steps):
            o, d = chunk(c0)
            lg, v, start, carry, routes = advance(params, o, d, carry, ended)
            taken.append(routes)
            ended = d[:, -1]
        before = [jnp.concatenate(r, axis=1) for r in zip(*taken[:-1])]
        seq = (lg, v, [jnp.concatenate([b, r], axis=1) for b, r in zip(before, taken[-1])])
        # the same chunk, one step at a time, from the start as the fused
        # program hands it to the learner (`start` is already reset)
        o, d = chunk(hist - steps)
        carry, ended = policy.chunk_start_carry(start, carry), np.zeros((lanes,), np.float32)
        lgs, vs, rs = [], [], []
        for t in range(steps):
            lg_t, v_t, carry, r_t = one_step(params, {k: x[:, t] for k, x in o.items()}, carry, ended)
            lgs.append(lg_t), vs.append(v_t), rs.append(r_t)
            ended = d[:, t]
        stack = lambda xs: jnp.stack(xs, axis=1)
        step = (
            jax.tree.map(lambda *xs: stack(xs), *lgs), stack(vs),
            [jnp.concatenate([b, *layer], axis=1) for b, layer in zip(before, zip(*rs))],
        )
    return seq, step


def reference_outputs(params: Any, obs, dones, model: Mapping[str, Any], routes, steps: int):
    """The reference over whole histories, a lane at a time, given the
    experts the program took: (logits, values) of the last ``steps`` steps
    and the worst routing margin anywhere in the histories."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lane_of(p, o, d, r):
        logits, values, routing = kimilinear_ref.history(p, o, d, model, routes=r)
        margin = jnp.stack([x["margin"].max() for x in routing]).max() if routing else jnp.zeros(())
        return {k: v[:, -steps:] for k, v in logits.items()}, values[:, -steps:], margin

    outs = [
        lane_of(params, {k: v[b:b + 1] for k, v in obs.items()}, dones[b:b + 1], [r[b:b + 1] for r in routes])
        for b in range(dones.shape[0])
    ]
    logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *[o[0] for o in outs])
    values = jnp.concatenate([o[1] for o in outs], axis=0)
    return logits, values, float(jnp.stack([o[2] for o in outs]).max())


def policy_agreement(
    policy: Any, params: Any, rc: Mapping[str, Mapping[str, Any]], seed: int,
    lanes: int, steps: int, history_steps: int,
) -> Dict[str, Any]:
    """Compare ``policy`` (the program's module, as configured) with the
    reference. Returns the four worst relative differences, the two routing
    margins, the limits, the outputs' size and ``ok``."""
    stated = rc["model"]["dtype"]
    model = dict(rc["model"])
    obs, dones = sample(rc, seed, lanes, steps, history_steps)
    exact = policy.clone(model=dataclasses.replace(policy.model, dtype="float32"))
    report: Dict[str, Any] = {
        "lanes": lanes, "steps": steps, "history_steps": history_steps,
        "episode_ends": int(dones.sum()), "stated_dtype": stated,
        "tol_exact": TOL_EXACT, "tol_stated": TOL_STATED[stated],
        "margin_exact": MARGIN_EXACT, "margin_stated": MARGIN_STATED[stated],
    }
    limits = {"exact": (TOL_EXACT, MARGIN_EXACT), "stated": (TOL_STATED[stated], MARGIN_STATED[stated])}
    ok = True
    for name, module, precision in (("exact", exact, "highest"), ("stated", policy, "default")):
        margins = []
        for mode, (logits, values, routes) in zip(
            ("sequence", "step"), program_outputs(module, params, obs, dones, steps, precision)
        ):
            want_logits, want_values, margin = reference_outputs(params, obs, dones, model, routes, steps)
            want = {"l": want_logits, "v": want_values}
            scale = max(1.0, policy_ref.max_abs_diff(want, {"l": {k: 0 * v for k, v in want_logits.items()}, "v": 0 * want_values}))
            diff = policy_ref.max_abs_diff({"l": logits, "v": values}, want) / scale
            report[f"{name}_{mode}"] = diff
            report["output_scale"] = scale
            margins.append(margin)
            # each compared on its own: a NaN compares false
            ok = ok and diff <= limits[name][0]
        report[f"{name}_routing_margin"] = max(margins)
        ok = ok and max(margins) <= limits[name][1]
    report["ok"] = bool(ok)
    return report
