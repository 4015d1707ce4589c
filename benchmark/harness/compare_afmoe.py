"""The comparison that decides ``correct`` for a policy with the afmoe core.

On the run's own device, at the cell's widths and with the run's trained
parameters, a seeded sample of lanes is fed ``history_steps`` observations in
chunks of ``steps`` through the program's ``Policy.sequence`` (a reset
through the core between chunks, ``dones`` inside them: episodes end at a
``RESET_SHARE`` of the steps, once inside the compared chunk, and one lane
runs its last 2,100 steps unbroken, past the window and around its ring), and
the LAST chunk once more step by step through ``Policy.step`` from the same
carry. Logits and values of that chunk are compared with
``reference/afmoe_ref.py`` over each lane's WHOLE history, computed a lane at
a time so that it fits beside the train state. Differences are relative to
the outputs' size, as in ``harness/compare.py``.

**Which experts.** A router's choice is a step function of its scores: two
experts whose scores differ by one rounding swap, and the token's output
moves by a whole expert term. With 128 scores a token and 41,000 tokens a
lane history that happens in every run, in float32 too. So the choice is
compared on its own, and the outputs with the choice held equal:

* the reference is given the experts the program took (the program sows
  them: ``mutable=["routing"]``) and computes everything else itself, the
  scores and the weights of those experts included. A dropped expert term, a
  wrong weight, a wrong reset or a lower precision all still show;
* ``*_routing_margin`` is how far below the reference's own cut line (its
  k-th largest score + bias) the program's lowest pick lies, worst token and
  layer: 0 where the program took the reference's own top k. A router that
  scores differently shows here.

Four output numbers and two margins, two pairs of limits, each set between
two readings on the v5e at the cell's widths (my chip runs, PR 26; the
readings' origin in full in PERF.md section 6):

* ``TOL_EXACT`` (2e-4, outputs) and ``MARGIN_EXACT`` (1e-4, scores in
  (0, 1)): the program with every product in float32 at "highest" precision.
  Same arithmetic as the reference in another order (rings, weighted experts,
  two-part softmax), so what is left is float32 accumulation over up to
  6,144 terms through five layers: 3e-6 on the CPU at toy widths, and on the
  v5e 4.0e-7 (outputs) and 1.2e-7 (margin) at most over 17 seeds. The
  program as stated (bfloat16) reads 1.6e-3 to 7.2e-3 and 7e-5 to 3.7e-4
  against the same reference and fails both: the pair pins the mathematics.
* ``TOL_STATED["bfloat16"]`` (0.025, outputs) and ``MARGIN_STATED`` (0.003):
  the policy as the configuration states it, bfloat16 products and caches,
  float32 parameters, residual stream, softmax and router. Five layers of
  attention over up to 3,072 cached bfloat16 keys and of expert products
  read 7.2e-3 at most over 17 seeds (the LSTM reads 5e-3 over 16 steps), and
  the float32 router 3.7e-4. The same reference with every product's
  operands rounded to 8-bit floats (unscaled e4m3, the nearest precision
  below; ``benchmark/tools/afmoe_precision_below.py``) reads 0.081 and
  0.032, and with them rounded to bfloat16 4.4e-3 and 2.9e-3 (its router
  rounds too). Each limit is near the geometric middle of its pair: 3.5
  times the program's worst reading and a third of the 8-bit one for the
  outputs, 8 and 10 times for the margin, so an 8-bit product or a dropped expert
  term (0.5 and more) fails both. A float32-stated configuration is held to
  the exact limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping

import numpy as np

from benchmark.harness import obs as obs_mod
from benchmark.reference import afmoe_ref, policy_ref

TOL_EXACT, MARGIN_EXACT = 2e-4, 1e-4
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.025}
MARGIN_STATED = {"float32": MARGIN_EXACT, "bfloat16": 0.003}
RESET_SHARE = 0.001   # share of steps that end an episode in the sample
UNBROKEN = 2100       # one lane's last steps with no episode end: past the window


def sample(rc: Mapping[str, Mapping[str, Any]], seed: int, lanes: int, steps: int, history_steps: int):
    """Seeded observations ``[lanes, history_steps, ...]`` and episode ends."""
    rng = np.random.default_rng(seed)
    obs = obs_mod.batch_of(rc, rng, lanes, history_steps)
    dones = (rng.random((lanes, history_steps)) < RESET_SHARE).astype(np.float32)
    dones[0, history_steps - steps // 2 - 1] = 1.0        # an end inside the compared chunk
    if lanes > 1:
        dones[1, -min(UNBROKEN, history_steps):] = 0.0
    dones[:, -1] = 0.0
    return obs, dones


def _routes(mutated: Mapping[str, Any]) -> List[Any]:
    """The experts each routed layer took, ``[lanes, T, k]``, in layer order."""
    layers = mutated["routing"]["core"]
    return [
        layers[name]["moe"]["chosen"][0]
        for name in sorted(layers, key=lambda n: int(n.rsplit("_", 1)[1]))
    ]


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
        (lg, v, carry2), mut = policy.apply(p, o, carry, d, method="sequence", mutable=["routing"])
        return lg, v, carry, carry2, _routes(mut)

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
        # the same chunk, one step at a time, from the same start
        o, d = chunk(hist - steps)
        carry, ended = start, np.zeros((lanes,), np.float32)      # `start` is already reset
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


def reference_outputs(params: Any, obs, dones, model: Mapping[str, Any], routes, steps: int, block: int = 1):
    """The reference over whole histories, ``block`` lanes at a time, given
    the experts the program took: (logits, values) of the last ``steps``
    steps and the worst routing margin anywhere in the histories."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lanes_of(p, o, d, r):
        logits, values, routing = afmoe_ref.history(p, o, d, model, routes=r)
        margin = jnp.stack([x["margin"].max() for x in routing]).max() if routing else jnp.zeros(())
        return {k: v[:, -steps:] for k, v in logits.items()}, values[:, -steps:], margin

    outs = []
    for b0 in range(0, dones.shape[0], block):
        cut = slice(b0, b0 + block)
        outs.append(lanes_of(
            params, {k: v[cut] for k, v in obs.items()}, dones[cut], [r[cut] for r in routes]
        ))
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
            zeros = {k: 0 * v for k, v in want_logits.items()}
            scale = max(1.0, policy_ref.max_abs_diff({"l": want_logits, "v": want_values}, {"l": zeros, "v": 0 * want_values}))
            diff = policy_ref.max_abs_diff(
                {"l": logits, "v": values}, {"l": want_logits, "v": want_values}
            ) / scale
            report[f"{name}_{mode}"] = diff
            report["output_scale"] = scale
            margins.append(margin)
            # each compared on its own: a NaN compares false
            ok = ok and diff <= limits[name][0]
        report[f"{name}_routing_margin"] = max(margins)
        ok = ok and max(margins) <= limits[name][1]
    report["ok"] = bool(ok)
    return report
