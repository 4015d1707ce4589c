"""The comparison that decides ``correct`` for a policy with the SDAR core.

On the run's own device, at the cell's widths and with the run's trained
parameters, a seeded sample of lanes (``compare_afmoe.sample``: episodes end
at a thousandth of the steps, once inside the compared chunk, and one lane
runs its last 2,100 steps unbroken; one lane a game) is decoded for
``history_steps`` steps by the program's own rollout step (``sdar.decode``:
three denoising passes with draws between them and the commit to the ring, a
reset through the core at each episode's end), in the precision the
configuration states. The draws and orders it made are then handed back to
the same decode in float32 at "highest" precision (teacher forcing:
``decode``'s ``forced``), and the last chunk is read by the program's
learner pass (``sdar.sequence``, SDAR's layout, from the chunk start the
fused program hands over: the start's counters beside the END's rings) in
both precisions. Every pass's logits and the values of that chunk, from the
rollout and from the learner's noisy copies, are compared with
``reference/sdar_ref.py`` over each lane's WHOLE history as one explicit
sequence of rows (teacher-forced on the same draws and orders), computed a
lane at a time, once a precision: the learner's rows, with the chunk read
again beside them under the rollout's experts (``sdar_ref.forward``'s
``again_routes``). Logits are compared, never sampled tokens. Differences
are relative to the outputs' size, as in ``harness/compare.py``.

**Which lanes and steps**: the stated program decodes and is compared on
every lane of the sample over its whole history. The float32 program is
forced on the stated one's draws over the first ``EXACT_LANES`` lanes (among
them the lane whose episode ends inside the chunk and the one with 2,100
unbroken steps) and the history's last ``EXACT_STEPS`` steps, read as a
history of their own from a fresh start (the reference is given the same
steps alone). A decode step costs the same at every position here (the rings
are read whole): on a TPU v5e the float32 decode of 4 lanes over 2,560 steps
took 75 s of a 389 s run (the stated decode 130 s), over the benchmark's
360 s a run. The short one keeps every mechanism (passes, ring writes, the
mask, an episode end inside the chunk) under the float32 limits. The report's ``seconds`` holds each phase's wall time.

**Which experts**: as ``compare_afmoe`` (its docstring says why): the
reference is given the experts the program took, row by row (a clean
observation's row from pass 1, a clean slot's from the commit, noisy copy s
from pass s; the learner's own rows for its chunk), and computes everything
else itself; ``*_routing_margin`` is how far below the reference's own cut
line the program's lowest pick lies, over 128 softmax scores a token here.

What a wrong core would show: an observation that sees its own block, a
noisy copy that sees the clean block, a causal block, no rotation, no head
norm, a sigmoid router each move the outputs by a hundredth and more of
their size (``tests/test_sdar.py`` makes the reference wrong in each way).

Four output numbers a precision (the rollout's passes and the learner's
copies, each against its reference) and a margin, two pairs of limits, each
set between two readings on a TPU v5e at the cell's widths (the readings'
origin in full in PERF.md section 6):

* ``TOL_EXACT`` (2e-4, outputs) and ``MARGIN_EXACT`` (1e-4, softmax scores):
  the program with every product in float32 at "highest" precision. Same
  arithmetic as the reference in another order (rings and passes against
  one explicit sequence, a two-part softmax, weighted experts): what is left
  is float32 accumulation, 2.8e-7 to 7.9e-7 (outputs) and at most 1.2e-7
  (margin) over seven runs; the afmoe, Kimi-Linear and LFM2 cells hold the
  same pair. The reference with its parameters rounded to bfloat16 reads
  0.0040 / 3.9e-4 and with a bfloat16 router 4.1e-4 / 2.5e-4
  (``benchmark/tools/sdar_precision_below.py``, one lane): each fails both.
* ``TOL_STATED["bfloat16"]`` (0.025, outputs) and ``MARGIN_STATED`` (0.002):
  the policy as the configuration states it (bfloat16 products and rings;
  float32 parameters, stream, norms, softmax and router): 0.0022-0.0038 and
  margins 2.2e-4 to 2.8e-4 over seven runs. The same reference with every
  product's operands rounded to 8-bit floats (unscaled e4m3, the nearest
  precision below) reads 1.118 and 0.030: not correct by either limit; with
  them rounded to bfloat16 0.0063 and 5.4e-4 (harsher than the program: its
  router rounds too). The output limit is 6.6 times the program's worst
  reading and 45 times under the 8-bit one (the afmoe, Kimi-Linear and LFM2
  cells' 0.025), the margin's 7 times and 15 times: each below the geometric
  middle of its pair (0.065, 0.0029), on the side of the program's readings.
  A softmax over 128 experts gives scores near 1/128, so the margin is read
  in smaller units than the sigmoid cells'. A float32-stated configuration
  is held to the exact limits.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Mapping

import numpy as np

from benchmark.harness.compare_afmoe import sample
from benchmark.reference import policy_ref, sdar_ref

TOL_EXACT, MARGIN_EXACT = 2e-4, 1e-4
EXACT_LANES, EXACT_STEPS = 4, 256    # the float32 program's lanes (the sample's first) and steps (the history's last)
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.025}
MARGIN_STATED = {"float32": MARGIN_EXACT, "bfloat16": 0.002}
HEADS = sdar_ref.HEADS


def _pass_routes(mutated: Mapping[str, Any]) -> List[Any]:
    """A decode's sown experts, per layer: (pass 1 ``[B, 6, k]``, passes 2..S
    and the commit ``[B, 5, k]``)."""
    layers = mutated["routing"]["core"]
    return [layers[f"layer_{l}"]["moe"]["chosen"] for l in range(len(layers))]


def program_decode(policy: Any, params: Any, obs, dones, steps: int, precision: str, forced=None, seed: int = 0):
    """The program's rollout over ``history = dones.shape[1] - 1`` steps (the
    last observation is the learner's bootstrap), ``steps`` at a time, one
    lane a game. Returns the history's actions and stages ``[B, history]``,
    the last chunk's pass logits ``[S, B, steps, K]`` and values, the routes
    in the reference's clean-row order (and the noisy rows of the last
    chunk), and the carries at the last chunk's start (reset) and end."""
    import jax
    import jax.numpy as jnp

    from dotaclient_tpu.models import sdar

    lanes, hist = dones.shape[0], dones.shape[1] - 1
    prev = np.concatenate([np.zeros((lanes, 1), np.float32), dones[:, :-1]], axis=1)     # ended before step t

    @jax.jit
    def advance(p, o, d_prev, carry, keys, f):
        def body(carry, x):
            o_t, d_t, k_t, f_t = x
            carry = policy.reset_carry(carry, 1.0 - d_t)
            (out, carry2), mut = policy.apply(p, o_t, carry, k_t, f_t, method=sdar.decode, mutable=["routing"])
            return carry2, (out, _pass_routes(mut))

        t_major = lambda x: jnp.moveaxis(x, 1, 0)
        return jax.lax.scan(body, carry, jax.tree.map(t_major, (o, d_prev, keys, f)))

    with jax.default_matmul_precision(precision):
        carry = policy.initial_state(lanes)
        acts, stages, logits, values, clean, noisy, start = [], [], None, None, [], None, None
        for c0 in range(0, hist, steps):
            cut = slice(c0, c0 + steps)
            o = {k: v[:, cut] for k, v in obs.items()}
            keys = jnp.stack([jax.random.split(jax.random.PRNGKey(seed * 100_003 + t), lanes) for t in range(c0, c0 + steps)], 1)
            f = None if forced is None else ({h: a[:, cut] for h, a in forced[0].items()}, forced[1][:, cut])
            if c0 + steps >= hist:
                # the learner's chunk start: the carry as the step's reset leaves it
                start = policy.reset_carry(carry, 1.0 - jnp.asarray(prev[:, c0]))
            carry, (out, routes) = advance(params, o, prev[:, cut], carry, keys, f)
            acts.append({h: jnp.moveaxis(a, 0, 1) for h, a in out["actions"].items()})
            stages.append(jnp.moveaxis(out["act_stage"], 0, 1))
            # a clean observation's experts from pass 1, a clean slot's from the commit
            clean.append([jnp.moveaxis(jnp.concatenate([r[0][:, :, :1], r[-1]], axis=2), 0, 1) for r in routes])
            if c0 + steps >= hist:
                logits = {h: jnp.moveaxis(lg, 0, 2) for h, lg in out["logits"].items()}      # [S, B, steps, K]
                values = jnp.moveaxis(out["value"], 0, 1)
                noisy = [jnp.moveaxis(jnp.stack([r[0][:, :, 1:]] + list(r[1:-1]), axis=2), 0, 1) for r in routes]
        actions = {h: jnp.concatenate([a[h] for a in acts], axis=1) for h in HEADS}
        act_stage = jnp.concatenate(stages, axis=1)
        clean = [jnp.concatenate([c[l] for c in clean], axis=1) for l in range(len(clean[0]))]   # [B, hist, 6, k]
    return {
        "actions": actions, "act_stage": act_stage, "logits": logits, "values": values,
        "clean_routes": clean, "noisy_routes": noisy, "start": start, "end": carry,
    }


def program_learner(policy: Any, params: Any, obs, dones, decoded, steps: int, precision: str):
    """The learner's pass over the last chunk and its bootstrap, as the fused
    program hands it over: (copy logits ``[S, B, steps, K]``, values ``[B,
    steps + 1]``, routes per layer in its row order ``[B, N, k]``)."""
    import jax

    from dotaclient_tpu.models import sdar

    hist = dones.shape[1] - 1
    chunk = slice(hist - steps, hist)
    carry0 = policy.chunk_start_carry(decoded["start"], decoded["end"])
    o = {k: v[:, hist - steps:] for k, v in obs.items()}
    acts = {h: a[:, chunk] for h, a in decoded["actions"].items()}

    @jax.jit
    def run(p, o, c, d, a, s):
        (lg, v), mut = policy.apply(p, o, c, d, a, s, method=sdar.sequence, mutable=["routing"])
        return lg, v, [r[0] for r in _pass_routes(mut)]

    with jax.default_matmul_precision(precision):
        return run(params, o, carry0, dones[:, chunk], acts, decoded["act_stage"][:, chunk])


def reference_outputs(reference, params, obs, dones, actions, act_stage, routes, again_routes):
    """The reference over whole histories, a lane at a time, given the
    experts the program took (``routes`` in the learner's row order, the
    chunk read again with ``again_routes``, the rollout's): (learner's pass
    logits ``[S, B, steps, K]`` and values ``[B, T]``, the rollout's pass
    logits and values ``[B, steps]``, the worst routing margin)."""
    import jax
    import jax.numpy as jnp

    outs = []
    for b in range(dones.shape[0]):
        one = slice(b, b + 1)
        outs.append(reference(
            params, {k: v[one] for k, v in obs.items()}, dones[one], {h: a[one] for h, a in actions.items()},
            act_stage[one], [r[one] for r in routes], [r[one] for r in again_routes],
        ))
    learner = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *[o[0] for o in outs])
    rollout = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *[o[2] for o in outs])
    return (
        (learner, jnp.concatenate([o[1] for o in outs], axis=0)),
        (rollout, jnp.concatenate([o[3] for o in outs], axis=0)),
        float(jnp.stack([o[4] for o in outs]).max()),
    )


def relative_difference(got_logits, got_values, want_logits, want_values):
    """(worst difference of logits and values over the outputs' size, that size)."""
    import jax
    import jax.numpy as jnp

    want = {"l": want_logits, "v": want_values}
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    return policy_ref.max_abs_diff({"l": got_logits, "v": got_values}, want) / scale, scale


def _rows(clean, noisy):
    """Routes per layer in the reference's row order: ``clean [B, T, 6, k]``
    step by step, then ``noisy [B, steps, S, 5, k]``."""
    import jax.numpy as jnp

    return [
        jnp.concatenate([c.reshape(c.shape[0], -1, c.shape[-1]), n.reshape(n.shape[0], -1, n.shape[-1])], axis=1)
        for c, n in zip(clean, noisy)
    ]


def make_reference(model, actions_cfg, first: int, steps: int):
    """The reference of one lane, jitted once for all lanes: (the learner's
    pass logits and values, the rollout's, the worst margin)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lane(p, o, d, a, s, r, r_again):
        logits, values, routing, (logits2, values2) = sdar_ref.forward(
            p, o, d, a, s, model, actions_cfg, noisy_first=first, noisy_steps=steps, routes=r, again_routes=r_again,
        )
        return logits, values, logits2, values2, jnp.stack([x["margin"].max() for x in routing]).max()

    return lane


def compare_precision(module, params, obs, dones, steps, precision, reference, forced=None, seed=0, seconds=None):
    """One precision's two comparisons: (rollout difference, learner
    difference, worst margin, the decode's draws). ``seconds`` gathers each
    phase's wall time."""
    import jax
    import jax.numpy as jnp

    seconds = {} if seconds is None else seconds
    clock = [time.perf_counter()]

    def lap(name, out):
        jax.block_until_ready(out)
        now = time.perf_counter()
        seconds[name] = now - clock[0]
        clock[0] = now
        return out

    hist = dones.shape[1] - 1
    first = hist - steps
    decoded = lap("decode", program_decode(module, params, obs, dones, steps, precision, forced, seed))
    lg, v, routes = lap("learner", program_learner(module, params, obs, dones, decoded, steps, precision))
    del decoded["start"], decoded["end"]            # the rings go before the reference's passes
    pad = lambda x: jnp.concatenate([x, jnp.zeros_like(x[:, :1])], axis=1)        # the bootstrap's block: never read
    actions = {h: pad(a) for h, a in decoded["actions"].items()}
    act_stage = pad(decoded["act_stage"])
    # the learner's rows: the rollout's clean rows before the chunk, then its own (the chunk, the bootstrap, its
    # copies); the chunk read again with the rollout's experts (its commits' and its passes')
    mixed = [
        jnp.concatenate([c[:, :first].reshape(c.shape[0], -1, c.shape[-1]), r], axis=1)
        for c, r in zip(decoded["clean_routes"], routes)
    ]
    again = _rows([c[:, first:] for c in decoded["clean_routes"]], decoded["noisy_routes"])
    want_l, want_r, margin = lap("reference", reference_outputs(reference, params, obs, dones, actions, act_stage, mixed, again))
    rollout, _ = relative_difference(decoded["logits"], decoded["values"], want_r[0], want_r[1])
    learner, _ = relative_difference(lg, v, want_l[0], want_l[1][:, first:])
    return rollout, learner, margin, (decoded["actions"], decoded["act_stage"])


def policy_agreement(
    policy: Any, params: Any, rc: Mapping[str, Mapping[str, Any]], seed: int,
    lanes: int, steps: int, history_steps: int,
) -> Dict[str, Any]:
    """Compare ``policy`` (the program's module, as configured) with the
    reference. Returns the four worst relative differences, the two routing
    margins, the limits, each phase's seconds and ``ok``."""
    t0 = time.perf_counter()
    stated = rc["model"]["dtype"]
    model, actions_cfg = dict(rc["model"]), dict(rc["actions"])
    obs, dones = sample(rc, seed, lanes, steps, history_steps + 1)
    exact = policy.clone(model=dataclasses.replace(policy.model, dtype="float32"))
    n_exact, exact_steps = min(lanes, EXACT_LANES), min(history_steps, EXACT_STEPS)
    seconds: Dict[str, float] = {"sample": time.perf_counter() - t0}
    report: Dict[str, Any] = {
        "lanes": lanes, "exact_lanes": n_exact, "exact_history_steps": exact_steps, "steps": steps,
        "history_steps": history_steps,
        "episode_ends": int(dones[:, :-1].sum()), "stated_dtype": stated,
        "tol_exact": TOL_EXACT, "tol_stated": TOL_STATED[stated],
        "margin_exact": MARGIN_EXACT, "margin_stated": MARGIN_STATED[stated],
        "seconds": seconds,
    }
    limits = {"exact": (TOL_EXACT, MARGIN_EXACT), "stated": (TOL_STATED[stated], MARGIN_STATED[stated])}
    ok = True
    drawn = None
    # the stated program draws over the whole sample; the float32 one is handed its draws and orders on the
    # first lanes over the last steps (and the bootstrap observation)
    for name, module, precision, n, hist in (
        ("stated", policy, "default", lanes, history_steps), ("exact", exact, "highest", n_exact, exact_steps),
    ):
        last = slice(history_steps - hist, None)
        forced = None if drawn is None else ({h: a[:n, last] for h, a in drawn[0].items()}, drawn[1][:n, last])
        phases: Dict[str, float] = {}
        rollout, learner, margin, out = compare_precision(
            module, params, {k: v[:n, last] for k, v in obs.items()}, dones[:n, last], steps, precision,
            make_reference(model, actions_cfg, hist - steps, steps), forced=forced, seed=seed, seconds=phases,
        )
        seconds.update({f"{name}_{k}": v for k, v in phases.items()})
        drawn = drawn or out
        report[f"{name}_rollout"], report[f"{name}_learner"] = rollout, learner
        report[f"{name}_routing_margin"] = margin
        # each compared on its own: a NaN compares false
        ok = ok and rollout <= limits[name][0] and learner <= limits[name][0] and margin <= limits[name][1]
    stage = np.asarray(drawn[1])
    report["committed_tokens"], report["none_slots"] = int((stage > 0).sum()), int((stage == 0).sum())
    seconds["total"] = time.perf_counter() - t0
    report["ok"] = bool(ok)
    return report
