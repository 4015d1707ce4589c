"""The comparison that decides ``correct`` for a policy with the LFM2 core.

On the run's own device, at the cell's widths and with the run's trained
parameters, a seeded sample of lanes (``compare_afmoe.sample``: episodes end
at a thousandth of the steps, once inside the compared chunk, and one lane
runs its last 2,100 steps unbroken) is fed ``history_steps`` observations in
chunks of ``steps`` through the program's ``Policy.sequence`` (a reset
through the core between chunks, ``dones`` inside them: the convolution over
each chunk and the two carried rows, the histories handed from chunk to
chunk, attention over the ring), and the LAST chunk once more step by step
through ``Policy.step`` (T = 1) from the learner's view of the chunk's start
(``Policy.chunk_start_carry``: the start's counters and histories beside
the END's ring), as the fused program hands it over
(``compare_kimilinear.program_outputs``: nothing in it knows a core). Logits
and values of that chunk are compared with ``reference/lfm2moe_ref.py`` over
each lane's WHOLE history (the convolution by its taps and attention over
the episode's whole history), computed a lane at a time. Differences are
relative to the outputs' size, as in ``harness/compare.py``.

**Which experts**: as ``compare_afmoe`` (its docstring says why): the
reference is given the experts the program took and computes everything else
itself, and ``*_routing_margin`` is how far below the reference's own cut
line the program's lowest pick lies, over 64 scores a token here.

What a wrong core would show: the taps in the other order or shifted, a
history kept across an episode's end, a gate left out, no rotation, no head
norm, KV heads shared wrongly each move the outputs by a tenth and more of
their size (``tests/test_lfm2moe.py`` makes the reference wrong in each way).

Four output numbers and two margins, two pairs of limits, each set between
two readings on the v5e at the cell's widths (my chip runs, PR 37; the
readings' origin in full in PERF.md section 6):

* ``TOL_EXACT`` (2e-4, outputs) and ``MARGIN_EXACT`` (1e-4, scores in (0,
  1)): the program with every product in float32 at "highest" precision.
  Same arithmetic as the reference in another order (a ring and a two-part
  softmax, carried rows, weighted experts, 1e-20 for 1e-6 in the
  renormalisation): what is left is float32 accumulation, 2.9e-7 to 5.2e-7
  (outputs) and at most 1.2e-7 (margin) over four seeds on the v5e, 3.7e-6
  at toy widths on the CPU: the two limits the afmoe and Kimi-Linear cells
  hold. The reference with its parameters rounded to bfloat16 reads 0.0102
  / 0.0061 and with a bfloat16 router 6.1e-5 / 0.0013
  (``benchmark/tools/lfm2moe_precision_below.py``): each fails one of the
  pair, and so does the program as stated (below): the pair pins the
  mathematics and the two float32 statements of the configuration.
* ``TOL_STATED["bfloat16"]`` (0.025, outputs) and ``MARGIN_STATED`` (0.012):
  the policy as the configuration states it (bfloat16 products, rings and
  histories; float32 parameters, stream, norms, softmax and router): 0.0041,
  0.0048, 0.0052, 0.0059 and margins 0.0021, 0.0026, 0.0031, 0.0034 over
  four seeds. The same reference with every product's operands rounded to
  8-bit floats (unscaled e4m3, the nearest precision below) reads 0.564 and
  0.346: not correct by either limit; with them rounded to bfloat16 it
  reads 0.0134 and 0.0074 (harsher than the program: its router rounds
  too). The output limit is 4.2 times the program's worst reading and 23
  times under the 8-bit one; the margin's 3.5 times and 29 times: each
  below the geometric middle of its pair (0.058, 0.034), on the side of the
  program's readings, with the room fresh seeds need. The margin is ten
  times the afmoe cell's because this block has no post-norm: what a
  bfloat16 product leaves in the stream reaches the next router's input
  unnormalised (Kimi-Linear's, for the same reason, reads 0.0010-0.0021). A
  float32-stated configuration is held to the exact limits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping

from benchmark.harness.compare_afmoe import sample
from benchmark.harness.compare_kimilinear import program_outputs
from benchmark.reference import lfm2moe_ref, policy_ref

TOL_EXACT, MARGIN_EXACT = 2e-4, 1e-4
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.025}
MARGIN_STATED = {"float32": MARGIN_EXACT, "bfloat16": 0.012}


def reference_outputs(params: Any, obs, dones, model: Mapping[str, Any], routes, steps: int):
    """The reference over whole histories, a lane at a time, given the
    experts the program took: (logits, values) of the last ``steps`` steps
    and the worst routing margin anywhere in the histories."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def lane_of(p, o, d, r):
        logits, values, routing = lfm2moe_ref.history(p, o, d, model, routes=r)
        margin = jnp.stack([x["margin"].max() for x in routing]).max() if routing else jnp.zeros(())
        return {k: v[:, -steps:] for k, v in logits.items()}, values[:, -steps:], margin

    outs = [
        lane_of(params, {k: v[b:b + 1] for k, v in obs.items()}, dones[b:b + 1], [r[b:b + 1] for r in routes])
        for b in range(dones.shape[0])
    ]
    logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *[o[0] for o in outs])
    values = jnp.concatenate([o[1] for o in outs], axis=0)
    return logits, values, float(jnp.stack([o[2] for o in outs]).max())


def relative_difference(got_logits, got_values, want_logits, want_values):
    """(worst difference of logits and values over the outputs' size, that size)."""
    import jax
    import jax.numpy as jnp

    want = {"l": want_logits, "v": want_values}
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    return policy_ref.max_abs_diff({"l": got_logits, "v": got_values}, want) / scale, scale


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
            report[f"{name}_{mode}"], report["output_scale"] = relative_difference(logits, values, want_logits, want_values)
            margins.append(margin)
            # each compared on its own: a NaN compares false
            ok = ok and report[f"{name}_{mode}"] <= limits[name][0]
        report[f"{name}_routing_margin"] = max(margins)
        ok = ok and max(margins) <= limits[name][1]
    report["ok"] = bool(ok)
    return report
