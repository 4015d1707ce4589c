"""The comparison that decides ``correct`` for the policy itself.

On the run's own device, at the cell's own width and with the run's seeded
parameters, the program's ``Policy.step`` and ``Policy.sequence`` (with
mid-chunk resets) are compared with ``benchmark/reference/policy_ref.py``
on logits and values (never on sampled actions: with random weights the
largest logit changes on rounding), for a seeded sample of lanes and steps.

Differences are taken relative to the outputs' size: the largest absolute
difference over max(1, largest absolute reference output). After some tens
of optimizer steps at H=4096 the value head reaches magnitudes of several
units, and a bfloat16 result carries its rounding in proportion (three of
twelve runs exceeded an absolute 0.03 that way: my chip run, PR 22).

Two comparisons, two tolerances, each with its reason:

* ``TOL_EXACT``: the program's policy with every product in float32 at
  "highest" precision against the reference. Same arithmetic, so only the
  order of float32 accumulation differs: with sums over up to 8,192 terms of
  magnitude below one that is some 1e-5. 2e-4 leaves room for that and fails
  a product computed in bfloat16 passes (about 5e-3 here), so it pins the
  mathematics: a dropped term or a wrong reset cannot hide in it.
* ``TOL_STATED["bfloat16"]``: the policy as the configuration states it
  (bfloat16 products, float32 parameters and logits) against the same
  reference. bfloat16 keeps 8 bits: logits of magnitude about 0.6 came out
  within 5.3e-3 at H=128 and 5.2e-3 at H=4096 over 16 recurrent steps (CPU,
  this PR). 0.03 is about six times that, and an 8-bit float with 3 bits of
  mantissa (16 times the rounding) would exceed it. A configuration that
  states float32 is held to ``TOL_EXACT``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping

import numpy as np

from benchmark.harness import obs as obs_mod
from benchmark.reference import policy_ref

TOL_EXACT = 2e-4
TOL_STATED = {"float32": TOL_EXACT, "bfloat16": 0.03}
RESET_SHARE = 0.1     # share of steps that end an episode in the sample


def policy_agreement(
    policy: Any,
    params: Any,
    rc: Mapping[str, Mapping[str, Any]],
    seed: int,
    lanes: int,
    steps: int,
) -> Dict[str, Any]:
    """Compare ``policy`` (the program's module, as configured) with the
    reference. Returns the four worst relative differences, the tolerances,
    the outputs' size and ``ok``."""
    import jax
    import jax.numpy as jnp

    stated = rc["model"]["dtype"]
    exact = policy.clone(
        model=dataclasses.replace(policy.model, dtype="float32")
    )
    rng = np.random.default_rng(seed)
    H = rc["model"]["hidden_dim"]
    obs = obs_mod.batch_of(rc, rng, lanes, steps)
    dones = (rng.random((lanes, steps)) < RESET_SHARE).astype(np.float32)
    carry = tuple(
        (rng.normal(size=(lanes, H)) * 0.5).astype(np.float32)
        for _ in range(2)
    )
    first = {k: v[:, 0] for k, v in obs.items()}

    ref_seq = jax.jit(policy_ref.sequence)(params, obs, carry, dones)
    ref_step = jax.jit(policy_ref.step)(params, first, carry)

    def run(module, precision, dtype):
        c = tuple(jnp.asarray(x, dtype) for x in carry)
        with jax.default_matmul_precision(precision):
            seq = jax.jit(
                lambda p, o, c, d: module.apply(p, o, c, d, method="sequence")
            )(params, obs, c, dones)
            stp = jax.jit(
                lambda p, o, c: module.apply(p, o, c, method="step")
            )(params, first, c)
        return seq, stp

    def scale_of(want) -> float:
        zeros = jax.tree.map(jnp.zeros_like, (want[0], want[1]))
        return max(1.0, policy_ref.max_abs_diff((want[0], want[1]), zeros))

    def worst(got, want) -> float:
        # logits and values; the carry is not compared (issue 22)
        return policy_ref.max_abs_diff(
            {"logits": got[0], "value": got[1]},
            {"logits": want[0], "value": want[1]},
        ) / scale_of(want)

    e_seq, e_step = run(exact, "highest", jnp.float32)
    s_seq, s_step = run(
        policy, "default", {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[stated]
    )
    report = {
        "lanes": lanes, "steps": steps, "stated_dtype": stated,
        "exact_sequence": worst(e_seq, ref_seq),
        "exact_step": worst(e_step, ref_step),
        "stated_sequence": worst(s_seq, ref_seq),
        "stated_step": worst(s_step, ref_step),
        "tol_exact": TOL_EXACT, "tol_stated": TOL_STATED[stated],
        "output_scale": scale_of(ref_seq),
    }
    # each compared on its own: a NaN compares false, so a run that is not
    # finite is not ok (max() would pass over a NaN in second place)
    report["ok"] = all(
        report[key] <= tol
        for key, tol in (
            ("exact_sequence", TOL_EXACT), ("exact_step", TOL_EXACT),
            ("stated_sequence", TOL_STATED[stated]), ("stated_step", TOL_STATED[stated]),
        )
    )
    return report
