"""The readings that set ``compare_sdar``'s limits from below: the plain
reference computed in a lower precision than the configuration states,
compared with itself in float32 the way ``policy_agreement`` compares the
program (the lowered pass's own experts handed to the float32 pass, every
pass's logits of the last chunk and the values relative to their size, the
routing margin beside them).

    python3 benchmark/tools/sdar_precision_below.py [--cell C] [--seed N] [--lanes 1]      (on the chip)

Prints one line a lowering:

* ``products bfloat16`` (what the configuration states for its products: it
  has to read about what the program reads; harsher, its router rounds too)
  and ``products float8_e4m3fn`` (the nearest precision below, unscaled: it
  has to read above ``TOL_STATED`` or above ``MARGIN_STATED``, that is, come
  out as not correct by the stated limits). The method is
  ``afmoe_precision_below.py``'s ``RoundedProducts``, standing in for the
  reference modules' ``jnp``.
* ``parameters bfloat16`` (every parameter rounded to bfloat16, all
  arithmetic float32: the configuration states float32 parameters) and
  ``router bfloat16`` (the router's product alone with bfloat16 operands):
  each has to read above ``TOL_EXACT`` or ``MARGIN_EXACT``.

Weights are the program's seeded initial ones at the cell's widths; the
history's actions are drawn from the seed (a type, its arguments, an order
of commitment as the program draws it): the reference is teacher-forced and
needs no program to draw them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def drawn_actions(rc, rng, lanes: int, hist: int):
    """Seeded actions and orders ``[lanes, hist]``: a type, each head's value
    in range, the type's arguments committed over passes 2 and 3 in a random
    order (``distributions.commit_stages``' schedule at S = 3), 0 elsewhere."""
    import numpy as np

    from benchmark.reference import sdar_ref

    sizes = sdar_ref.head_sizes(rc["actions"])
    actions = {h: rng.integers(0, sizes[h], (lanes, hist)).astype(np.int32) for h in sdar_ref.HEADS}
    rel = np.asarray(sdar_ref.relevant(actions["action_type"]))
    order = rng.random((lanes, hist, 4)).argsort(-1).argsort(-1)            # a rank a slot
    args = rel[..., 1:]
    rank = np.where(args, order, 99).argsort(-1).argsort(-1)                 # rank among the relevant
    stage = np.where(args, 2 + rank, 0)
    return actions, np.concatenate([np.ones_like(stage[..., :1]), stage], -1).astype(np.int8)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--cell", default="sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lanes", type=int, default=1)
    p.add_argument("--rehearse-cpu", action="store_true", help="tiny sizes, on the CPU: control flow only")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import cells, compare_sdar as compare, program
    from benchmark.reference import afmoe_ref, policy_ref, sdar_ref
    from benchmark.tools.afmoe_precision_below import RoundedProducts
    from dotaclient_tpu.models import init_params, make_policy

    cell = cells.load_cell(args.cell)
    tp = cell.traffic["params"]
    rc = program.merged_run_config(cell, args.rehearse_cpu)
    cfg = program.build_run_config(cell, args.seed, args.rehearse_cpu, top_level={})
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = jax.jit(lambda k: init_params(policy, k))(jax.random.PRNGKey(args.seed))
    steps = tp["sample_steps"]
    history = 4 * steps if args.rehearse_cpu else tp["sample_history_steps"]
    obs, dones = compare.sample(rc, args.seed, args.lanes, steps, history)
    actions, act_stage = drawn_actions(rc, np.random.default_rng(args.seed), args.lanes, history)
    model, actions_cfg = dict(rc["model"]), dict(rc["actions"])
    first = history - steps
    modules = (sdar_ref, afmoe_ref, policy_ref)
    plain_route = sdar_ref.route

    def lowered(with_params):
        # traced anew at every call (the lowerings patch the modules it reads), once for all lanes
        lane = jax.jit(lambda p_, o, d, a, s: sdar_ref.forward(
            p_, o, d, a, s, model, actions_cfg, noisy_first=first, noisy_steps=steps,
        ))
        return [
            lane(with_params, {k: v[i:i + 1] for k, v in obs.items()}, dones[i:i + 1],
                 {h: a[i:i + 1] for h, a in actions.items()}, act_stage[i:i + 1])
            for i in range(args.lanes)
        ]

    def products(dtype):
        shim = RoundedProducts(dtype)
        try:
            for m in modules:
                m.jnp = shim
            return lowered(params)
        finally:
            for m in modules:
                m.jnp = jnp

    def parameters_bfloat16():
        return lowered(jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype), params))

    def router_bfloat16():
        lower = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        sdar_ref.route = lambda p_, m, mdl, chosen=None, fault=None: plain_route(
            {**p_, "router": lower(p_["router"])}, lower(m), mdl, chosen, fault
        )
        try:
            return lowered(params)
        finally:
            sdar_ref.route = plain_route

    stated = (compare.TOL_STATED["bfloat16"], compare.MARGIN_STATED["bfloat16"])
    exact = (compare.TOL_EXACT, compare.MARGIN_EXACT)
    for name, run, (tol, line), held_to in (
        ("products bfloat16", lambda: products(jnp.bfloat16), stated, "stated"),
        ("products float8_e4m3fn", lambda: products(jnp.float8_e4m3fn), stated, "stated"),
        ("parameters bfloat16", parameters_bfloat16, exact, "exact"),
        ("router bfloat16", router_bfloat16, exact, "exact"),
    ):
        low = run()
        logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *[x[0] for x in low])
        values = jnp.concatenate([x[1] for x in low], axis=0)[:, first:]
        routes = [jnp.concatenate([x[2][layer]["chosen"] for x in low], axis=0) for layer in range(len(low[0][2]))]
        want_logits, want_values, margin = compare.reference_outputs(
            params, obs, dones, actions, act_stage, model, actions_cfg, first, steps, routes,
        )
        diff, _ = compare.relative_difference(logits, values, want_logits, want_values[:, first:])
        print(json.dumps({
            "lowered": name, "outputs": diff, "routing_margin": margin,
            "held_to": held_to, "tol": tol, "margin": line,
            "correct_by_those_limits": bool(diff <= tol and margin <= line),
            "lanes": args.lanes, "history_steps": history, "seed": args.seed,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
