"""The readings that set ``compare_lfm2moe``'s limits from below: the plain
reference computed in a lower precision than the configuration states,
compared with itself in float32 the way ``policy_agreement`` compares the
program (the lowered pass's own experts handed to the float32 pass, outputs
relative to their size, the routing margin beside them).

    chiprun -- python3 benchmark/tools/lfm2moe_precision_below.py [--cell C] [--seed N] [--lanes 2]

Prints one line a lowering:

* ``products bfloat16`` (what the configuration states for its products: it
  has to read about what the program reads; harsher, its router rounds too)
  and ``products float8_e4m3fn`` (the nearest precision below, unscaled: it
  has to read above ``TOL_STATED`` or above ``MARGIN_STATED``, that is, come
  out as not correct by the stated limits). The method is
  ``afmoe_precision_below.py``'s, whose ``RoundedProducts`` stands in for
  the reference modules' ``jnp``.
* ``parameters bfloat16`` (every parameter rounded to bfloat16, all
  arithmetic float32: the configuration states float32 parameters) and
  ``router bfloat16`` (the router's product alone with bfloat16 operands:
  the configuration states a float32 router): each has to read above
  ``TOL_EXACT`` or ``MARGIN_EXACT``, the limits that hold the program's
  float32 pass, that is, come out as not correct by the exact limits.

Weights are the program's seeded initial ones at the cell's widths.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--cell", default="lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lanes", type=int, default=2)
    p.add_argument("--rehearse-cpu", action="store_true", help="tiny sizes, on the CPU: control flow only")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cells, compare_lfm2moe as compare, program
    from benchmark.reference import afmoe_ref, lfm2moe_ref, policy_ref
    from benchmark.tools.afmoe_precision_below import RoundedProducts
    from dotaclient_tpu.models import init_params, make_policy

    cell = cells.load_cell(args.cell)
    tp = cell.traffic["params"]
    rc = program.merged_run_config(cell, args.rehearse_cpu)
    cfg = program.build_run_config(cell, args.seed, args.rehearse_cpu, top_level={})
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = init_params(policy, jax.random.PRNGKey(args.seed))
    steps = tp["sample_steps"]
    history = 4 * steps if args.rehearse_cpu else tp["sample_history_steps"]
    obs, dones = compare.sample(rc, args.seed, args.lanes, steps, history)
    model = dict(rc["model"])
    modules = (lfm2moe_ref, afmoe_ref, policy_ref)     # the norm, SwiGLU and the router's scores are afmoe_ref's
    plain_route = afmoe_ref.route

    def lowered_history(with_params):
        # traced anew at every call (the lowerings patch the modules it reads), once for all lanes
        lane = jax.jit(lambda p_, o, d: lfm2moe_ref.history(p_, o, d, model))
        return [lane(with_params, {k: v[i:i + 1] for k, v in obs.items()}, dones[i:i + 1]) for i in range(args.lanes)]

    def products(dtype):
        shim = RoundedProducts(dtype)
        try:
            for m in modules:
                m.jnp = shim
            return lowered_history(params)
        finally:
            for m in modules:
                m.jnp = jnp

    def parameters_bfloat16():
        return lowered_history(jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype), params))

    def router_bfloat16():
        lower = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        afmoe_ref.route = lambda p_, m, mdl, chosen=None: plain_route({**p_, "router": lower(p_["router"])}, lower(m), mdl, chosen)
        try:
            return lowered_history(params)
        finally:
            afmoe_ref.route = plain_route

    stated = (compare.TOL_STATED["bfloat16"], compare.MARGIN_STATED["bfloat16"])
    exact = (compare.TOL_EXACT, compare.MARGIN_EXACT)
    for name, run, (tol, line), held_to in (
        ("products bfloat16", lambda: products(jnp.bfloat16), stated, "stated"),
        ("products float8_e4m3fn", lambda: products(jnp.float8_e4m3fn), stated, "stated"),
        ("parameters bfloat16", parameters_bfloat16, exact, "exact"),
        ("router bfloat16", router_bfloat16, exact, "exact"),
    ):
        lowered = run()
        logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0)[:, -steps:], *[x[0] for x in lowered])
        values = jnp.concatenate([x[1] for x in lowered], axis=0)[:, -steps:]
        routes = [
            jnp.concatenate([x[2][layer]["chosen"] for x in lowered], axis=0)
            for layer in range(len(lowered[0][2]))
        ]
        want_logits, want_values, margin = compare.reference_outputs(params, obs, dones, model, routes, steps)
        diff, _ = compare.relative_difference(logits, values, want_logits, want_values)
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
