"""The reading that sets ``compare_kimilinear``'s stated limits from below:
the plain reference with every product's operands rounded to a lower
precision, compared with itself in float32 the way ``policy_agreement``
compares the program (the rounded pass's own experts handed to the float32
pass, outputs relative to their size, the routing margin beside them).

    chiprun -- python3 benchmark/tools/kimilinear_precision_below.py [--cell C] [--seed N] [--lanes 2]

Prints one line a precision: ``bfloat16`` (what the configuration states: it
has to read about what the program reads; harsher than the program, whose
KDA state stays float32 between steps while this rounds it into every
product) and ``float8_e4m3fn`` (the nearest precision below, unscaled: it
has to read above ``TOL_STATED`` or above ``MARGIN_STATED``, that is, come
out as not correct). Weights are the program's seeded initial ones at the
cell's widths. The method is ``afmoe_precision_below.py``'s, whose
``RoundedProducts`` stands in for the reference modules' ``jnp``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--cell", default="kimi-linear-5v5-ep32.fused-selfplay-anycore")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lanes", type=int, default=2)
    p.add_argument("--rehearse-cpu", action="store_true", help="tiny sizes, on the CPU: control flow only")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cells, compare_kimilinear, program
    from benchmark.reference import afmoe_ref, kimilinear_ref, policy_ref
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
    obs, dones = compare_kimilinear.sample(rc, args.seed, args.lanes, steps, history)
    model = dict(rc["model"])

    for name in ("bfloat16", "float8_e4m3fn"):
        shim = RoundedProducts(getattr(jnp, name))
        modules = (kimilinear_ref, afmoe_ref, policy_ref)     # the norm, SwiGLU and the experts are afmoe_ref's
        try:
            for m in modules:
                m.jnp = shim
            lowered = [
                jax.jit(lambda p_, o, d: kimilinear_ref.history(p_, o, d, model))(
                    params, {k: v[i:i + 1] for k, v in obs.items()}, dones[i:i + 1]
                )
                for i in range(args.lanes)
            ]
        finally:
            for m in modules:
                m.jnp = jnp
        logits = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0)[:, -steps:], *[x[0] for x in lowered])
        values = jnp.concatenate([x[1] for x in lowered], axis=0)[:, -steps:]
        routes = [
            jnp.concatenate([x[2][layer]["chosen"] for x in lowered], axis=0)
            for layer in range(len(lowered[0][2]))
        ]
        want_logits, want_values, margin = compare_kimilinear.reference_outputs(params, obs, dones, model, routes, steps)
        want = {"l": want_logits, "v": want_values}
        scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
        diff = policy_ref.max_abs_diff({"l": logits, "v": values}, want) / scale
        tol, line = compare_kimilinear.TOL_STATED["bfloat16"], compare_kimilinear.MARGIN_STATED["bfloat16"]
        print(json.dumps({
            "products_rounded_to": name, "outputs": diff, "routing_margin": margin,
            "tol_stated": tol, "margin_stated": line,
            "correct_by_the_stated_limits": bool(diff <= tol and margin <= line),
            "lanes": args.lanes, "history_steps": history, "seed": args.seed,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
