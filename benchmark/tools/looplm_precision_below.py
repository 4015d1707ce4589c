"""The reading that sets ``compare_looplm``'s stated limit from below: the
plain reference with every product's operands rounded to a lower precision,
compared with itself in float32 the way ``policy_agreement`` compares the
program's ``sequence`` (every loop step's logits and values and the exit
gates' logits, relative to the outputs' size).

    chiprun -- python3 benchmark/tools/looplm_precision_below.py [--cell C] [--seed N] [--lanes 2]

Prints one line a precision: ``bfloat16`` (what the configuration states: it
has to read about what the program reads) and ``float8_e4m3fn`` (the nearest
precision below, unscaled: it has to read above ``TOL_STATED``, that is,
come out as not correct). Weights are the program's seeded initial ones at
the cell's widths. The method is ``afmoe_precision_below.py``'s, whose
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
    p.add_argument("--cell", default="ouro-2.6b-5v5-ut4.fused-selfplay-anycore")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lanes", type=int, default=2)
    p.add_argument("--rehearse-cpu", action="store_true", help="tiny sizes, on the CPU: control flow only")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cells, compare_looplm, program
    from benchmark.reference import afmoe_ref, looplm_ref, policy_ref
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
    obs, dones = compare_looplm.sample(rc, args.seed, args.lanes, steps, history)
    model = dict(rc["model"])

    want = dict(zip("lvg", compare_looplm.reference_outputs(params, obs, dones, model, steps)))
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    for name in ("bfloat16", "float8_e4m3fn"):
        shim = RoundedProducts(getattr(jnp, name))
        modules = (looplm_ref, afmoe_ref, policy_ref)      # looplm_ref's norm, SwiGLU and RoPE are afmoe_ref's
        try:
            for m in modules:
                m.jnp = shim
            lowered = dict(zip("lvg", compare_looplm.reference_outputs(params, obs, dones, model, steps)))
        finally:
            for m in modules:
                m.jnp = jnp
        diff = policy_ref.max_abs_diff(lowered, want) / scale
        print(json.dumps({
            "products_rounded_to": name, "outputs": diff, "output_scale": scale,
            "tol_stated": compare_looplm.TOL_STATED["bfloat16"],
            "correct_by_the_stated_limit": bool(diff <= compare_looplm.TOL_STATED["bfloat16"]),
            "lanes": args.lanes, "history_steps": history, "seed": args.seed,
            "device": jax.devices()[0].device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
