"""Compile a training cell's fused program for a described TPU topology and
print what it would hold in memory: no chip, no chip time.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_topology.py \\
        --workload five5v5-lstm4096.fused-selfplay [--n-envs-per-chip 256]

The TPU's compiler is installed beside JAX and compiles for a chip that is
described and not attached (``jax.experimental.topologies``). The cell's
configuration is built as the runner builds it; the learner itself cannot
be (it places arrays on ``jax.devices()``), so this hands the program's
``make_fused_step`` a mesh of described devices and the shapes of its
arguments. ``memory_analysis()`` counts this one program: its arguments
(the train state, the actor state, the opponent's parameters), its outputs
(no donation: the new state beside the old) and its temporaries. What else
the process keeps on the chip is added by hand below: the league's
snapshots and one publish copy of the parameters.

A compile that passes is not a chip run and says nothing about time.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--n-envs-per-chip", type=int, default=None, help="try another lane count than the configuration's")
    p.add_argument("--topology", default="v5e:2x2")
    p.add_argument("--hlo", default=None, help="write the optimised HLO text here")
    args = p.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("compile_for_topology: set JAX_PLATFORMS=cpu (the topology is described, not attached)")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from benchmark.harness import cells, program
    from dotaclient_tpu.actor.device_rollout import DeviceActor, actor_state_sharding
    from dotaclient_tpu.models import init_params, make_policy
    from dotaclient_tpu.train.fused import make_fused_step
    from dotaclient_tpu.train.ppo import init_train_state, train_state_sharding

    cell = cells.load_cell(args.workload)
    if args.n_envs_per_chip is not None:
        cell = dataclasses.replace(
            cell, config={**cell.config, "n_envs_per_chip": args.n_envs_per_chip}
        )
    tp = cell.traffic["params"]
    cfg = program.build_run_config(
        cell, seed=0, rehearsal=False,
        top_level={"steps_per_dispatch": tp["steps_per_dispatch"]},
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    devices = list(topo.devices)[: cell.chips]
    mesh = Mesh(np.asarray(devices).reshape(cell.chips, 1), (cfg.mesh.data_axis, cfg.mesh.model_axis))

    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    actor = DeviceActor(cfg, policy, seed=0)          # state on the CPU: shapes only
    fused = make_fused_step(policy, cfg, mesh, actor)

    def with_sharding(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings,
        )

    state_shape = jax.eval_shape(
        lambda: init_train_state(init_params(policy, jax.random.PRNGKey(0)), cfg.ppo)
    )
    st_sh = train_state_sharding(policy, cfg, mesh)
    state = with_sharding(state_shape, st_sh)
    actor_state = with_sharding(
        jax.eval_shape(lambda: actor.state), actor_state_sharding(actor.state, mesh, cfg.mesh)
    )
    compiled = fused.lower(state, actor_state, state.params).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)

    def nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))

    params_b = nbytes(state_shape.params)
    pool = cfg.league.pool_size * params_b if cfg.env.opponent == "league" else 0
    lanes = actor.n_lanes
    gb = 1e9
    program_peak = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    print(f"cell {cell.name}: topology {args.topology}, {cell.chips} chip(s), "
          f"n_envs {cfg.env.n_envs} ({cfg.env.n_envs // cell.chips} a chip), "
          f"{lanes} learner lanes, {lanes * cfg.ppo.rollout_len} frames a dispatch")
    print(f"per chip, from memory_analysis(): arguments {mem.argument_size_in_bytes / gb:.3f} GB, "
          f"outputs {mem.output_size_in_bytes / gb:.3f} GB, temporaries {mem.temp_size_in_bytes / gb:.3f} GB, "
          f"aliased {mem.alias_size_in_bytes / gb:.3f} GB, code {mem.generated_code_size_in_bytes / gb:.3f} GB")
    print(f"program peak (arguments + outputs + temporaries - aliased): {program_peak / gb:.3f} GB")
    print(f"beside it: parameters {params_b / gb:.3f} GB, train state {nbytes(state_shape) / gb:.3f} GB, "
          f"league pool {pool / gb:.3f} GB, one publish copy {params_b / gb:.3f} GB")
    print(f"estimated process peak: {(program_peak + pool + params_b) / gb:.3f} GB of 16 GB")
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
    found = {op: sum(1 for ln in text.splitlines() if f" {op}(" in ln or f" {op}-start(" in ln) for op in ops}
    print(f"collectives in the optimised HLO: {found}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
