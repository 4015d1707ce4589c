"""Runner ``train_fused_anycore``: ``train_fused``'s window for a core that
is not the LSTM.

The same learner, the same warm-up and pool fill, the same held queue and
the same window as ``runners/train_fused.py``, through ITS ``DispatchMeter``
(imported, not copied), and the same checks on frames, compiles in the
window, logged steps and parameters. Two things differ:

* ``correct`` is decided by the module the configuration's ``comparison``
  key names (``benchmark/harness/<comparison>.py`` with
  ``policy_agreement(policy, params, rc, seed, lanes, steps,
  history_steps)``), where ``train_fused`` calls ``harness/compare.py``,
  which builds an LSTM carry. Everything but the parameters is released
  first, or the comparison (its own sample of lanes, the reference's
  whole-history attention, float32 weights at "highest" precision) does not
  fit beside the trained state. The process's memory peak is then the
  comparison's; the trained program's is noted before the release
  (``window.memory_stats_at_end``).
* where the program counts routed-expert pairs (``moe/*`` in its registry),
  a pair its weights leave out is a failure.

A rehearsal (``--rehearse-cpu``) keeps the harness's rule (one game,
``hidden_dim`` 256; every other size as published: the rings are as wide as
the KV heads, not the stream) and compares 2 lanes over 4 chunks: the
reference's whole-history attention at 2,560 steps is minutes a lane on the
CPU. Nothing it prints is a result.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import shutil
from typing import Any, Dict, List

from benchmark.harness import cells, device as device_mod, program, trace as trace_mod
from benchmark.runners import train_fused as base

REHEARSAL_LANES, REHEARSAL_CHUNKS = 2, 4


def run(cell: Any, args: Any) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device_mod.require_devices(devices, cell.chips, args.rehearse)

    from dotaclient_tpu.train.learner import Learner
    from dotaclient_tpu.utils import compile_cache, telemetry

    comparison = importlib.import_module(f"{cells.PACKAGE}.harness.{cell.config['comparison']}")
    cache_dir = compile_cache.enable()
    clock = device_mod.CompileClock()
    p = cell.traffic["params"]
    rc = program.merged_run_config(cell, args.rehearse)
    cfg = program.build_run_config(
        cell, args.seed, args.rehearse,
        top_level={"steps_per_dispatch": p["steps_per_dispatch"]},
    )
    cfg = dataclasses.replace(
        cfg,
        ppo=dataclasses.replace(
            cfg.ppo, epochs_per_batch=p["epochs_per_batch"], minibatches=p["minibatches"],
        ),
        mesh=dataclasses.replace(cfg.mesh, data_parallel=-1),
    )

    os.makedirs(args.out, exist_ok=True)
    jsonl = os.path.join(args.out, "metrics.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)          # the sink appends
    trace_dir = os.path.join(args.out, "trace") if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_start = device_mod.process_age_s()
    learner = Learner(cfg, actor="fused", seed=p["learner_seed"], metrics_jsonl=jsonl)
    stages = {
        "to_runner_s": t_start,
        "learner_built_s": device_mod.process_age_s() - t_start,
    }
    meter = base.DispatchMeter(
        learner, learner.fused_step, pool_fill=True,
        max_in_flight=p["max_dispatches_in_flight"],
    )
    learner.fused_step = meter
    actor = learner.device_actor
    lanes, T = actor.n_lanes, cfg.ppo.rollout_len
    opp_lanes = len(actor.opponent_players) * actor.spec.n_games
    frames_per_dispatch = lanes * T * cfg.steps_per_dispatch

    checksum = jax.jit(
        lambda tree: sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )

    # -- set-up: compile, warm up, fill the pool -------------------------------
    learner.train(p["warmup_dispatches"])
    params_before = float(checksum(learner.state.params))
    jax.block_until_ready((learner.state, actor.state))
    compile_s, built_setup, hits, misses = clock.read()
    registry = telemetry.get_registry()
    counters_before = registry.snapshot()
    setup_s = device_mod.process_age_s()
    stages["warmup_and_pool_fill_s"] = setup_s - stages["to_runner_s"] - stages["learner_built_s"]

    # -- the window ------------------------------------------------------------
    meter.arm(
        args.seconds, clock, trace_dir,
        p["trace_after_dispatches"], p["trace_dispatches"],
    )
    out = learner.train(10 ** 9)      # the meter ends it
    counters_after = registry.snapshot()
    elapsed = meter.t1 - meter.t0
    frames = meter.dispatches * frames_per_dispatch

    # -- correct? --------------------------------------------------------------
    failures: List[str] = []
    base._check(
        out["frames_trained"] == frames,
        f"learner counted {out['frames_trained']} frames, dispatches x lanes x T = {frames}",
        failures,
    )
    built_in_window = meter.built_at_end - built_setup
    base._check(built_in_window == 0, f"{built_in_window} program(s) built inside the window", failures)
    logged = []
    with open(jsonl) as f:
        for line in f:
            scalars = json.loads(line).get("scalars", {})
            if "loss" in scalars:
                logged.append(scalars)
    bad_steps = [
        s for s in logged
        if not (
            s.get("loss") is not None and math.isfinite(s["loss"])
            and s.get("grad_norm") is not None and math.isfinite(s["grad_norm"])
            and s.get("health_ok", 1.0) == 1.0
        )
    ]
    steps0 = meter.warmup_dispatches * cfg.steps_per_dispatch
    steps1 = steps0 + meter.dispatches * cfg.steps_per_dispatch
    if steps1 // cfg.log_every > steps0 // cfg.log_every:
        base._check(bool(logged), "a log boundary was passed and no logged step holds a loss", failures)
    base._check(not bad_steps, f"{len(bad_steps)} logged step(s) not finite or unhealthy", failures)
    dropped = counters_after.get("moe/dropped_assignments")
    if dropped is not None:
        base._check(dropped == 0, f"{dropped} routed token-expert pair(s) left out by the layer", failures)
    params_after = float(checksum(learner.state.params))
    base._check(
        math.isfinite(params_after) and params_after != params_before,
        f"parameters did not change (sum of squares {params_before} -> {params_after})",
        failures,
    )
    # where the core counts positions (its carry's "pos"), the lanes' mean
    # position as the window ends: the attention counts depend on it
    pos = actor.state.carry.get("pos") if isinstance(actor.state.carry, dict) else None
    position_at_end = None if pos is None else float(jnp.mean(pos.astype(jnp.float32)))
    # What the window held goes before the comparison builds its own sample
    # (the lanes' caches and games, Adam's moments, the league's pool): the
    # two do not fit the chip together. The peak so far is the trained
    # program's and is noted here; the comparison's is higher.
    params = learner.state.params
    at_end = device_mod.memory_stats(devices)
    actor.state = learner.state = None
    if learner.league is not None:
        learner.league.snapshots.clear()
    sample_lanes, chunks = p["sample_lanes"], p["sample_history_steps"] // p["sample_steps"]
    if args.rehearse:
        sample_lanes, chunks = REHEARSAL_LANES, REHEARSAL_CHUNKS
    agreement = comparison.policy_agreement(
        learner.policy, params, rc, args.seed,
        sample_lanes, p["sample_steps"], chunks * p["sample_steps"],
    )
    base._check(agreement["ok"], f"policy disagrees with the reference: {agreement}", failures)

    record: Dict[str, Any] = {
        "cell": cell.name, "chips": cell.chips, "seed": args.seed,
        "rehearsal": args.rehearse,
        "devices": devices, "run_config": rc,
        "lanes": lanes, "opp_lanes": opp_lanes, "rollout_len": T,
        "window": {
            "seconds": elapsed, "dispatches": meter.dispatches,
            "frames": frames, "frames_per_dispatch": frames_per_dispatch,
            "programs_built": built_in_window,
            "traced_seconds": meter.traced_seconds,
            "traced_dispatches": meter.traced_dispatches,
            "traced_until_dispatch": meter._trace_to if trace_dir else None,
            "memory_stats_at_end": at_end,
        },
        "core_position_at_end": position_at_end,
        "setup": {
            "setup_s": setup_s, "compile_s": compile_s,
            "programs_built": built_setup, "cache_hits": hits,
            "cache_misses": misses, "cache_dir": cache_dir,
            "stages": stages,
        },
        "counters": {"before": counters_before, "after": counters_after},
        "end_to_end": {
            "train_frames_per_s": frames / elapsed,
            "setup_s": setup_s,
        },
        "attempted": meter.dispatches,
        "failed": len(bad_steps),
        "failures": failures,
        "agreement": agreement,
        "trace": None,
        "trace_window": None,
    }
    if trace_dir:
        path = trace_mod.find_xplane(trace_dir)
        if path is not None:
            tr = trace_mod.load(path)
            span = tr.span(base.WINDOW_SPAN)
            if tr.devices and span is not None:
                record["trace"] = tr
                record["trace_window"] = (span.start, span.end)
            if not args.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
    return record
