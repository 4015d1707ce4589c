"""Runner ``train_fused``: the program's fused trainer over a fixed window.

Builds ``Learner(cfg, actor="fused", seed=<the mix's learner_seed>)`` with
``cfg.seed`` and ``cfg.env.seed`` from ``--seed``, as the program's own
entry point builds it, warms its one device program, fills the league's
pool, then lets ``Learner.train`` run until ``--seconds`` have passed and a
whole dispatch has ended. The only thing put between the learner and its
program is ``DispatchMeter``, which stands where ``learner.fused_step``
stood: it counts dispatches, holds the queue to the mix's
``max_dispatches_in_flight`` so that the window can end, and in a traced run
names the host's stretches for the profiler. The loop, the league draw, the
health and logging cadence and the snapshot thread are the program's,
untouched. The two departures from a user's run (the held seed, the held
queue) and what each hides are in the mix's ``assumed``.

``train_frames_per_s`` is frames trained between two ``block_until_ready``
points over the host seconds between them: the window starts after the
warm-up has drained and ends when the last dispatch's outputs are ready, so
the divisor is the time that really passed and nothing is quantised.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from benchmark.harness import compare, device as device_mod, program, trace as trace_mod

WINDOW_SPAN = "bench:traced_window"
ENQUEUE_SPAN = "bench:learner_enqueues_dispatch"
LOOP_SPAN = "bench:learner_loop_between_dispatches"
DRAIN_SPAN = "bench:benchmark_waits_for_device"


class DispatchMeter:
    """Stands in for ``learner.fused_step``; every call goes through to it."""

    def __init__(self, learner: Any, step: Any, pool_fill: bool, max_in_flight: int) -> None:
        self._learner = learner
        self._step = step
        self._pool_fill = pool_fill
        # The program sets no limit on how far its loop runs ahead of the
        # device: while the opponent is the live policy nothing in it waits,
        # an enqueue costs 10-18 ms against a step of 250-320 ms, every
        # dispatch in flight holds its outputs (no donation), and the
        # allocator stalls an enqueue only when HBM is full. A window has to
        # end, and a chip filled by the queue is erratic (PERF.md, PR 22),
        # so the meter waits for dispatch i - max_in_flight before it lets
        # dispatch i + 1 go: the device always has one queued behind the one
        # it runs, and the queue drains in under a second.
        self._max_in_flight = max_in_flight
        self._in_flight: collections.deque = collections.deque()
        self._armed = False
        self.dispatches = 0            # in the window
        self.warmup_dispatches = 0
        self.t0 = self.t1 = 0.0
        self.built_at_end: Optional[int] = None
        self._deadline = math.inf
        self._last: Any = None         # the newest dispatch's metrics (scalars)
        self._clock: Optional[device_mod.CompileClock] = None
        # traced run
        self._trace_dir: Optional[str] = None
        self._trace_from = self._trace_to = -1
        self._annot: Any = None
        self._window_annot: Any = None
        self.traced_dispatches = 0
        self.traced_seconds = 0.0

    def arm(
        self, seconds: float, clock: device_mod.CompileClock,
        trace_dir: Optional[str], trace_after: int, trace_n: int,
    ) -> None:
        self._armed = True
        self._clock = clock
        self._trace_dir = trace_dir
        if trace_dir is not None:
            self._trace_from, self._trace_to = trace_after, trace_after + trace_n
        self.t0 = time.perf_counter()
        self._deadline = self.t0 + seconds

    # -- the profiler's window: between two drained points ---------------------

    def _drain(self) -> None:
        import jax

        if self._last is not None:
            jax.block_until_ready(self._last)

    def _swap_annotation(self, name: Optional[str]) -> None:
        if self._annot is not None:
            self._annot.__exit__(None, None, None)
            self._annot = None
        if name is not None and self._window_annot is not None:
            import jax

            self._annot = jax.profiler.TraceAnnotation(name)
            self._annot.__enter__()

    def _start_trace(self) -> None:
        import jax

        self._drain()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python frames: large, and slow the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._window_annot = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window_annot.__enter__()
        self._t_trace = time.perf_counter()

    def _stop_trace(self) -> None:
        import jax

        self._swap_annotation(DRAIN_SPAN)
        self._drain()
        self._swap_annotation(None)
        self.traced_seconds = time.perf_counter() - self._t_trace
        self.traced_dispatches = self._trace_to - self._trace_from
        self._window_annot.__exit__(None, None, None)
        self._window_annot = None
        jax.profiler.stop_trace()

    # -- the call --------------------------------------------------------------

    def __call__(self, state: Any, actor_state: Any, opp_params: Any):
        if not self._armed:
            out = self._step(state, actor_state, opp_params)
            self.warmup_dispatches += 1
            league = self._learner.league
            if self._pool_fill and league is not None:
                # a frozen snapshot of each warm-up step's parameters, as if
                # snapshot_every steps had passed between them
                k = self.warmup_dispatches
                league.maybe_snapshot(
                    out[0].params, k, k * league.config.snapshot_every
                )
            return out
        i = self.dispatches
        if i == self._trace_from:
            self._start_trace()
        last = time.perf_counter() >= self._deadline
        if i == self._trace_to:
            self._stop_trace()
            last = True          # a traced run has what it came for
        self._swap_annotation(ENQUEUE_SPAN)
        out = self._step(state, actor_state, opp_params)
        self._swap_annotation(LOOP_SPAN)
        self.dispatches += 1
        self._last = out[2]
        self._in_flight.append(out[2])
        if len(self._in_flight) > self._max_in_flight:
            import jax

            jax.block_until_ready(self._in_flight.popleft())
        if last:
            if self._window_annot is not None:   # window shorter than the plan
                self._trace_to = self.dispatches
                self._stop_trace()
            self._swap_annotation(None)
            self._drain()
            self.t1 = time.perf_counter()
            self.built_at_end = self._clock.read()[1]
            self._learner.request_stop()
        return out


def _check(ok: bool, what: str, failures: List[str]) -> None:
    if not ok:
        failures.append(what)


def _spread_over_devices(leaves: List[Any], n_rows: int, devices: List[Any]) -> Optional[str]:
    """From the arrays, as ``chip_smoke.py`` reads it: every leaf holds a
    distinct block of rows on every device."""
    for x in leaves:
        if x.shape[0] != n_rows:
            return f"leaf {x.shape} has not {n_rows} rows"
        if x.sharding.device_set != set(devices):
            return f"leaf {x.shape} lives on {len(x.sharding.device_set)} of {len(devices)} devices"
        rows = {s.index[0].indices(n_rows)[:2] for s in x.addressable_shards}
        if len(rows) != len(devices):
            return f"leaf {x.shape} has {len(rows)} row blocks over {len(devices)} devices"
    return None


def run(cell: Any, args: Any) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device_mod.require_devices(devices, cell.chips, args.rehearse)

    from dotaclient_tpu.train.learner import Learner
    from dotaclient_tpu.utils import compile_cache, telemetry

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
            cfg.ppo, epochs_per_batch=p["epochs_per_batch"],
            minibatches=p["minibatches"],
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
    # --seed makes the weights (cfg.seed); the learner's own seed is the
    # traffic mix's, so that the league's draws are the same in every run
    learner = Learner(cfg, actor="fused", seed=p["learner_seed"], metrics_jsonl=jsonl)
    stages = {
        "to_runner_s": t_start,
        "learner_built_s": device_mod.process_age_s() - t_start,
    }
    meter = DispatchMeter(
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
    _check(
        out["frames_trained"] == frames,
        f"learner counted {out['frames_trained']} frames, dispatches x lanes x T = {frames}",
        failures,
    )
    built_in_window = meter.built_at_end - built_setup
    _check(built_in_window == 0, f"{built_in_window} program(s) built inside the window", failures)
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
    # the learner logs where its step counter passes a multiple of log_every
    # (a late line may replace an earlier one: the newest always lands)
    steps0 = meter.warmup_dispatches * cfg.steps_per_dispatch
    steps1 = steps0 + meter.dispatches * cfg.steps_per_dispatch
    if steps1 // cfg.log_every > steps0 // cfg.log_every:
        _check(bool(logged), "a log boundary was passed and no logged step holds a loss", failures)
    _check(not bad_steps, f"{len(bad_steps)} logged step(s) not finite or unhealthy", failures)
    params_after = float(checksum(learner.state.params))
    _check(
        math.isfinite(params_after) and params_after != params_before,
        f"parameters did not change (sum of squares {params_before} -> {params_after})",
        failures,
    )
    if cell.chips > 1:
        why = _spread_over_devices(
            jax.tree.leaves((actor.state.carry, actor.state.ep_return)), lanes, devices,
        ) or _spread_over_devices([actor.state.key], actor.spec.n_games, devices)
        _check(why is None, f"lanes not spread over the chips: {why}", failures)
    agreement = compare.policy_agreement(
        learner.policy, learner.state.params, rc, args.seed,
        min(p["sample_lanes"], 8) if args.rehearse else p["sample_lanes"],
        p["sample_steps"],
    )
    _check(agreement["ok"], f"policy disagrees with the reference: {agreement}", failures)

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
        },
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
            span = tr.span(WINDOW_SPAN)
            if tr.devices and span is not None:
                record["trace"] = tr
                record["trace_window"] = (span.start, span.end)
            if not args.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
    return record
