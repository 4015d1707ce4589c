"""Measure every driver benchmark config (BASELINE.md "Benchmark configs").

The five configs come from the driver metadata (BASELINE.json:6-12, mirrored
in BASELINE.md): 1v1 single-worker, 1v1 self-play at 8 workers, multi-hero
pool, 2v2 with unit-attention heads, and 5v5 at 256 envs with league
opponents. One command measures steady-state end-to-end TRAINED frames/sec
(full pipeline: on-device rollouts → HBM ring buffer → donated train step)
for each and prints one JSON line per config plus a summary table:

    python scripts/bench_configs.py              # all five (~10 min on TPU)
    python scripts/bench_configs.py --configs 1,4
    python scripts/bench_configs.py --steps 50   # quicker, noisier
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_config(n: int, base):
    """Driver benchmark config *n* → (RunConfig, description)."""
    env = base.env
    buf = base.buffer
    league = base.league
    if n == 1:
        # 1v1-mid Shadow Fiend PPO, single rollout worker. The TPU-native
        # "single worker" is one DeviceActor multiplexing enough lanes to
        # feed the learner batch (the reference's 1-env worker underfeeds
        # any optimizer; its modern reading is one actor process).
        env = dataclasses.replace(
            env, n_envs=128, team_size=1, hero_pool=(1,),
            opponent="scripted_easy", max_dota_time=120.0,
        )
        desc = "1v1-mid, single device-actor, scripted opponent"
    elif n == 2:
        # 1v1-mid self-play, 8 workers -> broker -> one optimizer: 8
        # independent lane groups in self-play mode (both sides learner-
        # controlled, rollouts from every lane).
        env = dataclasses.replace(
            env, n_envs=8 * 32, team_size=1, hero_pool=(1,),
            opponent="selfplay", max_dota_time=120.0,
        )
        desc = "1v1-mid self-play, 8x32 lanes"
    elif n == 3:
        # Multi-hero pool with hero embedding (Nevermore/Lina/Sniper).
        env = dataclasses.replace(
            env, n_envs=128, team_size=1, hero_pool=(1, 2, 3),
            opponent="selfplay", max_dota_time=120.0,
        )
        desc = "1v1-mid multi-hero pool {1,2,3}, self-play"
    elif n == 4:
        # 2v2 lane self-play (ally/enemy unit attention heads).
        env = dataclasses.replace(
            env, n_envs=64, team_size=2, hero_pool=(1, 2, 3),
            opponent="selfplay", max_dota_time=120.0,
        )
        desc = "2v2 self-play, 64 games (256 lanes)"
    elif n == 5:
        # 5v5 full-team, 256 concurrent envs, league opponents.
        env = dataclasses.replace(
            env, n_envs=256, team_size=5, hero_pool=(1, 2, 3),
            opponent="league", max_dota_time=120.0,
        )
        league = dataclasses.replace(
            league, enabled=True, snapshot_every=100, pool_size=4
        )
        desc = "5v5 league, 256 games (1280 learner lanes)"
    else:
        raise ValueError(f"unknown config {n}")
    buf = dataclasses.replace(buf, capacity_rollouts=512, min_fill=128)
    cfg = dataclasses.replace(
        base, env=env, buffer=buf, league=league, log_every=10_000
    )
    return cfg, desc


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--configs", type=str, default="1,2,3,4,5")
    p.add_argument("--steps", type=int, default=100,
                   help="timed optimizer steps per config")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mode", type=str, default="device",
                   choices=("device", "fused"),
                   help="device: buffered loop; fused: one program per step "
                   "(batch = lane set, so frames/step scales with lanes)")
    p.add_argument("--core", type=str, default="lstm",
                   choices=("lstm", "transformer"),
                   help="policy core used across all configs")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="with --mode fused: iterations scanned per dispatch "
                   "(RunConfig.steps_per_dispatch)")
    args = p.parse_args()
    if args.steps_per_dispatch > 1 and args.mode != "fused":
        p.error("--steps-per-dispatch needs --mode fused")

    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.train.learner import Learner
    from dotaclient_tpu.utils import compile_cache

    compile_cache.enable()
    base = default_config()
    if args.core != "lstm":
        base = dataclasses.replace(
            base, model=dataclasses.replace(base.model, core=args.core)
        )
    results = []
    for n in (int(s) for s in args.configs.split(",")):
        cfg, desc = build_config(n, base)
        cfg = dataclasses.replace(
            cfg, steps_per_dispatch=args.steps_per_dispatch
        )
        learner = Learner(cfg, actor=args.mode, seed=args.seed)
        learner.train(20)          # compile + buffer warmup
        fps = 0.0
        for _ in range(3):         # best of 3
            t0 = time.perf_counter()
            out = learner.train(args.steps)
            # frames_trained, not steps × a hand-derived frames-per-step:
            # epochs/minibatches re-use each chunk, and dispatch batching
            # overshoots the request in strides — the learner's own counter
            # is the unique-trained-frames truth
            fps = max(
                fps, out["frames_trained"] / (time.perf_counter() - t0)
            )
        row = {
            "config": n,
            "desc": desc,
            "mode": args.mode,
            "core": args.core,
            "steps_per_dispatch": args.steps_per_dispatch,
            "end_to_end_frames_per_sec": round(fps, 1),
            "n_envs": cfg.env.n_envs,
            "team_size": cfg.env.team_size,
            "learner_lanes": learner.device_actor.n_lanes,
        }
        results.append(row)
        print(json.dumps(row), flush=True)
        del learner

    print("\nconfig | description | e2e frames/sec")
    for r in results:
        print(f"{r['config']:>6} | {r['desc']:<46} | {r['end_to_end_frames_per_sec']:>10,.0f}")


if __name__ == "__main__":
    main()
