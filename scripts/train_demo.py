"""Seeded learning demonstration (VERDICT round 1, "Demonstrate learning").

One command reproduces the numbers recorded in BASELINE.md:

    python scripts/train_demo.py                # full demo (~10-20 min on TPU)
    python scripts/train_demo.py --steps 2000   # shorter sanity run

Protocol:
1. evaluate the INITIAL policy vs the easy and hard scripted bots;
2. train vs scripted_easy (seeded, fixed config) with periodic windowed
   reward/win-rate logging — the rising-reward curve;
3. evaluate the TRAINED policy vs scripted_easy, scripted_hard, and its own
   initial self (league-mode eval vs the frozen step-0 snapshot);
4. print one JSON summary line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=20_000)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--n-envs", type=int, default=128)
    p.add_argument("--eval-games", type=int, default=64)
    p.add_argument("--team-size", type=int, default=1,
                   help="heroes per side: 1 (1v1 demo), 2, or 5 "
                   "(the BASELINE config-5 game shape)")
    p.add_argument("--max-dota-time", type=float, default=300.0,
                   help="episode horizon in game seconds (timeout "
                   "adjudication decides un-finished games)")
    p.add_argument("--hero-pool", type=str, default=None,
                   help="comma-separated hero ids (default: single-hero "
                   "at team size 1, {1,2,3} otherwise)")
    p.add_argument("--opponent", type=str, default="scripted_easy",
                   choices=("scripted_easy", "scripted_hard", "selfplay",
                            "league"),
                   help="training opponent (evals always measure both "
                   "scripted bots); fine-tune stages should train against "
                   "an opponent the policy does NOT already beat — a "
                   "near-optimal matchup has ~zero advantage signal; "
                   "'league' trains vs frozen snapshots of past selves "
                   "(LeagueConfig; tune with --league)")
    p.add_argument("--league", type=str, default=None,
                   help="comma-separated LeagueConfig overrides with "
                   "--opponent league, e.g. 'anchor_prob=0.25,"
                   "snapshot_every=200,pool_size=8' — anchor_prob pins "
                   "that fraction of games to a scripted bot (AlphaStar-"
                   "style anchors; keeps push behavior in a self-play "
                   "meta)")
    p.add_argument("--ppo", type=str, default=None,
                   help="comma-separated PPOConfig overrides, e.g. "
                   "'entropy_coef=0.001,learning_rate=1e-4' — fine-tune "
                   "stages need weaker entropy pressure than from-scratch "
                   "runs (a near-optimal policy has ~zero advantage signal, "
                   "so the entropy bonus becomes the dominant gradient and "
                   "re-randomizes it)")
    p.add_argument("--reward", type=str, default=None,
                   help="comma-separated RewardConfig overrides, e.g. "
                   "'win=25,tower_damage=20,last_hits=0.08' — the lever "
                   "BASELINE.md's 5v5 probes identified (farm shaping can "
                   "dominate the sparse push/win terms at team sizes > 1)")
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument("--restore", action="store_true",
                   help="resume from the latest checkpoint in "
                   "--checkpoint-dir instead of starting at step 0")
    p.add_argument("--init-from", type=str, default=None, metavar="DIR",
                   help="seed a fresh run with the params of the latest "
                   "checkpoint in DIR; unlike --restore the source dir is "
                   "never written to (safe curriculum staging — a stage-2 "
                   "run resuming IN its source dir would garbage-collect "
                   "the stage-1 snapshot)")
    p.add_argument("--logdir", type=str, default=None)
    p.add_argument("--metrics-jsonl", type=str, default=None, metavar="PATH",
                   help="append log-boundary metrics snapshots as JSON "
                   "lines to PATH (the headless record; enables the "
                   "outcome win-rate curves scripts/outcome_report.py "
                   "renders — pair with --log-every)")
    p.add_argument("--log-every", type=int, default=None,
                   help="log-boundary cadence in optimizer steps; default "
                   "keeps the demo's drain-free behavior (boundaries only "
                   "with --logdir). Mid-block boundaries reset the "
                   "windowed stats the demo prints — accept that when you "
                   "want dense --metrics-jsonl curves")
    p.add_argument("--actor", type=str, default="fused",
                   choices=("fused", "device"),
                   help="fused: one program per optimizer step (fastest); "
                   "device: buffered loop (round-2 demo parity)")
    p.add_argument("--core", type=str, default="lstm",
                   choices=("lstm", "transformer"),
                   help="policy core; transformer = windowed-attention core "
                   "(rolling KV-cache carry), the scale-out option")
    p.add_argument("--moe-experts", type=int, default=0,
                   help="with --core transformer: experts per MoE FFN layer")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="with --actor fused: rollout+update iterations "
                   "scanned inside one dispatch (amortizes the host-device "
                   "round trip; demo stats/evals coarsen to this stride)")
    args = p.parse_args()
    if args.steps_per_dispatch > 1 and args.actor != "fused":
        p.error("--steps-per-dispatch needs --actor fused")
    if args.restore and not args.checkpoint_dir:
        p.error("--restore needs --checkpoint-dir")
    if args.init_from and args.restore:
        p.error("--init-from and --restore are mutually exclusive")

    from dotaclient_tpu.config import (
        LeagueConfig, PPOConfig, RewardConfig, default_config,
    )
    from dotaclient_tpu.league import evaluate
    from dotaclient_tpu.train.learner import Learner
    from dotaclient_tpu.utils import compile_cache

    compile_cache.enable()
    if args.hero_pool is not None:
        try:
            hero_pool = tuple(int(h) for h in args.hero_pool.split(","))
        except ValueError:
            p.error(f"--hero-pool: not a comma-separated id list: {args.hero_pool!r}")
        n_ids = default_config().model.n_hero_ids
        bad = [h for h in hero_pool if not 0 <= h < n_ids]
        if bad:
            # out-of-range ids would silently alias via the embedding
            # gather's clamping semantics — refuse instead
            p.error(f"--hero-pool: ids must be in [0, {n_ids}): {bad}")
    else:
        hero_pool = (1,) if args.team_size == 1 else (1, 2, 3)
    def parse_overrides(flag: str, text: str, cls) -> dict:
        from dotaclient_tpu.utils.overrides import parse_dataclass_overrides

        try:
            return parse_dataclass_overrides(cls, text, flag)
        except ValueError as e:
            p.error(str(e))

    reward_over = (
        parse_overrides("--reward", args.reward, RewardConfig)
        if args.reward else {}
    )
    ppo_over = (
        parse_overrides("--ppo", args.ppo, PPOConfig) if args.ppo else {}
    )
    if args.league and args.opponent != "league":
        p.error("--league overrides need --opponent league")
    league_over = (
        parse_overrides("--league", args.league, LeagueConfig)
        if args.league else {}
    )
    if args.opponent == "league":
        league_over.setdefault("enabled", True)
    config = default_config()
    config = dataclasses.replace(
        config,
        reward=dataclasses.replace(config.reward, **reward_over),
        ppo=dataclasses.replace(config.ppo, **ppo_over),
        league=dataclasses.replace(config.league, **league_over),
        model=dataclasses.replace(
            config.model, core=args.core, moe_experts=args.moe_experts
        ),
        env=dataclasses.replace(
            config.env, n_envs=args.n_envs, opponent=args.opponent,
            max_dota_time=args.max_dota_time, team_size=args.team_size,
            hero_pool=hero_pool,
        ),
        buffer=dataclasses.replace(
            config.buffer, capacity_rollouts=512, min_fill=128
        ),
        # drain-free logging: a mid-block log boundary would reset the
        # windowed stats the demo prints (TensorBoard cadence only
        # matters when a logdir is given); --log-every overrides for
        # dense --metrics-jsonl curves (the outcome plane's demo path)
        log_every=(
            args.log_every
            if args.log_every is not None
            else (10_000 if args.logdir else 1_000_000_000)
        ),
        steps_per_dispatch=args.steps_per_dispatch,
        seed=args.seed,
    )
    learner = Learner(config, actor=args.actor, seed=args.seed,
                      logdir=args.logdir, checkpoint_dir=args.checkpoint_dir,
                      restore=args.restore, init_from=args.init_from,
                      metrics_jsonl=args.metrics_jsonl)
    policy = learner.policy
    # On --restore this snapshot is the RESTORED policy, not a step-0 init:
    # the "init" evals then baseline the transfer/resume starting point
    # (restored_step in the summary flags such runs; weights-only transfer
    # resets the counter, so report the restore as such).
    restored_step = int(learner.state.step) if args.restore else 0
    if args.init_from:
        restored_step = learner._init_from_step
    init_params = jax.tree.map(lambda x: x.copy(), learner.state.params)

    print(f"== eval: INITIAL policy (step {restored_step}) ==", flush=True)
    init_easy = evaluate(config, policy, init_params, "scripted_easy",
                         n_games=args.eval_games, seed=7)
    init_hard = evaluate(config, policy, init_params, "scripted_hard",
                         n_games=args.eval_games, seed=7)
    print(f"init vs easy: {init_easy}", flush=True)
    print(f"init vs hard: {init_hard}", flush=True)

    print(f"== train: {args.steps} optimizer steps vs {args.opponent} ==", flush=True)
    t0 = time.time()
    block = 1000
    curve = []
    done_steps = 0
    while done_steps < args.steps:
        n = min(block, args.steps - done_steps)
        learner.train(n)
        done_steps += n
        s = learner.device_actor.stats()
        curve.append(
            {
                "step": done_steps,
                "win_rate_recent": round(s["win_rate_recent"], 3),
                "ep_reward_recent": round(s["ep_reward_recent"], 3),
            }
        )
        print(
            f"[{time.time() - t0:7.1f}s] step {done_steps}: "
            f"win_rate_recent={s['win_rate_recent']:.3f} "
            f"ep_reward_recent={s['ep_reward_recent']:.2f} "
            f"episodes={s['episodes_done']:.0f}",
            flush=True,
        )

    trained = jax.tree.map(lambda x: x.copy(), learner.state.params)
    print("== eval: TRAINED policy ==", flush=True)
    final_easy = evaluate(config, policy, trained, "scripted_easy",
                          n_games=args.eval_games, seed=7)
    final_hard = evaluate(config, policy, trained, "scripted_hard",
                          n_games=args.eval_games, seed=7)
    vs_past = evaluate(config, policy, trained, "league",
                       opponent_params=init_params,
                       n_games=args.eval_games, seed=7)
    summary = {
        "steps": args.steps,
        "team_size": args.team_size,
        "core": args.core,
        "restored_step": restored_step,
        "frames": args.steps * config.ppo.rollout_len * (
            learner.device_actor.n_lanes
            if args.actor == "fused"
            else config.ppo.batch_rollouts
        ),
        "wall_sec": round(time.time() - t0, 1),
        "init_win_vs_easy": round(init_easy["win_rate"], 3),
        "init_win_vs_hard": round(init_hard["win_rate"], 3),
        "final_win_vs_easy": round(final_easy["win_rate"], 3),
        "final_win_vs_hard": round(final_hard["win_rate"], 3),
        "final_win_vs_initial_self": round(vs_past["win_rate"], 3),
        "reward_first_block": curve[0]["ep_reward_recent"] if curve else None,
        "reward_last_block": curve[-1]["ep_reward_recent"] if curve else None,
    }
    print("DEMO_SUMMARY " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
