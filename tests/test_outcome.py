"""Outcome attribution plane tests (ISSUE 15).

Covers: the episode-record schema and host recording, the in-graph
done-masked reductions pinned BITWISE against host-loop recording (the
PR 10/11 parity-digest pattern) and against the numpy-sim oracle in
lockstep, window_stats episode accounting across lane resets, outcome
counters riding the fleet snapshot frames (delta-merge across restarts,
priority-aware leaf cut), the OutcomeAggregator's windowed curves +
arming discipline, the outcome alert rules end to end through the
engine, the --require-outcome schema tier, the JSONL sink's
crash-mid-write torn-tail seal (bugfix sweep), the outcome_report
console, and the alert-drift rule-key extension.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from dotaclient_tpu.config import default_config
from dotaclient_tpu.outcome import (
    BUCKETS,
    N_LEN_BUCKETS,
    REWARD_TERMS,
    OutcomeAggregator,
    ensure_actor_metrics,
    len_bucket,
    opponent_bucket,
    record_episode,
)
from dotaclient_tpu.outcome.records import counter_totals
from dotaclient_tpu.utils import alerts, fleet, telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# records: schema + host recording


class TestRecords:
    def test_opponent_bucket_mapping(self):
        assert opponent_bucket("scripted_easy") == "vs_scripted"
        assert opponent_bucket("scripted_hard") == "vs_scripted"
        assert opponent_bucket("selfplay") == "vs_selfplay"
        assert opponent_bucket("league") == "vs_league"

    def test_len_bucket_convention(self):
        # [2^i, 2^(i+1)) buckets, clipped; degenerate lengths land in 0
        assert len_bucket(0) == 0
        assert len_bucket(1) == 0
        assert len_bucket(2) == 1
        assert len_bucket(3) == 1
        assert len_bucket(256) == 8
        assert len_bucket(10**9) == N_LEN_BUCKETS - 1

    def test_record_episode_counters(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        record_episode(reg, "vs_scripted", True, 150, side="radiant")
        record_episode(reg, "vs_scripted", False, 150, side="radiant")
        record_episode(reg, "vs_league", True, 3, side="dire")
        snap = reg.snapshot()
        assert snap["outcome/episodes/vs_scripted"] == 2.0
        assert snap["outcome/wins/vs_scripted"] == 1.0
        assert snap["outcome/episodes/vs_league"] == 1.0
        assert snap["outcome/wins/vs_league"] == 1.0
        assert snap["outcome/episodes_side/radiant"] == 2.0
        assert snap["outcome/episodes_side/dire"] == 1.0
        assert snap["outcome/ep_len_sum"] == 303.0
        assert snap["outcome/ep_len_hist/07"] == 2.0   # 150 ∈ [128, 256)
        assert snap["outcome/ep_len_hist/01"] == 1.0   # 3 ∈ [2, 4)

    def test_counter_totals_merges_fleet_mirrors(self):
        totals = counter_totals(
            {
                "outcome/episodes/vs_scripted": 3.0,
                "fleet/a0/outcome/episodes/vs_scripted": 5.0,
                "fleet/a1/outcome/episodes/vs_scripted": 2.0,
                "fleet/a0/actor/env_steps": 999.0,   # not an outcome key
                "buffer/ingested": 7.0,
            }
        )
        assert totals == {"outcome/episodes/vs_scripted": 10.0}


# ---------------------------------------------------------------------------
# in-graph reductions: the parity digests


class TestIngraphParity:
    def test_reductions_match_host_recording_bitwise(self):
        """The device-path reduction and host-loop recording must agree
        BITWISE on identical episode streams (counts are integers — any
        drift is a real bug, not float noise)."""
        import jax

        from dotaclient_tpu.outcome import ingraph

        rng = np.random.default_rng(0)
        T, N = 64, 16
        done = rng.random((T, N)) < 0.08
        win = rng.random((T, N)) < 0.5
        ep_len = np.where(done, rng.integers(1, 2000, size=(T, N)), 0)

        dev = jax.jit(ingraph.chunk_outcome_stats)(
            done, win, ep_len.astype(np.int32)
        )
        dev = jax.device_get(dev)

        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        for t in range(T):
            for n in range(N):
                if done[t, n]:
                    record_episode(
                        reg, "vs_scripted", bool(win[t, n]),
                        int(ep_len[t, n]),
                    )
        snap = reg.snapshot()
        assert float(dev["out_eps_vs_scripted"]) == snap[
            "outcome/episodes/vs_scripted"
        ]
        assert float(dev["out_wins_vs_scripted"]) == snap[
            "outcome/wins/vs_scripted"
        ]
        assert float(dev["out_ep_len_sum"]) == snap["outcome/ep_len_sum"]
        for i in range(N_LEN_BUCKETS):
            assert float(dev["out_ep_len_hist"][i]) == snap[
                f"outcome/ep_len_hist/{i:02d}"
            ], f"hist bucket {i}"

    def test_bucket_masks_by_mode(self):
        from dotaclient_tpu.outcome import ingraph

        m = ingraph.bucket_masks(4, "scripted_hard", 0)
        assert bool(np.all(np.asarray(m["vs_scripted"])))
        m = ingraph.bucket_masks(4, "selfplay", 0)
        assert bool(np.all(np.asarray(m["vs_selfplay"])))
        m = ingraph.bucket_masks(4, "league", 1)
        assert np.asarray(m["vs_scripted"]).tolist() == [
            True, False, False, False,
        ]
        assert np.asarray(m["vs_league"]).tolist() == [
            False, True, True, True,
        ]

    def test_sim_lockstep_outcome_parity(self):
        """Drive the numpy sim (the semantic oracle) and the JAX sim in
        lockstep to the timeout horizon (wave-free window, so zero RNG
        divergence): the in-graph reduction over the jax stream must
        match host-loop recording over the vec stream bitwise."""
        import jax
        import jax.numpy as jnp

        from dotaclient_tpu.envs.lane_sim import TEAM_RADIANT
        from dotaclient_tpu.outcome import ingraph
        from tests.test_jax_sim import make_pair, noop

        # 20 s horizon = 100 steps < the 140-step wave-free bound
        spec, vsim, jstate = make_pair(n=4, max_dota_time=20.0)

        import dotaclient_tpu.envs.jax_lane_sim as J

        step = jax.jit(lambda s, a: J.step(spec, s, a))
        acts = noop(4, 2)
        jacts = {k: jnp.asarray(v) for k, v in acts.items()}

        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        host_prev_done = np.zeros(4, bool)
        host_steps = np.zeros(4, np.int64)
        dev_done, dev_win, dev_len = [], [], []
        j_prev_done = np.zeros(4, bool)
        j_steps = np.zeros(4, np.int64)
        for _ in range(120):
            vsim.step(acts)
            jstate = step(jstate, jacts)
            # host side: the VecActorPool recording semantics
            host_steps += ~host_prev_done
            now_done = np.asarray(vsim.done) & ~host_prev_done
            for g in np.nonzero(now_done)[0]:
                record_episode(
                    reg, "vs_scripted",
                    int(vsim.winning_team[g]) == TEAM_RADIANT,
                    int(host_steps[g]),
                )
            host_prev_done |= now_done
            # device side: the DeviceActor scan-body semantics
            jd = np.asarray(jstate.done)
            new_done = jd & ~j_prev_done
            j_steps += ~j_prev_done
            dev_done.append(new_done)
            dev_win.append(
                new_done & (np.asarray(jstate.winning_team) == TEAM_RADIANT)
            )
            dev_len.append(np.where(new_done, j_steps, 0))
            j_prev_done |= new_done
        dev = jax.device_get(
            jax.jit(ingraph.chunk_outcome_stats)(
                jnp.asarray(np.stack(dev_done)),
                jnp.asarray(np.stack(dev_win)),
                jnp.asarray(np.stack(dev_len), jnp.int32),
            )
        )
        snap = reg.snapshot()
        assert snap["outcome/episodes/vs_scripted"] == 4.0
        assert float(dev["out_eps_vs_scripted"]) == snap[
            "outcome/episodes/vs_scripted"
        ]
        assert float(dev["out_wins_vs_scripted"]) == snap[
            "outcome/wins/vs_scripted"
        ]
        assert float(dev["out_ep_len_sum"]) == snap["outcome/ep_len_sum"]
        for i in range(N_LEN_BUCKETS):
            assert float(dev["out_ep_len_hist"][i]) == snap[
                f"outcome/ep_len_hist/{i:02d}"
            ]

    @pytest.mark.slow   # ~11s: 25 jitted collects + drain
    def test_device_actor_outcome_matches_legacy_counts(self):
        """The device actor's folded outcome counters must equal its own
        legacy episodes/wins accounting bitwise — two accounting paths,
        one truth."""
        import jax

        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy

        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(
                cfg.env, n_envs=4, max_dota_time=30.0
            ),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=8),
        )
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        reg = telemetry.Registry()
        da = DeviceActor(cfg, policy, seed=0, registry=reg)
        for _ in range(25):
            da.collect(params)
        da.drain_stats()
        assert da.episodes_done >= 4
        snap = reg.snapshot()
        assert snap["outcome/episodes/vs_scripted"] == float(
            da.episodes_done
        )
        assert snap["outcome/wins/vs_scripted"] == float(da.wins)
        hist_total = sum(
            snap[f"outcome/ep_len_hist/{i:02d}"]
            for i in range(N_LEN_BUCKETS)
        )
        assert hist_total == float(da.episodes_done)
        assert snap["outcome/episodes_side/radiant"] == float(
            da.episodes_done
        )


class TestLearnerIntegration:
    @pytest.mark.slow   # fused program compile dominates
    def test_fused_learner_outcome_counts(self):
        """Fused mode runs the same in-graph reductions INSIDE its one
        donated program; the end-of-call drain must fold them into the
        outcome counters, matching the legacy episode accounting."""
        from dotaclient_tpu.train.learner import Learner

        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(
                cfg.env, n_envs=8, opponent="scripted_easy",
                max_dota_time=30.0,
            ),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=8),
            log_every=1_000_000,
        )
        reg = telemetry.get_registry()
        base = dict(reg.counters_and_gauges()[0])
        lrn = Learner(cfg, actor="fused")
        try:
            lrn.train(40)
        finally:
            if lrn._snap_engine is not None:
                lrn._snap_engine.stop()
        now = reg.counters_and_gauges()[0]

        def delta(key):
            return now.get(key, 0.0) - base.get(key, 0.0)

        assert lrn.device_actor.episodes_done >= 2
        assert delta("outcome/episodes/vs_scripted") == float(
            lrn.device_actor.episodes_done
        )
        assert delta("outcome/wins/vs_scripted") == float(
            lrn.device_actor.wins
        )

    @pytest.mark.slow   # a real device-mode learner run with JSONL record
    def test_learner_device_outcome_curves_in_jsonl(self, tmp_path):
        """The acceptance shape: a short real run produces non-empty
        outcome curves in the learner JSONL and the --require-outcome
        tier validates it."""
        from dotaclient_tpu.train.learner import Learner

        schema = _script_module("check_telemetry_schema")
        path = str(tmp_path / "learner.jsonl")
        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(
                cfg.env, n_envs=8, opponent="scripted_easy",
                max_dota_time=30.0,
            ),
            ppo=dataclasses.replace(
                cfg.ppo, rollout_len=8, batch_rollouts=8
            ),
            buffer=dataclasses.replace(
                cfg.buffer, capacity_rollouts=32, min_fill=8
            ),
            log_every=4,
        )
        lrn = Learner(cfg, actor="device", metrics_jsonl=path)
        try:
            lrn.train(40)
        finally:
            if lrn._snap_engine is not None:
                lrn._snap_engine.stop()
        lines = telemetry.load_jsonl(path)
        errs = schema.validate_lines(
            lines, extra_required=schema.OUTCOME_KEYS
        )
        assert errs == []
        report = _script_module("outcome_report")
        points, union, last_ts = report.parse_stream(lines)
        _text, status = report.render(points, union, last_ts, 40)
        assert status["ok"] is True
        assert status["episodes_total"] >= 8
        assert status["curve_points"] >= 1
        assert status["buckets"]["vs_scripted"]["episodes"] >= 8


# ---------------------------------------------------------------------------
# window stats: episode accounting across lane resets (host pools)


class TestWindowStatsAccounting:
    def _pool(self, n_envs=2):
        import jax

        from dotaclient_tpu.actor.vec_runtime import VecActorPool
        from dotaclient_tpu.models import init_params, make_policy

        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            model=dataclasses.replace(cfg.model, dtype="float32"),
            env=dataclasses.replace(
                cfg.env, n_envs=n_envs, max_dota_time=15.0
            ),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=8),
        )
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        sink = []
        return VecActorPool(
            cfg, policy, params, seed=0, rollout_sink=sink.extend
        )

    def test_vec_pool_outcome_across_resets(self):
        """Episodes spanning multiple resets: the outcome counters, the
        legacy counters, and the windowed drain must all agree — and the
        per-game step accounting must restart at each reset (the
        histogram total equals the episode count; lengths stay in the
        horizon's bucket instead of accumulating across episodes)."""
        pool = self._pool()
        reg = telemetry.get_registry()
        base = dict(reg.counters_and_gauges()[0])

        def delta(key):
            now = reg.counters_and_gauges()[0].get(key, 0.0)
            return now - base.get(key, 0.0)

        # window 1: at least one full episode per env
        steps = 0
        while pool.episodes_done < 2 and steps < 400:
            pool.step()
            steps += 1
        w1 = pool.drain_stats()
        assert w1["episodes_recent"] == float(pool.episodes_done)
        eps_after_w1 = pool.episodes_done
        # window 2: more episodes AFTER the resets
        steps = 0
        while pool.episodes_done < eps_after_w1 + 2 and steps < 400:
            pool.step()
            steps += 1
        w2 = pool.drain_stats()
        assert w2["episodes_recent"] == float(
            pool.episodes_done - eps_after_w1
        )
        assert delta("outcome/episodes/vs_scripted") == float(
            pool.episodes_done
        )
        assert delta("outcome/wins/vs_scripted") == float(pool.wins)
        # 15 s horizon = 75 env steps → bucket 6 ([64,128)); a counter
        # leaking across resets would land episodes in higher buckets
        hist = [
            delta(f"outcome/ep_len_hist/{i:02d}")
            for i in range(N_LEN_BUCKETS)
        ]
        assert sum(hist) == float(pool.episodes_done)
        assert hist[6] == float(pool.episodes_done)
        # every episode ran to the SAME timeout horizon (~76 env steps at
        # 15 s / 0.2 s-per-step): a per-game counter leaking across
        # resets would inflate later episodes' lengths
        mean_len = delta("outcome/ep_len_sum") / pool.episodes_done
        assert 64.0 <= mean_len < 128.0
        # identical horizons ⇒ identical lengths: the sum divides evenly
        assert delta("outcome/ep_len_sum") % pool.episodes_done == 0.0

    def test_reward_terms_accumulate(self):
        pool = self._pool()
        reg = telemetry.get_registry()
        base = dict(reg.counters_and_gauges()[0])
        for _ in range(30):
            pool.step()
        now = reg.counters_and_gauges()[0]
        moved = [
            t for t in REWARD_TERMS
            if now.get(f"outcome/reward_sum/{t}", 0.0)
            != base.get(f"outcome/reward_sum/{t}", 0.0)
        ]
        assert moved, "no reward term ever accumulated"

    def test_mixin_records_through_registry(self):
        from dotaclient_tpu.actor.window_stats import WindowedStatsMixin

        class FakePool(WindowedStatsMixin):
            episodes_done = 0
            wins = 0
            episode_rewards: list = []

            def stats(self):
                return self.windowed_entries()

        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        pool = FakePool()
        pool.record_episode_outcome(
            "vs_selfplay", True, 9, side="dire", registry=reg
        )
        snap = reg.snapshot()
        assert snap["outcome/episodes/vs_selfplay"] == 1.0
        assert snap["outcome/wins/vs_selfplay"] == 1.0
        assert snap["outcome/ep_len_hist/03"] == 1.0   # 9 ∈ [8, 16)


# ---------------------------------------------------------------------------
# transport: outcome counters inside fleet snapshot frames


class TestFleetTransport:
    def test_snapshot_ships_outcome_counters(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        record_episode(reg, "vs_scripted", True, 100)
        counters, gauges = reg.counters_and_gauges()
        payload = fleet.encode_snapshot(0, "actor", 0, counters, gauges)
        snap = fleet.decode_snapshot(payload)
        assert snap is not None
        assert snap["counters"]["outcome/episodes/vs_scripted"] == 1.0
        assert snap["counters"]["outcome/wins/vs_scripted"] == 1.0

    def test_cut_priority_protects_operational_keys(self):
        """Over the leaf cap, outcome histogram buckets drop FIRST and
        operational keys (alert rule sources) survive — alphabetical
        truncation would have silently blinded transport/* rules."""
        counters = {f"outcome/ep_len_hist/{i:02d}": float(i) for i in range(12)}
        counters.update(
            {f"outcome/reward_sum/fake_{i:02d}": 1.0 for i in range(70)}
        )
        counters["transport/reconnects_total"] = 7.0
        counters["trace/dropped_total"] = 1.0
        gauges = {"actor/weight_refresh_lag": 2.0}
        payload = fleet.encode_snapshot(3, "actor", 1, counters, gauges)
        snap = fleet.decode_snapshot(payload)
        assert snap["counters"]["transport/reconnects_total"] == 7.0
        assert snap["counters"]["trace/dropped_total"] == 1.0
        assert snap["gauges"]["actor/weight_refresh_lag"] == 2.0
        # the overflow was absorbed by the outcome namespace, hist first
        assert not any(
            k.startswith("outcome/ep_len_hist/")
            for k in snap["counters"]
        )

    def test_delta_merge_across_restart_no_double_count(self):
        """A supervisor-restarted actor re-counts its episodes from zero;
        the per-peer delta merge must add, never re-add."""
        reg = telemetry.Registry()
        agg = fleet.FleetAggregator(registry=reg, interval_s=0.05)
        c1 = {"outcome/episodes/vs_scripted": 5.0}
        agg.ingest(fleet.encode_snapshot(0, "actor", 0, c1, {}, pid=111))
        agg.tick(now=0.0)
        c2 = {"outcome/episodes/vs_scripted": 2.0}   # fresh pid, from zero
        agg.ingest(fleet.encode_snapshot(0, "actor", 0, c2, {}, pid=222))
        agg.tick(now=1.0)
        counters, _ = reg.counters_and_gauges()
        assert counters["fleet/a0/outcome/episodes/vs_scripted"] == 7.0
        totals = counter_totals(counters)
        assert totals["outcome/episodes/vs_scripted"] == 7.0


# ---------------------------------------------------------------------------
# the aggregator: windowed curves, arming, alerts


class TestOutcomeAggregator:
    def test_eager_keys_and_priors(self):
        reg = telemetry.Registry()
        OutcomeAggregator(registry=reg)
        snap = reg.snapshot()
        assert snap["outcome/win_rate/vs_scripted"] == 0.5
        assert snap["outcome/win_rate/vs_league"] == 0.5
        assert snap["outcome/win_rate/overall"] == 0.5
        assert snap["outcome/stream_age_s"] == -1.0
        assert snap["outcome/episode_len_anomaly"] == 0.0
        for term in REWARD_TERMS:
            assert f"outcome/reward/{term}" in snap

    def test_windowed_win_rate_and_stream_age(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, window_s=60.0, min_episodes=4)
        agg.tick(now=0.0)
        assert reg.snapshot()["outcome/stream_age_s"] == -1.0   # unarmed
        for i in range(4):
            record_episode(reg, "vs_scripted", i < 3, 150)
        agg.tick(now=1.0)
        snap = reg.snapshot()
        assert snap["outcome/win_rate/vs_scripted"] == 0.75
        assert snap["outcome/win_rate/overall"] == 0.75
        assert snap["outcome/win_rate/vs_league"] == 0.5   # prior holds
        assert snap["outcome/episodes_total"] == 4.0
        assert snap["outcome/stream_age_s"] == 0.0
        assert snap["outcome/episode_len_p50"] == 256.0   # 150's bucket bound
        # silence: the age grows on wall clock, the rates HOLD
        agg.tick(now=50.0)
        snap = reg.snapshot()
        assert snap["outcome/stream_age_s"] == 49.0
        assert snap["outcome/win_rate/vs_scripted"] == 0.75

    def test_window_expiry_drops_old_episodes(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, window_s=10.0, min_episodes=2)
        for _ in range(4):
            record_episode(reg, "vs_scripted", True, 100)
        agg.tick(now=0.0)
        agg.tick(now=1.0)
        for _ in range(2):
            record_episode(reg, "vs_scripted", False, 100)
        agg.tick(now=20.0)   # the t=0/1 samples age out of the window
        snap = reg.snapshot()
        assert snap["outcome/episodes_recent"] == 2.0
        assert snap["outcome/win_rate/vs_scripted"] == 0.0

    def test_anomaly_binary(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, min_episodes=2)
        agg.tick(now=0.0)
        for _ in range(4):
            record_episode(reg, "vs_scripted", False, 1)   # instant resets
        agg.tick(now=1.0)
        snap = reg.snapshot()
        assert snap["outcome/episode_len_p50"] == 2.0
        assert snap["outcome/episode_len_anomaly"] == 1.0
        for _ in range(12):
            record_episode(reg, "vs_scripted", False, 100)
        agg.tick(now=2.0)
        assert reg.snapshot()["outcome/episode_len_anomaly"] == 0.0

    def test_reward_term_means(self):
        from dotaclient_tpu.outcome.records import add_reward_terms

        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, min_episodes=1)
        agg.tick(now=0.0)
        for _ in range(2):
            record_episode(reg, "vs_scripted", True, 10)
        add_reward_terms(reg, {"gold": 6.0, "win": 10.0})
        agg.tick(now=1.0)
        snap = reg.snapshot()
        assert snap["outcome/reward/gold"] == 3.0
        assert snap["outcome/reward/win"] == 5.0
        assert snap["outcome/reward/xp"] == 0.0

    def _outcome_rules(self):
        return tuple(
            r for r in alerts.RULES
            if r.name in (
                "win_rate_collapse", "episode_len_anomaly",
                "outcome_stream_stale",
            )
        )

    def test_stream_stale_alert_fires_and_resolves(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, min_episodes=1)
        engine = alerts.AlertEngine(
            rules=self._outcome_rules(), registry=reg
        )

        def evaluate(now):
            counters, gauges = reg.counters_and_gauges()
            return engine.evaluate({**counters, **gauges}, now)

        # unarmed: silence forever must NOT fire (age = -1)
        agg.tick(now=0.0)
        fired, _ = evaluate(1000.0)
        assert "outcome_stream_stale" not in fired
        # armed, then silent past the threshold: fires
        record_episode(reg, "vs_scripted", True, 100)
        agg.tick(now=1000.0)
        evaluate(1000.0)
        agg.tick(now=1100.0)
        fired, _ = evaluate(1100.0)
        assert "outcome_stream_stale" in fired
        # a fresh episode resolves
        record_episode(reg, "vs_scripted", True, 100)
        agg.tick(now=1101.0)
        _, resolved = evaluate(1101.0)
        assert "outcome_stream_stale" in resolved

    def test_win_rate_collapse_alert(self):
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(
            registry=reg, window_s=1000.0, min_episodes=8
        )
        engine = alerts.AlertEngine(
            rules=self._outcome_rules(), registry=reg
        )

        def evaluate(now):
            counters, gauges = reg.counters_and_gauges()
            return engine.evaluate({**counters, **gauges}, now)

        # no scripted games ever: the 0.5 prior can never collapse
        agg.tick(now=0.0)
        evaluate(0.0)
        fired, _ = evaluate(500.0)
        assert fired == []
        # 8 losses: condition true, debounced 120 s, then fires
        for _ in range(8):
            record_episode(reg, "vs_scripted", False, 100)
        agg.tick(now=501.0)
        fired, _ = evaluate(501.0)
        assert fired == []   # debounce holding
        fired, _ = evaluate(622.0)
        assert "win_rate_collapse" in fired


# ---------------------------------------------------------------------------
# schema tier + consoles


class TestSchemaAndConsoles:
    def test_require_outcome_round_trip(self):
        schema = _script_module("check_telemetry_schema")
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        OutcomeAggregator(registry=reg)
        scalars = dict(reg.snapshot())
        line = json.dumps({"ts": 1.0, "step": 0, "scalars": scalars})
        errs = schema.validate_lines(
            [line], extra_required=schema.OUTCOME_KEYS, base_required=()
        )
        assert errs == []
        scalars.pop("outcome/win_rate/vs_scripted")
        line = json.dumps({"ts": 1.0, "step": 0, "scalars": scalars})
        errs = schema.validate_lines(
            [line], extra_required=schema.OUTCOME_KEYS, base_required=()
        )
        assert any("outcome/win_rate/vs_scripted" in e for e in errs)

    def test_outcome_keys_all_eager(self):
        """Every OUTCOME_KEYS tier entry must exist after nothing more
        than learner-construction-time calls (the --require-outcome
        determinism contract)."""
        schema = _script_module("check_telemetry_schema")
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        OutcomeAggregator(registry=reg)
        snap = reg.snapshot()
        missing = [k for k in schema.OUTCOME_KEYS if k not in snap]
        assert missing == []

    def _canned_jsonl(self, tmp_path, with_outcome=True):
        path = tmp_path / "learner.jsonl"
        reg = telemetry.Registry()
        ensure_actor_metrics(reg)
        agg = OutcomeAggregator(registry=reg, min_episodes=2)
        lines = []
        if with_outcome:
            agg.tick(now=0.0)
            for i in range(6):
                record_episode(reg, "vs_scripted", i % 2 == 0, 150)
            agg.tick(now=1.0)
        sc = dict(reg.snapshot())
        # an external peer's mirrored counters ride the same stream
        sc["fleet/a7/outcome/episodes/vs_scripted"] = 4.0
        sc["fleet/a7/outcome/wins/vs_scripted"] = 1.0
        lines.append(json.dumps({"ts": 1.0, "step": 10, "scalars": sc}))
        path.write_text("\n".join(lines) + "\n")
        return str(path)

    def test_outcome_report_on_canned_jsonl(self, tmp_path, capsys):
        report = _script_module("outcome_report")
        rc = report.main([self._canned_jsonl(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        status_line = [
            l for l in out.splitlines() if l.startswith("OUTCOME_STATUS ")
        ][0]
        status = json.loads(status_line[len("OUTCOME_STATUS "):])
        assert status["ok"] is True
        # local 6 + mirrored 4
        assert status["buckets"]["vs_scripted"]["episodes"] == 10.0
        assert status["buckets"]["vs_scripted"]["wins"] == 4.0
        assert status["win_rate_vs_scripted"] == 0.5
        assert "win-rate curves" in out

    def test_outcome_report_empty_stream(self, tmp_path, capsys):
        report = _script_module("outcome_report")
        rc = report.main([self._canned_jsonl(tmp_path, with_outcome=False)])
        out = capsys.readouterr().out
        assert rc == 1
        status = json.loads(
            [
                l for l in out.splitlines()
                if l.startswith("OUTCOME_STATUS ")
            ][0][len("OUTCOME_STATUS "):]
        )
        assert status["ok"] is False

    def test_fleet_status_outcome_panel(self, tmp_path, capsys):
        fs = _script_module("fleet_status")
        path = self._canned_jsonl(tmp_path)
        rc = fs.main([path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "outcome: win_rate vs_scripted" in out
        line = [
            l for l in out.splitlines() if l.startswith("FLEET_STATUS ")
        ][0]
        summary = json.loads(line[len("FLEET_STATUS "):])
        assert summary["outcome"]["episodes_total"] == 6
        assert summary["outcome"]["win_rate_vs_scripted"] == 0.5


# ---------------------------------------------------------------------------
# JSONL sink: crash-mid-write bugfix sweep


class TestJsonlTornTail:
    def test_sink_seals_torn_tail_before_appending(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "step": 0, "scalars": {}}) + "\n")
            f.write('{"ts": 2.0, "step": 1, "scal')   # SIGKILL mid-write
        sink = telemetry.JsonlSink(path)
        sink.emit(2, {"a": 1.0})
        sink.close()
        lines = telemetry.load_jsonl(path)
        parsed = [json.loads(l) for l in lines]   # every line must parse
        assert [p["step"] for p in parsed] == [0, 2]

    def test_sink_append_to_clean_file_unchanged(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"ts": 1.0, "step": 0, "scalars": {}}) + "\n")
        sink = telemetry.JsonlSink(path)
        sink.emit(1, {})
        sink.close()
        assert len(telemetry.load_jsonl(path)) == 2

    def test_load_jsonl_tolerates_torn_utf8(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        good = json.dumps({"ts": 1.0, "step": 0, "scalars": {}}) + "\n"
        with open(path, "wb") as f:
            f.write(good.encode())
            f.write('{"x": "é'.encode()[:-1])   # cut mid-codepoint
        lines = telemetry.load_jsonl(path)   # must not raise
        assert len(lines) == 1
        assert json.loads(lines[0])["step"] == 0

    def test_seal_whole_file_fragment(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write('{"torn')   # the only content is the fragment
        sink = telemetry.JsonlSink(path)
        sink.emit(5, {})
        sink.close()
        lines = telemetry.load_jsonl(path)
        assert len(lines) == 1
        assert json.loads(lines[0])["step"] == 5


# ---------------------------------------------------------------------------
# alert-drift extension: rule keys must be emitted


class TestAlertDriftRuleKeys:
    def test_ghost_key_flags(self):
        from dotaclient_tpu.lint.alert_drift import rule_key_findings

        rules = [
            {"name": "ok_rule", "runbook": "rb:x", "line": 1,
             "key": "outcome/stream_age_s"},
            {"name": "ghost", "runbook": "rb:y", "line": 2,
             "key": "outcome/never_emitted_key"},
            {"name": "pattern", "runbook": "rb:z", "line": 3,
             "key": "fleet/*/serve/p99_latency_ms"},
        ]
        findings = rule_key_findings(
            rules, {"outcome/stream_age_s"}
        )
        assert len(findings) == 1
        assert findings[0].context == "outcome/never_emitted_key"

    def test_shipped_rules_keys_emitted_on_head(self):
        """Every shipped rule's key resolves against the real extraction
        — the lint pass's clean-on-HEAD guarantee, pinned directly."""
        import ast as ast_mod

        from dotaclient_tpu.lint.alert_drift import (
            extract_rules,
            rule_key_findings,
        )
        from dotaclient_tpu.lint.core import FileCtx, package_py_files
        from dotaclient_tpu.lint.telemetry_drift import extract_emitted

        files = {}
        for rel in package_py_files():
            with open(os.path.join(_REPO, rel)) as f:
                src = f.read()
            files[rel] = FileCtx(rel, src)
        emitted, _, _ = extract_emitted(files)
        with open(
            os.path.join(_REPO, "dotaclient_tpu", "utils", "alerts.py")
        ) as f:
            rules, _ = extract_rules(ast_mod.parse(f.read()))
        assert rule_key_findings(rules, emitted) == []
