"""Learner-loop + checkpoint/resume tests (SURVEY.md §5.4, §7 e2e slice)."""

import dataclasses

import numpy as np
import jax
import pytest

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.models import init_params, make_policy
from dotaclient_tpu.train.learner import Learner
from dotaclient_tpu.train.ppo import init_train_state
from dotaclient_tpu.utils.checkpoint import CheckpointManager


def tiny_config() -> RunConfig:
    cfg = RunConfig()
    return dataclasses.replace(
        cfg,
        env=dataclasses.replace(cfg.env, n_envs=2, max_dota_time=30.0),
        ppo=dataclasses.replace(cfg.ppo, rollout_len=8, batch_rollouts=8),
        buffer=dataclasses.replace(cfg.buffer, capacity_rollouts=32, min_fill=8),
        log_every=1000,  # silence console in tests
        checkpoint_every=1000,
    )


class TestLearnerLoop:
    def test_trains_and_publishes_weights(self):
        learner = Learner(tiny_config())
        stats = learner.train(3)
        assert stats["optimizer_steps"] == 3
        assert stats["frames_trained"] == 3 * 8 * 8
        assert int(learner.state.step) == 3
        # final weights published for out-of-process actors
        msg = learner.transport.latest_weights()
        assert msg is not None and msg.version == 3
        # in-process pool got refreshed along the way
        assert learner.pool.version >= 2


class TestMinibatchEpochs:
    def test_minibatched_multi_epoch_training(self):
        """epochs_per_batch × minibatches shuffled slices per consumed
        batch — the standard PPO regime; counters advance per optimizer
        step (one per minibatch)."""
        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            ppo=dataclasses.replace(
                cfg.ppo, epochs_per_batch=2, minibatches=2, batch_rollouts=16
            ),
            log_every=4,   # a boundary fires within the run → loss captured
        )
        learner = Learner(cfg)
        stats = learner.train(4)   # one consumed batch = 4 optimizer steps
        assert stats["optimizer_steps"] == 4
        assert int(learner.state.step) == 4
        assert "loss" in stats and np.isfinite(stats["loss"])
        # frames count unique experience: one batch consumed
        assert stats["frames_trained"] == 16 * 8

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~110s on the reference container
    def test_minibatch_resume_reproduces_metrics(self, tmp_path):
        """The shuffle-stream position is checkpointed: a resumed learner
        replays the SAME upcoming permutations as the original's
        continuation (rel-tol: resumed state crosses a save/restore
        round-trip)."""
        from dotaclient_tpu.utils.checkpoint import CheckpointManager

        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            ppo=dataclasses.replace(
                cfg.ppo, epochs_per_batch=1, minibatches=2, batch_rollouts=16
            ),
            log_every=1,
        )
        ckdir = str(tmp_path / "ck")
        a = Learner(cfg, seed=4, actor="device")
        a.train(2)
        mgr = CheckpointManager(ckdir)
        mgr.save(a.state, cfg, force=True, pipeline=a._pipeline_state())
        mgr.wait()
        a.train(2)
        b = Learner(cfg, checkpoint_dir=ckdir, restore=True, actor="device")
        assert b._mb_draws == a._mb_draws - 1  # one batch consumed post-save
        b.train(2)
        for k in ("loss", "policy_loss", "entropy"):
            assert a._last_metrics[k] == pytest.approx(
                b._last_metrics[k], rel=1e-5
            ), f"{k} diverged after minibatch resume"

    def test_indivisible_minibatches_rejected(self):
        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=3)
        )
        with pytest.raises(ValueError, match="divisible"):
            Learner(cfg)


class TestFusedEpochStep:
    def multi_cfg(self, fused: bool) -> "RunConfig":
        cfg = tiny_config()
        return dataclasses.replace(
            cfg,
            ppo=dataclasses.replace(
                cfg.ppo, epochs_per_batch=2, minibatches=2,
                batch_rollouts=16, fused_epoch=fused,
            ),
        )

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~38s on the reference container
    def test_one_dispatch_per_batch(self):
        """The acceptance contract: with minibatches > 1, one consumed
        batch issues exactly ONE donated dispatch (the fused epoch step) —
        not epochs × minibatches gather+step pairs."""
        learner = Learner(self.multi_cfg(fused=True), actor="device")
        assert learner.epoch_step is not None
        calls = {"epoch": 0, "staged": 0, "gather": 0}
        real_epoch = learner.epoch_step
        learner.epoch_step = lambda *a: (calls.__setitem__(
            "epoch", calls["epoch"] + 1) or real_epoch(*a))
        learner.train_step = lambda *a: calls.__setitem__(
            "staged", calls["staged"] + 1)
        learner._minibatch_gather = lambda *a: calls.__setitem__(
            "gather", calls["gather"] + 1)
        learner.train(4)   # one consumed batch = 2 epochs × 2 minibatches
        assert calls == {"epoch": 1, "staged": 0, "gather": 0}

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~46s on the reference container
    def test_fused_epoch_off_uses_staged_path(self):
        learner = Learner(self.multi_cfg(fused=False), actor="device")
        assert learner.epoch_step is None
        stats = learner.train(4)
        assert stats["optimizer_steps"] == 4
        assert int(learner.state.step) == 4

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~64s on the reference container
    def test_fused_matches_staged_in_learner(self):
        """End-to-end parity: identical seeds and experience, fused epoch
        vs staged loop — same permutation stream, same final params (to
        the float-ulp XLA-fusion bound of the unit parity test in
        tests/test_train.py)."""
        a = Learner(self.multi_cfg(fused=True), seed=3, actor="device")
        b = Learner(self.multi_cfg(fused=False), seed=3, actor="device")
        a.train(4)
        b.train(4)
        assert a._mb_draws == b._mb_draws == 2
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-7
            ),
            a.state.params,
            b.state.params,
        )


class TestPrefetchLane:
    def surplus_cfg(self) -> "RunConfig":
        # device actor produces 8 rollouts per collect (n_lanes == n_envs
        # vs a scripted bot); batch of 8 with min_fill 16 leaves one whole
        # batch in the ring after the first take — the prefetch lane has
        # something to stage behind the dispatch
        cfg = tiny_config()
        return dataclasses.replace(
            cfg,
            env=dataclasses.replace(cfg.env, n_envs=8),
            ppo=dataclasses.replace(cfg.ppo, batch_rollouts=8),
            buffer=dataclasses.replace(
                cfg.buffer, capacity_rollouts=32, min_fill=16
            ),
        )

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~66s on the reference container
    def test_prefetch_hits_and_gauges(self):
        learner = Learner(self.surplus_cfg(), actor="device")
        learner.train(6)
        assert learner._prefetch_hits >= 1
        learner._publish_pipeline_gauges()
        snap = learner.telemetry.snapshot()
        assert 0.0 < snap["learner/prefetch_hit_rate"] <= 1.0
        assert 0.0 <= snap["learner/overlap_fraction"] <= 1.0
        assert snap["span/learner/prefetch/count"] >= 1

    def test_end_of_run_leaves_clean_lane_and_flush_restores_ring(self):
        """train() never ends with a held batch (the loop skips staging
        behind the final dispatch), and _flush_prefetch returns a staged
        batch's rows to the FRONT of the ring — prefetching can never turn
        into experience loss."""
        learner = Learner(self.surplus_cfg(), actor="device")
        learner.train(1)
        assert learner._prefetched is None
        assert learner.buffer._held == {}
        size_after = learner.buffer.size
        # stage a batch by hand, then flush: ring restored, and the next
        # take re-serves the SAME rows
        learner._prefetch_next(drain_transport=False)
        if learner._prefetched is None:
            pytest.skip("ring underfilled — nothing prefetched to flush")
        staged = np.asarray(learner._prefetched["rewards"])
        learner._flush_prefetch()
        assert learner._prefetched is None
        assert learner.buffer._held == {}
        assert learner.buffer.size == size_after
        again = learner.buffer.take(current_version=learner._host_version)
        np.testing.assert_array_equal(staged, np.asarray(again["rewards"]))

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~49s on the reference container
    def test_pipeline_checkpoint_includes_flushed_prefetch(self, tmp_path):
        """_pipeline_state folds an in-flight prefetched batch back into
        the buffer snapshot — a restore sees every unconsumed rollout."""
        learner = Learner(self.surplus_cfg(), actor="device")
        learner.train(2)
        # force a live prefetched batch, then snapshot
        chunk, _ = learner.device_actor.collect(learner.state.params)
        learner.buffer.add_device(chunk, learner._host_version)
        learner._prefetch_next(drain_transport=False)
        if learner._prefetched is None:
            pytest.skip("ring underfilled — nothing prefetched to flush")
        held_before = dict(learner.buffer._held)
        assert held_before
        state = learner._pipeline_state()
        assert learner._prefetched is None
        assert learner.buffer._held == {}
        order = [int(s) for s in state["buffer"]["order"] if s >= 0]
        for slots in held_before.values():
            for s in slots:
                assert s in order


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        cfg = tiny_config()
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        state = init_train_state(params, cfg.ppo)
        state = dataclasses.replace(
            state,
            step=jax.numpy.asarray(7, jax.numpy.int32),
            version=jax.numpy.asarray(7, jax.numpy.int32),
        )
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        assert mgr.save(state, cfg, force=True)
        mgr.wait()
        assert mgr.latest_step() == 7

        restored, rcfg = mgr.restore(cfg)
        assert int(restored.step) == 7
        assert int(restored.version) == 7
        assert rcfg.ppo.rollout_len == cfg.ppo.rollout_len
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            restored.params,
            state.params,
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            restored.opt_state,
            state.opt_state,
        )
        mgr.close()

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~42s on the reference container
    def test_learner_resume_continues_step_count(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        cfg = tiny_config()
        learner = Learner(cfg, checkpoint_dir=ckpt_dir)
        learner.train(2)
        learner.ckpt.wait()
        assert learner.ckpt.latest_step() == 2

        resumed = Learner(cfg, checkpoint_dir=ckpt_dir, restore=True)
        assert int(resumed.state.step) == 2
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            resumed.state.params,
            learner.state.params,
        )
        resumed.train(1)
        assert int(resumed.state.step) == 3


class TestFusedLoopSpans:
    """ISSUE 24: the fused loop names its host stretches (timers
    ``span/learner/*``, events of a profiler trace) and counts its
    dispatches, its log boundaries and the dispatches whose opponent was a
    frozen snapshot."""

    BOUNDARY_CHILDREN = (
        "flush_health", "league_fetch", "gauges", "stats_drain",
        "submit_metrics",
    )

    @pytest.fixture(scope="class")
    def run(self):
        """One fused ``train(4)`` over two log boundaries against league
        opponents → (what the registry gained, the draws, train's result).
        The registry is the process's, so everything is a delta."""
        from dotaclient_tpu.utils import telemetry

        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(cfg.env, opponent="league"),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=4),
            league=dataclasses.replace(
                cfg.league, enabled=True, snapshot_every=1, pool_size=2,
                selfplay_prob=0.0,        # a frozen draw whenever one exists
            ),
            log_every=2,
        )
        learner = Learner(cfg, actor="fused", seed=2)
        draws = []
        draw = learner._league_opponent

        def recorded_draw():
            params, uid = draw()
            draws.append(uid)
            return params, uid

        learner._league_opponent = recorded_draw
        reg = telemetry.get_registry()
        before = reg.snapshot()
        out = learner.train(4)
        after = reg.snapshot()
        gained = {k: v - before.get(k, 0.0) for k, v in after.items()}
        return gained, draws, out

    def test_dispatches_and_boundaries_are_counted(self, run):
        gained, draws, out = run
        assert out["optimizer_steps"] == 4.0 and len(draws) == 4
        assert gained["learner/dispatches_total"] == 4
        assert gained["learner/boundaries_total"] == 2       # steps 2 and 4
        assert gained["span/learner/boundary/count"] == 2
        for stage in ("iteration", "league_draw", "dispatch", "league_report"):
            assert gained[f"span/learner/{stage}/count"] == 4, stage
        for child in self.BOUNDARY_CHILDREN:
            assert gained[f"span/learner/boundary/{child}/count"] == 2, child
        # the snapshot thread's own fetches of the boundaries' stats
        assert gained["span/snapshot/stats_fetch/count"] >= 2

    def test_frozen_dispatches_follow_the_draw(self, run):
        from dotaclient_tpu.league import pool as league_pool

        gained, draws, _ = run
        frozen = sum(uid != league_pool.LIVE for uid in draws)
        assert frozen >= 1
        assert gained["league/frozen_dispatches_total"] == frozen
        # their outcomes were fetched, at a boundary or as the call ended
        assert gained["league/report_fetches_total"] >= 1
        # the LSTM's states are kilobytes: ONE undonated program, whose
        # opponent is an argument, so every rollout step is two passes
        assert gained.get("league/shared_pass_dispatches_total", 0.0) == 0

    def test_shared_pass_dispatches_are_the_donated_program_s_live_ones(self, monkeypatch):
        """ISSUE 33: where the states are donated the live opponent is a
        program of its own whose rollout KNOWS that both teams play one set
        of parameters and steps them in one pass: the counter moves by the
        live dispatches, not by the frozen ones."""
        from dotaclient_tpu.league import pool as league_pool
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.utils import telemetry

        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)   # toy states as "most of the chip"
        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(cfg.env, opponent="league"),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=4),
            league=dataclasses.replace(
                cfg.league, enabled=True, snapshot_every=1, pool_size=2, selfplay_prob=0.5, opponent_hold=1,
            ),
        )
        learner = Learner(cfg, actor="fused", seed=2)
        assert learner.fused_step.donate and learner.fused_step.live_shares_pass
        draws, draw = [], learner._league_opponent

        def recorded_draw():
            params, uid = draw()
            draws.append(uid)
            return params, uid

        learner._league_opponent = recorded_draw
        reg = telemetry.get_registry()
        before = reg.snapshot()
        out = learner.train(8)
        gained = {k: v - before.get(k, 0.0) for k, v in reg.snapshot().items()}
        live = sum(uid == league_pool.LIVE for uid in draws)
        assert out["optimizer_steps"] == 8.0 and 0 < live < len(draws) == 8
        assert gained["league/shared_pass_dispatches_total"] == live
        assert gained["league/frozen_dispatches_total"] == 8 - live

    def test_a_child_span_lies_inside_its_parent(self, run):
        gained, _, _ = run
        assert sum(
            gained[f"span/learner/boundary/{c}/total_s"]
            for c in self.BOUNDARY_CHILDREN
        ) <= gained["span/learner/boundary/total_s"]
        assert sum(
            gained[f"span/learner/{c}/total_s"]
            for c in ("boundary", "dispatch", "league_draw", "league_report")
        ) <= gained["span/learner/iteration/total_s"]
