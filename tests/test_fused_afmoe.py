"""The fused program's donation, and the fused path with a core whose carry
is attention caches (ISSUE 26; beside ``tests/test_fused.py``, a file of its
own so that the workers can share the two)."""

import dataclasses

import jax
import numpy as np
import pytest

from tests.test_fused import tiny_cfg


def afmoe_cfg(**league):
    """Two afmoe layers (dense with window, experts with full attention) at
    toy widths, league self-play, episodes of 20 steps in a full ring of 24."""
    cfg = tiny_cfg(opponent="league")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model, core="afmoe", hidden_dim=32, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=8, context_window=8, full_context=24,
            rollout_chunk=4, global_attn_every=2, n_dense_layers=1,
            dense_ffn_dim=48, expert_ffn_dim=16, moe_experts=8,
            experts_per_token=2, held_experts=4, dtype="float32",
        ),
        env=dataclasses.replace(cfg.env, max_dota_time=3.8),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=2, pool_size=1,
            **{"selfplay_prob": 0.5, **league},
        ),
    )


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


class TestFusedDonation:
    def test_afmoe_core_trains_through_the_fused_path(self, monkeypatch):
        """The learner's normal path with the cache-carrying core: finite,
        the parameters move, both of the first dispatch's arguments are
        donated, and what was copied before it (the league's snapshot, a
        weights publish) survives."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner

        # toy states are kilobytes: say that they are "most of the chip"
        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)

        learner = Learner(afmoe_cfg(), actor="fused", seed=1)
        # copies: a numpy VIEW of a buffer keeps the runtime from donating it
        before = jax.tree.map(lambda x: np.array(x, copy=True), learner.state.params)
        state0, actor0 = learner.state, learner.device_actor.state
        snapshot = learner.league.snapshots[0].params
        out = learner.train(4)
        assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
        after = jax.tree.map(np.asarray, learner.state.params)
        moved = [
            float(np.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
        ]
        assert all(np.isfinite(moved)) and max(moved) > 1e-4
        # both kinds of program were built at the first call, none later
        assert set(learner.fused_step._programs) == {"frozen", "live"}
        # donated: the first dispatch's caches and expert weights are gone
        assert all(_deleted(actor0.carry["kv"])) and all(_deleted(actor0.opp_carry["kv"]))
        core0 = state0.params["params"]["core"]
        assert all(_deleted(core0["layer_1"]["moe"]["expert_gate"]))
        assert all(_deleted(state0.opt_state[1][0].mu["params"]["core"]["layer_0"]))
        # the snapshot is the league's own copy of the initial parameters
        assert not any(_deleted(snapshot))
        for a, b in zip(jax.tree.leaves(snapshot), jax.tree.leaves(before)):
            np.testing.assert_array_equal(np.asarray(a), b)
        # a publish copies what it needs before the next dispatch
        learner._publish_weights()
        learner.train(1)
        learner._drain_snapshots()
        assert np.isfinite(float(np.asarray(learner.state.params["params"]["head_value"]["bias"])[0]))
        # the layer's counts reached the step's metrics and the registry
        assert out["moe_dropped_assignments"] == 0.0
        assert out["moe_local_assignments"] > 0
        from dotaclient_tpu.utils import telemetry

        snap = telemetry.get_registry().snapshot()
        assert snap["moe/dropped_assignments"] == 0.0 and snap["moe/local_assignments"] > 0

    @pytest.mark.parametrize("limit,donated", [(None, False), (0, True)])
    def test_donation_follows_the_states_bytes_not_the_core(self, monkeypatch, limit, donated):
        """``make_fused_step`` decides from what the two states hold on a
        device (``fused.DONATE_ABOVE_BYTES``). The LSTM's kilobytes under
        the limit: ONE program of three arguments whose arguments survive
        the call (a second program costs its build at every start: PERF.md,
        PR 26). The same LSTM with the limit at 0: both states donated, a
        program for each kind of opponent."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner

        if limit is not None:
            monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", limit)

        cfg = tiny_cfg(opponent="league")
        cfg = dataclasses.replace(
            cfg, league=dataclasses.replace(
                cfg.league, enabled=True, snapshot_every=2, pool_size=1, selfplay_prob=0.5,
            ),
        )
        learner = Learner(cfg, actor="fused", seed=1)
        state0, actor0 = learner.state, learner.device_actor.state
        out = learner.train(3)
        assert np.isfinite(out["loss"])
        assert learner.fused_step.donate == donated
        assert set(learner.fused_step._jits) == ({"frozen", "live"} if donated else {"frozen"})
        assert learner.fused_step._cache_size() == (2 if donated else 1)
        assert all(_deleted(state0.params)) == donated == all(_deleted(actor0.carry))
        assert any(_deleted(state0)) == donated == any(_deleted(actor0))

    @pytest.mark.parametrize("mode", ["device", "vec", "scalar", "external"])
    def test_every_other_mode_refuses_the_core_by_name(self, mode):
        """A carry of caches is not shipped with every chunk: the buffered
        and external-actor modes say so, with the bytes."""
        from dotaclient_tpu.models.afmoe import carry_bytes_per_lane
        from dotaclient_tpu.train.learner import Learner

        cfg = afmoe_cfg()
        with pytest.raises(ValueError, match="core 'afmoe' carries") as e:
            Learner(cfg, actor=mode)
        assert f"{carry_bytes_per_lane(cfg.model):,} bytes" in str(e.value)
        assert repr(mode) in str(e.value)


class TestServeResidentCarries:
    """``serve/engine.py`` steps the core with its caches resident in the
    carry store: a slot's rings are what a direct step-by-step run leaves."""

    @staticmethod
    def _config(**serve_over):
        from tests.test_serve import tiny_config

        cfg = tiny_config(max_batch=4, batch_window_ms=5.0, max_slots=4, **serve_over)
        return dataclasses.replace(cfg, model=dataclasses.replace(
            afmoe_cfg().model, unit_embed_dim=8, hero_embed_dim=4,
        ))

    def test_engine_steps_the_core_in_its_slots(self):
        from tests.test_serve import ReplyCollector, make_engine, one_obs

        config = self._config()
        engine = make_engine(config)
        try:
            sink = ReplyCollector()
            observations = [one_obs(config, seed=i) for i in range(3)]
            for i, obs in enumerate(observations):
                engine.submit(2, obs, reset=(i == 0), reply=sink, request_id=i + 1)
                sink.wait(i + 1)
            policy, params = engine._policy, engine._params
            carry = policy.initial_state(1)
            for obs in observations:
                batch = {k: np.asarray(v)[None] for k, v in obs.items()}
                _, _, carry = policy.apply(params, batch, carry, method="step")
            store = jax.tree.map(lambda c: np.asarray(c[2:3]), engine._carries)
            assert int(store["pos"][0]) == 3 == int(carry["pos"][0])
            for got, want in zip(jax.tree.leaves(store["kv"]), jax.tree.leaves(carry["kv"])):
                assert np.abs(want[:, :3]).max() > 0
                np.testing.assert_allclose(got[:, :3], np.asarray(want)[:, :3], rtol=1e-5, atol=1e-6)
            # a new episode in the slot: position 0 again, the rings left as they were
            engine.submit(2, observations[0], reset=True, reply=sink, request_id=9)
            sink.wait(4)
            assert int(np.asarray(engine._carries["pos"])[2]) == 1
        finally:
            engine.stop()

    def test_carry_shadow_is_refused_by_name(self):
        from tests.test_serve import make_engine

        with pytest.raises(ValueError, match="core 'afmoe' carries .* bytes"):
            make_engine(self._config(carry_shadow=True))
