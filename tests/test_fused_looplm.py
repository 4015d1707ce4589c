"""The fused path and the serve engine with the looped core (ISSUE 30;
beside ``tests/test_fused_afmoe.py``): a carry of one ring for every layer
and loop step that stays on the chip, an exit-weighted loss, and the scopes
and counters the benchmark's readers look for."""

import dataclasses
import re

import jax
import numpy as np
import pytest

from tests.test_fused import tiny_cfg
from tests.test_looplm import L, R, SIZES


def looplm_cfg(**league):
    """Two layers run three times at toy widths, league self-play, episodes
    of 20 steps in rings of 24."""
    cfg = tiny_cfg(opponent="league")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, **SIZES),
        env=dataclasses.replace(cfg.env, max_dota_time=3.8),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=2, pool_size=1,
            **{"selfplay_prob": 0.5, **league},
        ),
    )


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


class TestFusedLoopLM:
    def test_looped_core_trains_through_the_fused_path_and_donates_its_state(self, monkeypatch):
        """The learner's normal path: finite, the tied weights and the gate
        move, both of the first dispatch's arguments are donated, and the
        exit gauges and the pass counter reach the registry."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner
        from dotaclient_tpu.utils import telemetry

        # toy states are kilobytes: say that they are "most of the chip"
        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)

        learner = Learner(looplm_cfg(), actor="fused", seed=1)
        before = jax.tree.map(lambda x: np.array(x, copy=True), learner.state.params)
        state0, actor0 = learner.state, learner.device_actor.state
        assert len(actor0.carry["kv"]) == L * R == len(actor0.opp_carry["kv"])
        passes0 = telemetry.get_registry().snapshot().get("looplm/loop_passes_total", 0.0)
        out = learner.train(4)
        assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
        core_b, core_a = before["params"]["core"], learner.state.params["params"]["core"]
        for path in (("layer_0", "attn", "wq", "kernel"), ("layer_1", "ffn", "down_proj", "kernel"),
                     ("out_norm", "scale"), ("exit_gate", "kernel")):
            a, b = core_a, core_b
            for key in path:
                a, b = a[key], b[key]
            assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(a) - b).max() > 1e-5, path
        assert set(learner.fused_step._programs) == {"frozen", "live"}
        assert all(_deleted(actor0.carry["kv"])) and all(_deleted(actor0.opp_carry["kv"]))
        assert all(_deleted(state0.params["params"]["core"]["layer_0"]))
        # the step's metrics and the registry (log_every is 1 here: four logged forward passes)
        mass = [out[f"looplm_exit_mass_{r}"] for r in range(R)]
        assert abs(sum(mass) - 1.0) < 1e-5 and out["looplm_loop_passes"] == R
        snap = telemetry.get_registry().snapshot()
        assert snap["looplm/loop_passes_total"] - passes0 == 4 * R
        assert [snap[f"looplm/exit_mass/{r}"] for r in range(R)] == mass
        assert snap["looplm/expected_exit_step"] == out["looplm_expected_exit_step"]
        assert snap["looplm/exit_entropy"] == out["looplm_exit_entropy"] > 0.0

    @pytest.mark.parametrize("mode", ["device", "vec", "scalar", "external"])
    def test_every_other_mode_refuses_the_core_by_name(self, mode):
        from dotaclient_tpu.models.afmoe import carry_bytes_per_lane
        from dotaclient_tpu.train.learner import Learner

        cfg = looplm_cfg()
        with pytest.raises(ValueError, match="core 'looplm' carries") as e:
            Learner(cfg, actor=mode)
        assert f"{carry_bytes_per_lane(cfg.model):,} bytes" in str(e.value)
        assert repr(mode) in str(e.value)

    def test_the_fused_program_carries_the_loop_s_scopes(self):
        """What the benchmark's readers match as whole path segments:
        ``core_loop`` around every loop step with the layer's scopes in it,
        ``core_exit_gate``, and ``update_exit_mix`` inside ``update_loss``."""
        from benchmark.readers import _scopes
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        cfg = looplm_cfg()
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))
        state = jax.eval_shape(lambda p: init_train_state(p, cfg.ppo), params)
        actor = DeviceActor(cfg, policy, seed=3)
        hlo = make_fused_step(policy, cfg, mesh, actor).lower(state, actor.state, params).compile(
            compiler_options={"xla_backend_optimization_level": 0, "xla_llvm_disable_expensive_passes": True}
        ).as_text()
        names = [_scopes.segments(n) for n in re.findall(r'op_name="([^"]*)"', hlo)]

        def count(*scopes):
            return sum(all(s in segs for s in scopes) for segs in names)

        for inner in ("core_attn_full", "core_cache_write", "core_dense_ffn"):
            assert count("policy_core", "core_loop", inner) > 0, inner
            assert count(inner) == count("core_loop", inner)           # none outside a loop step
        assert count("policy_core", "core_exit_gate") > 0 and count("core_loop", "core_exit_gate") == 0
        assert count("phase_update", "update_loss", "update_exit_mix") > 0
        assert count("phase_rollout", "update_exit_mix") == 0
        assert count("core_attn_window") == count("core_router") == 0
        # a product of the tied stack in the update is written R times forward
        wq = [segs for segs in names if "core_attn_full" in segs and "wq" in segs and "phase_update" in segs
              and segs[-1] == "dot_general"]
        assert len(wq) >= R * L


class TestServeResidentCarries:
    """``serve/engine.py`` steps the looped core with its rings resident in
    the carry store, answering from the last loop step."""

    @staticmethod
    def _config(**serve_over):
        from tests.test_serve import tiny_config

        cfg = tiny_config(max_batch=4, batch_window_ms=5.0, max_slots=4, **serve_over)
        return dataclasses.replace(cfg, model=dataclasses.replace(
            looplm_cfg().model, unit_embed_dim=8, hero_embed_dim=4,
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
                logits, _, carry = policy.apply(params, batch, carry, method="step")
            assert logits["action_type"].shape == (1, config.actions.n_action_types)
            store = jax.tree.map(lambda c: np.asarray(c[2:3]), engine._carries)
            assert int(store["pos"][0]) == 3 == int(carry["pos"][0])
            assert len(store["kv"]) == L * R
            for got, want in zip(jax.tree.leaves(store["kv"]), jax.tree.leaves(carry["kv"])):
                assert np.abs(want[:, :3]).max() > 0
                np.testing.assert_allclose(got[:, :3], np.asarray(want)[:, :3], rtol=1e-5, atol=1e-6)
        finally:
            engine.stop()

    def test_carry_shadow_is_refused_by_name(self):
        from tests.test_serve import make_engine

        with pytest.raises(ValueError, match="core 'looplm' carries .* bytes"):
            make_engine(self._config(carry_shadow=True))
