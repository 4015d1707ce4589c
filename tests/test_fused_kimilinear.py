"""The fused path and the serve engine with the Kimi-Linear core (ISSUE 32;
beside ``tests/test_fused_afmoe.py`` and ``tests/test_fused_looplm.py``): a
carry of counters, a latent ring AND matrix states that stays on the chip,
whose chunk start is the start's states beside the end's ring, and the
scopes and gauges the benchmark's readers look for."""

import dataclasses
import re

import jax
import numpy as np
import pytest

from tests.test_fused import tiny_cfg
from tests.test_kimilinear import KDA_LAYERS, MLA_LAYERS, SIZES


def kimilinear_cfg(**league):
    """Five layers (four KDA, one MLA) at toy widths, league self-play,
    episodes of 20 steps in a latent ring of 24."""
    cfg = tiny_cfg(opponent="league")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, **SIZES),
        ppo=dataclasses.replace(cfg.ppo, moe_aux_coef=0.0),
        env=dataclasses.replace(cfg.env, max_dota_time=3.8),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=2, pool_size=1,
            **{"selfplay_prob": 0.5, **league},
        ),
    )


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


class TestFusedKimiLinear:
    def test_the_core_trains_through_the_fused_path_and_donates_its_state(self, monkeypatch):
        """The learner's normal path: finite, the KDA layers' and the MLA
        layer's weights move and the selection bias moves by its own rule,
        both of the first dispatch's arguments are donated (states, rows and
        ring with them), and the gauges and the void-read counter reach the
        registry."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner
        from dotaclient_tpu.utils import telemetry

        # toy states are kilobytes: say that they are "most of the chip"
        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)

        learner = Learner(kimilinear_cfg(), actor="fused", seed=1)
        before = jax.tree.map(lambda x: np.array(x, copy=True), learner.state.params)
        state0, actor0 = learner.state, learner.device_actor.state
        assert len(actor0.carry["kda"]) == KDA_LAYERS == len(actor0.opp_carry["kda"])
        assert len(actor0.carry["latent"]) == MLA_LAYERS
        reads0 = telemetry.get_registry().snapshot().get("kda/void_reads_total", 0.0)
        out = learner.train(6)
        assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
        core_b, core_a = before["params"]["core"], learner.state.params["params"]["core"]
        for path in (("layer_0", "kda", "wq", "kernel"), ("layer_1", "kda", "A_log"), ("layer_2", "kda", "conv"),
                     ("layer_3", "kda", "wb", "kernel"), ("layer_4", "attn", "wuk"), ("layer_4", "attn", "wkv_a", "kernel"),
                     ("layer_1", "moe", "router"), ("layer_1", "moe", "select_bias"), ("out_norm", "scale")):
            a, b = core_a, core_b
            for key in path:
                a, b = a[key], b[key]
            assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(a) - b).max() > 1e-6, path
        assert set(learner.fused_step._programs) == {"frozen", "live"}
        assert all(_deleted(actor0.carry["kda"])) and all(_deleted(actor0.carry["latent"]))
        assert all(_deleted(actor0.opp_carry["kda"])) and all(_deleted(actor0.opp_carry["latent"]))
        assert all(_deleted(state0.params["params"]["core"]["layer_1"]))
        # the lanes carry on: positions count an episode's steps, states are finite and not empty
        carry = learner.device_actor.state.carry
        assert 0 <= int(np.asarray(carry["pos"]).min()) and int(np.asarray(carry["pos"]).max()) <= 20
        assert all(np.isfinite(np.asarray(s)).all() and np.abs(np.asarray(s)).max() > 0 for s, _ in carry["kda"])
        # the step's metrics and the registry (log_every is 1 here: six logged passes)
        assert 0.0 < out["kda_decay_mean"] < 1.0 and 0.0 < out["kda_beta_mean"] < 1.0 and out["kda_state_rms"] > 0.0
        snap = telemetry.get_registry().snapshot()
        for key in ("decay_mean", "beta_mean", "state_rms"):
            assert snap[f"kda/{key}"] == out[f"kda_{key}"]
        # 8 games of 20 steps in chunks of 4: every game starts an episode in the first chunk and
        # again after 20 steps; each start is read void by every KDA layer of the owner team's lanes
        started = snap["kda/void_reads_total"] - reads0
        assert started > 0 and started % KDA_LAYERS == 0
        assert snap["moe/dropped_assignments"] == 0.0 and snap["moe/local_assignments"] > 0

    @pytest.mark.parametrize("mode", ["device", "vec", "scalar", "external"])
    def test_every_other_mode_refuses_the_core_by_name(self, mode):
        from dotaclient_tpu.models.kimilinear import carry_bytes_per_lane
        from dotaclient_tpu.train.learner import Learner

        cfg = kimilinear_cfg()
        with pytest.raises(ValueError, match="core 'kimilinear' carries") as e:
            Learner(cfg, actor=mode)
        assert f"{carry_bytes_per_lane(cfg.model):,} bytes" in str(e.value)
        assert repr(mode) in str(e.value)

    def test_the_fused_program_carries_the_core_s_scopes_and_keeps_the_chunk_s_start(self):
        """What the benchmark's readers match as whole path segments:
        ``core_kda`` with ``core_kda_state`` inside it, ``core_attn_latent``
        with ``core_latent_attend`` inside it, the cache write and the routed
        layer's scopes, in both phases."""
        from benchmark.readers import _scopes
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        cfg = kimilinear_cfg()
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

        for phase in ("phase_rollout", "phase_update"):
            for outer, inner in (("core_kda", "core_kda_state"), ("core_attn_latent", "core_latent_attend")):
                assert count(phase, "policy_core", outer, inner) > 0, (phase, inner)
            for scope in ("core_router", "core_experts_routed", "core_expert_shared", "core_dense_ffn"):
                assert count(phase, "policy_core", scope) > 0, (phase, scope)
        assert count("core_kda_state") == count("core_kda", "core_kda_state")
        assert count("core_latent_attend") == count("core_attn_latent", "core_latent_attend")
        assert count("phase_rollout", "core_cache_write") > 0
        assert count("core_attn_window") == count("core_attn_full") == count("core_loop") == 0
        # no weight is multiplied under either roofline's scope: neither holds a projection
        for inner in ("core_kda_state", "core_latent_attend"):
            assert not [segs for segs in names if inner in segs and {"wq", "wk", "wv", "wo", "wkv_a"} & set(segs)]


class TestServeResidentCarries:
    """``serve/engine.py`` steps the core with its states and its ring
    resident in the carry store; a slot's release is a reset."""

    @staticmethod
    def _config(**serve_over):
        from tests.test_serve import tiny_config

        cfg = tiny_config(max_batch=4, batch_window_ms=5.0, max_slots=4, **serve_over)
        return dataclasses.replace(cfg, model=dataclasses.replace(
            kimilinear_cfg().model, unit_embed_dim=8, hero_embed_dim=4,
        ))

    def test_engine_steps_the_core_in_its_slots_and_a_reset_starts_void(self):
        from tests.test_serve import ReplyCollector, make_engine, one_obs

        config = self._config()
        engine = make_engine(config)
        try:
            sink = ReplyCollector()
            observations = [one_obs(config, seed=i) for i in range(5)]
            # three steps of one session, then the slot is handed to a new session (reset)
            for i, obs in enumerate(observations):
                engine.submit(2, obs, reset=(i in (0, 3)), reply=sink, request_id=i + 1)
                sink.wait(i + 1)
            policy, params = engine._policy, engine._params
            carry = policy.initial_state(1)
            for obs in observations[3:]:                       # what the second session alone would leave
                batch = {k: np.asarray(v)[None] for k, v in obs.items()}
                logits, _, carry = policy.apply(params, batch, carry, method="step")
            assert logits["action_type"].shape == (1, config.actions.n_action_types)
            store = jax.tree.map(lambda c: np.asarray(c[2:3]), engine._carries)
            assert int(store["pos"][0]) == 2 == int(carry["pos"][0])
            assert len(store["kda"]) == KDA_LAYERS and len(store["latent"]) == MLA_LAYERS
            for (got_s, got_h), (want_s, want_h) in zip(store["kda"], carry["kda"]):
                assert np.abs(np.asarray(want_s)).max() > 0
                np.testing.assert_allclose(got_s, np.asarray(want_s), rtol=1e-4, atol=1e-6)
                np.testing.assert_allclose(got_h, np.asarray(want_h), rtol=1e-4, atol=1e-6)
        finally:
            engine.stop()

    def test_carry_shadow_is_refused_by_name(self):
        from tests.test_serve import make_engine

        with pytest.raises(ValueError, match="core 'kimilinear' carries .* bytes"):
            make_engine(self._config(carry_shadow=True))
