"""The fused path and the serve engine with the LFM2 core (ISSUE 37; beside
``tests/test_fused_afmoe.py``, ``test_fused_looplm.py`` and
``test_fused_kimilinear.py``): a carry of counters, one ring pair AND
convolution histories that stays on the chip, whose chunk start is the
start's histories beside the end's ring, and the scopes and gauges the
benchmark's readers look for."""

import dataclasses
import re

import jax
import numpy as np
import pytest

from tests.test_fused import tiny_cfg
from tests.test_lfm2moe import SIZES

# the fused programs compile for a minute at five layers on the CPU: three here (convolution
# with the dense FFN, attention, convolution; the published pattern is tests/test_lfm2moe.py's)
CONV_LAYERS, ATTN_LAYERS = 2, 1


def lfm2moe_cfg(**league):
    """Three layers (two convolutions around one attention layer) at toy
    widths, league self-play, episodes of 20 steps in a ring of 24."""
    cfg = tiny_cfg(opponent="league")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, **{**SIZES, "n_layers": 3, "pad_expert_groups": True}),     # as the cell runs it
        ppo=dataclasses.replace(cfg.ppo, moe_aux_coef=0.0),
        env=dataclasses.replace(cfg.env, max_dota_time=3.8),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=2, pool_size=1,
            **{"selfplay_prob": 0.5, **league},
        ),
    )


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


class TestFusedLfm2Moe:
    def test_the_core_trains_through_the_fused_path_and_donates_its_state(self, monkeypatch):
        """The learner's normal path: finite, the convolution layers' and the
        attention layer's weights move and the selection bias moves by its
        own rule, both of the first dispatch's arguments are donated (rings
        and histories with them), and the gauges and the void-read counter
        reach the registry."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner
        from dotaclient_tpu.utils import telemetry

        # toy rings are kilobytes: say that they are "most of the chip"
        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)

        learner = Learner(lfm2moe_cfg(), actor="fused", seed=1)
        before = jax.tree.map(lambda x: np.array(x, copy=True), learner.state.params)
        state0, actor0 = learner.state, learner.device_actor.state
        assert len(actor0.carry["conv"]) == CONV_LAYERS == len(actor0.opp_carry["conv"])
        assert len(actor0.carry["kv"]) == ATTN_LAYERS
        reads0 = telemetry.get_registry().snapshot().get("shortconv/void_reads_total", 0.0)
        out = learner.train(6)
        assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
        core_b, core_a = before["params"]["core"], learner.state.params["params"]["core"]
        for path in (("layer_0", "conv", "in_proj", "kernel"), ("layer_2", "conv", "conv"), ("layer_2", "conv", "out_proj", "kernel"),
                     ("layer_1", "attn", "wq", "kernel"), ("layer_1", "attn", "k_norm", "scale"),
                     ("layer_1", "moe", "select_bias"), ("out_norm", "scale")):     # (a router moves only where a pair lands on a held expert)
            a, b = core_a, core_b
            for key in path:
                a, b = a[key], b[key]
            assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(a) - b).max() > 1e-6, path
        assert "shared" not in core_a["layer_1"]["moe"]
        assert set(learner.fused_step._programs) == {"frozen", "live"}
        assert all(_deleted(actor0.carry["conv"])) and all(_deleted(actor0.carry["kv"]))
        assert all(_deleted(actor0.opp_carry["conv"])) and all(_deleted(actor0.opp_carry["kv"]))
        assert all(_deleted(state0.params["params"]["core"]["layer_1"]))
        # the lanes carry on: positions count an episode's steps, histories are finite and not empty
        carry = learner.device_actor.state.carry
        assert 0 <= int(np.asarray(carry["pos"]).min()) and int(np.asarray(carry["pos"]).max()) <= 20
        assert all(np.isfinite(np.asarray(h, np.float32)).all() and np.abs(np.asarray(h, np.float32)).max() > 0 for h in carry["conv"])
        # the step's metrics and the registry (log_every is 1 here: six logged passes)
        assert out["shortconv_history_rms"] > 0.0 and out["shortconv_gate_mean"] > 0.0
        snap = telemetry.get_registry().snapshot()
        for key in ("history_rms", "gate_mean"):
            assert snap[f"shortconv/{key}"] == out[f"shortconv_{key}"]
        # every episode start is read void by every convolution layer of the owner team's lanes
        started = snap["shortconv/void_reads_total"] - reads0
        assert started > 0 and started % CONV_LAYERS == 0
        assert snap["moe/dropped_assignments"] == 0.0 and snap["moe/local_assignments"] > 0

    @pytest.mark.parametrize("mode", ["device", "vec", "scalar", "external"])
    def test_every_other_mode_refuses_the_core_by_name(self, mode):
        from dotaclient_tpu.models.lfm2moe import carry_bytes_per_lane
        from dotaclient_tpu.train.learner import Learner

        cfg = lfm2moe_cfg()
        with pytest.raises(ValueError, match="core 'lfm2moe' carries") as e:
            Learner(cfg, actor=mode)
        assert f"{carry_bytes_per_lane(cfg.model):,} bytes" in str(e.value)
        assert repr(mode) in str(e.value)

    def test_the_fused_program_carries_the_core_s_scopes(self):
        """What the benchmark's readers match as whole path segments:
        ``core_conv`` with ``core_conv_state`` inside it, the attention
        layer's, the cache write's and the FFNs' scopes, in both phases; no
        shared expert's, no window layer's, no other core's."""
        from benchmark.readers import _scopes
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        cfg = lfm2moe_cfg()
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
            assert count(phase, "policy_core", "core_conv", "core_conv_state") > 0, phase
            for scope in ("core_attn_full", "core_router", "core_experts_routed", "core_dense_ffn"):
                assert count(phase, "policy_core", scope) > 0, (phase, scope)
        assert count("core_conv_state") == count("core_conv", "core_conv_state")
        assert count("phase_rollout", "core_cache_write") > 0
        assert count("core_expert_shared") == count("core_attn_window") == count("core_loop") == count("core_kda") == 0
        # the state's scope holds the taps and the history, no projection
        assert not [segs for segs in names if "core_conv_state" in segs and {"in_proj", "out_proj"} & set(segs)]


class TestServeResidentCarries:
    """``serve/engine.py`` steps the core with its ring and its histories
    resident in the carry store; a slot's release is a reset."""

    @staticmethod
    def _config(**serve_over):
        from tests.test_serve import tiny_config

        cfg = tiny_config(max_batch=4, batch_window_ms=5.0, max_slots=4, **serve_over)
        return dataclasses.replace(cfg, model=dataclasses.replace(
            lfm2moe_cfg().model, unit_embed_dim=8, hero_embed_dim=4,
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
            assert len(store["conv"]) == CONV_LAYERS and len(store["kv"]) == ATTN_LAYERS
            for got, want in zip(store["conv"], carry["conv"]):
                assert np.abs(np.asarray(want)).max() > 0
                # the second session's two rows: the first session's are read as void after the reset
                np.testing.assert_allclose(got[:, 1:], np.asarray(want)[:, 1:], rtol=1e-4, atol=1e-6)
        finally:
            engine.stop()

    def test_carry_shadow_is_refused_by_name(self):
        from tests.test_serve import make_engine

        with pytest.raises(ValueError, match="core 'lfm2moe' carries .* bytes"):
            make_engine(self._config(carry_shadow=True))
