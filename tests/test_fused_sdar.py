"""The fused path with the SDAR core (beside ``tests/test_fused_lfm2moe.py``):
a rollout step of three denoising passes with draws between them and a
commit, the stage of each slot recorded in the chunk, the learner's staged
loss, the counters and scopes the benchmark's readers look for, and the
refusal of every other mode and of the serve engine by name."""

import dataclasses
import re

import jax
import numpy as np
import pytest

from tests.test_fused import tiny_cfg
from tests.test_sdar import SIZES


def sdar_cfg(**league):
    """Two layers at toy widths, league self-play, episodes of 20 steps in
    rings of 6 x 24 positions."""
    cfg = tiny_cfg(opponent="league")
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, **{**SIZES, "pad_expert_groups": True}),     # as the cell runs it
        ppo=dataclasses.replace(cfg.ppo, moe_aux_coef=0.001, select_bias_rate=0.0),
        env=dataclasses.replace(cfg.env, max_dota_time=3.8),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=2, pool_size=1, **{"selfplay_prob": 0.5, **league},
        ),
    )


def _deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree.leaves(tree)]


class TestFusedSdar:
    def test_the_core_trains_through_the_fused_path_with_four_passes_a_step(self, monkeypatch):
        """The learner's normal path: finite, the ratio 1 at the rollout's
        parameters (approx_kl ~ 0 in the first epoch), the attention, the
        router and the token table move, both of the first dispatch's
        arguments are donated, ``diffusion/passes_total`` moves by S + 1 a
        rollout step (a pass a team here: the test's lanes are sharded over
        its eight devices; ``tests/test_sdar.py`` holds one pass over both
        teams equal to two), and the block counts reach the registry."""
        from dotaclient_tpu.train import fused
        from dotaclient_tpu.train.learner import Learner
        from dotaclient_tpu.utils import telemetry

        monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)        # toy rings are kilobytes
        cfg = sdar_cfg(selfplay_prob=1.0)
        learner = Learner(cfg, actor="fused", seed=1)
        before = jax.tree.map(lambda x: np.array(x, copy=True), learner.state.params)
        state0, actor0 = learner.state, learner.device_actor.state
        snap0 = telemetry.get_registry().snapshot()
        out = learner.train(4)
        assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
        assert abs(out["approx_kl"]) < 1e-5 and out["clip_frac"] == 0.0
        core_b, core_a = before["params"]["core"], learner.state.params["params"]["core"]
        for path in (("layer_0", "attn", "wq", "kernel"), ("layer_1", "attn", "k_norm", "scale"),
                     ("layer_1", "moe", "router"), ("tokens",), ("out_norm", "scale")):
            a, b = core_a, core_b
            for key in path:
                a, b = a[key], b[key]
            assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(a) - b).max() > 1e-6, path
        np.testing.assert_array_equal(np.asarray(core_a["layer_1"]["moe"]["select_bias"]), 0.0)   # no selection bias
        assert all(_deleted(actor0.carry["kv"])) and all(_deleted(actor0.opp_carry["kv"]))
        assert all(_deleted(state0.params["params"]["core"]["layer_1"]))
        carry = learner.device_actor.state.carry
        assert (np.asarray(carry["pos"]) % 6 == 0).all() and int(np.asarray(carry["pos"]).max()) <= 6 * 20
        snap = telemetry.get_registry().snapshot()
        moved = lambda key: snap[key] - snap0.get(key, 0.0)
        dispatches = moved("learner/dispatches_total")
        assert dispatches == 4 and moved("diffusion/passes_total") == dispatches * cfg.ppo.rollout_len * 4
        committed, none = moved("diffusion/tokens_committed_total"), moved("diffusion/none_slots_total")
        assert committed > 0 and none > 0
        # each logged chunk's slots are committed or NONE: 5 a lane-step (log_every 1: four logged updates)
        assert committed + none == dispatches * learner.device_actor.n_lanes * cfg.ppo.rollout_len * 5
        for s in (1, 2, 3):
            assert snap[f"diffusion/stage_entropy/{s}"] == out[f"diffusion_stage_entropy_{s}"] >= 0.0
        assert snap["moe/dropped_assignments"] == 0.0

    def test_the_chunk_records_the_pass_that_committed_each_slot(self):
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import distributions as D, init_params, make_policy

        cfg = sdar_cfg()
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = jax.jit(lambda k: init_params(policy, k))(jax.random.PRNGKey(0))
        actor = DeviceActor(cfg, policy, seed=3)
        _, chunk, _ = jax.jit(actor._rollout_impl)(params, actor.state, params)        # a frozen opponent: a pass a team
        stage = np.asarray(chunk["act_stage"])
        assert stage.shape == (actor.n_lanes, cfg.ppo.rollout_len, 5) and stage.dtype == np.int8
        rel = np.asarray(D.relevant(chunk["actions"]["action_type"]))
        np.testing.assert_array_equal(stage > 0, rel)
        np.testing.assert_array_equal(stage[..., 0], 1)
        assert set(np.unique(stage[..., 1:])) <= {0, 2, 3}

    @pytest.mark.parametrize("mode", ["device", "vec", "scalar", "external"])
    def test_every_other_mode_refuses_the_core_by_name(self, mode):
        from dotaclient_tpu.models.sdar import carry_bytes_per_lane
        from dotaclient_tpu.train.learner import Learner

        cfg = sdar_cfg()
        with pytest.raises(ValueError, match="core 'sdar' carries") as e:
            Learner(cfg, actor=mode)
        assert f"{carry_bytes_per_lane(cfg.model):,} bytes" in str(e.value)
        assert repr(mode) in str(e.value)

    def test_the_serve_engine_refuses_the_core_by_name(self):
        from tests.test_serve import make_engine, tiny_config

        cfg = tiny_config(max_batch=4, batch_window_ms=5.0, max_slots=4)
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(sdar_cfg().model, unit_embed_dim=8, hero_embed_dim=4))
        with pytest.raises(ValueError, match="core 'sdar' decodes an action as a block over 3 denoising passes"):
            make_engine(cfg)

    def test_the_fused_program_carries_the_core_s_scopes(self):
        """What the benchmark's readers match as whole path segments: the
        denoising passes and the commit under ``policy_core`` in the rollout
        alone, ``core_block_attend`` inside ``core_attn_full`` in both phases,
        the draws between passes under ``rollout_sample``."""
        from benchmark.readers import _scopes
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        cfg = sdar_cfg()
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

        for scope in ("core_denoise", "core_commit"):
            assert count("phase_rollout", "policy_core", scope) > 0, scope
            assert count("phase_update", scope) == 0, scope
        assert count("phase_rollout", "core_commit", "core_cache_write") > 0
        assert count("phase_rollout", "core_denoise", "core_cache_write") > 0         # pass 1 writes the observation
        for phase in ("phase_rollout", "phase_update"):
            assert count(phase, "policy_core", "core_attn_full", "core_block_attend") > 0, phase
            assert count(phase, "policy_core", "core_router") > 0, phase
        assert count("core_block_attend") == count("core_attn_full", "core_block_attend")
        assert count("phase_rollout", "rollout_sample", "rollout_stage_sample") > 0
        assert count("core_expert_shared") == count("core_attn_window") == count("core_kda") == count("core_conv") == 0
        # no weight is read under the attend scope: no projection's name inside it
        assert not [segs for segs in names if "core_block_attend" in segs and {"wq", "wk", "wv", "wo"} & set(segs)]
