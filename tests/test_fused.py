"""Fused rollout+update program tests (train/fused.py, actor="fused").

The fused program must be the same math as the unfused pair: one
``DeviceActor._rollout_impl`` + one ``_train_step`` on the produced chunk,
from identical initial state. Pinned by running both from copies of the
same params/actor-state and comparing losses and updated parameters.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import default_config


def tiny_cfg(n_envs=8, opponent="scripted_easy"):
    cfg = default_config()
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, dtype="float32"),
        ppo=dataclasses.replace(cfg.ppo, rollout_len=4, batch_rollouts=8),
        env=dataclasses.replace(
            cfg.env, n_envs=n_envs, opponent=opponent, max_dota_time=60.0
        ),
        buffer=dataclasses.replace(cfg.buffer, capacity_rollouts=16, min_fill=8),
        log_every=1,
    )


class TestFusedStep:
    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~49s on the reference container
    def test_fused_equals_collect_then_train(self):
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import _train_step, init_train_state
        from dotaclient_tpu.models import init_params

        cfg = tiny_cfg()
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        actor = DeviceActor(cfg, policy, seed=3)
        state = init_train_state(params, cfg.ppo)
        actor_state0 = jax.tree.map(jnp.copy, actor.state)

        # unfused reference: collect, then train on the chunk
        a1, chunk, _ = jax.jit(actor._rollout_impl)(
            state.params, actor_state0, state.params
        )
        ref_state, ref_metrics = jax.jit(
            lambda s, b: _train_step(policy, cfg.ppo, s, b)
        )(state, chunk)

        fused = make_fused_step(policy, cfg, mesh, actor)
        new_state, a2, metrics, stats = fused(
            init_train_state(params, cfg.ppo),
            jax.tree.map(jnp.copy, actor_state0),
            params,
        )

        np.testing.assert_allclose(
            float(np.asarray(metrics["loss"])),
            float(np.asarray(ref_metrics["loss"])),
            rtol=1e-5,
        )
        for got, want in zip(
            jax.tree.leaves(new_state.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6
            )
        # actor state advanced identically (sim arrays, carries, rng)
        for got, want in zip(jax.tree.leaves(a2), jax.tree.leaves(a1)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )

    def test_learner_fused_mode_trains(self):
        from dotaclient_tpu.train.learner import Learner

        learner = Learner(tiny_cfg(), actor="fused", seed=1)
        out = learner.train(4)
        assert out["optimizer_steps"] == 4.0
        assert np.isfinite(out["loss"])
        # frames accounting reflects the lane-set batch, not batch_rollouts
        assert out["frames_trained"] == 4 * learner.device_actor.n_lanes * 4

    def test_fused_multi_epoch_scans_updates_in_program(self):
        """epochs_per_batch > 1 in fused mode: the one program applies E
        optimizer steps over its chunk (lax.scan), and the host counters
        stay in lockstep with the device step/version counters."""
        from dotaclient_tpu.train.learner import Learner

        cfg = tiny_cfg()
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, epochs_per_batch=2)
        )
        learner = Learner(cfg, actor="fused", seed=1)
        out = learner.train(4)    # 2 fused calls × 2 epochs
        assert out["optimizer_steps"] == 4.0
        assert np.isfinite(out["loss"])
        assert int(learner.state.step) == 4
        assert int(learner.state.version) == learner._host_version
        # each fused call contributes ONE chunk of unique frames
        assert out["frames_trained"] == 2 * learner.device_actor.n_lanes * 4

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~40s on the reference container
    def test_fused_minibatches_shuffle_in_program(self):
        """minibatches > 1 in fused mode: each epoch permutes the lanes
        (keyed on seed + step) and scans an optimizer step per group —
        verified against a hand-built reference of the same math."""
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import _train_step, init_train_state

        M = 2
        cfg = tiny_cfg()
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=M)
        )
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        actor = DeviceActor(cfg, policy, seed=3)
        actor_state0 = jax.tree.map(jnp.copy, actor.state)
        L = actor.n_lanes

        # reference: collect, permute with the same shard-local derivation
        # (one shard on this 1-device mesh), M sequential optimizer steps
        # on the lane groups
        ref_state = init_train_state(params, cfg.ppo)
        _, chunk, _ = jax.jit(actor._rollout_impl)(
            ref_state.params, actor_state0, ref_state.params
        )
        key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.seed), ref_state.step
        )
        (shard_key,) = jax.random.split(key, 1)
        perm = jax.random.permutation(shard_key, L)
        shuf = jax.tree.map(lambda x: jnp.take(x, perm, axis=0), chunk)
        step_jit = jax.jit(
            lambda s, b: _train_step(policy, cfg.ppo, s, b)
        )
        for m in range(M):
            mb = jax.tree.map(
                lambda x: x[m * (L // M):(m + 1) * (L // M)], shuf
            )
            ref_state, _ = step_jit(ref_state, mb)

        fused = make_fused_step(policy, cfg, mesh, actor)
        got_state, _, metrics, _ = fused(
            init_train_state(params, cfg.ppo),
            jax.tree.map(jnp.copy, actor_state0),
            params,
        )
        assert int(got_state.step) == M
        for got, want in zip(
            jax.tree.leaves(got_state.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6
            )
        assert np.isfinite(float(np.asarray(metrics["loss"])))

    def test_learner_fused_minibatch_accounting(self):
        from dotaclient_tpu.train.learner import Learner

        # 32 lanes: each of the 2 minibatches (16 lanes) must itself split
        # over the forced 8-device data axis
        cfg = tiny_cfg(n_envs=32)
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=2)
        )
        learner = Learner(cfg, actor="fused", seed=1)
        out = learner.train(4)    # 2 dispatches × 2 minibatch steps
        assert out["optimizer_steps"] == 4.0
        assert int(learner.state.step) == 4
        assert int(learner.state.version) == learner._host_version
        # each dispatch contributes ONE chunk of unique frames
        assert out["frames_trained"] == 2 * learner.device_actor.n_lanes * 4

    def test_fused_minibatches_must_divide_lanes(self):
        from dotaclient_tpu.train.learner import Learner

        cfg = tiny_cfg(n_envs=8)
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=3)
        )
        with pytest.raises(ValueError, match="divisible"):
            Learner(cfg, actor="fused")

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~36s on the reference container
    def test_steps_per_dispatch_scans_whole_iterations(self):
        """K>1 dispatch batching is the same math as K sequential fused
        calls: identical final params/actor-state, stats summed over the
        scan, host counters advancing in strides of K."""
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        K = 3
        cfg = tiny_cfg()
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        actor = DeviceActor(cfg, policy, seed=3)
        actor_state0 = jax.tree.map(jnp.copy, actor.state)

        # reference: K sequential single-iteration dispatches
        one = make_fused_step(policy, cfg, mesh, actor)
        ref_state = init_train_state(params, cfg.ppo)
        ref_actor = jax.tree.map(jnp.copy, actor_state0)
        ref_stats_sum = None
        for _ in range(K):
            ref_state, ref_actor, _, st = one(
                ref_state, ref_actor, ref_state.params
            )
            st = jax.tree.map(np.asarray, st)
            # tree-map: the stats carry nested leaves now (the outcome
            # plane's reward-term dict + histogram vector, ISSUE 15)
            ref_stats_sum = (
                st if ref_stats_sum is None
                else jax.tree.map(lambda a, b: a + b, ref_stats_sum, st)
            )

        cfg_k = dataclasses.replace(cfg, steps_per_dispatch=K)
        fused_k = make_fused_step(policy, cfg_k, mesh, actor)
        got_state, got_actor, metrics, got_stats = fused_k(
            init_train_state(params, cfg.ppo),
            jax.tree.map(jnp.copy, actor_state0),
            params,
        )
        # NOTE: the reference passes the UPDATED params as opp_params each
        # iteration while the scanned program holds the dispatch-entry
        # params — identical here because opponent lanes don't exist in
        # scripted mode (opp_params is unused by the rollout).
        for got, want in zip(
            jax.tree.leaves(got_state.params), jax.tree.leaves(ref_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6
            )
        for got, want in zip(
            jax.tree.leaves(got_actor), jax.tree.leaves(ref_actor)
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )
        for (path_got, got), (_, want) in zip(
            jax.tree_util.tree_flatten_with_path(got_stats)[0],
            jax.tree_util.tree_flatten_with_path(ref_stats_sum)[0],
        ):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6,
                err_msg=f"stats leaf {jax.tree_util.keystr(path_got)}",
            )
        assert np.isfinite(float(np.asarray(metrics["loss"])))

    def test_learner_steps_per_dispatch_accounting(self):
        from dotaclient_tpu.train.learner import Learner

        cfg = dataclasses.replace(tiny_cfg(), steps_per_dispatch=4)
        learner = Learner(cfg, actor="fused", seed=1)
        out = learner.train(8)    # 2 dispatches × 4 iterations
        assert out["optimizer_steps"] == 8.0
        assert np.isfinite(out["loss"])
        assert int(learner.state.step) == 8
        assert learner._host_step == 8
        assert int(learner.state.version) == learner._host_version
        # each of the 8 in-program iterations produced a fresh chunk
        assert out["frames_trained"] == 8 * learner.device_actor.n_lanes * 4
        assert learner.device_actor.rollouts_shipped == 8 * learner.device_actor.n_lanes

    def test_steps_per_dispatch_rejected_outside_fused(self):
        from dotaclient_tpu.train.learner import Learner

        cfg = dataclasses.replace(tiny_cfg(), steps_per_dispatch=2)
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            Learner(cfg, actor="device")

    @pytest.mark.xfail(
        reason="pre-existing tolerance drift (tracked, ISSUE 6 satellite): "
        "on the forced 8-virtual-device CPU mesh the TP trajectory's loss "
        "drifts past rtol=2e-4 of the single-device run after 2 fused "
        "iterations (measured -0.0326 vs -0.0334 on clean PR 2..5 HEADs — "
        "XLA CPU fuses the sharded reductions differently, and the fused "
        "rollout+update program compounds the rounding across the scan). "
        "The TP equivalence guarantee itself is covered at step scope by "
        "test_parallel; widening the tolerance to the observed ~3e-2 "
        "would make this assertion vacuous, so it stays xfail until the "
        "trajectory-scope comparison is reworked (e.g. per-iteration "
        "re-sync or f64 accumulation).",
        strict=False,
    )
    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~40s on the reference container
    def test_fused_under_tensor_parallelism_matches_single_device(self):
        """The fused program with a (data, model=2) mesh must produce the
        same training trajectory as the single-device fused program —
        the TP equivalence guarantee (test_parallel) extended to the
        rollout+update fusion."""
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8 forced host devices")
        cfg = tiny_cfg(n_envs=16)   # 16 lanes / 4 data shards under TP
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))

        def run(cfg_run, devices):
            mesh = make_mesh(cfg_run.mesh, devices=devices)
            actor = DeviceActor(cfg_run, policy, seed=5)
            fused = make_fused_step(policy, cfg_run, mesh, actor)
            state = init_train_state(params, cfg_run.ppo)
            for _ in range(2):
                state, actor_state, metrics, _stats = fused(
                    state, actor.state, state.params
                )
                actor.state = actor_state
            return state, metrics

        s1, m1 = run(cfg, jax.devices()[:1])
        cfg_tp = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, model_parallel=2)
        )
        s2, m2 = run(cfg_tp, jax.devices())
        # params actually partition over the model axis under TP
        kernel = s2.params["params"]["core"]["hi"]["kernel"]
        assert "model" in str(kernel.sharding.spec)
        np.testing.assert_allclose(
            float(np.asarray(m1["loss"])), float(np.asarray(m2["loss"])),
            rtol=2e-4, atol=2e-5,
        )
        for a, b in zip(
            jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )

    def test_fused_league_uses_frozen_opponent(self):
        from dotaclient_tpu.train.learner import Learner

        cfg = tiny_cfg(opponent="league")
        cfg = dataclasses.replace(
            cfg,
            league=dataclasses.replace(
                cfg.league, enabled=True, snapshot_every=2, pool_size=2,
                selfplay_prob=0.0,
            ),
        )
        learner = Learner(cfg, actor="fused", seed=2)
        out = learner.train(3)
        assert np.isfinite(out["loss"])
        assert len(learner.league.snapshots) >= 1


# -- the program's scopes (ISSUE 24) ---------------------------------------
# What a profiler trace splits the fused step by. The benchmark's
# `*_device_share` readers match these names as whole path segments
# (benchmark/readers/_scopes.py); `policy_*` are the four the older readers
# match by substring, and must stay as they are.
PHASE_SCOPES = ("phase_rollout", "phase_update")
STAGE_SCOPES = (
    "rollout_featurize", "rollout_sample", "rollout_sim_step",
    "rollout_reward", "rollout_reset", "rollout_assemble",
    "update_loss", "update_gae", "update_optimizer", "update_probe",
)
POLICY_SCOPES = ("policy_trunk", "policy_core", "policy_core_scan", "policy_heads")


def _hlo_sources(hlo):
    """``stack_frame_id -> (file, line)`` of the program line an instruction
    of an HLO text came from, read from the tables at the module's head
    (``FileNames``, ``FileLocations``, ``StackFrames``)."""
    def table(name):
        rows = {}
        for line in hlo[hlo.index(f"\n{name}\n") + len(name) + 2:].split("\n"):
            m = re.match(r"(\d+) (.*)$", line)
            if not m:
                break
            rows[int(m.group(1))] = m.group(2)
        return rows

    def field(row, key):
        return int(re.search(rf"\b{key}=(\d+)", row).group(1))

    files = {k: v.strip('"') for k, v in table("FileNames").items()}
    locs = {
        k: (files[field(v, "file_name_id")], field(v, "line"))
        for k, v in table("FileLocations").items()
    }
    return {
        k: locs[field(v, "file_location_id")]
        for k, v in table("StackFrames").items()
    }


class TestFusedScopes:
    @pytest.fixture(scope="class")
    def lowered_and_hlo(self):
        """The fused step at test size, lowered (the metadata as written)
        and compiled without optimisation: only in the compiled module are
        calls inlined and every instruction's `op_name` the whole path from
        the program's root."""
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step
        from dotaclient_tpu.train.ppo import init_train_state

        cfg = tiny_cfg(n_envs=2)
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        # shapes only: nothing here runs
        params = jax.eval_shape(
            lambda: init_params(policy, jax.random.PRNGKey(0))
        )
        state = jax.eval_shape(lambda p: init_train_state(p, cfg.ppo), params)
        actor = DeviceActor(cfg, policy, seed=3)
        lowered = make_fused_step(policy, cfg, mesh, actor).lower(
            state, actor.state, params
        )
        hlo = lowered.compile(compiler_options={
            "xla_backend_optimization_level": 0,     # the names, not the code
            "xla_llvm_disable_expensive_passes": True,
        }).as_text()
        return lowered.as_text(debug_info=True), hlo

    def test_lowered_step_carries_every_scope_and_little_unscoped_work(
        self, lowered_and_hlo
    ):
        """The guard for `unscoped_device_share` that needs no chip: every
        scope is in the lowered step's metadata, and nearly every operation
        of the lowered module was written under one of the two phases."""
        from benchmark.readers import _scopes

        text, hlo = lowered_and_hlo
        for scope in PHASE_SCOPES + STAGE_SCOPES + POLICY_SCOPES:
            assert re.search(rf'[/"(]{scope}[/")]', text), scope

        names = re.findall(r'op_name="([^"]*)"', hlo)
        assert len(names) > 5000
        rest = collections.Counter(
            n for n in names
            if not set(PHASE_SCOPES) & set(_scopes.segments(n))
        )
        share = 1.0 - sum(rest.values()) / len(names)
        # found: 0.952. The rest are the entry's parameters (named after
        # the arguments) and the bodies of reductions, which XLA:CPU names
        # by their kind alone; neither is an operation of a TPU trace.
        assert share > 0.94, (share, rest.most_common(40))
        for phase in PHASE_SCOPES:
            assert sum(phase in _scopes.segments(n) for n in names) > 500

    def test_weight_gradient_products_stay_under_the_core_and_the_update(
        self, lowered_and_hlo
    ):
        """ISSUE 29: the LSTM's backward is written by hand
        (`models/lstm.py`), and a `custom_vjp`'s backward is traced apart
        from its forward. Its two weight-gradient products, the whole
        `[T x B]` contraction, must still carry `policy_core_scan` and
        `phase_update` as segments of their `op_name`: that is where
        `policy_core_share`, `policy_core_roofline` and
        `update_device_share` look for them. And nothing of the core runs
        under the loop's name but the recurrence's one product a step."""
        from benchmark.readers import _scopes

        _, hlo = lowered_and_hlo
        rows = re.findall(
            r'^\s*(?:ROOT )?%[\w.\-]+ = (\S+) dot\(.*op_name="([^"]*)"', hlo, re.M
        )
        products = [
            (shape, name) for shape, name in rows if name.endswith("->hg/dot_general")
        ]
        hidden = tiny_cfg().model.hidden_dim
        assert len(products) == 2, products
        for shape, name in products:
            assert shape.startswith(f"f32[{hidden},{4 * hidden}]"), shape
            assert {"policy_core_scan", "phase_update"} <= set(_scopes.segments(name)), name
            assert "while" not in _scopes.segments(name), name

    def test_sampling_and_loss_look_nothing_up_by_gather(self, lowered_and_hlo):
        """ISSUE 27: the action distribution looks a chosen action's
        log-probability up by compare-select-reduce, so under
        `rollout_sample` and under `update_loss` no `gather` and no
        `scatter` comes from `models/distributions.py` (on the chip they
        were 22% of the small cell's step, PERF.md section 6). ISSUE 31: nor
        does any come from `features/jax_featurizer.py`, whose slot
        translation in `actions_to_sim` was the last under `rollout_sample`.
        What is left under the two scopes is the trunk's hero embedding."""
        from benchmark.readers import _scopes

        _, hlo = lowered_and_hlo
        sources = _hlo_sources(hlo)
        rows = re.findall(
            r'^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(.*op_name="([^"]*)"'
            r"(?: stack_frame_id=(\d+))?",
            hlo, re.M,
        )
        assert len(rows) > 5000
        lookups = [
            (kind, name, sources[int(frame)][0] if frame else "")
            for kind, name, frame in rows
            if "gather" in kind or "scatter" in kind
        ]
        # the hero embedding's are found, so a lookup would be seen if it were there
        assert any("linen/linear.py" in src for _, _, src in lookups)
        for gone in ("models/distributions.py", "features/jax_featurizer.py"):
            assert not [row for row in lookups if gone in row[2]], gone
        left = collections.Counter(
            src.rsplit("/", 2)[-2] + "/" + src.rsplit("/", 1)[-1]
            for _, name, src in lookups
            if {"rollout_sample", "update_loss"} & set(_scopes.segments(name))
        )
        assert set(left) <= {"linen/linear.py"}, left
