"""Multi-chip learner (ISSUE 10): mesh-sharded train step, device-sharded
trajectory ring, sharded snapshot/checkpoint paths.

tests/conftest.py forces 8 host devices, so every test here runs on a real
8-way mesh; the 1-device comparisons build a second mesh over
``jax.devices()[:1]`` in the same process (make_mesh's explicit-layout
slicing) — exactly how the single-chip degenerate case works.
"""

import dataclasses
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import MeshConfig, RunConfig
from dotaclient_tpu.parallel import (
    batch_shard_count,
    make_mesh,
)
from dotaclient_tpu.train.ppo import (
    example_batch,
    init_train_state,
    make_epoch_step,
    train_state_sharding,
)
from dotaclient_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**over) -> RunConfig:
    # batch_rollouts/capacity stay multiples of 8: batches shard over the
    # 8-way data axis (same rule every sharded-path test file follows)
    cfg = RunConfig()
    return dataclasses.replace(
        cfg,
        env=dataclasses.replace(cfg.env, n_envs=2, max_dota_time=30.0),
        ppo=dataclasses.replace(cfg.ppo, rollout_len=4, batch_rollouts=8),
        buffer=dataclasses.replace(
            cfg.buffer, capacity_rollouts=16, min_fill=8
        ),
        log_every=1000,
        checkpoint_every=1000,
        **over,
    )


def seeded_batch(cfg: RunConfig, seed: int = 0):
    rng = np.random.default_rng(seed)
    B, T = cfg.ppo.batch_rollouts, cfg.ppo.rollout_len
    batch = dict(example_batch(cfg, batch=B))
    batch["obs"] = dict(batch["obs"])
    batch["obs"]["units"] = jnp.asarray(
        rng.normal(size=batch["obs"]["units"].shape).astype(np.float32)
    )
    batch["rewards"] = jnp.asarray(
        rng.normal(size=(B, T)).astype(np.float32) * 0.1
    )
    batch["behavior_logp"] = jnp.asarray(
        -np.abs(rng.normal(size=(B, T))).astype(np.float32)
    )
    return batch


class TestMeshConstruction:
    def test_explicit_layout_slices_devices(self):
        """An explicit data_parallel smaller than the visible device set
        takes the first dcn×data×model devices — the 1-device mesh is the
        degenerate case of the one sharded code path, buildable inside an
        8-device process (the parity probes depend on it)."""
        mesh1 = make_mesh(MeshConfig(data_parallel=1))
        assert mesh1.devices.size == 1
        mesh2 = make_mesh(MeshConfig(data_parallel=1, model_parallel=2))
        assert mesh2.devices.size == 2
        # the default -1 still takes everything
        assert make_mesh(MeshConfig()).devices.size == 8

    def test_batch_shard_count_shared_helper(self):
        cfg = MeshConfig()
        assert batch_shard_count(make_mesh(cfg), cfg) == 8
        assert batch_shard_count(
            make_mesh(MeshConfig(data_parallel=1)),
            MeshConfig(data_parallel=1),
        ) == 1

    def test_mesh_override_flag_parses(self):
        from dotaclient_tpu.utils.overrides import parse_dataclass_overrides

        out = parse_dataclass_overrides(
            MeshConfig, "data_parallel=4,model_parallel=2", "--mesh"
        )
        assert out == {"data_parallel": 4, "model_parallel": 2}
        with pytest.raises(ValueError, match="--mesh"):
            parse_dataclass_overrides(MeshConfig, "nope=1", "--mesh")


class TestShardedParity:
    @pytest.mark.slow   # two epoch-step compiles (1-dev + 8-dev mesh)
    def test_sharded_epoch_step_matches_single_device(self):
        """The 8-way data-sharded fused epoch step (grad psum emitted from
        the shardings) must produce the same updates as the 1-device mesh
        on the same data with the same ``_mb_rng`` permutation stream —
        within float-reassociation tolerance (the psum reorders sums)."""
        from dotaclient_tpu.models import init_params, make_policy

        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            ppo=dataclasses.replace(
                cfg.ppo, epochs_per_batch=2, minibatches=2
            ),
        )
        B, E = cfg.ppo.batch_rollouts, cfg.ppo.epochs_per_batch
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        batch = seeded_batch(cfg)
        results = {}
        for label, devices in (
            ("one", jax.devices()[:1]),
            ("mesh", None),
        ):
            mesh = make_mesh(cfg.mesh, devices=devices)
            st_sh = train_state_sharding(policy, cfg, mesh)
            state = jax.device_put(
                init_train_state(
                    init_params(policy, jax.random.PRNGKey(cfg.seed)),
                    cfg.ppo,
                ),
                st_sh,
            )
            step = make_epoch_step(policy, cfg, mesh)
            mb_rng = np.random.default_rng(cfg.seed + 1)   # learner stream
            losses = []
            for _ in range(3):
                perms = np.stack(
                    [mb_rng.permutation(B) for _ in range(E)]
                ).astype(np.int32)
                state, m = step(state, batch, perms)
                losses.append(float(np.asarray(m["loss"])))
            results[label] = (losses, jax.device_get(state.params))
        l_one, p_one = results["one"]
        l_mesh, p_mesh = results["mesh"]
        # Reassociation tolerance, not ulp: the psum reorders reduction
        # sums and the tiny-config training dynamics amplify the per-step
        # float noise across the 3 steps (measured ~7e-4 relative on this
        # shape). A REAL divergence — dropped minibatch slice,
        # sharding-dependent RNG, wrong perm stream — shows up as O(1).
        np.testing.assert_allclose(l_mesh, l_one, rtol=5e-3, atol=1e-5)
        for a, b in zip(jax.tree.leaves(p_one), jax.tree.leaves(p_mesh)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), rtol=5e-3, atol=1e-4
            )


class TestDirectToShardIngest:
    def _decoded(self, cfg, n, version=0, seed=0):
        """n decoded-payload-shaped (meta, arrays) rows through the real
        wire codec, honoring the config's rollout_wire_dtype."""
        from dotaclient_tpu.transport import serialize as S

        rng = np.random.default_rng(seed)
        row = jax.tree.map(
            lambda x: np.array(x[0]), example_batch(cfg, batch=1)
        )
        flat = S.flatten_tree(row)
        for name, arr in flat.items():
            if arr.dtype == np.float32:
                flat[name] = rng.normal(size=arr.shape).astype(np.float32)
        row = S.unflatten_tree(flat)
        payload = bytes(
            S.encode_rollout_bytes(
                row, version, 0, 0, cfg.ppo.rollout_len, 0.0,
                wire_dtype=cfg.transport.rollout_wire_dtype,
                int_bounds=S.rollout_int_bounds(cfg),
            )
        )
        out = []
        for i in range(n):
            meta, arrays = S.decode_rollout_bytes(payload)
            meta["rollout_id"] = i
            out.append((meta, arrays))
        return out, row

    def test_host_scatter_pins_data_sharded_rows(self):
        """The host ingest path's compiled scatter must take its rows
        DATA-SHARDED (each device receives 1/n of the group's bytes at
        H2D), not replicated — the single-device-memory/replicated-rows
        scatter is the regression this PR exists to fix."""
        from dotaclient_tpu.buffer import TrajectoryBuffer

        cfg = tiny_config()
        mesh = make_mesh(cfg.mesh)
        buf = TrajectoryBuffer(cfg, mesh)
        in_sh = buf._scatter.lower(
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), buf._store
            ),
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((8,) + x.shape[1:], x.dtype),
                buf._store,
            ),
            jax.ShapeDtypeStruct((8,), np.int32),
        ).compile().input_shardings[0]
        # arg order: store tree, rows tree, idx — rows must shard over data
        n_leaves = len(jax.tree.leaves(buf._store))
        rows_shardings = jax.tree.leaves(in_sh)[n_leaves:2 * n_leaves]
        for s in rows_shardings:
            assert not s.is_fully_replicated, (
                f"ingest rows compiled replicated ({s}) — every device "
                f"would receive the full group's bytes"
            )

    def test_ingest_roundtrip_narrow_ring_on_mesh(self):
        """Direct-to-shard ingest through the NARROW (bf16-wire) ring:
        decoded rows scatter to an 8-way-sharded store and ``take()``
        hands back the on-device-upcast batch, bit-identical to decoding
        the wire with upcast — the PR 7 contract carried onto the mesh."""
        try:
            import ml_dtypes  # noqa: F401
        except ImportError:
            pytest.skip("ml_dtypes unavailable")
        from dotaclient_tpu.buffer import TrajectoryBuffer
        from dotaclient_tpu.transport import serialize as S

        cfg = tiny_config()
        cfg = dataclasses.replace(
            cfg,
            transport=dataclasses.replace(
                cfg.transport, rollout_wire_dtype="bfloat16"
            ),
        )
        mesh = make_mesh(cfg.mesh)
        buf = TrajectoryBuffer(cfg, mesh)
        decoded, _ = self._decoded(cfg, 8)
        assert buf.add(decoded, current_version=0) == 8
        # ring leaves live sharded across all 8 devices, in the narrow dtype
        store_leaf = jax.tree.leaves(buf._store)[0]
        assert len(store_leaf.sharding.device_set) == 8
        batch = buf.take(batch_size=8, current_version=0)
        assert batch is not None
        # consumed batch is already laid out for the sharded step
        assert len(batch["rewards"].sharding.device_set) == 8
        assert not batch["rewards"].sharding.is_fully_replicated
        assert batch["rewards"].dtype == jnp.float32   # upcast on-device
        # value parity vs decoding the wire with upcast on the host
        payload_meta, arrays = decoded[0]
        host = S.decode_rollout_bytes(
            bytes(
                S.encode_rollout_bytes(
                    jax.tree.map(np.asarray, arrays), 0, 0, 0,
                    cfg.ppo.rollout_len, 0.0,
                )
            ),
            upcast=True,
        )[1]
        got_row = jax.tree.map(lambda x: np.asarray(x[0]), batch)
        for a, b in zip(jax.tree.leaves(got_row), jax.tree.leaves(host)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pad_rows_shard_divisible_and_trace_bounded(self):
        """Ingest groups pad to shard-divisible pow2 buckets: every padded
        size divides by the 8-way shard count (jax rejects a non-dividing
        NamedSharding) and the retrace bound tightens to
        log2(capacity/n_data)+1 distinct programs."""
        from dotaclient_tpu.buffer import TrajectoryBuffer

        cfg = tiny_config()
        buf = TrajectoryBuffer(cfg, make_mesh(cfg.mesh))
        assert [buf._pad_rows(n) for n in (1, 3, 8, 9, 16)] == [
            8, 8, 8, 16, 16
        ]
        rid = 0
        for n in (1, 3, 5, 8):   # 4 distinct sizes, all → the 8-bucket
            decoded, _ = self._decoded(cfg, n, seed=rid)
            for i, (meta, _a) in enumerate(decoded):
                meta["rollout_id"] = rid + i
            rid += n
            buf.add(decoded, current_version=0)
        assert buf.scatter_traces <= 2   # log2(16/8)+1

    def test_shard_bytes_gauge(self):
        from dotaclient_tpu.buffer import TrajectoryBuffer

        reg = telemetry.Registry()
        cfg = tiny_config()
        buf = TrajectoryBuffer(cfg, make_mesh(cfg.mesh), registry=reg)
        total = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(jax.device_get(buf._store))
        )
        assert reg.snapshot()["buffer/shard_bytes"] == float(total // 8)


class TestCrossDeviceCountRestore:
    def _tiny_state(self):
        params = {
            "dense": {"kernel": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)},
            "scale": jnp.asarray(2.5, jnp.float32),
        }
        return init_train_state(params, RunConfig().ppo)

    def test_checkpoint_restores_across_device_counts(self, tmp_path):
        """A checkpoint written by an 8-device-sharded state restores into
        a 1-device mesh (and vice versa): saves are host-layout arrays —
        device-count-free — and the restore side re-commits via the
        target mesh's state_shardings, exactly what the learner's
        --restore/rollback paths do."""
        from dotaclient_tpu.parallel.sharding import state_shardings
        from dotaclient_tpu.utils.checkpoint import CheckpointManager

        cfg = RunConfig()
        mesh8 = make_mesh(cfg.mesh)
        mesh1 = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        for src_mesh, dst_mesh in ((mesh8, mesh1), (mesh1, mesh8)):
            state = self._tiny_state()
            src_sh = state_shardings(state, src_mesh, cfg.mesh)
            state = jax.device_put(state, src_sh)
            d = tmp_path / f"ck_{src_mesh.devices.size}to{dst_mesh.devices.size}"
            mgr = CheckpointManager(str(d))
            try:
                assert mgr.save(state, cfg, force=True)
                mgr.wait()
                restored, _ = mgr.restore(cfg, abstract_state=state)
            finally:
                mgr.close()
            dst_sh = state_shardings(restored, dst_mesh, cfg.mesh)
            resharded = jax.device_put(restored, dst_sh)
            leaf = jax.tree.leaves(resharded.params)[0]
            assert len(leaf.sharding.device_set) == dst_mesh.devices.size
            for a, b in zip(
                jax.tree.leaves(jax.device_get(state)),
                jax.tree.leaves(jax.device_get(resharded)),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_buffer_state_dict_roundtrips_across_mesh_sizes(self):
        """The ring's state_dict is host arrays; load_state_dict re-commits
        to THIS buffer's sharding — an 8-way ring snapshot restores into a
        1-device ring and back with identical contents."""
        from dotaclient_tpu.buffer import TrajectoryBuffer

        cfg = tiny_config()
        mesh8 = make_mesh(cfg.mesh)
        cfg1 = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, data_parallel=1)
        )
        mesh1 = make_mesh(cfg1.mesh)
        src = TrajectoryBuffer(cfg, mesh8)
        decoded, _ = TestDirectToShardIngest()._decoded(cfg, 8)
        src.add(decoded, current_version=0)
        snap = src.state_dict()
        dst = TrajectoryBuffer(cfg1, mesh1)
        dst.load_state_dict(snap)
        assert dst.size == src.size
        b1 = dst.take(batch_size=8, current_version=0)
        assert len(jax.tree.leaves(b1)[0].sharding.device_set) == 1
        src.load_state_dict(snap)   # and back onto the mesh
        b8 = src.take(batch_size=8, current_version=0)
        for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b8)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestShardedSnapshots:
    @pytest.mark.slow   # learner construction compiles the full pipeline
    def test_zero_train_thread_fetches_for_sharded_snapshots(self):
        """Async publish/checkpoint boundaries on an 8-way-sharded state
        stay DISPATCH-ONLY on the train thread: the on-device copy + the
        engine submit perform zero train-thread device_gets — assembling
        replicated params from shard 0 is the engine thread's job."""
        from dotaclient_tpu.train.learner import Learner

        learner = Learner(tiny_config(), actor="device")
        try:
            learner.train(2)   # compile + warm every boundary program
            train_thread = threading.current_thread()
            calls = {"train": 0}
            real_device_get = jax.device_get

            def counting(x):
                if threading.current_thread() is train_thread:
                    calls["train"] += 1
                return real_device_get(x)

            jax.device_get = counting
            try:
                for _ in range(3):
                    learner._publish_weights()
                learner._snap_engine.submit_checkpoint(
                    learner._snap_copy(learner.state), learner.config
                )
            finally:
                jax.device_get = real_device_get
            assert calls["train"] == 0, (
                f"{calls['train']} device fetch(es) on the train thread "
                f"during sharded snapshot boundaries — the boundary must "
                f"stay dispatch-only"
            )
            assert learner._snap_engine.drain(timeout=30)
        finally:
            if learner._snap_engine is not None:
                learner._snap_engine.stop()

    @pytest.mark.slow   # learner construction compiles the full pipeline
    def test_learner_state_committed_to_mesh_and_telemetry(self):
        """The constructor commits the TrainState to its state_shardings
        (every param leaf lives on all 8 devices before the first
        dispatch) and eager-creates the --require-multichip keys."""
        from dotaclient_tpu.train.learner import Learner

        learner = Learner(tiny_config(), actor="device")
        try:
            leaf = jax.tree.leaves(learner.state.params)[0]
            assert len(leaf.sharding.device_set) == 8
            snap = telemetry.get_registry().snapshot()
            assert snap["mesh/n_devices"] == 8.0
            assert snap["mesh/data_shards"] == 8.0
            assert snap["buffer/shard_bytes"] > 0
        finally:
            if learner._snap_engine is not None:
                learner._snap_engine.stop()


class TestSchema:
    def _load_script(self, name):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "scripts", f"{name}.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_require_multichip_tier(self):
        """--require-multichip pins exactly the eager-created mesh keys."""
        mod = self._load_script("check_telemetry_schema")
        base = {k: 1.0 for k in mod.REQUIRED_KEYS}
        for root in {
            k.rsplit("/", 1)[0]
            for k in mod.REQUIRED_KEYS
            if k.startswith("span/")
        }:
            for leaf in mod.TIMER_LEAVES:
                base[f"{root}/{leaf}"] = 1.0
        full = dict(base)
        full.update({k: 8.0 for k in mod.MULTICHIP_KEYS})
        line = json.dumps({"ts": 1.0, "step": 0, "scalars": full})
        assert mod.validate_lines(
            [line], extra_required=mod.MULTICHIP_KEYS
        ) == []
        missing = dict(full)
        del missing["mesh/n_devices"]
        line = json.dumps({"ts": 1.0, "step": 0, "scalars": missing})
        errs = mod.validate_lines([line], extra_required=mod.MULTICHIP_KEYS)
        assert any("mesh/n_devices" in e for e in errs)
