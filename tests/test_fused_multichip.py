"""Pod-scale fused Anakin tests (PR 18): the lane-sharded one-dispatch
program.

The tentpole's contract, pinned from four sides:

* the COMPILED fused program takes its actor state lane-sharded (the
  ``input_shardings`` proof — a replicated layout means broadcast
  rollouts even when the numbers still agree);
* the lane-sharded rollout is BITWISE the 1-device rollout in-process
  (per-game keys partition random-bit generation with the games; stat
  partials reduce only the step axis — the rollout has no collective to
  reassociate, which ``test_rollout_phase_compiles_to_no_collective`` pins
  on the compiled program since PR 25: until then the simulation's
  gathers over the game axis cost six all-gathers and an all-reduce in
  every rollout step on the v5e, results unchanged; the cross-process
  ``--fused-parity`` digest allows 1e-7 relative for backend tiling
  differences) and fused losses track within Adam-amplified
  reassociation tolerance;
* the shard-local minibatch permutation (``lane_minibatches``) is
  deterministic in (seed, step), partitions the lane set exactly, and
  never moves a lane across shards;
* actor state round-trips host-layout across mesh sizes (8→1 and 1→8),
  because the per-game partial shapes are shard-count independent.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import default_config


def tiny_cfg(n_envs=8, opponent="scripted_easy", small_model=False):
    cfg = default_config()
    model = dataclasses.replace(cfg.model, dtype="float32")
    if small_model:
        # layout/error-path tests never check learned behaviour — a
        # narrow core keeps their construction cost out of tier-1
        model = dataclasses.replace(
            model, unit_embed_dim=8, hidden_dim=16, hero_embed_dim=4
        )
    return dataclasses.replace(
        cfg,
        model=model,
        ppo=dataclasses.replace(cfg.ppo, rollout_len=4, batch_rollouts=8),
        env=dataclasses.replace(
            cfg.env, n_envs=n_envs, opponent=opponent, max_dota_time=60.0
        ),
        buffer=dataclasses.replace(
            cfg.buffer, capacity_rollouts=16, min_fill=8
        ),
        log_every=1,
    )


def _build(cfg, mesh, seed=3):
    from dotaclient_tpu.actor.device_rollout import DeviceActor
    from dotaclient_tpu.models import init_params, make_policy
    from dotaclient_tpu.train.ppo import init_train_state, train_state_sharding

    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    actor = DeviceActor(
        cfg, policy, seed=seed, mesh=mesh, mesh_config=cfg.mesh
    )
    state = jax.device_put(
        init_train_state(
            init_params(policy, jax.random.PRNGKey(0)), cfg.ppo
        ),
        train_state_sharding(policy, cfg, mesh),
    )
    return policy, actor, state


class TestLaneShardedCompile:
    @pytest.mark.slow   # full fused compile at 8 devices, ~27s; the same
    # proof runs on every ci_gate pass via the fused-parity stage's probe
    def test_fused_step_pins_lane_sharded_actor_state(self):
        """The compiled program's actor-state argument must hold
        DATA-SHARDED lane arrays — sim worlds, carries, per-game keys,
        episode returns, stat partials — with only true scalars and the
        sim's batch-wide key replicated."""
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step

        cfg = tiny_cfg()
        mesh = make_mesh(cfg.mesh)   # conftest's 8 forced host devices
        policy, actor, state = _build(cfg, mesh)
        assert actor.lane_shards == 8
        fused = make_fused_step(policy, cfg, mesh, actor)
        in_sh = fused.lower(
            state, actor.state, state.params
        ).compile().input_shardings[0]
        actor_sh = in_sh[1]
        assert not actor_sh.ep_return.is_fully_replicated
        assert not actor_sh.key.is_fully_replicated       # per-game [N, 2]
        assert not actor_sh.carry[0].is_fully_replicated  # lane-major LSTM
        assert actor_sh.sim.key.is_fully_replicated       # batch-wide [2]
        sharded = [
            s for s in jax.tree.leaves(actor_sh)
            if not s.is_fully_replicated
        ]
        # the bulk of the state must be partitioned, not a token leaf
        assert len(sharded) >= len(jax.tree.leaves(actor_sh)) // 2

    def test_rollout_phase_compiles_to_no_collective(self):
        """No operation written under ``phase_rollout`` may compile to a
        collective: every lane's rollout is local to its shard. Until PR 25
        this did not hold. ``envs/jax_lane_sim.py`` looked units up with
        ``state.x[arange(N)[:, None], idx]``, a gather that names the
        sharded game axis; the partitioner cannot prove that index local,
        so it all-gathered the indices and all-reduced the results inside
        the rollout's loop body (v5e 2x2: six all-gathers and one all-reduce
        a simulation step, 2.35% of device time in flight; forced host
        devices: eight and three, all named ``rollout_sim_step/gather``).
        The lookups are compare-select-reduce along the unit axis now and
        name no game axis. The only collectives left belong to the update
        (gradient and loss reductions)."""
        import re

        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step

        # self-play, as the benchmark's cells compile the simulation
        # (scripted_possible=False); a narrow model: only the layout counts
        cfg = tiny_cfg(opponent="selfplay", small_model=True)
        mesh = make_mesh(cfg.mesh)   # conftest's 8 forced host devices
        policy, actor, state = _build(cfg, mesh)
        assert actor.lane_shards == 8
        hlo = make_fused_step(policy, cfg, mesh, actor).lower(
            state, actor.state, state.params
        ).compile().as_text()
        collectives = [
            (kind, op_name)
            for kind, op_name in re.findall(
                # `.*?`: a combined all-reduce's result is a tuple, with spaces
                r"= .*? (all-gather|all-reduce|all-to-all|reduce-scatter|"
                r"collective-permute|collective-broadcast)(?:-start)?\("
                r".*?op_name=\"([^\"]*)\"",
                hlo,
            )
        ]
        # the program IS partitioned and its scopes reach the HLO: the
        # update's reductions are there, the rollout's operations are named
        assert any("phase_update" in name for _, name in collectives)
        assert "phase_rollout/while/body" in hlo
        in_rollout = [c for c in collectives if "phase_rollout" in c[1]]
        assert not in_rollout, in_rollout
        assert not [c for c in collectives if c[0] == "all-gather"], collectives
        # The update reduces three times, each ONCE a minibatch: the two
        # scalar sums of the loss and one all-reduce of the whole gradient.
        # Until PR 29 this backend had a fourth INSIDE the LSTM's backward
        # loop (each step's weight gradient, reduced before it was added to
        # the accumulator); the v5e's compiler had six either way
        # (compile_for_topology.py, PERF.md section 6). `models/lstm.py`
        # contracts over the lane-sharded batch axis once, after the loop.
        in_update = [c for c in collectives if "phase_update" in c[1]]
        assert len(in_update) == len(collectives) == 3, collectives
        assert not [c for c in in_update if "/while/" in c[1]], in_update

    def test_degenerate_games_fall_back_to_replicated(self):
        """4 games on an 8-way mesh cannot lane-shard: the layout must
        degrade to replicated (lane_shards == 1) instead of failing."""
        from dotaclient_tpu.parallel import make_mesh

        cfg = tiny_cfg(n_envs=4, small_model=True)
        mesh = make_mesh(cfg.mesh)
        _, actor, _ = _build(cfg, mesh)
        assert actor.lane_shards == 1
        assert actor.lanes_per_shard == actor.n_lanes
        for leaf in jax.tree.leaves(actor.state):
            assert leaf.sharding.is_fully_replicated


class TestShardCountParity:
    @pytest.mark.slow   # two mesh sizes × (rollout + fused) compiles, ~1 min
    def test_rollout_bitwise_and_losses_close_8_vs_1(self):
        """Same seeds, 8-way lane-sharded vs 1-device: the rollout chunk
        must be BYTE-IDENTICAL (no collective in the rollout:
        ``test_rollout_phase_compiles_to_no_collective``), and fused
        losses over 3 dispatches must agree within the Adam-amplified
        reassociation tolerance (the gradient psum reorders sums;
        ``1/(sqrt(v)+eps)`` amplifies ~1e-7 deltas on near-zero-gradient
        coordinates — scripts/run_multichip.py --fused-parity gates the
        same three tiers cross-process)."""
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step

        cfg = tiny_cfg()
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=1)
        )
        mesh8 = make_mesh(cfg.mesh)
        mesh1 = make_mesh(cfg.mesh, devices=jax.devices()[:1])

        runs = {}
        for name, mesh in (("8", mesh8), ("1", mesh1)):
            policy, actor, state = _build(cfg, mesh)
            _, chunk, _ = jax.jit(actor._rollout_impl)(
                state.params, actor.state, state.params
            )
            fused = make_fused_step(policy, cfg, mesh, actor)
            ast, losses = actor.state, []
            for _ in range(3):
                state, ast, metrics, _stats = fused(
                    state, ast, state.params
                )
                losses.append(float(np.asarray(metrics["loss"])))
            runs[name] = (jax.device_get(chunk), losses)

        chunk8, losses8 = runs["8"]
        chunk1, losses1 = runs["1"]
        for a, b in zip(jax.tree.leaves(chunk8), jax.tree.leaves(chunk1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(losses8, losses1):
            assert abs(a - b) <= max(1e-3, 2e-2 * abs(a)), (losses8, losses1)

    def test_outcome_partials_shard_local_and_reduce_invariant(self):
        """Per-game outcome partials computed on game slices equal the
        matching rows of the full-batch partials (nothing crosses the
        game axis), and the host-side reduction is bitwise independent
        of how the games were split."""
        from dotaclient_tpu.outcome import ingraph

        T, N = 6, 8
        rng = np.random.default_rng(7)
        ep_done = jnp.asarray(rng.random((T, N)) < 0.3)
        win = jnp.asarray(rng.random((T, N)) < 0.5)
        ep_len = jnp.asarray(
            rng.integers(1, 2000, size=(T, N)).astype(np.float32)
        ) * ep_done
        full = ingraph.chunk_outcome_partials(ep_done, win, ep_len)
        for s0, s1 in ((0, 4), (4, 8)):
            part = ingraph.chunk_outcome_partials(
                ep_done[:, s0:s1], win[:, s0:s1], ep_len[:, s0:s1]
            )
            for k, v in part.items():
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(full[k][s0:s1])
                )
        reduced = ingraph.reduce_outcome_stats(full)
        direct = ingraph.chunk_outcome_stats(ep_done, win, ep_len)
        for k in reduced:
            np.testing.assert_array_equal(
                np.asarray(reduced[k]), np.asarray(direct[k])
            )


class TestShardLocalShuffle:
    def _lanes(self, L):
        return {"x": jnp.arange(L, dtype=jnp.int32)}

    def test_permutation_deterministic_and_partitioning(self):
        from dotaclient_tpu.train.fused import lane_minibatches

        L, S, M = 32, 8, 2
        a = lane_minibatches(self._lanes(L), jnp.asarray(5), 0, L, S, M)
        b = lane_minibatches(self._lanes(L), jnp.asarray(5), 0, L, S, M)
        np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
        c = lane_minibatches(self._lanes(L), jnp.asarray(6), 0, L, S, M)
        assert not np.array_equal(np.asarray(a["x"]), np.asarray(c["x"]))
        # exact partition: every lane appears exactly once across the
        # minibatches
        flat = np.sort(np.asarray(a["x"]).ravel())
        np.testing.assert_array_equal(flat, np.arange(L))

    def test_permutation_never_crosses_shards(self):
        """Each minibatch takes exactly Ls/M lanes from every shard's
        contiguous lane block — the gather is local, so minibatching
        adds no collective."""
        from dotaclient_tpu.train.fused import lane_minibatches

        L, S, M = 32, 8, 2
        Ls = L // S
        out = np.asarray(
            lane_minibatches(self._lanes(L), jnp.asarray(11), 3, L, S, M)["x"]
        )
        assert out.shape == (M, L // M)
        for m in range(M):
            for s in range(S):
                in_block = np.sum(
                    (out[m] >= s * Ls) & (out[m] < (s + 1) * Ls)
                )
                assert in_block == Ls // M, (m, s, out[m])


class TestCrossShardCountActorRestore:
    @pytest.mark.slow   # two mesh sizes × rollout compiles, ~40s
    def test_actor_state_roundtrips_8_to_1_and_back(self):
        """The fused pipeline checkpoint stores the actor state as
        host-layout numpy (shard-count-free, because stats are per-game
        partials); re-committing through actor_state_sharding on a
        DIFFERENT mesh size must reproduce the source rollout bitwise —
        the learner's _restore_pipeline path in both directions."""
        from dotaclient_tpu.actor.device_rollout import actor_state_sharding
        from dotaclient_tpu.parallel import make_mesh

        cfg = tiny_cfg()
        mesh8 = make_mesh(cfg.mesh)
        mesh1 = make_mesh(cfg.mesh, devices=jax.devices()[:1])
        for src_mesh, dst_mesh in ((mesh8, mesh1), (mesh1, mesh8)):
            policy, actor, state = _build(cfg, src_mesh)
            # advance once so the restored state is non-trivial
            roll = jax.jit(actor._rollout_impl)
            ast, _chunk0, _ = roll(state.params, actor.state, state.params)
            host = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), ast
            )
            _, dst_actor, dst_state = _build(cfg, dst_mesh)
            committed = jax.device_put(
                host, actor_state_sharding(host, dst_mesh, cfg.mesh)
            )
            # the SECOND rollout, from the same advanced state, on each
            # mesh — identical params (same init key), so byte-equal
            _, src_chunk, _ = roll(state.params, ast, state.params)
            _, dst_chunk, _ = jax.jit(dst_actor._rollout_impl)(
                dst_state.params, committed, dst_state.params
            )
            for a, b in zip(
                jax.tree.leaves(jax.device_get(src_chunk)),
                jax.tree.leaves(jax.device_get(dst_chunk)),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDivisibilityError:
    def test_minibatch_lane_divisibility_pinned_message(self):
        """32 lanes / 8 shards / 3 minibatches cannot split: the fused
        constructor must raise a clear ValueError naming the operative
        product — never the opaque mid-compile XLA reshape error."""
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.fused import make_fused_step

        cfg = tiny_cfg(n_envs=32, small_model=True)
        cfg = dataclasses.replace(
            cfg, ppo=dataclasses.replace(cfg.ppo, minibatches=3)
        )
        mesh = make_mesh(cfg.mesh)
        policy, actor, _state = _build(cfg, mesh)
        with pytest.raises(ValueError, match="divisible"):
            make_fused_step(policy, cfg, mesh, actor)
