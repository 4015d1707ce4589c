"""JAX sim / featurizer / on-device rollout tests.

The numpy ``vec_lane_sim`` is the semantic oracle: the JAX sim is a
phase-for-phase port, so over wave-free horizons (no RNG involved) the two
must agree EXACTLY, scripted bots included. The device rollout path is tested
against the training contract (chunk shapes, train-step consumption, the
mid-chunk done/carry-reset semantics of ``Policy.sequence``).
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import default_config
from dotaclient_tpu.envs import jax_lane_sim as J
from dotaclient_tpu.envs import lane_sim
from dotaclient_tpu.envs.vec_lane_sim import VecLaneSim, VecSimSpec
from dotaclient_tpu.protos import dota_pb2 as pb


def make_pair(n=4, team_size=1, p0=pb.CONTROL_SCRIPTED_EASY,
              p1=pb.CONTROL_SCRIPTED_HARD, seed=0, whole_teams=False, **kw):
    """A numpy vec sim and a JAX state initialized to the SAME world.
    ``p0``/``p1`` control the first player of each team, or with
    ``whole_teams`` every player of it (heroes then cycle the stat table)."""
    spec = VecSimSpec(n_games=n, team_size=team_size, max_units=32, **kw)
    P = spec.n_players
    hero = np.ones((n, P), np.int32)
    ctrl = np.full((n, P), pb.CONTROL_AGENT, np.int32)
    ctrl[:, 0] = p0
    ctrl[:, team_size] = p1
    if whole_teams:
        ctrl[:, :team_size] = p0
        ctrl[:, team_size:] = p1
        hero[:] = 1 + np.arange(P) % 4      # 4 = no table row: generic stats
    vsim = VecLaneSim(spec, hero, ctrl, seed=seed)
    jstate = state_from_vec(vsim)
    return spec, vsim, jstate


def state_from_vec(vsim: VecLaneSim) -> J.SimState:
    # jnp.array COPIES — jnp.asarray can zero-copy-alias the numpy buffers
    # on CPU, which the vec sim then mutates in place (async-read corruption)
    return J.SimState(
        key=jax.random.PRNGKey(0),
        **{
            k: jnp.array(getattr(vsim, "_next_wave_at" if k == "next_wave_at" else k))
            for k in J.SimState._fields
            if k not in ("key", "tick")
        },
        tick=jnp.array(vsim.tick.astype(np.int32)),
    )


def noop(n, P):
    a = {
        k: np.zeros((n, P), np.int32)
        for k in ("type", "move_x", "move_y", "target_slot", "ability")
    }
    a["type"][:] = -1
    return a


STATE_FIELDS = (
    "x", "y", "health", "health_max", "mana", "gold", "xp", "level",
    "alive", "kills", "deaths", "last_hits", "denies", "attack_cd",
    "ability_cd", "done", "winning_team",
)


def driven_actions(vsim, rng):
    """Seeded agent actions for EVERY hero that make the credit path run:
    nuke the weakest enemy hero in range, else attack the lowest-health unit
    the sim would let the hero hit (enemies, and the hero's OWN creeps under
    half health: a deny), else march to mid-lane. One hero in four instead
    draws attack / cast / move uniformly over all 32 target slots, legal or
    not. Computed from the numpy oracle's state only."""
    spec = vsim.spec
    n, P, S = spec.n_games, spec.n_players, spec.max_units
    dist = vsim._pairwise_dist()[:, :P, :]                       # [n, P, S]
    my_team = vsim.team[:, :P, None]
    enemy = vsim.alive[:, None, :] & (vsim.team[:, None, :] != my_team)
    is_creep = (vsim.unit_type == pb.UNIT_LANE_CREEP)[:, None, :]
    deniable = (
        vsim.alive[:, None, :] & ~enemy & is_creep
        & (vsim.health < 0.5 * vsim.health_max)[:, None, :]
    )
    hittable = (enemy | deniable) & (
        dist <= vsim.attack_range[:, :P, None] + 50.0
    )
    hp = np.broadcast_to(vsim.health[:, None, :], dist.shape)
    # creeps before heroes and towers: last hits and denies need the blow
    atk_key = np.where(hittable, hp + np.where(is_creep, 0.0, 1e6), np.inf)
    nukable = (
        enemy & (vsim.unit_type == pb.UNIT_HERO)[:, None, :]
        & (dist <= lane_sim.NUKE_RANGE)
    )
    can_nuke = nukable.any(2) & vsim.hero_castable()[:, :P]
    can_hit = hittable.any(2) & (vsim.attack_cd[:, :P] <= 0.0)

    acts = noop(n, P)
    toward_mid = np.where(vsim.x[:, :P] < 0.0, spec.move_bins - 1, 0)
    acts["type"][:] = pb.ACTION_MOVE
    acts["move_x"][:] = toward_mid
    acts["move_y"][:] = (spec.move_bins - 1) // 2
    acts["type"][can_hit] = pb.ACTION_ATTACK_UNIT
    acts["target_slot"][can_hit] = atk_key.argmin(2)[can_hit]
    acts["type"][can_nuke] = pb.ACTION_CAST
    acts["target_slot"][can_nuke] = np.where(nukable, hp, np.inf).argmin(2)[can_nuke]

    wild = rng.random((n, P)) < 0.25
    acts["type"][wild] = rng.integers(0, 4, size=(n, P))[wild]
    acts["move_x"][wild] = rng.integers(0, spec.move_bins, size=(n, P))[wild]
    acts["move_y"][wild] = rng.integers(0, spec.move_bins, size=(n, P))[wild]
    acts["target_slot"][wild] = rng.integers(0, S, size=(n, P))[wild]
    return acts


def assert_states_equal(vsim, jstate, context=""):
    for name in STATE_FIELDS:
        a = np.asarray(getattr(vsim, name), np.float64)
        b = np.asarray(getattr(jstate, name), np.float64)
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-3, err_msg=f"{context}: field {name}"
        )


class TestJaxSimParity:
    @pytest.mark.parametrize("team_size", [1, 5])
    def test_exact_parity_scripted_wave_free(self, team_size):
        """140 steps (28 s < first wave respawn at 30 s): zero randomness, so
        the JAX port must track the numpy sim exactly — scripted bots, combat,
        last-hits, XP, deaths, towers, the lot. At ``team_size=5`` (the
        benchmark's shape: ten heroes, ``[N, 10]`` lookups into 32 slots) all
        of Radiant is scripted easy and all of Dire hard."""
        spec, vsim, jstate = make_pair(
            n=4, team_size=team_size, whole_teams=team_size > 1
        )
        step = jax.jit(lambda s, a: J.step(spec, s, a))
        acts = noop(4, spec.n_players)
        jacts = {k: jnp.asarray(v) for k, v in acts.items()}
        for t in range(140):
            vsim.step(acts)
            jstate = step(jstate, jacts)
        assert_states_equal(vsim, jstate, "t=140")

    @pytest.mark.parametrize("team_size", [1, 5])
    def test_exact_parity_agent_actions(self, team_size):
        """Driven hero actions (attack / cast / move) resolve identically.
        1v1: random actions for player 0 against the hard bot. 5v5 (the
        benchmark's shape, ``scripted_possible=False`` as self-play compiles
        it): all ten heroes by ``driven_actions``, because uniformly random
        actions end 60 steps with no last hit, no deny and no kill, and the
        kill-credit path (``first_p``, ``deny_credit``, ``reduce_p``) never
        runs; the counters are asserted above zero at the end."""
        rng = np.random.default_rng(0)
        if team_size == 1:
            spec, vsim, jstate = make_pair(n=2, p0=pb.CONTROL_AGENT)
            n_steps, scripted = 60, True

            def draw():
                acts = noop(2, 2)
                # random-ish but legal-ish agent actions for player 0
                acts["type"][:, 0] = rng.integers(0, 4, size=2)
                acts["move_x"][:, 0] = rng.integers(0, 9, size=2)
                acts["move_y"][:, 0] = rng.integers(0, 9, size=2)
                acts["target_slot"][:, 0] = rng.integers(0, 32, size=2)
                acts["ability"][:, 0] = 0
                return acts
        else:
            spec, vsim, jstate = make_pair(
                n=3, team_size=team_size, whole_teams=True,
                p0=pb.CONTROL_AGENT, p1=pb.CONTROL_AGENT,
            )
            n_steps, scripted = 140, False

            def draw():
                return driven_actions(vsim, rng)

        step = jax.jit(
            lambda s, a: J.step(spec, s, a, scripted_possible=scripted)
        )
        for t in range(n_steps):
            acts = draw()
            vsim.step(acts)
            jstate = step(jstate, {k: jnp.asarray(v) for k, v in acts.items()})
        assert_states_equal(vsim, jstate, f"agent-driven t={n_steps}")
        if team_size > 1:
            for name in ("last_hits", "denies", "kills"):
                assert int(getattr(vsim, name).sum()) > 0, name
                assert int(getattr(jstate, name).sum()) > 0, name

    def test_full_episode_statistics(self):
        """Across full episodes (waves spawn → RNG differs) the port must
        still produce the same game: hard beats easy, games end."""
        spec = VecSimSpec(n_games=16, team_size=1, max_units=32, max_dota_time=300.0)
        hero = np.ones((16, 2), np.int32)
        ctrl = np.stack(
            [np.full(16, pb.CONTROL_SCRIPTED_EASY),
             np.full(16, pb.CONTROL_SCRIPTED_HARD)], 1
        )
        state = J.init_state(spec, jnp.asarray(hero), jnp.asarray(ctrl),
                             jax.random.PRNGKey(0))
        step = jax.jit(lambda s, a: J.step(spec, s, a))
        a = {k: jnp.asarray(v) for k, v in noop(16, 2).items()}
        for _ in range(1600):
            state = step(state, a)
            if bool(state.done.all()):
                break
        assert bool(state.done.all())
        # timeout wins are tower-HP noisy (hard retreats, easy pushes);
        # kills are the robust dominance signal
        hard_wins = int((state.winning_team == lane_sim.TEAM_DIRE).sum())
        assert hard_wins >= 7
        assert int(state.kills[:, 1].sum()) > 5 * int(state.kills[:, 0].sum())

    def test_deterministic_across_runs(self):
        """Regression: damage/credit accumulation must use fixed-order
        reductions — XLA scatter-add combines duplicate indices in
        unspecified order, which made full-battle outcomes flip run to run."""
        results = []
        for _ in range(2):
            spec = VecSimSpec(n_games=8, team_size=1, max_units=32,
                              max_dota_time=120.0)
            hero = np.ones((8, 2), np.int32)
            ctrl = np.stack(
                [np.full(8, pb.CONTROL_SCRIPTED_EASY),
                 np.full(8, pb.CONTROL_SCRIPTED_HARD)], 1
            )
            state = J.init_state(spec, jnp.asarray(hero), jnp.asarray(ctrl),
                                 jax.random.PRNGKey(3))
            step = jax.jit(lambda s, a: J.step(spec, s, a))
            a = {k: jnp.asarray(v) for k, v in noop(8, 2).items()}
            for _ in range(400):
                state = step(state, a)
            results.append(jax.device_get(state))
        for f in J.SimState._fields:
            if f == "key":
                continue
            np.testing.assert_array_equal(
                getattr(results[0], f), getattr(results[1], f),
                err_msg=f"nondeterministic field {f}",
            )

    def test_reset_where(self):
        spec, vsim, jstate = make_pair(n=3)
        step = jax.jit(lambda s, a: J.step(spec, s, a))
        a = {k: jnp.asarray(v) for k, v in noop(3, 2).items()}
        for _ in range(50):
            jstate = step(jstate, a)
        mask = jnp.asarray([False, True, False])
        jstate2 = jax.jit(lambda s, m: J.reset_where(spec, s, m))(jstate, mask)
        assert float(jstate2.dota_time[1]) == 0.0
        assert float(jstate2.dota_time[0]) > 0.0
        assert bool(jstate2.alive[1, :2].all())
        assert float(jstate2.gold[1, :2].sum()) == 0.0


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def _lookup_values(dtype, shape, rng):
    if dtype == "bool":
        return rng.random(shape) < 0.5
    if dtype == "int32":
        return rng.integers(-5, 2**31 - 1, size=shape).astype(np.int32)
    special = np.array(
        [J._BIG, 2.0 * J._BIG, 0.0, -0.0, -17.25, 550.0, 1e-30, np.inf, np.nan],
        np.float32,
    )
    vals = (rng.normal(size=shape) * 300.0).astype(np.float32)
    use = rng.random(shape) < 0.5
    return np.where(use, rng.choice(special, size=shape), vals).astype(np.float32)


class TestUnitLookup:
    """``J._at_slot``: a unit looked up by compare-select-reduce, never an
    XLA gather (ISSUE 25)."""

    N, P, S = 16, 10, 32
    # case: shape of x, shape and range of the index, NumPy's fancy
    # indexing, the same lookup through the helper
    LOOKUPS = {
        "units_by_player": (               # state.health[n, target]
            (N, S), (N, P), S,
            lambda x, i, n: x[n, i],
            lambda x, i: J._at_slot(x[:, None, :], J._slot_mask(i, 32)),
        ),
        "units_by_unit": (                 # state.armor[n, tgt]
            (N, S), (N, S), S,
            lambda x, i, n: x[n, i],
            lambda x, i: J._at_slot(x[:, None, :], J._slot_mask(i, 32)),
        ),
        "dist_row_by_player": (            # dist[n, p, target]
            (N, P, S), (N, P), S,
            lambda x, i, n: x[n, np.arange(10)[None, :], i],
            lambda x, i: J._at_slot(x, J._slot_mask(i, 32)),
        ),
        "players_by_unit": (               # hero_deny[n, first_p]
            (N, P), (N, S), P,
            lambda x, i, n: x[n, i],
            lambda x, i: J._at_slot(
                x[:, :, None],
                i[:, None, :] == jnp.arange(10)[None, :, None],
                axis=1,
            ),
        ),
    }

    @pytest.mark.parametrize("dtype", ["float32", "int32", "bool"])
    @pytest.mark.parametrize("case", sorted(LOOKUPS))
    def test_at_slot_is_numpy_fancy_indexing_bitwise(self, case, dtype):
        """Against NumPy fancy indexing, BIT for bit: ``_BIG``, 0.0, ``-0.0``,
        negatives, and ``inf``/``nan`` both selected and planted in slots
        that are NOT selected (they must not leak into the reduce). Index
        shapes ``[N, P]`` and ``[N, S]``, the 3-D ``dist[n, p, target]`` row
        case, the player-axis case of ``deny_credit``; indices 0 and the
        last slot forced in."""
        x_shape, idx_shape, n_slots, by_numpy, by_helper = self.LOOKUPS[case]
        rng = np.random.default_rng(zlib.crc32(f"{case}/{dtype}".encode()))
        x = _lookup_values(dtype, x_shape, rng)
        idx = rng.integers(0, n_slots, size=idx_shape).astype(np.int32)
        idx[0, 0], idx[1, 1] = 0, n_slots - 1
        want = by_numpy(x, idx, np.arange(self.N)[:, None])
        got = np.asarray(jax.jit(by_helper)(x, idx))
        assert got.dtype == want.dtype and got.shape == want.shape
        if dtype == "float32":
            # the values drawn do hold what the docstring names
            assert np.isnan(x).any() and np.isinf(x).any()
            assert (_bits(x) == 0x80000000).any()
        np.testing.assert_array_equal(_bits(got), _bits(want))

    @pytest.mark.parametrize(
        "program", ["step_selfplay", "step_scripted", "reset_where"]
    )
    def test_sim_compiles_to_no_data_dependent_gather(self, program):
        """The gathers must not come back. On the TPU a data-dependent
        gather runs close to one element at a time: 10.6 ns a value, 1.38 ms
        for ONE ``[4096, 32]`` lookup; the step's ten lookups and what they
        held up were 43% of the small cell's device time, 9.0 ms of a
        simulation step that now takes 0.23 ms (ISSUE 25; PERF.md section
        6). And a gather that names the game axis is not shard-local to the
        partitioner. So: the optimised HLO of ``step`` at 5v5 (as self-play
        compiles it, and with the scripted bots) and of ``reset_where``
        (the hero-stats table) holds no ``gather`` whose index is not a
        constant (static slices that XLA folds may lower however they
        like)."""
        import re

        spec = VecSimSpec(n_games=8, team_size=5, max_units=32)
        P = spec.n_players
        state = jax.eval_shape(
            lambda: J.init_state(
                spec, jnp.ones((8, P), jnp.int32), jnp.zeros((8, P), jnp.int32),
                jax.random.PRNGKey(0),
            )
        )
        if program == "reset_where":
            fn = lambda s, m: J.reset_where(spec, s, m)
            arg = jax.ShapeDtypeStruct((8,), jnp.bool_)
        else:
            fn = lambda s, a: J.step(
                spec, s, a, scripted_possible=program == "step_scripted"
            )
            arg = {
                k: jax.ShapeDtypeStruct((8, P), jnp.int32) for k in noop(8, P)
            }
        hlo = jax.jit(fn).lower(state, arg).compile().as_text()
        defined = dict(
            re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = \S+ ([\w\-]+)\(", hlo, re.M)
        )
        assert "select" in defined.values()     # the text IS the optimised HLO
        gathers = re.findall(
            r"^.* gather\((%[\w.\-]+), (%[\w.\-]+)\).*$", hlo, re.M
        )
        dynamic = [
            (operand, index) for operand, index in gathers
            if defined.get(index) != "constant"
        ]
        assert not dynamic, f"data-dependent gather in {program}: {dynamic}"


class TestJaxFeaturizerParity:
    def test_matches_numpy_featurizer(self):
        from dotaclient_tpu.features.jax_featurizer import JaxFeaturizer
        from dotaclient_tpu.features.vec_featurizer import VecFeaturizer

        cfg = default_config()
        spec, vsim, jstate = make_pair(n=3)
        step = jax.jit(lambda s, a: J.step(spec, s, a))
        acts = noop(3, 2)
        for _ in range(40):
            vsim.step(acts)
            jstate = step(jstate, {k: jnp.asarray(v) for k, v in acts.items()})
        vf = VecFeaturizer(vsim, cfg.obs, cfg.actions, [0])
        jf = JaxFeaturizer(spec, cfg.obs, cfg.actions, [0])
        a = vf.featurize_all()
        b = jax.device_get(jf.featurize(jstate))
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_allclose(
                np.asarray(a[k], np.float64), np.asarray(b[k], np.float64),
                rtol=1e-4, atol=1e-5, err_msg=f"obs field {k}",
            )

    def test_rewards_match_numpy(self):
        from dotaclient_tpu.features.jax_featurizer import shaped_rewards
        from dotaclient_tpu.features.vec_featurizer import VecRewards

        spec, vsim, jstate = make_pair(n=3)
        step = jax.jit(lambda s, a: J.step(spec, s, a))
        acts = noop(3, 2)
        jacts = {k: jnp.asarray(v) for k, v in acts.items()}
        for _ in range(20):
            vsim.step(acts)
            jstate = step(jstate, jacts)
        vr = VecRewards(vsim, [0])
        j_prev = jstate
        for _ in range(10):
            vsim.step(acts)
            jstate = step(jstate, jacts)
        r_np = vr.compute()
        r_j = np.asarray(
            shaped_rewards(spec, [0], j_prev, jstate)
        )
        np.testing.assert_allclose(r_np, r_j, rtol=1e-4, atol=1e-5)


class TestSequenceDoneReset:
    def test_sequence_resets_match_stepwise(self):
        """sequence(obs, carry0, dones) == per-step stepping with carry
        zeroed after each done — the contract device chunks rely on."""
        from dotaclient_tpu.models import init_params, make_policy
        from dotaclient_tpu.models.policy import dummy_obs_batch

        cfg = default_config()
        policy = make_policy(
            dataclasses.replace(cfg.model, dtype="float32"), cfg.obs, cfg.actions
        )
        params = init_params(policy, jax.random.PRNGKey(0))
        B, T = 2, 6
        rng = np.random.default_rng(0)
        obs = dummy_obs_batch(B, cfg.obs, cfg.actions, time=T)
        obs = dict(obs)
        obs["units"] = jnp.asarray(
            rng.normal(size=obs["units"].shape).astype(np.float32)
        )
        dones = jnp.asarray(
            [[0, 0, 1, 0, 0, 0], [0, 1, 0, 0, 1, 0]], jnp.float32
        )
        carry0 = policy.initial_state(B)
        logits_seq, values_seq, _ = policy.apply(
            params, obs, carry0, dones, method="sequence"
        )

        carry = carry0
        step_values = []
        step_logits = []
        for t in range(T):
            obs_t = {k: v[:, t] for k, v in obs.items()}
            lg, vv, carry = policy.apply(params, obs_t, carry, method="step")
            step_values.append(vv)
            step_logits.append(lg["action_type"])
            keep = (1.0 - dones[:, t])[:, None]
            carry = (carry[0] * keep, carry[1] * keep)
        np.testing.assert_allclose(
            np.asarray(values_seq), np.stack([np.asarray(v) for v in step_values], 1),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(logits_seq["action_type"]),
            np.stack([np.asarray(l) for l in step_logits], 1),
            rtol=1e-5, atol=1e-5,
        )


class TestDeviceRollout:
    def _actor(self, n_envs=4, opponent="scripted_easy", team_size=1, **env_kw):
        from dotaclient_tpu.actor.device_rollout import DeviceActor
        from dotaclient_tpu.models import init_params, make_policy

        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(
                cfg.env, n_envs=n_envs, opponent=opponent,
                team_size=team_size, max_dota_time=30.0, **env_kw,
            ),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=8),
        )
        policy = make_policy(cfg.model, cfg.obs, cfg.actions)
        params = init_params(policy, jax.random.PRNGKey(0))
        return cfg, DeviceActor(cfg, policy, seed=0), params

    def test_chunk_contract(self):
        cfg, da, params = self._actor()
        chunk, stats = da.collect(params)
        T = cfg.ppo.rollout_len
        L = da.n_lanes
        assert chunk["obs"]["units"].shape == (
            L, T + 1, cfg.obs.max_units, cfg.obs.unit_features
        )
        assert chunk["rewards"].shape == (L, T)
        assert chunk["valid"].shape == (L, T)
        assert (np.asarray(chunk["valid"]) == 1.0).all()
        assert chunk["carry0"][0].shape == (L, cfg.model.hidden_dim)
        assert set(chunk["actions"]) == set(cfg.actions.head_sizes)

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~38s on the reference container
    def test_feeds_train_step_and_buffer(self):
        from dotaclient_tpu.buffer import TrajectoryBuffer
        from dotaclient_tpu.parallel import make_mesh
        from dotaclient_tpu.train.ppo import init_train_state, make_train_step

        cfg, da, params = self._actor(n_envs=8)
        cfg = dataclasses.replace(
            cfg,
            ppo=dataclasses.replace(cfg.ppo, batch_rollouts=8),
            buffer=dataclasses.replace(cfg.buffer, capacity_rollouts=32, min_fill=8),
        )
        mesh = make_mesh(cfg.mesh)
        buffer = TrajectoryBuffer(cfg, mesh)
        state = init_train_state(params, cfg.ppo)
        step = make_train_step(da.policy, cfg, mesh)
        chunk, _ = da.collect(params)
        assert buffer.add_device(chunk, version=0) == 8
        batch = buffer.take(current_version=0)
        assert batch is not None
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))

    def test_episodes_complete_and_stats(self):
        cfg, da, params = self._actor()
        # 30s timeout / (8 steps * 0.2s) ≈ 19 collects per episode
        for _ in range(25):
            da.collect(params)
        s = da.drain_stats()
        assert s["episodes_done"] >= 4
        assert s["episode_reward_mean"] != 0.0

    def test_selfplay_lanes(self):
        cfg, da, params = self._actor(opponent="selfplay")
        assert da.n_lanes == cfg.env.n_envs * 2
        chunk, _ = da.collect(params)
        assert chunk["rewards"].shape[0] == da.n_lanes

    def test_league_opponent_params_used(self):
        """League mode: opponent lanes run on separate (frozen) params and
        ship nothing; different opponent params must change the game flow."""
        cfg, da, params = self._actor(opponent="league")
        assert da.n_lanes == cfg.env.n_envs  # only Radiant ships
        chunk, _ = da.collect(params, opp_params=params)
        assert chunk["rewards"].shape[0] == cfg.env.n_envs

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~102s on the reference container
    def test_learner_device_mode(self):
        from dotaclient_tpu.train.learner import Learner

        cfg = default_config()
        cfg = dataclasses.replace(
            cfg,
            env=dataclasses.replace(
                cfg.env, n_envs=8, opponent="scripted_easy", max_dota_time=30.0
            ),
            ppo=dataclasses.replace(cfg.ppo, rollout_len=8, batch_rollouts=8),
            buffer=dataclasses.replace(cfg.buffer, capacity_rollouts=32, min_fill=8),
            log_every=100,
        )
        lrn = Learner(cfg, actor="device")
        stats = lrn.train(6)
        assert stats["optimizer_steps"] >= 6
        assert stats["actor_rollouts_shipped"] > 0
