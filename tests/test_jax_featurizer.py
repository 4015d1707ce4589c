"""``features.jax_featurizer`` looks nothing up by an XLA ``gather`` (ISSUE 31).

An index known when the program is traced (the lane permutation, the agent
players) is slices and selects under constant masks; the one index that is
data (the sampled target slot) is compare-select-reduce over the slot axis.
The PARENT's indexing is kept here as fancy indexing: the helpers and the
three functions built on them must equal it bit for bit.
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import default_config
from dotaclient_tpu.envs import jax_lane_sim as J
from dotaclient_tpu.envs.lane_sim import NUKE_RANGE, TEAM_RADIANT
from dotaclient_tpu.envs.vec_lane_sim import VecSimSpec
from dotaclient_tpu.features import featurizer as F
from dotaclient_tpu.features import jax_featurizer as JF
from dotaclient_tpu.protos import dota_pb2 as pb
from tests.test_jax_sim import _bits, _lookup_values as _values

N, TEAM_SIZE, S = 6, 5, 32
SPEC = VecSimSpec(n_games=N, team_size=TEAM_SIZE, max_units=S)
P = SPEC.n_players

# self-play, either team of a league cell, one player, a tuple that is no run
PLAYERS = {
    "all_ten": tuple(range(10)),
    "radiant": (0, 1, 2, 3, 4),
    "dire": (5, 6, 7, 8, 9),
    "one": (0,),
    "scattered": (0, 2, 7),
}
DTYPES = ["float32", "int32", "bool"]
by_players = pytest.mark.parametrize("players", sorted(PLAYERS))
by_dtype = pytest.mark.parametrize("dtype", DTYPES)


def _rng(*key):
    return np.random.default_rng(zlib.crc32("/".join(key).encode()))


def _featurizer(players):
    cfg = default_config()
    return JF.JaxFeaturizer(SPEC, cfg.obs, cfg.actions, PLAYERS[players])


def _state(rng):
    """A ``SimState`` of plausible games: every unit type, both teams, dead
    and alive units, and (in ``x``, ``y``, ``mana``, ``gold``) ``-0.0`` and
    ``inf``."""
    shapes = jax.eval_shape(
        lambda: J.init_state(
            SPEC, jnp.ones((N, P), jnp.int32), jnp.zeros((N, P), jnp.int32),
            jax.random.PRNGKey(0),
        )
    )
    fields = {}
    for name, sds in shapes._asdict().items():
        if sds.dtype == jnp.bool_:
            fields[name] = rng.random(sds.shape) < 0.7
        elif sds.dtype == jnp.int32:
            fields[name] = rng.integers(0, 9, size=sds.shape).astype(np.int32)
        elif sds.dtype == jnp.float32:
            fields[name] = (rng.random(sds.shape) * 900.0).astype(np.float32)
        else:
            fields[name] = np.zeros(sds.shape, sds.dtype)
    fields["unit_type"] = rng.integers(0, 4, size=(N, S)).astype(np.int32)
    fields["unit_type"][:, :P] = pb.UNIT_HERO
    fields["team"] = rng.integers(2, 4, size=(N, S)).astype(np.int32)
    fields["winning_team"] = rng.integers(0, 4, size=(N,)).astype(np.int32)
    for name in ("x", "y", "mana", "gold"):
        where = rng.random((N, S)) < 0.2
        fields[name] = np.where(
            where, rng.choice(np.array([-0.0, np.inf], np.float32), size=(N, S)),
            fields[name],
        )
    return J.SimState(**{k: jnp.asarray(v) for k, v in fields.items()})


class TestHelpersAreFancyIndexing:
    """Each helper against NumPy fancy indexing, BIT for bit."""

    @by_dtype
    @by_players
    def test_permute_is_arr_perm(self, players, dtype):
        feat = _featurizer(players)
        x = _values(dtype, (N, S), _rng("permute", players, dtype))
        want = x[:, feat.perm]
        got = np.asarray(jax.jit(feat.permute)(x))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))

    @by_dtype
    @by_players
    def test_take_static_is_arr_players(self, players, dtype):
        ap = PLAYERS[players]
        x = _values(dtype, (N, S), _rng("take", players, dtype))
        want = x[:, np.asarray(ap)]
        got = np.asarray(jax.jit(lambda a: JF.take_static(a, ap))(x))
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))

    @by_dtype
    @by_players
    def test_scatter_players_is_at_set(self, players, dtype):
        ap = PLAYERS[players]
        rng = _rng("scatter", players, dtype)
        col = _values(dtype, (N, len(ap)), rng)
        fill = _values(dtype, (), rng)
        want = np.full((N, P), fill, col.dtype)
        want[:, np.asarray(ap)] = col
        got = np.asarray(
            jax.jit(lambda c: JF.scatter_players(c, ap, P, fill))(col)
        )
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))

    @by_players
    def test_sim_slot_is_take_along_axis(self, players):
        """Every slot 0..31 for every agent, and, through ``actions_to_sim``,
        slots out of range, which clip to the ends as they did."""
        feat = _featurizer(players)
        A = len(feat.agent_players)
        slots = np.broadcast_to(np.arange(S, dtype=np.int32)[:, None], (S, A))
        want = np.take_along_axis(
            np.broadcast_to(feat.perm[None], (S, A, S)), slots[..., None], axis=2
        )[..., 0]
        got = np.asarray(jax.jit(feat.sim_slot)(slots))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)

        packed = np.zeros((N, A, 5), np.int32)
        packed[..., 3] = _rng("clip", players).choice(
            np.array([-7, -1, 0, 31, 32, 1000], np.int32), size=(N, A)
        )
        sim = jax.jit(feat.actions_to_sim)(packed.reshape(N * A, 5))
        want = feat.perm[np.arange(A)[None, :], np.clip(packed[..., 3], 0, S - 1)]
        np.testing.assert_array_equal(
            np.asarray(sim["target_slot"])[:, np.asarray(feat.agent_players)], want
        )


def _parent_featurize(feat, state):
    """``JaxFeaturizer.featurize`` as the parent commit wrote it, kept as
    the reference: fifteen fields gathered by ``arr[:, perm]``, every
    column built per agent in ``[N, A, S]``, one 22-column stack."""
    spec = feat.spec
    N, S, P = spec.n_games, spec.max_units, spec.n_players
    A = len(feat.agent_players)
    ap = jnp.asarray(feat.agent_players, jnp.int32)
    perm = jnp.asarray(feat.perm)

    def g(arr):
        return arr[:, perm]                                # [N, A, S]

    unit_type = g(state.unit_type)
    team = g(state.team)
    alive = g(state.alive)
    x, y = g(state.x), g(state.y)
    health, health_max = g(state.health), g(state.health_max)
    mana, mana_max = g(state.mana), g(state.mana_max)
    castable = g(J.hero_castable(state))

    my_team = state.team[:, ap][:, :, None]
    sign = jnp.where(my_team == TEAM_RADIANT, 1.0, -1.0)
    me_x = state.x[:, ap][:, :, None]
    me_y = state.y[:, ap][:, :, None]
    me_alive = state.alive[:, ap]

    present = (unit_type != 0) & (alive | (unit_type == pb.UNIT_HERO))
    is_hero = unit_type == pb.UNIT_HERO
    is_creep = unit_type == pb.UNIT_LANE_CREEP
    is_tower = unit_type == pb.UNIT_TOWER
    is_ally = (team == my_team) & present
    is_self = jnp.zeros((N, A, S), bool).at[:, :, 0].set(present[:, :, 0])
    dx = (x - me_x) * sign / F._POS_SCALE
    dy = (y - me_y) / F._POS_SCALE
    dist = jnp.hypot(x - me_x, y - me_y)
    deniable = is_ally & ~is_self & is_creep & (health < 0.5 * health_max)

    cols = (
        is_hero, is_creep, is_tower, is_ally, present & ~is_ally, is_self,
        x * sign / F._POS_SCALE, y / F._POS_SCALE, dx, dy, dist / F._POS_SCALE,
        health / jnp.maximum(health_max, 1.0), health_max / F._HP_SCALE,
        mana / jnp.maximum(mana_max, 1.0),
        g(state.damage) / F._DMG_SCALE,
        g(state.attack_range) / F._RANGE_SCALE,
        g(state.move_speed) / F._SPEED_SCALE,
        g(state.armor) / F._ARMOR_SCALE,
        g(state.level) / F._LEVEL_SCALE, alive, castable, deniable,
    )
    f = jnp.stack([c.astype(jnp.float32) for c in cols], axis=-1)
    f = f * present[..., None]

    self_castable = castable[:, :, 0]
    cast_range = jnp.where(self_castable, NUKE_RANGE, 0.0)[:, :, None]
    is_enemy = present & (team != my_team)
    attackable = (
        present & alive & (is_enemy | deniable) & ~is_self
        & me_alive[:, :, None]
    )
    cast_tgt = is_enemy & alive & (dist <= cast_range) & me_alive[:, :, None]

    mask_action = (
        jnp.zeros((N, A, feat.action_spec.n_action_types), bool)
        .at[..., pb.ACTION_NOOP].set(True)
        .at[..., pb.ACTION_MOVE].set(me_alive)
        .at[..., pb.ACTION_ATTACK_UNIT].set(attackable.any(-1))
        .at[..., pb.ACTION_CAST].set(self_castable & cast_tgt.any(-1))
    )
    mask_ability = (
        jnp.zeros((N, A, feat.action_spec.max_abilities), bool)
        .at[..., 0].set(mask_action[..., pb.ACTION_CAST])
    )

    tower_r, tower_d = spec.tower_lo, spec.tower_lo + 1
    tower_hp = jnp.stack(
        [
            state.health[:, tower_r] / jnp.maximum(state.health_max[:, tower_r], 1.0),
            state.health[:, tower_d] / jnp.maximum(state.health_max[:, tower_d], 1.0),
        ],
        axis=1,
    )
    team_row = state.team[:, :P]
    kills_rad = (state.kills[:, :P] * (team_row == TEAM_RADIANT)).sum(1)
    kills_dire = (state.kills[:, :P] * (team_row != TEAM_RADIANT)).sum(1)
    i_rad = my_team[:, :, 0] == TEAM_RADIANT
    kill_diff = jnp.where(
        i_rad, (kills_rad - kills_dire)[:, None], (kills_dire - kills_rad)[:, None]
    ).astype(jnp.float32)
    own_tower = jnp.where(i_rad, tower_hp[:, 0:1], tower_hp[:, 1:2])
    enemy_tower = jnp.where(i_rad, tower_hp[:, 1:2], tower_hp[:, 0:1])

    gl = jnp.stack(
        [
            jnp.broadcast_to((state.dota_time / F._TIME_SCALE)[:, None], (N, A)),
            jnp.where(i_rad, 1.0, -1.0),
            state.gold[:, ap] / F._GOLD_SCALE,
            state.xp[:, ap] / F._XP_SCALE,
            state.level[:, ap] / F._LEVEL_SCALE,
            kill_diff / 10.0,
            own_tower,
            enemy_tower,
        ],
        axis=-1,
    ).astype(jnp.float32)
    pad = feat.obs_spec.global_features - gl.shape[-1]
    if pad:
        gl = jnp.concatenate([gl, jnp.zeros((N, A, pad), jnp.float32)], -1)

    L = N * A

    def flat(arr):
        return arr.reshape((L,) + arr.shape[2:])

    return {
        "units": flat(f),
        "unit_mask": flat(present),
        "unit_handles": jnp.broadcast_to(
            (perm + 1).astype(jnp.int32)[None], (N, A, S)
        ).reshape(L, S),
        "globals": flat(gl),
        "hero_id": state.hero_ids[:, ap].reshape(-1).astype(jnp.int32),
        "mask_action_type": flat(mask_action),
        "mask_target_unit": flat(attackable),
        "mask_cast_target": flat(cast_tgt),
        "mask_ability": flat(mask_ability),
    }


@pytest.fixture
def parent_indexing(monkeypatch):
    """The module's player selection, its inverse and the slot translation
    as the parent wrote them: fancy indexing, ``.at[].set`` and
    ``take_along_axis`` (XLA gathers and scatters)."""

    def take(arr, index, fill=0):
        return arr[:, jnp.asarray(index, jnp.int32)]

    def scatter(col, players, n_players, fill):
        ap = jnp.asarray(players, jnp.int32)
        return jnp.full((col.shape[0], n_players), fill, col.dtype).at[:, ap].set(col)

    def sim_slot(self, obs_slot):
        n, a = obs_slot.shape
        perm = jnp.broadcast_to(jnp.asarray(self.perm, jnp.int32)[None], (n, a, S))
        return jnp.take_along_axis(perm, obs_slot[..., None], axis=2)[..., 0]

    def apply():
        monkeypatch.setattr(JF, "take_static", take)
        monkeypatch.setattr(JF, "scatter_players", scatter)
        monkeypatch.setattr(JF.JaxFeaturizer, "sim_slot", sim_slot)

    return apply


def _assert_trees_bit_equal(got, want):
    """Bit for bit, but for a NaN's sign and payload: ``inf - inf`` is
    arithmetic, not a copy, and which NaN it gives follows the instruction
    the compiler picked (the same function differs there from itself run
    eagerly)."""
    assert set(got) == set(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        if g.dtype == np.float32:
            g, w = (np.where(np.isnan(a), np.float32(np.nan), a) for a in (g, w))
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=k)


class TestFunctionsEqualParentsBitwise:
    """``featurize`` against the parent's, kept above; ``actions_to_sim`` and
    ``shaped_reward_terms``, whose bodies only changed their lookups, against
    themselves with the parent's indexing."""

    def _both(self, parent_indexing, fn, *args):
        got = jax.device_get(jax.jit(fn)(*args))
        parent_indexing()
        want = jax.device_get(jax.jit(lambda *a: fn(*a))(*args))
        return got, want

    @by_players
    def test_featurize(self, players):
        feat = _featurizer(players)
        state = _state(_rng("featurize", players))
        got = jax.device_get(jax.jit(feat.featurize)(state))
        want = jax.device_get(jax.jit(lambda s: _parent_featurize(feat, s))(state))
        # the state does hold what a copy must not touch
        assert np.isinf(want["units"]).any() and np.isnan(want["units"]).any()
        assert (_bits(want["units"]) == 0x80000000).any()
        _assert_trees_bit_equal(got, want)

    @by_players
    def test_actions_to_sim(self, players, parent_indexing):
        feat = _featurizer(players)
        A = len(feat.agent_players)
        packed = _rng("actions", players).integers(
            -2, 40, size=(N * A, 5)
        ).astype(np.int32)
        got, want = self._both(
            parent_indexing, lambda p: feat.actions_to_sim(p), packed
        )
        _assert_trees_bit_equal(got, want)

    @by_players
    def test_shaped_reward_terms(self, players, parent_indexing):
        rng = _rng("reward", players)
        prev, cur = _state(rng), _state(rng)
        got, want = self._both(
            parent_indexing,
            lambda a, b: JF.shaped_reward_terms(SPEC, PLAYERS[players], a, b),
            prev, cur,
        )
        _assert_trees_bit_equal(got, want)


class TestCompilesToNoGather:
    """The gathers must not come back: on the TPU the featurizer's 69 small
    ones and its two ``take_along_axis`` were 9.1% of the small cell's device
    time, and each forced the arrays around it out to HBM (PERF.md section
    6, PR 31). The optimised HLO of each function holds no ``gather`` and no
    ``scatter``, for self-play and for one team of a league cell."""

    @pytest.mark.parametrize("players", ["all_ten", "dire"])
    @pytest.mark.parametrize(
        "program", ["featurize", "actions_to_sim", "shaped_rewards"]
    )
    def test_no_gather_no_scatter(self, program, players):
        feat = _featurizer(players)
        state = jax.eval_shape(lambda: _state(_rng("hlo")))
        if program == "featurize":
            lowered = jax.jit(feat.featurize).lower(state)
        elif program == "actions_to_sim":
            lowered = jax.jit(feat.actions_to_sim).lower(
                jax.ShapeDtypeStruct((feat.n_lanes, 5), jnp.int32)
            )
        else:
            lowered = jax.jit(
                lambda a, b: JF.shaped_rewards(SPEC, PLAYERS[players], a, b)
            ).lower(state, state)
        hlo = lowered.compile().as_text()
        kinds = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(", hlo, re.M)
        assert "select" in kinds or "slice" in kinds   # the text IS optimised HLO
        found = sorted({k for k in kinds if k in ("gather", "scatter")})
        assert not found, f"{program} for {players} compiles to {found}"
