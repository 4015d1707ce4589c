"""Featurization, rewards, and action translation over ``jax_lane_sim``
states — pure jnp functions, composable into the on-device rollout scan.

Port of ``features.vec_featurizer`` (same observation contract, per-lane slot
permutation and reward WEIGHTS; parity in ``tests/test_jax_sim.py``). No lookup
here lowers to an XLA gather or scatter (``tests/test_jax_featurizer.py``). It
all traces into ``actor.device_rollout``'s one XLA program (SURVEY.md §7).
"""

from __future__ import annotations

import itertools
from typing import Dict, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.config import ActionSpec, ObsSpec
from dotaclient_tpu.envs.jax_lane_sim import (
    SimState, _at_slot, _slot_mask, hero_castable,
)
from dotaclient_tpu.envs.lane_sim import NUKE_RANGE, TEAM_RADIANT
from dotaclient_tpu.envs.vec_lane_sim import VecSimSpec
from dotaclient_tpu.features import featurizer as F
from dotaclient_tpu.features.reward import WEIGHTS as _DEFAULT_WEIGHTS
from dotaclient_tpu.protos import dota_pb2 as pb


def build_perm(spec: VecSimSpec, agent_players: Sequence[int]) -> np.ndarray:
    """Static per-lane unit ordering [A, S]: self, other heroes, creeps,
    towers (identical to ``VecFeaturizer``'s)."""
    S, P = spec.max_units, spec.n_players
    creeps = np.arange(spec.creep_lo, S)
    towers = np.arange(spec.tower_lo, spec.creep_lo)
    perm = np.zeros((len(agent_players), S), np.int64)
    for a, p in enumerate(agent_players):
        others = [q for q in range(P) if q != p]
        perm[a] = np.concatenate([[p], others, creeps, towers])
    return perm


def take_static(arr: jnp.ndarray, index: Sequence[int], fill=0) -> jnp.ndarray:
    """``arr[:, index]`` for Python integers known at trace time, as static
    slices: each run of consecutive indices is one slice of ``arr``, each run
    of ``-1`` a block of ``fill``; one run is the slice itself, several are
    concatenated. Never an XLA ``gather``, which takes its operand from HBM
    and costs by the element (PERF.md section 6, PR 25 and PR 31)."""
    pieces = []
    for _, run in itertools.groupby(
        enumerate(index), key=lambda ji: None if ji[1] < 0 else ji[1] - ji[0]
    ):
        run = [i for _, i in run]
        if run[0] < 0:
            shape = (arr.shape[0], len(run)) + arr.shape[2:]
            pieces.append(jnp.full(shape, fill, arr.dtype))
        else:
            pieces.append(arr[:, run[0]:run[-1] + 1])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def scatter_players(
    col: jnp.ndarray, players: Sequence[int], n_players: int, fill
) -> jnp.ndarray:
    """``full((N, n_players), fill).at[:, players].set(col)``: the inverse of
    ``take_static(arr, players)``, by the same slices."""
    lane_of = {int(p): a for a, p in enumerate(players)}
    return take_static(
        col, [lane_of.get(q, -1) for q in range(n_players)], fill
    )


class JaxFeaturizer:
    """Pure featurize/translate functions bound to a static lane layout."""

    def __init__(
        self,
        spec: VecSimSpec,
        obs_spec: ObsSpec,
        action_spec: ActionSpec,
        agent_players: Sequence[int],
    ) -> None:
        if obs_spec.max_units != spec.max_units:
            raise ValueError("ObsSpec.max_units must equal sim slot count")
        if action_spec.max_units != spec.max_units:
            raise ValueError("ActionSpec.max_units must equal sim slot count")
        self.spec = spec
        self.obs_spec = obs_spec
        self.action_spec = action_spec
        self.agent_players = tuple(int(p) for p in agent_players)
        self.perm = build_perm(spec, agent_players)            # np [A, S]
        # ``perm`` as two static rearrangements of the slots under a
        # constant mask: past an agent's own slot 0, a slot reads one of at
        # most two sources over the agents (the hero before it or the hero at
        # it; a creep or a tower: the same one for all)
        lo, hi = self.perm.min(axis=0), self.perm.max(axis=0)
        self._lo_slots = tuple(int(s) for s in lo)
        self._hi_slots = tuple(int(s) for s in hi)
        self._from_hi = self.perm == hi                        # np [A, S]
        self._own_slot = np.arange(spec.max_units) == 0
        rebuilt = np.where(
            self._own_slot, self.perm[:, :1], np.where(self._from_hi, hi, lo)
        )
        if (rebuilt != self.perm).any():
            raise ValueError("build_perm: a slot past the first has three sources")
        self.n_lanes = spec.n_games * len(self.agent_players)

    def permute(self, arr: jnp.ndarray) -> jnp.ndarray:
        """``arr[:, self.perm]``, [N, S, ...] → [N, A, S, ...], without a
        gather: the agents' own column and two static rearrangements of
        ``arr``, broadcast over the agents and selected under the constant
        masks. A select copies: every dtype, ``-0.0`` and ``nan`` survive."""
        tail = (1,) * (arr.ndim - 2)
        own = take_static(arr, self.perm[:, 0])[:, :, None]          # [N, A, 1, ...]
        lo = take_static(arr, self._lo_slots)[:, None]               # [N, 1, S, ...]
        hi = take_static(arr, self._hi_slots)[:, None]
        own_slot = self._own_slot.reshape(self._own_slot.shape + tail)
        from_hi = self._from_hi.reshape(self._from_hi.shape + tail)
        return jnp.where(own_slot, own, jnp.where(from_hi, hi, lo))

    def sim_slot(self, obs_slot: jnp.ndarray) -> jnp.ndarray:
        """``perm[a, obs_slot[n, a]]``, [N, A] → [N, A]: the one lookup here
        whose index is data, by compare-select-reduce over the slot axis."""
        perm = jnp.asarray(self.perm, jnp.int32)[None]
        return _at_slot(perm, _slot_mask(obs_slot, perm.shape[-1]))

    # -- observations ------------------------------------------------------

    def featurize(self, state: SimState) -> Dict[str, jnp.ndarray]:
        """All lanes' observations; arrays with leading axis L = N*A."""
        spec = self.spec
        N, S, P = spec.n_games, spec.max_units, spec.n_players
        A = len(self.agent_players)
        ap = self.agent_players
        g = self.permute                                       # [N, A, S]

        def block(cols, present):
            """Named columns → float32 [..., len(cols)], zero where absent."""
            f = jnp.stack([c.astype(jnp.float32) for c in cols], axis=-1)
            return f * present[..., None]

        # [N, S]: the columns that are the same for every agent, finished
        # once and permuted as ONE block, not field by field and then
        # combined A times over
        is_hero = state.unit_type == pb.UNIT_HERO
        is_creep = state.unit_type == pb.UNIT_LANE_CREEP
        present = (state.unit_type != 0) & (state.alive | is_hero)
        castable = hero_castable(state)
        shared = {
            "is_hero": is_hero,
            "is_creep": is_creep,
            "is_tower": state.unit_type == pb.UNIT_TOWER,
            "y": state.y / F._POS_SCALE,
            "health_frac": state.health / jnp.maximum(state.health_max, 1.0),
            "health_max": state.health_max / F._HP_SCALE,
            "mana_frac": state.mana / jnp.maximum(state.mana_max, 1.0),
            "attack_damage": state.damage / F._DMG_SCALE,
            "attack_range": state.attack_range / F._RANGE_SCALE,
            "move_speed": state.move_speed / F._SPEED_SCALE,
            "armor": state.armor / F._ARMOR_SCALE,
            "level": state.level / F._LEVEL_SCALE,
            "is_alive": state.alive,
            "ability_castable": castable,
        }
        shared_names = [n for n in F.UNIT_FEATURES if n in shared]
        shared_block = g(block([shared[n] for n in shared_names], present))

        # [N, A, S]: what an agent sees relative to itself
        team, alive = g(state.team), g(state.alive)
        x, y = g(state.x), g(state.y)
        low_creep = g(is_creep & (state.health < 0.5 * state.health_max))
        present = g(present)

        my_team = take_static(state.team, ap)[:, :, None]
        # team-canonical frame: +x points at the enemy tower for BOTH sides
        # (see features/featurizer.py featurize); actions_to_sim un-mirrors
        sign = jnp.where(my_team == TEAM_RADIANT, 1.0, -1.0)
        me_x = take_static(state.x, ap)[:, :, None]
        me_y = take_static(state.y, ap)[:, :, None]
        me_alive = take_static(state.alive, ap)

        is_ally = (team == my_team) & present
        is_self = jnp.zeros((N, A, S), bool).at[:, :, 0].set(present[:, :, 0])  # NOT a mask-and: PERF.md §6, PR 31
        dist = jnp.hypot(x - me_x, y - me_y)
        deniable = is_ally & ~is_self & low_creep
        own = {
            "is_ally": is_ally,
            "is_enemy": present & ~is_ally,
            "is_self": is_self,
            "x": x * sign / F._POS_SCALE,
            "dx_self": (x - me_x) * sign / F._POS_SCALE,
            "dy_self": (y - me_y) / F._POS_SCALE,
            "dist_self": dist / F._POS_SCALE,
            "deniable": deniable,
        }

        # the observation's columns in their order: runs of shared columns
        # are slices of the permuted block
        pieces = []
        for is_shared, run in itertools.groupby(F.UNIT_FEATURES, key=shared.__contains__):
            run = list(run)
            if is_shared:
                lo = shared_names.index(run[0])
                pieces.append(shared_block[..., lo:lo + len(run)])
            else:
                pieces.append(block([own[n] for n in run], present))
        f = jnp.concatenate(pieces, axis=-1)

        self_castable = take_static(castable, ap)
        cast_range = jnp.where(self_castable, NUKE_RANGE, 0.0)[:, :, None]
        is_enemy = present & (team != my_team)
        attackable = (
            present & alive & (is_enemy | deniable) & ~is_self
            & me_alive[:, :, None]
        )
        cast_tgt = is_enemy & alive & (dist <= cast_range) & me_alive[:, :, None]

        mask_action = (
            jnp.zeros((N, A, self.action_spec.n_action_types), bool)
            .at[..., pb.ACTION_NOOP].set(True)
            .at[..., pb.ACTION_MOVE].set(me_alive)
            .at[..., pb.ACTION_ATTACK_UNIT].set(attackable.any(-1))
            .at[..., pb.ACTION_CAST].set(self_castable & cast_tgt.any(-1))
        )
        mask_ability = (
            jnp.zeros((N, A, self.action_spec.max_abilities), bool)
            .at[..., 0].set(mask_action[..., pb.ACTION_CAST])
        )

        tower_r, tower_d = self.spec.tower_lo, self.spec.tower_lo + 1
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
                jnp.broadcast_to(
                    (state.dota_time / F._TIME_SCALE)[:, None], (N, A)
                ),
                jnp.where(i_rad, 1.0, -1.0),
                take_static(state.gold, ap) / F._GOLD_SCALE,
                take_static(state.xp, ap) / F._XP_SCALE,
                take_static(state.level, ap) / F._LEVEL_SCALE,
                kill_diff / 10.0,
                own_tower,
                enemy_tower,
            ],
            axis=-1,
        ).astype(jnp.float32)
        pad = self.obs_spec.global_features - gl.shape[-1]
        if pad:
            gl = jnp.concatenate([gl, jnp.zeros((N, A, pad), jnp.float32)], -1)

        L = N * A

        def flat(arr):
            return arr.reshape((L,) + arr.shape[2:])

        return {
            "units": flat(f),
            "unit_mask": flat(present),
            "unit_handles": jnp.broadcast_to(
                jnp.asarray(self.perm + 1, jnp.int32)[None], (N, A, S)
            ).reshape(L, S),
            "globals": flat(gl),
            "hero_id": take_static(state.hero_ids, ap).reshape(-1).astype(jnp.int32),
            "mask_action_type": flat(mask_action),
            "mask_target_unit": flat(attackable),
            "mask_cast_target": flat(cast_tgt),
            "mask_ability": flat(mask_ability),
        }

    # -- action translation ------------------------------------------------

    def actions_to_sim(self, packed: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Policy head indices [L, 5] → sim action arrays [N, P]; non-agent
        players get type = -1 (scripted players are overridden in-sim)."""
        spec = self.spec
        N, P, S = spec.n_games, spec.n_players, spec.max_units
        A = len(self.agent_players)
        packed = packed.reshape(N, A, 5).astype(jnp.int32)
        sim_slot = self.sim_slot(jnp.clip(packed[..., 3], 0, S - 1))

        def scatter(col, fill=0):
            return scatter_players(col, self.agent_players, P, fill)

        # canonical → world: Dire lanes mirror the move-x bin back (teams
        # are static by player index — players ≥ team_size are Dire)
        mirror = jnp.asarray(
            [p >= self.spec.team_size for p in self.agent_players]
        )[None, :]
        mx = jnp.where(
            mirror, self.action_spec.move_bins - 1 - packed[..., 1],
            packed[..., 1],
        )
        return {
            "type": scatter(packed[..., 0], fill=-1),
            "move_x": scatter(mx),
            "move_y": scatter(packed[..., 2]),
            "target_slot": scatter(sim_slot),
            "ability": scatter(packed[..., 4]),
        }


def shaped_reward_terms(
    spec: VecSimSpec,
    agent_players: Sequence[int],
    prev: SimState,
    cur: SimState,
    weights=None,
):
    """Weighted per-term shaped-reward breakdown, each term a per-lane
    [L] array (jnp port of ``VecRewards``; same components as
    ``features.reward``; ``weights`` overrides the default table —
    Python floats, so they are compile-time constants). The dict is in
    the historical summation order — :func:`shaped_rewards` left-folds
    it, so the scalar reward is bit-identical to the pre-decomposition
    chain — and the per-term sums are what the device rollout
    accumulates for the outcome plane's reward decomposition
    (``outcome/reward_sum/<term>``, ISSUE 15)."""
    WEIGHTS = _DEFAULT_WEIGHTS if weights is None else weights
    P = spec.n_players
    ap = tuple(int(p) for p in agent_players)

    def hero_hp_frac(s: SimState) -> jnp.ndarray:
        return jnp.where(
            s.alive[:, :P],
            s.health[:, :P] / jnp.maximum(s.health_max[:, :P], 1.0),
            0.0,
        )

    def tower_frac(s: SimState) -> jnp.ndarray:
        tr, td = spec.tower_lo, spec.tower_lo + 1
        frac = jnp.stack(
            [
                s.health[:, tr] / jnp.maximum(s.health_max[:, tr], 1.0),
                s.health[:, td] / jnp.maximum(s.health_max[:, td], 1.0),
            ],
            axis=1,
        )
        alive = jnp.stack([s.alive[:, tr], s.alive[:, td]], axis=1)
        return jnp.where(alive, frac, 0.0)

    team_row = cur.team[:, :P]
    rad_mask = team_row == TEAM_RADIANT

    def team_mean_hp(s: SimState) -> Tuple[jnp.ndarray, jnp.ndarray]:
        hp = hero_hp_frac(s)
        cnt_r = jnp.maximum(rad_mask.sum(1), 1)
        cnt_d = jnp.maximum((~rad_mask).sum(1), 1)
        return (hp * rad_mask).sum(1) / cnt_r, (hp * ~rad_mask).sum(1) / cnt_d

    mean_r0, mean_d0 = team_mean_hp(prev)
    mean_r1, mean_d1 = team_mean_hp(cur)
    tower0, tower1 = tower_frac(prev), tower_frac(cur)

    my_team = take_static(cur.team, ap)
    i_rad = my_team == TEAM_RADIANT
    e_hp0 = jnp.where(i_rad, mean_d0[:, None], mean_r0[:, None])
    e_hp1 = jnp.where(i_rad, mean_d1[:, None], mean_r1[:, None])
    e_tw0 = jnp.where(i_rad, tower0[:, 1:2], tower0[:, 0:1])
    e_tw1 = jnp.where(i_rad, tower1[:, 1:2], tower1[:, 0:1])
    o_tw0 = jnp.where(i_rad, tower0[:, 0:1], tower0[:, 1:2])
    o_tw1 = jnp.where(i_rad, tower1[:, 0:1], tower1[:, 1:2])

    def d(field):
        return take_static(getattr(cur, field), ap) - take_static(
            getattr(prev, field), ap
        )

    hp0 = take_static(hero_hp_frac(prev), ap)
    hp1 = take_static(hero_hp_frac(cur), ap)

    just_ended = cur.done & ~prev.done & (cur.winning_team != 0)
    win_sign = jnp.where(cur.winning_team[:, None] == my_team, 1.0, -1.0)
    terms = {
        "xp": WEIGHTS["xp"] * d("xp"),
        "gold": WEIGHTS["gold"] * d("gold"),
        "hp": WEIGHTS["hp"] * (hp1 - hp0),
        "enemy_hp": WEIGHTS["enemy_hp"] * -(e_hp1 - e_hp0),
        "last_hits": WEIGHTS["last_hits"] * d("last_hits"),
        "denies": WEIGHTS["denies"] * d("denies"),
        "kills": WEIGHTS["kills"] * d("kills"),
        "deaths": WEIGHTS["deaths"] * d("deaths"),
        "tower_damage": WEIGHTS["tower_damage"] * (e_tw0 - e_tw1),
        "own_tower": WEIGHTS["own_tower"] * (o_tw1 - o_tw0),
        "win": WEIGHTS["win"] * win_sign * just_ended[:, None],
    }
    return {
        term: arr.reshape(-1).astype(jnp.float32)
        for term, arr in terms.items()
    }


def shaped_rewards(
    spec: VecSimSpec,
    agent_players: Sequence[int],
    prev: SimState,
    cur: SimState,
    weights=None,
) -> jnp.ndarray:
    """Per-lane shaped reward [L]: the left-fold of
    :func:`shaped_reward_terms` in table order (``features.reward.
    fold_terms`` — bit-identical to the historical single-expression
    sum)."""
    from dotaclient_tpu.features.reward import fold_terms

    return fold_terms(
        shaped_reward_terms(spec, agent_players, prev, cur, weights)
    )
