"""Pure-JAX lane simulator: the environment as a jittable device function.

Third (and fastest) implementation of the lane-game rules, after the scalar
``lane_sim`` (gRPC/proto boundary) and the numpy ``vec_lane_sim`` (vectorized
host path). Here the entire game — scripted bots included — is a pure
function over a pytree of device arrays, so the whole actor rollout loop
(policy step + env step + reward) compiles into ONE XLA program and runs for
T steps without touching the host (SURVEY.md §7 hard-part 2; the
Anakin/Podracer architecture, PAPERS.md [P:7]). On links where a host↔device
round trip costs ~100 ms this is the difference between ~1e3 and ~1e6
frames/sec.

Semantics: a line-for-line port of ``vec_lane_sim.VecLaneSim`` (same phase
order, same resolution rules, same constants by import); exact-state parity
between the two is tested in ``tests/test_jax_sim.py`` over wave-free
horizons, and statistically across full episodes. The only intentional
difference: creep-wave y-jitter draws from the single batch PRNG key carried
in ``SimState`` rather than per-game numpy streams.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.envs.lane_sim import (
    ATTACKS_PER_SECOND,
    CREEP_ARMOR,
    CREEP_DAMAGE,
    CREEP_HP,
    CREEP_RANGE,
    CREEP_SPEED,
    CREEP_WAVE_PERIOD,
    CREEP_XP,
    CREEPS_PER_WAVE,
    DENY_XP_FACTOR,
    GENERIC_HERO,
    GOLD_PASSIVE_PER_SEC,
    GOLD_PER_HERO_KILL,
    GOLD_PER_LASTHIT,
    HERO_STATS,
    LANE_HALF_LENGTH,
    MAX_LEVEL,
    NUKE_BASE_DAMAGE,
    NUKE_COOLDOWN,
    NUKE_DAMAGE_PER_LEVEL,
    NUKE_MANA,
    NUKE_RANGE,
    NUKE_SLOT,
    RESPAWN_BASE_SECONDS,
    RESPAWN_PER_LEVEL_SECONDS,
    TEAM_DIRE,
    TEAM_RADIANT,
    TICKS_PER_SECOND,
    TOWER_ARMOR,
    TOWER_DAMAGE,
    TOWER_HP,
    TOWER_RANGE,
    TOWER_X,
    XP_PER_HERO_KILL,
    XP_PER_LEVEL,
    XP_RADIUS,
)
from dotaclient_tpu.envs.vec_lane_sim import VecSimSpec
from dotaclient_tpu.protos import dota_pb2 as pb

_BIG = 1e9


class SimState(NamedTuple):
    """All arrays have leading axis N (games); unit axis S = spec.max_units."""

    unit_type: jnp.ndarray     # i32 [N, S]
    team: jnp.ndarray          # i32 [N, S]
    x: jnp.ndarray             # f32 [N, S]
    y: jnp.ndarray             # f32 [N, S]
    health: jnp.ndarray        # f32 [N, S]
    health_max: jnp.ndarray    # f32 [N, S]
    mana: jnp.ndarray          # f32 [N, S]
    mana_max: jnp.ndarray      # f32 [N, S]
    damage: jnp.ndarray        # f32 [N, S]
    attack_range: jnp.ndarray  # f32 [N, S]
    move_speed: jnp.ndarray    # f32 [N, S]
    armor: jnp.ndarray         # f32 [N, S]
    level: jnp.ndarray         # i32 [N, S]
    alive: jnp.ndarray         # bool [N, S]
    attack_cd: jnp.ndarray     # f32 [N, S]
    ability_cd: jnp.ndarray    # f32 [N, S]
    xp: jnp.ndarray            # f32 [N, S] (hero slots)
    gold: jnp.ndarray          # f32 [N, S]
    last_hits: jnp.ndarray     # i32 [N, S]
    denies: jnp.ndarray        # i32 [N, S]
    kills: jnp.ndarray         # i32 [N, S]
    deaths: jnp.ndarray        # i32 [N, S]
    respawn_at: jnp.ndarray    # f32 [N, S]
    dota_time: jnp.ndarray     # f32 [N]
    tick: jnp.ndarray          # i32 [N]
    done: jnp.ndarray          # bool [N]
    winning_team: jnp.ndarray  # i32 [N]
    next_wave_at: jnp.ndarray  # f32 [N]
    hero_ids: jnp.ndarray      # i32 [N, P]
    control_modes: jnp.ndarray # i32 [N, P]
    key: jnp.ndarray           # PRNG key (batch-wide)


Actions = Dict[str, jnp.ndarray]   # type/move_x/move_y/target_slot/ability, [N, P]


def _armor_mult(armor: jnp.ndarray) -> jnp.ndarray:
    return 1.0 - (0.06 * armor) / (1.0 + 0.06 * armor)


def _slot_mask(idx: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """bool [..., n_slots]: True at the one slot each index of ``idx`` names."""
    return idx[..., None] == jnp.arange(n_slots, dtype=idx.dtype)


def _at_slot(x: jnp.ndarray, mask: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """The value of ``x`` at the one position ``mask`` marks along ``axis``:
    ``x[n, idx[n, k]]`` as compare-select-reduce, never an XLA ``gather``.

    ``x`` broadcasts against ``mask``: per-unit values ``[N, S]`` go in as
    ``x[:, None, :]`` against a ``_slot_mask`` of ``[N, K, S]``, a row per
    looker (``dist[:, :P, :]``) as it is. A data-dependent gather runs close
    to one element at a time on the TPU (10 ns a value: 1.4 ms for one
    ``[4096, 32]`` lookup, PERF.md section 6, PR 25) and, because it names
    the game axis, is not shard-local to the partitioner; the select fuses
    into the elementwise work around it and is local to a game by
    construction.

    Exact for every dtype, ``-0.0``, ``inf`` and ``nan`` included: floats
    are reduced as their bit patterns (an integer sum with one non-zero
    term), and the select comes before the reduce, so nothing in a slot
    that is not selected reaches the result. Deliberately NOT a one-hot
    ``einsum``: at default precision the MXU rounds float32 inputs through
    bfloat16, which would move a looked-up health of 550.0 and with it a
    kill threshold.
    """
    if x.dtype == jnp.bool_:
        return (mask & x).any(axis=axis)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)   # f32 or i32 alike
    picked = jnp.where(mask, bits, 0).sum(axis=axis)
    return jax.lax.bitcast_convert_type(picked, x.dtype)


def _hero_stats_table() -> np.ndarray:
    """Dense hero_id → stats lookup (row 0.. = generic fallback)."""
    n = max(HERO_STATS) + 1
    table = np.tile(np.asarray(GENERIC_HERO, np.float32), (n + 1, 1))
    for hid, stats in HERO_STATS.items():
        table[hid] = stats
    return table


def init_state(
    spec: VecSimSpec,
    hero_ids: jnp.ndarray,
    control_modes: jnp.ndarray,
    key: jnp.ndarray,
) -> SimState:
    """Fresh batch of games (the jittable analogue of ``VecLaneSim.reset``
    over all rows)."""
    N, S, P = spec.n_games, spec.max_units, spec.n_players
    f0 = jnp.zeros((N, S), jnp.float32)
    i0 = jnp.zeros((N, S), jnp.int32)
    state = SimState(
        unit_type=i0, team=i0, x=f0, y=f0,
        health=f0, health_max=jnp.ones((N, S), jnp.float32),
        mana=f0, mana_max=f0, damage=f0, attack_range=f0,
        move_speed=f0, armor=f0, level=jnp.ones((N, S), jnp.int32),
        alive=jnp.zeros((N, S), bool), attack_cd=f0, ability_cd=f0,
        xp=f0, gold=f0, last_hits=i0, denies=i0, kills=i0, deaths=i0,
        respawn_at=jnp.full((N, S), -1.0, jnp.float32),
        dota_time=jnp.zeros((N,), jnp.float32),
        tick=jnp.zeros((N,), jnp.int32),
        done=jnp.zeros((N,), bool),
        winning_team=jnp.zeros((N,), jnp.int32),
        next_wave_at=jnp.zeros((N,), jnp.float32),
        hero_ids=jnp.asarray(hero_ids, jnp.int32),
        control_modes=jnp.asarray(control_modes, jnp.int32),
        key=key,
    )

    # heroes (slot == player id; Radiant first)
    pslots = jnp.arange(P)
    team_row = jnp.where(pslots < spec.team_size, TEAM_RADIANT, TEAM_DIRE)
    side = jnp.where(team_row == TEAM_RADIANT, -1.0, 1.0)
    table = jnp.asarray(_hero_stats_table())                    # [rows, 6]
    row = _slot_mask(
        jnp.clip(state.hero_ids, 0, table.shape[0] - 1), table.shape[0]
    )                                                           # [N, P, rows]
    stats = _at_slot(table.T, row[:, :, None, :])               # [N, P, 6]

    def set_cols(arr, vals):
        return arr.at[:, :P].set(vals)

    state = state._replace(
        unit_type=set_cols(state.unit_type, pb.UNIT_HERO),
        team=set_cols(state.team, jnp.broadcast_to(team_row, (N, P))),
        x=set_cols(state.x, jnp.broadcast_to(side * (LANE_HALF_LENGTH - 300.0), (N, P))),
        y=set_cols(state.y, jnp.broadcast_to(60.0 * (pslots % 5), (N, P)).astype(jnp.float32)),
        health=set_cols(state.health, stats[..., 0]),
        health_max=set_cols(state.health_max, stats[..., 0]),
        mana=set_cols(state.mana, stats[..., 1]),
        mana_max=set_cols(state.mana_max, stats[..., 1]),
        damage=set_cols(state.damage, stats[..., 2]),
        attack_range=set_cols(state.attack_range, stats[..., 3]),
        move_speed=set_cols(state.move_speed, stats[..., 4]),
        armor=set_cols(state.armor, stats[..., 5]),
        alive=set_cols(state.alive, True),
    )

    # towers
    for k, team in enumerate((TEAM_RADIANT, TEAM_DIRE)):
        t = spec.tower_lo + k
        state = state._replace(
            unit_type=state.unit_type.at[:, t].set(pb.UNIT_TOWER),
            team=state.team.at[:, t].set(team),
            x=state.x.at[:, t].set(TOWER_X[team]),
            health=state.health.at[:, t].set(TOWER_HP),
            health_max=state.health_max.at[:, t].set(TOWER_HP),
            damage=state.damage.at[:, t].set(TOWER_DAMAGE),
            attack_range=state.attack_range.at[:, t].set(TOWER_RANGE),
            armor=state.armor.at[:, t].set(TOWER_ARMOR),
            alive=state.alive.at[:, t].set(True),
        )

    key, sub = jax.random.split(state.key)
    state = _spawn_waves(spec, state._replace(key=key), jnp.ones((N,), bool), sub)
    return state._replace(next_wave_at=jnp.full((N,), CREEP_WAVE_PERIOD, jnp.float32))


def reset_where(spec: VecSimSpec, state: SimState, mask: jnp.ndarray) -> SimState:
    """Re-initialize the games where ``mask`` — pure/jittable (fresh rows are
    computed for the whole batch and merged where the mask holds)."""
    key, sub = jax.random.split(state.key)
    fresh = init_state(spec, state.hero_ids, state.control_modes, sub)

    def merge(a, b):
        m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)

    # the PRNG key has no game axis — it is threaded, not merged
    out = {
        k: merge(getattr(fresh, k), getattr(state, k))
        for k in SimState._fields
        if k != "key"
    }
    return SimState(key=key, **out)


def _spawn_waves(
    spec: VecSimSpec, state: SimState, due: jnp.ndarray, key: jnp.ndarray
) -> SimState:
    """Spawn one creep wave per team where ``due`` (claiming free pool slots)."""
    C = spec.creeps_per_team
    for i, team in enumerate((TEAM_RADIANT, TEAM_DIRE)):
        lo = spec.creep_lo + i * C
        pool = slice(lo, lo + C)
        sign = 1.0 if team == TEAM_RADIANT else -1.0
        free = ~state.alive[:, pool]                            # [N, C]
        order = jnp.cumsum(free, axis=1) - 1
        take = free & (order < CREEPS_PER_WAVE) & due[:, None]
        k = order.astype(jnp.float32)
        jitter = jax.random.uniform(
            jax.random.fold_in(key, i), free.shape, minval=-40.0, maxval=40.0
        )

        def w(arr, val):
            return arr.at[:, pool].set(jnp.where(take, val, arr[:, pool]))

        state = state._replace(
            unit_type=w(state.unit_type, pb.UNIT_LANE_CREEP),
            team=w(state.team, team),
            x=w(state.x, TOWER_X[team] + sign * (250.0 + 40.0 * k)),
            y=w(state.y, jitter),
            health=w(state.health, CREEP_HP),
            health_max=w(state.health_max, CREEP_HP),
            damage=w(state.damage, CREEP_DAMAGE),
            attack_range=w(state.attack_range, CREEP_RANGE),
            move_speed=w(state.move_speed, CREEP_SPEED),
            armor=w(state.armor, CREEP_ARMOR),
            level=w(state.level, 1),
            alive=w(state.alive, True),
            attack_cd=w(state.attack_cd, 0.0),
        )
    return state


def _pairwise_dist(state: SimState) -> jnp.ndarray:
    dx = state.x[:, :, None] - state.x[:, None, :]
    dy = state.y[:, :, None] - state.y[:, None, :]
    return jnp.hypot(dx, dy)


def hero_castable(state: SimState) -> jnp.ndarray:
    return (
        (state.unit_type == pb.UNIT_HERO)
        & (state.ability_cd <= 0.0)
        & (state.mana >= NUKE_MANA)
    )


# ---------------------------------------------------------------------------
# step
# ---------------------------------------------------------------------------


def step(
    spec: VecSimSpec,
    state: SimState,
    actions: Actions,
    scripted_possible: bool = True,
) -> SimState:
    """One observation interval for every non-done game (pure; jit this or a
    scan over it). Mirrors ``VecLaneSim.step`` phase for phase.

    ``scripted_possible`` is STATIC: control_modes is a traced array, so XLA
    cannot prune the scripted-bot subgraph on its own — callers that know no
    player is scripted (self-play, league) pass False and skip it entirely.
    """
    S, P = spec.max_units, spec.n_players
    live = ~state.done
    dt = spec.ticks_per_obs / TICKS_PER_SECOND
    dist = _pairwise_dist(state)

    a_type = jnp.where(actions["type"] < 0, pb.ACTION_NOOP, actions["type"])
    move_x = actions["move_x"]
    move_y = actions["move_y"]
    target = jnp.clip(actions["target_slot"], 0, S - 1).astype(jnp.int32)
    ability = actions["ability"]

    if scripted_possible:
        scripted = state.control_modes != pb.CONTROL_AGENT
        sa = _scripted_actions(spec, state, dist)
        a_type = jnp.where(scripted, sa["type"], a_type)
        move_x = jnp.where(scripted, sa["move_x"], move_x)
        move_y = jnp.where(scripted, sa["move_y"], move_y)
        target = jnp.where(scripted, sa["target_slot"], target)
        ability = jnp.where(scripted, sa["ability"], ability)

    hero_alive = state.alive[:, :P] & live[:, None]

    # 1. movement
    half = (spec.move_bins - 1) / 2.0
    moving = hero_alive & (a_type == pb.ACTION_MOVE)
    mdx = (move_x - half) / max(half, 1.0)
    mdy = (move_y - half) / max(half, 1.0)
    norm = jnp.hypot(mdx, mdy)
    ok = moving & (norm > 1e-6)
    scale = jnp.where(ok, state.move_speed[:, :P] * dt / jnp.maximum(norm, 1e-9), 0.0)
    new_hx = jnp.clip(state.x[:, :P] + mdx * scale, -LANE_HALF_LENGTH, LANE_HALF_LENGTH)
    new_hy = jnp.clip(state.y[:, :P] + mdy * scale, -400.0, 400.0)
    state = state._replace(
        x=state.x.at[:, :P].set(jnp.where(ok, new_hx, state.x[:, :P])),
        y=state.y.at[:, :P].set(jnp.where(ok, new_hy, state.y[:, :P])),
    )

    # 2. hero attacks / casts (phase A)
    at_target = _slot_mask(target, S)                           # [N, P, S]
    tgt_dist = _at_slot(dist[:, :P, :], at_target)
    t_alive = _at_slot(state.alive[:, None, :], at_target)
    t_team = _at_slot(state.team[:, None, :], at_target)
    t_type = _at_slot(state.unit_type[:, None, :], at_target)
    t_hp = _at_slot(state.health[:, None, :], at_target)
    t_hpmax = _at_slot(state.health_max[:, None, :], at_target)
    my_team = state.team[:, :P]

    is_deny = (t_team == my_team) & (t_type == pb.UNIT_LANE_CREEP) & (
        t_hp < 0.5 * t_hpmax
    )
    attack_ok = (
        hero_alive
        & (a_type == pb.ACTION_ATTACK_UNIT)
        & t_alive
        & ((t_team != my_team) | is_deny)
        & (tgt_dist <= state.attack_range[:, :P] + 50.0)
        & (state.attack_cd[:, :P] <= 0.0)
    )
    cast_ok = (
        hero_alive
        & (a_type == pb.ACTION_CAST)
        & (ability == NUKE_SLOT)
        & t_alive
        & (t_team != my_team)
        & (tgt_dist <= NUKE_RANGE)
        & (state.ability_cd[:, :P] <= 0.0)
        & (state.mana[:, :P] >= NUKE_MANA)
    )
    state = state._replace(
        attack_cd=state.attack_cd.at[:, :P].set(
            jnp.where(attack_ok, 1.0 / ATTACKS_PER_SECOND, state.attack_cd[:, :P])
        ),
        mana=state.mana.at[:, :P].set(
            jnp.where(cast_ok, state.mana[:, :P] - NUKE_MANA, state.mana[:, :P])
        ),
        ability_cd=state.ability_cd.at[:, :P].set(
            jnp.where(cast_ok, NUKE_COOLDOWN, state.ability_cd[:, :P])
        ),
    )
    raw = jnp.where(attack_ok, state.damage[:, :P], 0.0) + jnp.where(
        cast_ok,
        NUKE_BASE_DAMAGE + NUKE_DAMAGE_PER_LEVEL * state.level[:, :P],
        0.0,
    )
    hit = attack_ok | cast_ok
    t_mult = _armor_mult(_at_slot(state.armor[:, None, :], at_target))
    # one-hot matmul, NOT scatter-add: XLA scatter combines duplicate
    # indices in unspecified order (f32 non-associativity then flips kill
    # thresholds run-to-run); a reduction has a fixed order and maps to the
    # MXU anyway
    onehot_t = jax.nn.one_hot(target, S, dtype=jnp.float32)     # [N, P, S]
    dmg = jnp.einsum("np,nps->ns", jnp.where(hit, raw * t_mult, 0.0), onehot_t)
    state = _resolve_deaths(
        spec, state, dmg, dist,
        hero_hit=hit, hero_target=target, hero_deny=is_deny & attack_ok,
    )

    # 3. creeps and towers act (phase B, phase-start targeting world)
    state = _step_ai(spec, state, dist, dt, live)

    # 4. clocks, regen, respawns, waves, timeout
    state = _step_clocks(spec, state, dt, live)
    return state


def _resolve_deaths(
    spec: VecSimSpec,
    state: SimState,
    dmg: jnp.ndarray,
    dist: jnp.ndarray,
    hero_hit=None,
    hero_target=None,
    hero_deny=None,
) -> SimState:
    N, S, P = spec.n_games, spec.max_units, spec.n_players
    pre_alive = state.alive
    health = jnp.where(pre_alive, state.health - dmg, state.health)
    died = pre_alive & (health <= 0.0)
    health = jnp.where(died, 0.0, health)
    alive = pre_alive & ~died
    state = state._replace(health=health, alive=alive)

    is_creep = state.unit_type == pb.UNIT_LANE_CREEP
    is_hero = state.unit_type == pb.UNIT_HERO
    died_creep = died & is_creep
    died_hero = died & is_hero
    died_tower = died & (state.unit_type == pb.UNIT_TOWER)

    denied_creep = jnp.zeros((N, S), bool)
    if hero_hit is not None:
        # kill credit: lowest player index whose landed attack targeted the
        # dead slot (argmax over bool picks the first True)
        credit = hero_hit[:, :, None] & (
            hero_target[:, :, None] == jnp.arange(S)[None, None, :]
        )                                                       # [N, P, S]
        by_hero = died & credit.any(axis=1)                     # [N, S]
        first_p = jnp.argmax(credit, axis=1)                    # [N, S]
        deny_credit = _at_slot(
            hero_deny[:, :, None],
            first_p[:, None, :] == jnp.arange(P)[None, :, None],
            axis=1,
        )                                                       # [N, S]

        cred_creep = by_hero & is_creep
        denied_creep = cred_creep & deny_credit
        lasthit = cred_creep & ~deny_credit
        cred_hero = by_hero & is_hero

        # deterministic reduction over victim slots (see dmg comment above)
        onehot_p = jax.nn.one_hot(first_p, S, dtype=jnp.float32)  # [N, S, S]

        def reduce_p(vals):
            return jnp.einsum("ns,nsp->np", vals.astype(jnp.float32), onehot_p)

        state = state._replace(
            denies=state.denies + reduce_p(denied_creep).astype(jnp.int32),
            last_hits=state.last_hits + reduce_p(lasthit).astype(jnp.int32),
            kills=state.kills + reduce_p(cred_hero).astype(jnp.int32),
            gold=state.gold + reduce_p(
                GOLD_PER_LASTHIT * lasthit + GOLD_PER_HERO_KILL * cred_hero
            ),
        )
        state = _grant_xp(
            spec, state, reduce_p(XP_PER_HERO_KILL * cred_hero)[:, :P]
        )

    # creep XP: living enemy heroes within radius split it
    xp_each = jnp.where(denied_creep, CREEP_XP * DENY_XP_FACTOR, CREEP_XP)
    hero_d = dist[:, :P, :]                                     # [N, P, S]
    eligible = (
        state.alive[:, :P, None]
        & (state.team[:, :P, None] != state.team[:, None, :])
        & (hero_d <= XP_RADIUS)
        & died_creep[:, None, :]
    )                                                           # [N, P, S]
    cnt = jnp.maximum(eligible.sum(axis=1), 1)                  # [N, S]
    share = (eligible * (xp_each / cnt)[:, None, :]).sum(axis=2)  # [N, P]
    state = _grant_xp(spec, state, share)

    # hero deaths: respawn timers
    hp_slots = died_hero[:, :P]
    state = state._replace(
        deaths=state.deaths.at[:, :P].add(hp_slots.astype(jnp.int32)),
        respawn_at=state.respawn_at.at[:, :P].set(
            jnp.where(
                hp_slots,
                state.dota_time[:, None]
                + RESPAWN_BASE_SECONDS
                + RESPAWN_PER_LEVEL_SECONDS * state.level[:, :P],
                state.respawn_at[:, :P],
            )
        ),
    )

    # tower death ends the game
    rad_died = died_tower[:, spec.tower_lo]
    dire_died = died_tower[:, spec.tower_lo + 1]
    any_died = rad_died | dire_died
    return state._replace(
        done=state.done | any_died,
        winning_team=jnp.where(
            dire_died, TEAM_RADIANT,
            jnp.where(rad_died, TEAM_DIRE, state.winning_team),
        ),
    )


def _grant_xp(spec: VecSimSpec, state: SimState, xp_gain: jnp.ndarray) -> SimState:
    """Add XP [N, P] to hero slots; closed-form level-ups (level =
    1 + floor(xp/220) capped, +40 maxHP/heal, +20 maxMana, +4 damage per
    level — elementwise, so simultaneous grants cannot double-apply)."""
    P = spec.n_players
    xp = state.xp.at[:, :P].add(xp_gain)
    cur = state.level[:, :P]
    new = jnp.minimum(
        MAX_LEVEL, (xp[:, :P] // XP_PER_LEVEL).astype(jnp.int32) + 1
    )
    gained = jnp.maximum(new - cur, 0).astype(jnp.float32)
    hp_max = state.health_max.at[:, :P].add(40.0 * gained)
    return state._replace(
        xp=xp,
        level=state.level.at[:, :P].set(jnp.maximum(cur, new)),
        health_max=hp_max,
        health=state.health.at[:, :P].set(
            jnp.minimum(state.health[:, :P] + 40.0 * gained, hp_max[:, :P])
        ),
        mana_max=state.mana_max.at[:, :P].add(20.0 * gained),
        damage=state.damage.at[:, :P].add(4.0 * gained),
    )


def _step_ai(
    spec: VecSimSpec, state: SimState, dist: jnp.ndarray, dt: float, live: jnp.ndarray
) -> SimState:
    S = spec.max_units
    alive = state.alive & live[:, None]
    enemy = (
        alive[:, :, None]
        & alive[:, None, :]
        & (state.team[:, :, None] != state.team[:, None, :])
    )
    d_masked = jnp.where(enemy, dist, _BIG)

    is_creep = (state.unit_type == pb.UNIT_LANE_CREEP) & alive
    is_tower = (state.unit_type == pb.UNIT_TOWER) & alive

    nearest = d_masked.argmin(axis=2)
    nearest_d = d_masked.min(axis=2)    # the value at the argmin IS the min
    can_attack = is_creep & (nearest_d <= state.attack_range + 20.0)
    attacking = can_attack & (state.attack_cd <= 0.0)

    in_tower_range = d_masked <= state.attack_range[:, :, None]
    t_pref = jnp.where(
        in_tower_range,
        d_masked
        + jnp.where(state.unit_type[:, None, :] == pb.UNIT_HERO, 1e6, 0.0),
        _BIG * 2.0,
    )
    t_near = t_pref.argmin(axis=2)
    t_attacking = (
        is_tower & (t_pref.min(axis=2) < _BIG) & (state.attack_cd <= 0.0)
    )

    atk = attacking | t_attacking
    tgt = jnp.where(t_attacking, t_near, nearest)
    state = state._replace(
        attack_cd=jnp.where(atk, 1.0 / ATTACKS_PER_SECOND, state.attack_cd)
    )
    t_mult = _armor_mult(_at_slot(state.armor[:, None, :], _slot_mask(tgt, S)))
    # deterministic one-hot reduction (see phase-A dmg comment)
    onehot_t = jax.nn.one_hot(tgt, S, dtype=jnp.float32)        # [N, S, S]
    dmg = jnp.einsum(
        "na,nas->ns", jnp.where(atk, state.damage * t_mult, 0.0), onehot_t
    )
    state = _resolve_deaths(spec, state, dmg, dist)

    marching = is_creep & ~can_attack & state.alive
    goal_x = jnp.where(
        state.team == TEAM_RADIANT, TOWER_X[TEAM_DIRE], TOWER_X[TEAM_RADIANT]
    )
    step_len = state.move_speed * dt
    delta = goal_x - state.x
    return state._replace(
        x=jnp.where(
            marching,
            state.x + jnp.sign(delta) * jnp.minimum(step_len, jnp.abs(delta)),
            state.x,
        )
    )


def _step_clocks(
    spec: VecSimSpec, state: SimState, dt: float, live: jnp.ndarray
) -> SimState:
    N, P = spec.n_games, spec.n_players
    livef = live.astype(jnp.float32)[:, None]
    dota_time = jnp.where(live, state.dota_time + dt, state.dota_time)
    state = state._replace(
        dota_time=dota_time,
        tick=jnp.where(live, state.tick + spec.ticks_per_obs, state.tick),
        attack_cd=jnp.maximum(0.0, state.attack_cd - dt * livef),
        ability_cd=jnp.maximum(0.0, state.ability_cd - dt * livef),
    )
    hero_alive = (state.unit_type == pb.UNIT_HERO) & state.alive & live[:, None]
    state = state._replace(
        gold=jnp.where(hero_alive, state.gold + GOLD_PASSIVE_PER_SEC * dt, state.gold),
        health=jnp.where(
            hero_alive,
            jnp.minimum(state.health + 1.5 * dt, state.health_max),
            state.health,
        ),
        mana=jnp.where(
            hero_alive,
            jnp.minimum(state.mana + 1.0 * dt, state.mana_max),
            state.mana,
        ),
    )

    # respawns
    hero_dead = (
        (state.unit_type == pb.UNIT_HERO) & ~state.alive & live[:, None]
        & (state.respawn_at >= 0.0)
        & (state.respawn_at <= state.dota_time[:, None])
    )
    pslots = jnp.arange(P)
    team_row = state.team[:, :P]
    side = jnp.where(team_row == TEAM_RADIANT, -1.0, 1.0)
    hd = hero_dead[:, :P]
    state = state._replace(
        alive=state.alive.at[:, :P].set(state.alive[:, :P] | hd),
        health=state.health.at[:, :P].set(
            jnp.where(hd, state.health_max[:, :P], state.health[:, :P])
        ),
        mana=state.mana.at[:, :P].set(
            jnp.where(hd, state.mana_max[:, :P], state.mana[:, :P])
        ),
        x=state.x.at[:, :P].set(
            jnp.where(hd, side * (LANE_HALF_LENGTH - 300.0), state.x[:, :P])
        ),
        y=state.y.at[:, :P].set(
            jnp.where(hd, (60.0 * (pslots % 5)).astype(jnp.float32), state.y[:, :P])
        ),
        respawn_at=state.respawn_at.at[:, :P].set(
            jnp.where(hd, -1.0, state.respawn_at[:, :P])
        ),
    )

    # waves
    wave_due = live & ~state.done & (state.dota_time >= state.next_wave_at)
    key, sub = jax.random.split(state.key)
    state = _spawn_waves(spec, state._replace(key=key), wave_due, sub)
    state = state._replace(
        next_wave_at=jnp.where(
            wave_due, state.dota_time + CREEP_WAVE_PERIOD, state.next_wave_at
        )
    )

    # timeout adjudication: (tower hp, team kills, team gold) lexicographic
    timed_out = live & ~state.done & (state.dota_time >= spec.max_dota_time)
    team_row_p = state.team[:, :P]
    is_rad = team_row_p == TEAM_RADIANT
    rk = (state.kills[:, :P] * is_rad).sum(1).astype(jnp.float32)
    dk = (state.kills[:, :P] * ~is_rad).sum(1).astype(jnp.float32)
    rg = (state.gold[:, :P] * is_rad).sum(1)
    dg = (state.gold[:, :P] * ~is_rad).sum(1)
    rt = state.health[:, spec.tower_lo]
    dt_ = state.health[:, spec.tower_lo + 1]
    r_wins = (rt > dt_) | ((rt == dt_) & ((rk > dk) | ((rk == dk) & (rg > dg))))
    d_wins = (dt_ > rt) | ((rt == dt_) & ((dk > rk) | ((rk == dk) & (dg > rg))))
    return state._replace(
        done=state.done | timed_out,
        winning_team=jnp.where(
            timed_out,
            jnp.where(r_wins, TEAM_RADIANT, jnp.where(d_wins, TEAM_DIRE, 0)),
            state.winning_team,
        ),
    )


# ---------------------------------------------------------------------------
# scripted bots (jnp port of vec_lane_sim.scripted_actions_vec)
# ---------------------------------------------------------------------------


def _scripted_actions(
    spec: VecSimSpec, state: SimState, dist: jnp.ndarray
) -> Actions:
    N, S, P = spec.n_games, spec.max_units, spec.n_players
    half = (spec.move_bins - 1) / 2.0
    my_team = state.team[:, :P]
    hard = state.control_modes == pb.CONTROL_SCRIPTED_HARD
    hero_alive = state.alive[:, :P]
    hp_frac = state.health[:, :P] / jnp.maximum(state.health_max[:, :P], 1.0)

    enemy = state.alive[:, None, :] & (state.team[:, None, :] != my_team[:, :, None])
    pd = dist[:, :P, :]
    d_enemy = jnp.where(enemy, pd, _BIG)

    is_hero_s = state.unit_type == pb.UNIT_HERO
    is_creep_s = state.unit_type == pb.UNIT_LANE_CREEP
    enemy_hero = enemy & is_hero_s[:, None, :]
    d_ehero = jnp.where(enemy_hero, pd, _BIG)

    out_type = jnp.full((N, P), pb.ACTION_NOOP, jnp.int32)
    out_mx = jnp.zeros((N, P), jnp.int32)
    out_my = jnp.zeros((N, P), jnp.int32)
    out_tgt = jnp.zeros((N, P), jnp.int32)
    out_abl = jnp.zeros((N, P), jnp.int32)

    def move_toward(mask, gx, gy, outs):
        o_type, o_mx, o_my = outs
        dx = gx - state.x[:, :P]
        dy = gy - state.y[:, :P]
        norm = jnp.hypot(dx, dy)
        okm = mask & (norm >= 1e-6)
        mx = jnp.clip(
            jnp.round(half + half * dx / jnp.maximum(norm, 1e-9)), 0, spec.move_bins - 1
        ).astype(jnp.int32)
        my = jnp.clip(
            jnp.round(half + half * dy / jnp.maximum(norm, 1e-9)), 0, spec.move_bins - 1
        ).astype(jnp.int32)
        return (
            jnp.where(okm, pb.ACTION_MOVE, o_type),
            jnp.where(okm, mx, o_mx),
            jnp.where(okm, my, o_my),
        )

    todo = hero_alive

    # HARD retreat
    near_ehero = d_ehero.min(axis=2) <= 900.0
    retreat = todo & hard & (hp_frac < 0.3) & near_ehero
    own_tower_x = jnp.where(
        my_team == TEAM_RADIANT, TOWER_X[TEAM_RADIANT], TOWER_X[TEAM_DIRE]
    ).astype(jnp.float32)
    out_type, out_mx, out_my = move_toward(
        retreat, own_tower_x, jnp.zeros_like(own_tower_x), (out_type, out_mx, out_my)
    )
    todo = todo & ~retreat

    # HARD nuke lowest-HP enemy hero in range
    castable = (state.mana[:, :P] >= NUKE_MANA) & (state.ability_cd[:, :P] <= 0.0)
    nukable = enemy_hero & (pd <= NUKE_RANGE)
    hp_key = jnp.where(nukable, state.health[:, None, :], _BIG)
    nuke_tgt = hp_key.argmin(axis=2).astype(jnp.int32)
    can_nuke = todo & hard & castable & nukable.any(axis=2)
    out_type = jnp.where(can_nuke, pb.ACTION_CAST, out_type)
    out_tgt = jnp.where(can_nuke, nuke_tgt, out_tgt)
    out_abl = jnp.where(can_nuke, NUKE_SLOT, out_abl)
    todo = todo & ~can_nuke

    in_range = enemy & (pd <= state.attack_range[:, :P, None] + 50.0)

    # HARD last-hit killable creep
    eff_dmg = state.damage[:, :P, None] * _armor_mult(state.armor[:, None, :])
    killable = in_range & is_creep_s[:, None, :] & (state.health[:, None, :] <= eff_dmg)
    kill_tgt = jnp.where(killable, state.health[:, None, :], _BIG).argmin(2).astype(jnp.int32)
    do_lh = todo & hard & killable.any(axis=2)
    out_type = jnp.where(do_lh, pb.ACTION_ATTACK_UNIT, out_type)
    out_tgt = jnp.where(do_lh, kill_tgt, out_tgt)
    todo = todo & ~do_lh

    # HARD harass enemy hero while healthy
    heroes_in_range = in_range & is_hero_s[:, None, :]
    harass_tgt = jnp.where(heroes_in_range, state.health[:, None, :], _BIG).argmin(2).astype(jnp.int32)
    do_harass = todo & hard & heroes_in_range.any(axis=2) & (hp_frac >= 0.5)
    out_type = jnp.where(do_harass, pb.ACTION_ATTACK_UNIT, out_type)
    out_tgt = jnp.where(do_harass, harass_tgt, out_tgt)
    todo = todo & ~do_harass

    # HARD pressure lowest-HP creep in range
    creeps_in_range = in_range & is_creep_s[:, None, :]
    press_tgt = jnp.where(creeps_in_range, state.health[:, None, :], _BIG).argmin(2).astype(jnp.int32)
    do_press = todo & hard & creeps_in_range.any(axis=2)
    out_type = jnp.where(do_press, pb.ACTION_ATTACK_UNIT, out_type)
    out_tgt = jnp.where(do_press, press_tgt, out_tgt)
    todo = todo & ~do_press

    # EASY / fallback: attack nearest in range
    near_tgt = jnp.where(in_range, pd, _BIG).argmin(2).astype(jnp.int32)
    do_atk = todo & in_range.any(axis=2)
    out_type = jnp.where(do_atk, pb.ACTION_ATTACK_UNIT, out_type)
    out_tgt = jnp.where(do_atk, near_tgt, out_tgt)
    todo = todo & ~do_atk

    # march toward nearest enemy (or mid)
    nearest_any = d_enemy.argmin(axis=2)
    has_enemy = d_enemy.min(axis=2) < _BIG
    at_nearest = _slot_mask(nearest_any, S)
    gx = jnp.where(has_enemy, _at_slot(state.x[:, None, :], at_nearest), 0.0)
    gy = jnp.where(has_enemy, _at_slot(state.y[:, None, :], at_nearest), 0.0)
    out_type, out_mx, out_my = move_toward(todo, gx, gy, (out_type, out_mx, out_my))

    return {
        "type": out_type, "move_x": out_mx, "move_y": out_my,
        "target_slot": out_tgt, "ability": out_abl,
    }
