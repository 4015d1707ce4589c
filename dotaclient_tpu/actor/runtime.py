"""Batched actor runtime: many envs, one jitted policy step.

The reference runs one asyncio ``agent.py`` process per game doing batch-1
CPU inference in its hot loop (SURVEY.md §3.1 — "the #1 throughput sin the
TPU rebuild fixes"). Here a single multiplexer owns N environment *lanes*
(an env × agent-controlled player pair), featurizes all of them, and advances
every lane with ONE batched, jitted ``policy.step`` on the device
(SURVEY.md §7 step 6; Podracer/SEED-style batched inference, PAPERS.md).

Rollout-chunk semantics (parity with the reference's truncated-BPTT
transport, SURVEY.md §5.7, and the learner's ``train.ppo.Batch`` contract):

* a chunk is at most ``ppo.rollout_len`` steps and never spans episodes —
  on episode end it is padded (``valid=0``) and shipped early;
* the chunk carries its initial LSTM state (``carry0``) and ``T+1``
  observations (the trailing one is the learner's bootstrap state);
* each chunk is tagged with the model version that produced it.

Weight refresh follows the reference's hot-swap discipline (SURVEY.md §3.4):
the pool polls the transport for the latest published weights between steps
and bumps its version tag.

Host↔device discipline (the round-1 bottleneck, SURVEY.md §7 hard-part 2):
exactly ONE jitted dispatch and ONE host fetch per env step. Host numpy
arrays are passed straight into the jitted call (the transfer rides the
async dispatch path — orders of magnitude cheaper here than an explicit,
synchronizing ``device_put``), the recurrent carry and the PRNG key stay
device-resident between steps (episode resets are applied inside the step
via a mask; the key is split inside), and everything the host loop needs —
packed actions, log-probs, and an f32 carry copy for ``carry0`` snapshots —
comes back in a single ``jax.device_get``. On latency-dominated links the
per-step cost is one round trip, independent of lane count.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.actor.window_stats import WindowedStatsMixin
from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.outcome import records as outcome_records
from dotaclient_tpu.utils import telemetry, utilization
from dotaclient_tpu.envs.env_api import LocalDotaEnv
from dotaclient_tpu.envs import lane_sim
from dotaclient_tpu.features import (
    Observation,
    decode_action,
    featurize,
    observation_to_dict,
    shaped_reward,
    stack_observations,
)
from dotaclient_tpu.models import distributions as D
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.protos import dota_pb2 as pb
from dotaclient_tpu.transport import Transport, decode_weights, encode_rollout


def build_game_config(config: RunConfig, seed: int) -> pb.GameConfig:
    """EnvConfig → GameConfig proto (the env-boundary config the reference
    kept as a proto too, SURVEY.md §5.6)."""
    env = config.env
    picks = []
    pool = env.hero_pool or (1,)
    rng = np.random.default_rng(seed)
    opp_mode = {
        "scripted_easy": pb.CONTROL_SCRIPTED_EASY,
        "scripted_hard": pb.CONTROL_SCRIPTED_HARD,
        "selfplay": pb.CONTROL_AGENT,
        "league": pb.CONTROL_AGENT,
    }[env.opponent]
    for team, mode in (
        (lane_sim.TEAM_RADIANT, pb.CONTROL_AGENT),
        (lane_sim.TEAM_DIRE, opp_mode),
    ):
        for _ in range(env.team_size):
            picks.append(
                pb.HeroPick(
                    team_id=team,
                    hero_id=int(rng.choice(pool)),
                    control_mode=mode,
                )
            )
    return pb.GameConfig(
        ticks_per_observation=env.ticks_per_observation,
        seed=seed,
        max_dota_time=env.max_dota_time,
        hero_picks=picks,
    )


@dataclasses.dataclass
class _Lane:
    """One agent-controlled player inside one environment."""

    env_idx: int
    player_id: int
    team_id: int
    prev_ws: pb.WorldState = None  # type: ignore[assignment]
    obs: Observation = None        # type: ignore[assignment]
    # chunk accumulators
    obs_seq: List[Observation] = dataclasses.field(default_factory=list)
    actions: List[Dict[str, int]] = dataclasses.field(default_factory=list)
    logps: List[float] = dataclasses.field(default_factory=list)
    rewards: List[float] = dataclasses.field(default_factory=list)
    dones: List[float] = dataclasses.field(default_factory=list)
    carry0: Tuple[np.ndarray, np.ndarray] = None  # type: ignore[assignment]
    # model version at chunk start — a mid-chunk weight refresh must not
    # re-label earlier steps as fresh, so the chunk ships with the OLDEST
    # version that contributed to it (conservative for staleness filtering).
    version0: int = 0
    # episode stats
    episode_reward: float = 0.0


class ActorPool(WindowedStatsMixin):
    """N-lane batched actor.

    ``opponent="selfplay"`` makes every hero an agent lane sharing the same
    params (the reference's self-play configs, BASELINE.json:8); scripted
    opponents are driven inside the env. League opponents (frozen past
    params) plug in through ``league.opponents`` (separate pools).
    """

    def __init__(
        self,
        config: RunConfig,
        policy: Policy,
        params: Any,
        transport: Optional[Transport] = None,
        env_factory: Callable[[], LocalDotaEnv] = LocalDotaEnv,
        seed: int = 0,
        version: int = 0,
        rollout_sink: Optional[Callable[[pb.Rollout], None]] = None,
    ) -> None:
        if config.model.core != "lstm":
            raise NotImplementedError(
                "ActorPool (the scalar gRPC-parity path) supports the LSTM "
                "core only; the vec/device actors handle any core"
            )
        self.config = config
        self.policy = policy
        self._reward_weights = dict(config.reward.as_dict())
        self._warn_no_anchor_support()
        # (params, version) swap atomically as one tuple: the learner thread
        # may refresh weights while the actor thread is mid-step, and a chunk
        # must never be tagged with a version newer than the params that
        # produced it (the staleness filter keys on the tag).
        self._weights = (params, version)
        self._chunk_version = version
        self.transport = transport
        self.rollout_sink = rollout_sink
        self._seed = seed
        self._next_rollout_id = 0
        self._next_game_seed = seed * 100_003

        self.envs: List[LocalDotaEnv] = [
            env_factory() for _ in range(config.env.n_envs)
        ]
        # per-env episode length in env steps (outcome plane, ISSUE 15)
        self._ep_env_steps: List[int] = [0] * config.env.n_envs
        self._outcome_bucket = outcome_records.opponent_bucket(
            config.env.opponent
        )
        self.lanes: List[_Lane] = []
        for i, env in enumerate(self.envs):
            self._reset_env(i, env)
        n = len(self.lanes)
        H = config.model.hidden_dim
        # Device-resident recurrent state + PRNG key (never pulled per step).
        self._carry_dev = policy.initial_state(n)
        self._key_dev = jax.random.PRNGKey(seed)
        self._reset_mask = np.zeros((n,), np.bool_)
        zeros_row = np.zeros((H,), np.float32)
        for i, lane in enumerate(self.lanes):
            self._begin_chunk(lane, (zeros_row, zeros_row))

        self._step_fn = jax.jit(self._device_step)
        # Rollout wire narrowing (ISSUE 7): encode kwargs derived once from
        # config, applied when chunks leave through a transport (the
        # in-proc rollout_sink keeps full-width protos for gRPC parity).
        from dotaclient_tpu.transport.serialize import rollout_wire_kwargs

        self._wire_kwargs = rollout_wire_kwargs(config)
        # throughput counters
        self.env_steps = 0
        self.rollouts_shipped = 0
        self.episodes_done = 0
        self.episode_rewards: List[float] = []
        self.wins = 0
        self._tel = telemetry.get_registry()
        # outcome counters exist (zeroed) from the first fleet snapshot on
        outcome_records.ensure_actor_metrics(self._tel)
        # Utilization plane (ISSUE 16): always-on phase accounting — keys
        # eager-created by the factory, None when the module knob is off
        # (one pointer test per call site, same discipline as faults).
        self._util = utilization.make_actor(self._tel)

    # -- env / lane lifecycle ---------------------------------------------

    def _warn_no_anchor_support(self) -> None:
        # same visibility discipline as the host-pool PFSP warning: a knob
        # this pool cannot honor must say so, not silently no-op
        cfg = self.config
        if cfg.env.opponent == "league" and cfg.league.anchor_prob > 0:
            print(
                "WARNING: league.anchor_prob is implemented by the "
                "device/fused/vec actors; this scalar pool runs pure "
                "snapshot self-play (no scripted-anchor games)",
                flush=True,
            )

    def _reset_env(self, env_idx: int, env: LocalDotaEnv) -> None:
        game_cfg = build_game_config(self.config, self._next_game_seed)
        self._next_game_seed += 1
        self._ep_env_steps[env_idx] = 0
        init = env.reset(game_cfg)
        assert init.status == pb.STATUS_OK
        # Lanes for this env: every agent-controlled hero.
        existing = [l for l in self.lanes if l.env_idx == env_idx]
        ws_by_team = {ws.team_id: ws for ws in init.world_states}
        agent_players = self._agent_players(game_cfg)
        if existing:
            assert len(existing) == len(agent_players)
            for lane, (player_id, team_id) in zip(existing, agent_players):
                lane.player_id = player_id
                lane.team_id = team_id
                ws = ws_by_team[team_id]
                lane.prev_ws = ws
                lane.obs = self._featurize(ws, player_id)
                lane.episode_reward = 0.0
        else:
            for player_id, team_id in agent_players:
                ws = ws_by_team[team_id]
                lane = _Lane(env_idx=env_idx, player_id=player_id, team_id=team_id)
                lane.prev_ws = ws
                lane.obs = self._featurize(ws, player_id)
                self.lanes.append(lane)

    @staticmethod
    def _agent_players(game_cfg: pb.GameConfig) -> List[Tuple[int, int]]:
        return [
            (pid, pick.team_id)
            for pid, pick in enumerate(game_cfg.hero_picks)
            if pick.control_mode == pb.CONTROL_AGENT
        ]

    def _featurize(self, ws: pb.WorldState, player_id: int) -> Observation:
        return featurize(ws, player_id, self.config.obs, self.config.actions)

    def _begin_chunk(
        self, lane: _Lane, carry0: Tuple[np.ndarray, np.ndarray]
    ) -> None:
        lane.obs_seq = []
        lane.actions = []
        lane.logps = []
        lane.rewards = []
        lane.dones = []
        lane.carry0 = (
            np.asarray(carry0[0], np.float32).copy(),
            np.asarray(carry0[1], np.float32).copy(),
        )
        lane.version0 = self._chunk_version

    # -- device step -------------------------------------------------------

    def _device_step(self, params, obs_batch, carry, key, reset_mask):
        """One batched actor step, fully on device: zero carry rows for lanes
        whose episode just ended, split the key, forward + sample. Outputs are
        split into a host-bound group (packed actions, logp, f32 carry for
        ``carry0`` snapshots — fetched together as ONE transfer) and the
        device-resident group (carry, key) that never leaves HBM."""
        key, sub = jax.random.split(key)
        keep = jnp.logical_not(reset_mask)[:, None].astype(carry[0].dtype)
        carry = (carry[0] * keep, carry[1] * keep)
        logits, _, new_carry = self.policy.apply(
            params, obs_batch, carry, method="step"
        )
        actions, logp = D.sample(sub, logits, obs_batch)
        packed = jnp.stack(
            [actions[h] for h in D.HEADS], axis=1
        ).astype(jnp.int32)
        carry_f32 = (
            new_carry[0].astype(jnp.float32),
            new_carry[1].astype(jnp.float32),
        )
        return (packed, logp, carry_f32), (new_carry, key)

    # -- public API --------------------------------------------------------

    def refresh_weights(self) -> bool:
        """Hot-swap to the latest published weights, if any (SURVEY.md §3.4)."""
        if self.transport is None:
            return False
        msg = self.transport.latest_weights()
        if msg is None or msg.version == self.version:
            return False
        # how far behind this actor was when it caught up — the per-actor
        # refresh lag (IMPACT-style staleness accounting, PAPERS.md)
        self._tel.gauge("actor/weight_refresh_lag").set(
            msg.version - self.version
        )
        version, tree = decode_weights(msg)
        self._weights = (jax.tree.map(jnp.asarray, tree), version)
        return True

    def set_params(self, params: Any, version: int) -> None:
        """Direct replicated-params refresh (in-process learner path — the
        'actors read replicated JAX params' mode of BASELINE.json:5)."""
        self._tel.gauge("actor/weight_refresh_lag").set(version - self.version)
        self._weights = (params, version)

    @property
    def params(self) -> Any:
        return self._weights[0]

    @property
    def version(self) -> int:
        return self._weights[1]

    def step(self) -> None:
        """Advance every lane by one environment step."""
        with self._tel.span("actor/step"):
            self._step_impl()
        self._tel.counter("actor/env_steps").inc(len(self.lanes))

    def _step_impl(self) -> None:
        obs_batch = stack_observations([l.obs for l in self.lanes])
        # One atomic weights read serves the whole step: dispatch uses these
        # params, and chunks beginning this step are tagged with this version.
        params, self._chunk_version = self._weights
        with self._tel.span("actor/infer"):
            host_out, (self._carry_dev, self._key_dev) = self._step_fn(
                params,
                obs_batch,
                self._carry_dev,
                self._key_dev,
                self._reset_mask,
            )
            # ONE host transfer for everything the host loop needs this step —
            # per-array fetches each pay a full device round trip.
            actions_np, logp_np, carry_np = jax.device_get(host_out)
        self._reset_mask[:] = False

        # Submit actions grouped per (env, team) — env steps once all agent
        # teams have acted (env_api contract). Everything from here to the
        # end of the observe/reward loop is env_step for the utilization
        # plane, EXCEPT the per-lane featurize calls (accumulated apart).
        t_env = time.perf_counter()
        feat_s = 0.0
        by_env_team: Dict[Tuple[int, int], List[pb.Action]] = {}
        for i, lane in enumerate(self.lanes):
            idx = {h: int(actions_np[i, j]) for j, h in enumerate(D.HEADS)}
            lane.actions.append(idx)
            lane.logps.append(float(logp_np[i]))
            lane.obs_seq.append(lane.obs)
            proto = decode_action(
                idx, lane.obs, lane.player_id,
                move_bins=self.config.actions.move_bins,
            )
            by_env_team.setdefault((lane.env_idx, lane.team_id), []).append(proto)
        for (env_idx, team_id), protos in by_env_team.items():
            self.envs[env_idx].act(
                pb.Actions(team_id=team_id, actions=protos)
            )

        # Observe, reward, detect episode/chunk boundaries.
        T = self.config.ppo.rollout_len
        finished: List[Tuple[int, _Lane, bool]] = []
        # every env advances one observation per pool step (episode-length
        # accounting for the outcome plane)
        for e in range(len(self.envs)):
            self._ep_env_steps[e] += 1
        step_terms: Dict[str, float] = {}
        for i, lane in enumerate(self.lanes):
            env = self.envs[lane.env_idx]
            resp = env.observe(lane.team_id)
            ws = resp.world_state
            r, terms = shaped_reward(
                lane.prev_ws, ws, lane.player_id,
                weights=self._reward_weights,
            )
            for term, tv in terms.items():
                step_terms[term] = step_terms.get(term, 0.0) + tv
            done = env.done
            lane.rewards.append(r)
            lane.dones.append(1.0 if done else 0.0)
            lane.episode_reward += r
            lane.prev_ws = ws
            t_f = time.perf_counter()
            lane.obs = self._featurize(ws, lane.player_id)
            feat_s += time.perf_counter() - t_f
            self.env_steps += 1
            if done:
                # Fresh episode ⇒ fresh recurrent state: the device step
                # zeroes this row on the NEXT call, and the new chunk's
                # carry0 snapshot below is zeros to match.
                self._reset_mask[i] = True
            if done or len(lane.actions) >= T:
                finished.append((i, lane, done))
            if done and lane is self._env_owner(lane.env_idx):
                self._on_episode_end(lane.env_idx, ws)
        outcome_records.add_reward_terms(self._tel, step_terms)
        self._util.phase("featurize", feat_s)
        self._util.phase("env_step", time.perf_counter() - t_env - feat_s)

        if finished:
            H = self.config.model.hidden_dim
            zeros_row = np.zeros((H,), np.float32)
            for i, lane, done in finished:
                self._finish_chunk(i, lane)
                carry0 = (
                    (zeros_row, zeros_row)
                    if done
                    else (carry_np[0][i], carry_np[1][i])
                )
                self._begin_chunk(lane, carry0)

        # Reset envs whose episode finished (after all lanes shipped chunks).
        for env_idx, env in enumerate(self.envs):
            if env.done:
                self._reset_env(env_idx, env)

    def _env_owner(self, env_idx: int) -> _Lane:
        """First lane of an env (used to count each episode once)."""
        return next(l for l in self.lanes if l.env_idx == env_idx)

    def _on_episode_end(self, env_idx: int, ws: pb.WorldState) -> None:
        """Episode bookkeeping (carry zeroing happens at the done site in
        ``step``; episode_reward resets in ``_reset_env``)."""
        self.episodes_done += 1
        owner = self._env_owner(env_idx)
        self.episode_rewards.append(owner.episode_reward)
        won = ws.winning_team == owner.team_id
        if won:
            self.wins += 1
        self.record_episode_outcome(
            self._outcome_bucket,
            won,
            self._ep_env_steps[env_idx],
            side=(
                "radiant"
                if owner.team_id == lane_sim.TEAM_RADIANT
                else "dire"
            ),
            registry=self._tel,
        )

    def _finish_chunk(self, lane_idx: int, lane: _Lane) -> None:
        """Pad, pack, and ship one rollout chunk."""
        t_enc = time.perf_counter()
        T = self.config.ppo.rollout_len
        n = len(lane.actions)
        assert 0 < n <= T
        valid = [1.0] * n + [0.0] * (T - n)
        # obs sequence: the n step observations + the current (bootstrap)
        # obs, padded to T+1 by repeating the bootstrap.
        obs_seq = lane.obs_seq + [lane.obs] * (T + 1 - n)
        arrays = {
            "obs": {
                k: np.stack([d[k] for d in map(observation_to_dict, obs_seq)])
                for k in observation_to_dict(obs_seq[0])
            },
            "actions": {
                h: np.asarray(
                    [a[h] for a in lane.actions] + [0] * (T - n), np.int32
                )
                for h in self.config.actions.head_sizes
            },
            "behavior_logp": np.asarray(
                lane.logps + [0.0] * (T - n), np.float32
            ),
            "rewards": np.asarray(lane.rewards + [0.0] * (T - n), np.float32),
            "dones": np.asarray(lane.dones + [1.0] * (T - n), np.float32),
            "valid": np.asarray(valid, np.float32),
            "carry0": (lane.carry0[0], lane.carry0[1]),
        }
        meta = dict(
            model_version=lane.version0,
            env_id=lane.env_idx,
            rollout_id=self._next_rollout_id,
            length=n,
            total_reward=float(np.sum(lane.rewards)),
        )
        self._next_rollout_id += 1
        t_ship = time.perf_counter()
        # chunk assembly above is encode; the publish leg is ship_wait
        self._util.phase("encode", t_ship - t_enc)
        if self.rollout_sink is not None:
            # in-proc consumers get full-width protos (gRPC-parity path —
            # no wire to save bytes on)
            self.rollout_sink(encode_rollout(arrays, **meta))
        elif self.transport is not None:
            self.transport.publish_rollout(
                encode_rollout(arrays, **meta, **self._wire_kwargs)
            )
        self._util.phase("ship_wait", time.perf_counter() - t_ship)
        self.rollouts_shipped += 1
        self._tel.counter("actor/rollouts_shipped").inc()
        self._tel.counter("actor/frames_shipped").inc(n)

    def run(self, n_steps: int, refresh_every: int = 8) -> Dict[str, float]:
        """Drive the pool for ``n_steps`` batched steps; returns stats."""
        for t in range(n_steps):
            if refresh_every and t % refresh_every == 0:
                self.refresh_weights()
                # cadence-gated fold (one clock compare per boundary)
                self._util.maybe_fold()
            self.step()
        return self.stats()

    def stats(self) -> Dict[str, float]:
        recent = self.episode_rewards[-20:]
        return {
            "env_steps": float(self.env_steps),
            "rollouts_shipped": float(self.rollouts_shipped),
            "episodes_done": float(self.episodes_done),
            "episode_reward_mean": float(np.mean(recent)) if recent else 0.0,
            "win_rate": (
                self.wins / self.episodes_done if self.episodes_done else 0.0
            ),
            **self.windowed_entries(),
        }
