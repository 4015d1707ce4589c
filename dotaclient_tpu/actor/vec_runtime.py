"""Vectorized actor pool: N games × P players stepped as arrays.

Same responsibilities and chunk semantics as ``actor.runtime.ActorPool``
(truncated-BPTT chunks with carry0 + T+1 obs + version tags, SURVEY.md §5.7,
§3.1) but the environment is a ``VecLaneSim`` and featurize / reward / action
translation are single vectorized calls (`features.vec_featurizer`) — the
round-2 fix for the Python-per-lane hot loop (VERDICT round 1, "What's weak"
#1). One jitted device dispatch and one host fetch per step, exactly like the
scalar pool.

Rollout delivery: in-process consumers take *decoded* rollouts (the
``(meta, arrays)`` form ``TrajectoryBuffer.add`` ingests) through
``rollout_sink`` — no proto round-trip on the hot path. The proto wire format
still applies when shipping through a ``Transport`` (cluster topology,
SURVEY.md §2.4).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.actor.window_stats import WindowedStatsMixin
from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.outcome import records as outcome_records
from dotaclient_tpu.utils import faults, fleet, telemetry, tracing, utilization
from dotaclient_tpu.envs.vec_lane_sim import (
    OPPONENT_CONTROL,
    VecLaneSim,
    VecSimSpec,
    apply_anchor_games,
    draft_games,
)
from dotaclient_tpu.features.vec_featurizer import VecFeaturizer, VecRewards
from dotaclient_tpu.models import distributions as D
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.protos import dota_pb2 as pb
from dotaclient_tpu.transport import (
    Transport,
    decode_weights,
    encode_rollout,
    encode_rollout_bytes,
)

DecodedRollout = Tuple[Dict[str, Any], Any]


def make_device_step(policy: Policy):
    """The batched actor device step (shared shape with
    ``ActorPool._device_step``): zero reset carries, split key, forward +
    sample; host-bound outputs packed into one fetch."""

    from dotaclient_tpu.models.policy import mask_carry

    def _step(params, obs_batch, carry, key, reset_mask):
        key, sub = jax.random.split(key)
        carry = mask_carry(carry, 1.0 - reset_mask.astype(jnp.float32))
        logits, _, new_carry = policy.apply(params, obs_batch, carry, method="step")
        actions, logp = D.sample(sub, logits, obs_batch)
        packed = jnp.stack([actions[h] for h in D.HEADS], axis=1).astype(jnp.int32)
        carry_f32 = jax.tree.map(lambda t: t.astype(jnp.float32), new_carry)
        return (packed, logp, carry_f32), (new_carry, key)

    return jax.jit(_step)


class VecActorPool(WindowedStatsMixin):
    """Batched actor over a vectorized sim. Public surface matches
    ``ActorPool`` (step/run/stats/set_params/refresh_weights/params/version).
    """

    def __init__(
        self,
        config: RunConfig,
        policy: Policy,
        params: Any,
        transport: Optional[Transport] = None,
        seed: int = 0,
        version: int = 0,
        rollout_sink: Optional[Callable[[List[DecodedRollout]], None]] = None,
    ) -> None:
        self.config = config
        self.policy = policy
        self._weights = (params, version)
        self.transport = transport
        self.rollout_sink = rollout_sink
        env = config.env

        N, P = env.n_envs, 2 * env.team_size
        spec = VecSimSpec(
            n_games=N,
            team_size=env.team_size,
            max_units=config.obs.max_units,
            ticks_per_obs=env.ticks_per_observation,
            max_dota_time=env.max_dota_time,
            move_bins=config.actions.move_bins,
        )
        hero_ids, control = draft_games(
            N, env.team_size, env.hero_pool, env.opponent, seed
        )
        opp_mode = OPPONENT_CONTROL[env.opponent]
        # No per-game attribution mask here (unlike DeviceActor): host-pool
        # league draws never feed PFSP outcome attribution (the learner
        # warns and keeps the uniform prior), so there is nothing for
        # anchor games to contaminate.
        self.n_anchor_games = apply_anchor_games(
            control, env.team_size, env.opponent, config.league
        )
        self.sim = VecLaneSim(spec, hero_ids, control, seed=seed)
        self._reseed_rng = np.random.default_rng(seed ^ 0x5EED)

        # Learner lanes: every CONTROL_AGENT player on the Radiant side plus —
        # in self-play — the Dire side (all lanes ship experience and share
        # the live params; league opponents get frozen params via
        # ``set_opponent`` and never ship).
        if opp_mode == pb.CONTROL_AGENT:
            learner_players = list(range(P)) if env.opponent == "selfplay" else list(range(env.team_size))
            opponent_players = (
                [] if env.opponent == "selfplay" else list(range(env.team_size, P))
            )
        else:
            learner_players = list(range(env.team_size))
            opponent_players = []
        self.feat = VecFeaturizer(self.sim, config.obs, config.actions, learner_players)
        self.rewards = VecRewards(
            self.sim, learner_players, weights=dict(config.reward.as_dict())
        )
        self._opponent: Optional["_OpponentLanes"] = None
        if opponent_players:
            self._opponent = _OpponentLanes(
                self, opponent_players, params, version
            )

        L = self.feat.n_lanes
        self.n_lanes = L
        T = config.ppo.rollout_len

        self._carry_dev = policy.initial_state(L)
        self._key_dev = jax.random.PRNGKey(seed)
        self._reset_mask = np.zeros((L,), np.bool_)
        self._step_fn = make_device_step(policy)

        obs0 = self.feat.featurize_all()
        self._pending_obs = obs0
        self._obs_buf = {
            k: np.zeros((L, T + 1) + v.shape[1:], v.dtype) for k, v in obs0.items()
        }
        self._act_buf = np.zeros((L, T, len(D.HEADS)), np.int32)
        self._logp_buf = np.zeros((L, T), np.float32)
        self._rew_buf = np.zeros((L, T), np.float32)
        self._done_buf = np.zeros((L, T), np.float32)
        self._cursor = np.zeros((L,), np.int64)
        # carry0 snapshots: host pytree mirroring the policy's carry layout
        # (LSTM (h, c) or transformer KV cache), f32
        self._carry0 = jax.tree.map(
            lambda t: np.zeros(t.shape, np.float32), self._carry_dev
        )
        self._version0 = np.full((L,), version, np.int64)
        self._lane_reward = np.zeros((L,), np.float64)

        self._next_rollout_id = 0
        self.env_steps = 0
        self.rollouts_shipped = 0
        self.episodes_done = 0
        self.episode_rewards: List[float] = []
        self.wins = 0
        self._tel = telemetry.get_registry()
        # Outcome plane (ISSUE 15): per-game episode-length accounting +
        # the opponent bucket this pool's games attribute to; counters
        # eager-created so the first fleet snapshot ships the zeroed set.
        outcome_records.ensure_actor_metrics(self._tel)
        self._outcome_bucket = outcome_records.opponent_bucket(env.opponent)
        self._ep_game_steps = np.zeros((N,), np.int64)
        self._faults = faults.get()   # None unless chaos injection is on
        # Fleet-health publisher (ISSUE 13): captured ONCE like the fault
        # registry and the tracer — with the fanout off (in-proc pools,
        # --fleet-interval 0) the run loop pays exactly one `is not None`
        # test per refresh boundary (pinned by test).
        self._fleet = fleet.get()
        # Pipeline tracing (ISSUE 12): the tracer is captured ONCE, like
        # the fault registry — with tracing off the ship path pays exactly
        # one `is not None` test per emit batch (pinned by test). Per-lane
        # chunk-start stamps exist only when tracing is on.
        self._tracer = tracing.get()
        # Utilization plane (ISSUE 16): always-on phase accounting — keys
        # eager-created by the factory, None when the module knob is off
        # (one pointer test per call site, same discipline as faults).
        self._util = utilization.make_actor(self._tel)
        self._actor_tag = seed & 0xFFFF
        self._chunk_start = (
            np.full((L,), tracing.now()) if self._tracer is not None else None
        )
        # Rollout wire narrowing (ISSUE 7): encode-time kwargs derived once
        # from config. In-proc delivery (rollout_sink) ships full-width
        # decoded arrays; the learner's buffer quantizes at its own door
        # per its config.
        from dotaclient_tpu.transport.serialize import rollout_wire_kwargs

        self._wire_kwargs = rollout_wire_kwargs(config)
        # Every distinct weight version this pool has ever APPLIED — the
        # chaos harness's evidence that no poisoned (health-blocked)
        # version reached an actor (scripts/chaos_run.py divergence
        # scenario; bounded by the number of publishes).
        self.versions_applied = {version}

    # -- weights -----------------------------------------------------------

    @property
    def params(self) -> Any:
        return self._weights[0]

    @property
    def version(self) -> int:
        return self._weights[1]

    def set_params(self, params: Any, version: int) -> None:
        # per-actor refresh lag: how many optimizer versions this pool was
        # behind at the moment it caught up (IMPACT-style staleness)
        self._tel.gauge("actor/weight_refresh_lag").set(version - self.version)
        self._weights = (params, version)
        self.versions_applied.add(version)

    def set_opponent(self, params: Any, version: int) -> None:
        """Give the opponent lanes (league mode) their frozen params."""
        if self._opponent is None:
            raise ValueError("no opponent lanes (opponent is scripted or selfplay)")
        self._opponent.set_params(params, version)

    def refresh_weights(self) -> bool:
        if self.transport is None:
            return False
        msg = self.transport.latest_weights()
        if msg is None or msg.version == self.version:
            return False
        self._tel.gauge("actor/weight_refresh_lag").set(
            msg.version - self.version
        )
        version, tree = decode_weights(msg)
        self._weights = (jax.tree.map(jnp.asarray, tree), version)
        self.versions_applied.add(version)
        if self._tracer is not None:
            # staleness attribution (ISSUE 12): the publish-side trace
            # record (when the learner traces too) dates this version's
            # fanout; the apply event closes the publish→apply leg
            from dotaclient_tpu.transport.serialize import weights_trace

            rec = tracing.parse_blob(weights_trace(msg) or b"")
            publish_ts = rec["hops"][0][1] if rec and rec["hops"] else None
            self._tracer.emit(
                "apply", version=int(version), publish_ts=publish_ts
            )
        return True

    # -- stepping ----------------------------------------------------------

    def step(self) -> None:
        """Advance every game one step: one device dispatch, one fetch."""
        with self._tel.span("actor/step"):
            self._step_impl()
        self._tel.counter("actor/env_steps").inc(self.n_lanes)

    def _step_impl(self) -> None:
        cfg = self.config
        T = cfg.ppo.rollout_len
        L = self.n_lanes
        lanes = np.arange(L)
        obs = self._pending_obs
        params, version = self._weights

        # actor/infer = jitted dispatch + the one host fetch. The opponent
        # stays between them (overlapping the device) but its host compute
        # must not be attributed to inference, so time the two segments
        # explicitly instead of spanning the whole block.
        t0 = time.perf_counter()
        host_out, (self._carry_dev, self._key_dev) = self._step_fn(
            params, obs, self._carry_dev, self._key_dev, self._reset_mask
        )
        infer_s = time.perf_counter() - t0
        opp_actions = None
        if self._opponent is not None:
            opp_actions = self._opponent.step()
        t1 = time.perf_counter()
        actions_np, logp_np, carry_np = jax.device_get(host_out)
        infer_s += time.perf_counter() - t1
        self._tel.timer("span/actor/infer").observe(infer_s)
        self._reset_mask[:] = False

        # record pre-action obs + sampled actions at each lane's cursor
        cur = self._cursor
        for k, v in obs.items():
            self._obs_buf[k][lanes, cur] = v
        self._act_buf[lanes, cur] = actions_np
        self._logp_buf[lanes, cur] = logp_np

        sim_actions = self.feat.actions_to_sim(actions_np)
        if opp_actions is not None:
            for k in sim_actions:
                np.copyto(
                    sim_actions[k], opp_actions[k],
                    where=self._opponent.player_mask[None, :],
                )
        t_env = time.perf_counter()
        self.sim.step(sim_actions)

        r = self.rewards.compute()                                 # [L]
        # env_step = sim advance + reward compute (both host-side
        # simulation work, indivisible from the env's point of view)
        self._util.phase("env_step", time.perf_counter() - t_env)
        # outcome plane: every live game advanced one env step, and the
        # step's weighted per-term reward sums feed the decomposition
        self._ep_game_steps += 1
        outcome_records.add_reward_terms(
            self._tel, self.rewards.last_term_sums
        )
        done_game = self.sim.done                                  # [N]
        A = len(self.feat.agent_players)
        done_lane = np.repeat(done_game, A)                        # [L]
        self._rew_buf[lanes, cur] = r
        self._done_buf[lanes, cur] = done_lane
        self._lane_reward += r
        self._cursor += 1
        self.env_steps += L

        t_feat = time.perf_counter()
        obs_next = self.feat.featurize_all()
        self._util.phase("featurize", time.perf_counter() - t_feat)
        finished = (self._cursor >= T) | done_lane
        if finished.any():
            self._emit_chunks(np.nonzero(finished)[0], done_lane, obs_next, carry_np, version)

        if done_game.any():
            games = np.nonzero(done_game)[0]
            self._record_episodes(games)
            self.sim.reset(
                games,
                seeds=self._reseed_rng.integers(0, 2**31 - 1, size=len(games)),
            )
            # Terminal→fresh state is not an experienced transition: without a
            # re-snapshot the next compute() would credit the new episode's
            # first action with the (huge, negative) reset delta.
            self.rewards.snapshot()
            if self._opponent is not None:
                self._opponent.on_reset(games)
            self._reset_mask |= done_lane
            t_feat = time.perf_counter()
            obs_next = self.feat.featurize_all()  # fresh-episode observations
            self._util.phase("featurize", time.perf_counter() - t_feat)
        self._pending_obs = obs_next

    def _emit_chunks(
        self,
        lanes: np.ndarray,
        done_lane: np.ndarray,
        obs_next: Dict[str, np.ndarray],
        carry_np: Tuple[np.ndarray, np.ndarray],
        version: int,
    ) -> None:
        """Ship finished lanes' chunks; reset their accumulators."""
        cfg = self.config
        T = cfg.ppo.rollout_len
        out: List[DecodedRollout] = []
        blobs: List[Optional[bytes]] = []   # wire trace blob per chunk
        t_enc = time.perf_counter()
        for l in lanes:
            n = int(self._cursor[l])
            done = bool(done_lane[l])
            # bootstrap obs at position n; pad the rest by repeating it
            for k, v in obs_next.items():
                self._obs_buf[k][l, n:] = v[l]
            # pad steps beyond n
            self._act_buf[l, n:] = 0
            self._logp_buf[l, n:] = 0.0
            self._rew_buf[l, n:] = 0.0
            self._done_buf[l, n:] = 1.0
            valid = np.zeros((T,), np.float32)
            valid[:n] = 1.0
            arrays = {
                "obs": {k: v[l].copy() for k, v in self._obs_buf.items()},
                "actions": {
                    h: self._act_buf[l, :, j].copy()
                    for j, h in enumerate(D.HEADS)
                },
                "behavior_logp": self._logp_buf[l].copy(),
                "rewards": self._rew_buf[l].copy(),
                "dones": self._done_buf[l].copy(),
                "valid": valid,
                "carry0": jax.tree.map(lambda b: b[l].copy(), self._carry0),
            }
            meta = {
                "model_version": int(self._version0[l]),
                "env_id": int(l) // max(len(self.feat.agent_players), 1),
                "rollout_id": self._next_rollout_id,
                "length": n,
                "total_reward": float(self._rew_buf[l, :n].sum()),
            }
            self._next_rollout_id += 1
            trace_blob = None
            if self._tracer is not None:
                # per-lane chunk window: collect = when this lane's chunk
                # began accumulating (previous emit / pool start)
                collect_ts = float(self._chunk_start[l])
                self._chunk_start[l] = tracing.now()
                if self._tracer.should_sample():
                    rec = tracing.new_record(
                        self._tracer.next_tid(self._actor_tag),
                        self._actor_tag,
                        meta["model_version"],
                    )
                    rec["hops"].append(["collect", collect_ts])
                    tracing.append_hop(rec, "encode")
                    # actor-side partial record (the merge's origin-side
                    # evidence even when this process is later SIGKILLed)
                    self._tracer.emit_chunk(rec)
                    if self.rollout_sink is not None:
                        # in-proc delivery: the host record rides the meta
                        # directly — downstream hops append to it in place
                        meta["trace"] = rec
                    else:
                        trace_blob = tracing.record_to_blob(rec)
            blobs.append(trace_blob)
            if self._faults is not None and self._faults.fire(
                "actor.nonfinite_payload"
            ):
                # semantic-integrity chaos (ISSUE 6): a NaN reward ships in
                # an otherwise well-formed frame — the CRC layer passes it,
                # the learner buffer's admission control must reject it
                arrays["rewards"][0] = np.nan
            out.append((meta, arrays))
            # next chunk state
            self._cursor[l] = 0
            self._version0[l] = version
            if done:
                for buf in jax.tree.leaves(self._carry0):
                    buf[l] = 0.0
            else:
                for buf, src in zip(
                    jax.tree.leaves(self._carry0), jax.tree.leaves(carry_np)
                ):
                    buf[l] = src[l]
        # encode = chunk assembly (buffer slicing, pad, trace stamps);
        # the publish leg below is ship_wait
        self._util.phase("encode", time.perf_counter() - t_enc)
        self._tel.counter("actor/rollouts_shipped").inc(len(out))
        self._tel.counter("actor/frames_shipped").inc(
            float(sum(m["length"] for m, _ in out))
        )
        t_ship = time.perf_counter()
        if self.rollout_sink is not None:
            self.rollout_sink(out)
        elif self.transport is not None:
            # wire fast path: C encoder straight from the numpy buffers when
            # the transport ships bytes (socket/AMQP); in-proc passes protos
            publish_bytes = getattr(
                self.transport, "publish_rollout_bytes", None
            )
            for (meta, arrays), blob in zip(out, blobs):
                if publish_bytes is not None:
                    publish_bytes(
                        encode_rollout_bytes(
                            arrays, **meta, **self._wire_kwargs, trace=blob
                        )
                    )
                else:
                    self.transport.publish_rollout(
                        encode_rollout(
                            arrays, **meta, **self._wire_kwargs, trace=blob
                        )
                    )
        self._util.phase("ship_wait", time.perf_counter() - t_ship)
        self.rollouts_shipped += len(out)

    def _record_episodes(self, games: np.ndarray) -> None:
        from dotaclient_tpu.envs.lane_sim import TEAM_RADIANT

        A = len(self.feat.agent_players)
        owner_team = self.sim.player_team(int(self.feat.agent_players[0]))
        side = "radiant" if owner_team == TEAM_RADIANT else "dire"
        for g in games:
            self.episodes_done += 1
            owner_lane = int(g) * A
            self.episode_rewards.append(float(self._lane_reward[owner_lane]))
            won = int(self.sim.winning_team[g]) == owner_team
            if won:
                self.wins += 1
            # anchor games (the first n_anchor_games) played a scripted
            # bot regardless of the pool's nominal opponent mode
            bucket = (
                "vs_scripted"
                if int(g) < self.n_anchor_games
                else self._outcome_bucket
            )
            self.record_episode_outcome(
                bucket,
                won,
                int(self._ep_game_steps[g]),
                side=side,
                registry=self._tel,
            )
            self._ep_game_steps[int(g)] = 0
            self._lane_reward[int(g) * A:(int(g) + 1) * A] = 0.0

    # -- driving -----------------------------------------------------------

    def run(self, n_steps: int, refresh_every: int = 8) -> Dict[str, float]:
        for t in range(n_steps):
            if refresh_every and t % refresh_every == 0:
                self.refresh_weights()
                if self._fleet is not None and self.transport is not None:
                    # cadence-gated inside (one clock compare); send
                    # errors propagate like a failed rollout publish —
                    # the actor's reconnect machinery owns them
                    self._fleet.maybe_publish(self.transport)
                # cadence-gated fold (one clock compare) at refresh
                # boundaries, same rhythm as the fleet publisher
                self._util.maybe_fold()
            self.step()
        return self.stats()

    def flush_partial(self) -> int:
        """Ship every lane's in-progress (cursor > 0) chunk NOW — the
        graceful-stop path (ISSUE 4): a SIGTERM'd actor flushes the partial
        rollouts it is holding instead of discarding up to
        ``rollout_len - 1`` steps of experience per lane. Chunks go out with
        their true ``length`` and a zero-padded tail exactly like the
        episode-boundary partials ``_emit_chunks`` already ships, so the
        learner's buffer needs nothing new. Returns the chunk count."""
        lanes = np.nonzero(self._cursor > 0)[0]
        if len(lanes) == 0:
            return 0
        carry_np = jax.device_get(self._carry_dev)
        self._emit_chunks(
            lanes,
            np.zeros(self.n_lanes, dtype=bool),
            self._pending_obs,
            carry_np,
            self.version,
        )
        return len(lanes)

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


class _OpponentLanes:
    """Opponent-controlled players (league mode): frozen params drive the
    Dire side through a second featurizer + device step; their experience is
    never shipped (SURVEY.md §7 step 7)."""

    def __init__(
        self,
        pool: VecActorPool,
        players: List[int],
        params: Any,
        version: int,
    ) -> None:
        self.pool = pool
        self.players = players
        self.player_mask = np.zeros((pool.sim.spec.n_players,), bool)
        self.player_mask[players] = True
        self.feat = VecFeaturizer(
            pool.sim, pool.config.obs, pool.config.actions, players
        )
        self._weights = (params, version)
        L = self.feat.n_lanes
        self._carry = pool.policy.initial_state(L)
        self._key = jax.random.PRNGKey(hash(tuple(players)) & 0x7FFFFFFF)
        self._reset = np.zeros((L,), np.bool_)

    def set_params(self, params: Any, version: int) -> None:
        self._weights = (params, version)

    def on_reset(self, games: np.ndarray) -> None:
        A = len(self.players)
        for g in games:
            self._reset[int(g) * A:(int(g) + 1) * A] = True

    def step(self) -> Dict[str, np.ndarray]:
        obs = self.feat.featurize_all()
        params, _ = self._weights
        (packed, _, _), (self._carry, self._key) = self.pool._step_fn(
            params, obs, self._carry, self._key, self._reset
        )
        self._reset[:] = False
        return self.feat.actions_to_sim(jax.device_get(packed))
