"""On-device rollout generation: policy + env + reward in ONE XLA program.

The third and fastest actor (after the scalar proto pool and the numpy
vectorized pool): the jittable ``jax_lane_sim`` makes the entire experience
loop a ``lax.scan`` — featurize → policy step → sample → env step → reward →
in-scan episode reset — compiled once and run for a whole T-step chunk per
dispatch. Per-chunk host traffic is ZERO on the experience path (chunks are
consumed device-to-device by the trajectory buffer); only tiny episode stats
are fetched, and only at log boundaries.

This is the Anakin/Podracer architecture (PAPERS.md [P:7]) and the design
answer to SURVEY.md §7 hard-part 2: a host-driven actor pays a host↔device
round trip per env step, which bounds it by sync rate regardless of batch
size; the on-device loop is bounded by compute instead.

Chunks SPAN episodes (valid is all-ones; ``dones`` marks boundaries and the
learner's sequence mode resets the carry mid-chunk — ``Policy.sequence``) so
no frame is ever padding: fixed shapes, zero waste.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.envs import jax_lane_sim as sim_mod
from dotaclient_tpu.envs.lane_sim import TICKS_PER_SECOND
from dotaclient_tpu.envs.vec_lane_sim import VecSimSpec, draft_games
from dotaclient_tpu.features.jax_featurizer import (
    JaxFeaturizer,
    shaped_reward_terms,
)
from dotaclient_tpu.features.reward import fold_terms
from dotaclient_tpu.models import distributions as D
from dotaclient_tpu.models.lanes import LaneBlocks
from dotaclient_tpu.models.policy import Policy, require_episode_fits
from dotaclient_tpu.outcome import ingraph as outcome_ingraph
from dotaclient_tpu.outcome import records as outcome_records
from dotaclient_tpu.protos import dota_pb2 as pb
from dotaclient_tpu.utils import telemetry


class DeviceActorState(NamedTuple):
    """Everything the rollout loop carries across chunks, device-resident."""

    sim: sim_mod.SimState
    carry: Any       # learner lanes' recurrent carry (the core's pytree)
    opp_carry: Any   # opponent lanes' (or one dummy lane's)
    # f32/u32 [N, 2] per-GAME PRNG keys: each game's lanes sample from that
    # game's key, so action sampling is shard-local when the game axis is
    # partitioned over the mesh (and bitwise independent of the shard count)
    key: jnp.ndarray
    ep_return: jnp.ndarray                       # f32 [L] running episode return
    # i32 [N] env steps into each game's CURRENT episode (outcome plane:
    # episode length at the done site, reset in-scan)
    ep_steps: jnp.ndarray
    # cumulative episode stats, accumulated IN the rollout program as
    # per-game/per-lane PARTIALS (shard-local, no in-program collective);
    # a drain fetches them and reduce_device_stats sums the game axis
    stats: Dict[str, jnp.ndarray]


def actor_state_sharding(state: DeviceActorState, mesh, mesh_config):
    """The lane sharding of one ``DeviceActorState``: a matching tree of
    ``NamedSharding``s, game/lane leading axes partitioned over the
    (dcn×)data mesh axes, true scalars replicated.

    One rule (``parallel.mesh.row_sharding``): a leaf whose leading axis
    divides the batch shard count is data-sharded, anything else is
    replicated. Lane order is game-major (lane = game·A + player), so a
    game count divisible by the shard count keeps every derived lane
    tensor — featurized obs, carries, rewards — local to its games' shard;
    ``make_fused_step`` enforces that divisibility up front. The sim's
    batch-wide PRNG key (creep-wave jitter only) is pinned replicated
    explicitly: its [2] shape must never be mistaken for a 2-row batch.
    """
    from dotaclient_tpu.parallel.mesh import replicated, row_sharding

    repl = replicated(mesh)

    def rows(leaf):
        n = leaf.shape[0] if getattr(leaf, "ndim", 0) else 0
        return row_sharding(mesh, mesh_config, n)

    sim_sh = state.sim._replace(
        **{
            f: (repl if f == "key" else rows(getattr(state.sim, f)))
            for f in sim_mod.SimState._fields
        }
    )
    return DeviceActorState(
        sim=sim_sh,
        carry=jax.tree.map(rows, state.carry),
        opp_carry=jax.tree.map(rows, state.opp_carry),
        key=rows(state.key),
        ep_return=rows(state.ep_return),
        ep_steps=rows(state.ep_steps),
        stats=jax.tree.map(rows, state.stats),
    )


def own_buffers(tree: Any) -> Any:
    """``tree`` with every leaf a buffer of its own: a leaf that shares its
    device buffer with an earlier one (``sim.init_state`` builds many fields
    from one zeros array) is copied. A program that donates the actor state
    would otherwise donate that buffer twice."""
    seen: set = set()

    def own(x):
        buffers = {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}
        if seen & buffers:
            return jnp.copy(x)
        seen.update(buffers)
        return x

    return jax.tree.map(own, tree)


def reduce_device_stats(stats: Dict[str, Any]) -> Dict[str, Any]:
    """Collapse fetched per-game/per-lane stat partials to the scalar dict
    the host surfaces expect (counters → scalars, the per-game episode-
    length histogram ``[N, B]`` → ``[B]``). Pure host numpy — the drain
    reduces AFTER its one batched fetch; scalar-shaped legacy accumulators
    pass through unchanged."""
    out: Dict[str, Any] = {}
    for k, v in stats.items():
        if isinstance(v, dict):
            out[k] = reduce_device_stats(v)
        elif k == "out_ep_len_hist":
            a = np.asarray(v)
            out[k] = a.sum(axis=0) if a.ndim == 2 else a
        else:
            out[k] = np.asarray(v).sum()
    return out


def sample_per_game(
    keys: jnp.ndarray, logits, obs, n_games: int
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """``D.sample`` vmapped over the game axis: lanes are game-major, so
    each game's block of lanes draws from that game's own key ``[N, 2]``.
    Random-bit generation therefore partitions WITH the games when they
    shard over a mesh, and the sampled actions are bitwise independent of
    the shard count. A core that decodes over passes drew between them."""
    if "act_stage" in logits: return logits["actions"], logits["logp"]   # noqa: E701
    def split_g(t):
        return t.reshape((n_games, t.shape[0] // n_games) + t.shape[1:])

    def merge_g(t):
        return t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:])

    acts, logp = jax.vmap(D.sample)(
        keys, jax.tree.map(split_g, logits), jax.tree.map(split_g, obs)
    )
    return jax.tree.map(merge_g, acts), merge_g(logp)


def build_spec(config: RunConfig) -> VecSimSpec:
    env = config.env
    return VecSimSpec(
        n_games=env.n_envs,
        team_size=env.team_size,
        max_units=config.obs.max_units,
        ticks_per_obs=env.ticks_per_observation,
        max_dota_time=env.max_dota_time,
        move_bins=config.actions.move_bins,
    )


def lane_split(config: RunConfig) -> Tuple[list, list]:
    """(learner players, opponent players) per the opponent mode — identical
    to ``VecActorPool``'s split."""
    env = config.env
    P = 2 * env.team_size
    if env.opponent == "selfplay":
        return list(range(P)), []
    if env.opponent == "league":
        return list(range(env.team_size)), list(range(env.team_size, P))
    return list(range(env.team_size)), []


class DeviceActor:
    """Owns device-resident env+policy state; emits device chunk batches.

    API parallel to the pools where it makes sense (``stats`` /
    ``drain_stats`` are the host-visible surface); the unit of work is
    ``collect(params, opp_params=...)`` → one chunk batch [L, T, ...],
    already on device, ready for ``TrajectoryBuffer.add_device``. Opponent
    params are per-call (the league pool samples a fresh opponent each
    chunk) rather than stored setter state.
    """

    def __init__(
        self,
        config: RunConfig,
        policy: Policy,
        seed: int = 0,
        registry: Optional[telemetry.Registry] = None,
        mesh=None,
        mesh_config=None,
    ) -> None:
        self.config = config
        self.policy = policy
        self.spec = build_spec(config)
        learner_players, opponent_players = lane_split(config)
        self.learner_players = learner_players
        self.opponent_players = opponent_players
        self.feat = JaxFeaturizer(
            self.spec, config.obs, config.actions, learner_players
        )
        self._opp_feat = (
            JaxFeaturizer(self.spec, config.obs, config.actions, opponent_players)
            if opponent_players
            else None
        )
        self.n_lanes = self.feat.n_lanes
        # an episode ends at the first observation at or past max_dota_time
        require_episode_fits(
            config.model,
            int(np.ceil(
                config.env.max_dota_time * TICKS_PER_SECOND
                / config.env.ticks_per_observation
            )) + 1,
            config.ppo.rollout_len,
        )

        N, P = self.spec.n_games, self.spec.n_players
        hero_ids, control = draft_games(
            N, config.env.team_size, config.env.hero_pool,
            config.env.opponent, seed,
        )
        # League anchor games: shared scheme with the host vec pool
        # (envs.vec_lane_sim.apply_anchor_games — the sim's control-mode
        # override wins over the snapshot policy's actions there). Keeps
        # fight/push behavior in an otherwise pure self-play meta.
        from dotaclient_tpu.envs.vec_lane_sim import apply_anchor_games

        self.n_anchor_games = apply_anchor_games(
            control, config.env.team_size, config.env.opponent, config.league
        )
        # per-game mask of NON-anchor games: PFSP attribution must not
        # credit/blame a snapshot for games a scripted bot actually played
        self._league_game_mask = jnp.arange(N) >= self.n_anchor_games

        key = jax.random.PRNGKey(seed)
        key, k_init = jax.random.split(key)
        sim0 = sim_mod.init_state(self.spec, hero_ids, control, k_init)
        opp_lanes = max(len(opponent_players) * N, 1)
        self._own = lambda tree: tree     # until donate_state()
        self.state = DeviceActorState(
            sim=sim0,
            carry=policy.initial_state(self.n_lanes),
            opp_carry=policy.initial_state(opp_lanes),
            # one independent key per game: sampling stays shard-local (and
            # bitwise shard-count-invariant) when games partition over a mesh
            key=jax.random.split(key, N),
            ep_return=jnp.zeros((self.n_lanes,), jnp.float32),
            ep_steps=jnp.zeros((N,), jnp.int32),
            stats=self._zero_stats(),
        )
        # Pod-scale fused Anakin (ISSUE 18): when a mesh is given, the actor
        # state is COMMITTED lane-sharded at construction — games (and the
        # game-major lanes they own) partition over the (dcn×)data axes, so
        # the fused program's pinned in_shardings are satisfied by layout
        # instead of a first-call reshard, and the buffered device mode's
        # inferred-sharding collect computes on local lanes too.
        self.mesh = mesh
        self.mesh_config = mesh_config if mesh_config is not None else (
            config.mesh if mesh is not None else None
        )
        if mesh is not None:
            from dotaclient_tpu.parallel.mesh import batch_shard_count

            # EFFECTIVE lane shard count: the games (and their game-major
            # lanes) must split evenly over the batch shards, else
            # row_sharding has degraded the layout to replicated and the
            # honest answer is 1 — mirrors train/fused.py's eff_shards.
            n = batch_shard_count(mesh, self.mesh_config)
            self.lane_shards = (
                n if self.n_lanes % n == 0 and N % n == 0 else 1
            )
            self.state = jax.device_put(
                self.state,
                actor_state_sharding(self.state, mesh, self.mesh_config),
            )
        else:
            self.lane_shards = 1
        self.lanes_per_shard = self.n_lanes // self.lane_shards
        # Outcome plane (ISSUE 15): static per-game opponent-bucket masks
        # for the in-graph done-masked reductions, and the owner side the
        # drained stats attribute to.
        self._outcome_masks = outcome_ingraph.bucket_masks(
            N, config.env.opponent, self.n_anchor_games
        )
        self._owner_side = (
            "radiant" if learner_players[0] < config.env.team_size else "dire"
        )
        # Quantized experience plane (ISSUE 7): chunks bound for the
        # trajectory buffer narrow to the wire dtypes INSIDE the jitted
        # collect program (obs→bf16, bounded int leaves→int8; pinned
        # leaves stay f32), so ``add_device`` scatters narrow rows into
        # the narrow ring with no extra dispatch. Fused mode calls
        # ``_rollout_impl`` directly and keeps full width — it trains on
        # the chunk in the same program and never stores it, so
        # quantizing there would cost precision for zero resident bytes.
        self._chunk_cast: Dict[str, Any] = {}
        if config.transport.rollout_wire_dtype != "float32":
            from dotaclient_tpu.train.ppo import example_batch
            from dotaclient_tpu.transport.serialize import (
                flatten_tree,
                rollout_cast_plan,
                rollout_int_bounds,
            )

            flat = flatten_tree(example_batch(config, batch=1))
            self._chunk_cast = rollout_cast_plan(
                {n: np.dtype(a.dtype) for n, a in flat.items()},
                config.transport.rollout_wire_dtype,
                rollout_int_bounds(config),
            )

        def _collect_impl(params, state, opp_params):
            new_state, chunk, stats = self._rollout_impl(
                params, state, opp_params
            )
            if self._chunk_cast:
                from dotaclient_tpu.transport.serialize import (
                    apply_cast_plan,
                    flatten_tree,
                    unflatten_tree,
                )

                chunk = unflatten_tree(
                    apply_cast_plan(flatten_tree(chunk), self._chunk_cast)
                )
            return new_state, chunk, stats

        # No donation here: the buffered mode's state is small (the big
        # arrays are the chunk OUTPUTS), and zero-initialized leaves share
        # buffers. A program that does donate it says so (donate_state).
        self._rollout = jax.jit(_collect_impl)
        # host-side counters, updated from fetched stats at log boundaries
        self.env_steps = 0
        self.rollouts_shipped = 0
        self.episodes_done = 0
        self.wins = 0
        self._reward_sum = 0.0
        self._ep_count_window = 0.0
        self._tel = registry if registry is not None else telemetry.get_registry()
        outcome_records.ensure_actor_metrics(self._tel)

    @property
    def one_pass_when_live(self) -> bool:
        """Whether a rollout step whose two teams play the same parameters
        (``_rollout_impl`` with ``opp_params`` None) runs the policy ONCE over
        both teams' rows: there are opponent lanes, and the lanes are on one
        shard (joining two lane-sharded row sets would be a reshard, and the
        rollout holds no collective: tests/test_fused_multichip.py)."""
        return self._opp_feat is not None and self.lane_shards == 1

    def donate_state(self) -> None:
        """Whoever builds a program that DONATES ``self.state`` says so here
        (``train/fused.py``, where the states are most of the chip): from
        now on every leaf of the state, and of the fresh stat accumulators a
        drain puts in, is a buffer of its own."""
        self._own = own_buffers
        self.state = own_buffers(self.state)

    def reset_recurrent(self) -> None:
        """Zero every lane's recurrent carry (learner + opponent sides).

        Divergence-rollback hygiene (ISSUE 6): carries were computed by
        the poisoned params and would re-poison the restored policy's
        first forward; the sim worlds themselves stay finite (sampled
        actions are always in-range ints) and keep their episodes."""
        opp_lanes = max(
            len(self.opponent_players) * self.spec.n_games, 1
        )
        state = self.state._replace(
            carry=self.policy.initial_state(self.n_lanes),
            opp_carry=self.policy.initial_state(opp_lanes),
        )
        if self.mesh is not None:
            # fresh zero carries are host constants — re-commit them to the
            # lane sharding so the next dispatch starts layout-clean
            state = jax.device_put(
                state, actor_state_sharding(state, self.mesh, self.mesh_config)
            )
        self.state = state

    def _zero_stats(self) -> Dict[str, jnp.ndarray]:
        """Per-game/per-lane PARTIAL accumulators (ISSUE 18): counters keep
        the game axis, per-term reward sums the lane axis, so accumulation
        inside the sharded rollout program never crosses a shard boundary;
        shapes are mesh-size independent (checkpoints restore 8→1 and 1→8
        unchanged). ``reduce_device_stats`` folds them at drain time."""
        N, L = self.spec.n_games, self.n_lanes
        zg = jnp.zeros((N,), jnp.float32)
        zl = jnp.zeros((L,), jnp.float32)
        out = {
            "episodes": zg, "wins": zg, "reward_sum": zl, "ep_return_sum": zg,
            "league_episodes": zg, "league_wins": zg,
        }
        # outcome plane (ISSUE 15): per-bucket episode outcomes, episode
        # lengths (+ pow2 histogram), and the per-term reward sums
        out.update(outcome_ingraph.zero_outcome_stats(N))
        out["out_reward_terms"] = {
            term: zl for term in outcome_records.REWARD_TERMS
        }
        return out

    # -- the jitted chunk generator ---------------------------------------

    def _rollout_impl(
        self,
        params: Any,
        state: DeviceActorState,
        opp_params: Any,
    ):
        """``opp_params`` None says that the opponent lanes play ``params``
        themselves (a caller inside a ``jit`` cannot show it by identity: two
        arguments are two tracers). A step is then ONE pass of the policy over
        both teams' rows, every weight read once (``one_pass_when_live``);
        otherwise a pass a team, as a frozen opponent needs."""
        one_pass = opp_params is None and self.one_pass_when_live
        if opp_params is None:
            opp_params = params
        cfg = self.config
        spec = self.spec
        T = cfg.ppo.rollout_len
        A = len(self.learner_players)
        feat = self.feat
        owner_team = (
            sim_mod.TEAM_RADIANT
            if self.learner_players[0] < spec.team_size
            else sim_mod.TEAM_DIRE
        )
        staged = self.config.model.diffusion_steps > 0     # the core decodes an action over passes
        def policy_pass(p, *teams):
            """One pass of the policy over the teams' rows together: an
            ``(obs, carry)`` a team -> a ``(logits, carry)`` a team."""
            if len(teams) == 1:
                logits, _, carry = self.policy.apply(p, *teams[0], method="step")
                return ((logits, carry),)
            obs, carries = zip(*teams)
            logits, _, carries = self.policy.apply(
                p, jax.tree.map(lambda *rows: jnp.concatenate(rows), *obs),
                LaneBlocks(carries), method="step",
            )
            sizes = [o["hero_id"].shape[0] for o in obs]
            return tuple(
                (jax.tree.map(lambda x: x[end - n:end], logits), carry)
                for n, end, carry in zip(sizes, np.cumsum(sizes), carries)
            )

        def body(c, _):
            sim, lstm, opp_lstm, key, ep_ret, ep_steps = c
            # per-GAME key triple [N, 3, 2]: carry / learner lanes / opp
            # lanes — each game's stream is independent, so the whole split
            # is shard-local under the lane sharding
            with jax.named_scope("rollout_sample"):
                ks = jax.vmap(lambda k: jax.random.split(k, 3))(key)
                key2, k_act, k_opp = ks[:, 0], ks[:, 1], ks[:, 2]
            decode_pass = self._decode_pass(iter((k_act, k_opp))) if staged else policy_pass
            # Each stage of a rollout step carries a scope (metadata only;
            # the policy's two forward passes keep the policy's own), so a
            # profiler trace times the stages by name.
            with jax.named_scope("rollout_featurize"):
                obs = feat.featurize(sim)
                oobs = self._opp_feat.featurize(sim) if one_pass else None
            if one_pass:
                (logits, lstm2), (ologits, opp_lstm2) = decode_pass(
                    params, (obs, lstm), (oobs, opp_lstm)
                )
            else:
                ((logits, lstm2),) = decode_pass(params, (obs, lstm))
            with jax.named_scope("rollout_sample"):
                acts, logp = sample_per_game(k_act, logits, obs, spec.n_games)
                packed = jnp.stack(
                    [acts[h] for h in D.HEADS], axis=1
                ).astype(jnp.int32)
                sim_acts = feat.actions_to_sim(packed)

            if self._opp_feat is not None:
                if not one_pass:
                    with jax.named_scope("rollout_featurize"):
                        oobs = self._opp_feat.featurize(sim)
                    ((ologits, opp_lstm2),) = decode_pass(
                        opp_params, (oobs, opp_lstm)
                    )
                with jax.named_scope("rollout_sample"):
                    oacts, _ = sample_per_game(
                        k_opp, ologits, oobs, spec.n_games
                    )
                    opacked = jnp.stack(
                        [oacts[h] for h in D.HEADS], axis=1
                    ).astype(jnp.int32)
                    osim = self._opp_feat.actions_to_sim(opacked)
                    opp_mask = jnp.zeros((spec.n_players,), bool).at[
                        jnp.asarray(self.opponent_players)
                    ].set(True)
                    sim_acts = {
                        k: jnp.where(opp_mask[None, :], osim[k], sim_acts[k])
                        for k in sim_acts
                    }
            else:
                opp_lstm2 = opp_lstm

            with jax.named_scope("rollout_sim_step"):
                sim2 = sim_mod.step(
                    spec, sim, sim_acts,
                    scripted_possible=(
                        self.config.env.opponent not in ("selfplay", "league")
                        or self.n_anchor_games > 0
                    ),
                )
            with jax.named_scope("rollout_reward"):
                r_terms = shaped_reward_terms(
                    spec, self.learner_players, sim, sim2,
                    weights=cfg.reward.as_dict(),
                )
                # the single-sourced table-order fold: bit-identical to the
                # historical shaped_rewards sum (features.reward.fold_terms)
                r = fold_terms(r_terms)
            with jax.named_scope("rollout_reset"):
                done_g = sim2.done
                win_g = done_g & (sim2.winning_team == owner_team)
                ep_ret = ep_ret + r
                # outcome plane: this step closed the episode at length
                # ep_steps+1 for done games; the counter resets in-scan
                ep_steps2 = ep_steps + 1
                ep_len_g = jnp.where(done_g, ep_steps2, 0)
                ep_steps3 = jnp.where(done_g, 0, ep_steps2)

                sim3 = sim_mod.reset_where(spec, sim2, done_g)
                done_lane = jnp.repeat(done_g, A)
                # the reset is the core's own (Policy.reset_carry): the
                # LSTM's row is zeroed, a cache is never rewritten
                lstm3 = self.policy.reset_carry(
                    lstm2, 1.0 - done_lane.astype(jnp.float32)
                )
                if self._opp_feat is not None:
                    opp_done = jnp.repeat(done_g, len(self.opponent_players))
                    opp_lstm3 = self.policy.reset_carry(
                        opp_lstm2, 1.0 - opp_done.astype(jnp.float32)
                    )
                else:
                    opp_lstm3 = opp_lstm2

                # completed-episode returns leave through stats; the accumulator
                # resets on done (owner lane per game, matching the pools)
                owner_ret = ep_ret.reshape(-1, A)[:, 0]
                out = {
                    "obs": obs,
                    "packed": packed,
                    "logp": logp,
                    "reward": r,
                    "done_lane": done_lane.astype(jnp.float32),
                    "ep_done": done_g,
                    "win": win_g,
                    "ep_len": ep_len_g,
                    "ep_return": jnp.where(done_g, owner_ret, 0.0),
                    # per-term rewards kept PER-LANE [L]: the post-scan sums
                    # reduce only the step axis, so the accumulators stay
                    # shard-local partials under the lane sharding
                    "rew_terms": r_terms, **({"act_stage": logits["act_stage"]} if staged else {}),
                }
                ep_ret = jnp.where(done_lane, 0.0, ep_ret)
            return (sim3, lstm3, opp_lstm3, key2, ep_ret, ep_steps3), out

        (sim_f, lstm_f, opp_f, key_f, ep_ret_f, ep_steps_f), outs = jax.lax.scan(
            body,
            (
                state.sim, state.carry, state.opp_carry, state.key,
                state.ep_return, state.ep_steps,
            ),
            None,
            length=T,
        )

        with jax.named_scope("rollout_featurize"):
            bootstrap = feat.featurize(sim_f)                    # [L, ...]

        with jax.named_scope("rollout_assemble"):
            def to_chunk_obs(seq, boot):
                # [T, L, ...] -> [L, T+1, ...]
                seq = jnp.moveaxis(seq, 0, 1)
                return jnp.concatenate([seq, boot[:, None]], axis=1)

            obs_seq = jax.tree.map(to_chunk_obs, outs["obs"], bootstrap)
            packed = jnp.moveaxis(outs["packed"], 0, 1)              # [L, T, 5]
            chunk = {
                "obs": obs_seq,
                "actions": {
                    h: packed[:, :, j] for j, h in enumerate(D.HEADS)
                },
                "behavior_logp": jnp.moveaxis(outs["logp"], 0, 1),
                "rewards": jnp.moveaxis(outs["reward"], 0, 1),
                "dones": jnp.moveaxis(outs["done_lane"], 0, 1),
                "valid": jnp.ones((self.n_lanes, T), jnp.float32),
                # the chunk-start carry as the core hands it to a learner: float32 rows for
                # the LSTM, for a core with caches the start's counters beside the END's rings
                **({"act_stage": jnp.moveaxis(outs["act_stage"], 0, 1)} if staged else {}),
                "carry0": self.policy.chunk_start_carry(state.carry, lstm_f),
            }
            lg = self._league_game_mask[None, :]     # [1, N] non-anchor games
            # Stats are PER-GAME/PER-LANE partials (ISSUE 18): only the step
            # axis reduces here, the game/lane axis survives — under the lane
            # sharding every accumulation is shard-local and the rollout half
            # of the fused program emits NO collective. The host folds the
            # surviving axis at drain time (reduce_device_stats).
            stats = {
                "episodes": outs["ep_done"].sum(0).astype(jnp.float32),
                "wins": outs["win"].sum(0).astype(jnp.float32),
                "reward_sum": outs["reward"].sum(0),
                "ep_return_sum": outs["ep_return"].sum(0),
                # snapshot-attributable outcomes only (anchor games excluded)
                "league_episodes": (outs["ep_done"] & lg).sum(0).astype(jnp.float32),
                "league_wins": (outs["win"] & lg).sum(0).astype(jnp.float32),
            }
            # outcome plane (ISSUE 15): done-masked per-bucket reductions +
            # episode-length histogram + the per-term reward decomposition —
            # all accumulated on device, drained with the existing stats sync
            stats.update(
                outcome_ingraph.chunk_outcome_partials(
                    outs["ep_done"], outs["win"], outs["ep_len"],
                    self._outcome_masks,
                )
            )
            stats["out_reward_terms"] = {
                term: outs["rew_terms"][term].sum(0)
                for term in outcome_records.REWARD_TERMS
            }
            cum_stats = jax.tree.map(
                lambda a, b: a + b, state.stats, stats
            )
            new_state = DeviceActorState(
                sim=sim_f, carry=lstm_f, opp_carry=opp_f, key=key_f,
                ep_return=ep_ret_f, ep_steps=ep_steps_f, stats=cum_stats,
            )
        return new_state, chunk, stats

    # -- host surface ------------------------------------------------------

    def collect(self, params: Any, opp_params: Any = None):
        """Generate one chunk batch [L, T, ...] (device arrays). Returns
        (chunk, device stats dict) — dispatch-only, no host sync.

        League mode REQUIRES ``opp_params`` (the frozen opponent) — falling
        back to the live params would silently turn the league into mirror
        self-play."""
        if self._opp_feat is not None and opp_params is None:
            raise ValueError(
                "opponent lanes exist (league mode): pass opp_params "
                "(e.g. OpponentPool.sample(...)) to collect()"
            )
        if opp_params is None:
            opp_params = params
        # span measures DISPATCH latency only (the program runs async on the
        # device) — watching it grow is how you spot the device falling
        # behind the host without adding a sync to look
        with self._tel.span("actor/collect"):
            self.state, chunk, stats = self._rollout(
                params, self.state, opp_params
            )
        T = self.config.ppo.rollout_len
        self.env_steps += self.n_lanes * T
        self.rollouts_shipped += self.n_lanes
        self._tel.counter("actor/frames_shipped").inc(self.n_lanes * T)
        self._tel.counter("actor/rollouts_shipped").inc(self.n_lanes)
        return chunk, stats

    def begin_drain(self):
        """Dispatch-only half of :meth:`drain_stats` (async snapshots,
        ISSUE 5): copy the device stat accumulators — a tiny jitted copy,
        so a later donating dispatch (the fused step donates the whole
        actor state) can never invalidate the snapshot — reset them, and
        return ``(device_stats, finish)``. ``finish(host_stats)`` runs the
        host-side accounting and returns the scalar dict; the caller (the
        snapshot thread, or :meth:`drain_stats` inline) feeds it the ONE
        batched fetch of ``device_stats``."""
        if not hasattr(self, "_stats_copy"):
            self._stats_copy = jax.jit(
                lambda t: jax.tree.map(jnp.copy, t)
            )
        dev = self._stats_copy(self.state.stats)
        fresh = self._own(self._zero_stats())
        if self.mesh is not None:
            # commit the zeroed accumulators back to the lane sharding —
            # uncommitted host zeros would change the collect program's
            # input layout and force a recompile on the next dispatch
            fresh = jax.device_put(
                fresh,
                actor_state_sharding(
                    self.state, self.mesh, self.mesh_config
                ).stats,
            )
        self.state = self.state._replace(stats=fresh)

        def finish(s) -> Dict[str, float]:
            # the fetched accumulators are per-game/per-lane partials —
            # fold the game/lane axes on the host before any consumer
            s = reduce_device_stats(s)
            self.episodes_done += int(s["episodes"])
            self.wins += int(s["wins"])
            self._reward_sum += float(s["ep_return_sum"])
            self._ep_count_window += float(s["episodes"])
            # outcome plane: the drained window's in-graph reductions land
            # in the same outcome/ counters the host pools increment
            outcome_records.fold_device_stats(
                self._tel, s, owner_side=self._owner_side
            )
            # windowed (since previous drain) — the responsive learning signal
            self._recent = {
                "episodes": float(s["episodes"]),
                "wins": float(s["wins"]),
                "ep_return_sum": float(s["ep_return_sum"]),
            }
            return self.stats()

        return dev, finish

    def drain_stats(self) -> Dict[str, float]:
        """Fetch the device-accumulated episode stats (a few scalars, ONE
        host sync regardless of how many chunks were collected); call at
        log boundaries only (async runs fetch via the snapshot thread —
        see :meth:`begin_drain`)."""
        dev, finish = self.begin_drain()
        with self._tel.span("actor/drain"):
            s = jax.device_get(dev)
        return finish(s)

    def stats(self) -> Dict[str, float]:
        # mean return over COMPLETED episodes (owner-lane convention,
        # matching the host pools' episode_reward_mean)
        mean_ep = (
            self._reward_sum / self._ep_count_window
            if self._ep_count_window
            else 0.0
        )
        recent = getattr(self, "_recent", None) or {}
        r_eps = recent.get("episodes", 0.0)
        return {
            "env_steps": float(self.env_steps),
            "rollouts_shipped": float(self.rollouts_shipped),
            "episodes_done": float(self.episodes_done),
            "episode_reward_mean": mean_ep,
            "win_rate": (
                self.wins / self.episodes_done if self.episodes_done else 0.0
            ),
            "episodes_recent": r_eps,
            "win_rate_recent": recent.get("wins", 0.0) / r_eps if r_eps else 0.0,
            "ep_reward_recent": (
                recent.get("ep_return_sum", 0.0) / r_eps if r_eps else 0.0
            ),
        }

    def _decode_pass(self, keys):
        """``_rollout_impl``'s policy pass where the core decodes an action as
        a block over several passes (``models/sdar.py decode``), drawing
        between them: each team's ``(obs, carry)`` -> its ``(decoded, carry)``,
        ``decoded`` holding the actions, their log-probability and the pass
        that committed each slot (``act_stage``, recorded in the chunk). The
        teams' per-game keys come from ``keys`` in the order the teams are
        passed, the learner's before the opponent's, as ``sample_per_game``
        would have drawn from them. Several teams in one call are one pass
        over their rows, as ``policy_pass``."""
        from dotaclient_tpu.models.sdar import decode

        def decode_pass(p, *teams):
            obs, carries = zip(*teams)
            team_keys = tuple(next(keys) for _ in teams)
            if len(teams) == 1:
                out, carry = self.policy.apply(p, obs[0], carries[0], team_keys[0], method=decode)
                return ((out, carry),)
            out, carries = self.policy.apply(
                p, jax.tree.map(lambda *rows: jnp.concatenate(rows), *obs), LaneBlocks(carries), team_keys,
                method=decode,
            )
            sizes = [o["hero_id"].shape[0] for o in obs]
            lanes = {k: v for k, v in out.items() if k != "logits"}
            return tuple(
                (jax.tree.map(lambda x: x[end - n:end], lanes), carry)
                for n, end, carry in zip(sizes, np.cumsum(sizes), carries)
            )

        return decode_pass
