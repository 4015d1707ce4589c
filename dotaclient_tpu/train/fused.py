"""Fully-fused synchronous iteration: rollout + PPO update, ONE XLA program.

The reference's loop crosses process and device boundaries every iteration
(actor → RMQ → learner → GPU, SURVEY.md §3.1–3.2). The device actor already
collapsed the actor side into a single program; this module goes the rest of
the way for the synchronous on-policy regime: the whole iteration —
T-step rollout scan (featurize, policy, sample, env step, reward, episode
reset), then the PPO update on the chunk it just produced — is one jitted
call (``FusedStep``). One dispatch per optimizer step, zero host
round-trips, nothing staged through the trajectory buffer. Where the train
state and the actor state together hold more than
``DONATE_ABOVE_BYTES`` on one device (a core whose carry is ring caches,
``ModelConfig.carry_stays_on_chip``: gigabytes beside the parameters) the call DONATES both: they
update in place in HBM, so states that do not fit the chip twice run through
it, and what reads the state across a dispatch (the league's snapshot, a
weights publish, a checkpoint) copies on the device BEFORE the next enqueue.
Smaller states keep one undonated program (``FusedStep`` says why).
The episode reset and the chunk-start carry the update is handed are the
core's own (``Policy.reset_carry``, ``Policy.chunk_start_carry``): no cache
is rewritten or copied.

This is the Anakin architecture (PAPERS.md [P:7]) taken to its endpoint: the
buffered device loop issues 4–5 dispatches per optimizer step (collect +
scatter + gather + train), which at small batch is host-dispatch time, not
device time. How much each costs on a directly attached chip: not measured
on chip in this round (PERF.md).

Trade-offs vs the buffered path (why both exist):
  * strictly on-policy — every chunk is trained on exactly once, by the
    params that generated it (behavior_logp ratio ≡ 1 at epoch 1); the
    staleness/version machinery has nothing to do;
  * the train batch IS the lane set (``n_lanes`` rollouts of length T) —
    ``ppo.batch_rollouts`` does not apply;
  * ``epochs_per_batch`` > 1 runs as a ``lax.scan`` of update steps over
    the same chunk INSIDE the program (epoch 2+ are the standard PPO
    re-uses, ratio clipped against the rollout's behavior_logp);
    ``minibatches`` > 1 shuffles IN-PROGRAM and SHARD-LOCALLY
    (``lane_minibatches``): each epoch every mesh shard draws a fresh
    permutation of its own lanes (keyed on ``config.seed`` and the
    optimizer step, so it is deterministic and needs no host shuffle
    point or carried RNG) and contributes its m-th local group to
    minibatch m — the standard PPO minibatch pass, fully fused, with no
    cross-device gather;
  * ``RunConfig.steps_per_dispatch`` > 1 scans K whole rollout+update
    iterations per dispatch, amortizing the host↔device round trip K× at
    the cost of K-step granularity for everything host-side (opponent
    draws, logging, best-model capture);
  * no cross-process experience — single-host self-play only.

The learner exposes it as ``actor="fused"``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from dotaclient_tpu.actor.device_rollout import actor_state_sharding
from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.parallel.mesh import (
    batch_shard_count,
    data_sharding,
    replicated,
)
from dotaclient_tpu.train.ppo import (
    _train_step,
    fold_scan_metrics,
    train_state_shape,
    train_state_sharding,
)
from dotaclient_tpu.utils import telemetry


def lane_minibatches(chunk, step, seed: int, n_lanes: int, n_shards: int,
                     n_mb: int):
    """Shard-LOCAL in-program minibatch shuffle: permute lanes within each
    mesh shard, never across — the gather stays on the local axis, so
    minibatching adds NO collective to the hot loop (the only one left per
    update is ``_train_step``'s gradient psum).

    Each shard draws its own permutation of its ``n_lanes // n_shards``
    local lanes (keyed on the run seed and the optimizer step at epoch
    entry — strictly increasing, so every epoch of every iteration draws
    fresh with no host shuffle point or carried RNG). Minibatch ``m`` is
    the concatenation of every shard's ``m``-th local group, so each
    minibatch is itself an evenly lane-sharded batch and the downstream
    sharding constraint is a no-op assertion. The permutation stream is
    shard-count DEPENDENT by design (the blocks are the shards); cross-
    shard-count parity probes run with ``minibatches=1``, where the math
    is shard-count invariant.

    Returns the chunk reshaped to ``[n_mb, n_lanes // n_mb, ...]`` leaves.
    """
    S, Ls = n_shards, n_lanes // n_shards
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    perm = jax.vmap(
        lambda k: jax.random.permutation(k, Ls)
    )(jax.random.split(key, S))                     # [S, Ls] per-shard perms

    def shuffle(x):
        xs = x.reshape((S, Ls) + x.shape[1:])
        idx = perm.reshape((S, Ls) + (1,) * (x.ndim - 1))
        xs = jnp.take_along_axis(xs, idx, axis=1)   # local-axis gather
        xs = xs.reshape((S, n_mb, Ls // n_mb) + x.shape[1:])
        # [S, M, Ls/M] → [M, S·Ls/M]: minibatch m owns every shard's m-th
        # group; the sharded axis stays outermost of the merged dim, so the
        # result is born lane-sharded
        return jnp.moveaxis(xs, 0, 1).reshape(
            (n_mb, S * (Ls // n_mb)) + x.shape[1:]
        )

    return jax.tree.map(shuffle, chunk)


def make_fused_step(
    policy: Policy, config: RunConfig, mesh, actor, anchor_params=None
):
    """Build the fused program (``FusedStep``): (state, actor_state,
    opp_params) → (state', actor_state', metrics, stats) against ``mesh``.

    The train state keeps the TP/DP shardings of ``make_train_step``; the
    actor state is pinned LANE-SHARDED (``actor_state_sharding``): games —
    and the game-major lanes they own — partition over the (dcn×)data axes,
    so sim stepping, featurize, the policy forward, sampling, and the
    in-graph outcome partials all compute on local lanes only and the chunk
    is BORN data-sharded; the mid-program sharding constraints are no-op
    assertions, not reshards. ``opp_params`` is a frozen snapshot, or
    ``None`` (or the state's own ``params``) where the opponent is the live
    policy: see ``FusedStep``. Inside, ``None`` is handed down to the rollout
    as the fact that both teams play ``state.params`` (one iteration a
    dispatch; several hold the opponent at the dispatch's first parameters).
    """
    if (config.ppo.anchor_kl_coef > 0) != (anchor_params is not None):
        raise ValueError(
            "anchor_params must be passed exactly when ppo.anchor_kl_coef > 0"
        )
    ds = data_sharding(mesh, config.mesh)
    repl = replicated(mesh)
    st_sh = train_state_sharding(policy, config, mesh)
    st_act_sh = actor_state_sharding(actor.state, mesh, config.mesh)
    # Decided once, here, from what the two states hold on one device: past
    # the limit both are donated, and the actor is told that its state is.
    resident = _bytes_on_one_device(
        train_state_shape(policy, config), st_sh
    ) + _bytes_on_one_device(actor.state, st_act_sh)
    donate = resident > DONATE_ABOVE_BYTES
    if donate:
        actor.donate_state()

    n_epochs = config.ppo.epochs_per_batch
    n_mb = max(1, config.ppo.minibatches)
    n_iters = config.steps_per_dispatch
    n_shards = batch_shard_count(mesh, config.mesh)
    L = actor.n_lanes
    N = actor.spec.n_games
    # Lane sharding engages when the games (and their game-major lanes)
    # split evenly over the batch shards; otherwise the per-leaf
    # divisibility rule in actor_state_sharding has already degraded the
    # layout to replicated (tiny debug configs — e.g. 4 games on an
    # 8-device mesh) and the minibatch split treats the chunk as one
    # shard, exactly the pre-sharding behavior.
    lane_sharded = N % n_shards == 0 and L % n_shards == 0
    eff_shards = n_shards if lane_sharded else 1
    if L % (eff_shards * n_mb):
        raise ValueError(
            f"fused minibatching splits the {L}-lane chunk along lanes "
            f"WITHIN each of the {eff_shards} lane shard(s): n_lanes must "
            f"be divisible by data_parallel x minibatches "
            f"({eff_shards} x {n_mb} = {eff_shards * n_mb}) so every shard "
            f"contributes equal lane groups to each of the {n_mb} "
            f"minibatch(es)"
        )

    probe = config.health.enabled

    def update_on_chunk(state, chunk):
        if n_epochs == 1 and n_mb == 1:
            return _train_step(
                policy, config.ppo, state, chunk,
                anchor_params=anchor_params, probe=probe,
            )

        def epoch(st, _):
            if n_mb == 1:
                return _train_step(
                    policy, config.ppo, st, chunk,
                    anchor_params=anchor_params, probe=probe,
                )
            # In-program shuffle, shard-local (lane_minibatches): each mesh
            # shard permutes its own lanes and contributes its m-th group
            # to minibatch m — no cross-device gather enters the hot loop.
            mbs = lane_minibatches(
                chunk, st.step, config.seed, L, eff_shards, n_mb
            )

            def mb_step(s, mb):
                # no-op assertion under the lane-sharded layout (each
                # minibatch is born evenly lane-sharded); kept as the
                # contract pin rather than trusting propagation
                mb = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, ds), mb
                )
                return _train_step(
                    policy, config.ppo, s, mb,
                    anchor_params=anchor_params, probe=probe,
                )

            st, mseq = jax.lax.scan(mb_step, st, mbs)
            return st, fold_scan_metrics(mseq)

        new_state, metric_seq = jax.lax.scan(
            epoch, state, None, length=n_epochs
        )
        # report the final update (the state reflects it), like the
        # buffered loop's last logged step of a multi-epoch pass;
        # health_ok AND-folds across every scan level (fold_scan_metrics)
        return new_state, fold_scan_metrics(metric_seq)

    def one_iter(state, actor_state, opp_params):
        # The two phases carry a scope each (metadata only): a profiler
        # trace splits the program's device time by them, and what is
        # under neither is the benchmark's `unscoped_device_share`.
        with jax.named_scope("phase_rollout"):
            actor_state, chunk, stats = actor._rollout_impl(
                state.params, actor_state, opp_params
            )
            # no-op assertion: the chunk is BORN data-sharded (its lanes
            # inherit the actor state's lane sharding); this pin turns a
            # layout regression into a visible reshard instead of silence
            chunk = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(x, ds), chunk
            )
        with jax.named_scope("phase_update"):
            new_state, metrics = update_on_chunk(state, chunk)
        return new_state, actor_state, metrics, stats

    if n_iters == 1:
        fused = one_iter
    else:
        # Dispatch batching (RunConfig.steps_per_dispatch): scan K whole
        # rollout+update iterations, so ONE host dispatch advances K
        # optimizer steps. The opponent is fixed for the dispatch (the
        # learner rejects league configs whose opponent_hold is shorter
        # than the dispatch stride): a live one is the parameters the
        # dispatch STARTED from, which from the second iteration on are no
        # longer the learner's, so the rollout is handed them as an opponent
        # of its own and keeps a pass a team (`live_shares_pass` is false).
        # Per-chunk episode stats are additive scalars, summed over the
        # scan so league attribution sees the dispatch's true totals.
        def fused(state, actor_state, opp_params):
            if opp_params is None:
                opp_params = state.params

            def it(c, _):
                st, ast = c
                st, ast, metrics, stats = one_iter(st, ast, opp_params)
                return (st, ast), (metrics, stats)

            (state, actor_state), (metric_seq, stat_seq) = jax.lax.scan(
                it, (state, actor_state), None, length=n_iters
            )
            metrics = fold_scan_metrics(metric_seq)
            stats = jax.tree.map(lambda s: s.sum(axis=0), stat_seq)
            return state, actor_state, metrics, stats

    # opp_params shards like the live params (st_sh's params subtree): under
    # TP, pinning it replicated would all-gather the full param set every
    # step — on the one-dispatch hot path this module exists to shorten.
    # The actor state is pinned lane-sharded in AND out (st_act_sh): the
    # sim worlds, carries, per-game keys, and stat partials live
    # partitioned in HBM across dispatches; the per-chunk stats output
    # keeps the same partial layout (its game/lane axes are the sharded
    # ones), so emitting it is collective-free too.
    return FusedStep(
        fused,
        state_sharding=st_sh,
        actor_sharding=st_act_sh,
        out_shardings=(st_sh, st_act_sh, repl, st_act_sh.stats),
        donate=donate,
        both_kinds=actor.opponent_players != [],
        live_shares_pass=donate and n_iters == 1 and actor.one_pass_when_live,
    )


# Past this many bytes of train state and actor state on one device the fused
# program donates both: every dispatch in flight otherwise holds a second copy
# (a quarter of the smallest chip this runs on, the v5e's 16 GB). Below it the
# program stays the one undonated program: see ``FusedStep``.
DONATE_ABOVE_BYTES = 4e9


def _bytes_on_one_device(shapes: Any, shardings: Any) -> int:
    return sum(
        math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
        for x, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(shardings))
    )


class FusedStep:
    """The fused program as the learner calls it:
    ``step(state, actor_state, opp_params) -> (state', actor_state', metrics,
    stats)``; ``opp_params`` is a frozen snapshot, or ``None`` (or the
    state's own ``params``) where the opponent is the live policy.

    ``donate`` is ``make_fused_step``'s decision, from the bytes the train
    state and the actor state hold on one device against
    ``DONATE_ABOVE_BYTES``.

    **Without donation** (states that are a tenth of the chip: every LSTM
    and windowed-transformer configuration this repo runs) it is ONE
    program of three arguments and the live opponent is the state's
    ``params`` passed a second time, as before PR 26: a second program
    costs its build at every start (``setup_s`` +17.6% and +19.4% at the
    benchmark's small and wide LSTM cells, bound 10%: my chip runs, PR 26).

    **With donation** (states that are most of the chip and do not fit
    twice: a ring-cache core's attention caches beside its parameters and
    their Adam moments) both update in place in HBM, and a dispatch in
    flight holds no second copy of either. A donated buffer cannot also be read as another
    argument, so the live opponent is a program of its own with two
    arguments, which hands the rollout ``None`` for the opponent's
    parameters. The rollout then KNOWS that both teams play ``state.params``
    and, where ``live_shares_pass`` (``DeviceActor.one_pass_when_live``),
    runs the policy once a step over both teams' rows: every weight read
    once where the frozen program reads it twice. So the two programs are two
    traces (the live rollout's body is another jaxpr, a pass shorter), not
    one trace lowered twice as they were until PR 33. ``live_shares_pass``
    is fixed when the program is made and says whether a live dispatch
    (``opp_params`` None) runs such a rollout (the learner's
    ``league/shared_pass_dispatches_total``): false without donation, and
    false where a dispatch scans several iterations
    (``steps_per_dispatch`` > 1), whose live opponent is the parameters the
    dispatch started from in either program. With opponent lanes
    (``both_kinds``) either draw can come at any
    dispatch, so both programs are built at the first call, from its
    arguments' shapes: nothing is left to compile in the middle of a run.
    Whoever reads the state across a dispatch copies on the device before
    the next enqueue (``league/pool.py`` snapshot, ``train/snapshot.py``).

    ``lower(state, actor_state, opp_params)`` lowers the program those
    arguments would run, for ahead-of-time inspection.
    """

    def __init__(self, fused, state_sharding, actor_sharding, out_shardings,
                 donate: bool, both_kinds: bool, live_shares_pass: bool) -> None:
        self.donate = donate
        self.live_shares_pass = live_shares_pass
        three = (state_sharding, actor_sharding, state_sharding.params)
        if not donate:
            self._jits = {"frozen": jax.jit(
                fused, in_shardings=three, out_shardings=out_shardings,
            )}
            return

        def frozen_opponent(state, actor_state, opp_params):
            return fused(state, actor_state, opp_params)

        def live_opponent(state, actor_state):
            return fused(state, actor_state, None)

        self._jits = {
            "frozen": jax.jit(
                frozen_opponent, in_shardings=three,
                out_shardings=out_shardings, donate_argnums=(0, 1),
            ),
            "live": jax.jit(
                live_opponent, in_shardings=three[:2],
                out_shardings=out_shardings, donate_argnums=(0, 1),
            ),
        }
        self._both_kinds = both_kinds
        self._programs: dict = {}

    def _args(self, state, actor_state, opp_params):
        live = opp_params is None or opp_params is state.params
        if not self.donate:
            return "frozen", (
                state, actor_state, state.params if live else opp_params
            )
        if live:
            return "live", (state, actor_state)
        return "frozen", (state, actor_state, opp_params)

    def _build(self, kind: str, state, actor_state) -> None:
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (state, actor_state),
        )
        if kind == "frozen":
            args += (args[0].params,)
        # once a kind, behind __call__'s `k not in self._programs`: the
        # steady loop never comes here. `compile` is an XLA compile or the
        # persistent cache's load of the program's code.
        tel = telemetry.get_registry()
        with tel.span("fused/build", kind=kind):
            with tel.span("fused/build/lower"):
                lowered = self._jits[kind].lower(*args)
            with tel.span("fused/build/compile"):
                self._programs[kind] = lowered.compile()

    def __call__(self, state, actor_state, opp_params=None):
        kind, args = self._args(state, actor_state, opp_params)
        if not self.donate:
            return self._jits[kind](*args)
        for k in tuple(self._jits) if self._both_kinds else (kind,):
            if k not in self._programs:
                self._build(k, state, actor_state)
        return self._programs[kind](*args)

    def lower(self, state, actor_state, opp_params=None):
        kind, args = self._args(state, actor_state, opp_params)
        return self._jits[kind].lower(*args)

    def _cache_size(self) -> int:
        """Programs built so far (``tracing.InstrumentedJit`` reads it)."""
        if not self.donate:
            return self._jits["frozen"]._cache_size()
        return len(self._programs)
