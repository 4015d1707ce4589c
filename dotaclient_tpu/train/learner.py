"""The learner: end-to-end training loop and CLI entrypoint.

Counterpart of the reference's ``optimizer.py`` main loop — consume rollouts,
train, publish versioned weights, checkpoint, log scalars (SURVEY.md §3.2;
reconstructed — the reference checkout was an empty mount) — wired TPU-first:
the actor pool batches env inference on-device, experience flows through the
transport into the sharded HBM buffer, and each optimization is one donated
pjit step (SURVEY.md §7 "Minimum end-to-end slice").

Single-process mode interleaves actor and learner phases (the deterministic
test path) or overlaps them (``--overlap``: the actor pool runs in its own
thread feeding the transport while the learner trains — the async
actor-learner topology of SURVEY.md §1, in one process). The same components
run split across processes with an AMQP transport on a cluster
(``--transport amqp``).

Sync discipline (SURVEY.md §7 hard-part 2): the optimizer loop never reads a
device value per step — step/version counters are host-side mirrors, the
donated train step is dispatch-only, and metrics are fetched (one transfer)
only at ``log_every`` boundaries. On hardware where a host↔device round trip
is expensive this is the difference between dispatch-rate and sync-rate
training. ``scripts/check_host_sync.py`` guards the discipline statically.

Zero-stall snapshots (ISSUE 5, docs/ARCHITECTURE.md "Zero-stall snapshots"):
with ``learner.async_snapshots`` (the default) even the boundary-cadence
side effects leave the train thread. At a publish/checkpoint/log boundary
the loop runs one cheap jitted on-device copy of the needed state into
fresh HBM snapshot buffers and dispatches the next step immediately; the
background snapshot thread (train/snapshot.py) does the batched device→host
fetch, the bf16 wire cast + encode, the non-blocking fanout enqueue, and
the orbax write. Published versions stay monotonic under latest-wins
coalescing, graceful stop drains the engine and lands the forced checkpoint
at the EXACT stop step via the sync path, and async write failures surface
through ``checkpoint/save_failures_total``. ``--sync-snapshots`` opts out.

Pipelined data path (ISSUE 2, docs/ARCHITECTURE.md "Pipelined data path"):
multi-epoch/minibatch batches train through the fused epoch step — ONE
donated dispatch for all ``epochs × minibatches`` updates
(``ppo.fused_epoch``; ``train/ppo.make_epoch_step``) — and the loop
prefetches batch N+1 (transport drain → staged host rows → ring scatter →
batch gather, all dispatch) behind batch N's in-flight step, with hit-rate
and overlap-fraction gauges proving the overlap.

Usage:
    python -m dotaclient_tpu.train.learner --smoke       # tiny sanity run
    python -m dotaclient_tpu.train.learner --steps 1000 --logdir runs/x
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from collections import deque

from dotaclient_tpu.buffer import TrajectoryBuffer
from dotaclient_tpu.league import pool as league_pool
from dotaclient_tpu.config import RunConfig, default_config
from dotaclient_tpu.actor import ActorPool, VecActorPool
from dotaclient_tpu.models import init_params, make_policy
from dotaclient_tpu.parallel import make_mesh
from dotaclient_tpu.train.ppo import (
    init_train_state,
    make_epoch_step,
    make_train_step,
)
from dotaclient_tpu.transport import (
    InProcTransport,
    Transport,
    decode_rollout,
    encode_weights,
)
from dotaclient_tpu.utils import (
    compile_cache,
    faults,
    telemetry,
    tracing,
    utilization,
)
from dotaclient_tpu.utils.checkpoint import CheckpointManager, shape_mismatches
from dotaclient_tpu.utils.metrics import MetricsLogger


def _startup_span(init):
    """``Learner.__init__`` under the span ``startup/learner_init``: a
    decorator, so that the 600 lines stay the constructor's own text, which
    the ``host-sync`` and ``thread-ownership`` lints read by its name."""

    @functools.wraps(init)
    def spanned(self, *args, **kwargs) -> None:
        with telemetry.get_registry().span("startup/learner_init"):
            init(self, *args, **kwargs)

    return spanned


class Learner:
    """Owns the full training stack for single-host runs."""

    @_startup_span
    def __init__(
        self,
        config: RunConfig,
        transport: Optional[Transport] = None,
        logdir: Optional[str] = None,
        checkpoint_dir: Optional[str] = None,
        restore: bool = False,
        init_from: Optional[str] = None,
        seed: int = 0,
        vec: bool = True,
        actor: Optional[str] = None,
        debug_checkify: bool = False,
        metrics_jsonl: Optional[str] = None,
    ) -> None:
        # the start told by the program itself: what the process spent
        # before this line (interpreter, imports, accelerator runtime start),
        # then `startup/learner_init` (the decorator) with one child a stage
        # below; what no child names is the constructor's self time
        telemetry.get_registry().gauge("startup/process_age_at_init_s").set(
            telemetry.process_age_s()
        )
        # actor mode: "device" (on-device rollout scan feeding the buffered
        # learner), "fused" (rollout + PPO update in ONE XLA program — the
        # fastest synchronous path; train batch = lane set, strictly
        # on-policy, see train/fused.py), "vec" (numpy vectorized sim,
        # host-driven), "scalar" (proto/gRPC-parity pool), "external" (no
        # in-process actors — N standalone `python -m dotaclient_tpu.actor`
        # processes feed the transport, the reference's scale-out topology,
        # SURVEY.md §1). `vec` kept for backward compatibility.
        mode = actor or ("vec" if vec else "scalar")
        if mode not in ("device", "fused", "vec", "scalar", "external"):
            raise ValueError(f"unknown actor mode {mode!r}")
        # Fused mode shuffles/splits in-program along lanes (train/fused.py
        # validates n_lanes % minibatches); the buffered paths split the
        # optimizer batch host-side, so batch_rollouts must divide.
        if (
            mode != "fused"
            and config.ppo.minibatches > 1
            and config.ppo.batch_rollouts % config.ppo.minibatches
        ):
            raise ValueError(
                f"batch_rollouts {config.ppo.batch_rollouts} not "
                f"divisible by minibatches {config.ppo.minibatches}"
            )
        if config.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{config.steps_per_dispatch}"
            )
        if config.steps_per_dispatch > 1 and mode != "fused":
            raise ValueError(
                "steps_per_dispatch > 1 batches iterations inside the fused "
                "program — it has no meaning for the staged actor modes; "
                "use actor='fused' or leave it at 1"
            )
        if (
            # the pool itself is gated on env.opponent (below), so the
            # guard must be too — league.enabled alone can be stale
            (config.league.enabled or config.env.opponent == "league")
            and mode in ("fused", "device")
            and config.steps_per_dispatch * config.ppo.steps_per_batch
            > config.league.opponent_hold
        ):
            # opponent redraws can only happen at dispatch boundaries, so a
            # hold shorter than the stride is silently stretched to it —
            # PFSP mixing would degrade below its configured cadence.
            raise ValueError(
                f"league.opponent_hold ({config.league.opponent_hold}) is "
                f"shorter than one dispatch stride "
                f"(steps_per_dispatch × steps_per_batch = "
                f"{config.steps_per_dispatch * config.ppo.steps_per_batch}) "
                f"— raise opponent_hold or lower steps_per_dispatch"
            )
        if mode == "fused" and debug_checkify:
            raise ValueError(
                "checkify instruments the buffered train step, which fused "
                "mode never calls — use actor='device' to hunt NaNs"
            )
        if mode != "fused":
            from dotaclient_tpu.models.policy import require_carry_stays

            # every other mode ships each chunk's carry0 through the ring
            # buffer or over the wire
            require_carry_stays(config.model, f"actor mode {mode!r}")
        if mode == "external" and transport is None:
            raise ValueError(
                "external actor mode needs a transport (TransportServer or "
                "AmqpTransport) for the actor processes to reach"
            )
        self.actor_mode = mode
        self.config = config
        self.mesh = make_mesh(config.mesh)
        # Multi-chip telemetry (ISSUE 10): mesh geometry gauges, all
        # eager-created here so any learner run's JSONL validates
        # `check_telemetry_schema.py --require-multichip`
        # deterministically (`buffer/shard_bytes` stays 0 for bufferless
        # fused runs; the ring overwrites it when it allocates). The
        # gradient all-reduce itself is timed from a profiler trace (the
        # benchmark's `collective_share`, `collective_exposed_share`).
        from dotaclient_tpu.parallel.mesh import batch_shard_count

        reg = telemetry.get_registry()
        # Pipeline tracing + device hooks (ISSUE 12): the tracer is
        # captured ONCE (faults.get() discipline — configure before
        # constructing the learner); the trace/compile/mem keys are
        # eager-created so `check_telemetry_schema.py --require-trace`
        # validates ANY learner JSONL deterministically.
        tracing.ensure_metrics(reg)
        self._tracer = tracing.get()
        reg.gauge("mesh/n_devices").set(float(self.mesh.devices.size))
        reg.gauge("mesh/data_shards").set(
            float(batch_shard_count(self.mesh, config.mesh))
        )
        reg.gauge("buffer/shard_bytes")
        # Lane-sharded actor geometry (ISSUE 18): eager-created so any
        # learner JSONL validates --require-multichip; they stay 0 for
        # modes without a device-resident actor and are set to the real
        # lane split when the DeviceActor is constructed below.
        reg.gauge("mesh/lane_shards")
        reg.gauge("fused/lanes_per_shard")
        if config.ppo.minibatches > 1:
            # each minibatch is itself a data-sharded train batch. In fused
            # mode the chunk IS the lane set, split along lanes in-program
            # (train/fused.py); the buffered paths split batch_rollouts.
            shards = batch_shard_count(self.mesh, config.mesh)
            if mode == "fused":
                from dotaclient_tpu.actor.device_rollout import lane_split

                total = config.env.n_envs * len(lane_split(config)[0])
                what = f"lane count {total}"
            else:
                total = config.ppo.batch_rollouts
                what = f"batch_rollouts {total}"
            mb = total // config.ppo.minibatches
            if total % config.ppo.minibatches or mb % shards:
                raise ValueError(
                    f"{what} must split into minibatches "
                    f"({config.ppo.minibatches}) of a size divisible by the "
                    f"batch shard count {shards} (minibatches are "
                    f"data-sharded batches); got minibatch size {mb}"
                )
        with reg.span("startup/learner_init/params"):
            self.policy = make_policy(config.model, config.obs, config.actions)
            params = init_params(self.policy, jax.random.PRNGKey(config.seed))
        with reg.span("startup/learner_init/train_state"):
            self.state = init_train_state(params, config.ppo)
        # The TrainState's sharding tree (params + Adam mirrors replicated
        # under pure DP, TP-partitioned under model_parallel > 1; counters
        # replicated) — the SAME tree make_train_step/make_epoch_step pin
        # as in/out shardings, computed once and reused by every restore
        # path so a checkpoint written at a different device count is
        # re-committed to THIS mesh before its first dispatch (ISSUE 10).
        from dotaclient_tpu.train.ppo import train_state_sharding

        self.state_shardings = train_state_sharding(
            self.policy, config, self.mesh
        )
        self.ckpt: Optional[CheckpointManager] = None
        self._want_restore = restore
        self._init_from_step = 0   # source step when seeded via init_from
        if init_from:
            if restore:
                raise ValueError(
                    "init_from seeds a FRESH run from a source checkpoint; "
                    "restore resumes this run's own checkpoint_dir — "
                    "they are mutually exclusive"
                )
            if checkpoint_dir and (
                os.path.realpath(init_from) == os.path.realpath(checkpoint_dir)
            ):
                raise ValueError(
                    "init_from must point at a SEPARATE source directory: "
                    "seeding resets the step counter to 0, so writing into "
                    "the source dir would decline every periodic save "
                    "(step <= latest) and the end-of-run save would destroy "
                    "the source snapshot"
                )
            # Weights-only seed from a SEPARATE source directory: the run's
            # own checkpoint_dir stays the destination, so its rolling
            # garbage collection can never eat the source snapshot (the
            # failure mode of resuming curriculum stages in one directory).
            # Optimizer moments and counters start FRESH: restored Adam
            # second moments are calibrated to the SOURCE config's gradient
            # scales and can catastrophically over-step the transferred
            # policy in the first updates. (The source's opt_state is read
            # and discarded — a few MB at these model sizes; not worth a
            # partial-restore template.)
            if not os.path.isdir(init_from):
                # Constructing the manager would CREATE the missing dir
                # (orbax create=True) — a mistyped path must fail cleanly,
                # not leave a stray empty checkpoint tree masking the typo.
                raise FileNotFoundError(
                    f"init_from directory does not exist: {init_from!r}"
                )
            src = CheckpointManager(init_from)
            try:
                # Weights-only (template-free) restore: init_from must work
                # across optimizer configs — a plain-Adam source seeding a
                # KL-adaptive run has a different opt_state layout, and the
                # moments are discarded here anyway.
                with reg.span("startup/learner_init/init_from"):
                    seeded_params, seeded_step = src.restore_weights()
            except (KeyError, ValueError, TypeError) as e:
                raise ValueError(
                    f"init_from checkpoint at {init_from!r} does not match "
                    f"this run's model structure (different core?): {e}"
                ) from e
            finally:
                src.close()
            want = jax.eval_shape(lambda: self.state.params)
            bad = shape_mismatches(seeded_params, want)
            if bad:
                raise ValueError(
                    f"init_from checkpoint is incompatible with this run's "
                    f"model config (param shape {bad[0]}, +{len(bad) - 1} "
                    f"more mismatches) — was it trained with a different "
                    f"core/width?"
                )
            self.state = init_train_state(seeded_params, config.ppo)
            self._init_from_step = seeded_step
        self.ckpt_best: Optional[CheckpointManager] = None
        self._best_dir: Optional[str] = None
        self._best_win = -1.0
        if checkpoint_dir:
            self.ckpt = CheckpointManager(checkpoint_dir)
            if restore and self.ckpt.latest_step() is not None:
                try:
                    with reg.span("startup/learner_init/restore"):
                        self.state, _ = self.ckpt.restore(config, self.state)
                except ValueError as e:
                    # The only layout-changing PPO knob today is kl_target
                    # (inject_hyperparams adds an lr leaf to opt_state) —
                    # translate orbax's raw tree diff into the fix.
                    raise ValueError(
                        f"checkpoint at {checkpoint_dir!r} does not match "
                        f"this run's OPTIMIZER layout — toggling "
                        f"ppo.kl_target between a run and its --restore "
                        f"changes the opt_state structure. Restore with "
                        f"the original setting, or re-seed weights-only "
                        f"via --init-from. ({e})"
                    ) from e
            if config.checkpoint_best_min_episodes > 0:
                # Best-model rotation (see RunConfig.checkpoint_best_min_
                # episodes): the mid-run peak survives even when training
                # later slides off it. The manager is created lazily at the
                # first qualifying save (actor modes without windowed
                # win-rate stats would otherwise leave a stray empty tree),
                # but the best-so-far value must load EAGERLY: a resumed
                # run that reset it to -1 would let its first (possibly
                # collapsed) window overwrite the captured peak.
                self._best_dir = os.path.join(checkpoint_dir, "best")
                meta = os.path.join(self._best_dir, "best_meta.json")
                if os.path.exists(meta):
                    try:
                        with open(meta) as f:
                            self._best_win = float(
                                json.load(f)["win_rate_recent"]
                            )
                    except (OSError, ValueError, KeyError):
                        # Unreadable meta + a resumed collapsed run would
                        # let the first window displace the captured peak;
                        # +inf freezes the rotation until the operator
                        # inspects/removes best/ (loud, not silent).
                        print(
                            f"WARNING: {meta} unreadable — best-model "
                            f"rotation FROZEN to protect the existing "
                            f"best/ checkpoint; delete the dir to reset",
                            flush=True,
                        )
                        self._best_win = float("inf")
        # Commit the state to the mesh NOW (one device_put against
        # state_shardings), whatever path built it — fresh init, init_from
        # seed, or a --restore of a checkpoint written at ANY device count
        # (restores hand back host-layout arrays; this is the re-shard).
        # Committing before the first dispatch also means the first donated
        # step donates correctly-sharded buffers instead of paying a
        # layout change mid-program. A 1-device mesh is the degenerate
        # case of the same call.
        with reg.span("startup/learner_init/state_commit"):
            self.state = jax.device_put(self.state, self.state_shardings)
            # Anchor-KL regularizer (PPOConfig.anchor_kl_coef): the anchor is
            # the policy AS CONSTRUCTED — after --init-from/--restore — i.e.
            # the transferred policy in a curriculum fine-tune. Copied: the
            # train step donates/updates the live params.
            self.anchor_params = (
                jax.tree.map(jnp.copy, self.state.params)
                if config.ppo.anchor_kl_coef > 0
                else None
            )
        # instrument_jit (ISSUE 12): per-program compile/retrace counters
        # + (traced runs) cost analysis once per compile; transparent to
        # dispatch and to the donation lint (lint/donation.py unwraps it)
        self.train_step = tracing.instrument_jit(
            make_train_step(
                self.policy, config, self.mesh,
                debug_checkify=debug_checkify,
                anchor_params=self.anchor_params,
            ),
            "train_step",
        )
        # Fused epoch step (ppo.fused_epoch): when one consumed batch needs
        # E×M > 1 optimizer steps, run them all in ONE donated program
        # instead of the staged gather+step dispatch pair per minibatch.
        # The staged loop stays compiled-on-demand as the fallback
        # (--checkify instruments per-step; fused_epoch=false opts out).
        self.epoch_step = None
        if (
            config.ppo.fused_epoch
            and config.ppo.steps_per_batch > 1
            and mode != "fused"
            and not debug_checkify
        ):
            self.epoch_step = tracing.instrument_jit(
                make_epoch_step(
                    self.policy, config, self.mesh,
                    anchor_params=self.anchor_params,
                ),
                "epoch_step",
            )
        # One-pass advantage plane (ISSUE 14, train/advantage.py): a
        # jitted, mesh-sharded value-forward + GAE pass runs ONCE per
        # consumed batch at the buffer gather boundary, and the epoch
        # step consumes the staged (bf16-narrow) advantages/returns
        # across all E×M updates instead of recomputing them per step.
        # Fused mode trains in-program on-policy and vtrace needs the
        # current policy's per-step logp — both keep the in-step
        # recompute (one_pass_enabled gates on the estimator).
        from dotaclient_tpu.train.advantage import (
            make_advantage_pass,
            one_pass_enabled,
        )

        self.advantage_pass = None
        self._adv_overlap = config.learner.overlap_advantage
        self._adv_overlapped_s = 0.0
        self._adv_serial_s = 0.0
        self._adv_first = True   # first pass pays compile: not accounted
        if mode != "fused" and one_pass_enabled(config):
            self.advantage_pass = tracing.instrument_jit(
                make_advantage_pass(self.policy, config, self.mesh),
                "advantage_pass",
            )
        # eager-created so ANY learner JSONL validates
        # `check_telemetry_schema.py --require-advantage` (a recompute run
        # reports one_pass=0 and zeros, never missing keys)
        reg.gauge("advantage/one_pass").set(
            1.0 if self.advantage_pass is not None else 0.0
        )
        reg.gauge("advantage/pass_ms")
        reg.gauge("advantage/overlap_fraction")
        reg.counter("advantage/passes_total")
        # Fused mode trains each chunk inside its one program and never
        # stages experience: allocating the HBM ring there would pin
        # capacity_rollouts chunks of dead device memory.
        self.buffer = (
            None if mode == "fused" else TrajectoryBuffer(config, self.mesh)
        )
        self.transport = transport or InProcTransport()
        # Zero-stall snapshot engine (ISSUE 5, docs/ARCHITECTURE.md
        # "Zero-stall snapshots"): weight publishes, periodic checkpoints,
        # and log-boundary metrics fetches run on a background thread; at a
        # boundary the train thread only runs one cheap jitted on-device
        # copy (`_snap_copy`, dispatched BEFORE the next donating train
        # step, so device-stream ordering protects the snapshot) and keeps
        # dispatching. learner.async_snapshots=false (--sync-snapshots)
        # restores the inline behavior for debugging.
        self._snap_engine = None
        self._snap_copy = None
        # Training health guardian (ISSUE 6, train/health.py): the
        # in-graph probe's verdict scalars accumulate host-side per
        # consumed batch (zero device traffic) and are flushed as ONE
        # batched fetch through the snapshot engine at boundary cadence —
        # ordered before the publish job, so the publish gate is sound
        # without the train thread ever blocking on a verdict. On a
        # latched divergence the loop rolls the TrainState back to the
        # last_good checkpoint slot (bounded retries, distinct minibatch
        # RNG, loud exit when exhausted).
        self._health = None
        if config.health.enabled:
            from dotaclient_tpu.train.health import HealthMonitor

            self._health = HealthMonitor(config.health)
        self._rollback_count = 0
        # Device references to the LAST batch's verdict scalars (sync-mode
        # checkpoint/tail folds — see _sync_fold_latest).
        self._last_verdict_m = None
        # Highest version actually handed to the fanout on the SYNC path
        # (async mode asks the engine); the rollback audit line reports
        # whichever is live as its published-floor evidence.
        self._published_version = -1
        # Deferred best-model candidate, written by the snapshot thread's
        # metrics continuation and consumed on the train thread; the lock
        # makes the read-and-clear swap atomic against a concurrent write
        # (an unsynchronized swap could silently drop a qualifying peak).
        self._pending_best: Optional[Dict[str, float]] = None
        self._pending_best_lock = threading.Lock()
        self._stall_s = 0.0   # train-thread seconds lost to side effects
        if config.learner.async_snapshots:
            from dotaclient_tpu.train.snapshot import SnapshotEngine

            with reg.span("startup/learner_init/snapshot_engine"):
                self._snap_engine = SnapshotEngine(
                    transport=self.transport,
                    wire_dtype=config.transport.wire_dtype,
                    ckpt=self.ckpt,
                    health=self._health,
                )
            self._snap_copy = tracing.instrument_jit(
                jax.jit(lambda t: jax.tree.map(jnp.copy, t)), "snap_copy"
            )
        # eager-create the stall gauges (and, sync mode, the snapshot keys
        # the engine would have created): a clean run reports zeros —
        # check_telemetry_schema.py --require-snapshot pins all four
        for key in (
            "learner/publish_stall_ms",
            "learner/stall_fraction",
            "snapshot/pending",
            "snapshot/d2h_ms",
        ):
            telemetry.get_registry().gauge(key)
        # Vectorized mode ships decoded rollouts through an in-proc deque
        # (thread-safe append/drain) — no proto round-trip on the hot path;
        # the scalar pool keeps proto/gRPC parity coverage. Bounded with
        # drop-oldest, like InProcTransport: in overlap mode the actor thread
        # free-runs while the learner compiles/checkpoints.
        self._sink: Optional[deque] = (
            deque(maxlen=4 * config.buffer.capacity_rollouts)
            if mode == "vec" else None
        )
        self.device_actor = None
        self.fused_step = None
        if mode == "external":
            self.pool = None
        elif mode in ("device", "fused"):
            from dotaclient_tpu.actor.device_rollout import DeviceActor

            # the actor state is committed lane-sharded over the learner's
            # mesh (ISSUE 18): games partition over the (dcn×)data axes, so
            # the fused program's pinned shardings are satisfied by layout
            with reg.span("startup/learner_init/device_actor"):
                self.device_actor = DeviceActor(
                    config, self.policy, seed=seed,
                    mesh=self.mesh, mesh_config=config.mesh,
                )
            self.pool: Any = self.device_actor  # shared stats() surface
            reg = telemetry.get_registry()
            reg.gauge("mesh/lane_shards").set(
                float(self.device_actor.lane_shards)
            )
            reg.gauge("fused/lanes_per_shard").set(
                float(self.device_actor.lanes_per_shard)
            )
            if mode == "fused":
                from dotaclient_tpu.train.fused import make_fused_step

                with reg.span("startup/learner_init/fused_program"):
                    program = make_fused_step(
                        self.policy, config, self.mesh, self.device_actor,
                        anchor_params=self.anchor_params,
                    )
                # fixed with the program, whatever later stands in for
                # `fused_step`: a live dispatch's rollout is one pass
                self._live_shares_pass = program.live_shares_pass
                self.fused_step = tracing.instrument_jit(program, "fused_step")
        elif mode == "vec":
            self.pool = VecActorPool(
                config,
                self.policy,
                self.state.params,
                seed=seed,
                version=int(self.state.version),
                rollout_sink=self._sink.extend,
            )
        else:
            self.pool = ActorPool(
                config,
                self.policy,
                self.state.params,
                transport=self.transport,
                seed=seed,
                version=int(self.state.version),
            )
        # League: frozen-opponent pool driving the Dire side (SURVEY.md §7
        # step 7). Seeded from the initial params so opponent lanes are
        # frozen from step 0, never silently mirroring the live policy.
        self.league = None
        self._league_pending: List[Any] = []
        self._held_opponent = None      # (params|None, uid) held draw
        self._held_until = -1
        if config.env.opponent == "league":
            if mode == "scalar":
                raise NotImplementedError(
                    "league mode needs frozen-opponent lanes; the scalar "
                    "gRPC-parity pool has none — use actor='device' or 'vec'"
                )
            from dotaclient_tpu.league import OpponentPool

            with reg.span("startup/learner_init/league"):
                self.league = OpponentPool(config.league, seed=seed)
                self.league.maybe_snapshot(
                    self.state.params, int(self.state.version), 0
                )
            if mode == "vec":
                # live-params draws must be copies: the train step donates
                # the learner state, killing any buffer the pool holds
                self.pool.set_opponent(
                    *self.league.sample(self._actor_params_copy(), 0)
                )
                if config.league.matchmaking == "pfsp":
                    print(
                        "WARNING: PFSP matchmaking needs per-draw outcome "
                        "attribution, which only the device/fused loops "
                        "provide; host-pool league draws keep the 0.5 "
                        "prior and behave as uniform",
                        flush=True,
                    )
        self.telemetry = telemetry.get_registry()
        self.metrics = MetricsLogger(logdir, jsonl=metrics_jsonl)
        # Fleet health plane (ISSUE 13): the aggregator is ALWAYS
        # constructed — that alone eager-creates every fleet/ + alerts/
        # key, so `check_telemetry_schema.py --require-fleet` validates
        # any learner JSONL deterministically. Its merge/alert thread
        # only STARTS when a fleet can actually report (the external
        # transports); transport reader threads hand it kind-5 metric
        # snapshot frames via `metrics_handler`, and ALERT events ride
        # the metrics JSONL's flush-per-emit durability.
        from dotaclient_tpu.utils.fleet import FleetAggregator

        self.fleet = FleetAggregator(
            registry=self.telemetry, emit_event=self.metrics.emit_event
        )
        if transport is not None and hasattr(transport, "metrics_handler"):
            transport.metrics_handler = self.fleet.ingest
        # Outcome attribution plane (ISSUE 15): eager-create BOTH halves
        # of the outcome key schema — the actor-side counters (so
        # `--require-outcome` validates an external learner's JSONL that
        # only ever sees fleet mirrors) and the aggregator's curve gauges.
        # The aggregator has no thread of its own: the fleet aggregator's
        # tick hook drives it at fleet cadence in external modes (wall
        # clock — outcome staleness evaluates even when training stalls),
        # and _publish_pipeline_gauges ticks it at log boundaries in the
        # in-process modes.
        from dotaclient_tpu.outcome import OutcomeAggregator
        from dotaclient_tpu.outcome import records as outcome_records

        outcome_records.ensure_actor_metrics(self.telemetry)
        self.outcome = OutcomeAggregator(registry=self.telemetry)
        self.fleet.add_tick_hook(self.outcome.tick)
        self._fleet_started = False
        if mode == "external" and telemetry.fleet_interval_s > 0:
            self.fleet.start()
            self._fleet_started = True
        self.frames_per_rollout = config.ppo.rollout_len
        # Minibatch machinery: one jitted gather (a tree of row-gathers is
        # otherwise a dispatch per leaf), host RNG for the shuffles, and the
        # optimizer-steps-per-consumed-batch stride used by counters and
        # log/checkpoint gating.
        from dotaclient_tpu.parallel import data_sharding

        self._minibatch_gather = tracing.instrument_jit(
            jax.jit(
                lambda batch, idx: jax.tree.map(lambda x: x[idx], batch),
                # minibatches must arrive at the train step in its batch
                # sharding (the donated step pins its in_shardings)
                out_shardings=data_sharding(self.mesh, config.mesh),
            ),
            "minibatch_gather",
        )
        self._mb_rng = np.random.default_rng(config.seed + 1)
        self._mb_draws = 0          # permutations consumed (for exact resume)
        self._steps_per_batch = config.ppo.steps_per_batch
        self._last_metrics: Dict[str, float] = {}
        # Prefetch lane: batch N+1, already drained/scattered/gathered while
        # batch N's (dispatch-only) optimizer step runs on the device. Hit
        # and overlap accounting feed the learner/prefetch_hit_rate and
        # learner/overlap_fraction gauges — host floats, no device traffic.
        self._prefetched = None
        self._prefetch_ticket: Optional[int] = None
        self._prefetch_hits = 0
        self._prefetch_misses = 0
        self._prefetch_overlapped_s = 0.0
        self._prefetch_serial_s = 0.0
        # True between an optimizer dispatch and the next blocking fetch:
        # host work done in that window overlaps device compute.
        self._dispatch_inflight = False
        self._poll_timeout = config.buffer.consume_poll_timeout_s
        # Host-side mirrors of state.step/state.version: reading the device
        # scalars costs a full sync per read, so the loop never does.
        self._host_step = int(np.asarray(self.state.step))   # host-sync-ok: one-time init
        self._host_version = int(np.asarray(self.state.version))   # host-sync-ok: one-time init
        # Graceful-stop latch (ISSUE 4): request_stop() — typically from a
        # SIGTERM handler — makes every train loop exit at its next step
        # boundary, after which the normal end-of-run tail runs: the
        # prefetch lane requeues its held batch, the full-pipeline
        # checkpoint is taken, final weights publish, transports close.
        self._stop_requested = False
        self._faults = faults.get()   # None unless chaos injection is on
        # Pipeline utilization plane (ISSUE 16, utils/utilization.py):
        # always-on phase accountant attributing every train-thread
        # wall-clock second to a closed phase set at the boundaries the
        # loop already has. The factory eager-creates every util/* gauge
        # (so `check_telemetry_schema.py --require-utilization` validates
        # ANY learner JSONL) and returns the accountant.
        self._util = utilization.make_learner(self.telemetry)
        # Pipeline restore (buffer contents + device-actor state) happens
        # after those components exist; weights/opt-state restored above.
        if (
            self._want_restore
            and self.ckpt is not None
            and self.ckpt.latest_step() is not None
        ):
            with reg.span("startup/learner_init/pipeline_restore"):
                self._restore_pipeline()

    # -- loop --------------------------------------------------------------

    def request_stop(self) -> None:
        """Ask the running train() to drain and return at its next step
        boundary (signal-handler safe: one flag write, no locks). The
        end-of-run tail then checkpoints the FULL pipeline — a stopped run
        resumes at the exact step with no experience loss."""
        self._stop_requested = True

    @property
    def stop_requested(self) -> bool:
        return self._stop_requested

    def ingest(self) -> int:
        with self.telemetry.span("learner/consume"):
            return self._ingest_impl()

    def _ingest_impl(self) -> int:
        if self._sink is not None:
            rollouts = []
            cap = self.config.buffer.capacity_rollouts
            while self._sink and len(rollouts) < cap:
                rollouts.append(self._sink.popleft())
            if not rollouts:
                return 0
            return self.buffer.add(rollouts, self._host_version)
        # Poll budget (buffer.consume_poll_timeout_s): how long an EMPTY
        # drain may block. A ready prefetched batch never waits on this —
        # _next_batch serves the lane without reaching the drain at all.
        timeout = self._poll_timeout
        if hasattr(self.transport, "consume_decoded"):
            # socket path: raw bytes → native wire parser → zero-copy views
            rollouts = self.transport.consume_decoded(
                self.config.buffer.capacity_rollouts, timeout=timeout
            )
            if not rollouts:
                return 0
            return self.buffer.add(rollouts, self._host_version)
        protos = self.transport.consume_rollouts(
            self.config.buffer.capacity_rollouts, timeout=timeout
        )
        if not protos:
            return 0
        return self.buffer.add(
            [decode_rollout(p) for p in protos], self._host_version
        )

    def _optimize(self, batch) -> Dict[str, jnp.ndarray]:
        """Run ``epochs_per_batch`` passes over one batch, each split into
        ``minibatches`` shuffled slices (the standard PPO regime; with the
        defaults of 1×1 this is a single donated step). Dispatch-only.
        Returns the last pass's (device-resident) metrics.

        With ``ppo.fused_epoch`` (the default) and E×M > 1 this is ONE
        donated dispatch: the epoch permutations are drawn host-side from
        the same ``_mb_rng`` stream the staged loop uses (same updates on
        the same data, and ``_mb_draws`` keeps its exact-resume meaning —
        one draw per epoch), then the whole update loop runs in-program
        (``make_epoch_step``).
        The staged loop below is the fallback for --checkify and
        ``fused_epoch=false``.
        """
        if self._faults is not None and self._faults.fire(
            "learner.fail_train_step"
        ):
            raise RuntimeError(
                "injected fault: learner.fail_train_step (chaos harness)"
            )
        if self._faults is not None and self._faults.fire("learner.nan_grad"):
            # Divergence injection (ISSUE 6 chaos): one NaN reward poisons
            # the loss and the whole backward pass — the realistic NaN-
            # gradient shape — placed on the dispatch path (a tiny jitted
            # scatter, no host↔device sync). The health probe must flag
            # the step, the publish gate must hold the version back, and
            # rollback must restore last_good.
            batch = dict(batch)
            batch["rewards"] = batch["rewards"].at[0, 0].set(jnp.nan)
            if "advantages" in batch:
                # one-pass batches: the poisoned reward would have flowed
                # through the consume-time pass — mirror it into the
                # staged advantages or the loss never sees the NaN
                batch["advantages"] = (
                    batch["advantages"].at[0, 0].set(jnp.nan)
                )
        cfg = self.config.ppo
        M = max(1, cfg.minibatches)
        E = cfg.epochs_per_batch
        if self.epoch_step is not None:
            B = cfg.batch_rollouts
            if M > 1:
                perms = np.stack(
                    [self._mb_rng.permutation(B) for _ in range(E)]
                )
                self._mb_draws += E
            else:
                # unsplit batches are never shuffled (matches the staged
                # path); the in-program scan ignores this placeholder
                perms = np.broadcast_to(np.arange(B), (E, B))
            t0 = time.perf_counter()
            with self.telemetry.span("learner/dispatch"):
                self.state, m = self.epoch_step(
                    self.state, batch, perms.astype(np.int32)
                )
            # the dispatch call's host time: in a throughput-bound
            # loop it blocks on donation back-pressure — the
            # host-observable proxy for device busy time (the
            # accounting contract, docs/ARCHITECTURE.md)
            self._util.phase("dispatch_inflight", time.perf_counter() - t0)
            self._dispatch_inflight = True
            self._host_step += E * M
            self._host_version += E * M
            self._submit_health(m)
            if self._tracer is not None:
                self._emit_dispatch_traces()
            return m
        for _ in range(E):
            if M == 1:
                t0 = time.perf_counter()
                with self.telemetry.span("learner/dispatch"):
                    self.state, m = self.train_step(self.state, batch)
                self._util.phase("dispatch_inflight", time.perf_counter() - t0)
                self._dispatch_inflight = True
                self._host_step += 1
                self._host_version += 1
                continue
            B = cfg.batch_rollouts
            mb = B // M
            perm = self._mb_rng.permutation(B)
            self._mb_draws += 1
            for i in range(M):
                t0 = time.perf_counter()
                with self.telemetry.span("learner/assemble"):
                    idx = jnp.asarray(perm[i * mb:(i + 1) * mb], jnp.int32)
                    sub = self._minibatch_gather(batch, idx)
                t1 = time.perf_counter()
                with self.telemetry.span("learner/dispatch"):
                    self.state, m = self.train_step(self.state, sub)
                self._util.phase("gather", t1 - t0)
                self._util.phase("dispatch_inflight", time.perf_counter() - t1)
                self._dispatch_inflight = True
                self._host_step += 1
                self._host_version += 1
        self._submit_health(m)
        if self._tracer is not None:
            self._emit_dispatch_traces()
        return m

    def _emit_dispatch_traces(self) -> None:
        """Terminal hop of the chunk timeline (ISSUE 12): the batch the
        just-issued dispatch consumes carries the records its ``take``
        parked in the buffer — stamp ``dispatch`` and emit them, plus the
        sampled per-dispatch lifecycle event. Host dict appends only;
        caller guards on ``self._tracer``."""
        tracer = self._tracer
        ts = tracing.now()
        if self.buffer is not None:
            for rec in self.buffer.drain_traces():
                rec["hops"].append(["dispatch", ts])
                tracer.emit_chunk(rec)
        if tracer.should_sample():
            tracer.emit("dispatch", step=self._host_step)

    def _next_batch(self, drain_transport: bool = True):
        """The consume side of the prefetch lane: hand back the batch
        staged behind the previous dispatch if there is one, else do the
        (serial) ingest+take now. Dispatch-only either way."""
        batch, self._prefetched = self._prefetched, None
        if batch is not None:
            # consuming the held batch: its ring slots become reusable
            self.buffer.release(self._prefetch_ticket)
            self._prefetch_ticket = None
            self._prefetch_hits += 1
            # overlap_advantage=false stages the batch bare — the pass
            # runs here, at consume time (no-op when already attached)
            return self._attach_advantages(batch)
        t0 = time.perf_counter()
        if drain_transport:
            self.ingest()
        batch = self.buffer.take(current_version=self._host_version)
        dt = time.perf_counter() - t0
        # a productive take is batch assembly; an empty one is the
        # buffer below min consumable — starvation, not staging
        self._util.phase("gather" if batch is not None else "ingest_wait", dt)
        if batch is not None:
            # only productive staging counts toward the overlap accounting
            # — empty polls while starved are idle waiting, not assemble
            # cost (same rule the transport/consume span applies)
            self._prefetch_serial_s += dt
            self._prefetch_misses += 1
            batch = self._attach_advantages(batch)
        return batch

    def _prefetch_next(self, drain_transport: bool = True) -> None:
        """Stage batch N+1 while batch N's optimizer step is still in
        flight: the loop is dispatch-only, so the host returns from
        ``_optimize`` immediately and the transport drain, host-row
        staging, ring scatter, and batch gather issued here all overlap
        the device's epoch-step compute. Single-writer discipline holds —
        this runs on the learner thread, same as every other buffer op."""
        if self._prefetched is not None or self.buffer is None:
            return
        t0 = time.perf_counter()
        if drain_transport:
            self.ingest()
        # hold=True parks the slots: an ingest racing this in-flight
        # batch can neither evict nor overwrite them
        taken = self.buffer.take(
            current_version=self._host_version, hold=True
        )
        if taken is None:
            self._util.phase("ingest_wait", time.perf_counter() - t0)
            return   # nothing staged: idle waiting, not assemble cost
        self._prefetched, self._prefetch_ticket = taken
        dt = time.perf_counter() - t0
        self._util.phase("gather", dt)
        # recorded only when a batch was actually staged, like the
        # transport/consume span — empty attempts would dilute both the
        # span stats and the overlap fraction toward meaninglessness
        self.telemetry.timer("span/learner/prefetch").observe(dt)
        if self._dispatch_inflight:
            self._prefetch_overlapped_s += dt
        else:
            self._prefetch_serial_s += dt
        if self._adv_overlap:
            # stage compute, not just bytes (ISSUE 14): batch N+1's
            # advantage pass dispatches behind batch N's in-flight epoch
            # step — device-stream ordering runs it on the step's OUTPUT
            # params, exactly the params the staged batch's first update
            # will train from
            self._prefetched = self._attach_advantages(
                self._prefetched, overlapped=self._dispatch_inflight
            )

    def _flush_prefetch(self) -> None:
        """Return an unconsumed prefetched batch to the ring (front of the
        order) before anything that snapshots or ends the run — prefetching
        must never turn into experience loss. Advantages staged on the
        batch (``_attach_advantages``) die with it: only the ring slots
        survive, so the next take re-runs the pass with whatever params
        are live then — the invariant the divergence rollback leans on."""
        if self._prefetched is not None:
            self.buffer.requeue(self._prefetch_ticket)
            self._prefetched = None
            self._prefetch_ticket = None

    def _attach_advantages(self, batch, overlapped: bool = False):
        """Consume-time advantage plane (ISSUE 14, train/advantage.py):
        run the jitted value-forward + GAE pass over a just-gathered
        batch and attach the narrow ``advantages``/``returns`` leaves the
        epoch step consumes across all E×M updates. Dispatch-only: the
        host enqueues one program (behind the in-flight donated epoch
        step when called from the prefetch lane) and appends two array
        futures to the batch dict — no sync anywhere.

        ``overlapped`` is the CALLER's classification: only the prefetch
        lane stages the pass behind an in-flight dispatch; consume-time
        passes count serial. (``_dispatch_inflight`` alone cannot
        classify — the dispatch-only loop never clears it between
        batches in async-snapshot mode, so it would peg the fraction at
        1.0 even with ``overlap_advantage=false``.)"""
        if (
            self.advantage_pass is None
            or batch is None
            or "advantages" in batch
        ):
            return batch
        t0 = time.perf_counter()
        adv, ret = self.advantage_pass(self.state.params, batch)
        batch = dict(batch)
        batch["advantages"] = adv
        batch["returns"] = ret
        dt = time.perf_counter() - t0
        self.telemetry.gauge("advantage/pass_ms").set(dt * 1e3)
        self.telemetry.counter("advantage/passes_total").inc()
        self._util.phase("advantage_pass", dt)
        if self._adv_first:
            # the first call pays the pass's XLA compile — steady-state
            # dispatch is sub-ms, so folding seconds of compile into the
            # serial bucket would flatten overlap_fraction to noise
            self._adv_first = False
        elif overlapped:
            self._adv_overlapped_s += dt
        else:
            self._adv_serial_s += dt
        return batch

    def _actor_params_copy(self):
        """Device-to-device copy of the current params for the actor pool:
        the train step donates the state, so actors must never hold the
        learner's own buffers (they die on the next step)."""
        return jax.tree.map(jnp.copy, self.state.params)

    def _pipeline_state(self) -> Dict[str, Any]:
        """Everything beyond the TrainState a restore needs to resume the
        exact pipeline: buffer ring + cursors, and (device mode) the actor's
        full device state — sim worlds, recurrent carries, PRNG, episode
        accumulators — as flat leaves (checkpoint-format-stable regardless
        of the NamedTuple nesting)."""
        # an in-flight prefetched batch goes back to the ring first: the
        # snapshot must carry every unconsumed rollout
        self._flush_prefetch()
        out: Dict[str, Any] = (
            {"buffer": self.buffer.state_dict()} if self.buffer else {}
        )
        if self.device_actor is not None:
            leaves = jax.tree.leaves(jax.device_get(self.device_actor.state))
            out["actor_leaves"] = {f"{i:04d}": leaf for i, leaf in enumerate(leaves)}
        # minibatch-shuffle RNG position: the stream is seeded, so the count
        # of consumed permutations reconstructs it exactly on restore
        out["mb_draws"] = np.asarray(self._mb_draws, np.int64)
        return out

    def _restore_pipeline(self) -> None:
        restored, reason = self.ckpt.restore_pipeline(self._pipeline_state())
        if restored is None:
            if reason:  # mismatch is loud; a pipeline-less checkpoint is not
                print(
                    f"WARNING: checkpoint pipeline state not restored "
                    f"({reason}); resuming weights-only — in-flight "
                    f"experience and actor state are lost",
                    flush=True,
                )
            return
        if self.buffer is not None and "buffer" in restored:
            self.buffer.load_state_dict(restored["buffer"])
        if self.device_actor is not None and "actor_leaves" in restored:
            from dotaclient_tpu.actor.device_rollout import (
                actor_state_sharding,
            )

            treedef = jax.tree.structure(self.device_actor.state)
            state = jax.tree.unflatten(
                treedef,
                [
                    np.asarray(restored["actor_leaves"][k])
                    for k in sorted(restored["actor_leaves"])
                ],
            )
            # re-commit through THIS mesh's lane sharding (ISSUE 18): the
            # saved host leaves are layout-free, so a checkpoint written at
            # a different device count lands partitioned — not replicated —
            # before the first fused dispatch (the train-state analogue is
            # state_shardings re-commit above / in the rollback path)
            self.device_actor.state = jax.device_put(
                state,
                actor_state_sharding(state, self.mesh, self.config.mesh),
            )
        if "mb_draws" in restored:
            # fast-forward the seeded shuffle stream to its saved position
            self._mb_draws = int(np.asarray(restored["mb_draws"]))
            self._mb_rng = np.random.default_rng(self.config.seed + 1)
            for _ in range(self._mb_draws):
                self._mb_rng.permutation(self.config.ppo.batch_rollouts)

    def _submit_health(self, m) -> None:
        """Queue this batch's verdict scalars with the health monitor —
        a host-side append of three device scalars (program outputs, never
        donated); the boundary flush ships the whole backlog to the
        snapshot engine in ONE batched fetch. In sync-snapshots mode the
        boundary metrics fetch folds the verdicts instead (``fold_host``,
        zero extra transfers); the last batch's verdict leaves are kept
        either way so sync checkpoint boundaries and the end-of-run tail
        can close their coverage gap (``_sync_fold_latest``)."""
        if self._health is None:
            return
        from dotaclient_tpu.train.health import VERDICT_KEYS

        self._last_verdict_m = {k: m[k] for k in VERDICT_KEYS if k in m}
        if self._snap_engine is not None:
            self._health.submit(self._host_step, self._host_version, m)

    def _sync_fold_latest(self) -> None:
        """--sync-snapshots gap-closer: verdicts normally fold from the
        log-boundary metrics fetch, but a checkpoint boundary (or the
        end-of-run forced save) that is NOT a log boundary must not mark a
        state ``last_good`` on stale knowledge — fold the LAST batch's
        verdict scalars first (one tiny fetch at checkpoint/tail cadence;
        sync mode stalls by design)."""
        if (
            self._health is None
            or self._snap_engine is not None
            or self._last_verdict_m is None
        ):
            return
        host = jax.device_get(self._last_verdict_m)  # host-sync-ok: sync-snapshots checkpoint/tail cadence, three scalars
        self._health.fold_host(self._host_step, self._host_version, host)

    def _flush_health(self) -> None:
        """Hand every pending verdict to the snapshot engine's
        never-coalesced stats backlog. The engine processes stats jobs
        BEFORE the same cycle's publish/checkpoint jobs, so a publish
        submitted after this flush can only run once every verdict for
        steps ≤ its version has been folded — the ordering that makes the
        publish gate sound."""
        if self._health is None or self._snap_engine is None:
            return
        pending = self._health.take_pending()
        if pending:
            self._snap_engine.submit_stats(pending, self._health.fold_batch)

    def _maybe_rollback(self) -> int:
        """Recover from a latched divergence: restore the last_good
        checkpoint, abandon the poisoned timeline (its checkpoints, its
        buffered experience, its recurrent actor carries), resume with a
        DISTINCT minibatch-RNG stream, and return how many optimizer steps
        were rewound (0 when healthy) so the caller's step budget covers
        the retraining. Bounded by ``health.max_rollbacks``; exhaustion —
        or a run with no checkpoint manager to restore from — exits loudly
        with the runbook pointer (docs/OPERATIONS.md "Failure modes")."""
        if self._health is None or self._health.unhealthy is None:
            return 0
        ev = self._health.unhealthy
        if self.ckpt is None:
            # contain-only degrade: without a checkpoint dir there is no
            # restore point — publishes stay blocked (actors keep the last
            # good version) and the operator is told once, loudly.
            if self._health.note_unrecoverable():
                print(
                    f"WARNING: training health latched unhealthy "
                    f"({ev.reason} at step {ev.step}) but no "
                    f"--checkpoint-dir is configured — cannot roll back; "
                    f"weight publishes stay BLOCKED (see docs/OPERATIONS.md "
                    f"'Failure modes')",
                    flush=True,
                )
            return 0
        runbook = (
            "see docs/OPERATIONS.md 'Failure modes' (divergence runbook): "
            "inspect the batch data and learning rate, consider "
            "--ppo kl_target/max_grad_norm, and restart from "
            "<checkpoint_dir>/last_good"
        )
        # exhaustion check BEFORE counting: the give-up path performs no
        # restore, so it must not inflate health/rollbacks_total
        if self._rollback_count >= self.config.health.max_rollbacks:
            raise RuntimeError(
                f"training health guardian: divergence persisted after "
                f"{self.config.health.max_rollbacks} rollback(s) "
                f"({ev.reason} at step {ev.step}, value {ev.value!r}) — "
                f"giving up; {runbook}"
            )
        self._rollback_count += 1
        self.telemetry.counter("health/rollbacks_total").inc()
        # Drain the engine FIRST, with the monitor still latched: any
        # pending publish/checkpoint job of the poisoned timeline hits the
        # engine-side health gate and is refused — clearing the latch
        # before the drain would let one slip through.
        self._drain_snapshots()
        published_floor = (
            self._snap_engine.last_published
            if self._snap_engine is not None
            else self._published_version
        )
        restored = self.ckpt.restore_last_good(self.config, self.state)
        if restored is None:
            # no verified slot yet (divergence before the first healthy
            # checkpoint): fall back to the newest manifest-valid main
            # save — every main save was itself health-gated
            try:
                restored = self.ckpt.restore(self.config, self.state)
            except (FileNotFoundError, ValueError, RuntimeError) as e:
                raise RuntimeError(
                    f"training health guardian: divergence at step "
                    f"{ev.step} ({ev.reason}) and no restorable checkpoint "
                    f"to roll back to ({type(e).__name__}: {e}) — {runbook}"
                ) from e
        state, _ = restored
        from_step, from_version = self._host_step, self._host_version
        restored_version = int(np.asarray(state.version))  # host-sync-ok: rollback cadence, host-bound restore
        # The VERSION counter stays monotone across the rollback AND skips
        # past the poisoned range entirely: the restored state resumes at
        # from_version + 1, so every version the poisoned steps produced —
        # (restored_version, from_version] — is never reused on the wire
        # and "no actor ever applied a poisoned version" becomes a
        # checkable set invariant (chaos divergence scenario); the
        # engine's monotonic-publish floor needs no rewind. Steps DO
        # rewind (the retraining re-earns them); step and version diverge
        # from here on, which nothing downstream assumes away.
        resumed_version = from_version + 1
        # re-commit to the mesh (restores return host-layout arrays; the
        # next donated step expects its state_shardings — same re-shard
        # the constructor applies)
        self.state = jax.device_put(
            dataclasses.replace(
                state, version=jnp.asarray(resumed_version, jnp.int32)
            ),
            self.state_shardings,
        )
        self._host_step = int(np.asarray(state.step))      # host-sync-ok: rollback cadence
        self._host_version = resumed_version
        rewound = from_step - self._host_step
        # the abandoned timeline's saves must not be restorable (and the
        # retrained timeline re-reaches their step numbers)
        self.ckpt.discard_steps_above(self._host_step)
        # experience produced by the poisoned policy is dropped (slots
        # tagged with a version inside the poisoned range); the prefetch
        # lane is flushed first so held slots fold back in — and with it
        # die any STAGED ADVANTAGES computed by the poisoned params (they
        # ride the flushed batch dict, never the ring): the retrained
        # timeline's takes re-run the pass with the restored params,
        # pinned by tests/test_advantage.py
        if self.buffer is not None:
            self._flush_prefetch()
            self.buffer.drop_newer_than(restored_version)
        # recurrent carries computed by poisoned params must not leak into
        # the restored run (the sim worlds themselves stay finite)
        if self.device_actor is not None:
            self.device_actor.reset_recurrent()
        elif self.pool is not None and hasattr(self.pool, "set_params"):
            self.pool.set_params(self._actor_params_copy(), self._host_version)
        # distinct RNG resume: the retry must not replay the exact
        # minibatch permutation stream that diverged
        self._mb_rng = np.random.default_rng(
            self.config.seed + 1 + 7919 * self._rollback_count
        )
        self._mb_draws = 0
        # the poisoned batch's verdict scalars must not be re-folded into
        # the cleared monitor by the next sync-mode boundary (fold_host
        # folds with the CURRENT generation — clear() alone doesn't shield)
        self._last_verdict_m = None
        self._health.clear()
        self.telemetry.gauge("health/last_good_step").set(
            float(self._host_step)   # host-sync-ok: host int mirror
        )
        # machine-readable audit line (scripts/chaos_run.py divergence
        # scenario): published_floor ≤ to_version proves no poisoned
        # version ever reached the actor fleet
        print(
            "HEALTH_ROLLBACK " + json.dumps(
                {
                    "reason": ev.reason,
                    "detected_step": ev.step,
                    # the first version the flagged update produced: the
                    # POISONED range is [detected_version, resumed_version)
                    # — versions between the restore point and detection
                    # were produced by verdict-clean steps and may have
                    # been legitimately published before the latch
                    "detected_version": ev.version,
                    "from_step": from_step,
                    "to_step": self._host_step,
                    "restored_version": restored_version,
                    "resumed_version": resumed_version,
                    "published_floor": published_floor,
                    "rollback": self._rollback_count,
                },
                sort_keys=True,
            ),
            flush=True,
        )
        return rewound

    def _push_pool_params(self, params) -> None:
        """In-process weight refresh (``pool.set_params``) behind the same
        health gate as the transport publish paths: a latched-unhealthy
        monitor blocks the push (counted in ``health/publish_blocked_total``)
        so in-proc actors keep serving the last good params too — the
        contain promise must hold whether actors are across a wire or in
        this process. The rollback path pushes restored params directly
        (the monitor is cleared by then)."""
        if self._health is not None:
            if self._snap_engine is None:
                self._sync_fold_latest()
            if self._health.unhealthy is not None:
                self.telemetry.counter("health/publish_blocked_total").inc()
                return
        self.pool.set_params(params, self._host_version)

    def _publish_weights(self) -> None:
        """Hand the current params to the weights fanout (call at refresh
        cadence, not per step). Async (the default): one jitted on-device
        copy into fresh HBM snapshot buffers, then the snapshot thread does
        the device→host fetch, the ``transport.wire_dtype`` cast + encode,
        and the non-blocking fanout enqueue — the train thread never waits
        on the host. Sync (``--sync-snapshots``): everything inline, with
        ONE batched fetch inside :func:`encode_weights`. Either way the
        fanout itself never blocks on a stalled actor (socket_transport.py),
        and a latched-unhealthy monitor blocks the publish entirely — the
        contain stage of the health guardian (ISSUE 6): actors keep
        serving the last good version."""
        t0 = time.perf_counter()
        if self._snap_engine is not None:
            # verdicts for every step ≤ this version reach the engine
            # before the publish job (stats-before-jobs ordering): the
            # engine-side gate sees a current latch, never a stale one
            self._flush_health()
            self._snap_engine.submit_publish(
                self._snap_copy(self.state.params), self._host_version
            )
        else:
            # sync mode folds verdicts at LOG cadence, but the gate below
            # must see the last batch's verdict even when the refresh
            # boundary isn't a log boundary — same gap-closer the sync
            # checkpoint branch uses (a poisoned publish is exactly the
            # fanout this gate exists to stop)
            self._sync_fold_latest()
            if self._health is not None and self._health.unhealthy is not None:
                self.telemetry.counter("health/publish_blocked_total").inc()
            else:
                trace_blob = None
                if self._tracer is not None:
                    rec = tracing.weights_record(self._host_version)
                    trace_blob = tracing.record_to_blob(rec, pad=False)
                    self._tracer.emit(
                        "publish", version=self._host_version
                    )
                with self.telemetry.span("transport/publish_weights"):
                    self.transport.publish_weights(
                        encode_weights(
                            self.state.params,   # one batched fetch inside
                            self._host_version,
                            wire_dtype=self.config.transport.wire_dtype,
                            trace=trace_blob,
                        )
                    )
                self._published_version = max(
                    self._published_version, self._host_version
                )
        stall = time.perf_counter() - t0
        self._stall_s += stall
        self._util.phase("publish_stall", stall)
        self.telemetry.gauge("learner/publish_stall_ms").set(stall * 1e3)

    def _drain_snapshots(self) -> None:
        """Wait out the snapshot thread (graceful stop / end-of-run tail /
        crash rescue): pending publishes reach the wire and pending async
        saves land BEFORE the forced sync checkpoint, so the final save
        still lands at the exact stop step with no writer overlap. Applies
        any best-model save the async metrics path deferred to this
        thread."""
        if self._snap_engine is None:
            return
        if not self._snap_engine.drain(
            timeout=self.config.learner.snapshot_drain_timeout_s
        ):
            print(
                "WARNING: snapshot engine did not drain within "
                f"{self.config.learner.snapshot_drain_timeout_s:.0f}s — "
                "proceeding with the forced sync checkpoint (its error, "
                "if any, will be the loud one)",
                flush=True,
            )
        self._apply_pending_best()

    def _apply_pending_best(self) -> None:
        """Consume the best-model candidate the async metrics continuation
        deferred to this thread (atomic swap — a concurrent write from the
        snapshot thread must never be lost)."""
        with self._pending_best_lock:
            best, self._pending_best = self._pending_best, None
        if best is not None:
            self._maybe_save_best(best)

    def _league_opponent(self):
        """Snapshot-if-due and return the current frozen opponent for the
        device/fused loops → (params | None, snapshot uid). Draws are HELD
        for ``league.opponent_hold`` optimizer steps: episodes span many
        chunks, so holding keeps (most of) each episode against one
        opponent — without it the per-chunk outcome attribution PFSP feeds
        on dilutes toward the pool average. Residual bias: episodes that
        straddle a redraw credit their final opponent."""
        if self.league is None:
            return None, league_pool.LIVE
        self.league.maybe_snapshot(
            self.state.params, self._host_version, self._host_step
        )
        if (
            self._held_opponent is None
            or self._host_step >= self._held_until
        ):
            params, _, uid = self.league.sample_indexed(
                self.state.params, self._host_version
            )
            # LIVE draws are never cached: the buffered path donates the
            # train state every step, so held live params would be dead
            # buffers by the next iteration — re-resolve them per call.
            self._held_opponent = (
                None if uid == league_pool.LIVE else params, uid
            )
            self._held_until = (
                self._host_step + self.config.league.opponent_hold
            )
        params, uid = self._held_opponent
        if uid == league_pool.LIVE and self.fused_step is None:
            params = self.state.params
        # fused mode: a live draw stays None, and the fused program reads
        # the opponent from the state it is handed (the state is donated,
        # so it is never passed a second reference to state.params)
        return params, uid

    def _report_league(self, idx: int, chunk_stats) -> None:
        """Queue one chunk's (device-resident) episode outcomes against the
        snapshot that produced them; resolved in batches at log boundaries
        so the hot loop never syncs."""
        if self.league is None or idx == league_pool.LIVE:
            return
        self._league_pending.append((idx, chunk_stats))
        if len(self._league_pending) >= 64:
            self._flush_league_reports()

    def _flush_league_reports(self) -> None:
        if not self._league_pending:
            return
        pending, self._league_pending = self._league_pending, []
        self.telemetry.counter("league/report_fetches_total").inc()
        fetched = jax.device_get([st for _, st in pending])  # one sync
        for (idx, _), st in zip(pending, fetched):
            # anchor games (scripted-bot opponents) are excluded from the
            # snapshot's PFSP record — it never played them. Chunk stats
            # are per-game partials (ISSUE 18) — fold the game axis here.
            self.league.report(
                idx,
                float(np.sum(st.get("league_wins", st["wins"]))),
                float(np.sum(st.get("league_episodes", st["episodes"]))),
            )

    def _refresh_league_opponent(self) -> None:
        """Snapshot-if-due and re-draw the frozen opponent (host-pool modes;
        the device actor samples per collect instead)."""
        if self.league is None or self.device_actor is not None:
            return
        self.league.maybe_snapshot(
            self.state.params, self._host_version, self._host_step
        )
        params, version = self.league.sample(
            self._actor_params_copy(), self._host_version
        )
        self.pool.set_opponent(params, version)

    def _maybe_save_best(self, scalars: Dict[str, float]) -> None:
        """Best-model rotation: save weights to ``<checkpoint_dir>/best``
        when the windowed win-rate beats the best seen, with the
        ``checkpoint_best_min_episodes`` noise guard (RunConfig comment)."""
        if self._best_dir is None:
            return
        wr = scalars.get("win_rate_recent")
        eps = scalars.get("episodes_recent", 0.0)
        if (
            wr is None
            or eps < self.config.checkpoint_best_min_episodes
            or wr <= self._best_win
        ):
            return
        if self.ckpt_best is None:
            self.ckpt_best = CheckpointManager(self._best_dir, max_to_keep=1)
        # An orbax-declined save (resumed run whose step counter sits below
        # the captured peak's step) must not advance the best marker.
        if self.ckpt_best.save(self.state, self.config):
            self._best_win = wr
            # temp+rename: the orbax save is atomic, the sidecar must be
            # too — a kill mid-write would otherwise reset the marker on
            # resume and let a collapsed window rotate out the peak.
            meta = os.path.join(self._best_dir, "best_meta.json")
            tmp = meta + ".tmp"
            with open(tmp, "w") as f:
                json.dump(
                    {"win_rate_recent": wr, "step": int(self.state.step)}, f
                )
            os.replace(tmp, meta)

    def _make_metrics_finish(
        self,
        step: int,
        host_extra: Dict[str, float],
        stats_source,
    ):
        """Build the host-side continuation of one async log boundary. It
        runs ON the snapshot thread after that thread's one batched fetch
        of the train metrics dict and must never touch ``self.state``
        (in-flight dispatches donate its buffers) — a qualifying best-model
        save is deferred to the train thread via ``_pending_best`` instead.
        ``stats_source`` is a HOST-ONLY callable (the actor's ``stats()``)
        — the actual device stat drain rides the engine's never-coalesced
        ``submit_stats`` backlog, so a coalesced log line can never lose an
        episode window."""
        # captured HERE, on the train thread: _best_win is train-owned
        # (lint/ownership.py) and reading it from the snapshot thread was
        # an unsynchronized race — the submit-time value is also the more
        # honest log field (the save that could move it is itself deferred
        # back to the train thread and lands after this boundary)
        best_win = self._best_win

        def _finish_metrics(host) -> None:
            scalars = {k: float(v) for k, v in host["m"].items()}   # host-sync-ok: snapshot thread, fetched host arrays
            self._fold_core_counters(scalars)
            if stats_source is not None:
                # host-only read: every stat drain submitted up to this
                # boundary was folded by the engine BEFORE this job ran
                # (submit_stats ordering), so the accumulators are current
                scalars.update(stats_source())
            # outcome curves (ISSUE 15): tick AFTER the stat drain above
            # folded this window's episodes into the outcome counters, so
            # the line logged below carries curves consistent with its
            # own counters (tick is lock-guarded — safe on this thread)
            if not self._fleet_started:
                self.outcome.tick()
            scalars.update(host_extra)
            if self._best_dir is not None:
                # the save itself happens on the train thread at the next
                # boundary (or the end-of-run drain) — see _drain_snapshots
                with self._pending_best_lock:
                    self._pending_best = dict(scalars)
                scalars["best_win_rate"] = best_win
            # lint-ok: thread-ownership(handoff, not shared state: train()
            # reads _last_metrics only after the _drain_snapshots barrier
            # has joined every pending engine job)
            self._last_metrics = self.metrics.log(step, scalars)

        return _finish_metrics

    def _fold_core_counters(self, scalars: Dict[str, float]) -> None:
        """A logged step's counts from a core that keeps some, into the registry at the log cadence (docs/ARCHITECTURE.md): ``moe/*`` from ``train/ppo._moe_counters``, ``looplm/*`` from ``exit_weighted_loss``, ``kda/*`` from ``_kda_gauges``, ``shortconv/*`` from ``_shortconv_gauges``, ``diffusion/*`` from ``_diffusion_gauges``, ``sdar/ring_rows_read_share`` from ``staged_loss``; a void read is a lane-layer at position 0 of an episode."""
        tel = self.telemetry
        _fold_diffusion(tel, scalars, self.config.model.diffusion_steps)
        if "moe_local_assignments" in scalars:
            tel.gauge("moe/local_assignments").set(scalars["moe_local_assignments"])
            tel.gauge("moe/max_over_mean_expert_load").set(scalars["moe_max_over_mean_load"])
            tel.counter("moe/dropped_assignments").inc(scalars["moe_dropped_assignments"])
        if "moe_kernel_rows_share" in scalars:
            tel.gauge("moe/kernel_rows_share").set(scalars["moe_kernel_rows_share"])
        if "sdar_ring_rows_read_share" in scalars:
            tel.gauge("sdar/ring_rows_read_share").set(scalars["sdar_ring_rows_read_share"])
        if "looplm_loop_passes" in scalars:
            tel.counter("looplm/loop_passes_total").inc(scalars["looplm_loop_passes"])
            tel.gauge("looplm/expected_exit_step").set(scalars["looplm_expected_exit_step"])
            tel.gauge("looplm/exit_entropy").set(scalars["looplm_exit_entropy"])
            for r in range(self.config.model.loop_steps):
                tel.gauge(f"looplm/exit_mass/{r}").set(scalars[f"looplm_exit_mass_{r}"])
        if "kda_void_reads" in scalars:
            tel.counter("kda/void_reads_total").inc(scalars["kda_void_reads"])
            for key in ("kda/decay_mean", "kda/beta_mean", "kda/state_rms"):
                tel.gauge(key).set(scalars[key.replace("/", "_")])
        if "shortconv_void_reads" in scalars:
            tel.counter("shortconv/void_reads_total").inc(scalars["shortconv_void_reads"])
            for key in ("shortconv/history_rms", "shortconv/gate_mean"):
                tel.gauge(key).set(scalars[key.replace("/", "_")])

    def _publish_pipeline_gauges(self) -> None:
        """Refresh the cross-stage gauges at a log boundary: actor weight
        staleness (host version mirror minus the actor pool's in-use
        version — 0 for the on-policy device/fused paths, which have no
        separate actor copy) and the transport's experience-queue depth.
        Host integers only — no device traffic."""
        pool_version = getattr(self.pool, "version", None)
        self.telemetry.gauge("actor/weight_staleness").set(
            float(self._host_version - pool_version)
            if pool_version is not None
            else 0.0
        )
        pending = getattr(self.transport, "pending_rollouts", None)
        if pending is not None:
            # absent attribute ≠ empty queue: a transport that can't report
            # its backlog must not masquerade as a healthy one
            self.telemetry.gauge("transport/queue_depth").set(float(pending))
        # Prefetch-lane health: hit rate (batches served from the lane /
        # batches served at all) and overlap fraction (prefetch host time
        # spent while a dispatch was in flight / all prefetch host time) —
        # the proof the data path actually pipelines.
        served = self._prefetch_hits + self._prefetch_misses
        if served:
            self.telemetry.gauge("learner/prefetch_hit_rate").set(
                self._prefetch_hits / served
            )
        staged = self._prefetch_overlapped_s + self._prefetch_serial_s
        if staged > 0:
            self.telemetry.gauge("learner/overlap_fraction").set(
                self._prefetch_overlapped_s / staged
            )
        # advantage-plane overlap (ISSUE 14): pass host time staged from
        # the prefetch lane behind an in-flight dispatch / all pass host
        # time (consume-time passes count serial) — the proof the
        # compute stage pipelines, reported next to the byte-staging one
        adv_staged = self._adv_overlapped_s + self._adv_serial_s
        if adv_staged > 0:
            self.telemetry.gauge("advantage/overlap_fraction").set(
                self._adv_overlapped_s / adv_staged
            )
        # device-memory watermark (ISSUE 12): host-only allocator metadata,
        # refreshed at log cadence; CPU backends report none → stays 0
        tracing.update_memory_gauges(self.telemetry)
        # outcome curves (ISSUE 15): in-process modes tick the windowed
        # aggregation at log cadence (host counter arithmetic only);
        # external modes tick from the fleet aggregator thread instead.
        # This tick keeps the tail/log_files_only snapshot fresh; the
        # boundary-cadence ticks that feed the JSONL curves run AFTER the
        # stats drain folds the window's episodes (the async metrics
        # continuation / the sync branch) — ticking only here would lag
        # the device/fused curves one full boundary behind the counters
        # logged on the same line (review finding).
        if not self._fleet_started:
            self.outcome.tick()
        # utilization fold (ISSUE 16): close the accounting window at the
        # same host-sync boundary — host arithmetic only, arms
        # util/duty_cycle and advances the steps/s EMA + the
        # warmup-armed baseline the throughput sentinel compares against
        self._util.fold(self._host_step)

    def train(
        self,
        num_steps: int,
        actor_steps_per_iter: Optional[int] = None,
        overlap: bool = False,
        refresh_every: int = 1,
    ) -> Dict[str, float]:
        """Run until ``num_steps`` optimizer steps have completed.

        ``overlap=False``: strictly alternating actor/learner phases
        (deterministic; the test path). ``overlap=True``: the actor pool runs
        in its own thread feeding the transport while this thread trains —
        the staleness filter and version tags do real work here.
        """
        # One span a call, with `prepare` before the mode loop and `finish`
        # after it; the loop's own `learner/iteration` spans lie between the
        # two, as they were. Opened and closed by hand: a decorator or a
        # second method puts one more Python frame between the entry point
        # and the fused program's trace, every operation's location grows by
        # it, and the wide cell's lowering by 0.25-0.5 s a start (PERF.md,
        # PR 35); a `with` would re-indent the method. An exception leaves
        # the span unrecorded: it ends the run.
        call = self.telemetry.span("learner/train")
        call.__enter__()
        with self.telemetry.span("learner/train/prepare"):
            cfg = self.config
            epochs = self._steps_per_batch
            # host-visible counter stride per loop iteration: fused dispatch
            # batching advances K×epochs steps per call, so the log/checkpoint
            # boundary windows must widen with it or boundaries get stepped over
            stride = epochs * (
                cfg.steps_per_dispatch if self.fused_step is not None else 1
            )
            actor_steps = actor_steps_per_iter or cfg.ppo.rollout_len
            t_start = time.time()
            frames_trained = 0
            steps_done = 0
            self._stall_s = 0.0   # per-call: stall_fraction is per train() call
            # Mid-run weights publish for the device/fused loops (ISSUE 5):
            # they never refresh an in-process pool, so consumers on a real
            # transport (same-host eval actors on the shm lane, socket
            # listeners) would only ever see the end-of-run weights. In-proc
            # transports skip it — nobody is listening.
            publish_midrun = self.device_actor is not None and not isinstance(
                self.transport, InProcTransport
            )

            boundaries = self.telemetry.counter("learner/boundaries_total")

        def after_step(m, frames: Optional[int] = None) -> int:
            """Boundary side effects for one loop iteration. Returns the
            number of optimizer steps a divergence rollback rewound (0 on
            the healthy path) — callers subtract it from their step budget
            so the run still completes to its target step."""
            nonlocal frames_trained
            frames_trained += (
                frames
                if frames is not None
                else cfg.ppo.batch_rollouts * cfg.ppo.rollout_len
            )
            step = self._host_step
            tel = self.telemetry
            at_log = step % cfg.log_every < stride
            # `< stride` (not `== 0`): the counter advances in strides of
            # epochs_per_batch × steps_per_dispatch, which may step over
            # exact multiples.
            at_ckpt = bool(self.ckpt) and step % cfg.checkpoint_every < stride
            if at_log:
                # every host stretch of the log boundary is a span, so a
                # profiler trace says which of them held the device back
                with tel.span("learner/boundary", step=step):
                    boundaries.inc()
                    # ship pending health verdicts ahead of this boundary's
                    # jobs (one batched fetch on the snapshot thread); the
                    # publish branch flushes inside _publish_weights itself
                    with tel.span("learner/boundary/flush_health"):
                        self._flush_health()
                    t0 = time.perf_counter()
                    # a best-model save the async metrics continuation deferred
                    # here: self.state must never be read from the snapshot
                    # thread — in-flight dispatches donate its buffers
                    self._apply_pending_best()
                    host_extra: Dict[str, float] = {}
                    if self.league is not None:
                        with tel.span("learner/boundary/league_fetch"):
                            self._flush_league_reports()
                        wrs = self.league.win_rates()
                        host_extra["league_snapshots"] = float(len(wrs))   # host-sync-ok: host ints
                        if wrs:
                            host_extra["league_winrate_mean"] = float(np.mean(wrs))   # host-sync-ok: host floats
                    if self.buffer is not None:
                        host_extra.update(self.buffer.metrics())
                    elapsed = time.time() - t_start
                    host_extra["frames_per_sec"] = frames_trained / max(elapsed, 1e-9)
                    with tel.span("learner/boundary/gauges"):
                        self._publish_pipeline_gauges()
                    if self._snap_engine is not None:
                        # async (default): the device values leave through the
                        # snapshot thread's batched fetches; this thread only
                        # dispatches the tiny stats copy and keeps training.
                        # The stat drain rides the never-coalesced backlog (its
                        # accumulators were just reset — dropping it would lose
                        # the window); the log job itself is latest-wins.
                        stats_source = None
                        if self.device_actor is not None:
                            with tel.span("learner/boundary/stats_drain"):
                                s_dev, s_fin = self.device_actor.begin_drain()
                                self._snap_engine.submit_stats(s_dev, s_fin)
                            stats_source = self.device_actor.stats
                        elif self.pool is not None:
                            # host pools: windowed stats are host floats already
                            host_extra.update(self.pool.drain_stats())
                        with tel.span("learner/boundary/submit_metrics"):
                            self._snap_engine.submit_metrics(
                                {"m": m},
                                self._make_metrics_finish(
                                    step, host_extra, stats_source
                                ),
                            )
                    else:
                        # sync-snapshots mode: ONE transfer for the whole
                        # metrics dict — the only host↔device sync this loop
                        # performs (spans and gauges above are host values).
                        with tel.span("learner/metrics_fetch"):
                            scalars = {
                                k: float(v) for k, v in jax.device_get(m).items()   # host-sync-ok: log_every boundary (sync-snapshots mode)
                            }
                            if self.device_actor is not None:
                                scalars.update(self.device_actor.drain_stats())
                            elif self.pool is not None:
                                scalars.update(self.pool.drain_stats())
                        self._fold_core_counters(scalars)
                        # the fetch blocked on the dispatched step — overlap
                        # window for prefetch accounting closes here
                        self._dispatch_inflight = False
                        if self._health is not None:
                            # sync-mode health verdicts fold from the boundary
                            # scalars just fetched — zero extra transfers,
                            # detection at log cadence
                            self._health.fold_host(
                                step, self._host_version, scalars
                            )
                        scalars.update(host_extra)
                        self._maybe_save_best(scalars)
                        if self._best_dir is not None:
                            scalars["best_win_rate"] = self._best_win
                        # outcome curves (ISSUE 15): tick after the drain
                        # above folded this window's episodes — same-line
                        # consistency as the async continuation
                        if not self._fleet_started:
                            self.outcome.tick()
                        with tel.span("learner/boundary/log"):
                            self._last_metrics = self.metrics.log(step, scalars)
                    self._stall_s += time.perf_counter() - t0
                    tel.gauge("learner/stall_fraction").set(
                        self._stall_s / max(elapsed, 1e-9)
                    )
            if at_ckpt:
                # periodic saves are weights-only: the pipeline extras cost a
                # full buffer+actor device fetch (tens of MB, a train-loop
                # stall); the forced end-of-run save below captures the
                # complete pipeline
                with tel.span("learner/checkpoint_submit", step=step):
                    if not at_log:
                        # the log boundary above has flushed them already
                        self._flush_health()
                    t0 = time.perf_counter()
                    if self._snap_engine is not None:
                        # one cheap on-device copy of the WHOLE TrainState; the
                        # snapshot thread fetches it (one transfer), health-
                        # gates it (verdicts ≤ this step land first — flushed
                        # above), and writes
                        self._snap_engine.submit_checkpoint(
                            self._snap_copy(self.state), cfg
                        )
                    else:
                        # sync mode: log-boundary folds may not cover THIS
                        # step (checkpoint_every and log_every need not align)
                        # — fold the latest verdict before gating, or a
                        # poisoned state could earn the last_good mark
                        self._sync_fold_latest()
                        if (
                            self._health is not None
                            and self._health.unhealthy is not None
                        ):
                            # contain (sync mode): a poisoned state never
                            # enters the rolling retention
                            tel.counter(
                                "health/checkpoints_blocked_total"
                            ).inc()
                        else:
                            self.ckpt.save(
                                self.state, cfg,
                                mark_good=self._health is not None,
                            )
                    ckpt_dt = time.perf_counter() - t0
                    self._stall_s += ckpt_dt
                    self._util.phase("checkpoint_stall", ckpt_dt)
            if (
                publish_midrun
                and refresh_every
                and step % (refresh_every * stride) < stride
            ):
                self._publish_weights()
            return self._maybe_rollback()

        def _run_mode_loop() -> None:
            """One pass of the mode-specific training loop, until
            ``steps_done`` reaches ``num_steps`` or a stop is
            requested. Factored so the tail's divergence-rollback
            check (ISSUE 6) can re-enter it: a health verdict that
            folds only after the loop hits its target must still be
            able to roll back AND retrain to the exact target step."""
            nonlocal steps_done
            if self.fused_step is not None:
                # Fused mode: rollout + update is ONE program; each dispatch
                # runs steps_per_dispatch iterations of epochs_per_batch
                # optimizer steps (train/fused.py). Train batch = the lane set.
                da = self.device_actor
                k_iters = cfg.steps_per_dispatch
                frames_per = da.n_lanes * cfg.ppo.rollout_len * k_iters
                # The loop's host stretches, named for the profiler (telemetry.Registry.span):
                # one `learner/iteration` per dispatch, its self time the loop work no child names.
                tel = self.telemetry
                passes_ran, passes = tel.counter("diffusion/passes_total"), _diffusion_passes(cfg)
                dispatches = tel.counter("learner/dispatches_total")
                frozen = tel.counter("league/frozen_dispatches_total")
                shared = tel.counter("league/shared_pass_dispatches_total")
                kda_ran, kda_steps = tel.counter("kda/kernel_steps_total"), _kda_kernel_steps(cfg, self.mesh)
                grouped_ran, grouped_calls = tel.counter("moe/grouped_kernel_calls_total"), _grouped_kernel_calls(cfg, self.mesh)
                ring_ran, ring_calls = tel.counter("sdar/ring_kernel_calls_total"), _ring_kernel_calls(cfg, self.mesh, da)
                while steps_done < num_steps and not self._stop_requested:
                    with tel.span("learner/iteration", step=self._host_step):
                        with tel.span("learner/league_draw"):
                            opp_params, opp_idx = self._league_opponent()
                        # opp_params None: self-play, scripted, or a live
                        # league draw (the opponent is the state's own params)
                        t0 = time.perf_counter()
                        with tel.span("learner/dispatch"):
                            self.state, da.state, m, chunk_stats = self.fused_step(
                                self.state, da.state, opp_params
                            )
                        self._util.phase("dispatch_inflight", time.perf_counter() - t0)
                        # the core's passes a rollout step, the commit counted (0 for a one-pass core)
                        passes_ran.inc(passes)
                        dispatches.inc()
                        kda_ran.inc(kda_steps)
                        grouped_ran.inc(grouped_calls)
                        ring_ran.inc(ring_calls)
                        if opp_idx != league_pool.LIVE:
                            frozen.inc()
                        elif self._live_shares_pass:
                            shared.inc()
                        with tel.span("learner/league_report"):
                            self._report_league(opp_idx, chunk_stats)
                        # the program ran `stride` optimizer steps over K chunks —
                        # keep the host mirrors in lockstep with the device counters
                        self._host_step += stride
                        self._host_version += stride
                        da.env_steps += frames_per
                        da.rollouts_shipped += da.n_lanes * k_iters
                        self._submit_health(m)
                        if self._tracer is not None:
                            self._emit_dispatch_traces()
                        steps_done += stride
                        steps_done -= after_step(m, frames=frames_per)
            elif self.device_actor is not None:
                # On-device rollout mode: collect→ingest→train is all dispatch
                # (the device serializes rollout and train programs back-to-back,
                # so a host thread would add nothing; `overlap` is a no-op here).
                # The prefetch lane still earns its keep: batch N+1's gather is
                # issued behind batch N's epoch step, so the host-side take/
                # bookkeeping cost never sits between two dispatches.
                da = self.device_actor
                while steps_done < num_steps and not self._stop_requested:
                    opp_params, opp_idx = self._league_opponent()
                    chunk, chunk_stats = da.collect(
                        self.state.params, opp_params=opp_params
                    )
                    self._report_league(opp_idx, chunk_stats)
                    self.buffer.add_device(chunk, self._host_version)
                    while (
                        batch := self._next_batch(drain_transport=False)
                    ) is not None:
                        m = self._optimize(batch)
                        if steps_done + epochs < num_steps:
                            # there is a next step to feed; a batch staged
                            # behind the FINAL dispatch could never be consumed
                            # and would only be requeued at the flush below
                            self._prefetch_next(drain_transport=False)
                        steps_done += epochs
                        steps_done -= after_step(m)
                        if steps_done >= num_steps or self._stop_requested:
                            break
            elif self.actor_mode == "external":
                # Experience arrives from standalone actor processes over the
                # transport; this loop only trains and publishes weights. The
                # transport drain + host-row staging + scatter + gather for
                # batch N+1 run behind batch N's dispatched step (prefetch).
                self._publish_weights()
                while steps_done < num_steps and not self._stop_requested:
                    batch = self._next_batch()
                    if batch is None:
                        time.sleep(0.005)
                        self._util.phase("ingest_wait", 0.005)
                        continue
                    m = self._optimize(batch)
                    if steps_done + epochs < num_steps:   # see device loop
                        self._prefetch_next()
                    steps_done += epochs
                    steps_done -= after_step(m)
                    if refresh_every and (steps_done // epochs) % refresh_every == 0:
                        self._publish_weights()
            elif overlap:
                stop = threading.Event()
                actor_error: List[BaseException] = []

                def actor_loop() -> None:
                    try:
                        while not stop.is_set():
                            self.pool.step()
                    except BaseException as e:  # surface, never swallow
                        actor_error.append(e)

                self.pool.set_params(self._actor_params_copy(), self._host_version)
                actor_thread = threading.Thread(
                    target=actor_loop, name="actor", daemon=True
                )
                actor_thread.start()
                try:
                    while steps_done < num_steps and not self._stop_requested:
                        if actor_error:
                            raise RuntimeError(
                                "actor thread died; learner cannot make progress"
                            ) from actor_error[0]
                        batch = self._next_batch()
                        if batch is None:
                            time.sleep(0.002)
                            self._util.phase("ingest_wait", 0.002)
                            continue
                        m = self._optimize(batch)
                        if steps_done + epochs < num_steps:   # see device loop
                            self._prefetch_next()
                        steps_done += epochs
                        steps_done -= after_step(m)
                        if refresh_every and (steps_done // epochs) % refresh_every == 0:
                            self._push_pool_params(self._actor_params_copy())
                            self._refresh_league_opponent()
                finally:
                    stop.set()
                    actor_thread.join(timeout=30.0)
            else:
                while steps_done < num_steps and not self._stop_requested:
                    # Actor phase: generate experience with the current weights.
                    self._push_pool_params(self.state.params)
                    self._refresh_league_opponent()
                    self.pool.run(actor_steps, refresh_every=0)
                    self.ingest()
                    # Learner phase: drain full batches; each iteration stages
                    # the next batch behind the in-flight dispatch.
                    while (batch := self._next_batch()) is not None:
                        m = self._optimize(batch)
                        if steps_done + epochs < num_steps:   # see device loop
                            self._prefetch_next()
                        steps_done += epochs
                        steps_done -= after_step(m)
                        if steps_done >= num_steps or self._stop_requested:
                            break
        _run_mode_loop()
        # drains, the closing publish of all weights, the forced save
        with self.telemetry.span("learner/train/finish"):
            while True:
                # End-of-call prefetch flush: a batch staged behind the final
                # dispatch was never trained on — return it to the ring so the
                # final checkpoint (and the next train() call) see it.
                if self.buffer is not None:
                    self._flush_prefetch()
                self._dispatch_inflight = False
                # Async boundary jobs still in flight must land before the tail
                # reads/mutates the shared stats below (and any deferred
                # best-model save applies); the snapshot thread is idle
                # afterwards. Pending health verdicts flush first so the
                # tail's publish/save gates see the final steps' verdicts.
                self._flush_health()
                self._drain_snapshots()
                # Tail rollback check (ISSUE 6): on a fast run the engine can
                # fold the poisoned verdict only AFTER the loop hit its step
                # target — containment already held (the gates were latched
                # before anything left the learner), but the run must not be
                # SEALED on poisoned params: roll back and re-enter the loop
                # so it still completes to the exact target step. Bounded by
                # health.max_rollbacks like every rollback.
                rewound = self._maybe_rollback()
                if not rewound or self._stop_requested:
                    break
                steps_done -= rewound
                _run_mode_loop()
            if self.device_actor is not None:
                # End-of-call drain: the windowed stats cover this train() call
                # (the demo's block cadence) — the second best-model hook, so
                # peak capture works even when log_every never fires mid-call.
                self._maybe_save_best(self.device_actor.drain_stats())
            elif self.pool is not None:
                self._maybe_save_best(self.pool.drain_stats())
            if self.league is not None:
                self._flush_league_reports()
            # Publish final weights for out-of-process actors (cluster parity);
            # drain so they reach the wire before the caller closes transports.
            self._publish_weights()
            self._drain_snapshots()
            if self.ckpt:
                # The forced end-of-run/drain save stays SYNC (the snapshot
                # thread is drained and idle): it lands at the EXACT stop step
                # and an I/O failure here raises loudly (ISSUE 4 policy). It is
                # NEVER health-blocked — exact-step resume outranks hygiene —
                # but only a verdict-clean state earns the last_good mark (a
                # divergence detected in the final steps restores through the
                # guardian on the next --restore instead). Sync mode folds the
                # final batch's verdict first — its last log boundary may
                # predate the final steps.
                self._sync_fold_latest()
                self.ckpt.save(
                    self.state, cfg, force=True,
                    pipeline=self._pipeline_state(),
                    mark_good=(
                        self._health is not None
                        and self._health.unhealthy is None
                    ),
                )
                self.ckpt.wait()
        elapsed = time.time() - t_start
        actor_stats = self.pool.stats() if self.pool is not None else {}
        out = {
            **self._last_metrics,
            **{f"actor_{k}": v for k, v in actor_stats.items()},
            # Fresh end-of-run figures last so they win over logged snapshots.
            "optimizer_steps": float(steps_done),     # host-sync-ok: host ints
            "frames_trained": float(frames_trained),  # host-sync-ok: host ints
            "frames_per_sec": frames_trained / max(elapsed, 1e-9),
            "elapsed_sec": elapsed,
        }
        self._publish_pipeline_gauges()
        # Close the machine-readable record with a final full snapshot (the
        # end-of-run publish/checkpoint spans and `learner/train/finish` land
        # here; `learner/train` itself closes after it); console is spared.
        self.metrics.log_files_only(self._host_step, out)
        call.__exit__(None, None, None)
        return out


def main(argv=None) -> Dict[str, float]:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--smoke", action="store_true", help="tiny fast config")
    p.add_argument("--logdir", type=str, default=None)
    p.add_argument(
        "--metrics-jsonl", type=str, default=None, metavar="PATH",
        help="append every log-boundary metrics snapshot (training scalars "
        "+ pipeline telemetry: per-stage spans, queue depth, staleness, "
        "buffer occupancy) as JSON lines to PATH — the headless/bench "
        "record; schema in docs/ARCHITECTURE.md 'Observability'",
    )
    p.add_argument("--checkpoint-dir", type=str, default=None)
    p.add_argument(
        "--checkpoint-every", type=int, default=None,
        help="optimizer steps between periodic checkpoints (default "
        "RunConfig.checkpoint_every); the chaos divergence scenario "
        "tightens this so a last_good restore point exists early",
    )
    p.add_argument("--restore", action="store_true")
    p.add_argument("--init-from", type=str, default=None, metavar="DIR",
                   help="seed a fresh run with the params of the latest "
                   "checkpoint in DIR (source stays untouched; mutually "
                   "exclusive with --restore)")
    p.add_argument("--n-envs", type=int, default=None)
    p.add_argument("--opponent", type=str, default=None)
    p.add_argument("--team-size", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--core", type=str, default=None,
                   choices=("lstm", "transformer"),
                   help="policy core: scanned LSTM(128) (reference parity, "
                   "default) or the GTrXL-gated windowed-attention "
                   "transformer (scale-out option)")
    p.add_argument("--moe-experts", type=int, default=None,
                   help="with --core transformer: experts per MoE FFN "
                   "layer (0 = dense FFN)")
    p.add_argument(
        "--ppo", type=str, default=None, metavar="K=V,...",
        help="comma-separated PPOConfig overrides, e.g. "
        "'learning_rate=1e-4,entropy_coef=0.001,anchor_kl_coef=0.05'",
    )
    p.add_argument(
        "--reward", type=str, default=None, metavar="K=V,...",
        help="comma-separated RewardConfig overrides, e.g. "
        "'win=25,tower_damage=20'",
    )
    p.add_argument(
        "--league", type=str, default=None, metavar="K=V,...",
        help="comma-separated LeagueConfig overrides (with --opponent "
        "league), e.g. 'anchor_prob=0.25,snapshot_every=200'",
    )
    p.add_argument(
        "--buffer", type=str, default=None, metavar="K=V,...",
        help="comma-separated BufferConfig overrides, e.g. "
        "'capacity_rollouts=64,min_fill=8'",
    )
    p.add_argument(
        "--health", type=str, default=None, metavar="K=V,...",
        help="comma-separated HealthConfig overrides (training health "
        "guardian, ISSUE 6), e.g. 'explosion_band=50,max_rollbacks=2' or "
        "'enabled=false'",
    )
    p.add_argument(
        "--learner", type=str, default=None, metavar="K=V,...",
        help="comma-separated LearnerConfig overrides (snapshot-engine "
        "knobs, ISSUE 5), e.g. 'snapshot_drain_timeout_s=120' or "
        "'async_snapshots=false' (the long form of --sync-snapshots)",
    )
    p.add_argument(
        "--mesh", type=str, default=None, metavar="K=V,...",
        help="comma-separated MeshConfig overrides (device-mesh layout, "
        "ISSUE 10), e.g. 'data_parallel=4,model_parallel=2' or "
        "'dcn_slices=2'; data_parallel=-1 (default) takes every remaining "
        "device. --model-parallel/--dcn-slices are shorthands for the "
        "same fields; an explicit layout smaller than the visible device "
        "set uses the first dcn×data×model devices (a 1-device mesh is "
        "the degenerate case of the one sharded code path)",
    )
    p.add_argument(
        "--serve", type=str, default=None, metavar="K=V,...",
        help="comma-separated ServeConfig overrides (policy-serving "
        "plane, ISSUE 11), e.g. 'batch_window_ms=4,max_batch=128'. The "
        "learner itself never serves — the knobs ride the config tree "
        "into checkpoints, so a serve server restored from this run "
        "(`python -m dotaclient_tpu.serve --checkpoint DIR`) starts with "
        "them; its own --serve flag overrides at serve time",
    )
    p.add_argument(
        "--sync-snapshots", action="store_true",
        help="debug opt-out of the async snapshot engine (ISSUE 5): run "
        "the weights publish, periodic checkpoints, and log-boundary "
        "metrics fetch inline on the train thread (stalling it) instead "
        "of on the background snapshot thread",
    )
    p.add_argument(
        "--on-crash-checkpoint", action="store_true",
        help="on an unexpected exception, attempt a best-effort weights-"
        "only checkpoint before re-raising (needs --checkpoint-dir); the "
        "graceful path — SIGTERM/SIGINT — always drains and saves the full "
        "pipeline regardless of this flag",
    )
    p.add_argument(
        "--steps-per-dispatch", type=int, default=None,
        help="with --actor fused: scan this many rollout+update iterations "
        "inside the one compiled program per host dispatch (amortizes the "
        "host-device round trip; host-side cadences coarsen to this stride)",
    )
    p.add_argument(
        "--overlap", action="store_true",
        help="run the actor pool in a background thread (async actor-learner)",
    )
    p.add_argument(
        "--no-vec", action="store_true",
        help="use the scalar (proto/gRPC-parity) actor pool instead of the "
        "vectorized sim",
    )
    p.add_argument(
        "--actor", type=str, default=None,
        choices=("device", "fused", "vec", "scalar", "external"),
        help="actor implementation: on-device rollout scan (default), "
        "fused single-program rollout+update (fastest synchronous path), "
        "numpy vectorized sim, scalar proto pool, or external "
        "(standalone `python -m dotaclient_tpu.actor` processes)",
    )
    p.add_argument(
        "--transport", type=str, default="inproc",
        choices=("inproc", "socket", "shm", "amqp"),
        help="experience/weights transport; socket listens for actor "
        "processes, shm serves same-host actors over shared memory "
        "(zero syscalls/copies on the wire), amqp targets a RabbitMQ broker",
    )
    p.add_argument(
        "--listen", type=str, default="127.0.0.1:7777",
        help="host:port for --transport socket",
    )
    p.add_argument(
        "--shm-name", type=str, default=None,
        help="shared-memory lane name for --transport shm (default "
        "tpu-dota-<pid>; actors connect with --connect shm://NAME)",
    )
    p.add_argument(
        "--wire-dtype", type=str, default=None,
        choices=("float32", "bfloat16"),
        help="weights fanout wire dtype (overrides transport.wire_dtype); "
        "bfloat16 halves fanout bytes, actors upcast on apply",
    )
    p.add_argument(
        "--rollout-wire-dtype", type=str, default=None,
        choices=("float32", "bfloat16"),
        help="rollout payload wire dtype (overrides "
        "transport.rollout_wire_dtype); bfloat16 roughly halves experience "
        "wire bytes AND the resident trajectory-ring bytes (the ring "
        "stores the narrow dtypes; the upcast to f32 runs on-device at "
        "consume). Precision-critical leaves (behavior_logp, rewards, "
        "dones, carries) stay f32 on the wire. Set the SAME value on "
        "actors (docs/OPERATIONS.md)",
    )
    p.add_argument(
        "--amqp-host", type=str, default="localhost",
        help="broker address for --transport amqp",
    )
    p.add_argument(
        "--refresh-every", type=int, default=10,
        help="publish weights to actors every N optimizer steps",
    )
    p.add_argument(
        "--profile", "--profile-dir", dest="profile", type=str, default=None,
        metavar="DIR",
        help="capture a jax.profiler device trace of the run to DIR "
        "(utils/profiling.trace; view with tensorboard + "
        "tensorboard-plugin-profile). --profile-dir is the long spelling",
    )
    p.add_argument(
        "--trace-jsonl", type=str, default=None, metavar="PATH",
        help="pipeline tracing (ISSUE 12): append sampled lifecycle "
        "events (chunk hop timelines, publish/apply, per-compile cost "
        "analysis, dispatches) as JSON lines to PATH; merge a "
        "learner+actors run's logs with scripts/trace_report.py. Off by "
        "default — the hot paths then pay one pointer test",
    )
    p.add_argument(
        "--trace-sample", type=int, default=None, metavar="N",
        help="with --trace-jsonl: trace every Nth sampling decision "
        "(default telemetry.trace_sample_n = 16; 1 = every chunk, the "
        "chaos-harness setting)",
    )
    p.add_argument(
        "--fleet-interval", type=float, default=None, metavar="S",
        help="fleet health plane (ISSUE 13): aggregate actor/serve metric "
        "snapshots and evaluate the alert rules every S seconds (default "
        "telemetry.fleet_interval_s = 5; 0 disables the fanout — the "
        "fleet/ and alerts/ keys stay eager-created). External-transport "
        "modes only; read the merged table with scripts/fleet_status.py",
    )
    p.add_argument(
        "--checkify", action="store_true",
        help="debug numerics: checkify-instrumented train step that raises "
        "on the first NaN/Inf (slow; never for production runs)",
    )
    p.add_argument(
        "--multihost", action="store_true",
        help="join the job-wide JAX distributed runtime before any device "
        "op (TPU pods/GKE auto-detect coordinator); required on every host "
        "of a multi-host or multi-slice (--dcn-slices > 1) job",
    )
    p.add_argument("--dcn-slices", type=int, default=None,
                   help="ICI-connected slices bridged over DCN (mesh axis)")
    p.add_argument("--model-parallel", type=int, default=None,
                   help="tensor-parallel width (model mesh axis)")
    args = p.parse_args(argv)
    if args.transport != "inproc" and args.actor is None:
        args.actor = "external"

    if args.multihost:
        # must precede every jax op in this process
        from dotaclient_tpu.parallel import initialize_runtime, process_info

        initialize_runtime()
        print(f"learner: distributed runtime up: {process_info()}", flush=True)
    # persistent compile cache (JAX_COMPILATION_CACHE_DIR places it): the
    # programs compile once per machine instead of once per process
    compile_cache.enable()

    config = default_config()
    model_over = {}
    if args.core is not None:
        model_over["core"] = args.core
    if args.moe_experts is not None:
        model_over["moe_experts"] = args.moe_experts
    if model_over:
        config = dataclasses.replace(
            config, model=dataclasses.replace(config.model, **model_over)
        )
    mesh_over = {}
    if args.dcn_slices is not None:
        mesh_over["dcn_slices"] = args.dcn_slices
    if args.model_parallel is not None:
        mesh_over["model_parallel"] = args.model_parallel
    if mesh_over:
        config = dataclasses.replace(
            config, mesh=dataclasses.replace(config.mesh, **mesh_over)
        )
    if args.smoke:
        config = dataclasses.replace(
            config,
            env=dataclasses.replace(config.env, n_envs=4, max_dota_time=60.0),
            ppo=dataclasses.replace(
                config.ppo, rollout_len=8, batch_rollouts=8
            ),
            buffer=dataclasses.replace(
                config.buffer, capacity_rollouts=32, min_fill=8
            ),
            log_every=1,
        )
        args.steps = min(args.steps, 5)
    if args.steps_per_dispatch is not None:
        config = dataclasses.replace(
            config, steps_per_dispatch=args.steps_per_dispatch
        )
    if args.checkpoint_every is not None:
        config = dataclasses.replace(
            config, checkpoint_every=args.checkpoint_every
        )
    from dotaclient_tpu.config import (
        BufferConfig,
        HealthConfig,
        LeagueConfig,
        LearnerConfig,
        MeshConfig,
        PPOConfig,
        RewardConfig,
        ServeConfig,
    )
    from dotaclient_tpu.utils.overrides import parse_dataclass_overrides

    if args.league and args.opponent != "league":
        p.error("--league overrides need --opponent league")
    parsed: Dict[str, dict] = {}
    for flag, text, sub, cls in (
        ("--ppo", args.ppo, "ppo", PPOConfig),
        ("--reward", args.reward, "reward", RewardConfig),
        ("--league", args.league, "league", LeagueConfig),
        ("--buffer", args.buffer, "buffer", BufferConfig),
        ("--health", args.health, "health", HealthConfig),
        ("--learner", args.learner, "learner", LearnerConfig),
        # serving-plane knobs checkpoint with the run (a serve server
        # restored from this checkpoint starts with them)
        ("--serve", args.serve, "serve", ServeConfig),
        # --mesh composes with the --dcn-slices/--model-parallel
        # shorthands (applied above); explicit --mesh keys win
        ("--mesh", args.mesh, "mesh", MeshConfig),
    ):
        if not text:
            continue
        try:
            parsed[sub] = parse_dataclass_overrides(cls, text, flag)
        except ValueError as e:
            p.error(str(e))
    if args.opponent == "league":
        # same glue as the demo: a league run DEFAULTS to a live league
        # config (so the enabled-gated validations apply and the
        # checkpointed config says what ran); an explicit enabled=false
        # override is respected
        parsed.setdefault("league", {}).setdefault("enabled", True)
    for sub, over in parsed.items():
        config = dataclasses.replace(
            config, **{sub: dataclasses.replace(getattr(config, sub), **over)}
        )
    env_over = {}
    if args.n_envs is not None:
        env_over["n_envs"] = args.n_envs
    if args.opponent is not None:
        env_over["opponent"] = args.opponent
    if args.team_size is not None:
        env_over["team_size"] = args.team_size
    if env_over:
        config = dataclasses.replace(
            config, env=dataclasses.replace(config.env, **env_over)
        )

    if args.wire_dtype is not None:
        config = dataclasses.replace(
            config, transport=dataclasses.replace(
                config.transport, wire_dtype=args.wire_dtype
            )
        )
    if args.rollout_wire_dtype is not None:
        config = dataclasses.replace(
            config, transport=dataclasses.replace(
                config.transport,
                rollout_wire_dtype=args.rollout_wire_dtype,
            )
        )
    if args.sync_snapshots:
        config = dataclasses.replace(
            config, learner=dataclasses.replace(
                config.learner, async_snapshots=False
            )
        )

    # tracer BEFORE any pipeline object: pools/buffers/learner capture
    # tracing.get() at construction (the faults.get() discipline)
    if args.trace_jsonl:
        tracing.configure(args.trace_jsonl, sample_n=args.trace_sample)
    if args.fleet_interval is not None:
        # before the Learner exists: its FleetAggregator reads the knob
        # at construction (telemetry.fleet_interval_s is the one source)
        telemetry.fleet_interval_s = args.fleet_interval

    transport = None
    if args.transport == "socket":
        from dotaclient_tpu.transport.socket_transport import TransportServer

        host, port = args.listen.rsplit(":", 1)
        transport = TransportServer(
            host, int(port),
            fanout_max_lag=config.transport.fanout_max_lag,
            poison_frame_limit=config.transport.poison_frame_limit,
            heartbeat_interval_s=config.transport.heartbeat_interval_s,
            idle_timeout_s=config.transport.idle_timeout_s,
        )
        print(f"learner: listening for actors on {transport.address}", flush=True)
    elif args.transport == "shm":
        from dotaclient_tpu.transport.shm_transport import ShmTransportServer

        transport = ShmTransportServer(
            name=args.shm_name,
            slots=config.transport.shm_slots,
            ring_bytes=config.transport.shm_ring_bytes,
            weights_bytes=config.transport.shm_weights_bytes,
            poison_frame_limit=config.transport.poison_frame_limit,
        )
        print(
            f"learner: shm lane {transport.address!r} "
            f"({transport.slots} actor slots; actors: "
            f"--connect shm://{transport.address})",
            flush=True,
        )
    elif args.transport == "amqp":
        from dotaclient_tpu.transport.queues import AmqpTransport

        host, _, port = args.amqp_host.partition(":")
        transport = AmqpTransport(host, int(port or 5672))

    learner = Learner(
        config,
        transport=transport,
        logdir=args.logdir,
        checkpoint_dir=args.checkpoint_dir,
        restore=args.restore,
        init_from=args.init_from,
        seed=args.seed,
        actor=args.actor or ("scalar" if args.no_vec else "device"),
        debug_checkify=args.checkify,
        metrics_jsonl=args.metrics_jsonl,
    )
    from dotaclient_tpu.utils.profiling import trace

    # Graceful stop (ISSUE 4): the FIRST SIGTERM/SIGINT converts to a drain
    # — the train loop exits at its next step boundary and the end-of-run
    # tail requeues held batches, takes the full-pipeline checkpoint, and
    # closes transports (the finally below). A SECOND signal forces exit:
    # the handler restores the default disposition and re-raises it, so a
    # wedged drain can still be killed with the same signal.
    import signal as _signal

    def _graceful(signum, frame):
        learner.request_stop()
        name = _signal.Signals(signum).name
        print(
            f"learner: {name} received — draining (checkpoint + clean "
            f"shutdown); send {name} again to force exit",
            flush=True,
        )
        _signal.signal(signum, _signal.SIG_DFL)

    previous_handlers = {}
    try:
        for _sig in (_signal.SIGTERM, _signal.SIGINT):
            previous_handlers[_sig] = _signal.signal(_sig, _graceful)
    except ValueError:
        pass  # not the main thread (embedded use): signals stay external

    try:
        with trace(args.profile):
            stats = learner.train(
                args.steps, overlap=args.overlap,
                refresh_every=args.refresh_every,
            )
    except BaseException as e:
        if (
            args.on_crash_checkpoint
            and not isinstance(e, (KeyboardInterrupt, SystemExit))
            and learner.ckpt is not None
        ):
            # Best-effort weights-only save: the state may be mid-donation
            # or the disk may be the very thing that failed — never let the
            # rescue attempt mask the original exception. The crash save is
            # SYNC by contract (ISSUE 5): drain the snapshot thread first so
            # a pending async write can't race the rescue write.
            try:
                learner._drain_snapshots()
            except Exception:  # noqa: BLE001 - rescue path, keep going
                pass
            try:
                # force=True: failures raise instead of degrading to the
                # periodic-save counter — success must not be claimed below
                # when the disk is the very thing that broke
                saved = learner.ckpt.save(
                    learner.state, learner.config, force=True
                )
                learner.ckpt.wait()
                print(
                    f"learner: crash checkpoint "
                    f"{'saved to ' + learner.ckpt.directory if saved else 'declined (step already checkpointed)'}"
                    f" before re-raising",
                    flush=True,
                )
            except Exception as save_err:  # noqa: BLE001 - reported, not masked
                print(
                    f"learner: crash checkpoint failed too "
                    f"({type(save_err).__name__}: {save_err})",
                    flush=True,
                )
        raise
    finally:
        # an in-process caller (chip_smoke.py, a notebook) gets its own
        # handlers back instead of ones bound to this finished learner
        for _sig, _prev in previous_handlers.items():
            if _prev is not None:   # None: installed from C, not restorable
                _signal.signal(_sig, _prev)
        if args.trace_jsonl:
            # drain + fsync the trace log (clean exits; a SIGKILL relies
            # on the writer thread's per-batch flush and the torn-line-
            # tolerant reader)
            tracing.shutdown()
        # the fleet aggregator thread outlives train() by design (the
        # tail still merges late snapshots); main is its owner
        learner.fleet.stop()
        if transport is not None and hasattr(transport, "close"):
            # deterministic teardown even when train() raises: the shm
            # server unlinks its segments (the resource tracker would
            # otherwise warn "leaked" at exit), the socket server closes
            # its listener and connections
            transport.close()
    print(
        f"done: {stats['optimizer_steps']:.0f} steps, "
        f"{stats['frames_trained']:.0f} frames, "
        f"{stats['frames_per_sec']:.0f} frames/sec",
        flush=True,
    )
    return stats


def _kda_kernel_steps(config: RunConfig, mesh) -> int:
    """Delta-rule (KDA) layer-steps that ONE fused dispatch's rollout runs as
    the Pallas kernel (``ops/pallas/kda_step.py``): layers x rollout steps
    where the core's own predicate, the one its model traces by, takes the
    kernel on the platform the program is lowered for; 0 where the closed
    form runs (a CPU, toy widths) and for a core without such layers.
    ``kda/kernel_steps_total`` over ``learner/dispatches_total`` x layers x
    steps is the share of dispatches in which the kernel was engaged."""
    from dotaclient_tpu.models.policy import resident_core

    model = config.model
    core = resident_core(model) if model.carry_stays_on_chip else None
    if not hasattr(core, "step_takes_kernel") or not core.step_takes_kernel(model, mesh.devices.flat[0].platform):
        return 0
    return len(core.kda_layers(model)) * config.ppo.rollout_len * config.steps_per_dispatch


def _grouped_kernel_calls(config: RunConfig, mesh) -> int:
    """Routed-layer passes ONE fused dispatch makes through the grouped-matmul
    kernels (``ops/pallas/grouped_matmul.py``): routed layers x (the core's
    passes a rollout step x rollout steps + the update's optimizer steps),
    times iterations, where ``afmoe.grouped_takes_kernel`` holds on the
    platform the program is lowered for; 0 where ``ragged_dot`` runs (a CPU,
    toy widths) and for a core without routed layers. A pass over both teams'
    rows counts once, as in ``diffusion/passes_total``."""
    from dotaclient_tpu.models import afmoe
    from dotaclient_tpu.models.policy import resident_core

    model = config.model
    if not (model.carry_stays_on_chip and model.moe_experts) or not afmoe.grouped_takes_kernel(model, mesh.devices.flat[0].platform):
        return 0
    routed = sum(not afmoe.layer_is_dense(model, layer) for layer in range(model.n_layers))
    passes = resident_core(model).rollout_passes(model) if model.diffusion_steps else 1
    return routed * (passes * config.ppo.rollout_len + config.ppo.steps_per_batch) * config.steps_per_dispatch


def _ring_kernel_calls(config: RunConfig, mesh, actor) -> int:
    """Calls of the ring kernel (``ops/pallas/ring_attend.py``) ONE fused
    dispatch's rollout makes: a call a layer, rollout pass and lane set (the
    learner's and, where the opponent is a policy, the opponent's; one pass
    over both teams' rows is a call a set as two passes are), but for the
    commit's last layer, whose output nothing reads, times steps and
    iterations; 0 where ``sdar.pass_takes_kernel`` does not hold on the
    platform the program is lowered for (a CPU, toy widths) and for every
    other core. ``sdar/ring_kernel_calls_total`` over
    ``learner/dispatches_total`` says whether the kernel was engaged."""
    from dotaclient_tpu.models.policy import resident_core

    model = config.model
    core = resident_core(model) if model.carry_stays_on_chip else None
    if not hasattr(core, "pass_takes_kernel") or not core.pass_takes_kernel(model, core.ROWS, mesh.devices.flat[0].platform):
        return 0
    lane_sets = 1 + bool(actor.opponent_players)
    layer_passes = model.n_layers * core.rollout_passes(model) - 1
    return layer_passes * lane_sets * config.ppo.rollout_len * config.steps_per_dispatch


def _diffusion_passes(config: RunConfig) -> int:
    """Core passes ONE fused dispatch's rollout runs where the core decodes an
    action over several (``models/sdar.py``): S denoising passes and the
    commit a rollout step (a pass over both teams' rows counts once), times
    steps and iterations; 0 for every other core. ``diffusion/passes_total``
    over ``learner/dispatches_total`` x steps is the passes a step."""
    from dotaclient_tpu.models.policy import resident_core

    model = config.model
    if not model.diffusion_steps:
        return 0
    return resident_core(model).rollout_passes(model) * config.ppo.rollout_len * config.steps_per_dispatch


def _fold_diffusion(tel, scalars: Dict[str, float], steps: int) -> None:
    """A logged step's block counts (``train/ppo._diffusion_gauges``): the
    chunk's committed tokens and NONE slots, and each pass's mean entropy of
    the heads it committed."""
    if "diffusion_none_slots" not in scalars:
        return
    tel.counter("diffusion/tokens_committed_total").inc(scalars["diffusion_tokens_committed"])
    tel.counter("diffusion/none_slots_total").inc(scalars["diffusion_none_slots"])
    for s in range(1, steps + 1):
        tel.gauge(f"diffusion/stage_entropy/{s}").set(scalars[f"diffusion_stage_entropy_{s}"])


if __name__ == "__main__":
    main()
