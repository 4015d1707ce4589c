"""Sharded HBM-resident trajectory ring buffer.

The reference's learner blocked on RabbitMQ and stacked rollouts in host
memory each step (SURVEY.md §3.2). The TPU-native design keeps the trajectory
store *on device*, batch-sharded over the mesh's data axis — the north-star
architecture of BASELINE.json:5 — so a train step consumes its batch without
any host↔device copy beyond the initial staged ingest (SURVEY.md §7 step 5).

Shape contract: one slot holds one rollout chunk laid out exactly like a
``train.ppo.Batch`` row (obs ``[T+1, ...]``, actions/rewards/... ``[T]``,
``carry0`` ``([H],[H])``); a consumed batch of B slots IS a train batch.

Concurrency: host-side bookkeeping (cursor, versions) is plain Python driven
by the single learner thread; actors never touch the buffer — they hand
protos to the transport, and the learner's ingest drains it (same
single-writer discipline the reference gets from its one blocking consumer).
"""

from __future__ import annotations

import logging
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import ml_dtypes
import numpy as np
from jax.sharding import Mesh

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.train.ppo import example_batch
from dotaclient_tpu.utils import telemetry, tracing

logger = logging.getLogger(__name__)


def _pow2ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (max(1, n) - 1).bit_length()


# the narrow wire dtype the admission scan must treat as float
_WIRE_BF16 = np.dtype(ml_dtypes.bfloat16)


class TrajectoryBuffer:
    """FIFO ring of rollout chunks in device memory.

    PPO is (nearly) on-policy: rollouts are consumed oldest-first, exactly
    once, with version-based staleness filtering at ingest (SURVEY.md §2.3
    "Async off-policy DP").
    """

    def __init__(
        self,
        config: RunConfig,
        mesh: Mesh,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        self.config = config
        self.mesh = mesh
        self._tel = registry if registry is not None else telemetry.get_registry()
        from dotaclient_tpu.parallel.mesh import (
            batch_axes,
            batch_shard_count,
            data_sharding,
            replicated,
        )

        axes = batch_axes(mesh, config.mesh)
        n_data = batch_shard_count(mesh, config.mesh)
        self._n_shards = n_data
        desc = "×".join(f"{a}={mesh.shape[a]}" for a in axes)
        cap = config.buffer.capacity_rollouts
        if cap % n_data:
            raise ValueError(
                f"buffer capacity {cap} not divisible by the batch shard "
                f"count {n_data} ({desc})"
            )
        if config.ppo.batch_rollouts % n_data:
            raise ValueError(
                f"batch_rollouts {config.ppo.batch_rollouts} not divisible "
                f"by the batch shard count {n_data} ({desc}; batches are "
                f"sharded over these axes)"
            )
        self.capacity = cap
        # Staleness is denominated in CONSUMED BATCHES (the cadence actors
        # can actually refresh at), while the version counter ticks once per
        # optimizer step — epochs_per_batch × minibatches ticks per batch.
        # Scale the threshold so max_staleness keeps meaning "batches
        # behind" regardless of the multi-epoch/minibatch configuration.
        # buffer.max_weight_staleness >= 0 overrides with a RAW version
        # delta — the admission-control knob (ISSUE 6) fleets bound
        # staleness with directly.
        self._staleness_limit = (
            config.buffer.max_weight_staleness
            if config.buffer.max_weight_staleness >= 0
            else config.ppo.max_staleness * config.ppo.steps_per_batch
        )
        # Admission control (ISSUE 6): semantic integrity at the buffer
        # door. Counters are eager-created — a clean run reports zeros
        # (check_telemetry_schema.py --require-health pins
        # buffer/stale_rejected_total).
        self._reject_nonfinite = config.buffer.reject_nonfinite
        self.dropped_nonfinite = 0
        self._tel.counter("buffer/stale_rejected_total")
        self._tel.counter("buffer/nonfinite_rejected_total")
        self._tel.counter("buffer/poison_dropped_total")
        self._sharding = data_sharding(mesh, config.mesh)
        template = example_batch(config, batch=cap)
        # Quantized experience plane (ISSUE 7): with
        # transport.rollout_wire_dtype narrow, the ring STORES the wire
        # dtypes — ≈half the resident HBM bytes and per-scatter H2D traffic
        # — and the upcast to the train dtypes happens on-device inside the
        # already-jitted consume gather, so `take()` hands the train step
        # f32 inputs bit-identical to decoding the wire (bf16→f32 and
        # int8→int32 are exact). The f32 template's dtypes are kept as the
        # consume-time upcast targets; the narrow template drives the
        # staging lanes, the skew check, and the scatter.
        from dotaclient_tpu.transport.serialize import (
            apply_cast_plan,
            flatten_tree,
            rollout_cast_plan,
            rollout_int_bounds,
            unflatten_tree,
        )

        self._consume_dtypes = jax.tree.map(
            lambda x: np.dtype(x.dtype), template
        )
        wire_dtype = config.transport.rollout_wire_dtype
        flat_tmpl = flatten_tree(template)
        int_bounds = rollout_int_bounds(config)
        self._wire_plan = rollout_cast_plan(
            {n: np.dtype(a.dtype) for n, a in flat_tmpl.items()},
            wire_dtype,
            int_bounds,
        )
        # Per-leaf admission dtypes: the stored dtype plus every width the
        # same leaf may legitimately arrive at — the original full width
        # (an in-proc actor or an f32-knob fleet member) and the narrow
        # wire width (a bf16-knob actor shipping to an f32 learner). The
        # staging copy casts on assignment either way; genuinely skewed
        # dtypes (wrong kind/meaning) still drop at the door.
        accept_flat: Dict[str, frozenset] = {}
        if self._wire_plan:   # a narrow config's plan IS the bf16 plan
            alt_plan = self._wire_plan
        else:
            alt_plan = rollout_cast_plan(
                {n: np.dtype(a.dtype) for n, a in flat_tmpl.items()},
                "bfloat16",
                int_bounds,
            )
        for n, a in flat_tmpl.items():
            widths = {np.dtype(a.dtype)}
            if n in alt_plan:
                widths.add(np.dtype(alt_plan[n]))
            accept_flat[n] = frozenset(widths)
        self._accept_dtypes = jax.tree.leaves(unflatten_tree(accept_flat))
        # Bound guards for the mixed-fleet door (review round 2): a
        # FULL-WIDTH int row admitted into a narrow ring is cast by the
        # staging copy / in-program astype with no range check, which
        # would WRAP silently — the exact failure the encode path's
        # exactness guard fails loudly on. Guard the buffer door the same
        # way: np.iinfo of the narrow target per int-narrowed leaf, in
        # template leaf order (same discipline as ``_accept_dtypes``);
        # the scan runs only on rows arriving wider than the store.
        guard_flat = {
            n: (
                np.iinfo(self._wire_plan[n])
                if n in self._wire_plan
                and np.dtype(self._wire_plan[n]).kind == "i"
                else 0
            )
            for n in flat_tmpl
        }
        self._int_guards = jax.tree.leaves(unflatten_tree(guard_flat))
        self._has_int_guards = any(g != 0 for g in self._int_guards)
        self._tel.counter("buffer/intbound_rejected_total")
        if self._wire_plan:
            template = unflatten_tree(
                apply_cast_plan(flat_tmpl, self._wire_plan)
            )
        self._store_dtypes = jax.tree.map(lambda x: np.dtype(x.dtype), template)
        self._store = jax.tree.map(
            lambda x: jax.device_put(x, self._sharding), template
        )
        # Multi-chip residency accounting (ISSUE 10): the ring is
        # batch-sharded, so each device holds 1/n_data of every leaf —
        # `buffer/shard_bytes` is the PER-DEVICE resident HBM cost of the
        # ring (the number an operator sizes capacity_rollouts against).
        total_bytes = sum(
            x.size * np.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(template)
        )
        self._tel.gauge("buffer/shard_bytes").set(
            float(total_bytes // n_data)
        )
        # Host-side bookkeeping: consumption order is an explicit deque of
        # slot ids (oldest first) plus a free list — NOT ring-cursor
        # arithmetic. Chunk versions are not monotone in ship order (an
        # episode-end chunk ships early with a newer version than a longer
        # chunk still in flight), so consume-time staleness drops must be
        # able to remove arbitrary slots, not just the head.
        self._order: Deque[int] = deque()
        self._free: List[int] = list(range(cap - 1, -1, -1))
        # Held batches (prefetch lane): slots taken with ``hold=True`` are
        # parked here — out of ``_order`` (cannot be re-taken or evicted by
        # an interleaved ingest) and out of ``_free`` (cannot be
        # overwritten) — until the consumer either ``release``s them
        # (batch trained on) or ``requeue``s them (end-of-run flush: the
        # experience returns to the front of the ring untrained, so a
        # checkpoint loses nothing).
        self._held: Dict[int, List[int]] = {}
        self._next_ticket = 0
        self._warmed = False       # min_fill reached at least once
        # Per-slot producer version, host-side: staleness is re-checked at
        # consume time too — a rollout that was fresh at ingest can go stale
        # sitting in the ring while the learner trains (ADVICE round 1).
        self._slot_version = np.zeros((cap,), np.int64)
        self.dropped_stale = 0
        self.dropped_overflow = 0
        self.dropped_skew = 0
        self.dropped_bounds = 0
        self.ingested = 0
        # Per-slot leaf spec for the ingest-door shape guard: a rollout from
        # a config-skewed actor (different rollout_len / obs shapes / model
        # core) must be dropped like any other malformed payload — actors
        # are disposable, the learner is not (SURVEY.md §5.3).
        self._tmpl_struct = jax.tree.structure(template)
        self._tmpl_leaves = [
            (x.shape[1:], np.dtype(x.dtype)) for x in jax.tree.leaves(template)
        ]
        self._skew_warned = False
        self._bounds_warned = False
        # Host staging lanes (BufferConfig.staging_slots): the ingest path
        # copies decoded rows into one of these REUSED preallocated numpy
        # buffers instead of np.stack-allocating per call, rotating lanes so
        # the scatter issued for ingest N (async dispatch may still read the
        # host rows) never shares a lane with ingest N+1's assembly.
        # Allocated lazily at first host-path ingest — the device-rollout
        # path scatters device chunks and never stages host rows.
        self._staging_lanes = max(1, config.buffer.staging_slots)
        self._staging: Optional[List[Any]] = None
        self._staging_idx = 0
        # Host ingest pads to shard-divisible power-of-two buckets (see
        # _pad_rows), so the lanes must hold the padded form of a
        # full-capacity ingest (monotone in n, so the cap is the max).
        self._staging_rows = self._pad_rows(cap)

        # Pipeline tracing (ISSUE 12): captured once, the faults/tracer
        # discipline — with tracing off every ingest/consume pays one
        # `is not None` test. Traced slots remember their host record
        # across ring residency so gather/dispatch hops can close the
        # chunk's timeline (the learner emits at dispatch).
        self._tracer = tracing.get()
        self._slot_trace: Optional[List[Optional[dict]]] = (
            [None] * cap if self._tracer is not None else None
        )
        self._pending_traces: List[dict] = []

        # Retrace accounting (ADVICE round 1): every distinct rows leading
        # dim compiles one XLA program. Host ingest pads to shard-divisible
        # pow2 buckets and the device path scatters pow2 chunks, so the
        # program set per path is bounded at log2(capacity)+1 —
        # `scatter_traces` proves it.
        self.scatter_traces = 0

        def _scatter_impl(store, rows, idx):
            self.scatter_traces += 1   # runs at trace time only
            # dtype-aware: rows arriving wider than the store (the
            # device-rollout path's f32 chunks into a narrow ring, or an
            # f32-knob actor at a narrow learner) are cast in-program; a
            # same-dtype astype is free in XLA
            return jax.tree.map(
                lambda s, r: s.at[idx].set(r.astype(s.dtype)), store, rows
            )

        store_shardings = jax.tree.map(lambda _: self._sharding, template)
        # HOST ingest path: rows are numpy staging-lane views, and the
        # explicit data-sharded in_shardings makes the H2D transfer land
        # DIRECTLY in each device's shard — 1/n_data of the group's bytes
        # per device. Without it the compiler replicates uncommitted host
        # inputs: every device received a FULL copy of every ingest group
        # (n_devices × the bytes; measured via compiled input shardings) —
        # the single-device-memory scatter ISSUE 10 exists to fix.
        # _pad_rows guarantees the leading dim divides by n_data.
        # instrument_jit (ISSUE 12): compile/retrace accounting per
        # program; transparent to dispatch AND to the donation lint
        # (lint/donation.py unwraps it) and to `.lower(...)` introspection
        self._scatter = tracing.instrument_jit(
            jax.jit(
                _scatter_impl,
                donate_argnums=(0,),
                in_shardings=(
                    store_shardings,
                    jax.tree.map(lambda _: self._sharding, template),
                    replicated(mesh),
                ),
                out_shardings=store_shardings,
            ),
            "buffer_scatter",
        )
        # DEVICE ingest path (add_device): rows are committed slices of an
        # in-process chunk (whatever sharding the producing program left
        # them with — explicit in_shardings would REJECT them, jax refuses
        # committed args whose sharding mismatches); no H2D happens here,
        # the program reshards in HBM. Separate jit so the two paths'
        # programs never mix; same impl, same trace bound.
        self._scatter_dev = tracing.instrument_jit(
            jax.jit(
                _scatter_impl,
                donate_argnums=(0,),
                out_shardings=store_shardings,
            ),
            "buffer_scatter_dev",
        )
        # Consume-time upcast (ISSUE 7): the gather restores the train
        # dtypes in the same jitted program — the only place narrow rows
        # widen, and it runs on-device (no host copy ever sees f32).
        consume_dtypes = self._consume_dtypes
        self._gather = tracing.instrument_jit(
            jax.jit(
                lambda store, idx: jax.tree.map(
                    lambda s, d: s[idx].astype(d), store, consume_dtypes
                ),
                out_shardings=jax.tree.map(
                    lambda _: self._sharding, template
                ),
            ),
            "buffer_gather",
        )

    def _pad_rows(self, n: int) -> int:
        """Padded row count for a host ingest group of ``n`` rows: the
        smallest power-of-two-per-shard multiple of the batch shard count
        that covers ``n``. With one shard this is exactly the historical
        pow2 bucket; with n_data shards it additionally guarantees the
        sharded scatter's leading dim divides evenly (jax rejects a
        NamedSharding whose axis does not divide). Distinct values stay
        bounded at log2(capacity/n_data)+1, so the retrace bound holds."""
        per_shard = -(-max(1, n) // self._n_shards)
        return _pow2ceil(per_shard) * self._n_shards

    # -- properties --------------------------------------------------------

    @property
    def size(self) -> int:
        return len(self._order)

    @property
    def ready(self) -> bool:
        return self.size >= max(
            self.config.buffer.min_fill, self.config.ppo.batch_rollouts
        )

    # -- ingest ------------------------------------------------------------

    def add(
        self,
        rollouts: List[Tuple[Dict[str, Any], Any]],
        current_version: int,
    ) -> int:
        """Ingest decoded rollouts ``(meta, arrays)``; returns number kept.

        Stale rollouts (older than ``ppo.max_staleness`` versions) are
        dropped here — the reference's version-tag discipline (SURVEY.md
        §3.4) applied at the buffer door.
        """
        fresh = []
        for meta, arrays in rollouts:
            if current_version - meta["model_version"] > self._staleness_limit:
                self.dropped_stale += 1
                self._tel.counter("buffer/stale_rejected_total").inc()
                continue
            if not self._matches_slot(arrays):
                self.dropped_skew += 1
                # Counted (rates come from diffing JSONL lines) AND logged —
                # never a bare print: headless runs must see the skew in
                # both the log stream and the telemetry record.
                self._tel.counter("buffer/skew_drops_total").inc()
                if not self._skew_warned:
                    self._skew_warned = True
                    logger.warning(
                        "trajectory_buffer: dropping rollout whose shapes do "
                        "not match this learner's config (actor running a "
                        "different rollout_len/obs/model config?) — align "
                        "actor and learner configs"
                    )
                continue
            if self._has_int_guards and not self._payload_in_bounds(arrays):
                # Mixed-fleet bound guard (ISSUE 7): a FULL-WIDTH int row
                # headed into a narrow ring would wrap silently at the
                # staging/scatter cast — the exact corruption the encode
                # path fails loudly on. Same door policy as nonfinite:
                # counted, never fatal.
                self.dropped_bounds += 1
                self._tel.counter("buffer/intbound_rejected_total").inc()
                if not self._bounds_warned:
                    self._bounds_warned = True
                    logger.warning(
                        "trajectory_buffer: dropping full-width rollout "
                        "whose integer leaves exceed this learner's "
                        "narrow-ring bounds (rollout_int_bounds promise "
                        "violated by an f32-wire actor?) — fix the actor "
                        "or widen rollout_int_bounds"
                    )
                continue
            if self._reject_nonfinite and not self._payload_finite(arrays):
                # Semantic admission control (ISSUE 6): a NaN/Inf anywhere
                # in a payload's float leaves (observations, rewards,
                # behavior logp, carries) would flow straight into the loss
                # and poison the params — reject at the door, like the wire
                # layer rejects CRC failures. Counted, never fatal: actors
                # are disposable, the learner is not.
                self.dropped_nonfinite += 1
                self._tel.counter("buffer/nonfinite_rejected_total").inc()
                continue
            fresh.append((meta, arrays))
        if len(fresh) > self.capacity:
            # A single scatter must not contain duplicate slot indices (the
            # winning write would be undefined); keep only the newest.
            self.dropped_overflow += len(fresh) - self.capacity
            fresh = fresh[-self.capacity:]
        if not fresh:
            self._publish_telemetry()
            return 0

        with self._tel.span("buffer/insert"):
            slots = self._alloc_slots(len(fresh))
            if len(slots) < len(fresh):
                fresh = fresh[: len(slots)]
                if not fresh:
                    self._publish_telemetry()
                    return 0
            n = len(fresh)
            # Pad the ingest group to a shard-divisible power-of-two bucket
            # and scatter ONCE (ADVICE round 1): a varying leading dim
            # would compile one XLA program per distinct count — up to
            # `capacity` of them. Pad rows are copies of the LAST REAL ROW
            # and their indices duplicate its slot, so the duplicate writes
            # are identical (order-independent) and the pad never enters
            # the slot bookkeeping below. Bounds the program set at
            # log2(capacity/n_data)+1 (asserted via `scatter_traces` in
            # tests). numpy rows transfer on the dispatch path, sharded —
            # each device receives only its slice (see _scatter).
            n_pad = self._pad_rows(n)
            rows = self._stage_rows(
                [arrays for _, arrays in fresh], pad_to=n_pad
            )
            idx = np.empty((n_pad,), np.int32)
            idx[:n] = slots   # host-sync-ok: host ints
            idx[n:] = slots[-1]
            self._store = self._scatter(self._store, rows, idx)
            self._slot_version[idx[:n]] = [
                m["model_version"] for m, _ in fresh
            ]
            if self._tracer is not None:
                # admission hop: the row passed the door and owns a slot.
                # Untraced rows CLEAR the slot's record — a reused slot
                # must never inherit an evicted chunk's timeline.
                ts = tracing.now()
                for (m, _), s in zip(fresh, slots):
                    rec = m.get("trace")
                    if rec is not None:
                        rec["hops"].append(["admit", ts])
                    self._slot_trace[s] = rec
            self._order.extend(slots)
            self.ingested += n
        self._publish_telemetry()
        return len(fresh)

    def _payload_finite(self, arrays: Any) -> bool:
        """True iff every float leaf of a host payload is finite. One
        vectorized pass per leaf — the staging copy touches the same bytes
        anyway, so the scan rides the ingest's existing memory traffic.

        Narrow-dtype rows (ISSUE 7) are scanned DIRECTLY: ml_dtypes
        registers a native ``np.isfinite`` loop for bfloat16 (a bf16 NaN
        is still a NaN), so the pass never materializes an f32 upcast
        copy — pinned by a test. Note bf16's numpy ``dtype.kind`` is
        ``'V'``, not ``'f'``: the kind check alone would silently skip
        exactly the leaves the narrow wire carries."""
        for leaf in jax.tree.leaves(arrays):
            a = np.asarray(leaf)
            if (
                a.dtype.kind == "f" or a.dtype == _WIRE_BF16
            ) and not np.isfinite(a).all():
                return False
        return True

    def _payload_in_bounds(self, arrays: Any) -> bool:
        """True iff every int leaf arriving WIDER than its narrow store
        dtype fits that dtype's range. Only the mixed-fleet path pays the
        min/max pass (a row already at the narrow width fits by dtype;
        a full-width ring has no guards at all)."""
        for leaf, guard in zip(jax.tree.leaves(arrays), self._int_guards):
            if guard == 0:
                continue
            a = np.asarray(leaf)
            if (
                a.dtype.kind == "i"
                and a.dtype.itemsize > guard.dtype.itemsize
                and a.size
                and (a.min() < guard.min or a.max() > guard.max)
            ):
                return False
        return True

    def _matches_slot(self, arrays: Any) -> bool:
        """True iff ``arrays`` has exactly the slot pytree/shapes, with
        every leaf at one of its admissible widths (the stored dtype, the
        original full width, or the narrow wire width — see
        ``_accept_dtypes``; the staging copy casts on assignment). Any
        other dtype is config skew and drops at the door."""
        try:
            if jax.tree.structure(arrays) != self._tmpl_struct:
                return False
            return all(
                np.shape(leaf) == shape and np.asarray(leaf).dtype in accept
                for leaf, (shape, _), accept in zip(
                    jax.tree.leaves(arrays),
                    self._tmpl_leaves,
                    self._accept_dtypes,
                )
            )
        except (TypeError, ValueError, AttributeError):
            return False

    def _alloc_slots(self, n: int) -> List[int]:
        """Allocate up to ``n`` writable slots for an ingest scatter: free
        slots first, then evict oldest unconsumed (counted in
        ``dropped_overflow``). Held (in-flight prefetched) slots are in
        neither pool — they can be neither evicted nor overwritten — so
        when everything else is exhausted the remainder is dropped
        (counted) rather than corrupting a batch mid-consumption. The
        returned list may be shorter than ``n``."""
        slots: List[int] = []
        for k in range(n):
            if self._free:
                slots.append(self._free.pop())
            elif self._order:
                slots.append(self._order.popleft())
                self.dropped_overflow += 1
            else:
                self.dropped_overflow += n - k
                break
        return slots

    def _stage_rows(self, arrays_list: List[Any], pad_to: int = 0) -> Any:
        """Copy decoded rollout rows into the next staging lane and return
        per-leaf views of the first ``max(len(arrays_list), pad_to)`` rows,
        with rows beyond ``len(arrays_list)`` filled with copies of the
        last real row (the pow2 scatter pad — see :meth:`add`).

        The lanes are preallocated at (pow2-padded) ring capacity (the most
        one ``add`` can ingest) and REUSED round-robin: no per-ingest
        allocation, and the ``staging_slots``-deep rotation guarantees the
        rows a possibly still-in-flight previous scatter reads are never
        overwritten by the current assembly — the double-buffering that
        lets the learner issue batch N+1's ingest while batch N's epoch
        step runs.
        """
        if self._staging is None:
            leaves_per_lane = [
                [
                    np.empty((self._staging_rows,) + shape, dtype)
                    for shape, dtype in self._tmpl_leaves
                ]
                for _ in range(self._staging_lanes)
            ]
            self._staging = [
                jax.tree.unflatten(self._tmpl_struct, leaves)
                for leaves in leaves_per_lane
            ]
        lane = self._staging[self._staging_idx]
        self._staging_idx = (self._staging_idx + 1) % self._staging_lanes
        n = len(arrays_list)
        n_out = max(n, pad_to)
        with self._tel.span("buffer/stage"):
            dst_leaves = jax.tree.leaves(lane)
            for i, arrays in enumerate(arrays_list):
                # leaf order matches the template: _matches_slot already
                # verified the pytree structure at the ingest door
                for dst, src in zip(dst_leaves, jax.tree.leaves(arrays)):
                    dst[i] = src
            for dst in dst_leaves:
                # pad rows mirror the last real row — their scatter indices
                # duplicate its slot, so the writes must be bit-identical
                dst[n:n_out] = dst[n - 1]
        return jax.tree.map(lambda dst: dst[:n_out], lane)

    def add_device(self, chunk: Dict[str, Any], version: int) -> int:
        """Ingest a device-resident chunk batch (arrays ``[L, T, ...]``, the
        on-device rollout path) — device-to-device scatter, no host copy of
        the experience tensors.

        Freshness: these chunks are produced with the current params by
        construction, so no staleness filter runs here; the slots are still
        version-tagged for consume-time re-checks.
        """
        with self._tel.span("buffer/insert"):
            L = chunk["valid"].shape[0]
            take = min(L, self.capacity)
            if take < L:
                self.dropped_overflow += L - take
            slots = self._alloc_slots(take)
            take = len(slots)
            if not take:
                self._publish_telemetry()
                return 0
            idx = np.asarray(slots, dtype=np.int32)   # host-sync-ok: host ints
            pos = 0
            remaining = take
            while remaining:
                n = 1 << (remaining.bit_length() - 1)
                rows = jax.tree.map(lambda r: r[pos:pos + n], chunk)
                # device-path scatter: rows keep their producer's sharding
                self._store = self._scatter_dev(
                    self._store, rows, idx[pos:pos + n]
                )
                pos += n
                remaining -= n
            if self._slot_trace is not None:
                # device chunks are untraced, but the slots they claim may
                # have been evicted from under a traced host row — a
                # reused slot must never inherit that chunk's timeline
                # (same invariant the host-path assignment keeps)
                for s in slots:
                    self._slot_trace[s] = None
            self._slot_version[idx] = version
            self._order.extend(slots)
            self.ingested += take
        self._publish_telemetry()
        return take

    # -- consume -----------------------------------------------------------

    def take(
        self,
        batch_size: Optional[int] = None,
        current_version: Optional[int] = None,
        hold: bool = False,
    ) -> Optional[Any]:
        """Consume the oldest ``batch_size`` rollouts as a train batch
        (device arrays, batch-sharded). Returns None if underfilled, or
        before ``min_fill`` has been reached for the first time (warmup
        diversity guard).

        This gather is the CONSUME BOUNDARY of the one-pass advantage
        plane (ISSUE 14): the learner runs its jitted advantage pass over
        the batch returned here — once per batch, not per optimizer step
        — and stages the narrow advantages/returns ON the batch dict, not
        in the ring (slots hold wire-shaped experience only, so requeue/
        rollback hygiene never has to invalidate derived tensors: they
        die with the batch dict — see train/learner.py).

        When ``current_version`` is given, staleness is re-enforced here:
        every unconsumed slot whose producer version has fallen more than
        ``max_staleness`` behind is dropped (slots are scanned, not just the
        head — ship order does not imply version order).

        With ``hold=True`` (the prefetch lane) the return is ``(batch,
        ticket)`` and the slots are PARKED instead of freed: an interleaved
        ingest can neither evict nor overwrite them while the batch is in
        flight. The consumer must then call :meth:`release` (trained on) or
        :meth:`requeue` (flushed untrained — the rows go back to the front
        of the ring, so checkpoints lose nothing).
        """
        b = batch_size or self.config.ppo.batch_rollouts
        if current_version is not None:
            max_st = self._staleness_limit
            stale = [
                s for s in self._order
                if current_version - self._slot_version[s] > max_st
            ]
            if stale:
                stale_set = set(stale)
                self._order = deque(
                    s for s in self._order if s not in stale_set
                )
                self._free.extend(stale)
                self.dropped_stale += len(stale)
                self._tel.counter("buffer/stale_rejected_total").inc(
                    len(stale)
                )
        if not self._warmed:
            if not self.ready:
                return None
            self._warmed = True
        if self.size < b:
            return None
        with self._tel.span("buffer/sample"):
            idx = np.asarray(   # host-sync-ok: host ints
                [self._order.popleft() for _ in range(b)], np.int32
            )
            batch = self._gather(self._store, idx)
            if hold:
                ticket = self._next_ticket
                self._next_ticket += 1
                self._held[ticket] = [int(s) for s in idx]
            else:
                self._free.extend(int(s) for s in idx)
            if self._tracer is not None:
                # consume-gather hop: the slot left the ring in this batch
                # (ring residency = gather − admit). The records park in
                # _pending_traces until the learner stamps `dispatch` on
                # the batch they ride (drain_traces) — a requeued batch's
                # records attribute to the NEXT dispatch, a documented
                # end-of-run approximation.
                ts = tracing.now()
                for s in idx:
                    rec = self._slot_trace[int(s)]
                    if rec is not None:
                        self._slot_trace[int(s)] = None
                        rec["hops"].append(["gather", ts])
                        self._pending_traces.append(rec)
        if current_version is not None:
            # host-side ints: how far behind the optimizer the experience in
            # this batch is, in optimizer steps (the IMPACT-style staleness
            # signal the --overlap path needs; 0 on the on-device path)
            self._tel.gauge("buffer/batch_staleness").set(
                float(current_version - self._slot_version[idx].mean())   # host-sync-ok: host ints
            )
        self._publish_telemetry()
        return (batch, ticket) if hold else batch

    def drain_traces(self) -> List[dict]:
        """Hand off the trace records of every batch gathered since the
        last call (ISSUE 12) — the learner stamps ``dispatch`` and emits
        them. Empty (and allocation-free) when tracing is off."""
        if not self._pending_traces:
            return self._pending_traces
        out, self._pending_traces = self._pending_traces, []
        return out

    def release(self, ticket: int) -> None:
        """The held batch was consumed — its slots become reusable.
        Tolerates an already-cleared ticket (a ``state_dict`` snapshot may
        have folded held slots back via :meth:`requeue_all_held`)."""
        self._free.extend(self._held.pop(ticket, ()))

    def requeue(self, ticket: int) -> None:
        """The held batch was NOT consumed (end-of-run flush): its slots
        return to the FRONT of the consumption order, in their original
        relative order — the next ``take`` re-gathers the same rows."""
        self._order.extendleft(reversed(self._held.pop(ticket, ())))

    def drop_newer_than(self, version: int) -> int:
        """Divergence-rollback hygiene (ISSUE 6): drop every unconsumed
        slot whose producer version is NEWER than ``version`` — experience
        generated by the poisoned policy of the abandoned timeline must
        not train the restored state. Counted in
        ``buffer/poison_dropped_total``; held (prefetch) slots must be
        requeued by the caller first (the learner's rollback flushes its
        prefetch lane before calling this)."""
        bad = [s for s in self._order if self._slot_version[s] > version]
        if bad:
            bad_set = set(bad)
            self._order = deque(s for s in self._order if s not in bad_set)
            self._free.extend(bad)
            self._tel.counter("buffer/poison_dropped_total").inc(len(bad))
            self._publish_telemetry()
        return len(bad)

    def requeue_all_held(self) -> None:
        """Defensive checkpoint hook: park nothing across a state_dict —
        newest tickets first, so the oldest held batch ends up at the very
        front and global FIFO order is preserved."""
        for ticket in sorted(self._held, reverse=True):
            self.requeue(ticket)

    # -- checkpointing -----------------------------------------------------

    def state_dict(self) -> Dict[str, Any]:
        """Full buffer state for checkpointing: the HBM ring contents plus
        the host bookkeeping, as host arrays (SURVEY.md §5.4 — a restore
        must not lose in-flight experience)."""
        def padded(vals) -> np.ndarray:
            # orbax rejects zero-size arrays: fixed capacity, -1 fill
            out = np.full((self.capacity,), -1, np.int64)
            out[: len(vals)] = list(vals)
            return out

        # in-flight held batches are unconsumed experience: fold them back
        # into the order so the snapshot is self-contained
        self.requeue_all_held()
        return {
            "store": jax.tree.map(np.asarray, self._store),
            "order": padded(self._order),
            "free": padded(self._free),
            "slot_version": self._slot_version.copy(),
            "counters": np.asarray(
                [
                    int(self._warmed), self.dropped_stale,
                    self.dropped_overflow, self.ingested,
                    self.dropped_skew, self.dropped_nonfinite,
                    self.dropped_bounds,
                ],
                np.int64,
            ),
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        def _put(x, dtype):
            a = np.asarray(x)   # host-sync-ok: checkpoint-restore host arrays
            if a.dtype != dtype:
                # snapshot written under a different rollout_wire_dtype
                # (f32 ring restored into a narrow config, or vice versa):
                # cast to THIS config's storage width — exact upward,
                # quantizing floats downward like a fresh ingest; int
                # slots that would WRAP are freed below instead
                a = a.astype(dtype)
            return jax.device_put(a, self._sharding)

        # Same bound guard the ingest door runs (`_payload_in_bounds`): a
        # full-width snapshot restored into a narrow ring would WRAP any
        # out-of-range int slot at the astype below — scan per slot first
        # and free the offenders instead (counted, never fatal, exactly
        # the fresh-ingest policy for the same rows).
        bad_slots = np.zeros((self.capacity,), bool)
        if self._has_int_guards:
            for leaf, guard in zip(
                jax.tree.leaves(state["store"]), self._int_guards
            ):
                if guard == 0:
                    continue
                a = np.asarray(leaf)   # host-sync-ok: checkpoint-restore
                if (
                    a.dtype.kind == "i"
                    and a.dtype.itemsize > guard.dtype.itemsize
                    and a.shape[:1] == (self.capacity,)
                ):
                    over = (a < guard.min) | (a > guard.max)
                    bad_slots |= over.reshape(self.capacity, -1).any(axis=1)

        self._store = jax.tree.map(_put, state["store"], self._store_dtypes)
        self._order = deque(
            int(s) for s in np.asarray(state["order"]) if s >= 0
        )
        self._free = [int(s) for s in np.asarray(state["free"]) if s >= 0]
        self._held = {}   # snapshots never carry in-flight holds
        if self._slot_trace is not None:
            # restored slots carry no live trace timeline
            self._slot_trace = [None] * self.capacity
            self._pending_traces = []
        self._slot_version = np.asarray(state["slot_version"]).copy()
        counters = [int(v) for v in np.asarray(state["counters"])]
        # snapshots written before dropped_skew/dropped_nonfinite/
        # dropped_bounds joined the array carry fewer entries; missing
        # counters resume at 0
        counters += [0] * (7 - len(counters))
        (warmed, stale, overflow, ingested, skew, nonfinite,
         bounds) = counters[:7]
        self._warmed = bool(warmed)
        self.dropped_stale = stale
        self.dropped_overflow = overflow
        self.ingested = ingested
        self.dropped_skew = skew
        self.dropped_nonfinite = nonfinite
        self.dropped_bounds = bounds
        dropped = (
            [s for s in self._order if bad_slots[s]]
            if bad_slots.any()
            else []
        )
        if dropped:
            self._order = deque(s for s in self._order if not bad_slots[s])
            self._free.extend(dropped)
            self.dropped_bounds += len(dropped)
            self._tel.counter("buffer/intbound_rejected_total").inc(
                len(dropped)
            )
            logger.warning(
                "trajectory_buffer: freed %d restored slot(s) whose int "
                "values exceed this config's narrow wire bounds (snapshot "
                "written under a wider rollout_wire_dtype?) — casting "
                "them would wrap silently",
                len(dropped),
            )

    def _publish_telemetry(self) -> None:
        """Mirror the host-side bookkeeping into the registry (gauges are
        cheap host writes; called at ingest/consume, never mid-dispatch)."""
        self._tel.gauge("buffer/occupancy").set(float(self.size))
        self._tel.gauge("buffer/capacity").set(float(self.capacity))
        self._tel.gauge("buffer/ingested").set(float(self.ingested))
        self._tel.gauge("buffer/dropped_stale").set(float(self.dropped_stale))
        self._tel.gauge("buffer/dropped_overflow").set(
            float(self.dropped_overflow)
        )
        self._tel.gauge("buffer/dropped_skew").set(float(self.dropped_skew))
        self._tel.gauge("buffer/dropped_nonfinite").set(
            float(self.dropped_nonfinite)
        )
        self._tel.gauge("buffer/dropped_bounds").set(
            float(self.dropped_bounds)
        )

    def metrics(self) -> Dict[str, float]:
        return {
            "buffer_size": float(self.size),
            "buffer_ingested": float(self.ingested),
            "buffer_dropped_stale": float(self.dropped_stale),
            "buffer_dropped_overflow": float(self.dropped_overflow),
            "buffer_dropped_skew": float(self.dropped_skew),
            "buffer_dropped_nonfinite": float(self.dropped_nonfinite),
            "buffer_dropped_bounds": float(self.dropped_bounds),
        }
