"""Continuous-batching inference engine: the serving plane's hot loop.

Training's batched-env idiom (TF Agents, PAPERS.md) inverted: instead of one
program stepping N resident envs, N remote games each want ONE action at
tight latency. The engine collects per-game step requests into preallocated
staging lanes until ``serve.batch_window_ms`` elapses or ``serve.max_batch``
requests are staged (whichever first), runs ONE jitted dispatch over the
padded batch, and scatters sampled actions back per requester — Podracer's
one-program-per-dispatch discipline (PAPERS.md) applied to serving.

Carry residency: recurrent state never rides the wire. Each attached game
owns a server-resident carry SLOT; the dispatch gathers the batch's slot
rows from the carry store, steps the core, and scatters the new rows back —
all inside the one compiled program. Row ``max_slots`` is a scratch slot:
padding rows of a partial batch gather it (reset-zeroed) and scatter into
it, so they can never touch a live game's state, and duplicate scatter
indices cannot occur (a window never holds two requests for one slot — the
second waits for the next window, preserving per-game request order).

Weight swaps are hot and atomic at dispatch granularity: ``submit_weights``
parks a (version, host params) pair in a latest-wins slot (monotonic —
stale versions are dropped); the batcher commits it to device BETWEEN
dispatches, so every action in one batch is sampled by exactly one weights
version (the version rides each reply). Slot releases are marshalled the
same way: ``release_slot`` enqueues, the batcher zeroes the carry row
between dispatches — every carry mutation happens on the batcher thread.

Sampling determinism: dispatch ``i`` samples with ``fold_in(key(seed), i)``.
The parity digest (tests/test_router.py::test_rehome_parity_digest_is_bitwise,
the router stage of scripts/ci_gate.sh) replays the same request stream
through this same compiled function in-process and requires bitwise-equal
actions — the transport and batching machinery must be invisible to the
policy.

Telemetry (eager-created; ``check_telemetry_schema.py --require-serve``):
``serve/requests_total``, ``serve/replies_total``, ``serve/reply_errors_total``,
``serve/dispatches_total``, ``serve/batch_window_hits``,
``serve/max_batch_hits``, ``serve/batch_fill``, ``serve/p99_latency_ms``,
``serve/weights_version``, ``serve/weight_swaps_total``, and the
``serve/request`` span (arrival→reply wall time per request).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.models import distributions as D
from dotaclient_tpu.models.policy import (
    Policy,
    dummy_obs_batch,
    require_carry_stays,
    require_one_pass_decode,
)
from dotaclient_tpu.utils import telemetry, utilization

logger = logging.getLogger(__name__)

# reply callback: (packed_actions [5] int32, logp, weights_version,
# request_id, dispatch_index). Must never block for long — it runs on the
# batcher thread (socket replies enqueue to a per-connection writer).
# Carry-shadow engines (ISSUE 19) additionally pass carry=<wire dict> by
# keyword; default-mode callbacks never see the kwarg.
ReplyFn = Callable[[np.ndarray, float, int, int, int], None]


@dataclasses.dataclass
class _Request:
    slot: int
    obs: Dict[str, np.ndarray]
    reset: float
    t0: float
    reply: ReplyFn
    request_id: int


class ServeEngine:
    """One batcher thread + preallocated staging lanes + a carry store."""

    def __init__(
        self,
        config: RunConfig,
        policy: Policy,
        params: Any,
        version: int = 0,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        scfg = config.serve
        self._config = config
        self._scfg = scfg
        self._policy = policy
        self._tel = (
            registry if registry is not None else telemetry.get_registry()
        )
        B, S = scfg.max_batch, scfg.max_slots
        self._scratch_slot = S  # padding rows gather/scatter here, never a game
        # Preallocated staging lanes: one [max_batch, ...] host row block
        # per obs leaf (the PR 2 buffer staging idiom — request arrays are
        # copied in, never stacked fresh per window).
        template = dummy_obs_batch(1, config.obs, config.actions)
        self._lanes: Dict[str, np.ndarray] = {
            name: np.zeros((B,) + arr.shape[1:], arr.dtype)
            for name, arr in template.items()
        }
        # per-leaf row shapes/element counts for submit-time validation: a
        # decodable request whose obs tree does not fit the lanes must be
        # rejected at the door (the READER's thread), never reach the
        # batcher — one shape-skewed client must not kill dispatch for
        # everyone. A separate immutable dict: validation runs on
        # submitting threads and must not touch the batcher-owned lanes.
        self._row_shapes: Dict[str, Tuple[int, ...]] = {
            name: arr.shape[1:] for name, arr in template.items()
        }
        self._slots_np = np.full((B,), self._scratch_slot, np.int32)
        self._reset_np = np.ones((B,), np.float32)
        # Server-resident carries: one row per attached game + the scratch
        # row. Committed to device once; every later mutation happens
        # inside the donated dispatch (or the donated slot-zero program).
        self._carries = jax.tree.map(
            jnp.asarray, policy.initial_state(S + 1)
        )
        self._params = jax.device_put(params)
        # Where the params landed, read off the arrays: ``device_put`` with
        # no sharding commits to the process's first device (weight swaps
        # land there too), so one engine is one single-device replica
        # however many chips the host holds.
        self.param_devices: Set[Any] = set().union(
            *(leaf.devices() for leaf in jax.tree.leaves(self._params))
        )
        self._version = version
        self._rng0 = jax.random.PRNGKey(scfg.seed)
        self._dispatch_idx = 0
        self._cond = threading.Condition()
        self._pending: Deque[_Request] = deque()
        self._reset_slots: Set[int] = set()
        self._stopped = False
        self._weights_lock = threading.Lock()
        self._pending_weights: Optional[Tuple[int, Any]] = None
        # Carry-shadow plane (ISSUE 19): when enabled, every reply also
        # hands the requester its updated carry ROW (host numpy), and a
        # re-homed client resends that row so its session resumes
        # bit-exact on a fresh backend. Inbound rows park here (slot →
        # host row tree) and the batcher installs them BETWEEN dispatches
        # — the same marshalling discipline as slot zeroes.
        require_one_pass_decode(policy.model, "the serve engine")
        self._carry_shadow = bool(scfg.carry_shadow)
        if self._carry_shadow:
            require_carry_stays(policy.model, "serve.carry_shadow")
        self._install_carries: Dict[int, Any] = {}
        # one carry ROW's pytree shape: the wire flatten/unflatten template
        # (leaves keyed c0..cN in jax.tree order)
        row_template = policy.initial_state(1)
        self._carry_row_treedef = jax.tree_util.tree_structure(row_template)
        self._carry_row_shapes = [
            np.asarray(leaf).shape[1:]
            for leaf in jax.tree_util.tree_leaves(row_template)
        ]

        def _dispatch_impl(params, obs, slots, reset, carries, rng):
            carry = jax.tree.map(lambda c: c[slots], carries)   # [B, ...]
            # reset rows (fresh episodes AND padding rows) start afresh, as
            # the core defines it (a zeroed row; a cache core's position 0)
            carry = self._policy.reset_carry(carry, 1.0 - reset)
            logits, _, carry2 = self._policy.apply(
                params, obs, carry, method="step"
            )
            acts, logp = D.sample(rng, logits, obs)
            packed = jnp.stack(
                [acts[h] for h in D.HEADS], axis=1
            ).astype(jnp.int32)
            new_carries = jax.tree.map(
                lambda store, new: store.at[slots].set(new), carries, carry2
            )
            # carry2 (the batch's per-row NEW carries) is returned for the
            # shadow plane; the host fetch is gated on the knob, so the
            # default path never pays the transfer
            return packed, logp.astype(jnp.float32), new_carries, carry2

        # carries donated: the store updates in place in HBM every dispatch.
        # instrument_jit (ISSUE 12): serve recompiles are latency cliffs —
        # the per-program compile counters name them; the donation lint
        # unwraps the wrapper, so the call site keeps its taint tracking.
        from dotaclient_tpu.utils import tracing

        tracing.ensure_metrics(self._tel)
        self._dispatch_fn = tracing.instrument_jit(
            jax.jit(_dispatch_impl, donate_argnums=(4,)),
            "serve_dispatch",
            self._tel,
        )

        def _zero_slots_impl(carries, slots):
            return jax.tree.map(
                lambda c: c.at[slots].set(jnp.zeros_like(c[slots])), carries
            )

        self._zero_slots_fn = jax.jit(_zero_slots_impl, donate_argnums=(0,))

        def _install_carry_impl(carries, slot, row):
            # row leaves arrive [1, ...] (a one-row tree); cast to the
            # store dtype so a narrowed wire row still installs
            return jax.tree.map(
                lambda c, r: c.at[slot].set(
                    jnp.reshape(r, c.shape[1:]).astype(c.dtype)
                ),
                carries, row,
            )

        self._install_carry_fn = jax.jit(
            _install_carry_impl, donate_argnums=(0,)
        )

        # eager-create: a serve run that never falls into a state still
        # reports zeros (check_telemetry_schema.py --require-serve)
        for name in (
            "serve/requests_total",
            "serve/replies_total",
            "serve/reply_errors_total",
            "serve/dispatches_total",
            "serve/batch_window_hits",
            "serve/max_batch_hits",
            "serve/weight_swaps_total",
            "serve/dispatch_errors_total",
            "serve/carry_installs_total",
        ):
            self._tel.counter(name)
        self._tel.gauge("serve/batch_fill")
        self._tel.gauge("serve/p99_latency_ms")
        self._tel.gauge("serve/weights_version").set(float(version))
        self._tel.timer("span/serve/request")
        # Pipeline utilization plane (ISSUE 16): window_wait / dispatch /
        # reply splits of the batcher thread's wall clock. Eager keys
        # either way; None when the module knob is off (one pointer test
        # per loop turn).
        self._util = utilization.make_serve(self._tel)
        self._batcher = threading.Thread(
            target=self._batch_loop, name="serve-batcher", daemon=True
        )
        self._batcher.start()

    # -- submission (reader / weight-swap threads) ---------------------------

    @property
    def max_slots(self) -> int:
        return self._scfg.max_slots

    @property
    def version(self) -> int:
        """Weights version of the LAST committed swap. Latched int written
        by the batcher; readers (attach frames) tolerate one-dispatch-stale
        values by design."""
        return self._version

    def _validate_obs(self, obs: Dict[str, np.ndarray]) -> None:
        """Reject a request whose obs tree cannot land in the staging
        lanes — missing leaves or wrong element counts (a version-skewed
        client's config). Runs on the SUBMITTING thread, so the error
        surfaces where the wire's poison discipline can count it and the
        batcher never sees an undispatable row."""
        for name, row_shape in self._row_shapes.items():
            leaf = obs.get(name)
            if leaf is None:
                raise ValueError(f"request missing obs leaf {name!r}")
            shape = np.shape(leaf)
            if int(np.prod(shape, dtype=np.int64)) != int(
                np.prod(row_shape, dtype=np.int64)
            ):
                raise ValueError(
                    f"request obs leaf {name!r} has shape {shape} — "
                    f"incompatible with the serving lane {row_shape} "
                    f"(config skew between client and server?)"
                )

    def submit(
        self,
        slot: int,
        obs: Dict[str, np.ndarray],
        reset: bool,
        reply: ReplyFn,
        request_id: int = 0,
        carry: Optional[Dict[str, np.ndarray]] = None,
    ) -> None:
        """Queue one game's step request. ``obs`` is a single observation
        (unbatched leaves matching the staging-lane template; validated
        here, on the caller's thread); ``reset`` marks the first step of
        an episode (the slot's carry row is zeroed before the core — the
        actor-side episode-boundary discipline). ``carry`` is a re-homed
        session's shadowed row (the wire dict of :meth:`carry_row_to_wire`)
        — installed into the slot by the batcher BEFORE this request
        dispatches, so the session resumes where its dead backend left
        off. Rejected when carry_shadow is off (an unexpected carry is a
        protocol skew, and the poison discipline should see it)."""
        if not 0 <= slot < self._scfg.max_slots:
            raise ValueError(
                f"slot {slot} out of range [0, {self._scfg.max_slots})"
            )
        self._validate_obs(obs)
        row = None
        if carry is not None:
            if not self._carry_shadow:
                raise ValueError(
                    "request carries a shadow row but serve.carry_shadow "
                    "is off on this backend (fleet config skew)"
                )
            row = self.wire_to_carry_row(carry)
        req = _Request(
            slot=slot,
            obs=obs,
            reset=1.0 if reset else 0.0,
            t0=time.perf_counter(),
            reply=reply,
            request_id=request_id,
        )
        with self._cond:
            if self._stopped:
                raise RuntimeError("serve engine is stopped")
            if row is not None:
                # latest-wins per slot; ordered before the request it
                # rode in on (installs drain before the next window)
                self._install_carries[slot] = row
            self._pending.append(req)
            self._cond.notify()
        self._tel.counter("serve/requests_total").inc()

    def submit_weights(self, version: int, params: Any) -> None:
        """Latest-wins weight refresh (host params). Applied by the batcher
        BETWEEN dispatches; versions at or below the newest seen are
        dropped — published versions are monotonic on the wire, so a stale
        frame is a reorder, never a rollback."""
        with self._weights_lock:
            newest = (
                self._pending_weights[0]
                if self._pending_weights is not None
                else self._version
            )
            if version <= newest:
                return
            self._pending_weights = (version, params)
        with self._cond:
            self._cond.notify()

    def release_slot(self, slot: int) -> None:
        """A game detached (disconnect, quarantine): zero its carry row so
        the slot's next owner starts fresh even if it never sends reset.
        Marshalled to the batcher — carry mutations never race a dispatch.
        The dead game's still-pending requests are DISCARDED here: a stale
        request dispatched after the zero would scatter the old game's
        carry back into the reclaimed row (and its requester is gone
        anyway — nobody is waiting on the reply)."""
        with self._cond:
            if any(r.slot == slot for r in self._pending):
                self._pending = deque(
                    r for r in self._pending if r.slot != slot
                )
            self._reset_slots.add(slot)
            self._cond.notify()

    def stop(self, timeout: float = 30.0) -> None:
        """Serve every pending request, then stop the batcher (tests and
        script teardown; production engines live for the process)."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._batcher.join(timeout)

    @property
    def pending(self) -> int:
        with self._cond:
            return len(self._pending)

    # -- batcher thread ------------------------------------------------------

    def _batch_loop(self) -> None:
        while True:
            with self._cond:
                while (
                    not self._pending
                    and not self._reset_slots
                    and not self._stopped
                    and self._peek_pending_weights() is None
                ):
                    # idle waiting for ANY request counts as window_wait:
                    # the batcher is request-starved either way
                    t_w = time.perf_counter()
                    self._cond.wait()
                    self._util.phase("window_wait", time.perf_counter() - t_w)
                if self._stopped and not self._pending:
                    return
                resets = list(self._reset_slots)
                self._reset_slots.clear()
                installs = list(self._install_carries.items())
                self._install_carries.clear()
            if resets:
                self._carries = self._zero_slots_fn(
                    self._carries, np.asarray(resets, np.int32)
                )
            for slot, row in installs:
                # after zeroes (a reclaimed slot re-attached with a shadow
                # row must keep the row), before the window that carries
                # the re-homed request
                self._carries = self._install_carry_fn(
                    self._carries, np.int32(slot), row
                )
                self._tel.counter("serve/carry_installs_total").inc()
            self._apply_pending_weights()
            rows = self._collect_window()
            if rows:
                try:
                    self._dispatch_window(rows)
                except Exception as e:  # noqa: BLE001 - the batcher must outlive any window
                    # submit-time validation makes this unreachable for
                    # request-shaped trouble; whatever remains (device
                    # error, OOM) must not silently wedge serving for
                    # every client — count it and keep dispatching
                    self._tel.counter("serve/dispatch_errors_total").inc()
                    logger.warning(
                        "serve dispatch failed (%s: %s) — window of %d "
                        "request(s) dropped; batcher continues",
                        type(e).__name__, e, len(rows),
                    )
            self._util.maybe_fold()

    def _peek_pending_weights(self) -> Optional[Tuple[int, Any]]:
        with self._weights_lock:
            return self._pending_weights

    def _apply_pending_weights(self) -> None:
        with self._weights_lock:
            pending, self._pending_weights = self._pending_weights, None
        if pending is None:
            return
        version, params = pending
        # one commit per swap; the next dispatch reads the new tree. The
        # old params buffers free once the last dispatch using them lands.
        self._params = jax.device_put(params)
        self._version = version
        self._tel.gauge("serve/weights_version").set(float(version))
        self._tel.counter("serve/weight_swaps_total").inc()

    def _collect_window(self) -> List[_Request]:
        scfg = self._scfg
        window_s = scfg.batch_window_ms / 1e3
        rows: List[_Request] = []
        slots: Set[int] = set()
        deadline: Optional[float] = None
        while True:
            with self._cond:
                held: List[_Request] = []
                while self._pending and len(rows) < scfg.max_batch:
                    req = self._pending.popleft()
                    if req.slot in slots:
                        # one outstanding request per slot per dispatch:
                        # a pipelining client's second request waits for
                        # the next window (duplicate scatter indices would
                        # make the carry update order-undefined)
                        held.append(req)
                        continue
                    rows.append(req)
                    slots.add(req.slot)
                for req in reversed(held):
                    self._pending.appendleft(req)
                if not rows:
                    return rows
                if deadline is None:
                    # the window opened when the FIRST request arrived,
                    # not when the batcher noticed it
                    deadline = rows[0].t0 + window_s
                if len(rows) >= scfg.max_batch:
                    self._tel.counter("serve/max_batch_hits").inc()
                    return rows
                now = time.perf_counter()
                if now >= deadline or self._stopped:
                    self._tel.counter("serve/batch_window_hits").inc()
                    return rows
                self._cond.wait(min(deadline - now, 0.05))
                self._util.phase("window_wait", time.perf_counter() - now)

    def _dispatch_window(self, rows: List[_Request]) -> None:
        n = len(rows)
        lanes = self._lanes
        for i, req in enumerate(rows):
            for name, lane in lanes.items():
                # the one host copy per request; reshape absorbs the wire
                # codec's 0-d→(1,) scalar normalization (zero-copy view)
                lane[i] = np.asarray(req.obs[name]).reshape(lane.shape[1:])
            self._slots_np[i] = req.slot
            self._reset_np[i] = req.reset
        self._slots_np[n:] = self._scratch_slot
        self._reset_np[n:] = 1.0            # padding gathers a zeroed carry
        rng = jax.random.fold_in(self._rng0, self._dispatch_idx)
        t_d = time.perf_counter()
        with self._tel.span("serve/dispatch"):
            packed, logp, self._carries, carry2 = self._dispatch_fn(
                self._params, lanes, self._slots_np, self._reset_np,
                self._carries, rng,
            )
            # the serving plane's one sync: replies need host actions
            packed_np = np.asarray(packed)   # host-sync-ok: serve batcher thread — replies leave the process here
            logp_np = np.asarray(logp)       # host-sync-ok: serve batcher thread
            carry2_np = (
                jax.tree.map(np.asarray, carry2)   # host-sync-ok: serve batcher thread — shadow rows ride the replies
                if self._carry_shadow
                else None
            )
        idx = self._dispatch_idx
        self._dispatch_idx += 1
        version = self._version
        t_done = time.perf_counter()
        self._util.phase("dispatch", t_done - t_d)
        timer = self._tel.timer("span/serve/request")
        errors = 0
        for i, req in enumerate(rows):
            timer.observe(t_done - req.t0)
            try:
                if carry2_np is None:
                    req.reply(
                        packed_np[i], float(logp_np[i]), version,
                        req.request_id, idx,
                    )
                else:
                    req.reply(
                        packed_np[i], float(logp_np[i]), version,
                        req.request_id, idx,
                        carry=self.carry_row_to_wire(
                            jax.tree.map(lambda c: c[i], carry2_np)
                        ),
                    )
            except Exception:   # noqa: BLE001 - a dead client must not kill the batcher
                errors += 1
        self._util.phase("reply", time.perf_counter() - t_done)
        self._tel.counter("serve/dispatches_total").inc()
        self._tel.counter("serve/replies_total").inc(n - errors)
        if errors:
            self._tel.counter("serve/reply_errors_total").inc(errors)
        self._tel.gauge("serve/batch_fill").set(n / self._scfg.max_batch)
        self._tel.gauge("serve/p99_latency_ms").set(
            timer.quantile(0.99) * 1e3
        )

    # -- parity probe --------------------------------------------------------

    def reference_step(
        self,
        obs_rows: List[Dict[str, np.ndarray]],
        slots: List[int],
        resets: List[float],
        carries: Any,
        dispatch_idx: int,
        params: Any = None,
    ) -> Tuple[np.ndarray, np.ndarray, Any]:
        """Replay one dispatch through the SAME compiled function the
        batcher runs — the in-process reference the serve parity digest
        compares server replies against (scripts/serve_loadgen.py
        rehome_parity). Maintains
        its OWN carry tree (pass the previous call's return), so it never
        perturbs the live store. Returns ``(packed [B,5], logp [B],
        carries)``; rows past ``len(obs_rows)`` are padding."""
        B = self._scfg.max_batch
        lanes = {
            name: np.zeros_like(lane) for name, lane in self._lanes.items()
        }
        slots_np = np.full((B,), self._scratch_slot, np.int32)
        reset_np = np.ones((B,), np.float32)
        for i, obs in enumerate(obs_rows):
            for name, lane in lanes.items():
                lane[i] = np.asarray(obs[name]).reshape(lane.shape[1:])
            slots_np[i] = slots[i]
            reset_np[i] = resets[i]
        rng = jax.random.fold_in(self._rng0, dispatch_idx)
        # donated carries: callers thread the returned tree back in
        packed, logp, carries, _carry2 = self._dispatch_fn(
            self._params if params is None else jax.device_put(params),
            lanes, slots_np, reset_np, carries, rng,
        )
        return np.asarray(packed), np.asarray(logp), carries   # host-sync-ok: parity probe, off the serving path

    # -- carry-shadow wire form ----------------------------------------------

    def carry_row_to_wire(self, row: Any) -> Dict[str, np.ndarray]:
        """One carry row tree → the flat wire dict replies ship
        (``{"c0": leaf, ...}`` in ``jax.tree`` leaf order). The treedef
        stays server-side; clients stash and resend the dict opaquely."""
        return {
            f"c{i}": np.asarray(leaf)
            for i, leaf in enumerate(jax.tree_util.tree_leaves(row))
        }

    def wire_to_carry_row(self, wire: Dict[str, np.ndarray]) -> Any:
        """Inverse of :meth:`carry_row_to_wire`, validated on the
        SUBMITTING thread (bad structure raises → the wire's poison
        discipline counts it, the batcher never sees it)."""
        n = len(self._carry_row_shapes)
        leaves = []
        for i, shape in enumerate(self._carry_row_shapes):
            leaf = wire.get(f"c{i}")
            if leaf is None:
                raise ValueError(
                    f"shadow carry missing leaf c{i} (expected {n})"
                )
            arr = np.asarray(leaf)
            if int(np.prod(arr.shape, dtype=np.int64)) != int(
                np.prod(shape, dtype=np.int64)
            ):
                raise ValueError(
                    f"shadow carry leaf c{i} has shape {arr.shape} — "
                    f"incompatible with the carry row {shape}"
                )
            leaves.append(arr.reshape(shape))
        if len(wire) != n:
            raise ValueError(
                f"shadow carry has {len(wire)} leaves, expected {n}"
            )
        return jax.tree_util.tree_unflatten(self._carry_row_treedef, leaves)
