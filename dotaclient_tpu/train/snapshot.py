"""Async snapshot engine: learner side effects off the train thread (ISSUE 5).

The learner's throughput discipline says the train loop is dispatch-only —
yet until this module every side effect broke it: ``_publish_weights`` did a
full device→host param fetch plus serialization inline, ``CheckpointManager.
save`` synchronously fetched params + opt state before a blocking orbax
write, and the log-boundary metrics fetch parked the train thread on the
in-flight step. Keeping the optimizer busy by overlapping those host phases
with device compute is the pipeline-overlap win OPPO demonstrates for PPO,
and it finishes the Podracer "device never waits on the host" discipline
(PAPERS.md) that the actor half already applies.

Division of labor:

* **train thread** — at a publish/checkpoint/log boundary it runs ONE cheap
  jitted on-device copy of the needed state (params / TrainState / the tiny
  stat accumulators) into fresh HBM snapshot buffers and submits the copy
  here. The copy program is enqueued on the device stream *before* the next
  (donating) train step, so the snapshot can never read donated buffers;
  the thread returns to dispatching immediately.
* **snapshot thread** (one per engine) — drains the job slots: the batched
  ``jax.device_get`` (the one transfer per job), the bf16 wire cast +
  ``encode_weights``, the non-blocking ``transport.publish_weights``
  enqueue, the orbax write via ``CheckpointManager.save_host``, and the
  host-side metrics continuation.

Semantics preserved, not relaxed (the contract tests/test_snapshot.py pins):

* one latest-wins slot per job kind — when the thread falls behind, unsent
  work coalesces to the newest submission (counted in
  ``snapshot/<kind>_coalesced``; the PR3 fanout-slot pattern) and published
  versions stay MONOTONIC (an engine-side guard skips anything at or below
  the last published version). Coalescing only ever drops IDEMPOTENT work
  (an older weights version, an older checkpoint, an older log line):
  actor stat drains — whose device accumulators are destructively reset at
  submit time — go through :meth:`submit_stats`, a backlog that is ALWAYS
  fully processed (before the same cycle's log job, so the surviving log
  sees every fold) and never coalesced;
* ``drain()`` blocks until every pending job has landed — the graceful-stop
  path drains before its forced sync checkpoint, so the final save still
  lands at the EXACT stop step;
* failures never kill the engine: checkpoint I/O errors degrade through the
  existing ``checkpoint/save_failures_total`` policy inside ``save_host``;
  anything else is counted in ``snapshot/errors_total`` + a warning, and
  the next job proceeds.

HBM budget: at most two snapshots per kind are alive at once (one pending
slot + one being fetched) — for the checkpoint kind that is ~2× the
TrainState, freed as soon as the fetch completes.

Telemetry: ``snapshot/pending`` (job slots occupied), ``snapshot/d2h_ms``
(last batched device→host fetch), ``span/transport/publish_weights`` and
``span/learner/metrics_fetch`` keep their documented keys — they are simply
recorded from this thread now — and ``span/snapshot/stats_fetch`` times each
stats job's fetch, so a profiler trace shows what this thread held while the
train thread's enqueue waited.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from dotaclient_tpu.utils import telemetry, tracing

logger = logging.getLogger(__name__)

_KINDS = ("publish", "checkpoint", "metrics")


class SnapshotEngine:
    """One background thread + three latest-wins job slots."""

    def __init__(
        self,
        transport: Any = None,
        wire_dtype: str = "float32",
        ckpt: Any = None,
        registry: Optional[telemetry.Registry] = None,
        health: Any = None,
    ) -> None:
        self._transport = transport
        self._wire_dtype = wire_dtype
        self._ckpt = ckpt
        # Training-health gate (ISSUE 6, train/health.py): verdicts ride
        # the never-coalesced stats backlog, which this thread processes
        # BEFORE the same cycle's publish/checkpoint jobs — so by the time
        # a version-V publish job runs, every verdict for steps <= V has
        # been folded. An unhealthy latch refuses the publish (actors keep
        # serving the last good version) and the periodic checkpoint (the
        # retention loop must not rotate good saves out for poisoned ones).
        self._health = health
        # Pipeline tracing (ISSUE 12): captured once, like the learner's —
        # with tracing off the publish path pays one pointer test
        self._tracer = tracing.get()
        self._tel = registry if registry is not None else telemetry.get_registry()
        self._cond = threading.Condition()
        self._jobs: Dict[str, Optional[Tuple]] = {k: None for k in _KINDS}
        # Never-coalesced backlog of (device_stats, finish) actor stat
        # drains: each entry's device accumulators were already reset at
        # submit, so dropping one would lose those episodes forever.
        # Entries are a few scalars each and arrive at boundary cadence —
        # the backlog stays tiny unless the thread is fully wedged.
        self._stats_jobs: list = []
        self._busy = False
        self._stopped = False
        # Monotonic-publish floor: the train thread submits strictly
        # increasing versions and the slot keeps only the newest, but a
        # drain/tail re-submit of an already-published version must be a
        # no-op, never a duplicate or regression on the wire.
        self._last_published = -1
        # eager-create: a run whose engine never falls behind still reports
        # zeros (check_telemetry_schema.py --require-snapshot pins these)
        self._tel.gauge("snapshot/pending")
        self._tel.gauge("snapshot/d2h_ms")
        self._tel.counter("snapshot/errors_total")
        for k in _KINDS:
            self._tel.counter(f"snapshot/{k}_coalesced")
        self._thread = threading.Thread(
            target=self._run, name="snapshot", daemon=True
        )
        self._thread.start()

    # -- submission (train thread) -----------------------------------------

    def _submit(self, kind: str, job: Tuple) -> None:
        with self._cond:
            if self._stopped:
                raise RuntimeError("snapshot engine is stopped")
            if self._jobs[kind] is not None:
                # an unprocessed older snapshot just became worthless:
                # latest wins (the PR3 fanout-slot rule)
                self._tel.counter(f"snapshot/{kind}_coalesced").inc()
            self._jobs[kind] = job
            self._tel.gauge("snapshot/pending").set(float(self._pending_locked()))   # host-sync-ok: host ints
            self._cond.notify_all()

    def _pending_locked(self) -> int:
        """Jobs not yet fully processed (slot jobs + stats backlog + the
        batch currently in flight). Caller holds ``_cond``."""
        return (
            sum(j is not None for j in self._jobs.values())
            + len(self._stats_jobs)
            + (1 if self._busy else 0)
        )

    def submit_publish(self, params: Any, version: int) -> None:
        """``params`` must be an on-device COPY (the train step donates the
        live state; a jitted ``jnp.copy`` tree dispatched before the next
        step is the cheap, ordering-safe way to get one)."""
        self._submit("publish", (params, version))

    def submit_checkpoint(self, state: Any, config: Any) -> None:
        """``state`` is an on-device TrainState copy (same donation rule)."""
        self._submit("checkpoint", (state, config))

    def submit_metrics(
        self, device_tree: Any, finish: Callable[[Any], None]
    ) -> None:
        """Fetch ``device_tree`` (one transfer) and hand the host result to
        ``finish`` on the snapshot thread. ``device_tree`` leaves must be
        program OUTPUTS or copies — never buffers a later step donates.
        Latest-wins: only the newest unprocessed log boundary survives a
        backlog — put anything non-idempotent in :meth:`submit_stats`."""
        self._submit("metrics", (device_tree, finish))

    def submit_stats(
        self, device_stats: Any, finish: Callable[[Any], Any]
    ) -> None:
        """Queue one actor stat drain: ``finish(fetched)`` folds the window
        into the host accumulators. NEVER coalesced — the device
        accumulators were reset when this drain began, so this entry is the
        only copy of its window — and always processed BEFORE the same
        cycle's metrics job, so the surviving log line reflects every
        fold."""
        with self._cond:
            if self._stopped:
                raise RuntimeError("snapshot engine is stopped")
            self._stats_jobs.append((device_stats, finish))
            self._tel.gauge("snapshot/pending").set(float(self._pending_locked()))   # host-sync-ok: host ints
            self._cond.notify_all()

    # -- lifecycle ----------------------------------------------------------

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every pending job has been processed (False on
        timeout). The graceful-stop/forced-checkpoint path calls this so
        the sync save that follows cannot race an in-flight async write."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._pending_locked():
                wait = 1.0
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        return False
                self._cond.wait(min(wait, 1.0))
        return True

    def stop(self, timeout: float = 30.0) -> None:
        """Process whatever is pending, then stop the thread (tests and
        script teardown; production engines live for the process)."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout)

    @property
    def pending(self) -> int:
        with self._cond:
            return self._pending_locked()

    # -- snapshot thread -----------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cond:
                while (
                    not self._stopped
                    and all(j is None for j in self._jobs.values())
                    and not self._stats_jobs
                ):
                    self._cond.wait()
                batch = {k: j for k, j in self._jobs.items() if j is not None}
                stats_batch, self._stats_jobs = self._stats_jobs, []
                if not batch and not stats_batch:
                    return  # stopped with nothing left
                for k in batch:
                    self._jobs[k] = None
                self._busy = True
                # the in-flight batch still counts as pending: an operator
                # reading the last metrics line of a crashed run must see
                # that work was outstanding (OPERATIONS.md runbook)
                self._tel.gauge("snapshot/pending").set(float(self._pending_locked()))   # host-sync-ok: host ints
            try:
                # stat drains first (their fold must land before the log
                # job that reports it), then publish (actors get fresh
                # weights at fanout latency), then the slower orbax write
                for dev, finish in stats_batch:
                    try:
                        with self._tel.span("snapshot/stats_fetch"):
                            host = jax.device_get(dev)  # host-sync-ok: snapshot thread, tiny stat scalars
                        finish(host)
                    except Exception as e:  # noqa: BLE001 - engine must outlive any job
                        self._tel.counter("snapshot/errors_total").inc()
                        logger.warning(
                            "snapshot stats fold failed (%s: %s)",
                            type(e).__name__, e,
                        )
                for kind in _KINDS:
                    job = batch.get(kind)
                    if job is None:
                        continue
                    try:
                        getattr(self, f"_do_{kind}")(*job)
                    except Exception as e:  # noqa: BLE001 - engine must outlive any job
                        self._tel.counter("snapshot/errors_total").inc()
                        logger.warning(
                            "snapshot %s job failed (%s: %s) — engine "
                            "continues; the next boundary retries",
                            kind, type(e).__name__, e,
                        )
            finally:
                with self._cond:
                    self._busy = False
                    self._tel.gauge("snapshot/pending").set(float(self._pending_locked()))   # host-sync-ok: host ints
                    self._cond.notify_all()

    def _fetch(self, tree: Any) -> Any:
        """The ONE batched device→host transfer per job.

        Mesh-sharded snapshots (ISSUE 10) need no special casing here:
        the submitted copies keep the live state's shardings, and
        ``device_get`` assembles replicated leaves from shard 0 (and
        gathers TP-partitioned ones) — ON THIS THREAD, so the train
        thread's boundary stays dispatch-only at every device count
        (pinned by tests/test_multichip.py's zero-fetch test)."""
        t0 = time.perf_counter()
        host = jax.device_get(tree)  # host-sync-ok: snapshot thread — the transfer this engine exists to absorb
        self._tel.gauge("snapshot/d2h_ms").set(
            (time.perf_counter() - t0) * 1e3
        )
        return host

    @property
    def last_published(self) -> int:
        """Highest version ever handed to the fanout (the rollback
        audit's published-floor evidence — train/learner.py; rollback
        keeps the version counter monotone, so the floor never needs a
        rewind)."""
        # lint-ok: thread-ownership(rollback reads this only after drain()
        # returned — the engine thread is provably idle at that point)
        return self._last_published

    def _do_publish(self, params: Any, version: int) -> None:
        if self._health is not None and self._health.unhealthy is not None:
            # contain: a flagged step's params never reach the wire; the
            # fanout keeps serving the last good version until rollback
            self._tel.counter("health/publish_blocked_total").inc()
            logger.warning(
                "snapshot: publish of version %d BLOCKED — training "
                "health latched unhealthy (%s at step %d); actors keep "
                "the last good weights",
                version, self._health.unhealthy.reason,
                self._health.unhealthy.step,
            )
            return
        if version <= self._last_published:
            return  # stale re-submit (drain/tail overlap): monotonic wins
        from dotaclient_tpu.transport.serialize import encode_weights

        host = self._fetch(params)
        trace_blob = None
        if self._tracer is not None:
            # publish-side trace record (ISSUE 12): stamped AFTER the
            # fetch so the hop dates the moment the version hits the
            # fanout, which is what actor-apply lag is measured against
            rec = tracing.weights_record(version)
            trace_blob = tracing.record_to_blob(rec, pad=False)
            self._tracer.emit("publish", version=version)
        msg = encode_weights(
            host, version, wire_dtype=self._wire_dtype, trace=trace_blob
        )
        with self._tel.span("transport/publish_weights"):
            self._transport.publish_weights(msg)
        self._last_published = version

    def _do_checkpoint(self, state: Any, config: Any) -> None:
        healthy = True
        if self._health is not None:
            if self._health.unhealthy is not None:
                # contain: a poisoned TrainState must not enter the rolling
                # retention (it would eventually GC the last healthy save —
                # the exact failure mode ISSUE 6 exists to close)
                self._tel.counter("health/checkpoints_blocked_total").inc()
                logger.warning(
                    "snapshot: periodic checkpoint BLOCKED — training "
                    "health latched unhealthy; awaiting rollback",
                )
                return
            healthy = self._health.cfg.enabled
        host = self._fetch(
            {
                "step": state.step,
                "version": state.version,
                "params": state.params,
                "opt_state": state.opt_state,
            }
        )
        # periodic cadence (force=False): I/O failures degrade to the
        # checkpoint/save_failures_total counter inside save_host — exactly
        # the policy a sync periodic save follows. With the guardian on,
        # every verdict <= this state's step has already been folded (the
        # stats backlog precedes this job), so a save that reaches here is
        # health-verified: mirror it into the last_good slot.
        self._ckpt.save_host(host, config, force=False, mark_good=healthy)

    def _do_metrics(
        self, device_tree: Any, finish: Callable[[Any], None]
    ) -> None:
        with self._tel.span("learner/metrics_fetch"):
            host = self._fetch(device_tree)
        finish(host)
