"""Pipeline tracing plane: cross-process trace context + device hooks.

Telemetry (``utils/telemetry.py``) answers *which stage is slow on
average* — per-process counters and span timers. It cannot answer the
questions every remaining ROADMAP direction hinges on: *where did THIS
chunk's latency go across five processes*, and *which hop ages the
weights an actor collects with* (IMPACT makes staleness a first-class
quantity; Podracer-style scaling lives on measured end-to-end
attribution — PAPERS.md). This module is that instrument:

* **Cross-process trace context.** A sampled rollout chunk (and every
  weights-publish frame) carries a compact trace record as one extra
  in-band entry on the existing ``__wire_cast__``-style marker
  discipline (``serialize._TRACE_MARKER``): origin pid/actor id, a
  unique trace id, the weights version at collect, and
  monotonic-clock epoch-aligned hop timestamps. The record is stamped
  at actor encode (hops ``collect``/``encode``) and extended host-side
  at every later hop — wire receive + CRC verify (one stamp, ``recv``:
  both lanes verify in the same pass), ingest decode (``consume``),
  buffer admission (``admit``), consume gather (``gather``; ring
  residency = gather − admit), and train dispatch (``dispatch``) — on
  both the socket and shm lanes, through both codecs. Serve
  request/reply frames carry the same record (``encode``→``recv``→
  ``reply``→``done``).

* **Clock alignment.** Every timestamp is ``time.monotonic()`` plus a
  per-process epoch offset captured at import, so intra-process deltas
  are monotonic-exact and cross-process joins are wall-clock-aligned.
  Same-host processes (the shm lane's whole premise, and the chaos
  harness topology) share one monotonic source modulo the offset
  capture jitter (µs); cross-host joins inherit NTP error — documented
  in docs/ARCHITECTURE.md "Pipeline tracing".

* **Lifecycle events** stream to a per-process JSONL trace log
  (``--trace-jsonl``), sampled via ``telemetry.trace_sample_n``.
  Records are enqueued LOCK-FREE on the hot path (a GIL-atomic deque
  append — the SnapshotEngine division of labor) and drained by one
  writer thread; when tracing is off the hot paths pay exactly one
  pointer test (``tracing.get() is None`` captured at construction —
  the ``utils/faults.py`` discipline, pinned by test).
  ``scripts/trace_report.py`` joins the logs of a learner+actors+serve
  run into per-chunk latency histograms, a critical-path breakdown,
  and a weight-staleness attribution table.

* **Device observability hooks.** :func:`instrument_jit` wraps the jit
  entry points the learner/buffer/serve own: per-program compile and
  retrace counters (``compile/<program>/...`` + the process-wide
  ``compile/{compiles,retraces}_total``), elapsed compile time, and,
  with a tracer configured, XLA cost analysis (flops / bytes accessed)
  logged ONCE per compile — never per step. What those wrappers cannot
  see (eager one-operation programs, a compile told from a cache load)
  comes from JAX's own events: :func:`ensure_metrics` installs ONE
  ``jax.monitoring`` listener pair a process behind
  ``compile/{trace_s,lower_s,backend_s,cache_load_s}_total``,
  ``compile/programs_total`` and ``compile/cache_{hits,misses}_total``.
  :func:`update_memory_gauges` reads
  ``jax.local_devices()`` memory stats into ``mem/hbm_peak_bytes``,
  degrading to 0 on backends (CPU) that report none.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from dotaclient_tpu.utils import telemetry

__all__ = [
    "Tracer",
    "TraceWriter",
    "configure",
    "get",
    "shutdown",
    "now",
    "ensure_metrics",
    "new_record",
    "record_to_blob",
    "parse_blob",
    "append_hop",
    "instrument_jit",
    "update_memory_gauges",
]

# Epoch-aligned monotonic clock: monotonic deltas within a process,
# wall-aligned across processes (captured once; see module docstring).
_EPOCH_OFFSET = time.time() - time.monotonic()


def now() -> float:
    """Epoch-aligned monotonic timestamp (seconds)."""
    return time.monotonic() + _EPOCH_OFFSET


# Wire blobs are padded to a fixed width so the native encoder's
# per-layout template cache (serialize._SPEC_CACHE keys on shapes) sees
# ONE traced layout per rollout structure instead of one per blob length.
TRACE_WIRE_LEN = 192

# JAX's own compile events (``jax.monitoring``) feed process-wide counters.
# A duration event adds its seconds; the backend event also counts the
# program (an XLA compile or, on a persistent-cache hit, the load), so after
# a warm-up ``compile/programs_total`` moving means "a step recompiled",
# whichever program it was: eager one-operation programs and uninstrumented
# jits included, which ``InstrumentedJit`` cannot see.
_listener_lock = threading.Lock()
_listener_installed = False


def _install_compile_listener() -> None:
    """One listener pair a process, however many learners are built, feeding
    the process-wide registry. Never what imports JAX: a jax-free tool that
    configures tracing compiles nothing to count."""
    global _listener_installed
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return
    with _listener_lock:
        if _listener_installed:
            return
        _listener_installed = True
    # counters are resolved by name at each event: compiles are rare, and a
    # registry cleared for test isolation must not orphan them
    reg = telemetry.get_registry()

    def on_seconds(event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            reg.counter("compile/trace_s_total").inc(duration)
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            reg.counter("compile/lower_s_total").inc(duration)
        elif event == "/jax/core/compile/backend_compile_duration":
            reg.counter("compile/backend_s_total").inc(duration)
            reg.counter("compile/programs_total").inc()
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            reg.counter("compile/cache_load_s_total").inc(duration)

    def on_event(event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            reg.counter("compile/cache_hits_total").inc()
        elif event == "/jax/compilation_cache/cache_misses":
            reg.counter("compile/cache_misses_total").inc()

    monitoring.register_event_duration_secs_listener(on_seconds)
    monitoring.register_event_listener(on_event)


def ensure_metrics(registry: Optional[telemetry.Registry] = None) -> None:
    """Eager-create the trace/compile/mem keys so
    `check_telemetry_schema.py --require-trace` validates any learner
    JSONL deterministically (zeros when nothing fired), and install the
    process's one ``jax.monitoring`` listener pair behind the seven
    whole-process compile counters."""
    reg = registry if registry is not None else telemetry.get_registry()
    for key in (
        "trace/emitted_total",
        "trace/dropped_total",
        "compile/compiles_total",
        "compile/retraces_total",
        "compile/compile_time_s_total",
        "compile/trace_s_total",
        "compile/lower_s_total",
        "compile/backend_s_total",
        "compile/programs_total",
        "compile/cache_load_s_total",
        "compile/cache_hits_total",
        "compile/cache_misses_total",
    ):
        reg.counter(key)
    reg.gauge("mem/hbm_peak_bytes")
    _install_compile_listener()


# -- trace records -----------------------------------------------------------
#
# Host form: {"tid": str, "pid": int, "actor": int, "wv": int,
#             "hops": [[name, ts], ...]}.
# Wire form: newline-joined ASCII, one header line then one line per hop,
# padded with spaces to TRACE_WIRE_LEN:
#     tid=<id> pid=<int> actor=<int> wv=<int>
#     h <name> <ts.6f>


def new_record(tid: str, actor: int, weights_version: int) -> Dict[str, Any]:
    return {
        "tid": tid,
        "pid": os.getpid(),
        "actor": int(actor),
        "wv": int(weights_version),
        "hops": [],
    }


def append_hop(
    record: Dict[str, Any], name: str, ts: Optional[float] = None
) -> Dict[str, Any]:
    record["hops"].append([name, now() if ts is None else ts])
    return record


def record_to_blob(record: Dict[str, Any], pad: bool = True) -> bytes:
    lines = [
        f"tid={record['tid']} pid={record['pid']} "
        f"actor={record['actor']} wv={record['wv']}"
    ]
    lines += [f"h {name} {ts:.6f}" for name, ts in record["hops"]]
    blob = "\n".join(lines).encode()
    if pad and len(blob) < TRACE_WIRE_LEN:
        blob = blob.ljust(TRACE_WIRE_LEN, b" ")
    return blob


def parse_blob(blob: Any) -> Optional[Dict[str, Any]]:
    """Wire blob → host record; None on anything unparseable (a corrupt
    trace entry must never take a consume path down)."""
    try:
        text = bytes(blob).decode("ascii", "replace")
    except (TypeError, ValueError):
        return None
    record: Optional[Dict[str, Any]] = None
    for line in text.split("\n"):
        line = line.strip()
        if not line:
            continue
        if line.startswith("tid="):
            fields = dict(
                kv.split("=", 1) for kv in line.split() if "=" in kv
            )
            try:
                record = {
                    "tid": fields["tid"],
                    "pid": int(fields["pid"]),
                    "actor": int(fields["actor"]),
                    "wv": int(fields["wv"]),
                    "hops": [],
                }
            except (KeyError, ValueError):
                return None
        elif line.startswith("h ") and record is not None:
            parts = line.split()
            if len(parts) == 3:
                try:
                    record["hops"].append([parts[1], float(parts[2])])
                except ValueError:
                    return None
    return record


def stamp_serve_recv(meta: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Serve-lane twin of :func:`stamp_wire_hops`: one ``recv`` stamp
    (receive + CRC verify happen in the same ``_recv_frame`` pass) on a
    decoded request's record."""
    record = parse_blob(meta.get("trace_blob"))
    if record is None:
        return None
    record["hops"].append(["recv", now()])
    meta["trace"] = record
    return record


def weights_record(version: int) -> Dict[str, Any]:
    """The publish-side trace record a weights frame carries (ISSUE 12):
    origin pid + a ``publish`` hop, so actor-side apply events can
    attribute fanout latency. ``actor=-1`` marks the learner origin."""
    rec = new_record(f"w{os.getpid():x}-{int(version):x}", -1, version)
    return append_hop(rec, "publish")


def stamp_wire_hops(
    meta: Dict[str, Any], recv_ts: Optional[float]
) -> Optional[Dict[str, Any]]:
    """Promote a decoded payload's raw in-band blob (``meta["trace_blob"]``)
    to the host record (``meta["trace"]``) and stamp the learner-side
    ingest hops: ``recv`` (transport receive + CRC verify — one stamp,
    both lanes verify in the same pass) and ``consume`` (drain decode).
    An unparseable blob is silently dropped — tracing must never take a
    consume path down."""
    record = parse_blob(meta.get("trace_blob"))
    if record is None:
        return None
    if recv_ts is not None:
        record["hops"].append(["recv", recv_ts])
    record["hops"].append(["consume", now()])
    meta["trace"] = record
    return record


# -- the writer thread -------------------------------------------------------


class TraceWriter:
    """Per-process trace-event sink: lock-free producer deque + ONE writer
    thread appending JSON lines (the SnapshotEngine division of labor —
    hot paths never touch the file). The queue is bounded: when the
    writer falls behind, NEW events drop (counted in
    ``trace/dropped_total``) — a wedged disk must never backpressure the
    train loop. Every drained batch is flushed line-complete, so a
    SIGKILL'd process (the chaos harness's bread and butter) tears at
    most the line the OS was mid-writing — which the shared
    torn-line-tolerant reader (``telemetry.load_jsonl``) drops."""

    MAX_QUEUE = 8192

    def __init__(
        self, path: str, registry: Optional[telemetry.Registry] = None
    ) -> None:
        reg = registry if registry is not None else telemetry.get_registry()
        self._emitted = reg.counter("trace/emitted_total")
        self._dropped = reg.counter("trace/dropped_total")
        # line-buffered: each write() is one complete line on disk
        self._f = open(path, "a", buffering=1)
        self._queue: deque = deque()
        self._stopped = False
        self._thread = threading.Thread(
            target=self._run, name="trace-writer", daemon=True
        )
        self._thread.start()

    def enqueue(self, event: Dict[str, Any]) -> None:
        """Hot-path entry: one length check + one GIL-atomic append."""
        if self._stopped:
            return
        if len(self._queue) >= self.MAX_QUEUE:
            self._dropped.inc()
            return
        self._queue.append(event)

    def _run(self) -> None:
        while True:
            drained = 0
            while self._queue:
                event = self._queue.popleft()
                try:
                    self._f.write(json.dumps(event, sort_keys=True) + "\n")
                except (OSError, ValueError, TypeError):
                    self._dropped.inc()
                    continue
                drained += 1
            if drained:
                self._emitted.inc(drained)
                try:
                    self._f.flush()
                except OSError:
                    pass
            if self._stopped and not self._queue:
                return
            time.sleep(0.05)

    def close(self, timeout: float = 5.0) -> None:
        """Drain and stop the writer, then close the file durably
        (flush + fsync — the atomic-close half of the JsonlSink
        durability contract)."""
        self._stopped = True
        self._thread.join(timeout)
        # lint-ok: thread-ownership(join() above — the writer thread has
        # provably exited before this thread touches the file)
        f = self._f
        try:
            f.flush()
            os.fsync(f.fileno())
        except (OSError, ValueError):
            pass
        try:
            f.close()
        except OSError:
            pass


class Tracer:
    """Sampling + event emission for one process."""

    def __init__(
        self,
        jsonl_path: Optional[str],
        sample_n: int,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        self.sample_n = max(1, int(sample_n))
        self._seq = 0
        self.pid = os.getpid()
        self._writer = (
            TraceWriter(jsonl_path, registry) if jsonl_path else None
        )

    def should_sample(self) -> bool:
        """One int increment + one modulo — the whole tracing-enabled
        hot-path cost for an unsampled chunk."""
        self._seq += 1
        return self._seq % self.sample_n == 0

    def next_tid(self, actor: int) -> str:
        return f"{self.pid:x}-{actor & 0xFFFF:x}-{self._seq:x}"

    def emit(self, event: str, **fields: Any) -> None:
        if self._writer is not None:
            self._writer.enqueue(
                {"ts": now(), "pid": self.pid, "event": event, **fields}
            )

    def emit_chunk(self, record: Dict[str, Any]) -> None:
        """One chunk's merged trace record (emitted at its terminal hop
        in this process)."""
        if self._writer is not None:
            self._writer.enqueue(
                {
                    "ts": now(),
                    "pid": self.pid,
                    "event": "chunk",
                    "tid": record["tid"],
                    "origin_pid": record["pid"],
                    "actor": record["actor"],
                    "wv": record["wv"],
                    # snapshot, not alias: the in-proc delivery path keeps
                    # appending hops to the live record after this emit,
                    # racing the writer thread's serialization otherwise
                    "hops": list(record["hops"]),
                }
            )

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


_ACTIVE: Optional[Tracer] = None


def get() -> Optional[Tracer]:
    """The process tracer, or None when tracing is off. Hot paths capture
    this ONCE at construction (the faults.get() discipline) so the
    disabled cost is a single ``is not None`` test."""
    return _ACTIVE


def configure(
    jsonl_path: Optional[str],
    sample_n: Optional[int] = None,
    registry: Optional[telemetry.Registry] = None,
) -> Optional[Tracer]:
    """Install (or, with ``jsonl_path=None``, remove) the process tracer.
    Call BEFORE constructing pools/buffers/learners — they capture
    ``get()`` at init. ``sample_n`` defaults to
    ``telemetry.trace_sample_n``."""
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.close()
        _ACTIVE = None
    if jsonl_path is None:
        return None
    ensure_metrics(registry)
    n = telemetry.trace_sample_n if sample_n is None else sample_n
    _ACTIVE = Tracer(jsonl_path, n, registry)
    return _ACTIVE


def shutdown() -> None:
    """Flush and close the process tracer (clean-exit paths)."""
    configure(None)


# -- device observability hooks ----------------------------------------------


class InstrumentedJit:
    """Transparent wrapper over a jitted callable counting compiles.

    Detection: ``jax.jit``'s C++ dispatch cache grows by one entry per
    compiled signature; comparing ``_cache_size()`` around the call
    costs two cheap host reads per dispatch and zero device traffic.
    On a compile (cache grew — or, when the backend exposes no cache
    probe, the wrapper's first call) the per-program and process-wide
    counters advance, elapsed time (trace + compile + first execution;
    compile dominates) is recorded, and, where a tracer is configured to
    read it, XLA cost analysis runs ONCE (span ``compile/cost_analysis``) —
    never per step, and never with tracing off. ``retraces`` = compiles
    beyond this wrapper's first (the "a shape bump recompiled the program"
    signal).

    Attribute access (``.lower``, ``._cache_size``) delegates to the
    wrapped function, so call sites that introspect the jit keep
    working.
    """

    def __init__(
        self,
        fn: Any,
        name: str,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        reg = registry if registry is not None else telemetry.get_registry()
        self._fn = fn
        self._name = name
        self._reg = reg
        self._seen = 0
        self._compiles = reg.counter("compile/compiles_total")
        self._retraces = reg.counter("compile/retraces_total")
        self._time = reg.counter("compile/compile_time_s_total")
        # per-program keys: program names are the finite set declared in
        # lint/telemetry_drift.py DYNAMIC_KEY_EXPANSIONS — add new names
        # there (and to the ARCHITECTURE wildcard row) when instrumenting
        # a new entry point
        self._p_compiles = reg.counter(f"compile/{name}/compiles_total")
        self._p_retraces = reg.counter(f"compile/{name}/retraces_total")
        self._p_last = reg.gauge(f"compile/{name}/last_compile_s")

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        n0 = self._cache_entries()
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        n1 = self._cache_entries()
        if (n1 is not None and n0 is not None and n1 > n0) or (
            n1 is None and self._seen == 0
        ):
            self._on_compile(time.perf_counter() - t0, args, kwargs)
        return out

    def _cache_entries(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:  # noqa: BLE001 - probe-free backends degrade
            return None

    def _on_compile(self, elapsed: float, args: tuple, kwargs: dict) -> None:
        self._seen += 1
        self._compiles.inc()
        self._p_compiles.inc()
        if self._seen > 1:
            self._retraces.inc()
            self._p_retraces.inc()
        self._time.inc(elapsed)
        self._p_last.set(elapsed)
        tracer = get()
        if tracer is not None:
            # a second trace and lowering of the whole program: it runs only
            # where its result has a reader, and under a span, so whoever
            # turns tracing on sees what it costs
            with self._reg.span("compile/cost_analysis"):
                flops, bytes_accessed = self._cost(args, kwargs)
            tracer.emit(
                "compile",
                program=self._name,
                n=self._seen,
                elapsed_s=round(elapsed, 6),
                flops=flops,
                bytes_accessed=bytes_accessed,
            )

    def _cost(self, args: tuple, kwargs: dict) -> Tuple[float, float]:
        """(flops, bytes accessed) by XLA's cost analysis, zeros where it
        cannot tell."""
        flops = bytes_accessed = 0.0
        try:
            # abstract re-trace only (no second backend compile); on a
            # donating program whose inputs were just consumed this can
            # raise on a deleted buffer — cost analysis then degrades to
            # zeros rather than ever touching the dispatch path
            cost = self._fn.lower(*args, **kwargs).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if isinstance(cost, dict):
                flops = float(cost.get("flops", 0.0) or 0.0)
                bytes_accessed = float(
                    cost.get("bytes accessed", 0.0) or 0.0
                )
        except Exception:  # noqa: BLE001 - analysis is best-effort
            pass
        return flops, bytes_accessed

    def __getattr__(self, item: str) -> Any:
        return getattr(object.__getattribute__(self, "_fn"), item)


def instrument_jit(
    fn: Any, name: str, registry: Optional[telemetry.Registry] = None
) -> InstrumentedJit:
    """Wrap a jitted callable with compile/retrace accounting. The
    donation lint (lint/donation.py) unwraps this call, so
    ``self.step = tracing.instrument_jit(jax.jit(..., donate_argnums=...),
    "step")`` keeps its use-after-donate tracking."""
    return InstrumentedJit(fn, name, registry)


def update_memory_gauges(
    registry: Optional[telemetry.Registry] = None,
) -> float:
    """Refresh ``mem/hbm_peak_bytes`` from the local devices' allocator
    stats (max peak across devices). Host-only metadata reads — safe at
    log-boundary cadence. ``memory_stats()`` is a dict on TPU and ``None``
    on CPU, where the gauge stays at its eager-created 0 (pinned by
    test)."""
    import jax  # deferred: jax-free tools import this module for its records

    reg = registry if registry is not None else telemetry.get_registry()
    peak = 0.0
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            peak = max(peak, float(stats.get("peak_bytes_in_use", 0)))
    gauge = reg.gauge("mem/hbm_peak_bytes")
    if peak:
        gauge.set(peak)
    return peak
