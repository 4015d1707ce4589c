"""Dependency-free pipeline telemetry: one registry, per-stage spans, sinks.

The actor→transport→buffer→learner pipeline is only as fast as its slowest
stage, and Podracer-style scaling work (PAPERS.md, arXiv:2104.06272) starts
from per-stage throughput accounting; IMPACT (arXiv:1912.00167) adds that
actor-side weight *staleness* must be tracked for async correctness. This
module is the shared instrument panel: every layer records into one process-
wide :class:`Registry`, and the learner's ``MetricsLogger`` (utils/metrics.py,
now a facade over this registry) drains it to pluggable sinks.

Primitives
----------
* :class:`Counter` — monotone count (``inc``); rates are derived by diffing
  consecutive JSONL lines.
* :class:`Gauge` — last-write-wins level (``set``): queue depth, buffer
  occupancy, weight-version staleness.
* :class:`Timer` — duration accumulator with EMA, mean, last, and an
  approximate power-of-two-bucket histogram (``p95_s``).
* ``Registry.span("stage")`` — context manager timing a pipeline stage into
  the timer ``span/<stage>``; spans NEST via a per-thread stack
  (``span("a")`` inside ``span("b")`` records ``span/b/a``). Where JAX is
  loaded the span is also a ``jax.profiler.TraceAnnotation`` under its full
  name: with a profiler session on (``--profile-dir``, the benchmark's
  ``--trace 1``) every span of every thread is an event of ``/host:CPU`` on
  the device trace's clock; with none the annotation is a flag test.

Everything here is host-side wall clock — recording a span never touches the
device, so the learner's "no host↔device sync except at ``log_every``"
discipline is preserved by construction. This module never imports JAX:
jax-free tools keep the timers and get no annotation.

Snapshot key schema (the JSONL contract; see docs/ARCHITECTURE.md
"Observability" and scripts/check_telemetry_schema.py):

* counters / gauges: ``<name>`` → float value
* timers: ``<name>/count``, ``/total_s``, ``/last_s``, ``/mean_s``,
  ``/ema_s``, ``/p95_s``
* spans are timers named ``span/<stage>``

Pipeline stage names wired in this repo: ``actor/step``, ``actor/infer``,
``actor/collect``, ``actor/drain``, ``transport/consume``,
``transport/publish_weights``, ``buffer/stage`` (host-row staging into the
reused ingest lanes), ``buffer/insert``, ``buffer/sample``,
``learner/consume``, ``learner/assemble``, ``learner/dispatch``,
``learner/metrics_fetch``, ``learner/prefetch`` (batch N+1's
drain+stage+scatter+gather, issued behind batch N's in-flight dispatch),
``league/evaluate``; the fused loop's own (ISSUE 24): ``learner/iteration``
(one pass of the loop, ``step=``), ``learner/league_draw``,
``learner/league_report``, ``learner/boundary`` and its children
``learner/boundary/<child>`` (the log boundary's host work),
``learner/checkpoint_submit``, and the snapshot thread's
``snapshot/stats_fetch``. The pipelined data path also reports two gauges:
``learner/prefetch_hit_rate`` (batches served from the prefetch lane /
batches served) and ``learner/overlap_fraction`` (prefetch host time spent
while a dispatch was in flight / all prefetch host time) — see
docs/ARCHITECTURE.md "Pipelined data path".

Zero-stall snapshot engine (ISSUE 5; docs/ARCHITECTURE.md "Zero-stall
snapshots"): ``snapshot/pending`` (engine job slots occupied),
``snapshot/d2h_ms`` (last batched device→host fetch on the snapshot
thread), ``snapshot/<kind>_coalesced`` for kind ∈ publish/checkpoint/
metrics (latest-wins replacements when the thread falls behind),
``snapshot/errors_total`` (jobs that failed without killing the engine),
``learner/publish_stall_ms`` (train-thread time lost to the last publish —
the on-device copy dispatch in async mode, the full fetch+encode+enqueue in
sync mode), and ``learner/stall_fraction`` (cumulative side-effect stall /
train() wall time). The engine records ``span/transport/publish_weights``
and ``span/learner/metrics_fetch`` from its own thread, keeping those keys
stable across modes.

Fault-tolerance counters (ISSUE 4; docs/OPERATIONS.md "Failure modes"):
``transport/frames_corrupt_total`` (CRC-failed wire frames dropped),
``transport/peers_quarantined`` (poison-frame streaks cut),
``transport/conn_idle_drops`` (half-open connections dropped),
``transport/heartbeats_sent``, ``transport/reader_exits``,
``checkpoint/save_failures_total`` (degraded periodic saves), and
``faults/injected_total`` (chaos-harness injections that actually fired).

Sinks: :class:`ConsoleSink` (prints only un-slashed legacy scalar keys, so
log lines stay readable), :class:`JsonlSink` (one JSON object per emit —
``{"ts", "step", "scalars"}`` — for headless/bench runs), and
:class:`TensorBoardSink` (tensorboardX when available; degrades to a
one-line warning when not).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, TextIO, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "Timer",
    "Registry",
    "ConsoleSink",
    "JsonlSink",
    "TensorBoardSink",
    "get_registry",
    "load_jsonl",
    "process_age_s",
    "trace_sample_n",
]

# Pipeline-tracing sample cadence (ISSUE 12; utils/tracing.py): with a
# trace log configured (``--trace-jsonl``), every Nth sampling decision —
# chunk encode, train dispatch, serve request — carries/emits a trace
# record; the rest pay one int test. 1 = trace everything (chaos runs,
# latency hunts); with tracing OFF the knob is never consulted at all
# (``tracing.get() is None`` is the whole hot-path cost). ``--trace-sample``
# overrides per process.
trace_sample_n = 16

# Fleet-health snapshot cadence (ISSUE 13; utils/fleet.py): actors and
# serve processes push one compact metric snapshot (counter totals + gauge
# values) upstream every this many seconds, and the learner-side
# FleetAggregator merges/evaluates at the same cadence. <= 0 disables the
# fanout (the aggregator's keys stay eager-created so schema tiers hold);
# ``--fleet-interval`` overrides per process. A peer silent for several
# intervals is itself a signal (``fleet/peers_stale``).
fleet_interval_s = 5.0


class Counter:
    """Monotone counter. ``inc`` is the only mutator."""

    __slots__ = ("value", "_lock")

    def __init__(self) -> None:
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Last-write-wins level (assignment is atomic under the GIL)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


# Histogram buckets: powers of two from 1 µs up; 36 buckets reach ~64 s,
# far past any sane stage latency. Bucket i covers [2^i, 2^(i+1)) µs.
_N_BUCKETS = 36
_BUCKET0_S = 1e-6


class Timer:
    """Duration accumulator: count/total/last, EMA, approximate p95.

    The EMA (alpha=0.2) is the responsive per-stage latency signal; the
    histogram answers "was that spike real" without storing samples.
    """

    __slots__ = ("count", "total", "last", "ema", "_buckets", "_lock")

    EMA_ALPHA = 0.2

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.last = 0.0
        self.ema = 0.0
        self._buckets = [0] * _N_BUCKETS
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self.count += 1
            self.total += seconds
            self.last = seconds
            self.ema = (
                seconds
                if self.count == 1
                else self.EMA_ALPHA * seconds + (1 - self.EMA_ALPHA) * self.ema
            )
            if seconds > 0:
                i = int(math.log2(max(seconds, _BUCKET0_S) / _BUCKET0_S))
                self._buckets[min(max(i, 0), _N_BUCKETS - 1)] += 1
            else:
                self._buckets[0] += 1

    @contextlib.contextmanager
    def time(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(time.perf_counter() - t0)

    def quantile(self, q: float) -> float:
        """Approximate quantile from the bucket upper bounds (within 2× of
        the true value — enough to separate 1 ms from 100 ms stalls)."""
        with self._lock:
            if not self.count:
                return 0.0
            target = q * self.count
            seen = 0
            for i, c in enumerate(self._buckets):
                seen += c
                if seen >= target:
                    return _BUCKET0_S * (2.0 ** (i + 1))
        return _BUCKET0_S * (2.0 ** _N_BUCKETS)

    def stats(self) -> Dict[str, float]:
        with self._lock:
            count, total, last, ema = self.count, self.total, self.last, self.ema
        return {
            "count": float(count),
            "total_s": total,
            "last_s": last,
            "mean_s": total / count if count else 0.0,
            "ema_s": ema,
            "p95_s": self.quantile(0.95),
        }


def _trace_annotation() -> Any:
    """``jax.profiler.TraceAnnotation`` from a JAX that is already loaded,
    ``None`` where none is: a span must never be what imports JAX."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return getattr(profiler, "TraceAnnotation", None)


class Registry:
    """Named counters/gauges/timers plus the nesting ``span`` timer.

    Create-or-get semantics: ``registry.counter("x")`` is cheap enough for
    call sites to re-resolve by name every time — no handles to thread
    through constructors. All mutation is thread-safe (the overlap-mode
    actor thread and the learner thread share one registry).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._timers: Dict[str, Timer] = {}
        self._span_stack = threading.local()

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge()
            return g

    def timer(self, name: str) -> Timer:
        with self._lock:
            t = self._timers.get(name)
            if t is None:
                t = self._timers[name] = Timer()
            return t

    @contextlib.contextmanager
    def span(self, stage: str, **attrs: Any) -> Iterator[None]:
        """Time one pipeline stage into ``span/<stage>`` and, where JAX is
        loaded, name it in the profiler's trace.

        A *bare* name (no "/") nests under the enclosing span via a
        per-thread stack — ``span("b")`` inside ``span("a")`` records
        ``span/a/b``. A name containing "/" is absolute: the documented
        pipeline stages ("buffer/insert", "learner/dispatch", ...) keep
        stable keys no matter which outer span the caller holds.

        ``attrs`` go to the trace event only (``step=`` ties the spans of
        one dispatch together; an event's parent is the event that
        encloses it on the same thread). The timer's key never holds them.
        """
        stack: List[str] = getattr(self._span_stack, "names", None) or []
        if "/" in stage or not stack:
            full = stage
        else:
            # stack entries are already full names — extend the innermost
            full = f"{stack[-1]}/{stage}"
        self._span_stack.names = stack + [full]
        annotation = _trace_annotation()
        t0 = time.perf_counter()
        try:
            with (
                annotation(full, **attrs) if annotation is not None
                else contextlib.nullcontext()
            ):
                yield
        finally:
            self.timer(f"span/{full}").observe(time.perf_counter() - t0)
            self._span_stack.names = stack

    def snapshot(self) -> Dict[str, float]:
        """Flatten every metric to ``name → float`` per the key schema."""
        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            timers = list(self._timers.items())
        out: Dict[str, float] = {}
        for name, c in counters:
            out[name] = c.value
        for name, g in gauges:
            out[name] = g.value
        for name, t in timers:
            for stat, v in t.stats().items():
                out[f"{name}/{stat}"] = v
        return out

    def counters_and_gauges(self) -> "Tuple[Dict[str, float], Dict[str, float]]":
        """Current counter totals and gauge values as two plain dicts —
        the fleet-health snapshot source (ISSUE 13; utils/fleet.py). Kept
        separate because the two kinds merge differently downstream:
        counters are delta-merged (a restarted pid must not double-count),
        gauges are last-write-wins. Timers are excluded — their stat
        leaves are derived, not mergeable."""
        with self._lock:
            return (
                {n: c.value for n, c in self._counters.items()},
                {n: g.value for n, g in self._gauges.items()},
            )

    def clear(self) -> None:
        """Drop every metric (test isolation)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._timers.clear()


# One process-wide registry: the pipeline layers (actor pools, transports,
# buffer) self-instrument against it so telemetry needs zero constructor
# plumbing; tests that want isolation construct their own Registry.
_GLOBAL = Registry()


def get_registry() -> Registry:
    return _GLOBAL


def process_age_s() -> float:
    """Seconds since the kernel started this process (``/proc/self/stat``
    start time against the boot clock, as the benchmark's
    ``harness/device.py`` reads it for ``setup_s``): the interpreter's start,
    every import and the accelerator runtime's start are in it. The learner
    sets ``startup/process_age_at_init_s`` from it at its constructor's
    first line; 0 where the kernel keeps no such record."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces: split after its ")"
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")   # field 22
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


# -- sinks -------------------------------------------------------------------


class ConsoleSink:
    """The legacy console line: only un-slashed keys print (telemetry keys
    all contain "/"), so per-step log lines stay the familiar short form."""

    def __init__(self, t0: Optional[float] = None) -> None:
        self._t0 = t0 if t0 is not None else time.time()

    def emit(self, step: int, scalars: Dict[str, float]) -> None:
        parts = " ".join(
            f"{k}={v:.4g}" for k, v in sorted(scalars.items()) if "/" not in k
        )
        print(f"[{time.time() - self._t0:8.1f}s] step {step}: {parts}", flush=True)

    def close(self) -> None:
        pass


def _json_safe(v: float) -> Optional[float]:
    # NaN/Inf are not JSON; a diverged loss must not corrupt the stream.
    return v if math.isfinite(v) else None


class JsonlSink:
    """Append one JSON object per emit: ``{"ts": <unix>, "step": <int>,
    "scalars": {name: number|null}}`` — the machine-readable record for
    headless/bench runs (non-finite values become null).

    Durability (ISSUE 12): the stream is line-buffered and every emit is
    ONE ``write`` of a complete line followed by a flush, so a SIGKILL'd
    process (the chaos harness's stock in trade) can tear at most the
    line the OS was mid-writing — never interleave two lines; ``close``
    fsyncs before closing. Readers go through :func:`load_jsonl`, which
    drops an unterminated trailing line instead of choking on it."""

    def __init__(self, path: str) -> None:
        self.path = path
        # Crash-mid-write repair (ISSUE 15 bugfix sweep): a SIGKILL'd
        # writer leaves a torn TRAILING line, which load_jsonl tolerates —
        # but a RESTARTED process appending to the same path (chaos
        # restarts, --restore relaunches reusing --metrics-jsonl) would
        # concatenate its first line onto the fragment, producing a
        # corrupt INTERIOR line no reader drops. Truncate the fragment
        # before appending: it was already unreadable.
        _seal_torn_tail(path)
        self._f: Optional[TextIO] = open(path, "a", buffering=1)
        self._lock = threading.Lock()

    def emit(self, step: int, scalars: Dict[str, float]) -> None:
        line = json.dumps(
            {
                "ts": time.time(),
                "step": int(step),
                "scalars": {k: _json_safe(float(v)) for k, v in scalars.items()},
            },
            sort_keys=True,
        )
        self._write_line(line)

    def emit_event(self, event: Dict[str, object]) -> None:
        """Append one structured event line (``{"ts", "event", ...}``) to
        the same stream as the metrics envelopes — the alert channel
        (ISSUE 13). Rides the SAME durability contract as :meth:`emit`
        (one write of a complete line + flush), so a SIGKILL'd learner's
        last ``ALERT`` events survive for the post-mortem. Readers
        (``scripts/check_telemetry_schema.py``, ``scripts/fleet_status.py``)
        dispatch on the ``event`` key."""
        self._write_line(
            json.dumps({"ts": time.time(), **event}, sort_keys=True)
        )

    def _write_line(self, line: str) -> None:
        with self._lock:
            if self._f is None:
                return
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                try:
                    self._f.flush()
                    os.fsync(self._f.fileno())
                except (OSError, ValueError):
                    pass  # durability is best-effort; close must not raise
                self._f.close()
                self._f = None


def _seal_torn_tail(path: str) -> None:
    """Drop an unterminated trailing fragment from an existing JSONL file
    (see :class:`JsonlSink`). Best-effort: a missing file or an
    unwritable one degrades to the reader-side torn-line tolerance."""
    try:
        with open(path, "rb+") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            if size == 0:
                return
            f.seek(size - 1)
            if f.read(1) == b"\n":
                return
            pos = size
            while pos > 0:
                step = min(4096, pos)
                f.seek(pos - step)
                data = f.read(step)
                idx = data.rfind(b"\n")
                if idx >= 0:
                    f.truncate(pos - step + idx + 1)
                    return
                pos -= step
            f.truncate(0)
    except FileNotFoundError:
        return
    except OSError:
        return


def load_jsonl(path: str) -> List[str]:
    """Read a JSONL file's COMPLETE lines, tolerating the one torn
    trailing line a SIGKILL can leave (no terminating newline → the
    write was cut mid-line → the line is dropped, never parsed). The
    shared reader for ``scripts/trace_report.py`` and
    ``scripts/check_telemetry_schema.py`` — both must survive a chaos
    harness's corpses (ISSUE 12). ``errors="replace"``: a write torn
    mid-UTF-8-sequence must not raise before the torn-tail drop below
    can even run (ISSUE 15 bugfix sweep)."""
    with open(path, "r", errors="replace") as f:
        text = f.read()
    if not text:
        return []
    complete = text.endswith("\n")
    lines = text.split("\n")
    if lines and lines[-1] == "":
        lines.pop()
    if lines and not complete:
        lines.pop()  # torn trailing line: mid-write at kill time
    return lines


class TensorBoardSink:
    """tensorboardX scalars; construct via :meth:`create`, which degrades to
    ``None`` with a one-line warning when tensorboardX is not installed
    (console/JSONL sinks keep working — the logdir request must never crash
    a training run in a slim image)."""

    def __init__(self, writer) -> None:
        self._writer = writer

    @classmethod
    def create(cls, logdir: str) -> Optional["TensorBoardSink"]:
        try:
            from tensorboardX import SummaryWriter
        except ImportError:
            print(
                f"WARNING: tensorboardX not installed — logdir {logdir!r} "
                f"ignored; continuing with console/JSONL sinks only",
                flush=True,
            )
            return None
        return cls(SummaryWriter(logdir))

    def emit(self, step: int, scalars: Dict[str, float]) -> None:
        for name, v in scalars.items():
            self._writer.add_scalar(name, v, step)

    def close(self) -> None:
        self._writer.close()
