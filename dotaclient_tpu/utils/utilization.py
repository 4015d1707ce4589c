"""Pipeline utilization plane: always-on phase accounting (ISSUE 16).

Attributes every wall-clock second of a process to one of a CLOSED set of
phases via monotonic interval accumulation at the phase boundaries the
code already has — no sampling (PR 12's tracing samples 1/N chunks and
merges offline; this plane is the always-on complement), no extra device
work, no per-step host syncs. Each process class gets its own taxonomy:

* **learner** — ``dispatch_inflight`` (the donated train step: measured
  as the host time inside the dispatch call, which in a throughput-bound
  loop blocks on donation/back-pressure and is the host-observable proxy
  for device busy time), ``ingest_wait`` (buffer below min consumable),
  ``gather`` (batch staging/assembly), ``advantage_pass`` (consume-time
  value+GAE host dispatch), ``publish_stall``, ``checkpoint_stall``, and
  the residual ``host_other``. Duty cycle = the dispatch_inflight
  fraction of the fold window.
* **actor pools** (host + vec) — ``env_step`` / ``featurize`` /
  ``encode`` / ``ship_wait`` + residual ``other``.
* **serve** — ``window_wait`` / ``dispatch`` / ``reply`` + residual
  ``other`` on the batcher thread.

Fractions are normalized by the fold window so they sum to 1.0 by
construction (the residual absorbs unattributed time; clock noise is
clamped). The learner fold additionally maintains a rolling steps/s EMA
and a slow warmup-armed baseline EMA: ``util/throughput_regression``
latches to 1 while the fast EMA drops below ``REGRESSION_RATIO`` × the
baseline — the cross-run perf-regression sentinel two alert rules watch
(``learner_duty_cycle_low``, ``throughput_regression``; see
``utils/alerts.py`` and docs/OPERATIONS.md).

Every factory eager-creates its ``util/*`` gauges so
``check_telemetry_schema.py --require-utilization`` validates ANY
learner JSONL deterministically, then returns its accountant: the plane
is always on and has no switch. ``util/duty_cycle`` initializes to the
neutral 1.0 (and ``util/armed`` to 0) so the duty-cycle alert cannot fire
before the first fold arms the plane. What the accounting costs a
dispatch is not measured on chip (ROADMAP.md S7).
"""

from __future__ import annotations

import math
import time
from typing import Dict, Optional, Tuple

from dotaclient_tpu.utils import telemetry

# steps/s smoothing: the fast EMA tracks the current regime, the slow
# baseline EMA remembers the run's demonstrated throughput. Both are
# TIME-CONSTANT weighted (alpha = 1 - exp(-window / tau)): fold windows
# on the fused path vary from milliseconds (host racing ahead of async
# dispatches) to seconds (blocked on donation), and a fixed per-sample
# alpha would let a 20 ms window's wild rate swing the EMA as hard as a
# 10 s one. Warmup seeds both EMAs with the CUMULATIVE rate over the
# whole warmup span for the same reason; the sentinel arms only after
# WARMUP_WINDOWS folds and trips when fast < ratio * slow.
EMA_TAU_S = 30.0
BASELINE_TAU_S = 600.0
WARMUP_WINDOWS = 3
REGRESSION_RATIO = 0.7

LEARNER_PHASES = (
    "dispatch_inflight",
    "ingest_wait",
    "gather",
    "advantage_pass",
    "publish_stall",
    "checkpoint_stall",
    "host_other",
)
ACTOR_PHASES = ("env_step", "featurize", "encode", "ship_wait", "other")
SERVE_PHASES = ("window_wait", "dispatch", "reply", "other")


def ensure_learner_keys(reg: telemetry.Registry) -> Dict[str, telemetry.Gauge]:
    """Eager-create the learner-side ``util/*`` gauges; returns handles.

    Called at construction, so the schema tier holds for any learner
    JSONL. Key names are literal (the telemetry-drift lint
    statically resolves every emission)."""
    handles: Dict[str, telemetry.Gauge] = {}
    for key in (
        "util/armed",
        "util/duty_cycle",
        "util/steps_per_sec_ema",
        "util/steps_per_sec_baseline",
        "util/throughput_regression",
        "util/phase/dispatch_inflight",
        "util/phase/ingest_wait",
        "util/phase/gather",
        "util/phase/advantage_pass",
        "util/phase/publish_stall",
        "util/phase/checkpoint_stall",
        "util/phase/host_other",
    ):
        handles[key] = reg.gauge(key)
    # neutral until the first fold: an eager-created 0.0 would trip the
    # learner_duty_cycle_low rule before any accounting happened
    if handles["util/armed"].value == 0.0:
        handles["util/duty_cycle"].set(1.0)
    return handles


def ensure_actor_keys(reg: telemetry.Registry) -> Dict[str, telemetry.Gauge]:
    """Eager-create the actor-pool ``util/actor/*`` phase gauges."""
    handles: Dict[str, telemetry.Gauge] = {}
    for key in (
        "util/actor/env_step",
        "util/actor/featurize",
        "util/actor/encode",
        "util/actor/ship_wait",
        "util/actor/other",
    ):
        handles[key] = reg.gauge(key)
    return handles


def ensure_serve_keys(reg: telemetry.Registry) -> Dict[str, telemetry.Gauge]:
    """Eager-create the serve-side ``util/serve/*`` phase gauges."""
    handles: Dict[str, telemetry.Gauge] = {}
    for key in (
        "util/serve/window_wait",
        "util/serve/dispatch",
        "util/serve/reply",
        "util/serve/other",
    ):
        handles[key] = reg.gauge(key)
    return handles


class PhaseAccountant:
    """Monotonic interval accumulator over a closed phase set.

    Single-thread owned (the lint ownership map pins which thread):
    ``phase()`` adds a measured interval to a named bucket, ``fold()``
    normalizes the window into per-phase fraction gauges — the residual
    phase absorbs whatever the buckets did not claim — and resets. The
    fractions sum to 1.0 by construction: the denominator is
    ``max(window, accounted)``, so clock noise (accounted microseconds
    past the window edge) shrinks the residual to 0 instead of pushing
    the sum past 1."""

    def __init__(
        self,
        gauges: Dict[str, telemetry.Gauge],
        phases: Tuple[str, ...],
        residual: str,
        now: Optional[float] = None,
    ) -> None:
        self._gauges = gauges
        self._phases = phases
        self._residual = residual
        self._acc: Dict[str, float] = {p: 0.0 for p in phases}
        self._window_start = time.perf_counter() if now is None else now

    def phase(self, name: str, seconds: float) -> None:
        if seconds > 0.0:
            self._acc[name] += seconds

    def fold(
        self, now: Optional[float] = None
    ) -> Tuple[Dict[str, float], float]:
        """→ (phase fractions, window seconds); resets the window."""
        now = time.perf_counter() if now is None else now
        window = now - self._window_start
        if window <= 0.0:
            return {}, 0.0
        accounted = sum(self._acc.values())
        residual_s = max(0.0, window - accounted)
        denom = max(window, accounted)
        fractions: Dict[str, float] = {}
        for name in self._phases:
            v = residual_s if name == self._residual else self._acc[name]
            frac = v / denom
            self._gauges[name].set(frac)
            fractions[name] = frac
            self._acc[name] = 0.0
        self._window_start = now
        return fractions, window


class LearnerUtilization:
    """The learner's accountant + the throughput sentinel state.

    ``fold(step)`` runs at the existing host-sync boundaries (the
    ``_publish_pipeline_gauges`` sites — log_every cadence and the final
    flush), so the plane adds zero per-step host work beyond interval
    arithmetic."""

    def __init__(self, handles: Dict[str, telemetry.Gauge]) -> None:
        phase_gauges = {
            p: handles[f"util/phase/{p}"] for p in LEARNER_PHASES
        }
        self._acct = PhaseAccountant(
            phase_gauges, LEARNER_PHASES, residual="host_other"
        )
        self._armed = handles["util/armed"]
        self._duty = handles["util/duty_cycle"]
        self._ema = handles["util/steps_per_sec_ema"]
        self._baseline = handles["util/steps_per_sec_baseline"]
        self._regression = handles["util/throughput_regression"]
        self._last_step: Optional[int] = None
        self._ema_v = 0.0
        self._baseline_v = 0.0
        self._windows = 0
        self._warm_steps = 0
        self._warm_span = 0.0

    def phase(self, name: str, seconds: float) -> None:
        self._acct.phase(name, seconds)

    def fold(
        self, step: int, now: Optional[float] = None
    ) -> Dict[str, float]:
        fractions, window = self._acct.fold(now)
        if not fractions:
            return {}
        self._duty.set(fractions["dispatch_inflight"])
        self._armed.set(1.0)
        # step must have ADVANCED: a zero-step window only happens when a
        # boundary double-folds (the end-of-run flush re-folding at the
        # final step) — a rate-0 sample there would poison the EMA and
        # spuriously latch the sentinel. A genuinely wedged learner never
        # reaches a fold at all (the duty-cycle rule covers that mode).
        if self._last_step is not None and step > self._last_step:
            rate = (step - self._last_step) / window
            self._windows += 1
            if self._windows <= WARMUP_WINDOWS:
                # warmup: both EMAs track the cumulative rate over the
                # whole warmup span — duration-weighted by construction,
                # so a 20 ms host-racing window cannot arm the baseline
                # at an anomalous regime; the sentinel stays disarmed
                # through compile transients either way
                self._warm_steps += step - self._last_step
                self._warm_span += window
                self._ema_v = self._warm_steps / self._warm_span
                self._baseline_v = self._ema_v
            else:
                a_fast = 1.0 - math.exp(-window / EMA_TAU_S)
                a_slow = 1.0 - math.exp(-window / BASELINE_TAU_S)
                self._ema_v += a_fast * (rate - self._ema_v)
                self._baseline_v += a_slow * (rate - self._baseline_v)
            self._ema.set(self._ema_v)
            self._baseline.set(self._baseline_v)
            regressed = (
                self._windows > WARMUP_WINDOWS
                and self._baseline_v > 0.0
                and self._ema_v < REGRESSION_RATIO * self._baseline_v
            )
            self._regression.set(1.0 if regressed else 0.0)
        self._last_step = step
        return fractions


class PoolUtilization:
    """Actor/serve accountant: phase fractions + a cadence-gated fold
    (one monotonic compare per loop turn when due-check fails)."""

    def __init__(
        self,
        gauges: Dict[str, telemetry.Gauge],
        phases: Tuple[str, ...],
        prefix: str,
        interval_s: float,
    ) -> None:
        phase_gauges = {p: gauges[f"{prefix}{p}"] for p in phases}
        self._acct = PhaseAccountant(phase_gauges, phases, residual="other")
        self._interval = max(0.25, float(interval_s))  # host-sync-ok: host-only config scalar
        self._last_fold = time.perf_counter()

    def phase(self, name: str, seconds: float) -> None:
        self._acct.phase(name, seconds)

    def maybe_fold(
        self, now: Optional[float] = None
    ) -> Optional[Dict[str, float]]:
        now = time.perf_counter() if now is None else now
        if now - self._last_fold < self._interval:
            return None
        self._last_fold = now
        fractions, _ = self._acct.fold(now)
        return fractions or None


def make_learner(
    registry: Optional[telemetry.Registry] = None,
) -> LearnerUtilization:
    reg = registry if registry is not None else telemetry.get_registry()
    return LearnerUtilization(ensure_learner_keys(reg))


def make_actor(
    registry: Optional[telemetry.Registry] = None,
    interval_s: Optional[float] = None,
) -> PoolUtilization:
    reg = registry if registry is not None else telemetry.get_registry()
    handles = ensure_actor_keys(reg)
    itv = telemetry.fleet_interval_s if interval_s is None else interval_s
    return PoolUtilization(handles, ACTOR_PHASES, "util/actor/", itv)


def make_serve(
    registry: Optional[telemetry.Registry] = None,
    interval_s: Optional[float] = None,
) -> PoolUtilization:
    reg = registry if registry is not None else telemetry.get_registry()
    handles = ensure_serve_keys(reg)
    itv = telemetry.fleet_interval_s if interval_s is None else interval_s
    return PoolUtilization(handles, SERVE_PHASES, "util/serve/", itv)
