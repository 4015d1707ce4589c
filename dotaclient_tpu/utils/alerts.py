"""Runbook-encoded alert engine: the OPERATIONS.md failure table as code.

Every failure threshold the runbook documents — staleness spikes,
corrupt-frame streaks, buffer starvation, serve p99 blowups — used to be
prose a human had to notice after the fact. This module turns the runbook
into machinery (ISSUE 13): a declarative rule table over telemetry
registry values, evaluated on the fleet aggregator's thread at
``telemetry.fleet_interval_s`` cadence, with firing/resolving emitted as
structured ``ALERT`` JSONL events through the learner's metrics sink
(flush-per-emit, so a SIGKILL'd learner's last alerts survive).

Rule predicates (``AlertRule.kind``):

* ``threshold`` — compare the watched value against ``value`` with
  ``op`` (``>``/``<``/``>=``/``<=``).
* ``rate`` — rate of change of a (monotone) counter over ``window_s``
  seconds, compared ``> value`` per second. ``value=0`` means "any
  increase fires".
* ``stale`` — the watched key has not CHANGED for more than ``value``
  seconds (a heartbeat-shaped signal going quiet).

``for_s`` is the debounce: the condition must hold continuously that long
before the alert fires; a firing alert resolves at the first evaluation
where the condition clears. ``key`` may be an ``fnmatch`` pattern
(``fleet/*/serve/p99_latency_ms``) aggregated across matching keys with
``agg`` (``max`` for levels, ``sum`` for counters). A key with no data in
the snapshot is skipped — rules over planes a run does not exercise
(serve, fleet peers) stay silent instead of false-firing.

**Every rule carries a mandatory OPERATIONS.md runbook anchor**
(``rb:<name>``, a backticked token in the "Failure modes" table). The
``alert-drift`` pass of ``python -m dotaclient_tpu.lint`` cross-checks
BOTH ways: a rule can never point at a deleted runbook row, and every
documented failure mode must have a rule or an explicit entry in
``ALERT_WAIVERS`` naming why it is not machine-watchable. The "Alert
catalog" table in OPERATIONS.md mirrors this table row-for-row and is
checked against it too.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from dotaclient_tpu.utils import telemetry

__all__ = ["AlertRule", "AlertEngine", "RULES", "ALERT_WAIVERS"]


@dataclasses.dataclass(frozen=True)
class AlertRule:
    """One declarative runbook rule. All fields are literals by contract —
    the ``alert-drift`` lint pass reads them via AST, so a computed field
    would escape the rules↔runbook cross-check."""

    name: str              # stable id (the Alert catalog row key)
    key: str               # registry key or fnmatch pattern it watches
    kind: str              # "threshold" | "rate" | "stale"
    value: float           # threshold level / rate-per-sec bound / stale seconds
    op: str = ">"          # threshold comparison
    window_s: float = 60.0   # rate-of-change lookback
    for_s: float = 0.0     # condition must hold this long before firing
    agg: str = "max"       # pattern-key aggregation: "max" | "sum"
    severity: str = "warn"   # "warn" | "page"
    runbook: str = ""      # MANDATORY `rb:<anchor>` in docs/OPERATIONS.md
    summary: str = ""


# The shipped rule table: the existing runbook, encoded. Thresholds are
# deliberately conservative defaults — each row's full triage story lives
# at its runbook anchor, and the Alert catalog table in OPERATIONS.md
# mirrors this tuple (both machine-checked by the alert-drift lint pass).
RULES: Tuple[AlertRule, ...] = (
    AlertRule(
        # buffer/batch_staleness, not actor/weight_staleness: the engine
        # only runs in external-transport mode, where the learner has no
        # in-process pool and pins actor/weight_staleness to 0 — the
        # consume-time gauge is the signal that actually moves there
        "weight_staleness_high", key="buffer/batch_staleness",
        kind="threshold", op=">", value=64.0, for_s=10.0, severity="warn",
        runbook="rb:staleness-spike",
        summary="consumed batches trained on weights > 64 versions old",
    ),
    AlertRule(
        "fleet_peer_stale", key="fleet/peers_stale",
        kind="threshold", op=">", value=0.0, for_s=0.0, severity="page",
        runbook="rb:fleet-peer-stale",
        summary="a fleet peer stopped reporting metric snapshots",
    ),
    AlertRule(
        "corrupt_frame_rate", key="transport/frames_corrupt_total",
        kind="rate", value=0.02, window_s=30.0, severity="warn",
        runbook="rb:corrupt-frames",
        summary="wire frames failing CRC faster than background rate",
    ),
    AlertRule(
        "peer_quarantined", key="transport/peers_quarantined",
        kind="rate", value=0.0, window_s=60.0, severity="page",
        runbook="rb:corrupt-frames",
        summary="a peer was quarantined for a poison-frame streak",
    ),
    AlertRule(
        "buffer_starved", key="buffer/occupancy",
        kind="threshold", op="<", value=0.02, for_s=60.0, severity="warn",
        runbook="rb:buffer-starvation",
        summary="trajectory ring near-empty: the learner is starved",
    ),
    AlertRule(
        "nonfinite_ingest", key="buffer/nonfinite_rejected_total",
        kind="rate", value=0.0, window_s=60.0, severity="warn",
        runbook="rb:nonfinite-payload",
        summary="actors shipping NaN/Inf payloads (admission rejecting)",
    ),
    AlertRule(
        "intbound_ingest", key="buffer/intbound_rejected_total",
        kind="rate", value=0.0, window_s=60.0, severity="warn",
        runbook="rb:intbound-reject",
        summary="f32-wire actor exceeding the narrow ring's int bounds",
    ),
    AlertRule(
        "stale_ingest_rejections", key="buffer/stale_rejected_total",
        kind="rate", value=0.5, window_s=30.0, severity="warn",
        runbook="rb:stale-rejection",
        summary="ingest rejecting over-stale frames faster than churn",
    ),
    AlertRule(
        "health_latched", key="health/nonfinite_steps_total",
        kind="rate", value=0.0, window_s=60.0, severity="page",
        runbook="rb:divergence",
        summary="the in-graph health probe flagged a non-finite step",
    ),
    AlertRule(
        "checkpoint_save_failures", key="checkpoint/save_failures_total",
        kind="rate", value=0.0, window_s=120.0, severity="page",
        runbook="rb:disk-full",
        summary="periodic checkpoint saves degrading (disk/permissions)",
    ),
    AlertRule(
        "manifest_failures", key="checkpoint/manifest_failures_total",
        kind="rate", value=0.0, window_s=120.0, severity="page",
        runbook="rb:corrupt-checkpoint",
        summary="checkpoint integrity manifests failing verification",
    ),
    AlertRule(
        "snapshot_errors", key="snapshot/errors_total",
        kind="rate", value=0.0, window_s=60.0, severity="warn",
        runbook="rb:snapshot-failures",
        summary="async snapshot jobs (publish/metrics) failing",
    ),
    AlertRule(
        "weights_publish_stalled", key="transport/weights_published",
        kind="stale", value=120.0, severity="warn",
        runbook="rb:snapshot-failures",
        summary="no weights publish reached the transport for 2 minutes",
    ),
    AlertRule(
        "trace_drops", key="trace/dropped_total",
        kind="rate", value=1.0, window_s=30.0, severity="warn",
        runbook="rb:trace-drops",
        summary="trace writer falling behind: events dropped",
    ),
    AlertRule(
        "serve_p99_over_budget", key="fleet/*/serve/p99_latency_ms",
        kind="threshold", op=">", value=100.0, agg="max", for_s=10.0,
        severity="warn", runbook="rb:serve-latency",
        summary="a serve peer's p99 reply latency exceeds the budget",
    ),
    AlertRule(
        "reconnect_storm", key="fleet/*/transport/reconnects_total",
        kind="rate", value=0.5, window_s=30.0, agg="sum", severity="warn",
        runbook="rb:learner-crash",
        summary="fleet-wide reconnect storm: actors losing the learner",
    ),
    # -- outcome attribution plane (ISSUE 15; dotaclient_tpu/outcome/) --
    AlertRule(
        # the gauge initializes to the 0.5 NEUTRAL PRIOR and only moves
        # once a window holds OutcomeAggregator.min_episodes scripted
        # games, so runs that play no scripted bot can never false-fire
        "win_rate_collapse", key="outcome/win_rate/vs_scripted",
        kind="threshold", op="<", value=0.2, for_s=120.0, severity="page",
        runbook="rb:win-rate-collapse",
        summary="windowed win-rate vs scripted bots collapsed below 0.2",
    ),
    AlertRule(
        # derived binary set by the OutcomeAggregator (1 while the ARMED
        # window's p50 episode length sits below its floor — degenerate
        # instant-reset episodes); watching the binary instead of the raw
        # p50 keeps the unarmed zero state from false-firing
        "episode_len_anomaly", key="outcome/episode_len_anomaly",
        kind="threshold", op=">", value=0.0, for_s=60.0, severity="warn",
        runbook="rb:episode-len-anomaly",
        summary="median episode length degenerate: envs are churn-resetting",
    ),
    AlertRule(
        # −1 until the first episode ever arrives (arming), then seconds
        # since the fleet-wide episode total last advanced — fires only
        # when a previously-live outcome stream stops
        "outcome_stream_stale", key="outcome/stream_age_s",
        kind="threshold", op=">", value=90.0, for_s=0.0, severity="warn",
        runbook="rb:outcome-stale",
        summary="no completed-episode outcome reached the learner for 90 s",
    ),
    # -- pipeline utilization plane (ISSUE 16; utils/utilization.py) ----
    AlertRule(
        # util/duty_cycle initializes to the NEUTRAL 1.0 and only moves
        # once the first fold arms the plane, so a just-started learner
        # (or one with the accountant disabled) can never false-fire
        "learner_duty_cycle_low", key="util/duty_cycle",
        kind="threshold", op="<", value=0.1, for_s=120.0, severity="warn",
        runbook="rb:duty-cycle-low",
        summary="donated dispatch in flight under 10% of wall-clock",
    ),
    AlertRule(
        # binary sentinel set by the learner fold: 1 while the fast
        # steps/s EMA runs below REGRESSION_RATIO x the warmup-armed
        # baseline EMA — watching the latch instead of the raw EMA keeps
        # compile transients (baseline unarmed) from false-firing
        "throughput_regression", key="util/throughput_regression",
        kind="threshold", op=">", value=0.5, for_s=60.0, severity="warn",
        runbook="rb:throughput-regression",
        summary="learner steps/s EMA regressed below 0.7x its baseline",
    ),
    # -- serve-fleet failover (ISSUE 19; dotaclient_tpu/serve/router.py) -
    AlertRule(
        # the router's probe plane declares a backend DEAD only after the
        # router_dead_after_s grace window of failed reconnects — this
        # gauge is zero in every healthy fleet, so any nonzero value is a
        # page. Rules with no data are skipped, so learner registries
        # (no router/ keys) never evaluate it.
        "serve_peer_dead", key="router/backends_dead",
        kind="threshold", op=">", value=0.0, for_s=0.0, severity="page",
        runbook="rb:serve-peer-dead",
        summary="a serve backend is dead past the probe grace window",
    ),
    AlertRule(
        # every re-home is a state discontinuity for a live game (carry
        # reset, or a shadow-row transfer) — a nonzero rate means the
        # fleet is actively failing over and capacity planning should
        # hear about it even after the page resolves
        "sessions_rehomed_burst", key="router/sessions_rehomed_total",
        kind="rate", value=0.0, window_s=60.0, severity="warn",
        runbook="rb:sessions-rehomed",
        summary="sessions re-homing off dead serve backends",
    ),
)


# Documented failure modes with NO alert rule, by runbook anchor, each
# with the reason it is not machine-watchable from the learner's registry.
# The alert-drift lint pass fails when an anchor has neither a rule nor a
# waiver — and when a waiver goes stale (anchor deleted, or a rule now
# covers it). Keep this a PLAIN DICT LITERAL: the pass literal-evals it.
ALERT_WAIVERS: Dict[str, str] = {
    "rb:actor-death": (
        "supervisor-restarted churn is steady state; sustained silence "
        "pages via rb:fleet-peer-stale instead"
    ),
    "rb:graceful-drain": "clean-exit path; exit code is the signal",
    "rb:half-open-conn": (
        "idle drops auto-heal per connection; a fleet-wide stall also "
        "surfaces as rb:fleet-peer-stale silence"
    ),
    "rb:garbage-sender": (
        "covered by the rb:corrupt-frames rules (same counters)"
    ),
    "rb:crash-pending-snapshot": (
        "post-mortem signal read from the LAST line after death; nothing "
        "to watch while alive"
    ),
    "rb:stall-diagnostics": (
        "diagnostic gauge pair with no universal threshold; a human "
        "compares it with the run's own earlier windows"
    ),
    "rb:advantage-speedup": (
        "the runtime overlap fraction varies legitimately with consume "
        "patterns (serial consume-time passes are correct, just "
        "unoverlapped); a human compares it with the run's own earlier "
        "windows"
    ),
    "rb:divergence-exhausted": (
        "terminal non-zero exit is its own page; the precursor pages via "
        "rb:divergence"
    ),
    "rb:divergence-no-ckpt": (
        "config-time condition warned once at startup, not a runtime level"
    ),
    "rb:cross-process-latency": (
        "needs a traced run and trace_report's critical path; no single "
        "registry level encodes it"
    ),
    "rb:fused-lane-divisibility": (
        "construction-time ValueError before any compile; the process "
        "never reaches a runtime level to watch"
    ),
    "rb:serve-stuck-window": (
        "needs a cross-rate comparison (requests vs dispatches) the rule "
        "grammar deliberately excludes; p99 blowups page via "
        "rb:serve-latency"
    ),
    "rb:serve-version-skew": (
        "surfaces as the serve server's corrupt-frame/quarantine "
        "counters — rb:corrupt-frames covers the watchable half"
    ),
    "rb:serve-slots": (
        "capacity planning, not an incident: rejects are by design at "
        "the configured ceiling"
    ),
    "rb:lint-ci": "CI-time failure; never reachable from a running fleet",
    "rb:alerts-stuck": (
        "the alert plane cannot page on itself; operator row for reading "
        "alerts/active directly"
    ),
}


def _match_keys(pattern: str, snapshot: Mapping[str, float]) -> List[float]:
    import fnmatch

    return [
        v for k, v in snapshot.items()
        if v is not None and fnmatch.fnmatchcase(k, pattern)
    ]


class _RuleState:
    __slots__ = ("since", "active", "samples", "last_value", "last_change")

    def __init__(self) -> None:
        self.since: Optional[float] = None     # condition-true start
        self.active = False
        self.samples: deque = deque()          # (t, value) for rate rules
        self.last_value: Optional[float] = None  # for stale rules
        self.last_change: Optional[float] = None


class AlertEngine:
    """Evaluate the rule table against registry snapshots.

    Single-threaded by contract: ``evaluate`` runs on the fleet
    aggregator's thread (lint/ownership.py maps the aggregator; this
    engine is its private state). ``emit`` receives one dict per
    fire/resolve transition — the learner wires it to
    ``MetricsLogger.emit_event`` so ``ALERT`` events ride the metrics
    JSONL's flush-per-emit durability."""

    def __init__(
        self,
        rules: Optional[Tuple[AlertRule, ...]] = None,
        registry: Optional[telemetry.Registry] = None,
        emit: Optional[Callable[[Dict[str, object]], None]] = None,
    ) -> None:
        self.rules = RULES if rules is None else tuple(rules)
        for rule in self.rules:
            if not rule.runbook.startswith("rb:"):
                raise ValueError(
                    f"alert rule {rule.name!r} has no OPERATIONS.md runbook "
                    f"anchor (rb:<name>) — every rule must point operators "
                    f"at its triage row"
                )
        reg = registry if registry is not None else telemetry.get_registry()
        # eager-created so `check_telemetry_schema.py --require-fleet`
        # validates any learner JSONL deterministically
        for key in ("alerts/fired_total", "alerts/resolved_total"):
            reg.counter(key)
        reg.gauge("alerts/active")
        self._reg = reg
        self._emit = emit
        self._state: Dict[str, _RuleState] = {
            rule.name: _RuleState() for rule in self.rules
        }

    # -- predicate plumbing ------------------------------------------------

    def _observe(
        self, rule: AlertRule, snapshot: Mapping[str, float]
    ) -> Optional[float]:
        if "*" in rule.key or "?" in rule.key or "[" in rule.key:
            values = _match_keys(rule.key, snapshot)
            if not values:
                return None
            return sum(values) if rule.agg == "sum" else max(values)
        v = snapshot.get(rule.key)
        return None if v is None else float(v)

    def _condition(
        self, rule: AlertRule, st: _RuleState, value: float, now: float
    ) -> bool:
        if rule.kind == "threshold":
            return {
                ">": value > rule.value,
                "<": value < rule.value,
                ">=": value >= rule.value,
                "<=": value <= rule.value,
            }[rule.op]
        if rule.kind == "rate":
            if st.samples and value < st.samples[-1][1]:
                st.samples.clear()   # counter reset: restart the window
            st.samples.append((now, value))
            while st.samples and now - st.samples[0][0] > rule.window_s:
                st.samples.popleft()
            if len(st.samples) < 2:
                return False
            t0, v0 = st.samples[0]
            span = now - t0
            return span > 0 and (value - v0) / span > rule.value
        if rule.kind == "stale":
            if st.last_value is None or value != st.last_value:
                st.last_value = value
                st.last_change = now
                return False
            return (
                st.last_change is not None
                and now - st.last_change > rule.value
            )
        raise ValueError(f"unknown alert rule kind {rule.kind!r}")

    # -- the evaluation tick -----------------------------------------------

    def evaluate(
        self,
        snapshot: Optional[Mapping[str, float]] = None,
        now: Optional[float] = None,
    ) -> Tuple[List[str], List[str]]:
        """One evaluation pass; returns (fired rule names, resolved rule
        names). ``now`` is injectable for the debounce/rate tests.

        The default snapshot is counters + gauges only — rules never
        address timer-stat leaves, and skipping them keeps a tick at
        microseconds where a full ``Registry.snapshot()`` pays every
        timer's stat computation."""
        if snapshot is None:
            counters, gauges = self._reg.counters_and_gauges()
            snapshot = {**counters, **gauges}
        if now is None:
            now = time.monotonic()
        fired: List[str] = []
        resolved: List[str] = []
        active = 0
        for rule in self.rules:
            st = self._state[rule.name]
            value = self._observe(rule, snapshot)
            cond = (
                self._condition(rule, st, value, now)
                if value is not None
                else False
            )
            if cond:
                if st.since is None:
                    st.since = now
                if not st.active and now - st.since >= rule.for_s:
                    st.active = True
                    fired.append(rule.name)
                    self._reg.counter("alerts/fired_total").inc()
                    self._event(rule, "fired", value)
            else:
                st.since = None
                if st.active:
                    st.active = False
                    resolved.append(rule.name)
                    self._reg.counter("alerts/resolved_total").inc()
                    self._event(rule, "resolved", value)
            if st.active:
                active += 1
        self._reg.gauge("alerts/active").set(float(active))
        return fired, resolved

    def active_rules(self) -> List[str]:
        return [n for n, st in self._state.items() if st.active]

    def _event(
        self, rule: AlertRule, state: str, value: Optional[float]
    ) -> None:
        if self._emit is None:
            return
        self._emit(
            {
                "event": "ALERT",
                "state": state,
                "rule": rule.name,
                "severity": rule.severity,
                "runbook": rule.runbook,
                "key": rule.key,
                "kind": rule.kind,
                "value": None if value is None else float(value),
                "threshold": rule.value,
                "summary": rule.summary,
            }
        )
