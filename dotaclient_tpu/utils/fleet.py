"""Fleet health plane: in-band metrics fanout + learner-side aggregation.

Every process in the split topology (learner, N actors, serve) writes its
own metrics JSONL; nothing merged them while the run was alive, so "is
the fleet healthy RIGHT NOW" had no answer (ISSUE 13). This module closes
the loop over the lanes the fleet already has:

* **Snapshot frames.** Actors and serve processes push one compact
  metric snapshot — counter totals + gauge values from their telemetry
  registry, filtered to the fleet-relevant namespaces — upstream every
  ``telemetry.fleet_interval_s`` seconds, serialized through the
  EXISTING rollout codec (scalar float64 leaves under ``c/``/``g/``
  prefixes; peer identity rides the rollout header: pid in
  ``model_version``, peer id in ``env_id``, snapshot seq in
  ``rollout_id``, peer kind in ``length``). The frames ride a new wire
  frame kind on the shared CRC/quarantine discipline of BOTH transports
  (socket kind 5; shm: the length word's high bit) — a corrupt snapshot
  counts and streaks exactly like a corrupt rollout.

* **FleetPublisher** (actor/serve side). Captured ONCE at pool
  construction like the tracer (``fleet.get()`` — the faults.get()
  discipline): with the fanout off, the ship path pays a single pointer
  test; on, one monotonic-clock compare per call plus the snapshot
  encode at cadence.

* **FleetAggregator** (learner side). Transport reader threads hand it
  decoded snapshots (``ingest`` — parked under a lock); its OWN thread
  (graftlint OWNERSHIP-mapped) merges them at fleet cadence into
  per-peer keys (``fleet/<peer>/<metric>`` — counters delta-merged so a
  restarted pid never double-counts, gauges last-write-wins, plus a
  derived ``fleet/<peer>/env_fps`` rate) and fleet rollups
  (``fleet/agg/<metric>/{min,max,mean}`` across live peers). Peer
  death/silence is itself a signal: a peer quiet for
  ``stale_after_s`` shows in ``fleet/peers_stale``, which the
  ``fleet_peer_stale`` alert rule (utils/alerts.py) pages on. The alert
  engine evaluates on this same thread, so rule state never races.

All rollup and alert keys are eager-created at construction so
``check_telemetry_schema.py --require-fleet`` validates ANY learner
JSONL deterministically; per-peer keys are dynamic and documented as the
``fleet/<peer>/*`` wildcard family (declared in
lint/telemetry_drift.py DYNAMIC_KEY_EXPANSIONS).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from dotaclient_tpu.utils import telemetry

__all__ = [
    "FleetPublisher",
    "FleetAggregator",
    "encode_snapshot",
    "decode_snapshot",
    "peer_label",
    "configure",
    "get",
    "shutdown",
]

# Namespaces a peer ships in its snapshot — the compact subset that the
# fleet table and rollups feed on (span timers never ship: their stat
# leaves are derived, not mergeable). "outcome/" is the outcome
# attribution plane (ISSUE 15): episode outcomes ride the SAME snapshot
# frames — no new frame kind — and delta-merge per peer like every other
# counter.
SNAPSHOT_PREFIXES = (
    "actor/", "transport/", "serve/", "faults/", "trace/", "shm/",
    "outcome/", "util/",
)

# Peer kinds, indexed by the rollout header's `length` field. The peer
# label is `<kind initial><peer id>` — `a0`, `s7788` — a STABLE name
# across process restarts (actors key on their seed, serve servers on
# their listen port), so a supervisor restart updates the SAME peer row
# instead of leaking a new one — and the fleet_peer_stale page resolves
# on the fresh incarnation's first snapshot.
PEER_KINDS = ("actor", "serve")


def peer_label(kind: str, peer_id: int) -> str:
    """The ONE derivation of a fleet peer's stable label
    (``<kind initial><peer id>``: ``a0``, ``s7788``). Serve peers key on
    their listen port, so the serve-fleet router (ISSUE 19) can name a
    backend's fleet row — ``fleet/<label>/serve/p99_latency_ms`` — from
    nothing but the address it routes to."""
    return f"{kind[0]}{int(peer_id)}"

# Fleet rollups: metric name → (source kind, peer-side key). "gauge" =
# last value per peer, "counter" = delta-merged total per peer, "rate" =
# per-second rate of the named counter between snapshots.
AGG_SOURCES: Dict[str, Tuple[str, str]] = {
    "weight_staleness": ("gauge", "actor/weight_refresh_lag"),
    "env_fps": ("rate", "actor/env_steps"),
    "reconnects": ("counter", "transport/reconnects_total"),
    "corrupt_frames": ("counter", "transport/frames_corrupt_total"),
    # utilization plane (ISSUE 16): the actor-side ship stall fraction —
    # a fleet-wide climb means the learner-side ingest path (or the wire)
    # is the bottleneck, not the envs
    "ship_wait": ("gauge", "util/actor/ship_wait"),
}
AGG_STATS = ("min", "max", "mean")
# The 15 eager-created rollup gauges — keep in sync with the
# ("fleet/agg/", "") expansion in lint/telemetry_drift.py and the
# FLEET_KEYS tier in scripts/check_telemetry_schema.py.
AGG_KEYS = tuple(
    f"{metric}/{stat}" for metric in AGG_SOURCES for stat in AGG_STATS
)

# Snapshot payloads must fit the native codec's entry table
# (serialize._MAX_TENSORS = 64): cap the shipped leaves. The cut is
# deterministic AND priority-aware — fleet-critical operational keys
# (the rollup sources, liveness counters) are kept ahead of the outcome
# plane's keys, and within the outcome plane the episode-length
# histogram buckets go first: dropping a histogram tail degrades the
# p50's resolution, dropping transport/reconnects_total would blind an
# alert rule (pinned by test).
_MAX_SNAPSHOT_LEAVES = 60


def _cut_priority(name: str) -> int:
    if name.startswith("outcome/ep_len_hist/"):
        return 2
    if name.startswith("outcome/"):
        return 1
    return 0


# -- snapshot codec -----------------------------------------------------------


def encode_snapshot(
    peer_id: int,
    kind: str,
    seq: int,
    counters: Dict[str, float],
    gauges: Dict[str, float],
    pid: Optional[int] = None,
) -> bytes:
    """One metric snapshot → wire bytes, through the existing rollout
    codec (``encode_rollout_bytes``): each metric is a scalar float64
    leaf named ``c/<key>`` (counter total) or ``g/<key>`` (gauge value).
    Counter totals are CUMULATIVE — the aggregator delta-merges them
    receiver-side (the Prometheus counter pattern), which survives both
    lost frames and peer restarts."""
    from dotaclient_tpu.transport.serialize import encode_rollout_bytes

    flat: Dict[str, np.ndarray] = {}
    names = sorted(
        (n for n in (*counters, *gauges) if n.startswith(SNAPSHOT_PREFIXES)),
        key=lambda n: (_cut_priority(n), n),
    )[:_MAX_SNAPSHOT_LEAVES]
    keep = set(names)
    for name, v in counters.items():
        if name in keep:
            flat[f"c/{name}"] = np.float64(v)
    for name, v in gauges.items():
        if name in keep:
            flat[f"g/{name}"] = np.float64(v)
    payload = encode_rollout_bytes(
        flat,
        # pid override: tests exercise the restarted-incarnation
        # delta-merge without forking
        model_version=os.getpid() if pid is None else int(pid),
        env_id=int(peer_id),
        rollout_id=int(seq),
        length=PEER_KINDS.index(kind),
        total_reward=0.0,
    )
    return bytes(payload)


def decode_snapshot(payload: Any) -> Optional[Dict[str, Any]]:
    """Wire bytes → snapshot dict, or None on anything unparseable (a
    malformed snapshot must never take a reader thread down)."""
    from dotaclient_tpu.transport.serialize import (
        decode_rollout_bytes,
        flatten_tree,
    )

    try:
        meta, arrays = decode_rollout_bytes(payload)
        flat = flatten_tree(arrays)
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        for name, arr in flat.items():
            # scalar leaves; reshape(-1)[0] also accepts a 1-element
            # vector (numpy deprecation-proof either way)
            if name.startswith("c/"):
                counters[name[2:]] = float(np.asarray(arr).reshape(-1)[0])
            elif name.startswith("g/"):
                gauges[name[2:]] = float(np.asarray(arr).reshape(-1)[0])
        kind_idx = int(meta["length"])
        kind = (
            PEER_KINDS[kind_idx]
            if 0 <= kind_idx < len(PEER_KINDS)
            else "actor"
        )
        return {
            "peer": peer_label(kind, int(meta["env_id"])),
            "kind": kind,
            "pid": int(meta["model_version"]),
            "seq": int(meta["rollout_id"]),
            "counters": counters,
            "gauges": gauges,
        }
    except Exception:  # noqa: BLE001 - disposable-peer failure model
        return None


# -- the peer side ------------------------------------------------------------


class FleetPublisher:
    """Peer-side snapshot shipper. ``maybe_publish`` is the only hot-path
    entry: one monotonic compare per call, the encode+send only at
    cadence. Send errors propagate — on the actor they engage the same
    reconnect machinery as a failed rollout publish."""

    def __init__(
        self,
        peer_id: int,
        kind: str = "actor",
        interval_s: Optional[float] = None,
        registry: Optional[telemetry.Registry] = None,
    ) -> None:
        if kind not in PEER_KINDS:
            raise ValueError(f"unknown fleet peer kind {kind!r}")
        self.peer_id = int(peer_id)
        self.kind = kind
        self.interval_s = (
            telemetry.fleet_interval_s if interval_s is None else interval_s
        )
        self._reg = (
            registry if registry is not None else telemetry.get_registry()
        )
        # -inf, not 0: time.monotonic() counts from boot, so on a machine up
        # for less than interval_s a 0 start would swallow the first publish
        self._last = float("-inf")
        self._seq = 0

    def maybe_publish(self, transport: Any, force: bool = False) -> bool:
        now = time.monotonic()
        if not force and now - self._last < self.interval_s:
            return False
        publish = getattr(transport, "publish_metrics_bytes", None)
        if publish is None:
            return False   # lane without a metrics channel (AMQP, in-proc)
        self._last = now
        counters, gauges = self._reg.counters_and_gauges()
        publish(
            encode_snapshot(self.peer_id, self.kind, self._seq, counters, gauges)
        )
        self._seq += 1
        return True


_PUBLISHER: Optional[FleetPublisher] = None


def get() -> Optional[FleetPublisher]:
    """The process's fleet publisher, or None when the fanout is off.
    Pools capture this ONCE at construction (the faults.get()/tracing
    discipline) so the disabled cost is a single ``is not None`` test."""
    return _PUBLISHER


def configure(
    peer_id: int,
    kind: str = "actor",
    interval_s: Optional[float] = None,
    registry: Optional[telemetry.Registry] = None,
) -> Optional[FleetPublisher]:
    """Install the process publisher (call BEFORE constructing pools —
    they capture ``get()`` at init). ``interval_s`` defaults to
    ``telemetry.fleet_interval_s``; <= 0 removes the publisher."""
    global _PUBLISHER
    iv = telemetry.fleet_interval_s if interval_s is None else interval_s
    if iv is None or iv <= 0:
        _PUBLISHER = None
        return None
    _PUBLISHER = FleetPublisher(peer_id, kind, iv, registry)
    return _PUBLISHER


def shutdown() -> None:
    global _PUBLISHER
    _PUBLISHER = None


# -- the learner side ---------------------------------------------------------


class _PeerState:
    """Aggregator-thread-private view of one peer."""

    __slots__ = (
        "pid", "kind", "last_seen", "last_raw", "totals", "gauges",
        "rate_samples",
    )

    def __init__(self, kind: str) -> None:
        self.pid = 0
        self.kind = kind
        self.last_seen = 0.0
        # raw cumulative counter values of the CURRENT pid (delta base)
        self.last_raw: Dict[str, float] = {}
        # restart-corrected accumulated totals across incarnations
        self.totals: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.rate_samples: deque = deque()   # (t, actor/env_steps total)


class FleetAggregator:
    """Learner-side merge + alert evaluation.

    Thread split (graftlint OWNERSHIP map, lint/ownership.py):

    * ``ingest`` runs on transport READER threads (socket) or the
      learner's consume thread (shm drain) — it only decodes and parks
      the snapshot in ``_inbox`` under ``_lock``;
    * ``tick``/``_merge``/``_rollup`` and every touch of ``_peers`` and
      the alert engine run on THIS aggregator's own thread (``start``),
      at ``interval_s`` cadence — rule state never races the readers;
    * everything the rest of the process reads goes through the
      (thread-safe) telemetry registry, never this object's state.

    Construction alone eager-creates every ``fleet/``+``alerts/`` tier
    key; ``start()`` is only called when a fleet can actually report
    (the learner's external-transport modes)."""

    def __init__(
        self,
        registry: Optional[telemetry.Registry] = None,
        interval_s: Optional[float] = None,
        stale_after_s: Optional[float] = None,
        forget_after_s: float = 300.0,
        emit_event: Optional[Callable[[Dict[str, object]], None]] = None,
        rules: Optional[tuple] = None,
    ) -> None:
        from dotaclient_tpu.utils.alerts import AlertEngine

        self._reg = (
            registry if registry is not None else telemetry.get_registry()
        )
        self.interval_s = max(
            0.05,
            telemetry.fleet_interval_s
            if interval_s is None
            else float(interval_s),
        )
        # silence hysteresis: several missed snapshots, floored so a slow
        # host's jittery publish cadence cannot flap the stale gauge
        self.stale_after_s = (
            max(4.0 * self.interval_s, 6.0)
            if stale_after_s is None
            else float(stale_after_s)
        )
        self.forget_after_s = float(forget_after_s)
        self._lock = threading.Lock()
        self._inbox: List[Tuple[float, Dict[str, Any]]] = []
        self._peers: Dict[str, _PeerState] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # per-tick hooks (the outcome aggregator, ISSUE 15): run after the
        # merge/rollup but BEFORE alert evaluation, so rules watch gauges
        # the hook just refreshed. Registered at construction time (before
        # start()); the hook itself must be thread-safe — the outcome
        # aggregator locks internally because in-process modes tick it
        # from the train thread instead.
        self._tick_hooks: List[Callable[[], None]] = []
        # eager keys (schema tier determinism — the --require-fleet
        # contract holds for ANY learner JSONL, fleet traffic or not)
        for key in ("fleet/snapshots_total", "fleet/bad_snapshots_total"):
            self._reg.counter(key)
        for key in ("fleet/peers", "fleet/peers_stale"):
            self._reg.gauge(key)
        for name in AGG_KEYS:
            self._reg.gauge(f"fleet/agg/{name}")
        self._engine = AlertEngine(
            rules=rules, registry=self._reg, emit=emit_event
        )

    # -- reader-thread surface --------------------------------------------

    def ingest(self, payload: Any, recv_ts: Optional[float] = None) -> bool:
        """Decode one metrics frame and park it for the aggregator thread.
        Runs on whatever thread drained the wire; a malformed payload is
        counted and dropped, never raised."""
        snap = decode_snapshot(payload)
        if snap is None:
            self._reg.counter("fleet/bad_snapshots_total").inc()
            return False
        self._reg.counter("fleet/snapshots_total").inc()
        ts = time.monotonic() if recv_ts is None else recv_ts
        with self._lock:
            self._inbox.append((ts, snap))
        return True

    # -- aggregator-thread surface ----------------------------------------

    def add_tick_hook(self, hook: Callable[[], None]) -> None:
        """Register a callable run every tick between rollup and alert
        evaluation (call BEFORE start(); see ``_tick_hooks``)."""
        self._tick_hooks.append(hook)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="fleet-aggregator", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception as e:  # noqa: BLE001 - aggregation must not die
                import warnings

                warnings.warn(f"fleet aggregator tick failed: {e}")

    def tick(self, now: Optional[float] = None) -> None:
        """One merge + rollup + alert-evaluation pass (public for tests;
        production calls come from ``_run``)."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            batch, self._inbox = self._inbox, []
        for recv_ts, snap in batch:
            self._merge(recv_ts, snap)
        self._rollup(now)
        for hook in self._tick_hooks:
            hook()
        # counters + gauges only: rules never address timer-stat leaves,
        # and the full registry snapshot() computes every timer's stats —
        # measured ~3 ms on a populated registry vs µs for this view
        counters, gauges = self._reg.counters_and_gauges()
        self._engine.evaluate({**counters, **gauges}, now)

    def _peer_counter(self, key: str, delta: float) -> None:
        self._reg.counter(f"fleet/{key}").inc(delta)

    def _peer_gauge(self, key: str, value: float) -> None:
        self._reg.gauge(f"fleet/{key}").set(value)

    def _merge(self, recv_ts: float, snap: Dict[str, Any]) -> None:
        label = snap["peer"]
        st = self._peers.get(label)
        if st is None:
            st = self._peers[label] = _PeerState(snap["kind"])
        if st.pid != snap["pid"]:
            # restarted incarnation: its cumulative counters start from
            # zero, so the delta base resets — the old pid's totals are
            # already folded in and must NOT be re-added (pinned by test)
            st.pid = snap["pid"]
            st.last_raw = {}
            st.rate_samples.clear()
        st.last_seen = recv_ts
        for name, v in snap["counters"].items():
            prev = st.last_raw.get(name, 0.0)
            delta = v - prev if v >= prev else v   # reset within a pid
            st.last_raw[name] = v
            st.totals[name] = st.totals.get(name, 0.0) + delta
            self._peer_counter(f"{label}/{name}", delta)
        for name, v in snap["gauges"].items():
            st.gauges[name] = v
            self._peer_gauge(f"{label}/{name}", v)
        # derived env-steps/sec over the snapshot stream
        total = st.totals.get("actor/env_steps")
        if total is not None:
            st.rate_samples.append((recv_ts, total))
            while (
                len(st.rate_samples) > 2
                and recv_ts - st.rate_samples[0][0] > 4 * self.interval_s
            ):
                st.rate_samples.popleft()
            t0, v0 = st.rate_samples[0]
            span = recv_ts - t0
            fps = (total - v0) / span if span > 0 else 0.0
            st.gauges["env_fps"] = fps
            self._peer_gauge(f"{label}/env_fps", fps)

    def _peer_metric(self, st: _PeerState, metric: str) -> Optional[float]:
        source, key = AGG_SOURCES[metric]
        if source == "gauge":
            return st.gauges.get(key)
        if source == "counter":
            return st.totals.get(key)
        return st.gauges.get(metric)   # "rate": the derived env_fps gauge

    def _rollup(self, now: float) -> None:
        for label in [
            l for l, st in self._peers.items()
            if now - st.last_seen > self.forget_after_s
        ]:
            del self._peers[label]   # long-gone peer: retire its row
        live = [
            st for st in self._peers.values()
            if now - st.last_seen <= self.stale_after_s
        ]
        self._reg.gauge("fleet/peers").set(float(len(live)))
        self._reg.gauge("fleet/peers_stale").set(
            float(len(self._peers) - len(live))
        )
        for metric in AGG_SOURCES:
            values = [
                v
                for st in live
                if (v := self._peer_metric(st, metric)) is not None
            ]
            stats = (
                (min(values), max(values), sum(values) / len(values))
                if values
                else (0.0, 0.0, 0.0)
            )
            for stat_name, v in zip(AGG_STATS, stats):
                name = f"{metric}/{stat_name}"
                self._reg.gauge(f"fleet/agg/{name}").set(v)
