"""CI guard: the learner's --metrics-jsonl output matches the documented schema.

Runs a ``--smoke`` learner step with a JSONL sink attached (or validates an
existing file via ``--path``) and checks:

* every line parses as JSON and has the envelope
  ``{"ts": float, "step": int >= 0, "scalars": {str: number|null}}``;
* the union of scalar keys across lines covers the documented pipeline
  telemetry contract (docs/ARCHITECTURE.md "Observability"): per-stage span
  timings for the actor, buffer, transport, and learner stages, the
  transport queue-depth gauge, the actor weight-version staleness gauge,
  and the buffer occupancy gauge.

Exit status 0 on success; 1 with a diagnostic on any violation. Invoked
from the test suite (tests/test_telemetry.py), so tier-1 covers the schema.

The hand-maintained tier lists below are themselves machine-checked: the
``telemetry-drift`` pass of ``python -m dotaclient_tpu.lint`` statically
extracts every key the package emits and fails CI when a tier list
requires a key no code emits (and, symmetrically, when an emitted key is
missing from the docs/ARCHITECTURE.md "Observability" tables). Renaming a
counter without updating these tuples is caught before any smoke run.

Usage:
    python scripts/check_telemetry_schema.py            # run smoke + validate
    python scripts/check_telemetry_schema.py --path x.jsonl   # validate only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # direct `python scripts/...` invocation
    sys.path.insert(0, _REPO)


def _light_load_jsonl():
    """The torn-line-tolerant reader (ISSUE 12: a SIGKILL'd process can
    leave one unterminated trailing line; validation drops it instead of
    failing) WITHOUT the dotaclient_tpu package import chain —
    utils/__init__ pulls jax + orbax, a multi-second cost the pure
    `--path` validation flow must not pay. Reuse the already-imported
    module when a host process (tests, the smoke runner) loaded it;
    otherwise exec telemetry.py (stdlib-only) straight from its file.
    Shared semantics with scripts/trace_report.py."""
    mod = sys.modules.get("dotaclient_tpu.utils.telemetry")
    if mod is None:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "_dota_telemetry_light",
            os.path.join(_REPO, "dotaclient_tpu", "utils", "telemetry.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return mod.load_jsonl


load_jsonl = _light_load_jsonl()

# Every key a --smoke run (device actor, in-proc transport, HBM buffer) must
# emit. Timer stats are spot-checked through their /mean_s leaf; the other
# leaves (count/total_s/last_s/ema_s/p95_s) share the emission path.
REQUIRED_KEYS = (
    # per-stage spans: actor → buffer → learner, + the transport publish
    "span/actor/collect/mean_s",
    "span/actor/drain/mean_s",
    "span/buffer/insert/mean_s",
    "span/buffer/sample/mean_s",
    "span/learner/dispatch/mean_s",
    "span/learner/metrics_fetch/mean_s",
    # (span/learner/prefetch is NOT required: it records only productive
    # staging — a smoke run whose ring never holds a surplus batch
    # legitimately emits none; the gauges below always emit)
    "span/transport/publish_weights/mean_s",
    # pipeline-health gauges
    "transport/queue_depth",
    "actor/weight_staleness",
    "buffer/occupancy",
    # pipelined-data-path gauges (ISSUE 2): batches served from the
    # prefetch lane, and the fraction of staging work overlapped with an
    # in-flight dispatch
    "learner/prefetch_hit_rate",
    "learner/overlap_fraction",
    # throughput counters
    "actor/frames_shipped",
    "actor/rollouts_shipped",
)

TIMER_LEAVES = ("count", "total_s", "last_s", "mean_s", "ema_s", "p95_s")

# Cross-process transport metrics (ISSUE 3). Not in REQUIRED_KEYS: a --smoke
# run uses the in-proc transport and legitimately never emits them. A run
# that DID use the socket/shm transport validates them via
# --require-transport / --require-shm (the servers eager-create every one of
# these at construction, so presence is deterministic, not event-driven).
SOCKET_TRANSPORT_KEYS = (
    "transport/weights_coalesced",      # unsent frame replaced: latest wins
    "transport/fanout_conns_dropped",   # over-budget conns cut loose
    "transport/weights_sent",           # frames fully written to a wire
    "transport/fanout_lag_max",         # worst conn publish-seq lag
    "transport/fanout_queue_depth",     # conns with an unsent frame
    "transport/actors_connected",
)
SHM_TRANSPORT_KEYS = (
    "shm/ring_occupancy",               # max ring fill fraction
    "shm/ring_dropped_total",           # producer-side ring-full drops
    "transport/queue_depth",
)

# Zero-stall snapshot engine (ISSUE 5). The learner eager-creates every one
# of these at construction — in BOTH async and sync-snapshots modes — so a
# clean run deterministically reports zeros. Validated with
# --require-snapshot against any learner run's JSONL (the keys are
# unconditional, unlike the transport tiers).
SNAPSHOT_KEYS = (
    "snapshot/pending",             # job slots occupied (engine backlog)
    "snapshot/d2h_ms",              # last batched device→host fetch
    "learner/publish_stall_ms",     # train-thread time lost per publish
    "learner/stall_fraction",       # side-effect stall / train() wall time
)

# Fault-tolerance layer (ISSUE 4). Validated with --require-faults against
# a run that used the socket transport AND a checkpoint dir (both eager-
# create their counters, so presence is deterministic even for a run that
# never saw a fault — the value is just 0). scripts/chaos_run.py's learner
# invocations qualify.
FAULT_KEYS = (
    "transport/frames_corrupt_total",   # CRC-failed frames dropped
    "transport/peers_quarantined",      # poison_frame_limit streaks cut
    "transport/conn_idle_drops",        # half-open conns dropped (learner)
    "transport/heartbeats_sent",        # liveness frames interleaved
    "transport/reader_exits",           # server-side connection endings
    "checkpoint/save_failures_total",   # degraded periodic saves
)

# Quantized experience plane (ISSUE 7). Validated with --require-wire
# against a run that used the socket OR shm transport: both servers
# eager-create the byte counters and the compression-ratio gauge at
# construction (the gauge initializes to 1.0 — an f32 run deterministically
# reports "no compression", never "no data").
WIRE_KEYS = (
    "transport/rollout_bytes_total",        # actual rollout wire bytes consumed
    "transport/rollout_raw_bytes_total",    # what full-width would have cost
    "transport/rollout_compression_ratio",  # raw / wire over the run
)

# Training health guardian (ISSUE 6). Validated with --require-health
# against any health-enabled learner run's JSONL (health.enabled defaults
# on): the HealthMonitor eager-creates every one of these at construction —
# in BOTH sync and async snapshot modes — so a clean run deterministically
# reports zeros (buffer/stale_rejected_total is pinned by the monitor too,
# covering bufferless fused runs).
HEALTH_KEYS = (
    "health/nonfinite_steps_total",     # NaN/Inf loss or grad-norm verdicts
    "health/rollbacks_total",           # last_good restores performed
    "health/last_good_step",            # newest health-verified save
    "buffer/stale_rejected_total",      # admission-control staleness drops
)

# Multi-chip learner (ISSUE 10; lane-sharding gauges PR 18). Validated
# with --require-multichip against ANY learner run's JSONL: the Learner
# eager-creates every key here at construction (mesh geometry and the
# lane-sharding layout — 0s outside device/fused modes;
# buffer/shard_bytes stays 0 for bufferless fused runs and carries the
# per-device resident ring bytes otherwise), so presence is deterministic
# at every device count — a 1-device mesh is the degenerate case of the
# same code path.
MULTICHIP_KEYS = (
    "mesh/n_devices",        # devices in the learner's mesh
    "mesh/data_shards",      # batch shard count (dcn × data axes)
    "mesh/lane_shards",      # fused actor-state lane shard count (PR 18)
    "fused/lanes_per_shard", # local lanes per shard (0 in non-device modes)
    "buffer/shard_bytes",    # per-device resident bytes of the HBM ring
)

# Policy-serving plane (ISSUE 11). Validated with --require-serve against
# a serve run's JSONL (`python -m dotaclient_tpu.serve
# --serve-metrics-jsonl PATH`): the ServeEngine and PolicyServer
# eager-create every one of these at construction, so a server that never
# saw a request still deterministically reports zeros.
SERVE_KEYS = (
    "serve/requests_total",        # step requests accepted
    "serve/batch_fill",            # last dispatch's fill fraction
    "serve/batch_window_hits",     # windows closed by the deadline
    "serve/p99_latency_ms",        # arrival→reply p99 (rolling)
    "serve/weights_version",       # version serving right now
    "serve/dispatches_total",      # jitted dispatches run
    "serve/max_batch_hits",        # windows closed by a full batch
    "serve/weight_swaps_total",    # hot swaps committed between dispatches
    "serve/dispatch_errors_total", # windows dropped by dispatch failures
    "serve/replies_total",         # actions scattered back to requesters
    "serve/reply_errors_total",    # replies to already-dead clients
    "serve/clients_connected",     # attached games
    "serve/slots_in_use",          # carry slots owned by live games
    "serve/conns_rejected_total",  # joiners shed with every slot taken
    "serve/carry_installs_total",  # re-homed shadow rows installed (ISSUE 19)
)

# Serve-fleet router (ISSUE 19). Validated with --require-router against a
# SessionRouter run's JSONL (`python -m dotaclient_tpu.serve.router
# --metrics-jsonl PATH`): the router eager-creates every one of these at
# construction, so a fleet that never lost a backend still deterministically
# reports zeros. Per-backend keys (router/backend/<i>/sessions) are dynamic
# and NOT in the tier.
ROUTER_KEYS = (
    "router/sessions_attached_total",   # sessions assigned a home
    "router/sessions_detached_total",   # clean client detaches
    "router/sessions_rehomed_total",    # sessions moved off dead backends
    "router/carry_resets_total",        # client-reported default-mode resets
    "router/spares_promoted_total",     # hot spares entered the pool
    "router/backend_deaths_total",      # probes declared past the grace window
    "router/probe_reconnects_total",    # probe redials (blips + deaths)
    "router/route_requests_total",      # control ops served
    "router/route_errors_total",        # malformed/unroutable control ops
    "router/backends_live",             # live non-spare backends
    "router/backends_dead",             # dead non-spare backends (page signal)
    "router/spares_available",          # live unpromoted spares
    "router/sessions_active",           # sessions currently mapped
)

# Pipeline tracing + device observability (ISSUE 12). Validated with
# --require-trace against ANY learner run's JSONL: the Learner
# eager-creates all of them at construction (tracing.ensure_metrics) —
# the trace emit/drop counters stay 0 with tracing off, the compile
# counters track the instrumented jit entry points and (ISSUE 35, from
# JAX's own events) every program of the process regardless of tracing,
# and mem/hbm_peak_bytes degrades to 0 on backends without allocator
# stats (CPU).
TRACE_KEYS = (
    "trace/emitted_total",          # trace events written to --trace-jsonl
    "trace/dropped_total",          # events dropped (writer behind / queue full)
    "compile/compiles_total",       # XLA compiles across instrumented programs
    "compile/retraces_total",       # compiles beyond each program's first
    "compile/compile_time_s_total", # cumulative seconds spent compiling
    "compile/trace_s_total",        # every program: seconds tracing to a jaxpr
    "compile/lower_s_total",        # every program: seconds lowering to MLIR
    "compile/backend_s_total",      # every program: XLA compile or cache load
    "compile/programs_total",       # programs compiled or loaded
    "compile/cache_load_s_total",   # seconds reading the persistent cache
    "compile/cache_hits_total",     # persistent-cache hits
    "compile/cache_misses_total",   # persistent-cache misses (entries written)
    "mem/hbm_peak_bytes",           # device allocator peak (max over devices)
)

# One-pass advantage plane (ISSUE 14). Validated with --require-advantage
# against ANY learner run's JSONL: the Learner eager-creates every one of
# these at construction — a recompute-mode run (one_pass_advantage=false,
# vtrace, fused mode) deterministically reports advantage/one_pass = 0
# and zeros, never missing keys.
ADVANTAGE_KEYS = (
    "advantage/one_pass",          # 1 when the consume-time pass is live
    "advantage/pass_ms",           # last pass's host dispatch time
    "advantage/overlap_fraction",  # pass host time hidden behind a dispatch
    "advantage/passes_total",      # consume-time passes run
)

# Fleet health plane (ISSUE 13). Validated with --require-fleet against
# ANY learner run's JSONL: the Learner constructs its FleetAggregator
# unconditionally, which eager-creates every rollup/alert key at
# construction — a run with no fleet traffic deterministically reports
# zeros. Per-peer keys (fleet/<peer>/*) are dynamic and NOT in the tier.
FLEET_KEYS = (
    "fleet/peers",                  # peers reporting within the stale window
    "fleet/peers_stale",            # peers gone silent (the page signal)
    "fleet/snapshots_total",        # metric snapshot frames merged
    "fleet/bad_snapshots_total",    # undecodable snapshot frames dropped
    "fleet/agg/weight_staleness/min",
    "fleet/agg/weight_staleness/max",
    "fleet/agg/weight_staleness/mean",
    "fleet/agg/env_fps/min",
    "fleet/agg/env_fps/max",
    "fleet/agg/env_fps/mean",
    "fleet/agg/reconnects/min",
    "fleet/agg/reconnects/max",
    "fleet/agg/reconnects/mean",
    "fleet/agg/corrupt_frames/min",
    "fleet/agg/corrupt_frames/max",
    "fleet/agg/corrupt_frames/mean",
    "fleet/agg/ship_wait/min",
    "fleet/agg/ship_wait/max",
    "fleet/agg/ship_wait/mean",
    "alerts/fired_total",           # alert rules that fired
    "alerts/resolved_total",        # alerts that cleared
    "alerts/active",                # rules firing right now
)

# Outcome attribution plane (ISSUE 15). Validated with --require-outcome
# against ANY learner JSONL: the Learner eager-creates BOTH halves at
# construction — the actor-side outcome counters
# (outcome.records.ensure_actor_metrics; zeros until episodes complete)
# and the OutcomeAggregator's curve gauges (win-rates initialized to the
# 0.5 neutral prior, stream age to -1 until armed) — so presence is
# deterministic in every actor mode, external fleets included.
OUTCOME_KEYS = (
    # aggregator curves (learner side)
    "outcome/win_rate/vs_scripted",     # THE tier-2 honesty metric, windowed
    "outcome/win_rate/vs_league",
    "outcome/win_rate/overall",
    "outcome/episode_len_p50",          # windowed median episode length
    "outcome/episode_len_anomaly",      # 1 while armed p50 < floor
    "outcome/stream_age_s",             # -1 unarmed; seconds since last episode
    "outcome/episodes_total",
    "outcome/episodes_recent",
    "outcome/reward/xp",                # windowed per-episode term means
    "outcome/reward/gold",
    "outcome/reward/hp",
    "outcome/reward/enemy_hp",
    "outcome/reward/last_hits",
    "outcome/reward/denies",
    "outcome/reward/kills",
    "outcome/reward/deaths",
    "outcome/reward/tower_damage",
    "outcome/reward/own_tower",
    "outcome/reward/win",
    # actor-side counters (episode-boundary records; fleet-shipped)
    "outcome/episodes/vs_scripted",
    "outcome/episodes/vs_league",
    "outcome/episodes/vs_selfplay",
    "outcome/wins/vs_scripted",
    "outcome/wins/vs_league",
    "outcome/wins/vs_selfplay",
    "outcome/episodes_side/radiant",
    "outcome/episodes_side/dire",
    "outcome/ep_len_sum",
    "outcome/ep_len_hist/00",
    "outcome/ep_len_hist/01",
    "outcome/ep_len_hist/02",
    "outcome/ep_len_hist/03",
    "outcome/ep_len_hist/04",
    "outcome/ep_len_hist/05",
    "outcome/ep_len_hist/06",
    "outcome/ep_len_hist/07",
    "outcome/ep_len_hist/08",
    "outcome/ep_len_hist/09",
    "outcome/ep_len_hist/10",
    "outcome/ep_len_hist/11",
    "outcome/reward_sum/xp",
    "outcome/reward_sum/gold",
    "outcome/reward_sum/hp",
    "outcome/reward_sum/enemy_hp",
    "outcome/reward_sum/last_hits",
    "outcome/reward_sum/denies",
    "outcome/reward_sum/kills",
    "outcome/reward_sum/deaths",
    "outcome/reward_sum/tower_damage",
    "outcome/reward_sum/own_tower",
    "outcome/reward_sum/win",
)

# Pipeline utilization plane (ISSUE 16). Validated with
# --require-utilization against ANY learner JSONL: the Learner's
# utilization.make_learner eager-creates every gauge at construction
# even when the module knob disables the accountant, so presence is
# deterministic — duty_cycle reads its neutral 1.0 and armed 0 until the
# first fold.
UTILIZATION_KEYS = (
    "util/armed",                    # 0 until the first fold lands
    "util/duty_cycle",               # dispatch_inflight fraction (neutral 1.0)
    "util/steps_per_sec_ema",        # fast throughput EMA
    "util/steps_per_sec_baseline",   # slow warmup-armed baseline EMA
    "util/throughput_regression",    # 1 while ema < ratio * baseline
    "util/phase/dispatch_inflight",  # donated step in flight (duty cycle)
    "util/phase/ingest_wait",        # buffer below min consumable
    "util/phase/gather",             # batch staging/assembly
    "util/phase/advantage_pass",     # consume-time value+GAE dispatch
    "util/phase/publish_stall",      # weight-publish wait
    "util/phase/checkpoint_stall",   # checkpoint wait
    "util/phase/host_other",         # residual unattributed host time
)

# Keys only an IN-PROCESS actor emits. A learner serving external actor
# processes over socket/shm never runs its own collect loop, so its JSONL
# legitimately lacks these — they are waived when the line union carries an
# external-transport marker (both servers eager-create theirs at
# construction, so detection is deterministic, not event-driven).
IN_PROC_ACTOR_KEYS = (
    "span/actor/collect/mean_s",
    "span/actor/drain/mean_s",
    "actor/frames_shipped",
    "actor/rollouts_shipped",
)
EXTERNAL_TRANSPORT_MARKERS = (
    "transport/actors_connected",       # socket server
    "shm/ring_occupancy",               # shm server
)


def validate_lines(
    lines: List[str],
    extra_required: tuple = (),
    base_required: Optional[tuple] = None,
) -> List[str]:
    """Return a list of violations (empty = schema holds).

    ``base_required`` overrides the learner-pipeline contract
    (``REQUIRED_KEYS``) for JSONLs written by a different process class —
    the serve plane's record (``--require-serve``) carries serve keys, not
    actor/buffer/learner spans."""
    errors: List[str] = []
    union: Dict[str, object] = {}
    if not lines:
        return ["JSONL file is empty — no metrics were emitted"]
    for i, raw in enumerate(lines, 1):
        try:
            obj = json.loads(raw)
        except json.JSONDecodeError as e:
            errors.append(f"line {i}: not valid JSON ({e})")
            continue
        if not isinstance(obj, dict):
            errors.append(f"line {i}: top level is {type(obj).__name__}, not object")
            continue
        if "event" in obj:
            # the structured event channel (ALERT lines, ISSUE 13) rides
            # the same file as the metrics envelopes; events are shaped
            # by their emitter, not this schema — skip, don't fail
            continue
        if not isinstance(obj.get("ts"), (int, float)):
            errors.append(f"line {i}: missing/invalid 'ts'")
        if not isinstance(obj.get("step"), int) or obj.get("step", -1) < 0:
            errors.append(f"line {i}: missing/invalid 'step'")
        scalars = obj.get("scalars")
        if not isinstance(scalars, dict):
            errors.append(f"line {i}: missing/invalid 'scalars'")
            continue
        for k, v in scalars.items():
            if not isinstance(k, str):
                errors.append(f"line {i}: non-string scalar key {k!r}")
            elif v is not None and not isinstance(v, (int, float)):
                errors.append(f"line {i}: scalar {k!r} is {type(v).__name__}")
        union.update(scalars)
    required = (
        *(REQUIRED_KEYS if base_required is None else base_required),
        *extra_required,
    )
    if any(m in union for m in EXTERNAL_TRANSPORT_MARKERS):
        required = tuple(
            k for k in required if k not in IN_PROC_ACTOR_KEYS
        )
    missing = [k for k in required if k not in union]
    if missing:
        errors.append(
            "required telemetry keys never emitted: " + ", ".join(missing)
        )
    # every span timer must carry the full stat leaf set
    span_roots = {
        k.rsplit("/", 1)[0]
        for k in union
        if k.startswith("span/") and k.rsplit("/", 1)[1] in TIMER_LEAVES
    }
    for root in sorted(span_roots):
        for leaf in TIMER_LEAVES:
            if f"{root}/{leaf}" not in union:
                errors.append(f"timer {root!r} missing stat leaf {leaf!r}")
    return errors


def run_smoke(path: str) -> None:
    """One tiny learner run with the JSONL sink attached."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:  # direct `python scripts/...` invocation
        sys.path.insert(0, repo_root)
    from dotaclient_tpu.train.learner import main as learner_main

    learner_main(["--smoke", "--steps", "2", "--metrics-jsonl", path])


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--path", type=str, default=None,
        help="validate an existing JSONL file instead of running the smoke",
    )
    p.add_argument(
        "--require-transport", action="store_true",
        help="also require the socket-transport fanout metrics (for "
        "validating a --transport socket run's JSONL)",
    )
    p.add_argument(
        "--require-shm", action="store_true",
        help="also require the shared-memory lane metrics (for validating "
        "a --transport shm run's JSONL)",
    )
    p.add_argument(
        "--require-faults", action="store_true",
        help="also require the fault-tolerance counters (for validating a "
        "--transport socket + --checkpoint-dir run's JSONL, e.g. a "
        "scripts/chaos_run.py learner)",
    )
    p.add_argument(
        "--require-snapshot", action="store_true",
        help="also require the zero-stall snapshot-engine keys (ISSUE 5); "
        "valid against ANY learner run's JSONL — the learner eager-creates "
        "them in async and sync-snapshots modes alike",
    )
    p.add_argument(
        "--require-wire", action="store_true",
        help="also require the quantized-experience-plane byte accounting "
        "(ISSUE 7); valid against any --transport socket/shm run's JSONL — "
        "both servers eager-create the counters and the ratio gauge",
    )
    p.add_argument(
        "--require-health", action="store_true",
        help="also require the training-health-guardian keys (ISSUE 6); "
        "valid against any learner run with health.enabled (the default) — "
        "the HealthMonitor eager-creates them in both snapshot modes",
    )
    p.add_argument(
        "--require-serve", action="store_true",
        help="also require the policy-serving-plane keys (ISSUE 11); valid "
        "against a serve run's JSONL (--serve-metrics-jsonl) — the "
        "ServeEngine and PolicyServer eager-create every key at "
        "construction",
    )
    p.add_argument(
        "--require-router", action="store_true",
        help="also require the serve-fleet router keys (ISSUE 19); valid "
        "against a SessionRouter run's JSONL (--metrics-jsonl) — the "
        "router eager-creates every key at construction",
    )
    p.add_argument(
        "--require-trace", action="store_true",
        help="also require the pipeline-tracing + device-observability "
        "keys (ISSUE 12); valid against ANY learner run's JSONL — the "
        "Learner eager-creates trace/compile/mem keys at construction",
    )
    p.add_argument(
        "--require-fleet", action="store_true",
        help="also require the fleet-health-plane keys (ISSUE 13); valid "
        "against ANY learner run's JSONL — the Learner's FleetAggregator "
        "eager-creates every rollup and alert key at construction",
    )
    p.add_argument(
        "--require-outcome", action="store_true",
        help="also require the outcome-attribution-plane keys (ISSUE 15); "
        "valid against ANY learner run's JSONL — the Learner eager-creates "
        "the actor-side outcome counters AND the OutcomeAggregator's curve "
        "gauges at construction, in every actor mode",
    )
    p.add_argument(
        "--require-advantage", action="store_true",
        help="also require the one-pass advantage-plane keys (ISSUE 14); "
        "valid against ANY learner run's JSONL — the Learner eager-creates "
        "them whether the pass is live or the run recomputes in-step",
    )
    p.add_argument(
        "--require-utilization", action="store_true",
        help="also require the pipeline-utilization-plane keys (ISSUE 16); "
        "valid against ANY learner run's JSONL — the Learner eager-creates "
        "every util/* gauge at construction, accountant enabled or not",
    )
    p.add_argument(
        "--require-multichip", action="store_true",
        help="also require the multi-chip learner keys (ISSUE 10); valid "
        "against ANY learner run's JSONL at any device count — the "
        "Learner eager-creates mesh geometry and the ring's per-shard "
        "byte gauge at construction",
    )
    args = p.parse_args(argv)
    extra: tuple = ()
    if args.require_transport:
        extra += SOCKET_TRANSPORT_KEYS
    if args.require_shm:
        extra += SHM_TRANSPORT_KEYS
    if args.require_faults:
        extra += FAULT_KEYS
    if args.require_snapshot:
        extra += SNAPSHOT_KEYS
    if args.require_wire:
        extra += WIRE_KEYS
    if args.require_health:
        extra += HEALTH_KEYS
    if args.require_serve:
        extra += SERVE_KEYS
    if args.require_router:
        extra += ROUTER_KEYS
    if args.require_advantage:
        extra += ADVANTAGE_KEYS
    if args.require_multichip:
        extra += MULTICHIP_KEYS
    if args.require_trace:
        extra += TRACE_KEYS
    if args.require_fleet:
        extra += FLEET_KEYS
    if args.require_outcome:
        extra += OUTCOME_KEYS
    if args.require_utilization:
        extra += UTILIZATION_KEYS

    path = args.path
    if path is None:
        fd, path = tempfile.mkstemp(suffix=".jsonl", prefix="telemetry_schema_")
        os.close(fd)
        try:
            run_smoke(path)
            lines = load_jsonl(path)
        finally:
            os.unlink(path)
    else:
        lines = load_jsonl(path)

    # serve and router runs are different process classes: their JSONLs
    # carry their own plane's keys, not the learner's actor/buffer spans
    base = () if args.require_serve or args.require_router else None
    errors = validate_lines(lines, extra_required=extra, base_required=base)
    if errors:
        print("telemetry schema check FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    print(f"telemetry schema OK: {len(lines)} lines validated")
    return 0


if __name__ == "__main__":
    sys.exit(main())
