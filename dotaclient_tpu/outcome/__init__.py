"""Outcome attribution plane (ISSUE 15).

The run-time planes before this one observe *throughput* — tracing
(ISSUE 12) explains where a chunk's time went, fleet health (ISSUE 13)
says which peers are alive — but nothing live says whether the policy is
actually WINNING, against whom, or why a regression happened. This
package closes that loop end to end:

* **Extraction** (``records.py`` + ``ingraph.py``): per-lane episode
  outcomes — win/loss, episode length, reward decomposition by shaping
  term, opponent bucket (scripted anchor vs league snapshot vs mirror
  self-play), side — surfaced at episode boundary from BOTH rollout
  paths. Host pools record through ``actor/window_stats.py`` into the
  process telemetry registry at the episode-end site they already own;
  the device/fused rollout accumulates the same facts as done-masked
  in-graph reductions inside the rollout program (``ingraph.py``),
  flushed with the existing decimated stats drain — zero added host
  syncs (``lint/host_sync.py`` scans the aggregator module whole).

* **Transport**: the outcome counters are ordinary telemetry counters
  under ``outcome/``, so external actors ship them inside the EXISTING
  fleet metric snapshot frames (``utils/fleet.py`` — same codec, same
  CRC/quarantine discipline on both lanes, no new frame kind) and the
  learner's ``FleetAggregator`` delta-merges them per peer exactly like
  every other counter (a restarted actor never double-counts).

* **Aggregation** (``aggregator.py``): the learner-side
  ``OutcomeAggregator`` merges local counters + fleet mirrors into
  windowed curves — ``outcome/win_rate/{vs_scripted,vs_league,overall}``,
  ``outcome/episode_len_p50``, per-term ``outcome/reward/<term>`` means —
  restart-safe, eager-created so ``check_telemetry_schema.py
  --require-outcome`` validates ANY learner JSONL.

* **Surfacing**: alert rules with runbook anchors (win-rate collapse,
  episode-length anomaly, outcome-stream staleness) in the PR 13 engine,
  ``scripts/outcome_report.py`` (curves + per-opponent table +
  ``OUTCOME_STATUS`` line), and an outcome panel in
  ``scripts/fleet_status.py``. What the plane costs a step is not measured
  on chip (ROADMAP.md R1).
"""

from dotaclient_tpu.outcome.records import (  # noqa: F401
    BUCKETS,
    N_LEN_BUCKETS,
    REWARD_TERMS,
    SIDES,
    add_reward_terms,
    ensure_actor_metrics,
    fold_device_stats,
    len_bucket,
    opponent_bucket,
    record_episode,
)
from dotaclient_tpu.outcome.aggregator import OutcomeAggregator  # noqa: F401

__all__ = [
    "BUCKETS",
    "N_LEN_BUCKETS",
    "REWARD_TERMS",
    "SIDES",
    "OutcomeAggregator",
    "add_reward_terms",
    "ensure_actor_metrics",
    "fold_device_stats",
    "len_bucket",
    "opponent_bucket",
    "record_episode",
]
