"""Cross-record perf trajectory from the repo's ``BENCH_*.json`` records.

Each PR that runs ``bench.py`` leaves a ``BENCH_rNN.json`` record, but
the records were written on WHATEVER host the round happened to have —
a TPU v5 lite one round, a shared CPU sandbox the next — so the
headline frames/sec across records is meaningless without a host
fingerprint, and until now nothing could read the trajectory at all.

This script makes the record sequence legible:

* extracts each record's **host/device fingerprint** (platform, device
  kind + count, forced-host-device flag, jax/libtpu versions — stamped
  by ``bench.py`` going forward under the ``host`` key; older records
  degrade to ``unknown``) plus its headline and stage numbers, handling
  BOTH historical shapes (the flat bench line and the driver wrapper
  with a ``parsed`` sub-dict);
* compares **absolute headline numbers only between like-fingerprint
  records** — across unlike hosts only the WITHIN-RUN stage ratios
  (speedups, overheads, recoveries, parities) are comparable, and those
  are compared across every record that carries them;
* prints a human table plus one machine-readable ``BENCH_TRAJECTORY``
  JSON line (the driver's cross-round evidence).

Usage:
    python scripts/bench_trajectory.py              # repo-root BENCH_*.json
    python scripts/bench_trajectory.py --dir /path  # records elsewhere
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Stage keys that are WITHIN-RUN ratios/fractions — dimensionless, so
# comparable across unlike hosts (absolute *_fps / *_ms / *_s stages are
# not). Keep in sync with the `stages` dict bench.py assembles.
RATIO_STAGES = (
    "stall_sync_recovery",
    "stall_async_recovery",
    "health_overhead",
    "trace_overhead",
    "fleet_overhead",
    "outcome_overhead",
    "rollout_compression",
    "quantize_optimizer_ratio",
    "advantage_speedup",
    "advantage_overlap",
    "advantage_parity",
    "multichip_parity",
    "scaling_efficiency",
    "fused_multichip_parity",
    "fused_scaling_efficiency",
    "serve_parity",
    "prefetch_hit_rate",
    "overlap_fraction",
    "utilization_overhead",
)

# Gate direction (ISSUE 16): stages named `*_overhead` are fractions of
# throughput LOST — a regression is the value going UP (compared
# absolutely: overheads sit near 0 where relative deltas explode).
# Every other ratio stage is higher-is-better (speedups, recoveries,
# parities, hit rates, compression) — a regression is a RELATIVE drop
# beyond tolerance.
def _stage_regression(
    stage: str, prev: float, cur: float, tolerance: float
) -> Optional[float]:
    """→ the regression magnitude when (prev → cur) regresses ``stage``
    beyond ``tolerance``, else None."""
    if stage.endswith("_overhead"):
        delta = cur - prev
        return delta if delta > tolerance else None
    drop = (prev - cur) / max(abs(prev), 1e-9)
    return drop if prev > 0 and drop > tolerance else None


def load_record(path: str) -> Optional[Dict]:
    """One BENCH record → a normalized dict, or None when unreadable.

    Two shapes exist: the flat bench.py line (r02+) and the driver
    wrapper ``{"n", "cmd", "rc", "tail", "parsed"}`` whose ``parsed``
    holds (a prefix of) the bench line (r01)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    body = raw.get("parsed") if isinstance(raw.get("parsed"), dict) else raw
    if not isinstance(body, dict) or "value" not in body:
        return None
    host = body.get("host") if isinstance(body.get("host"), dict) else None
    return {
        "name": os.path.basename(path),
        "value": body.get("value"),
        "unit": body.get("unit"),
        "vs_baseline": body.get("vs_baseline"),
        "stages": body.get("stages") if isinstance(
            body.get("stages"), dict
        ) else {},
        "host": host,
    }


def fingerprint(host: Optional[Dict]) -> Tuple:
    """Comparable host identity; unknown fingerprints compare like
    nothing (None sentinel — two unknown hosts are NOT assumed alike)."""
    if not host:
        return (None,)
    return (
        host.get("platform"),
        host.get("device_kind"),
        host.get("device_count"),
        bool(host.get("forced_host")),
        host.get("jax"),
        host.get("libtpu"),
    )


def fingerprint_label(host: Optional[Dict]) -> str:
    if not host:
        return "unknown"
    kind = host.get("device_kind", "?")
    n = host.get("device_count", "?")
    forced = " forced-host" if host.get("forced_host") else ""
    return f"{kind} x{n}{forced}"


def build_trajectory(records: List[Dict]) -> Dict:
    """The cross-record comparison: headline deltas between consecutive
    LIKE-fingerprint records, ratio stages across every record."""
    comparisons = []
    prev_by_fp: Dict[Tuple, Dict] = {}
    for rec in records:
        fp = fingerprint(rec["host"])
        prev = prev_by_fp.get(fp) if fp != (None,) else None
        if prev is not None and prev["value"]:
            comparisons.append(
                {
                    "from": prev["name"],
                    "to": rec["name"],
                    "host": fingerprint_label(rec["host"]),
                    "headline_ratio": round(
                        rec["value"] / prev["value"], 4
                    ),
                }
            )
        if fp != (None,):
            prev_by_fp[fp] = rec
    ratio_trajectory: Dict[str, List] = {}
    for stage in RATIO_STAGES:
        series = [
            {"record": rec["name"], "value": rec["stages"][stage]}
            for rec in records
            if stage in rec["stages"]
        ]
        if series:
            ratio_trajectory[stage] = series
    return {
        "records": [
            {
                "name": rec["name"],
                "value": rec["value"],
                "unit": rec["unit"],
                "vs_baseline": rec["vs_baseline"],
                "host": fingerprint_label(rec["host"]),
                "host_known": rec["host"] is not None,
                "n_stages": len(rec["stages"]),
            }
            for rec in records
        ],
        "headline_comparisons": comparisons,
        "ratio_stages": ratio_trajectory,
    }


def gate_regressions(
    records: List[Dict], tolerance: float
) -> List[Dict]:
    """Ratio-stage regressions between consecutive LIKE-FINGERPRINT
    records (the CI gate, ISSUE 16). Unknown hosts never pair — a
    regression verdict needs the host held constant even for the
    nominally dimensionless stages (a forced-host record's overheads are
    not a TPU record's)."""
    regressions: List[Dict] = []
    prev_by_fp: Dict[Tuple, Dict] = {}
    for rec in records:
        fp = fingerprint(rec["host"])
        if fp == (None,):
            continue
        prev = prev_by_fp.get(fp)
        if prev is not None:
            for stage in RATIO_STAGES:
                if stage not in prev["stages"] or stage not in rec["stages"]:
                    continue
                magnitude = _stage_regression(
                    stage, prev["stages"][stage], rec["stages"][stage],
                    tolerance,
                )
                if magnitude is not None:
                    regressions.append(
                        {
                            "stage": stage,
                            "from": prev["name"],
                            "to": rec["name"],
                            "prev": prev["stages"][stage],
                            "value": rec["stages"][stage],
                            "magnitude": round(magnitude, 4),
                            "host": fingerprint_label(rec["host"]),
                        }
                    )
        prev_by_fp[fp] = rec
    return regressions


def render(trajectory: Dict) -> str:
    lines: List[str] = ["== bench trajectory =="]
    rows = [["record", "headline", "unit", "vs_baseline", "host"]]
    for rec in trajectory["records"]:
        rows.append(
            [
                rec["name"],
                f"{rec['value']:.1f}" if rec["value"] is not None else "-",
                str(rec["unit"] or "-"),
                f"{rec['vs_baseline']}" if rec["vs_baseline"] is not None
                else "-",
                rec["host"],
            ]
        )
    widths = [max(len(r[c]) for r in rows) for c in range(len(rows[0]))]
    for i, row in enumerate(rows):
        lines.append(
            "  ".join(c.ljust(widths[j]) for j, c in enumerate(row))
        )
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    if trajectory["headline_comparisons"]:
        lines.append("headline comparisons (like-fingerprint hosts only):")
        for c in trajectory["headline_comparisons"]:
            lines.append(
                f"  {c['from']} → {c['to']}: ×{c['headline_ratio']} "
                f"({c['host']})"
            )
    else:
        lines.append(
            "headline comparisons: none — no two records share a known "
            "host fingerprint (absolute frames/sec across unlike hosts "
            "is a host artifact, not a trajectory)"
        )
    if trajectory["ratio_stages"]:
        lines.append("within-run ratio stages (host-comparable):")
        for stage, series in sorted(trajectory["ratio_stages"].items()):
            path = " → ".join(
                f"{s['value']}@{s['record'].replace('BENCH_', '').replace('.json', '')}"
                for s in series
            )
            lines.append(f"  {stage:26s} {path}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--dir", default=REPO,
        help="directory holding BENCH_*.json records (default: repo root)",
    )
    p.add_argument(
        "--gate", action="store_true",
        help="exit nonzero when a like-fingerprint record regresses a "
        "ratio stage beyond --tolerance (the CI gate, ISSUE 16)",
    )
    p.add_argument(
        "--tolerance", type=float, default=0.05,
        help="gate tolerance: max relative drop for higher-is-better "
        "stages / max absolute rise for *_overhead stages (default 0.05)",
    )
    args = p.parse_args(argv)
    paths = sorted(glob.glob(os.path.join(args.dir, "BENCH_*.json")))
    records = [r for r in (load_record(p_) for p_ in paths) if r is not None]
    skipped = len(paths) - len(records)
    trajectory = build_trajectory(records)
    trajectory["skipped_unreadable"] = skipped
    print(render(trajectory), flush=True)
    print(
        "BENCH_TRAJECTORY " + json.dumps(trajectory, sort_keys=True),
        flush=True,
    )
    if args.gate:
        regressions = gate_regressions(records, args.tolerance)
        for r in regressions:
            print(
                f"BENCH_GATE FAIL {r['stage']}: {r['prev']} → {r['value']} "
                f"({r['from']} → {r['to']}, {r['host']}, "
                f"magnitude {r['magnitude']} > tol {args.tolerance})",
                flush=True,
            )
        if regressions:
            return 1
        print(
            f"BENCH_GATE PASS ({len(records)} records, "
            f"tolerance {args.tolerance})",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
