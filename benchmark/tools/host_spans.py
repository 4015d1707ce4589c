"""Device idle gaps put down to the program's own host spans, and host
milliseconds per dispatch and per log boundary by span.

    python3 benchmark/run.py --workload <cell> --seed <n> --trace 1 --keep-trace
    python3 benchmark/tools/host_spans.py benchmark_out/<cell>

The program's ``telemetry.Registry.span`` (PR 24) is a
``jax.profiler.TraceAnnotation`` too, so in a kept trace every span of
every thread is an event of ``/host:CPU`` on the device trace's clock:
``learner/iteration`` with its ``step``, and inside it ``learner/league_draw``,
``learner/dispatch``, ``learner/league_report`` and, at a log boundary,
``learner/boundary`` with its children; on the snapshot thread
``snapshot/stats_fetch``, ``learner/metrics_fetch`` and
``transport/publish_weights``. A span's parent is the span that encloses it
on the same thread.

For each idle gap of the fullest chip of ``--min-gap-ms`` (1) or more inside
``bench:traced_window`` this prints, for EACH host thread that ran a program
span, the innermost span that covers most (more than half) of the gap, with
its chain of parents and its ``step``, and how the gap divides among the
thread's spans by self time; where no program span covers the gap it says so
and names the host event of that thread that covers most of it.
A Python thread's line in the trace carries the process's name whichever
thread it is, so a thread is called by the outermost program span it spent
most time in. A span that began before the profiler's session did is not in
the trace: the first dispatch of a traced window (the benchmark starts the
session inside its ``fused_step`` stand-in) shows as ``PjitFunction(...)``
under no program span.

From ``metrics.jsonl`` (the program's own record of the same run) it prints
host milliseconds per dispatch and per log boundary by span timer, between
the record's first line that holds the loop's counters (the end of the
warm-up) and its last, and ``learner/iteration``'s self time: what no child
span names. Under the benchmark ``learner/dispatch`` is NOT an enqueue time:
``DispatchMeter`` stands where ``fused_step`` stood and waits inside that
call for dispatch i-2, and in a traced run starts and stops the profiler
there too (seconds): read ``learner/dispatch`` and ``learner/iteration`` from
an untraced run's record, the boundary's children from either.

The harness keeps only host events named ``bench:`` and the runner deletes
the trace before any reader runs, so none of this is a per-layer metric yet
(``PERF.md`` section 7): this is a tool for a person, over a kept trace.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.harness import trace, xplane  # noqa: E402

WINDOW_SPAN = "bench:traced_window"
# a Registry.span's full name: lower-case segments joined by "/"
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(/[a-z0-9_]+)+$")
ITERATION = "learner/iteration"
BOUNDARY = "learner/boundary"
DISPATCHES = "learner/dispatches_total"
BOUNDARIES = "learner/boundaries_total"
# the spans that learner/iteration encloses directly
ITERATION_CHILDREN = (
    "learner/league_draw", "learner/dispatch", "learner/league_report",
    BOUNDARY, "learner/checkpoint_submit",
)


@dataclasses.dataclass(frozen=True)
class HostEvent:
    name: str
    start: float                  # ns, on trace.load's clock
    end: float
    step: Optional[int] = None

    @property
    def program(self) -> bool:
        return bool(PROGRAM_SPAN.match(self.name))


@dataclasses.dataclass
class Thread:
    label: str
    events: List[HostEvent]       # sorted by start, enclosing first


def load_threads(path: str) -> List[Thread]:
    """The lines of ``/host:CPU`` that hold at least one program span, times
    on the clock ``trace.load`` gives the device planes."""
    space = xplane.read(path)
    base = min(
        (ln.timestamp_ns for pl in space.planes for ln in pl.lines if ln.events),
        default=0,
    )
    threads: List[Thread] = []
    for plane in space.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        meta = xplane.event_metadata(plane)
        stat_names = xplane.stat_names(plane)
        for line in plane.lines:
            events = []
            for ev in line.events:
                start = (line.timestamp_ns - base) + ev.offset_ps * 1e-3
                step = next(
                    (int(xplane.stat_value(st, stat_names)) for st in ev.stats
                     if stat_names.get(st.metadata_id) == "step"),
                    None,
                )
                events.append(HostEvent(
                    meta.get(ev.metadata_id, {}).get("name", ""),
                    start, start + ev.duration_ps * 1e-3, step,
                ))
            events.sort(key=lambda e: (e.start, -e.end))
            outer = main_span(events)
            if outer is not None:
                threads.append(Thread(f"{line.name}[{outer}]", events))
    # two threads that ran the same span (none do today) stay apart
    seen: Dict[str, int] = {}
    for th in threads:
        seen[th.label] = seen.get(th.label, 0) + 1
        if seen[th.label] > 1:
            th.label += f"#{seen[th.label]}"
    return threads


def main_span(events: Sequence[HostEvent]) -> Optional[str]:
    """The name of the outermost program span (one that no program span
    encloses) in which ``events``' thread spent most time; ``events`` are
    sorted by start, enclosing first."""
    total: Dict[str, float] = {}
    end = float("-inf")
    for e in events:
        if e.program and e.start >= end:         # not inside the last outermost
            total[e.name] = total.get(e.name, 0.0) + (e.end - e.start)
            end = e.end
    return max(total, key=total.get) if total else None


def _overlap(e: HostEvent, a: float, b: float) -> float:
    return min(b, e.end) - max(a, e.start)


def covering(events: Sequence[HostEvent], a: float, b: float) -> Optional[HostEvent]:
    """Of ``events``, the innermost one that covers most of ``[a, b]``: the
    shortest of those that cover more than half of it (a span's parents
    cover at least what it covers); where none does, the one that covers
    the largest part."""
    best, best_key = None, None
    for e in events:
        cover = _overlap(e, a, b)
        if cover <= 0:
            continue
        most = cover > 0.5 * (b - a)
        key = (most, -(e.end - e.start) if most else cover)
        if best_key is None or key > best_key:
            best, best_key = e, key
    return best


def self_cover(events: Sequence[HostEvent], a: float, b: float) -> List[Tuple[str, float]]:
    """How ``[a, b]`` divides among ``events`` (one thread's spans, sorted by
    start, enclosing first): each span's cover less its children's, as
    ``(name, ns)``, largest first. A gap that no one span covers most of
    (the end of a boundary, then the next enqueue) reads off this."""
    out: Dict[str, float] = {}
    stack: List[HostEvent] = []
    for e in events:
        cover = _overlap(e, a, b)
        if cover <= 0:
            continue
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            out[stack[-1].name] -= cover
        out[e.name] = out.get(e.name, 0.0) + cover
        stack.append(e)
    return sorted(out.items(), key=lambda kv: -kv[1])


def chain(events: Sequence[HostEvent], span: HostEvent) -> List[HostEvent]:
    """``span``'s parents among ``events``, outermost first, then ``span``."""
    parents = [
        e for e in events
        if e is not span and e.start <= span.start and span.end <= e.end
    ]
    parents.sort(key=lambda e: e.start - e.end)
    return parents + [span]


def attribute_gaps(
    tr: trace.Trace, threads: Sequence[Thread], min_gap_ns: float = 1e6
) -> List[Dict[str, object]]:
    """One row per idle gap of the fullest chip inside the traced window,
    longest first: its start (ms from the window's start) and length (ms),
    the benchmark's own label, and per thread the covering program span's
    chain, step and cover, or the host event that ran instead."""
    window = tr.span(WINDOW_SPAN)
    if window is None or not tr.devices:
        return []
    lo, hi = window.start, window.end
    plane = max(tr.devices, key=lambda d: trace.busy_seconds(d, lo, hi))
    bench = [s for s in tr.spans if s.name != WINDOW_SPAN]
    rows = []
    found = trace.gaps([(o.start, o.end) for o in plane.ops], lo, hi)
    for a, b in sorted(found, key=lambda g: g[0] - g[1]):
        if b - a < min_gap_ns:
            break
        # the benchmark's span that covers most of it, as the result line's
        # breakdown.idle_gaps labels it
        cover, label = max(
            ((min(b, s.end) - max(a, s.start), s.name) for s in bench),
            default=(0.0, ""),
        )
        row: Dict[str, object] = {
            "at_ms": (a - lo) * 1e-6, "gap_ms": (b - a) * 1e-6,
            "bench": label if cover > 0 else "unlabelled", "threads": {},
        }
        for th in threads:
            program = [e for e in th.events if e.program]
            span = covering(program, a, b)
            if span is not None:
                parents = chain(program, span)
                row["threads"][th.label] = {
                    "span": span.name,
                    "chain": [e.name for e in parents],
                    "step": next((e.step for e in reversed(parents) if e.step is not None), None),
                    "cover_ms": _overlap(span, a, b) * 1e-6,
                    "parts_ms": [[n, ns * 1e-6] for n, ns in self_cover(program, a, b)[:4]],
                }
            else:
                other = covering(th.events, a, b)
                row["threads"][th.label] = {
                    "span": None,
                    "running": other.name if other else None,
                    "cover_ms": _overlap(other, a, b) * 1e-6 if other else 0.0,
                }
        rows.append(row)
    return rows


# -- the program's own record ----------------------------------------------------


def read_scalars(jsonl: str) -> List[Dict[str, float]]:
    """The ``scalars`` of every metrics line that holds the loop's counters."""
    out = []
    with open(jsonl) as f:
        for line in f:
            if not line.endswith("\n"):
                break                           # a torn last line
            scalars = json.loads(line).get("scalars")
            if scalars and DISPATCHES in scalars:
                out.append(scalars)
    return out


def host_table(first: Dict[str, float], last: Dict[str, float]) -> Dict[str, object]:
    """Host milliseconds by span between two snapshots of the registry:
    per dispatch for every ``learner/*``, ``snapshot/*`` and ``transport/*``
    span, per log boundary for ``learner/boundary`` and its children, and
    ``learner/iteration``'s self time per dispatch."""

    def delta(key: str) -> float:
        return (last.get(key) or 0.0) - (first.get(key) or 0.0)

    dispatches, boundaries = delta(DISPATCHES), delta(BOUNDARIES)
    spans = sorted(
        k[len("span/"):-len("/total_s")] for k in last
        if k.startswith(("span/learner/", "span/snapshot/", "span/transport/"))
        and k.endswith("/total_s")
    )
    total_ms = {s: delta(f"span/{s}/total_s") * 1e3 for s in spans}
    count = {s: delta(f"span/{s}/count") for s in spans}
    out: Dict[str, object] = {
        "dispatches": dispatches, "boundaries": boundaries,
        "frozen_dispatches": delta("league/frozen_dispatches_total"),
        "league_report_fetches": delta("league/report_fetches_total"),
        "per_dispatch_ms": {}, "per_boundary_ms": {}, "count": count,
    }
    if dispatches > 0:
        out["per_dispatch_ms"] = {s: total_ms[s] / dispatches for s in spans}
        if ITERATION in total_ms:
            children = sum(total_ms.get(c, 0.0) for c in ITERATION_CHILDREN)
            out["iteration_self_ms"] = (total_ms[ITERATION] - children) / dispatches
    if boundaries > 0:
        out["per_boundary_ms"] = {
            s: total_ms[s] / boundaries for s in spans
            if s == BOUNDARY or s.startswith(BOUNDARY + "/")
        }
    return out


# -- printing ------------------------------------------------------------------


def _print_gaps(rows: List[Dict[str, object]]) -> None:
    if not rows:
        print("no idle gap that long inside the traced window")
    for row in rows:
        print(f"gap {row['gap_ms']:.3f} ms at {row['at_ms']:.1f} ms   benchmark's label: {row['bench']}")
        for label, t in row["threads"].items():
            if t["span"] is not None:
                step = "" if t["step"] is None else f" step={t['step']}"
                print(f"    {label}: {' > '.join(t['chain'])}{step} covers {t['cover_ms']:.3f} ms")
                if len(t["parts_ms"]) > 1:
                    parts = ", ".join(f"{n} {ms:.3f}" for n, ms in t["parts_ms"])
                    print(f"        by self time: {parts}")
            elif t["running"] is not None:
                print(f"    {label}: no program span; running {t['running']} for {t['cover_ms']:.3f} ms")
            else:
                print(f"    {label}: no program span, no host event")


def _print_table(table: Dict[str, object]) -> None:
    print(
        f"{table['dispatches']:.0f} dispatches ({table['frozen_dispatches']:.0f} against a frozen "
        f"opponent), {table['boundaries']:.0f} log boundaries, "
        f"{table['league_report_fetches']:.0f} league report fetches"
    )
    print("host ms per dispatch, by span (count in the interval):")
    for s, ms in table["per_dispatch_ms"].items():
        print(f"    {s:40s} {ms:10.4f}   ({table['count'][s]:.0f})")
    if "iteration_self_ms" in table:
        print(f"    {ITERATION + ' self':40s} {table['iteration_self_ms']:10.4f}")
    print("host ms per log boundary, by span:")
    for s, ms in table["per_boundary_ms"].items():
        print(f"    {s:40s} {ms:10.4f}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("run_dir", help="benchmark_out/<cell>: holds trace/ and metrics.jsonl")
    p.add_argument("--xplane", help="the .xplane.pb, where it is not under <run_dir>/trace")
    p.add_argument("--min-gap-ms", type=float, default=1.0)
    p.add_argument("--json", action="store_true", help="print one JSON object instead")
    args = p.parse_args(argv)
    path = args.xplane or trace.find_xplane(os.path.join(args.run_dir, "trace"))
    out: Dict[str, object] = {}
    if path is not None:
        out["gaps"] = attribute_gaps(trace.load(path), load_threads(path), args.min_gap_ms * 1e6)
    jsonl = os.path.join(args.run_dir, "metrics.jsonl")
    if os.path.exists(jsonl):
        lines = read_scalars(jsonl)
        if len(lines) >= 2:
            out["host"] = host_table(lines[0], lines[-1])
    if not out:
        sys.exit(f"host_spans: neither a kept trace nor a metrics.jsonl with the loop's counters under {args.run_dir}")
    if args.json:
        print(json.dumps(out, indent=1))
        return 0
    if "gaps" in out:
        _print_gaps(out["gaps"])
    if "host" in out:
        _print_table(out["host"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
