"""From a profiler trace to busy time, idle gaps, scope shares and
collective overlap.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``
(read by ``harness/xplane.py``). On a TPU each chip is a plane
``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per executed HLO
operation; a ``while`` (every ``lax.scan``) or a conditional is an event too
and encloses the events of its body, so time is attributed by SELF time: an
event's duration less what its directly enclosed events cover. Its line
``XLA Modules`` holds one event per executed program. Host threads are
lines of the plane ``/host:CPU``; the benchmark's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench:``) land on
the line ``python``. Device and host times are on one clock to within about
a millisecond (the first traced program is stamped some 1.3 ms before the
host call that enqueued it: v5e, PR 22), which is nothing against a window
of seconds and is why idle gaps shorter than that are not worth a label.

The reduction is plain interval arithmetic on ``(start, end)`` pairs, kept
apart from the loading so that it can be checked on hand-made intervals
(``benchmark/tests/test_trace.py``) as well as on the recorded traces kept
beside the tests.

What an operation belongs to is read from its scoped name: the program
names its layers with ``jax.named_scope`` (``policy_trunk``,
``policy_core``, ``policy_core_scan``, ``policy_heads``), XLA keeps that
path in each instruction's metadata, and the profiler stores it once per
operation as the stat ``tf_op`` (``jit(f)/jvp(policy_core_scan)/while/body/
closed_call/policy_core/dot_general:``). A fusion carries the path of the
instruction it was built around; a backward operation carries
``transpose(jvp(...))`` around the same path.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
SCOPE_STAT = "tf_op"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "async-collective",
)
# layer -> the names that mark it in an operation's scoped name. The program
# scopes its trunk only in step mode; in sequence mode the trunk is found by
# the path Flax gives the method (Policy._trunk), until the program names it.
POLICY_SCOPES = {
    "policy_trunk": ("policy_trunk", "Policy._trunk"),
    "policy_core": ("policy_core",),           # and policy_core_scan
    "policy_heads": ("policy_heads", "Policy._heads"),
}


# -- interval arithmetic -------------------------------------------------------


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    """Length of the union."""
    return sum(b - a for a, b in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of the union of ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    cover = merge(b)
    for lo, hi in merge(a):
        cur = lo
        for c, d in cover:
            if d <= cur:
                continue
            if c >= hi:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of ``[lo, hi]``: what the union leaves uncovered."""
    return subtract([(lo, hi)], clip(intervals, lo, hi))


# -- the loaded trace ----------------------------------------------------------


@dataclasses.dataclass
class Op:
    name: str          # HLO instruction name, e.g. "fusion.12"
    scope: str         # scoped framework name, "" where the trace has none
    start: float       # ns
    end: float         # ns
    source: str = ""        # file:line of the program that the operation came from
    self_ns: float = 0.0    # duration less directly enclosed events
    encloses: int = 0       # events directly enclosed (a loop's body)

    @property
    def collective(self) -> bool:
        return self.name.startswith(COLLECTIVES)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Op]
    modules: List[Span]

    def __post_init__(self) -> None:
        set_self_times(self.ops)


@dataclasses.dataclass
class Trace:
    devices: List[DevicePlane]
    spans: List[Span]            # the benchmark's own host spans

    def span(self, name: str) -> Optional[Span]:
        for s in self.spans:
            if s.name == name:
                return s
        return None


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return found[-1] if found else None


def set_self_times(ops: List[Op]) -> List[Op]:
    """Sort ``ops`` by start and give each its self time. Events on one line
    nest properly (a loop encloses its body), so a stack suffices."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_ns, op.encloses = op.end - op.start, 0
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.end - op.start
            stack[-1].encloses += 1
        stack.append(op)
    return ops


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into device planes and ``bench:`` host spans,
    times in nanoseconds from the earliest line's start."""
    from benchmark.harness import xplane

    space = xplane.read(path)
    base = min(
        (ln.timestamp_ns for pl in space.planes for ln in pl.lines if ln.events),
        default=0,
    )

    def times(line, ev) -> Tuple[float, float]:
        start = (line.timestamp_ns - base) + ev.offset_ps * 1e-3
        return start, start + ev.duration_ps * 1e-3

    devices: List[DevicePlane] = []
    spans: List[Span] = []
    for plane in space.planes:
        if plane.name.startswith(DEVICE_PLANE):
            meta = xplane.event_metadata(plane)
            ops: List[Op] = []
            modules: List[Span] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        md = meta.get(ev.metadata_id, {})
                        name = md.get("display_name") or md.get("name", "")
                        ops.append(Op(
                            name, str(md.get(SCOPE_STAT, "")), *times(line, ev),
                            source=str(md.get("source", "")),
                        ))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        md = meta.get(ev.metadata_id, {})
                        modules.append(Span(md.get("name", ""), *times(line, ev)))
            devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            meta = xplane.event_metadata(plane)
            for line in plane.lines:
                for ev in line.events:
                    name = meta.get(ev.metadata_id, {}).get("name", "")
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Span(name, *times(line, ev)))
    devices.sort(key=lambda d: d.name)
    spans.sort(key=lambda sp: sp.start)
    return Trace(devices, spans)


# -- metrics of one device plane over a window ---------------------------------


def scope_of(op: Op) -> str:
    """The layer an operation belongs to: the first of the program's policy
    scopes its scoped name holds (``policy_core_scan`` counts as
    ``policy_core``), ``collective`` for a collective, else ``other``."""
    if op.collective:
        return "collective"
    for layer, marks in POLICY_SCOPES.items():
        if any(mark in op.scope for mark in marks):
            return layer
    return "other"


def _intervals(ops: Iterable[Op]) -> List[Interval]:
    return [(o.start, o.end) for o in ops]


def busy_seconds(plane: DevicePlane, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an operation ran on the device."""
    return total(clip(_intervals(plane.ops), lo, hi)) * 1e-9


def _whole_ops(plane: DevicePlane, lo: float, hi: float) -> List[Op]:
    """The operations that start inside the window. A traced window starts
    and ends with the device drained, so these are whole operations."""
    return [op for op in plane.ops if lo <= op.start < hi]


def scope_seconds(plane: DevicePlane, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds by layer, by self time: they add up to the busy time
    (operations of one core run one after the other)."""
    out: Dict[str, float] = {}
    for op in _whole_ops(plane, lo, hi):
        key = scope_of(op)
        out[key] = out.get(key, 0.0) + op.self_ns * 1e-9
    return out


def module_gaps(plane: DevicePlane, lo: float, hi: float, module: str) -> List[float]:
    """Seconds between the end of one execution of ``module`` and the start
    of the next, for the executions inside the window."""
    runs = sorted(
        (m for m in plane.modules if module in m.name and m.start >= lo and m.end <= hi),
        key=lambda m: m.start,
    )
    return [
        max(b.start - a.end, 0.0) * 1e-9 for a, b in zip(runs, runs[1:])
    ]


def collective_seconds(plane: DevicePlane, lo: float, hi: float) -> Tuple[float, float]:
    """(seconds in collectives, the part of them with no compute running).

    An asynchronous collective is a ``-start`` and a ``-done`` operation of
    one name: it is in flight from the start of the first to the end of the
    second, and the compute between them hides it. A synchronous one is its
    own interval. Exposed is what remains of those intervals once every
    other operation's interval is taken away."""
    starts: Dict[str, List[Op]] = {}          # by name, first started first done
    inflight: List[Interval] = []
    compute: List[Interval] = []
    for op in plane.ops:                      # sorted by start
        if op.encloses:
            continue                          # a loop is not itself compute
        if not op.collective:
            compute.append((op.start, op.end))
            continue
        base = op.name.split(".", 1)
        kind, suffix = base[0], (base[1] if len(base) > 1 else "")
        if kind.endswith("-start"):
            starts.setdefault(kind[: -len("-start")] + "." + suffix, []).append(op)
        elif kind.endswith("-done"):
            queue = starts.get(kind[: -len("-done")] + "." + suffix)
            first = queue.pop(0) if queue else op
            inflight.append((first.start, op.end))
        else:
            inflight.append((op.start, op.end))
    # a start whose done falls outside the trace: count what is seen
    inflight.extend((op.start, op.end) for queue in starts.values() for op in queue)
    inflight = clip(inflight, lo, hi)
    exposed = subtract(inflight, clip(compute, lo, hi))
    return total(inflight) * 1e-9, total(exposed) * 1e-9


def label(op: Op) -> str:
    """``layer/name kind file:line``: the layer, the HLO instruction, the
    framework operation it was built around and the program line it came
    from (the last two parts of the path)."""
    kind = op.scope.rstrip(":").rsplit("/", 1)[-1]
    where = "/".join(op.source.rsplit("/", 2)[-2:])
    return " ".join(part for part in (f"{scope_of(op)}/{op.name}", kind, where) if part)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> List[List[object]]:
    """The device operations that took most (self) time, as ``[label,
    seconds]`` averaged over the chips, longest first."""
    acc: Dict[str, float] = {}
    for plane in trace.devices:
        for op in _whole_ops(plane, lo, hi):
            key = label(op)
            acc[key] = acc.get(key, 0.0) + op.self_ns * 1e-9
    k = max(len(trace.devices), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def idle_gaps_by_span(
    plane: DevicePlane, spans: Sequence[Span], lo: float, hi: float, n: int = 10
) -> List[List[object]]:
    """The ``n`` longest idle gaps of the device, each named by the
    benchmark's host span that covers most of it (``unlabelled`` where none
    does)."""
    longest = sorted(
        gaps(_intervals(plane.ops), lo, hi), key=lambda g: g[0] - g[1]
    )[:n]
    out = []
    for a, b in longest:
        best, best_cover = "unlabelled", 0.0
        for s in spans:
            cover = min(b, s.end) - max(a, s.start)
            if cover > best_cover:
                best, best_cover = s.name, cover
        out.append([best, (b - a) * 1e-9])
    return out


def dominant_module(plane: DevicePlane, lo: float, hi: float) -> Optional[str]:
    """The program that held the device longest inside the window: the
    cell's main program, whatever a refactor calls it."""
    acc: Dict[str, float] = {}
    for m in plane.modules:
        a, b = max(m.start, lo), min(m.end, hi)
        if b > a:
            acc[m.name] = acc.get(m.name, 0.0) + (b - a)
    return max(acc, key=acc.get) if acc else None


def mean_share_where(trace: Trace, lo: float, hi: float, keep) -> Optional[float]:
    """``share_where`` in percent, mean over the chips that were busy."""
    shares = [share_where(p, lo, hi, keep) for p in trace.devices]
    shares = [s for s in shares if s is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None


def share_where(plane: DevicePlane, lo: float, hi: float, keep) -> Optional[float]:
    """The share of the plane's busy (self) time in the window spent in the
    operations for which ``keep(op)`` holds."""
    kept = busy = 0.0
    for op in _whole_ops(plane, lo, hi):
        busy += op.self_ns
        if keep(op):
            kept += op.self_ns
    return kept / busy if busy > 0 else None
