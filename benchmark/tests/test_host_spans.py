"""``benchmark/tools/host_spans.py``: gaps put down to hand-made spans with
hand-computed answers, the host table from two hand-made snapshots, the
host plane of a trace recorded here on the CPU (threads, parents, steps),
and the trace recorded on the v5e kept beside this file."""

import glob
import json
import os
import threading
import time

import pytest

from benchmark.harness import trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.tools import host_spans
from benchmark.tools.host_spans import HostEvent, Thread

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6      # ns


def test_program_spans_are_told_from_other_host_events():
    for name in ("learner/iteration", "learner/boundary/league_fetch", "snapshot/stats_fetch"):
        assert HostEvent(name, 0, 1).program
    for name in ("bench:traced_window", "PjitFunction(fused)", "ParseArguments", "learner", "tsl::Foo/Bar", ""):
        assert not HostEvent(name, 0, 1).program


def hand_made():
    """A window of 100 ms. The device idles over [10, 14] (the enqueue of
    step 8 into an empty queue), [50, 57] (a league fetch ended at 51, then
    the rest of the boundary) and [80, 80.5] (too short to report)."""
    ops = [
        Op("fusion.1", "jit(fused)/phase_rollout/mul", 0 * MS, 10 * MS),
        Op("fusion.1", "jit(fused)/phase_rollout/mul", 14 * MS, 50 * MS),
        Op("fusion.1", "jit(fused)/phase_rollout/mul", 57 * MS, 80 * MS),
        Op("fusion.1", "jit(fused)/phase_rollout/mul", 80.5 * MS, 100 * MS),
    ]
    tr = trace.Trace(
        [DevicePlane("/device:TPU:0", ops, [])],
        [Span("bench:traced_window", 0, 100 * MS),
         Span("bench:learner_enqueues_dispatch", 9 * MS, 14 * MS),
         Span("bench:learner_loop_between_dispatches", 14 * MS, 58 * MS)],
    )
    train = Thread("python[learner/iteration]", [
        HostEvent("learner/iteration", 8 * MS, 60 * MS, step=8),
        HostEvent("learner/league_draw", 8 * MS, 9 * MS),
        HostEvent("learner/dispatch", 9 * MS, 14.5 * MS),
        HostEvent("PjitFunction(fused)", 9.5 * MS, 14 * MS),
        HostEvent("learner/boundary", 20 * MS, 58 * MS, step=10),
        HostEvent("learner/boundary/league_fetch", 20 * MS, 51 * MS),
        HostEvent("learner/boundary/gauges", 51 * MS, 56 * MS),
        HostEvent("learner/boundary/stats_drain", 56 * MS, 57.5 * MS),
    ])
    snapshot = Thread("python[snapshot/stats_fetch]", [
        HostEvent("snapshot/stats_fetch", 30 * MS, 50.5 * MS),
        HostEvent("tsl::BlockUntilReady", 9 * MS, 13 * MS),
    ])
    return tr, [train, snapshot]


def test_gaps_are_put_down_to_the_innermost_covering_span_of_each_thread():
    tr, threads = hand_made()
    rows = host_spans.attribute_gaps(tr, threads, min_gap_ns=1 * MS)
    assert [(r["at_ms"], r["gap_ms"]) for r in rows] == [(50.0, 7.0), (10.0, 4.0)]
    first, second = rows
    assert first["bench"] == "bench:learner_loop_between_dispatches"
    train = dict(first["threads"]["python[learner/iteration]"])
    # how the gap divides among the thread's spans, by self time
    assert [(n, round(ms, 6)) for n, ms in train.pop("parts_ms")] == [
        ("learner/boundary/gauges", 5.0), ("learner/boundary/league_fetch", 1.0),
        ("learner/boundary/stats_drain", 1.0), ("learner/iteration", 0.0),
    ]
    assert train == {
        "span": "learner/boundary/gauges",
        "chain": ["learner/iteration", "learner/boundary", "learner/boundary/gauges"],
        "step": 10, "cover_ms": pytest.approx(5.0),
    }
    # half a millisecond of the fetch reaches into the gap
    assert first["threads"]["python[snapshot/stats_fetch]"]["span"] == "snapshot/stats_fetch"
    assert first["threads"]["python[snapshot/stats_fetch]"]["cover_ms"] == pytest.approx(0.5)
    assert second["bench"] == "bench:learner_enqueues_dispatch"
    assert second["threads"]["python[learner/iteration]"] == {
        "span": "learner/dispatch", "chain": ["learner/iteration", "learner/dispatch"],
        "step": 8, "cover_ms": pytest.approx(4.0),
        "parts_ms": [["learner/dispatch", pytest.approx(4.0)], ["learner/iteration", pytest.approx(0.0)]],
    }
    # no program span on that thread then: the host event that ran instead
    assert second["threads"]["python[snapshot/stats_fetch]"] == {
        "span": None, "running": "tsl::BlockUntilReady", "cover_ms": pytest.approx(3.0),
    }
    # a lower threshold brings the short gap in
    assert len(host_spans.attribute_gaps(tr, threads, min_gap_ns=0.1 * MS)) == 3
    host_spans._print_gaps(rows)


def test_host_table_is_a_difference_of_two_snapshots(tmp_path):
    def snap(dispatches, boundaries, scale):
        s = {
            "learner/dispatches_total": dispatches, "learner/boundaries_total": boundaries,
            "league/frozen_dispatches_total": dispatches / 2, "league/report_fetches_total": boundaries,
        }
        for name, ms in {
            "learner/iteration": 20.0, "learner/league_draw": 1.0, "learner/dispatch": 12.0,
            "learner/league_report": 0.5, "learner/boundary": 4.0,
            "learner/boundary/league_fetch": 3.0, "learner/boundary/gauges": 0.5,
            "snapshot/stats_fetch": 2.0, "buffer/insert": 9.0,
        }.items():
            s[f"span/{name}/total_s"] = ms * 1e-3 * scale
            s[f"span/{name}/count"] = scale
        return s

    # 3 warm-up dispatches, then 10 more and 2 boundaries; totals are given
    # per unit of `scale`, so the interval holds 10 units
    first, last = snap(3, 0, 3), snap(13, 2, 13)
    table = host_spans.host_table(first, last)
    assert (table["dispatches"], table["boundaries"], table["frozen_dispatches"]) == (10, 2, 5)
    assert table["per_dispatch_ms"]["learner/dispatch"] == pytest.approx(12.0)
    assert table["per_dispatch_ms"]["snapshot/stats_fetch"] == pytest.approx(2.0)
    assert "buffer/insert" not in table["per_dispatch_ms"]
    # 20 - (1 + 12 + 0.5 + 4): what no child of the iteration names
    assert table["iteration_self_ms"] == pytest.approx(2.5)
    assert table["per_boundary_ms"] == pytest.approx({
        "learner/boundary": 20.0, "learner/boundary/league_fetch": 15.0, "learner/boundary/gauges": 2.5,
    })
    host_spans._print_table(table)

    jsonl = tmp_path / "metrics.jsonl"
    jsonl.write_text(
        json.dumps({"step": 0, "scalars": {"loss": 1.0}}) + "\n"
        + json.dumps({"step": 3, "scalars": first}) + "\n"
        + json.dumps({"event": "ALERT"}) + "\n"
        + json.dumps({"step": 13, "scalars": last}) + "\n"
        + '{"step": 14, "scalars": {"learner/dispatches_total"'        # torn
    )
    lines = host_spans.read_scalars(str(jsonl))
    assert lines == [first, last]


def test_threads_parents_and_steps_from_a_trace_recorded_here(tmp_path):
    """The host plane as the profiler writes it: both Python threads' lines
    are named ``python``; a span's attributes are stats of its event."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    A = jax.profiler.TraceAnnotation

    def snapshot_thread():
        with A("snapshot/stats_fetch"):
            time.sleep(0.002)

    with A("bench:traced_window"):
        with A("learner/iteration", step=41):
            other = threading.Thread(target=snapshot_thread)
            other.start()
            with A("learner/boundary", step=42):
                with A("learner/boundary/gauges"):
                    time.sleep(0.001)
            other.join()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    threads = {t.label: t for t in host_spans.load_threads(path)}
    assert set(threads) == {"python[learner/iteration]", "python[snapshot/stats_fetch]"}
    program = [e for e in threads["python[learner/iteration]"].events if e.program]
    assert [(e.name, e.step) for e in program] == [
        ("learner/iteration", 41), ("learner/boundary", 42), ("learner/boundary/gauges", None),
    ]
    gauges = program[-1]
    assert [e.name for e in host_spans.chain(program, gauges)] == [e.name for e in program]
    assert gauges.end - gauges.start >= 1 * MS
    # the window is on the same clock as trace.load's spans
    window = trace.load(path).span("bench:traced_window")
    assert window.start <= program[0].start and program[0].end <= window.end


# -- the recorded trace --------------------------------------------------------
# data/tpu_v5e_1chip_phases.xplane.pb: one v5e chip, PR 24, written by
# tools/record_phases_fixture.py. Three dispatches of a tiny fused learner
# (8 games of 1v1 against frozen league opponents, 2-step rollouts, a log
# boundary after the second) between two drained points, with the program's scopes on the device
# plane and its spans on the host plane. At this size the device is idle most
# of the window, which is what the tool is for. The numbers below were read
# from it once and are pinned.

TRAIN, SNAPSHOT = "python3[learner/iteration]", "python3[snapshot/stats_fetch]"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data", "tpu_v5e_1chip_phases.xplane.pb")
    assert os.path.getsize(path) < 300 * 1024
    threads = sorted(host_spans.load_threads(path), key=lambda t: t.label)
    return trace.load(path), threads


def test_recorded_threads_and_their_program_spans(recorded):
    tr, threads = recorded
    assert [(t.label, sum(e.program for e in t.events)) for t in threads] == [(TRAIN, 19), (SNAPSHOT, 5)]
    train = [e for e in threads[0].events if e.program]
    assert [e.step for e in train if e.name == "learner/iteration"] == [2, 3, 4]
    assert [e.step for e in train if e.name == "learner/boundary"] == [4]
    names = {e.name for e in train}
    assert {f"learner/boundary/{c}" for c in ("flush_health", "league_fetch", "gauges", "stats_drain", "submit_metrics")} <= names
    assert {e.name for e in threads[1].events if e.program} == {
        "snapshot/stats_fetch", "learner/metrics_fetch", "transport/publish_weights",
    }
    # every child lies inside its parent on the same thread
    for e in train:
        if e.name.startswith("learner/boundary/"):
            assert [p.name for p in host_spans.chain(train, e)] == ["learner/iteration", "learner/boundary", e.name]


def test_recorded_gaps_are_put_down_to_the_programs_spans(recorded):
    tr, threads = recorded
    window = tr.span("bench:traced_window")
    assert window.end - window.start == pytest.approx(99_048_501.0)
    rows = host_spans.attribute_gaps(tr, threads)
    assert len(rows) == 13
    assert sum(r["gap_ms"] for r in rows) == pytest.approx(65.572676, rel=1e-6)
    got = [(round(r["gap_ms"], 3), r["threads"][TRAIN]["span"], r["threads"][TRAIN].get("step")) for r in rows]
    assert got == [
        (9.642, "learner/dispatch", 2),            # an enqueue into an empty queue
        (9.299, None, None),                       # after train() returned: no span of the program
        (9.188, "learner/dispatch", 3),
        (8.061, "learner/boundary/league_fetch", 4),
        (7.996, "learner/dispatch", 4),
        (7.277, "actor/drain", None),              # the end of train(): the last drain
        (3.939, "learner/iteration", 4),           # no child over half of it: see parts_ms
        (3.286, "learner/league_draw", 4),         # a pool snapshot on the train thread
        (1.822, "learner/boundary/stats_drain", 4),
        (1.553, "learner/boundary/stats_drain", 4),
        (1.382, "learner/dispatch", 2),
        (1.113, "learner/boundary/stats_drain", 4),
        (1.016, "learner/boundary/stats_drain", 4),
    ]
    fetch = rows[3]["threads"]
    assert fetch[TRAIN]["chain"] == ["learner/iteration", "learner/boundary", "learner/boundary/league_fetch"]
    assert fetch[TRAIN]["cover_ms"] == pytest.approx(5.82376)
    assert fetch[SNAPSHOT] == {"span": None, "running": None, "cover_ms": 0.0}
    assert rows[1]["threads"][TRAIN]["running"] == "bench:traced_window"
    # no span over half of this one (the train thread was between two
    # iterations for the rest): the largest cover, and its division by self time
    parts = rows[6]["threads"][TRAIN]["parts_ms"]
    assert [n for n, _ in parts] == ["learner/dispatch", "learner/iteration", "learner/league_report"]
    assert parts[0][1] == pytest.approx(1.705557)
    # what the second thread held meanwhile
    assert rows[7]["threads"][SNAPSHOT]["span"] == "snapshot/stats_fetch"
    assert rows[7]["threads"][SNAPSHOT]["cover_ms"] == pytest.approx(2.061981)
    # the fixture's runner opens no bench: span but the window
    assert {r["bench"] for r in rows} == {"unlabelled"}


def test_recorded_scopes_are_read_by_the_new_readers(recorded):
    """Where the wrappers sit on the chip: the rule of readers/_scopes.py
    was pinned from this trace and the cells' own."""
    from benchmark.harness import cells
    from benchmark.readers import _scopes

    tr, _ = recorded
    window = tr.span("bench:traced_window")
    record = {"trace": tr, "trace_window": (window.start, window.end)}

    def read(name):
        return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=name)).read(record)

    got = {n: read(n) for n in (
        "rollout_device_share", "update_device_share", "unscoped_device_share", "rollout_policy_device_share",
        "sampling_device_share", "update_loss_device_share", "optimizer_device_share",
    )}
    assert got == pytest.approx({
        "rollout_device_share": 65.751001, "update_device_share": 11.936925, "unscoped_device_share": 22.312073,
        "rollout_policy_device_share": 4.045318, "sampling_device_share": 8.369064,
        "update_loss_device_share": 2.399738, "optimizer_device_share": 4.589438,
    }, rel=1e-6)
    assert got["rollout_device_share"] + got["update_device_share"] + got["unscoped_device_share"] == pytest.approx(100.0)
    scopes = {op.scope for op in tr.devices[0].ops}
    # the outer scopes stay outside the wrappers; a backward operation keeps them
    assert any(s.startswith("jit(one_iter)/phase_update/update_loss/transpose(jvp(Policy.sequence))/policy_core_scan/") for s in scopes)
    assert any(s.startswith("jit(one_iter)/phase_rollout/while/body/closed_call/rollout_sim_step/") for s in scopes)
    # what is under neither phase holds none of the program's scopes: the
    # compiler's own operations (no scoped name at all: copies, the loop
    # itself), copies named after the entry's arguments, tiny eager programs
    unscoped = [op for op in tr.devices[0].ops if not any(_scopes.under(op, p) for p in _scopes.PHASES)]
    assert unscoped
    for op in unscoped:
        assert not any(seg.startswith(("phase_", "rollout_", "update_", "policy_")) for seg in _scopes.segments(op.scope)), op.scope
