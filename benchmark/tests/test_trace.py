"""The trace reduction: interval arithmetic on hand-made intervals with
hand-computed answers, then the same functions on the small recorded TPU
traces kept beside this file."""

import os

import pytest

from benchmark.harness import trace
from benchmark.harness.trace import DevicePlane, Op, Span

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_total_clip_subtract_gaps():
    iv = [(5, 9), (0, 2), (1, 3), (9, 10), (20, 20)]
    assert trace.merge(iv) == [(0, 3), (5, 10)]
    assert trace.total(iv) == 8
    assert trace.clip(iv, 2, 6) == [(5, 6), (2, 3)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert trace.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]
    assert trace.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]


def plane():
    """One chip, times in ns. Two executions of the program ``jit_fused``
    ([0, 100] and [130, 230]) and one of ``jit_copy`` between them."""
    ops = [
        Op("fusion.1", "jit(fused)/policy_trunk/dot_general", 0, 20),
        Op("fusion.2", "jit(fused)/while/body/policy_core/dot_general", 20, 50),
        Op("fusion.3", "jit(fused)/transpose(jvp(policy_core_scan))/while/body/dot_general", 50, 70),
        Op("fusion.4", "jit(fused)/policy_heads/dot_general", 70, 75),
        Op("all-reduce-start.1", "jit(fused)/psum", 75, 76),
        Op("fusion.5", "jit(fused)/adam/mul", 76, 90),
        Op("all-reduce-done.1", "jit(fused)/psum", 90, 100),
        Op("copy.1", "jit(copy)/copy", 110, 115),
        Op("fusion.1", "jit(fused)/policy_trunk/dot_general", 130, 150),
        Op("all-reduce.7", "jit(fused)/psum", 150, 160),
        Op("fusion.5", "jit(fused)/adam/mul", 160, 230),
    ]
    modules = [
        Span("jit_fused(123)", 0, 100), Span("jit_copy(9)", 108, 116),
        Span("jit_fused(123)", 130, 230),
    ]
    return DevicePlane("/device:TPU:0", ops, modules)


def test_busy_union_and_idle_share():
    p = plane()
    assert trace.busy_seconds(p, 0, 230) == pytest.approx(205e-9)      # 100 + 5 + 100
    assert trace.busy_seconds(p, 95, 135) == pytest.approx((5 + 5 + 5) * 1e-9)
    idle = 1 - trace.busy_seconds(p, 0, 230) / 230e-9
    assert idle == pytest.approx(25 / 230)


def test_scope_grouping():
    got = trace.scope_seconds(plane(), 0, 230)
    assert got == pytest.approx({
        "policy_trunk": 40e-9,
        "policy_core": 50e-9,          # forward and the transposed scan body
        "policy_heads": 5e-9,
        "collective": 21e-9,
        "other": 14e-9 + 5e-9 + 70e-9,
    })
    assert sum(got.values()) == pytest.approx(trace.busy_seconds(plane(), 0, 230))
    # whole operations that start inside the window
    assert trace.scope_seconds(plane(), 20, 50) == pytest.approx({"policy_core": 30e-9})


def test_a_loop_is_counted_by_self_time():
    """A while event encloses its body's events: the body's time belongs to
    the body's layers, the loop keeps only what is left."""
    ops = [
        Op("while.1", "jit(f)/while", 0, 100),
        Op("fusion.a", "jit(f)/while/body/closed_call/policy_core/dot_general", 5, 45),
        Op("fusion.b", "jit(f)/while/body/closed_call/sim_step", 50, 90),
        Op("fusion.c", "jit(f)/adam", 100, 110),
    ]
    p = DevicePlane("/device:TPU:0", ops, [])
    loop = p.ops[0]
    assert (loop.name, loop.encloses, loop.self_ns) == ("while.1", 2, 20)
    assert trace.busy_seconds(p, 0, 110) == pytest.approx(110e-9)
    assert trace.scope_seconds(p, 0, 110) == pytest.approx(
        {"policy_core": 40e-9, "other": 70e-9}      # fusion.a | loop 20 + b 40 + c 10
    )


def test_module_gaps_and_dominant_module():
    p = plane()
    assert trace.dominant_module(p, 0, 230) == "jit_fused(123)"
    assert trace.module_gaps(p, 0, 230, "jit_fused(123)") == pytest.approx([30e-9])
    # an execution cut by the window's edge is not a whole execution
    assert trace.module_gaps(p, 10, 230, "jit_fused(123)") == []


def test_collective_overlap():
    inflight, exposed = trace.collective_seconds(plane(), 0, 230)
    # async pair in flight [75, 100], hidden by fusion.5 over [76, 90];
    # the synchronous all-reduce.7 [150, 160] is all exposed
    assert inflight == pytest.approx((25 + 10) * 1e-9)
    assert exposed == pytest.approx((1 + 10 + 10) * 1e-9)


def test_breakdown_lists():
    p = plane()
    tr = trace.Trace([p], [Span("bench:learner_loop_between_dispatches", 98, 128),
                           Span("bench:learner_enqueues_dispatch", 128, 131)])
    top = trace.top_ops(tr, 0, 230, n=3)
    assert top[0] == ["other/fusion.5 mul", pytest.approx(84e-9)]
    assert top[1] == ["policy_trunk/fusion.1 dot_general", pytest.approx(40e-9)]
    gaps = trace.idle_gaps_by_span(p, tr.spans, 0, 230)
    assert gaps[0] == ["bench:learner_loop_between_dispatches", pytest.approx(15e-9)]
    assert gaps[1] == ["bench:learner_loop_between_dispatches", pytest.approx(10e-9)]
    assert len(gaps) == 2


# -- the recorded trace --------------------------------------------------------
# data/tpu_v5e_1chip.xplane.pb: one v5e chip, PR 22. Three executions of a
# small jitted train step (a scoped trunk, a three-step lax.scan scoped
# policy_core_scan with policy_core inside, scoped heads, and their backward
# pass) with a tiny unscoped program between them, inside the benchmark's
# own host spans. The numbers below were read from it once and are pinned.


@pytest.fixture(scope="module")
def recorded():
    tr = trace.load(os.path.join(HERE, "data", "tpu_v5e_1chip.xplane.pb"))
    w = tr.span("bench:traced_window")
    return tr, w.start, w.end


def test_recorded_trace_loads_with_scopes_modules_and_spans(recorded):
    tr, lo, hi = recorded
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    plane = tr.devices[0]
    assert len(plane.ops) == 153 and len(plane.modules) == 6
    assert {m.name.split("(")[0] for m in plane.modules} == {"jit_train", "jit_other"}
    names = [s.name for s in tr.spans]
    assert names.count("bench:learner_enqueues_dispatch") == 3
    assert names.count("bench:learner_loop_between_dispatches") == 3
    assert names.count("bench:benchmark_waits_for_device") == 1
    assert (hi - lo) == pytest.approx(9_729_319.0)
    scopes = {op.scope for op in plane.ops}
    assert "jit(train)/jvp(policy_trunk)/dot_general:" in scopes
    assert any("transpose(jvp(policy_core_scan))" in s for s in scopes)


def test_recorded_loops_enclose_their_bodies(recorded):
    tr, lo, hi = recorded
    loops = [op for op in tr.devices[0].ops if op.encloses]
    assert {op.name for op in loops} == {"while.6", "while.7"} and len(loops) == 6
    for op in loops:
        assert op.encloses == 9                      # 3 steps x 3 operations
        assert 0 < op.self_ns < 0.01 * (op.end - op.start)


def test_recorded_busy_idle_and_scope_shares(recorded):
    tr, lo, hi = recorded
    plane = tr.devices[0]
    busy = trace.busy_seconds(plane, lo, hi)
    assert busy == pytest.approx(127_770.704e-9, rel=1e-6)
    assert 1 - busy / ((hi - lo) * 1e-9) == pytest.approx(0.986868, rel=1e-5)
    by_scope = trace.scope_seconds(plane, lo, hi)
    assert by_scope == pytest.approx({
        "policy_core": 64.609140e-6, "other": 32.596018e-6,
        "policy_heads": 20.119296e-6, "policy_trunk": 10.446250e-6,
    }, rel=1e-6)
    # self times of whole operations add up to the busy union
    assert sum(by_scope.values()) == pytest.approx(busy, rel=1e-9)


def test_recorded_module_gaps_and_gap_labels(recorded):
    tr, lo, hi = recorded
    plane = tr.devices[0]
    assert trace.dominant_module(plane, lo, hi).startswith("jit_train(")
    # the device stamped the first execution 0.6 ms before the host span
    # opened (the two clocks agree only to about a millisecond), so two
    # whole executions of jit_train lie inside the window
    assert trace.module_gaps(plane, lo, hi, "jit_train") == pytest.approx([3.08686625e-3])
    spans = [s for s in tr.spans if s.name != "bench:traced_window"]
    gaps = trace.idle_gaps_by_span(plane, spans, lo, hi)
    assert gaps[0] == ["bench:learner_loop_between_dispatches", pytest.approx(2.6457675e-3)]
    assert trace.top_ops(tr, lo, hi, 2) == [
        ["other/sine_reduce_fusion reduce_sum tmp/record_fixture.py:28", pytest.approx(23.121094e-6)],
        ["policy_core/fusion.46 dot_general tmp/record_fixture.py:14", pytest.approx(19.242890e-6)],
    ]
    # one chip: nothing to reduce across
    assert trace.collective_seconds(plane, lo, hi) == (0.0, 0.0)


def test_share_where_by_source_and_kind(recorded):
    ops = [
        Op("fusion.1", "jit(f)/while/body/gather:", 0, 30, source="/x/dotaclient_tpu/envs/jax_lane_sim.py:533"),
        Op("fusion.2", "jit(f)/while/body/mul:", 30, 40, source="/x/dotaclient_tpu/features/jax_featurizer.py:10"),
        Op("fusion.3", "jit(f)/policy_core/dot_general:", 40, 100, source="/x/flax/linen/linear.py:1"),
    ]
    p = DevicePlane("/device:TPU:0", ops, [])
    assert trace.share_where(p, 0, 100, lambda op: "envs/jax_lane_sim.py" in op.source) == pytest.approx(0.3)
    assert trace.share_where(p, 0, 100, lambda op: op.scope.rstrip(":").endswith("/gather")) == pytest.approx(0.3)
    assert trace.share_where(p, 0, 100, lambda op: False) == 0.0
    assert trace.share_where(p, 200, 300, lambda op: True) is None
    # the recorded operations carry the line of the script that made them
    tr, lo, hi = recorded
    sources = {op.source.rsplit("/", 1)[-1].split(":")[0] for op in tr.devices[0].ops if op.source}
    assert sources == {"record_fixture.py"}


def test_async_collectives_of_one_name_pair_first_in_first_out():
    ops = [
        Op("async-collective-start", "jit(f)/while/body/gather", 0, 1),
        Op("async-collective-start", "jit(f)/while/body/gather", 1, 2),
        Op("fusion.1", "jit(f)/while/body/mul", 2, 10),
        Op("async-collective-done", "jit(f)/while/body/gather", 10, 12),
        Op("async-collective-done", "jit(f)/while/body/gather", 12, 13),
    ]
    inflight, exposed = trace.collective_seconds(DevicePlane("/device:TPU:0", ops, []), 0, 13)
    assert inflight == pytest.approx(13e-9)          # [0, 12] and [1, 13]
    assert exposed == pytest.approx(5e-9)            # all but fusion.1's [2, 10]


def test_recorded_four_chip_trace_has_exposed_all_reduces():
    """data/tpu_v5e_4chip.xplane.pb: the same script on a 2x2 host (PR 22),
    the batch sharded over four chips, so the gradient is all-reduced."""
    tr = trace.load(os.path.join(HERE, "data", "tpu_v5e_4chip.xplane.pb"))
    w = tr.span("bench:traced_window")
    lo, hi = w.start, w.end
    assert [d.name for d in tr.devices] == [f"/device:TPU:{i}" for i in range(4)]
    for plane in tr.devices:
        assert len(plane.ops) == 165
        assert sorted({op.name for op in plane.ops if op.collective}) == ["all-reduce", "all-reduce.4"]
        inflight, exposed = trace.collective_seconds(plane, lo, hi)
        # synchronous all-reduces: nothing runs beside them
        assert 70e-6 < inflight < 90e-6 and exposed == pytest.approx(inflight)
        assert trace.scope_seconds(plane, lo, hi)["collective"] == pytest.approx(inflight)
    first = tr.devices[0]
    assert trace.collective_seconds(first, lo, hi)[0] == pytest.approx(84.627578e-6, rel=1e-6)
    assert trace.busy_seconds(first, lo, hi) == pytest.approx(131.317732e-6, rel=1e-6)
    assert trace.mean_share_where(tr, lo, hi, lambda op: op.collective) == pytest.approx(63.377, rel=1e-4)
