"""The seven ``*_device_share`` readers of PR 24 and the helper they share:
the segment rule on hand-made paths, every reader on a hand-made plane with
hand-computed answers, and on the traces recorded in PR 22, which hold none
of the program's new scopes."""

import os

import pytest

from benchmark.harness import cells, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.readers import _scopes

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = (
    "rollout_device_share", "update_device_share", "unscoped_device_share",
    "rollout_policy_device_share", "sampling_device_share",
    "update_loss_device_share", "optimizer_device_share",
)


def read(name, record):
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=name)).read(record)


@pytest.mark.parametrize("path, want", [
    # forward, in the rollout's scan
    ("jit(fused)/phase_rollout/while/body/closed_call/rollout_sim_step/gather:",
     ("jit(fused)", "phase_rollout", "while", "body", "closed_call", "rollout_sim_step", "gather")),
    # backward: the outer scopes stay outside the wrappers
    ("jit(fused)/phase_update/update_loss/transpose(jvp(policy_core_scan))/while/body/dot_general:",
     ("jit(fused)", "phase_update", "update_loss", "policy_core_scan", "while", "body", "dot_general")),
    # a scope wrapped on its own, and an empty wrapper
    ("jit(fused)/phase_update/update_loss/transpose(phase_update)/update_loss/jvp()/mul",
     ("jit(fused)", "phase_update", "update_loss", "phase_update", "update_loss", "", "mul")),
    # another function's brackets are not a wrapper
    ("jit(f)/jvp(jit(log_softmax))/sub", ("jit(f)", "jit(log_softmax)", "sub")),
    ("", ("",)),
])
def test_segments_take_the_wrappers_off(path, want):
    assert _scopes.segments(path) == want


def test_a_scope_is_matched_as_a_whole_segment():
    inside = Op("fusion.1", "jit(f)/phase_rollout/while/body/dynamic_update_slice:", 0, 1)
    assert _scopes.under(inside, "phase_rollout")
    assert not _scopes.under(inside, "update_slice") and not _scopes.under(inside, "update")
    named = Op("fusion.2", "jit(f)/phase_update/update_loss/jvp(update_gae)/scan:", 0, 1)
    assert _scopes.under(named, "phase_update", "update_loss", "update_gae")
    assert not _scopes.under(named, "phase_update", "update_optimizer")
    # the policy's scopes are decided as the older readers decide them
    assert _scopes.in_policy(Op("f", "jit(f)/phase_rollout/while/body/policy_core/dot_general", 0, 1))
    assert _scopes.in_policy(Op("f", "jit(f)/phase_update/update_loss/jvp(Policy.sequence)/policy_trunk/Policy._trunk/dot", 0, 1))
    assert not _scopes.in_policy(named)


def plane():
    """One chip, one dispatch of 100 ns with a loop for the rollout."""
    R, U = "jit(fused)/phase_rollout", "jit(fused)/phase_update"
    ops = [
        Op("while.1", f"{R}/while", 0, 50),                                              # self 2
        Op("fusion.1", f"{R}/while/body/closed_call/rollout_featurize/gather", 0, 8),
        Op("fusion.2", f"{R}/while/body/closed_call/policy_core/dot_general", 8, 20),
        Op("fusion.3", f"{R}/while/body/closed_call/rollout_sample/jit(take_along_axis)/gather", 20, 26),
        Op("fusion.4", f"{R}/while/body/closed_call/rollout_sim_step/gather", 26, 48),
        Op("fusion.5", f"{R}/rollout_assemble/transpose", 50, 52),
        Op("fusion.6", f"{U}/update_loss/jvp(policy_core_scan)/while/body/dot_general", 52, 62),
        Op("fusion.7", f"{U}/update_loss/transpose(jvp(policy_core_scan))/while/body/dot_general", 62, 80),
        Op("fusion.8", f"{U}/update_loss/transpose(jvp(jit(take_along_axis)))/scatter-add", 80, 86),
        Op("fusion.9", f"{U}/update_loss/jvp(update_gae)/while/body/mul", 86, 88),
        Op("all-reduce.1", f"{U}/update_loss/transpose(jvp(policy_core_scan))/while/body/dot_general", 88, 90),
        Op("fusion.10", f"{U}/update_optimizer/mul", 90, 96),
        Op("fusion.11", f"{U}/update_probe/is_finite", 96, 97),
        Op("copy.1", "jit(fused)/dynamic_update_slice", 97, 100),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_fused(1)", 0, 100)])


def test_every_new_reader_on_a_hand_made_plane():
    record = {"trace": trace.Trace([plane()], []), "trace_window": (0, 100)}
    got = {name: read(name, record) for name in NEW}
    assert got == pytest.approx({
        "rollout_device_share": 52.0,            # the loop's 50 and the assembly's 2
        "update_device_share": 45.0,
        "unscoped_device_share": 3.0,            # dynamic_update_slice is no update_*
        "rollout_policy_device_share": 12.0,
        "sampling_device_share": 6.0,
        # scatter-add 6, GAE 2, and the all-reduce 2: a collective is no policy_* layer
        "update_loss_device_share": 10.0,
        "optimizer_device_share": 6.0,
    })
    assert got["rollout_device_share"] + got["update_device_share"] + got["unscoped_device_share"] == pytest.approx(100.0)
    # no trace, nothing to read
    assert all(read(name, {"trace": None, "trace_window": None}) is None for name in NEW)


@pytest.mark.parametrize("fixture", ["tpu_v5e_1chip.xplane.pb", "tpu_v5e_4chip.xplane.pb"])
def test_a_trace_without_the_scopes_reads_zero_and_all_unscoped(fixture):
    """The parent of PR 24 and the traces recorded in PR 22: each reader
    returns a number, so the line holds every per-layer metric either way."""
    tr = trace.load(os.path.join(HERE, "data", fixture))
    w = tr.span("bench:traced_window")
    record = {"trace": tr, "trace_window": (w.start, w.end)}
    for name in NEW:
        want = 100.0 if name == "unscoped_device_share" else 0.0
        assert read(name, record) == pytest.approx(want), name


def test_the_new_metrics_are_in_every_cell_with_their_layers():
    layers = {
        "rollout_device_share": "fused_program.rollout", "update_device_share": "fused_program.update",
        "unscoped_device_share": "fused_program", "rollout_policy_device_share": "fused_program.rollout",
        "sampling_device_share": "fused_program.rollout", "update_loss_device_share": "fused_program.update",
        "optimizer_device_share": "fused_program.update",
    }
    for w in cells.load_manifest()["workloads"]:
        per_layer = {m.name: m for m in cells.load_cell(w["name"]).per_layer}
        for name, layer in layers.items():
            m = per_layer[name]
            assert (m.layer, m.moves, m.source, m.unit) == (layer, "train_frames_per_s", "device_trace", "%")
