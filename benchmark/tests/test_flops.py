"""``harness/flops.py`` against counts made by hand."""

import json
import os

import pytest

from benchmark.harness import cells, flops


def shape_of(config_name):
    with open(os.path.join(cells.BENCH_DIR, "configs", f"{config_name}.json")) as f:
        return flops.PolicyShape.from_run_config(json.load(f)["run_config"])


# multiply-adds by hand, from models/policy.py: U=32 units of F=22 features,
# E=64, G=8 globals, hero embedding 16, heads 4 + 9 + 9 + 4, query 64, value 1
UNIT_ENCODER = 32 * (22 * 64 + 64 * 64)          # 176,128
GLOBALS = 8 * 64                                  # 512
HEAD_WIDTH = 4 + 9 + 9 + 4 + 64 + 1               # 91
TARGET_DOT = 32 * 64                              # 2,048


@pytest.mark.parametrize(
    "config, hidden, trunk, core, heads",
    [
        ("dota5v5-lstm128", 128,
         UNIT_ENCODER + GLOBALS + 208 * 128,      # 203,264
         256 * 512,                               # 131,072
         128 * HEAD_WIDTH + TARGET_DOT),          # 13,696
        ("five5v5-lstm4096", 4096,
         UNIT_ENCODER + GLOBALS + 208 * 4096,     # 1,028,608
         8192 * 16384,                            # 134,217,728
         4096 * HEAD_WIDTH + TARGET_DOT),         # 374,784
    ],
)
def test_step_flops_match_hand_counts(config, hidden, trunk, core, heads):
    s = shape_of(config)
    assert s.hidden == hidden
    got = flops.step_flops(s)
    assert got == {"trunk": 2.0 * trunk, "core": 2.0 * core, "heads": 2.0 * heads}


def test_hand_totals():
    assert flops.step_flops(shape_of("dota5v5-lstm128")) == {
        "trunk": 406_528.0, "core": 262_144.0, "heads": 27_392.0,
    }
    assert flops.step_flops(shape_of("five5v5-lstm4096")) == {
        "trunk": 2_057_216.0, "core": 268_435_456.0, "heads": 749_568.0,
    }


def test_train_flops_per_frame():
    # equal learner and opponent lanes, T=16: per trained frame the policy
    # runs 2 rollout forwards, 17/16 learner forwards and 2 x 1 backward:
    # (32 + 17 + 32) / 16 = 5.0625 steps
    s = shape_of("five5v5-lstm4096")
    per_step = 2_057_216 + 268_435_456 + 749_568
    assert flops.train_flops_per_frame(s, 1280, 1280, 16) == per_step * 5.0625
    # no opponent lanes (self-play on every lane): (16 + 17 + 32) / 16
    assert flops.train_flops_per_frame(s, 10, 0, 16) == per_step * 65 / 16


def test_core_dispatch_work_by_hand():
    s = flops.PolicyShape(
        hidden=2, unit_embed=1, hero_embed=1, max_units=1, unit_features=1,
        global_features=1, action_types=1, move_bins=1, abilities=1,
        dtype="bfloat16",
    )
    w = flops.core_dispatch_work(s, lanes=3, opp_lanes=3, rollout_len=2)
    # core: 2 x (2+2) x 8 = 64 operations a lane-step; lane-steps: rollout
    # (3+3) x 2 = 12, learner forward 3 x 3 = 9, backward 3 x 2 counted twice
    assert w["flops"] == 64 * (12 + 9 + 12)
    # kernels 8 x 4 x 2 B = 64; forward step 64 + 3x2x2x5 = 124, saving
    # gates 64 + 3x2x2x9 = 172, backward 64 + 3x2x2x10 = 184; rollout 2 steps
    # x two lane sets, learner 3 forward + 2 backward, weight gradient 128
    assert w["bytes"] == 2 * (124 + 124) + 3 * 172 + 2 * 184 + 128


def test_roofline_picks_the_larger_bound():
    peaks = flops.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    r = flops.roofline_seconds({"flops": 197e12, "bytes": 81.9e9}, peaks, "bfloat16")
    assert r["bound"] == "compute" and r["seconds"] == pytest.approx(1.0)
    r = flops.roofline_seconds({"flops": 19.7e12, "bytes": 819e9}, peaks, "bfloat16")
    assert r["bound"] == "memory" and r["seconds"] == pytest.approx(1.0)


def test_wide_core_is_compute_bound_and_small_core_is_not():
    peaks = flops.peaks_for("TPU v5 lite")
    wide = flops.roofline_seconds(
        flops.core_dispatch_work(shape_of("five5v5-lstm4096"), 1280, 1280, 16), peaks, "bfloat16")
    small = flops.roofline_seconds(
        flops.core_dispatch_work(shape_of("dota5v5-lstm128"), 20480, 20480, 16), peaks, "bfloat16")
    assert wide["bound"] == "compute" and small["bound"] == "memory"


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(flops.UnknownDevice):
        flops.peaks_for("TPU v9 imaginary")
    with pytest.raises(flops.UnknownDevice):
        flops.peaks_for("cpu")


def run_config_of(config_name, **model):
    with open(os.path.join(cells.BENCH_DIR, "configs", f"{config_name}.json")) as f:
        rc = json.load(f)["run_config"]
    rc["model"].update(model)
    return rc


@pytest.mark.parametrize("core", ["transformer", "switch", None])
def test_another_core_does_not_inherit_the_lstm_counts(core):
    with pytest.raises(flops.UnsupportedShape):
        flops.PolicyShape.from_run_config(run_config_of("dota5v5-lstm128", core=core))


def test_a_type_with_no_peak_is_an_error_not_the_bfloat16_peak():
    peaks = flops.peaks_for("TPU v5 lite")
    s = flops.PolicyShape.from_run_config(run_config_of("dota5v5-lstm128", dtype="float32"))
    assert s.compute_bytes == 4
    with pytest.raises(flops.UnsupportedShape):
        flops.peak_flops_per_s(peaks, s.dtype)
    with pytest.raises(flops.UnsupportedShape):
        flops.roofline_seconds({"flops": 1.0, "bytes": 1.0}, peaks, s.dtype)
    with pytest.raises(flops.UnsupportedShape):
        flops.PolicyShape.from_run_config(run_config_of("dota5v5-lstm128", dtype="float8"))
