"""The cell ``sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore``: it loads, its
configuration holds the catalog's row, ``harness/flops_sdar.py``'s counts
check by hand (rows a pass, passes a step, the block mask's visible rows,
``pos`` / 6), each of its seven metrics' readers reads a hand-made trace or
record (and nothing from a program without the scopes or counters: the
programs that predate this core), its comparison passes at toy widths and fails what it
must, and ``--rehearse-cpu`` walks it. That the program's
``diffusion/passes_total`` moves by S + 1 a rollout step through a toy fused
run is ``tests/test_fused_sdar.py``'s (tier-1)."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.harness import cells, compare_sdar, flops_sdar, program, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.reference import sdar_ref

CELL = "sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore"
NEW = (
    "sdar_denoise_device_share", "sdar_commit_device_share", "sdar_block_attend_roofline", "sdar_moe_device_share",
    "sdar_held_expert_load_max_over_mean", "diffusion_passes_per_step", "sdar_train_mfu",
)


def read(name, record):
    """Through the reader the metric's own file names (the routed layer's share and the held
    experts' load are read by the LFM2 and Kimi-Linear cells' readers: the same scopes and gauge)."""
    with open(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json")) as f:
        reader = json.load(f)["reader"]
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=reader)).read(record)


def test_the_cell_loads_with_its_own_metrics():
    cell = cells.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names
    assert not {"policy_core_roofline", "train_mfu", "afmoe_core_roofline", "kda_state_roofline", "lfm2moe_train_mfu"} & names
    assert cell.runner == "train_fused_anycore" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {"train_frames_per_s", "setup_s"}
    manifest = cells.load_manifest()
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m.name for m in cells.load_cell(other["name"]).per_layer}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 <= len(manifest["workloads"]) // 4
    for name in NEW:
        entry = [m for m in manifest["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_frames_per_s"
    assert manifest["configs"][-1]["name"] == "sdar-30b-a3b-5v5-ep16" and manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-7:]] == list(NEW)


def test_the_configuration_holds_the_catalog_row_and_states_its_cut():
    cfg = cells.load_cell(CELL).config
    # the model catalog's row for the published config, copied whole
    with open(os.path.join(os.path.dirname(__file__), "data", "SDAR-30B-A3B-Chat.catalog.json")) as f:
        row = json.load(f)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    model = cfg["run_config"]["model"]
    assert (model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (2048, 32, 4, 128)
    assert (model["expert_ffn_dim"], model["moe_experts"], model["experts_per_token"], model["n_shared_experts"]) == (768, 128, 8, 0)
    assert (model["route_score"], model["route_norm"], model["rms_norm_eps"], model["rope_theta"]) == ("softmax", True, 1e-6, 1e6)
    assert (model["diffusion_steps"], model["full_context"], model["n_dense_layers"]) == (3, 18432, 0)
    assert (cfg["layers"], model["n_layers"], cfg["held_experts"], model["held_experts"]) == (4, 4, 8, 8)
    assert set(cfg["reduced"]) == {"layers", "held_experts", "batch_timesteps_per_chip"} == set(cfg["reduced_why"])
    assert cfg["batch_timesteps_per_chip"] == 5 * cfg["n_envs_per_chip"] * cfg["run_config"]["ppo"]["rollout_len"]
    assert cfg["run_config"]["ppo"]["moe_aux_coef"] == 0.001 and cfg["run_config"]["ppo"]["select_bias_rate"] == 0.0
    assert all("recalled, not verifiable here" in v for k, v in cfg["assumed"].items() if k.startswith("recalled"))
    assert "16 chips" in cfg["deployment"] and "12.0 GB" in cfg["assumed"]["n_envs_per_chip"]
    from dotaclient_tpu.models import sdar

    built = program.build_run_config(cells.load_cell(CELL), seed=0, rehearsal=False).model
    sdar.check_config(built)
    sdar.require_episode_fits(built, 3001, 16)


# -- the counts, by hand ----------------------------------------------------------


def test_rows_passes_and_visible_rows_by_hand():
    from dotaclient_tpu.models import sdar

    model = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)["model"]
    assert flops_sdar.passes_per_step(model) == 4
    assert flops_sdar.rollout_rows(model) == 6 + 5 + 5 + 5 == 21
    assert flops_sdar.learner_rows(model, 16) == 6 * 17 + 15 * 16 == 342
    own = flops_sdar.own_visible(model, 16)
    assert own["rollout"] == (1 + 5 * 6) + 3 * 25
    # the learner's: the program's own block mask, summed
    assert own["learner"] == sdar.learner_rows(16, 3)[3].sum()
    assert flops_sdar.own_visible(model, 1)["learner"] == sdar.learner_rows(1, 3)[3].sum()
    # a step at step 100 of its episode sees 600 ring positions a row
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    a = flops_sdar.step_flops(rc, 100.0, 0.0)["attn"]
    b = flops_sdar.step_flops(rc, 101.0, 0.0)["attn"]
    assert b - a == pytest.approx(2 * 21 * 6 * 4 * 32 * 2 * 128)
    w = flops_sdar.core_weight_count(model)
    assert w["attn"] == 4 * (2 * 2048 * 4096 + 2 * 2048 * 512) and w["routed"] == 4 * 3 * 2048 * 768 * 8
    assert flops_sdar.carry_bytes_per_lane(model) == 8 + 4 * 18432 * 2 * 4 * 128 * 2
    # weights once a pass, once for both teams where the dispatch's teams share it, three times in the update
    per_pass = (w["attn"] + w["router"] + w["routed"]) * 2
    assert flops_sdar.weight_bytes_per_dispatch(rc, 16, 1.0) == pytest.approx(per_pass * (16 * 4 + 3))
    assert flops_sdar.weight_bytes_per_dispatch(rc, 16, 0.0) == pytest.approx(per_pass * (16 * 8 + 3))
    work = flops_sdar.block_attend_work(rc, 15, 15, 16, 100.0)
    assert work["flops"] == pytest.approx(work["rollout_flops"] + work["learner_flops"])
    ring_row = 4 * 2 * 4 * 128 * 2
    assert work["bytes"] > 4 * 601 * ring_row * 30 * 16 + 2 * 600 * ring_row * 15


# -- the readers, on a hand-made record ----------------------------------------------


class FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 12 * 10 ** 9, "bytes_reserved": 10 ** 9}


def plane():
    """One chip, one dispatch of 300 ns."""
    R = "jit(live_opponent)/phase_rollout/while/body/closed_call/policy_core"
    U = "jit(live_opponent)/phase_update/update_loss"
    F, B = f"{U}/jvp(policy_core)/core.run", f"{U}/transpose(jvp(policy_core))/core.run"
    ops = [
        Op("fusion.1", f"{R}/core_denoise/core.run/layer_0/attn/core_attn_full/wq/dot_general", 0, 20),
        Op("fusion.2", f"{R}/core_denoise/core.run/layer_0/attn/core_attn_full/core_block_attend/dot_general", 20, 60),
        Op("fusion.3", f"{R}/core_denoise/core.run/layer_0/moe/core_router/dot_general", 60, 66),
        Op("fusion.4", f"{R}/core_denoise/core.run/layer_0/moe/core_experts_routed/ragged_dot", 66, 80),
        Op("fusion.5", f"{R}/core_commit/core.run/layer_0/attn/core_attn_full/core_block_attend/dot_general", 80, 95),
        Op("fusion.6", f"{R}/core_commit/core.run/layer_0/attn/core_cache_write/scatter", 95, 100),
        Op("fusion.7", "jit(live_opponent)/phase_rollout/while/body/closed_call/rollout_sample/rollout_stage_sample/x", 100, 110),
        Op("fusion.8", f"{F}/layer_1/attn/core_attn_full/core_block_attend/while/body/dot_general", 110, 170),
        Op("fusion.9", f"{B}/layer_1/attn/core_attn_full/core_block_attend/while/body/dot_general", 170, 230),
        Op("fusion.10", f"{B}/layer_1/moe/core_experts_routed/ragged_dot", 230, 240),
        Op("fusion.11", "jit(live_opponent)/phase_update/update_optimizer/mul", 240, 300),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_live_opponent(1)", 0, 300)])


def record_of(**over):
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False, "failures": [],
        "attempted": 12, "failed": 0, "run_config": rc, "lanes": 15, "opp_lanes": 15, "rollout_len": 16,
        "setup": {"compile_s": 60.0}, "core_position_at_end": 6.0 * 16 * 15,
        "window": {"programs_built": 0, "dispatches": 15, "traced_dispatches": 1, "traced_until_dispatch": 14,
                   "traced_seconds": 300e-9, "frames_per_dispatch": 240},
        "counters": {
            "before": {"diffusion/passes_total": 640.0, "learner/dispatches_total": 10.0},
            "after": {"diffusion/passes_total": 640.0 + 15 * 16 * 4, "learner/dispatches_total": 25.0,
                      "moe/max_over_mean_expert_load": 2.5, "moe/local_assignments": 15 * 342 * 2.0},
        },
        "trace": trace.Trace([plane()], [Span("bench:traced_window", 0, 300)]), "trace_window": (0, 300),
    }
    record.update(over)
    return record


def test_every_new_reader_on_a_hand_made_plane():
    record = record_of()
    assert read("sdar_denoise_device_share", record) == pytest.approx(100 * 80 / 300)
    assert read("sdar_commit_device_share", record) == pytest.approx(100 * 20 / 300)
    assert read("sdar_moe_device_share", record) == pytest.approx(100 * (6 + 14 + 10) / 300)
    assert read("sdar_held_expert_load_max_over_mean", record) == 2.5
    assert read("diffusion_passes_per_step", record) == 4.0
    # the traced dispatch is number 14 of 15: its middle step is at 240 - 1.5 x 16
    work = flops_sdar.block_attend_work(record["run_config"], 15, 15, 16, 216.0)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert read("sdar_block_attend_roofline", record) == pytest.approx(100 * least / ((40 + 15 + 60 + 60) * 1e-9), rel=1e-6)
    per_frame = flops_sdar.train_flops_per_frame(record["run_config"], 15, 15, 16, 216.0, 2.0)
    assert read("sdar_train_mfu", record) == pytest.approx(100 * per_frame * 240 / 300e-9 / 197e12)


def test_readers_find_nothing_where_nothing_was_counted_or_traced():
    """A program without the counters or the scopes (a program that predates this core), an
    untraced run: ``None`` or 0, never an exception."""
    bare = record_of(counters={"before": {}, "after": {}}, core_position_at_end=None)
    for name in ("sdar_held_expert_load_max_over_mean", "diffusion_passes_per_step", "sdar_train_mfu", "sdar_block_attend_roofline"):
        assert read(name, bare) is None
    assert read("diffusion_passes_per_step", record_of(counters={})) is None
    untraced = record_of(trace=None, trace_window=None)
    for name in ("sdar_denoise_device_share", "sdar_commit_device_share", "sdar_block_attend_roofline", "sdar_moe_device_share"):
        assert read(name, untraced) is None
    old = trace.load(os.path.join(cells.BENCH_DIR, "tests", "data", "tpu_v5e_1chip.xplane.pb"))
    w = old.span("bench:traced_window")
    unscoped = record_of(trace=old, trace_window=(w.start, w.end))
    for name in ("sdar_denoise_device_share", "sdar_commit_device_share", "sdar_moe_device_share"):
        assert read(name, unscoped) == 0.0
    assert read("sdar_block_attend_roofline", unscoped) is None


# -- the comparison, at toy widths ------------------------------------------------


def toy(dtype="float32"):
    from tests.test_sdar import tiny_model
    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params
    from dotaclient_tpu.models.policy import Policy

    cfg = default_config()
    model = tiny_model(dtype=dtype, full_context=6 * 40)
    policy = Policy(model, cfg.obs, cfg.actions)
    rc = {"model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs), "actions": dataclasses.asdict(cfg.actions)}
    return policy, jax.jit(lambda key: init_params(policy, key))(jax.random.PRNGKey(7)), rc


def test_comparison_passes_at_toy_widths_with_a_seed_past_32_bits():
    policy, params, rc = toy()
    report = compare_sdar.policy_agreement(policy, params, rc, seed=2 ** 31 + 5, lanes=3, steps=4, history_steps=24)
    assert report["ok"], report
    for key in ("exact_rollout", "exact_learner", "stated_rollout", "stated_learner"):
        assert report[key] < 1e-5, key
    assert report["episode_ends"] >= 1 and report["committed_tokens"] > 0 and report["none_slots"] > 0


def test_the_float32_program_is_held_on_the_first_lanes_over_the_history_s_last_steps(monkeypatch):
    """The float32 side reads the first ``EXACT_LANES`` lanes over the last
    ``EXACT_STEPS`` steps as a history of their own (the stated side reads
    every lane over the whole history): it holds the float32 limits there."""
    monkeypatch.setattr(compare_sdar, "EXACT_LANES", 2)
    monkeypatch.setattr(compare_sdar, "EXACT_STEPS", 12)
    policy, params, rc = toy()
    report = compare_sdar.policy_agreement(policy, params, rc, seed=11, lanes=4, steps=4, history_steps=24)
    assert report["ok"], report
    assert (report["exact_lanes"], report["exact_history_steps"]) == (2, 12)
    assert report["exact_rollout"] < 1e-5 and report["exact_learner"] < 1e-5
    assert set(report["seconds"]) >= {"stated_decode", "stated_reference", "exact_decode", "exact_reference", "total"}


def test_a_lower_precision_than_stated_fails():
    policy, params, rc = toy(dtype="bfloat16")
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare_sdar.policy_agreement(policy, params, rc, seed=3, lanes=3, steps=4, history_steps=24)
    assert report["stated_rollout"] > compare_sdar.TOL_EXACT and not report["ok"]


@pytest.mark.parametrize("fault", sdar_ref.FAULTS)
def test_a_core_that_differs_in_one_way_fails_the_comparison(monkeypatch, fault):
    policy, params, rc = toy()
    forward = sdar_ref.forward
    monkeypatch.setattr(sdar_ref, "forward", lambda *a, **kw: forward(*a, **kw, fault=fault))
    report = compare_sdar.policy_agreement(policy, params, rc, seed=5, lanes=3, steps=4, history_steps=24)
    assert not report["ok"], report


def test_the_precision_tool_walks_its_four_lowerings_on_the_cpu():
    out = subprocess.run(
        [sys.executable, "benchmark/tools/sdar_precision_below.py", "--rehearse-cpu", "--lanes", "1"],
        cwd=cells.ROOT, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    assert [l["lowered"] for l in lines] == ["products bfloat16", "products float8_e4m3fn", "parameters bfloat16", "router bfloat16"]


def test_the_drawn_orders_follow_the_program_s_schedule():
    from benchmark.tools.sdar_precision_below import drawn_actions

    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    acts, stage = drawn_actions(rc, np.random.default_rng(0), 2, 50)
    rel = np.asarray(sdar_ref.relevant(acts["action_type"]))
    np.testing.assert_array_equal(stage > 0, rel)
    for n, want in ((0, []), (1, [2]), (2, [2, 3])):
        for row in stage[rel[..., 1:].sum(-1) == n]:
            assert sorted(row[1:][row[1:] > 0]) == want
    assert set(np.unique(stage[..., 1:])) <= {0, 2, 3}


@pytest.mark.parametrize("traced", [1])
def test_rehearsal_walks_the_cell(traced):
    """``--rehearse-cpu`` at the harness's tiny rule (one game, hidden 256)
    beside the published heads, rings and router: control flow only."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--rehearse-cpu", "--trace", str(traced), "--seconds", "1"],
        cwd=cells.ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    would = [l for l in out.stdout.splitlines() if "would print" in l][-1]
    line = json.loads(would.split("would print ", 1)[1])
    assert line["correct"] and line["device"]["platform"] == "cpu"
