"""The cell ``lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore``: it loads, its
configuration holds the catalog's row, each of its seven metrics' readers reads a
hand-made trace or record (and nothing from a program without the scopes or
gauges: the parent of PR 37), its comparison passes at toy widths and fails
what it must (a lower precision, the taps reversed or shifted, a history
kept across a reset, a gate, the rotation or the head norm left out), and
``--rehearse-cpu`` walks it with and without ``--trace 1``. The counts' hand
checks are in ``tests/test_lfm2moe.py``."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.harness import cells, compare_lfm2moe, flops_lfm2moe, program, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.reference import lfm2moe_ref

CELL = "lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore"
NEW = (
    "shortconv_device_share", "shortconv_state_device_share", "gqa_ring_device_share", "lfm2_moe_device_share",
    "lfm2_dense_ffn_device_share", "lfm2_held_expert_load_max_over_mean", "lfm2moe_train_mfu",
)


def read(name, record):
    """Through the reader the metric's own file names (the held experts' load is read by the
    Kimi-Linear cell's reader: the gauge is the same at 64 outputs as at 256)."""
    with open(os.path.join(cells.BENCH_DIR, "metrics", f"{name}.json")) as f:
        reader = json.load(f)["reader"]
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=reader)).read(record)


def test_the_cell_loads_with_its_own_metrics_and_without_the_other_cores():
    cell = cells.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names
    assert not {"policy_core_roofline", "train_mfu", "afmoe_core_roofline", "kda_state_roofline", "moe_device_share", "attn_full_device_share"} & names
    assert {"policy_core_share", "hbm_peak_gb", "compiles_in_window", "device_idle_share", "optimizer_device_share"} <= names
    assert cell.runner == "train_fused_anycore" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {"train_frames_per_s", "setup_s"}
    manifest = cells.load_manifest()
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m.name for m in cells.load_cell(other["name"]).per_layer}
    # one four-chip cell of seven: a quarter rounded down
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 <= len(manifest["workloads"]) // 4
    for name in NEW:
        entry = [m for m in manifest["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_frames_per_s"
        if "roofline" in name or "mfu" in name:
            assert entry["unit"] == "%" and entry["better"] == "higher" and entry["layer"] == "kernels.whole_step"
    # new entries at the end of their lists
    assert manifest["configs"][-1]["name"] == "lfm2-24b-a2b-5v5-ep8" and manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-7:]] == list(NEW)


def test_the_configuration_holds_the_catalog_row_and_states_its_cut():
    cfg = cells.load_cell(CELL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "LFM2-24B-A2B"][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key                       # every published key, nested groups whole
    model = cfg["run_config"]["model"]
    # every width as published; depth and the held share cut and listed
    assert (model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"], model["shortconv_taps"]) == (2048, 32, 8, 64, 3)
    assert (model["dense_ffn_dim"], model["expert_ffn_dim"], model["moe_experts"], model["experts_per_token"]) == (11776, 1536, 64, 4)
    assert (model["route_scale"], model["route_norm"], model["rms_norm_eps"], model["rope_theta"]) == (1.0, True, 1e-5, 1e6)
    assert (model["mup_enabled"], model["n_shared_experts"], model["attn_qk_norm"], model["attn_out_gate"], model["rope_full_layers"]) == (False, 0, True, False, True)
    assert (cfg["layers"], model["n_layers"], cfg["held_experts"], model["held_experts"]) == (5, 5, 8, 8)
    assert set(cfg["reduced"]) == {"layers", "held_experts", "batch_timesteps_per_chip"} == set(cfg["reduced_why"])
    assert cfg["batch_timesteps_per_chip"] == 5 * cfg["n_envs_per_chip"] * cfg["run_config"]["ppo"]["rollout_len"]
    assert cfg["run_config"]["ppo"]["moe_aux_coef"] == 0.0 and cfg["run_config"]["league"]["pool_size"] == 1
    assert all("recalled, not verifiable here" in v for k, v in cfg["assumed"].items() if k.startswith("recalled"))
    assert sum(k.startswith("recalled") for k in cfg["assumed"]) >= 5
    assert "8 chips" in cfg["deployment"] and "no shared expert" in cfg["deployment"] and len(cfg["departures"]) >= 4
    assert "12.0 GB" in cfg["assumed"]["n_envs_per_chip"] and "32 games" in cfg["assumed"]["n_envs_per_chip"]
    entry = [c for c in cells.load_manifest()["configs"] if c["name"] == "lfm2-24b-a2b-5v5-ep8"][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    # the kept layers are the published pattern's: conv (dense), full_attention, conv, conv, conv
    kept = [cfg["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert kept == ["conv", "full_attention", "conv", "conv", "conv"]
    from dotaclient_tpu.models import lfm2moe

    built = program.build_run_config(cells.load_cell(CELL), seed=0, rehearsal=False).model
    lfm2moe.check_config(built)
    assert lfm2moe.conv_layers(built) == [0, 2, 3, 4] and lfm2moe.attn_layers(built) == [1]
    assert flops_lfm2moe.layer_kinds(cfg["run_config"]["model"]) == [(False, True), (True, False)] + [(False, False)] * 3


class FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 12 * 10 ** 9, "bytes_reserved": 10 ** 9}


def plane():
    """One chip, one dispatch of 300 ns: a rollout step and an update."""
    R = "jit(live_opponent)/phase_rollout/while/body/closed_call/Policy.step/policy_core/core"
    U = "jit(live_opponent)/phase_update/update_loss"
    F, B = f"{U}/jvp(Policy.sequence)/policy_core/core", f"{U}/transpose(jvp(Policy.sequence))/policy_core/core"
    ops = [
        Op("fusion.1", f"{R}/layer_2/conv/core_conv/in_proj/dot_general", 0, 20),
        Op("fusion.2", f"{R}/layer_2/conv/core_conv/core_conv_state/mul", 20, 26),
        Op("fusion.3", f"{R}/layer_2/conv/core_conv/out_proj/dot_general", 26, 40),
        Op("fusion.4", f"{R}/layer_1/attn/core_attn_full/wq/dot_general", 40, 50),
        Op("fusion.5", f"{R}/layer_1/attn/core_attn_full/checkpoint/brc,btcn->bntr/dot_general", 50, 90),
        Op("fusion.6", f"{R}/layer_1/attn/core_cache_write/scatter", 90, 94),
        Op("fusion.7", f"{R}/layer_2/moe/core_router/dot_general", 94, 100),
        Op("fusion.8", f"{R}/layer_2/moe/core_experts_routed/ragged_dot", 100, 120),
        Op("fusion.9", f"{R}/layer_0/core_dense_ffn/ffn/dot_general", 120, 140),
        Op("fusion.10", "jit(live_opponent)/phase_rollout/while/body/closed_call/rollout_sim_step/select_n", 140, 150),
        Op("fusion.11", f"{F}/layer_3/conv/core_conv/core_conv_state/add", 150, 154),
        Op("fusion.12", f"{B}/layer_3/conv/core_conv/in_proj/dot_general", 154, 190),
        Op("fusion.13", f"{B}/layer_1/attn/core_attn_full/checkpoint/rematted_computation/dot_general", 190, 210),
        Op("fusion.14", f"{B}/layer_3/moe/core_experts_routed/transpose/ragged_dot", 210, 220),
        Op("fusion.15", "jit(live_opponent)/phase_update/update_optimizer/mul", 220, 300),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_live_opponent(1)", 0, 300)])


def record_of(**over):
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False, "failures": [],
        "attempted": 12, "failed": 0, "run_config": rc, "lanes": 80, "opp_lanes": 80, "rollout_len": 16,
        "setup": {"compile_s": 60.0}, "core_position_at_end": 16.0 * 15,
        "window": {"programs_built": 0, "dispatches": 15, "traced_dispatches": 1, "traced_until_dispatch": 14,
                   "traced_seconds": 300e-9, "frames_per_dispatch": 1280},
        "counters": {"before": {}, "after": {
            "shortconv/gate_mean": 0.5, "shortconv/void_reads_total": 64.0, "moe/max_over_mean_expert_load": 2.5,
            "moe/local_assignments": 80 * 17 * 1.0,
        }},
        "trace": trace.Trace([plane()], [Span("bench:traced_window", 0, 300)]), "trace_window": (0, 300),
    }
    record.update(over)
    return record


def test_every_new_reader_on_a_hand_made_plane():
    record = record_of()
    assert read("shortconv_device_share", record) == pytest.approx(100 * (20 + 6 + 14 + 4 + 36) / 300)
    assert read("shortconv_state_device_share", record) == pytest.approx(100 * (6 + 4) / 300)
    assert read("gqa_ring_device_share", record) == pytest.approx(100 * (10 + 40 + 4 + 20) / 300)
    assert read("lfm2_moe_device_share", record) == pytest.approx(100 * (6 + 20 + 10) / 300)
    assert read("lfm2_dense_ffn_device_share", record) == pytest.approx(100 * 20 / 300)
    assert read("lfm2_held_expert_load_max_over_mean", record) == 2.5
    # the traced dispatch is number 13 of 15: its middle step is at 240 - 1.5 x 16
    per_frame = flops_lfm2moe.train_flops_per_frame(record["run_config"], 80, 80, 16, 216.0, 1.0)
    assert read("lfm2moe_train_mfu", record) == pytest.approx(100 * per_frame * 1280 / 300e-9 / 197e12)
    # no pair on a held expert in the logged update is a count of 0, not a missing reading
    none_held = record_of(counters={"before": {}, "after": {"moe/local_assignments": 0.0, "moe/max_over_mean_expert_load": 0.0}})
    assert read("lfm2_held_expert_load_max_over_mean", none_held) == 0.0
    without = flops_lfm2moe.train_flops_per_frame(record["run_config"], 80, 80, 16, 216.0, 0.0)
    assert read("lfm2moe_train_mfu", none_held) == pytest.approx(100 * without * 1280 / 300e-9 / 197e12)


def test_readers_find_nothing_where_nothing_was_counted_or_traced():
    """A program without the gauges or the scopes (the parent of PR 37), an
    untraced run: ``None`` or 0, never an exception."""
    bare = record_of(counters={"before": {}, "after": {}}, core_position_at_end=None)
    for name in ("lfm2_held_expert_load_max_over_mean", "lfm2moe_train_mfu"):
        assert read(name, bare) is None
    untraced = record_of(trace=None, trace_window=None)
    for name in NEW[:5]:
        assert read(name, untraced) is None
    old = trace.load(os.path.join(cells.BENCH_DIR, "tests", "data", "tpu_v5e_1chip.xplane.pb"))
    w = old.span("bench:traced_window")
    unscoped = record_of(trace=old, trace_window=(w.start, w.end))
    for name in NEW[:5]:
        assert read(name, unscoped) == 0.0


# -- the comparison, at toy widths ------------------------------------------------


def toy(dtype="bfloat16", **over):
    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params
    from dotaclient_tpu.models.policy import Policy

    cfg = default_config()
    sizes = dict(
        core="lfm2moe", hidden_dim=32, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=8, shortconv_taps=3,
        full_context=32, rollout_chunk=4, global_attn_every=4, global_attn_offset=2, n_dense_layers=1,
        dense_ffn_dim=48, expert_ffn_dim=16, moe_experts=16, experts_per_token=2, held_experts=2, expert_offset=0,
        n_shared_experts=0, route_scale=1.0, rope_theta=1e6, mup_enabled=False, attn_qk_norm=True,
        attn_out_gate=False, rope_full_layers=True, dtype=dtype,
    )
    model = dataclasses.replace(cfg.model, **{**sizes, **over})
    policy = Policy(model, cfg.obs, cfg.actions)
    rc = {"model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs), "actions": dataclasses.asdict(cfg.actions)}
    return policy, jax.jit(lambda key: init_params(policy, key))(jax.random.PRNGKey(7)), rc


def test_comparison_passes_at_toy_widths_with_a_seed_past_32_bits():
    """Stated in float32 here (at a stream of 32 a bfloat16 rounding is
    percent-level after five layers without a post-norm: the stated limit is
    set at the cell's widths and is the rehearsal's and the chip's to meet)."""
    policy, params, rc = toy(dtype="float32")
    report = compare_lfm2moe.policy_agreement(policy, params, rc, seed=2 ** 31 + 5, lanes=3, steps=4, history_steps=24)
    assert report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) < 1e-5 and report["exact_routing_margin"] < 1e-5
    assert report["stated_sequence"] < compare_lfm2moe.TOL_EXACT and report["stated_step"] < compare_lfm2moe.TOL_EXACT
    assert report["episode_ends"] >= 1 and report["tol_stated"] == compare_lfm2moe.TOL_EXACT


def test_a_lower_precision_than_stated_fails():
    policy, params, rc = toy()
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare_lfm2moe.policy_agreement(policy, params, rc, seed=3, lanes=3, steps=4, history_steps=24)
    assert report["tol_stated"] == compare_lfm2moe.TOL_EXACT
    assert report["stated_sequence"] > compare_lfm2moe.TOL_EXACT and not report["ok"]


@pytest.mark.parametrize("fault", lfm2moe_ref.FAULTS)
def test_a_core_that_differs_in_one_way_fails_the_comparison(monkeypatch, fault):
    policy, params, rc = toy(dtype="float32")
    history = lfm2moe_ref.history
    monkeypatch.setattr(lfm2moe_ref, "history", lambda *a, **kw: history(*a, **kw, fault=fault))
    report = compare_lfm2moe.policy_agreement(policy, params, rc, seed=5, lanes=3, steps=4, history_steps=24)
    assert not report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) > 100 * compare_lfm2moe.TOL_EXACT


def test_the_precision_tool_walks_its_four_lowerings_on_the_cpu():
    out = subprocess.run(
        [sys.executable, "benchmark/tools/lfm2moe_precision_below.py", "--rehearse-cpu", "--lanes", "1"],
        cwd=cells.ROOT, capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    assert [l["lowered"] for l in lines] == ["products bfloat16", "products float8_e4m3fn", "parameters bfloat16", "router bfloat16"]
    assert [l["held_to"] for l in lines] == ["stated", "stated", "exact", "exact"]


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_walks_the_cell(traced):
    """``--rehearse-cpu`` at the harness's tiny rule (one game, hidden 256)
    beside the published heads, ring, FFN widths and router: control flow
    only. With ``--trace 1`` every reader of the cell runs on the rehearsal's
    record and none raises (a CPU trace has no device plane and four
    dispatches pass no log boundary, so what they return there is nothing:
    the hand-made plane above is where each returns its number)."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--rehearse-cpu", "--trace", str(traced), "--seconds", "1"],
        cwd=cells.ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    would = [l for l in out.stdout.splitlines() if "would print" in l][-1]
    line = json.loads(would.split("would print ", 1)[1])
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert "REHEARSAL" in would and not any(l.startswith("{") for l in out.stdout.splitlines())
    if traced:
        assert "compiles_in_window" in line["metrics"] and not set(line["metrics"]) & {"train_frames_per_s"}
