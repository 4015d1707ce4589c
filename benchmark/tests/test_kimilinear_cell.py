"""The cell ``kimi-linear-5v5-ep32.fused-selfplay-anycore``: it loads, its
configuration holds the catalog's row, each of its nine readers reads a
hand-made trace or record (and nothing from a program without the scopes or
gauges), its comparison passes at toy widths and fails what it must (a lower
precision, no decay, no beta, a tap shifted, a state kept across a reset, an
unnormalised latent, a rotation), and ``--rehearse-cpu`` walks it. The
counts' hand checks are in ``tests/test_kimilinear.py``."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.harness import cells, compare_kimilinear, flops, flops_kimilinear, program, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.reference import kimilinear_ref

CELL = "kimi-linear-5v5-ep32.fused-selfplay-anycore"
NEW = (
    "kda_device_share", "kda_state_device_share", "attn_latent_device_share", "moe_device_share",
    "kda_state_roofline", "latent_attend_roofline", "kda_decay_mean", "held_expert_load_max_over_mean",
    "kimilinear_train_mfu",
)


def read(name, record):
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=name)).read(record)


def test_the_cell_loads_with_its_own_metrics_and_without_the_other_cores():
    cell = cells.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names
    assert not {"policy_core_roofline", "train_mfu", "afmoe_core_roofline", "looplm_core_roofline", "attn_window_device_share"} & names
    assert {"policy_core_share", "hbm_peak_gb", "compiles_in_window", "device_idle_share", "optimizer_device_share"} <= names
    assert cell.runner == "train_fused_anycore" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {"train_frames_per_s", "setup_s"}
    manifest = cells.load_manifest()
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m.name for m in cells.load_cell(other["name"]).per_layer}
    # one four-chip cell of six: a quarter rounded down
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 <= len(manifest["workloads"]) // 4
    for name in NEW:
        entry = [m for m in manifest["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_frames_per_s"
        if "roofline" in name or "mfu" in name:
            assert entry["unit"] == "%" and entry["better"] == "higher"


def test_the_configuration_holds_the_catalog_row_and_states_its_cut():
    cfg = cells.load_cell(CELL).config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key                       # every published key, nested groups whole
    model = cfg["run_config"]["model"]
    # every width as published; depth and the held share cut and listed
    assert (model["hidden_dim"], model["n_heads"], model["kda_head_dim"], model["kda_conv_kernel"]) == (2304, 32, 128, 4)
    assert (model["kv_lora_rank"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]) == (512, 128, 64, 128)
    assert (model["dense_ffn_dim"], model["expert_ffn_dim"], model["moe_experts"], model["experts_per_token"]) == (9216, 1024, 256, 8)
    assert (model["route_scale"], model["rms_norm_eps"], model["mup_enabled"], model["n_shared_experts"]) == (2.446, 1e-5, False, 1)
    assert (cfg["layers"], model["n_layers"], cfg["held_experts"], model["held_experts"]) == (5, 5, 8, 8)
    assert set(cfg["reduced"]) == {"layers", "held_experts", "batch_timesteps_per_chip"} == set(cfg["reduced_why"])
    assert cfg["batch_timesteps_per_chip"] == 5 * cfg["n_envs_per_chip"] * cfg["run_config"]["ppo"]["rollout_len"]
    assert cfg["run_config"]["ppo"]["moe_aux_coef"] == 0.0 and cfg["run_config"]["league"]["pool_size"] == 1
    assert all("recalled, not verifiable here" in v for k, v in cfg["assumed"].items() if k.startswith("recalled"))
    assert sum(k.startswith("recalled") for k in cfg["assumed"]) >= 6
    assert "32 chips" in cfg["deployment"] and "irregular" in cfg["deployment"] and len(cfg["departures"]) >= 3
    assert "12.0 GB" in cfg["assumed"]["n_envs_per_chip"] and "16 games" in cfg["assumed"]["n_envs_per_chip"]
    entry = [c for c in cells.load_manifest()["configs"] if c["name"] == "kimi-linear-5v5-ep32"][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    # the program builds it: KDA dense, three KDA with experts, one MLA with experts
    from dotaclient_tpu.models import kimilinear

    built = program.build_run_config(cells.load_cell(CELL), seed=0, rehearsal=False).model
    kimilinear.check_config(built)
    assert kimilinear.kda_layers(built) == [0, 1, 2, 3] and kimilinear.mla_layers(built) == [4]
    assert flops_kimilinear.layer_kinds(cfg["run_config"]["model"]) == [(False, True)] + [(False, False)] * 3 + [(True, False)]


class FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 14 * 10 ** 9, "bytes_reserved": 10 ** 9}


def plane():
    """One chip, one dispatch of 300 ns: a rollout step and an update."""
    R = "jit(live_opponent)/phase_rollout/while/body/closed_call/Policy.step/policy_core/core"
    U = "jit(live_opponent)/phase_update/update_loss"
    F, B = f"{U}/jvp(Policy.sequence)/policy_core/core", f"{U}/transpose(jvp(Policy.sequence))/policy_core/core"
    ops = [
        Op("fusion.1", f"{R}/layer_1/kda/core_kda/wq/dot_general", 0, 20),
        Op("fusion.2", f"{R}/layer_1/kda/core_kda/core_kda_state/checkpoint/add", 20, 60),
        Op("fusion.3", f"{R}/layer_4/attn/core_attn_latent/wq/dot_general", 60, 70),
        Op("fusion.4", f"{R}/layer_4/attn/core_attn_latent/core_latent_attend/checkpoint/bkgtr,brkd->btkgd/dot_general", 70, 90),
        Op("fusion.5", f"{R}/layer_4/attn/core_cache_write/scatter", 90, 94),
        Op("fusion.6", f"{R}/layer_2/moe/core_router/dot_general", 94, 100),
        Op("fusion.7", f"{R}/layer_2/moe/core_experts_routed/ragged_dot", 100, 110),
        Op("fusion.8", f"{R}/layer_2/moe/core_expert_shared/shared/dot_general", 110, 120),
        Op("fusion.9", f"{R}/layer_0/core_dense_ffn/ffn/dot_general", 120, 140),
        Op("fusion.10", "jit(live_opponent)/phase_rollout/while/body/closed_call/rollout_sim_step/select_n", 140, 150),
        Op("fusion.11", f"{F}/layer_1/kda/core_kda/core_kda_state/checkpoint/dot_general", 150, 160),
        Op("fusion.12", f"{B}/layer_1/kda/core_kda/core_kda_state/checkpoint/rematted_computation/mul", 160, 190),
        Op("fusion.13", f"{B}/layer_4/attn/core_attn_latent/core_latent_attend/checkpoint/rematted_computation/dot_general", 190, 210),
        Op("fusion.14", f"{B}/layer_3/moe/core_experts_routed/transpose/ragged_dot", 210, 220),
        Op("fusion.15", "jit(live_opponent)/phase_update/update_optimizer/mul", 220, 300),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_live_opponent(1)", 0, 300)])


def record_of(**over):
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False, "failures": [],
        "attempted": 12, "failed": 0, "run_config": rc, "lanes": 40, "opp_lanes": 40, "rollout_len": 16,
        "setup": {"compile_s": 60.0}, "core_position_at_end": 16.0 * 15,
        "window": {"programs_built": 0, "dispatches": 15, "traced_dispatches": 1, "traced_until_dispatch": 14,
                   "traced_seconds": 300e-9, "frames_per_dispatch": 640},
        "counters": {"before": {}, "after": {
            "kda/decay_mean": 0.875, "kda/void_reads_total": 64.0, "moe/max_over_mean_expert_load": 3.5,
            "moe/local_assignments": 40 * 17 * 1.0,
        }},
        "trace": trace.Trace([plane()], [Span("bench:traced_window", 0, 300)]), "trace_window": (0, 300),
    }
    record.update(over)
    return record


def test_every_new_reader_on_a_hand_made_plane():
    record = record_of()
    assert read("kda_device_share", record) == pytest.approx(100 * (20 + 40 + 10 + 30) / 300)
    assert read("kda_state_device_share", record) == pytest.approx(100 * (40 + 10 + 30) / 300)
    assert read("attn_latent_device_share", record) == pytest.approx(100 * (10 + 20 + 20) / 300)
    assert read("moe_device_share", record) == pytest.approx(100 * (6 + 10 + 10 + 10) / 300)
    assert read("kda_decay_mean", record) == 0.875 and read("held_expert_load_max_over_mean", record) == 3.5
    rc = record["run_config"]
    peaks = flops.peaks_for("TPU v5 lite")
    state = flops.roofline_seconds(flops_kimilinear.kda_state_work(rc, 40, 40, 16), peaks, "bfloat16")
    assert read("kda_state_roofline", record) == pytest.approx(100 * state["seconds"] / 80e-9)
    assert record["notes"]["kda_state_roofline"]["bound"] == "memory"
    # the traced dispatch is number 13 of 15: its middle step is at 240 - 1.5 x 16
    attend = flops.roofline_seconds(flops_kimilinear.latent_attend_work(rc, 40, 40, 16, 216.0), peaks, "bfloat16")
    assert read("latent_attend_roofline", record) == pytest.approx(100 * attend["seconds"] / 40e-9)
    assert record["notes"]["latent_attend_roofline"]["traced_s_per_dispatch"] == pytest.approx(40e-9)
    per_frame = flops_kimilinear.train_flops_per_frame(rc, 40, 40, 16, 216.0, 1.0)
    assert read("kimilinear_train_mfu", record) == pytest.approx(100 * per_frame * 640 / 300e-9 / 197e12)
    # no pair on a held expert in the logged update is a count of 0, not a missing reading
    none_held = record_of(counters={"before": {}, "after": {"moe/local_assignments": 0.0, "moe/max_over_mean_expert_load": 0.0}})
    assert read("held_expert_load_max_over_mean", none_held) == 0.0
    without = flops_kimilinear.train_flops_per_frame(rc, 40, 40, 16, 216.0, 0.0)
    assert read("kimilinear_train_mfu", none_held) == pytest.approx(100 * without * 640 / 300e-9 / 197e12)


def test_readers_find_nothing_where_nothing_was_counted_or_traced():
    """A program without the gauges or the scopes (the parent of PR 32), an
    untraced run: ``None`` or 0, never an exception."""
    bare = record_of(counters={"before": {}, "after": {}}, core_position_at_end=None)
    for name in ("kda_decay_mean", "held_expert_load_max_over_mean", "latent_attend_roofline", "kimilinear_train_mfu"):
        assert read(name, bare) is None
    untraced = record_of(trace=None, trace_window=None)
    for name in NEW[:6]:
        assert read(name, untraced) is None
    old = trace.load(os.path.join(cells.BENCH_DIR, "tests", "data", "tpu_v5e_1chip.xplane.pb"))
    w = old.span("bench:traced_window")
    unscoped = record_of(trace=old, trace_window=(w.start, w.end))
    for name in NEW[:4]:
        assert read(name, unscoped) == 0.0
    assert read("kda_state_roofline", unscoped) is None and read("latent_attend_roofline", unscoped) is None


# -- the comparison, at toy widths ------------------------------------------------


def toy(dtype="bfloat16", **over):
    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params
    from dotaclient_tpu.models.policy import Policy

    cfg = default_config()
    sizes = dict(
        core="kimilinear", hidden_dim=32, n_layers=5, n_heads=2, kda_head_dim=8, kda_conv_kernel=4,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, full_context=32,
        rollout_chunk=4, global_attn_every=4, global_attn_offset=3, n_dense_layers=1, dense_ffn_dim=48,
        expert_ffn_dim=16, moe_experts=16, experts_per_token=2, held_experts=2, expert_offset=0,
        route_scale=2.446, mup_enabled=False, dtype=dtype,
    )
    model = dataclasses.replace(cfg.model, **{**sizes, **over})
    policy = Policy(model, cfg.obs, cfg.actions)
    rc = {"model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs), "actions": dataclasses.asdict(cfg.actions)}
    return policy, init_params(policy, jax.random.PRNGKey(7)), rc


def test_comparison_passes_at_toy_widths_with_a_seed_past_32_bits():
    """Stated in float32 here: at 2 heads of 8 on a stream of 32 a bfloat16
    rounding is several percent of an output after five layers without a
    post-norm (rounding errors average out over a width, and these have
    none), so the stated limit, which is set at the cell's widths, is the
    rehearsal's to meet (hidden 256 beside the published heads: below) and
    the chip's."""
    policy, params, rc = toy(dtype="float32")
    report = compare_kimilinear.policy_agreement(policy, params, rc, seed=2 ** 31 + 5, lanes=3, steps=4, history_steps=24)
    assert report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) < 1e-5 and report["exact_routing_margin"] < 1e-5
    assert report["stated_sequence"] < compare_kimilinear.TOL_EXACT and report["stated_step"] < compare_kimilinear.TOL_EXACT
    assert report["episode_ends"] >= 1 and report["tol_stated"] == compare_kimilinear.TOL_EXACT


def test_a_lower_precision_than_stated_fails():
    policy, params, rc = toy()
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare_kimilinear.policy_agreement(policy, params, rc, seed=3, lanes=3, steps=4, history_steps=24)
    assert report["tol_stated"] == compare_kimilinear.TOL_EXACT
    assert report["stated_sequence"] > compare_kimilinear.TOL_EXACT and not report["ok"]


@pytest.mark.parametrize("fault", kimilinear_ref.FAULTS)
def test_a_core_that_differs_in_one_way_fails_the_comparison(monkeypatch, fault):
    """The program against a reference without the decay or beta, with a tap
    shifted, a state kept across an episode's end, the latent unnormalised or
    a rotation applied: each is far outside the limits."""
    policy, params, rc = toy(dtype="float32")
    history = kimilinear_ref.history
    monkeypatch.setattr(kimilinear_ref, "history", lambda *a, **kw: history(*a, **kw, fault=fault))
    report = compare_kimilinear.policy_agreement(policy, params, rc, seed=5, lanes=3, steps=4, history_steps=24)
    assert not report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) > 100 * compare_kimilinear.TOL_EXACT


def test_rehearsal_walks_the_cell():
    """``--rehearse-cpu`` at the harness's tiny rule (one game, hidden 256)
    beside the published heads, states, latent, FFN widths and router:
    control flow only, minutes on the CPU."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--rehearse-cpu", "--trace", "1", "--seconds", "1"],
        cwd=cells.ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    would = [l for l in out.stdout.splitlines() if "would print" in l][-1]
    line = json.loads(would.split("would print ", 1)[1])
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert "REHEARSAL" in would and not any(l.startswith("{") for l in out.stdout.splitlines())
