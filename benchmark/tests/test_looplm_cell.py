"""The cell ``ouro-2.6b-5v5-ut4.fused-selfplay-anycore``: it loads, its
configuration holds the catalog's row, its counts agree with hand counts,
each of its readers reads a hand-made trace or record, its comparison passes
at toy widths and fails what it must (a lower precision, a loop step
skipped, one cache for all loop steps, weights that are not tied), and
``--rehearse-cpu`` walks it."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmark.harness import cells, compare_looplm, flops, flops_looplm, program, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.reference import looplm_ref

CELL = "ouro-2.6b-5v5-ut4.fused-selfplay-anycore"
NEW = (
    "loop_attn_device_share", "loop_ffn_device_share", "loop_cache_write_device_share",
    "exit_mix_device_share", "exit_expected_step", "looplm_core_roofline", "looplm_train_mfu",
)


def read(name, record):
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=name)).read(record)


def test_the_cell_loads_with_its_own_metrics_and_without_the_other_cores():
    cell = cells.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names
    assert not {"policy_core_roofline", "train_mfu", "afmoe_core_roofline", "attn_window_device_share"} & names
    assert {"policy_core_share", "hbm_peak_gb", "compiles_in_window", "device_idle_share"} <= names
    assert cell.runner == "train_fused_anycore" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {"train_frames_per_s", "setup_s"}
    p = cell.traffic["params"]
    assert (p["warmup_dispatches"], p["trace_dispatches"], p["max_dispatches_in_flight"]) == (3, 10, 2)
    assert (p["sample_lanes"], p["sample_steps"], p["sample_history_steps"]) == (16, 16, 2560)
    manifest = cells.load_manifest()
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m.name for m in cells.load_cell(other["name"]).per_layer}
    # one four-chip cell of five: a quarter rounded down
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 <= len(manifest["workloads"]) // 4


def test_the_configuration_holds_the_catalog_row_and_states_its_cut():
    cfg = cells.load_cell(CELL).config
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == ["full_attention"] * 48
    model = cfg["run_config"]["model"]
    # every width as published; depth cut and listed; all four loop steps kept
    assert (model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (2048, 16, 16, 128)
    assert (model["dense_ffn_dim"], model["rope_theta"], model["rms_norm_eps"]) == (5632, 1e6, 1e-6)
    assert (cfg["layers"], model["n_layers"], model["loop_steps"]) == (4, 4, cfg["total_ut_steps"])
    assert set(cfg["reduced"]) == {"layers", "batch_timesteps_per_chip"} == set(cfg["reduced_why"])
    assert cfg["batch_timesteps_per_chip"] == 5 * cfg["n_envs_per_chip"] * cfg["run_config"]["ppo"]["rollout_len"]
    assert all("recalled, not verifiable here" in v for k, v in cfg["assumed"].items() if k.startswith("recalled"))
    assert sum(k.startswith("recalled") for k in cfg["assumed"]) >= 6
    assert cfg["deployment"] and len(cfg["departures"]) >= 3
    entry = [c for c in cells.load_manifest()["configs"] if c["name"] == "ouro-2.6b-5v5-ut4"][0]
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    # the program builds it: sixteen rings a lane, every layer full attention with a dense FFN
    from dotaclient_tpu.models import afmoe, looplm

    built = program.build_run_config(cells.load_cell(CELL), seed=0, rehearsal=False).model
    looplm.check_config(built)
    assert built.carry_is_rings and afmoe.carry_bytes_per_lane(built) == 8 + 16 * 3072 * 8192


def test_counts_against_hand_counts():
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    model = rc["model"]
    w = flops_looplm.stack_weight_count(model)
    assert w["attention"] == 4 * 4 * 2048 * 2048 and w["ffn"] == 4 * 3 * 2048 * 5632
    weights = int(sum(w.values()))
    assert weights == 4 * 51_380_224 == 205_520_896          # 51.4 M a layer, ONE set for four loop steps
    assert flops_looplm.cache_bytes_per_lane(model) == 16 * 3072 * 8192 == 402_653_184
    # a step at position 999: 1,000 keys in each of 16 layer passes
    parts = flops_looplm.step_flops(rc, position=999.0)
    assert parts["attention"] == 2 * 4 * (4 * 4 * 2048 * 2048 + 4 * 2 * 16 * 128 * 1000)
    assert parts["ffn"] == 2 * 4 * 4 * 3 * 2048 * 5632
    assert flops_looplm.step_flops(rc, 999.0, head_passes=4)["heads"] == 4 * parts["heads"]
    # a dispatch of one game: forward 10 x 16 + 5 x 17, backward twice 5 x 16
    per_frame = flops_looplm.train_flops_per_frame(rc, 5, 5, 16, 999.0)
    rollout, learner = sum(parts.values()), sum(parts.values()) + 3 * parts["heads"]
    assert per_frame == pytest.approx((rollout * 160 + learner * (85 + 160)) / 80)
    work = flops_looplm.core_dispatch_work(rc, 5, 5, 16, 999.0)
    assert work["flops"] == pytest.approx((parts["attention"] + parts["ffn"]) * (160 + 85 + 160))
    # of a lane's 403 MB of rings a pass has to read the rows its query may see
    cache, row = 16 * 1000 * 8192, 16 * 8192
    assert work["seen_cache_bytes_per_lane"] == cache < work["cache_bytes_per_lane"]
    # the tied weights once a LOOP STEP a pass: 4 x 411 MB
    a_pass = 4 * weights * 2
    assert work["weight_bytes_a_pass"] == a_pass == 1_644_167_168
    want = 16 * 2 * (a_pass + 5 * (cache + row)) + (a_pass + 5 * cache) + (a_pass + 5 * cache + weights * 4)
    assert work["bytes"] == pytest.approx(want)
    least = flops.roofline_seconds(work, flops.peaks_for("TPU v5 lite"), "bfloat16")
    assert least["bound"] == "memory"
    with pytest.raises(flops.UnsupportedShape):
        flops_looplm.step_flops({**rc, "model": {**model, "core": "afmoe"}}, 0.0)


class FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 9 * 10 ** 9, "bytes_reserved": 10 ** 9}


def plane():
    """One chip, one dispatch of 200 ns: a rollout step and an update."""
    R = "jit(live_opponent)/phase_rollout/while/body/closed_call/Policy.step/policy_core/core"
    U = "jit(live_opponent)/phase_update/update_loss"
    F, B = f"{U}/jvp(Policy.sequence)/policy_core/core", f"{U}/transpose(jvp(Policy.sequence))/policy_core/core"
    ops = [
        Op("fusion.1", f"{R}/core_loop/layer_1/attn/core_attn_full/dot_general", 0, 30),
        Op("fusion.2", f"{R}/core_loop/layer_1/attn/core_cache_write/scatter", 30, 34),
        Op("fusion.3", f"{R}/core_loop/layer_0/core_dense_ffn/ffn/dot_general", 34, 54),
        Op("fusion.4", f"{R}/core_exit_gate/exit_gate/dot_general", 54, 56),
        Op("fusion.5", f"{R}/core_loop/out_norm/mul", 56, 60),
        Op("fusion.6", "jit(live_opponent)/phase_rollout/while/body/closed_call/Policy.step/policy_heads/dot_general", 60, 70),
        Op("fusion.7", "jit(live_opponent)/phase_rollout/while/body/closed_call/rollout_sim_step/select_n", 70, 100),
        Op("fusion.8", f"{F}/core_loop/layer_1/attn/core_attn_full/checkpoint/dot_general", 100, 110),
        Op("fusion.9", f"{B}/core_loop/layer_1/attn/core_attn_full/checkpoint/rematted_computation/dot_general", 110, 130),
        Op("fusion.10", f"{B}/core_loop/layer_2/core_dense_ffn/ffn/dot_general", 130, 150),
        Op("fusion.11", f"{U}/jvp(Policy.sequence)/policy_heads/dot_general", 150, 158),
        Op("fusion.12", f"{U}/update_exit_mix/mul", 158, 160),
        Op("fusion.13", f"{U}/transpose(update_exit_mix)/mul", 160, 162),
        Op("fusion.14", "jit(live_opponent)/phase_update/update_optimizer/mul", 162, 200),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_live_opponent(1)", 0, 200)])


def record_of(**over):
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False, "failures": [],
        "attempted": 12, "failed": 0, "run_config": rc, "lanes": 5, "opp_lanes": 5, "rollout_len": 16,
        "setup": {"compile_s": 60.0}, "core_position_at_end": 16.0 * 15,
        "window": {"programs_built": 0, "dispatches": 15, "traced_dispatches": 1, "traced_until_dispatch": 14,
                   "traced_seconds": 200e-9, "frames_per_dispatch": 80},
        "counters": {"before": {}, "after": {"looplm/expected_exit_step": 2.25, "looplm/loop_passes_total": 8.0}},
        "trace": trace.Trace([plane()], [Span("bench:traced_window", 0, 200)]), "trace_window": (0, 200),
    }
    record.update(over)
    return record


def test_every_new_reader_on_a_hand_made_plane():
    record = record_of()
    assert read("loop_attn_device_share", record) == pytest.approx(100 * 60 / 200)
    assert read("loop_ffn_device_share", record) == pytest.approx(100 * 40 / 200)
    assert read("loop_cache_write_device_share", record) == pytest.approx(100 * 4 / 200)
    # the gate 2, the mix 2 + 2 transposed, three quarters of the update's heads (8)
    assert read("exit_mix_device_share", record) == pytest.approx(100 * (2 + 4 + 0.75 * 8) / 200)
    assert read("exit_expected_step", record) == 2.25
    # the traced dispatch is number 13 of 15: its middle step is at 240 - 1.5 x 16
    rc = record["run_config"]
    work = flops_looplm.core_dispatch_work(rc, 5, 5, 16, 216.0)
    least = flops.roofline_seconds(work, flops.peaks_for("TPU v5 lite"), "bfloat16")
    core_ns = 60 + 4 + 40 + 2 + 4                       # everything under policy_core
    assert read("looplm_core_roofline", record) == pytest.approx(100 * least["seconds"] / (core_ns * 1e-9))
    assert record["notes"]["looplm_core_roofline"]["bound"] == "memory"
    per_frame = flops_looplm.train_flops_per_frame(rc, 5, 5, 16, 216.0)
    assert read("looplm_train_mfu", record) == pytest.approx(100 * per_frame * 80 / 200e-9 / 197e12)


def test_readers_find_nothing_where_nothing_was_counted_or_traced():
    """A program without the gauges or the scopes (the parent of PR 30), an
    untraced run: ``None`` or 0, never an exception."""
    bare = record_of(counters={"before": {}, "after": {}}, core_position_at_end=None)
    for name in ("exit_expected_step", "looplm_core_roofline", "looplm_train_mfu"):
        assert read(name, bare) is None
    untraced = record_of(trace=None, trace_window=None)
    for name in NEW[:4] + ("looplm_core_roofline",):
        assert read(name, untraced) is None
    old = trace.load(os.path.join(cells.BENCH_DIR, "tests", "data", "tpu_v5e_1chip.xplane.pb"))
    w = old.span("bench:traced_window")
    for name in NEW[:4]:
        assert read(name, record_of(trace=old, trace_window=(w.start, w.end))) == 0.0


# -- the comparison, at toy widths ------------------------------------------------


def toy(dtype="bfloat16", **over):
    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.models import init_params
    from dotaclient_tpu.models.policy import Policy

    cfg = default_config()
    sizes = dict(
        core="looplm", hidden_dim=32, n_layers=2, loop_steps=3, n_heads=4, n_kv_heads=4, head_dim=8,
        full_context=32, rollout_chunk=4, global_attn_every=1, n_dense_layers=0, dense_ffn_dim=48,
        moe_experts=0, attn_qk_norm=False, attn_out_gate=False, rope_full_layers=True,
        mup_enabled=False, rope_theta=1e6, rms_norm_eps=1e-6, dtype=dtype,
    )
    model = dataclasses.replace(cfg.model, **{**sizes, **over})
    policy = Policy(model, cfg.obs, cfg.actions)
    rc = {"model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs), "actions": dataclasses.asdict(cfg.actions)}
    return policy, init_params(policy, jax.random.PRNGKey(7)), rc


def test_comparison_passes_over_more_lanes_than_a_block():
    policy, params, rc = toy()
    report = compare_looplm.policy_agreement(
        policy, params, rc, seed=2 ** 31 + 5, lanes=compare_looplm.LANE_BLOCK + 1, steps=4, history_steps=24
    )
    assert report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) < 1e-5
    assert 1e-4 < report["stated_sequence"] < compare_looplm.TOL_STATED["bfloat16"]
    assert 1e-4 < report["stated_step"] < compare_looplm.TOL_STATED["bfloat16"]
    assert report["loop_steps"] == 3


def test_a_lower_precision_than_stated_fails():
    policy, params, rc = toy()
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare_looplm.policy_agreement(policy, params, rc, seed=3, lanes=3, steps=4, history_steps=24)
    assert report["tol_stated"] == compare_looplm.TOL_EXACT
    assert report["stated_sequence"] > compare_looplm.TOL_EXACT and not report["ok"]


@pytest.mark.parametrize("fault", [f for f in looplm_ref.FAULTS if f != "last_exit_not_the_remainder"])
def test_a_loop_that_differs_in_one_way_fails_the_comparison(monkeypatch, fault):
    """The program against a reference whose loop skips a loop step, keeps
    one cache for all loop steps, unties the weights or leaves out the norm
    between loop steps: each is far outside the limits (the last exit's mass
    is the loss's, not the outputs': ``tests/test_looplm.py``)."""
    policy, params, rc = toy(dtype="float32")
    history = looplm_ref.history
    monkeypatch.setattr(looplm_ref, "history", lambda *a, **kw: history(*a, **kw, fault=fault))
    report = compare_looplm.policy_agreement(policy, params, rc, seed=5, lanes=3, steps=4, history_steps=24)
    assert not report["ok"], report
    assert report["exact_sequence"] > 100 * compare_looplm.TOL_EXACT


def test_rehearsal_walks_the_cell():
    """``--rehearse-cpu`` at the harness's tiny rule beside the published
    heads, FFN width, rings and loop steps: control flow only (some eleven
    minutes: a dispatch through 4 GB of rings takes the CPU 50 s, so the
    window ends before the traced dispatches would begin)."""
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
