"""The cell ``trinity-mini-5v5-ep16.fused-selfplay-anycore``: it loads, its
counts agree with hand counts, each of its readers reads a hand-made trace
or record, its comparison passes at toy widths and fails what it must, and
``--rehearse-cpu`` walks it."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.harness import cells, compare_afmoe, flops, flops_afmoe, program, trace
from benchmark.harness.trace import DevicePlane, Op, Span
from benchmark.reference import afmoe_ref

CELL = "trinity-mini-5v5-ep16.fused-selfplay-anycore"
NEW = (
    "attn_window_device_share", "attn_full_device_share", "experts_routed_device_share",
    "cache_write_device_share", "expert_load_max_over_mean", "afmoe_core_roofline",
    "afmoe_train_mfu",
)


def read(name, record):
    return cells.load_reader(cells.Metric(name, "%", "lower", "device_trace", reader=name)).read(record)


def test_the_cell_loads_with_its_own_metrics_and_without_the_lstms():
    cell = cells.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert set(NEW) <= names and not {"policy_core_roofline", "train_mfu"} & names
    assert {"policy_core_share", "hbm_peak_gb", "compiles_in_window", "device_idle_share"} <= names
    assert cell.runner == "train_fused_anycore" and cell.chips == 1
    assert {m.name for m in cell.end_to_end} == {"train_frames_per_s", "setup_s"}
    # the base mix's parameters, and this mix's own
    p = cell.traffic["params"]
    assert (p["warmup_dispatches"], p["trace_dispatches"], p["max_dispatches_in_flight"]) == (3, 10, 2)
    assert (p["sample_lanes"], p["sample_steps"], p["sample_history_steps"]) == (16, 16, 2560)
    # no other cell reports the new metrics
    for other in cells.load_manifest()["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW) & {m.name for m in cells.load_cell(other["name"]).per_layer}


def test_the_configuration_holds_the_catalog_row_and_states_its_cut():
    cfg = cells.load_cell(CELL).config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "sliding_window": 2048, "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 1,
        "num_hidden_layers": 32, "num_dense_layers": 2, "route_scale": 2.826,
        "rope_theta": 10000, "rms_norm_eps": 1e-05, "global_attn_every_n_layers": 4,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    model = cfg["run_config"]["model"]
    # every width as published; depth and held experts cut and listed
    assert (model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (2048, 32, 4, 128)
    assert (model["context_window"], model["dense_ffn_dim"], model["expert_ffn_dim"]) == (2048, 6144, 1024)
    assert (model["moe_experts"], model["experts_per_token"], model["held_experts"]) == (128, 8, 8)
    assert (cfg["layers"], cfg["held_experts"]) == (model["n_layers"], model["held_experts"]) == (5, 8)
    assert flops_afmoe.layer_kinds(model) == [
        (False, True), (False, False), (False, False), (False, False), (True, False),
    ]
    assert set(cfg["reduced"]) == {"layers", "held_experts", "batch_timesteps_per_chip"}
    assert all("recalled, not verifiable here" in v for k, v in cfg["assumed"].items() if k.startswith("recalled"))
    assert sum(k.startswith("recalled") for k in cfg["assumed"]) >= 7


def test_counts_against_hand_counts():
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    model = rc["model"]
    w = flops_afmoe.core_weight_count(model)
    attn = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048            # q, gate, k, v, o
    assert w["attention"] == 5 * attn == 5 * 27_262_976
    assert w["dense_ffn"] == 3 * 2048 * 6144
    assert w["routed"] == 4 * 8 * 3 * 2048 * 1024 and w["shared"] == 4 * 3 * 2048 * 1024
    assert w["router"] == 4 * 2048 * 128
    assert round(sum(w.values()) / 1e6, 1) == 401.6
    assert flops_afmoe.cache_bytes_per_lane(model) == (4 * 2064 + 3072) * 2 * 512 * 2 == 22.125 * 2 ** 20
    # a step at position 999 (1,000 keys in every layer) with half a pair a token
    parts = flops_afmoe.step_flops(rc, position=999.0, pairs_per_token=0.5)
    assert parts["attention"] == 2 * (5 * attn + 5 * 2 * 32 * 128 * 1000)
    assert parts["routed_experts"] == 2 * 3 * 2048 * 1024 * 0.5
    # past the window a window layer sees 2,048 keys and the full layer all
    far = flops_afmoe.step_flops(rc, position=2999.0, pairs_per_token=0.5)
    assert far["attention"] - 2 * 5 * attn == 2 * 2 * 32 * 128 * (4 * 2048 + 3000)
    # a dispatch: forward 160 x 16 + 80 x 17, backward twice 80 x 16
    passes = 160 * 16 + 80 * 17 + 2 * 80 * 16
    per_frame = flops_afmoe.train_flops_per_frame(rc, 80, 80, 16, 999.0, 0.5)
    assert per_frame == pytest.approx(sum(parts.values()) * passes / (80 * 16))
    work = flops_afmoe.core_dispatch_work(rc, 80, 80, 16, 999.0, 0.5)
    core = sum(parts[k] for k in ("attention", "dense_ffn", "router_and_shared", "routed_experts"))
    assert work["flops"] == pytest.approx(core * passes)
    # of a lane's 22 MiB of rings a pass has to read the rows its query may
    # see: 1,000 keys in each of five layers, K and V of 512 bfloat16
    weights, cache = 401_604_608 * 2, 5 * 1000 * 2 * 512 * 2
    assert work["seen_cache_bytes_per_lane"] == cache < work["cache_bytes_per_lane"] == 22.125 * 2 ** 20
    # and past the window and the episode's 3,000 steps no more than the rings hold less their slack
    assert flops_afmoe.seen_cache_bytes_per_lane(model, 2999.0) == (4 * 2048 + 3000) * 2 * 512 * 2
    row = 2 * 512 * 2 * 5
    want = 16 * 2 * (weights + 80 * (cache + row)) + (weights + 80 * cache) + (weights + 80 * cache + 401_604_608 * 4)
    assert work["bytes"] == pytest.approx(want)
    least = flops.roofline_seconds(work, flops.peaks_for("TPU v5 lite"), "bfloat16")
    assert least["bound"] == "memory"
    with pytest.raises(flops.UnsupportedShape):
        flops_afmoe.step_flops({**rc, "model": {**model, "core": "lstm"}}, 0.0, 0.0)


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
        Op("fusion.1", f"{R}/layer_1/attn/core_attn_window/dot_general", 0, 30),
        Op("fusion.2", f"{R}/layer_1/attn/core_cache_write/scatter", 30, 34),
        Op("fusion.3", f"{R}/layer_4/attn/core_attn_full/dot_general", 34, 54),
        Op("fusion.4", f"{R}/layer_1/moe/core_router/top_k", 54, 56),
        Op("fusion.5", f"{R}/layer_1/moe/core_experts_routed/dot_general", 56, 76),
        Op("fusion.6", f"{R}/layer_1/moe/core_expert_shared/dot_general", 76, 80),
        Op("fusion.7", f"{R}/layer_0/core_dense_ffn/dot_general", 80, 90),
        Op("fusion.8", "jit(live_opponent)/phase_rollout/while/body/closed_call/rollout_sim_step/select_n", 90, 100),
        Op("fusion.9", f"{F}/layer_1/attn/core_attn_window/checkpoint/dot_general", 100, 110),
        Op("fusion.10", f"{B}/layer_1/attn/core_attn_window/checkpoint/rematted_computation/dot_general", 110, 130),
        Op("fusion.11", f"{B}/layer_2/moe/core_experts_routed/dot_general", 130, 160),
        Op("fusion.12", "jit(live_opponent)/phase_update/update_optimizer/mul", 160, 200),
    ]
    return DevicePlane("/device:TPU:0", ops, [Span("jit_live_opponent(1)", 0, 200)])


def record_of(**over):
    rc = program.merged_run_config(cells.load_cell(CELL), rehearsal=False)
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False, "failures": [],
        "attempted": 12, "failed": 0, "run_config": rc, "lanes": 80, "opp_lanes": 80, "rollout_len": 16,
        "setup": {"compile_s": 60.0}, "core_position_at_end": 16.0 * 15,
        "window": {"programs_built": 0, "dispatches": 15, "traced_dispatches": 1, "traced_until_dispatch": 14,
                   "traced_seconds": 200e-9, "frames_per_dispatch": 1280},
        "counters": {"before": {}, "after": {
            "moe/local_assignments": 680.0, "moe/max_over_mean_expert_load": 1.7, "moe/dropped_assignments": 0.0,
        }},
        "trace": trace.Trace([plane()], [Span("bench:traced_window", 0, 200)]), "trace_window": (0, 200),
    }
    record.update(over)
    return record


def test_every_new_reader_on_a_hand_made_plane():
    record = record_of()
    assert read("attn_window_device_share", record) == pytest.approx(100 * 60 / 200)
    assert read("attn_full_device_share", record) == pytest.approx(100 * 20 / 200)
    assert read("experts_routed_device_share", record) == pytest.approx(100 * 50 / 200)
    assert read("cache_write_device_share", record) == pytest.approx(100 * 4 / 200)
    assert read("expert_load_max_over_mean", record) == 1.7
    # the traced dispatch is number 13 of 15: its middle step is at 240 - 1.5 x 16
    rc = record["run_config"]
    work = flops_afmoe.core_dispatch_work(rc, 80, 80, 16, 216.0, 680.0 / (80 * 17))
    least = flops.roofline_seconds(work, flops.peaks_for("TPU v5 lite"), "bfloat16")
    assert read("afmoe_core_roofline", record) == pytest.approx(100 * least["seconds"] / 150e-9)
    assert record["notes"]["afmoe_core_roofline"]["bound"] == "memory"
    per_frame = flops_afmoe.train_flops_per_frame(rc, 80, 80, 16, 216.0, 680.0 / (80 * 17))
    assert read("afmoe_train_mfu", record) == pytest.approx(100 * per_frame * 1280 / 200e-9 / 197e12)


def test_readers_find_nothing_where_nothing_was_counted_or_traced():
    """A program without the counters or the scopes (the parent of PR 26), an
    untraced run: ``None`` or 0, never an exception."""
    bare = record_of(counters={"before": {}, "after": {}}, core_position_at_end=None)
    for name in ("expert_load_max_over_mean", "afmoe_core_roofline", "afmoe_train_mfu"):
        assert read(name, bare) is None
    untraced = record_of(trace=None, trace_window=None)
    for name in NEW[:4] + ("afmoe_core_roofline",):
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
        core="afmoe", hidden_dim=32, n_layers=3, n_heads=4, n_kv_heads=2, head_dim=8,
        context_window=8, full_context=32, rollout_chunk=4, global_attn_every=3,
        n_dense_layers=1, dense_ffn_dim=48, expert_ffn_dim=16, moe_experts=8,
        experts_per_token=2, held_experts=4, dtype=dtype,
    )
    model = dataclasses.replace(cfg.model, **{**sizes, **over})
    policy = Policy(model, cfg.obs, cfg.actions)
    rc = {"model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs), "actions": dataclasses.asdict(cfg.actions)}
    return policy, init_params(policy, jax.random.PRNGKey(7)), rc


def test_comparison_passes_and_its_sample_has_what_it_says():
    policy, params, rc = toy()
    report = compare_afmoe.policy_agreement(policy, params, rc, seed=2 ** 31 + 5, lanes=3, steps=4, history_steps=24)
    assert report["ok"], report
    assert max(report["exact_sequence"], report["exact_step"]) < 1e-5 and report["exact_routing_margin"] < 1e-5
    assert 1e-4 < report["stated_sequence"] < compare_afmoe.TOL_STATED["bfloat16"]
    _, dones = compare_afmoe.sample(rc, 3, lanes=3, steps=4, history_steps=24)
    assert dones[0, -4:].sum() == 1 and dones[1].sum() == 0 and dones[:, -1].sum() == 0


def test_a_lower_precision_than_stated_fails():
    policy, params, rc = toy()
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare_afmoe.policy_agreement(policy, params, rc, seed=3, lanes=3, steps=4, history_steps=24)
    assert report["tol_stated"] == compare_afmoe.TOL_EXACT
    assert report["stated_sequence"] > compare_afmoe.TOL_EXACT and not report["ok"]


@pytest.mark.parametrize("what", ["dropped_expert_term", "router_scores_differently"])
def test_what_the_held_routes_must_not_hide(what):
    """The reference is given the program's choice of experts; a term the
    program leaves out, or a router whose scores are not the reference's,
    still shows: in the outputs, in the margin."""
    policy, params, rc = toy(dtype="float32")
    obs, dones = compare_afmoe.sample(rc, 5, lanes=3, steps=4, history_steps=24)
    moe = lambda p: p["params"]["core"]["layer_1"]["moe"]
    broken = jax.tree.map(lambda x: x, params)
    if what == "dropped_expert_term":
        moe(broken)["expert_down"] = moe(params)["expert_down"].at[1].set(0.0)
    else:
        moe(broken)["router"] = moe(params)["router"][:, ::-1]
    (logits, values, routes), _ = compare_afmoe.program_outputs(policy, broken, obs, dones, 4, "highest")
    want_logits, want_values, margin = compare_afmoe.reference_outputs(params, obs, dones, rc["model"], routes, 4)
    diff = afmoe_ref.policy_ref.max_abs_diff({"l": logits, "v": values}, {"l": want_logits, "v": want_values})
    if what == "dropped_expert_term":
        assert diff > 100 * compare_afmoe.TOL_EXACT
    else:
        assert margin > 100 * compare_afmoe.MARGIN_EXACT


def test_rehearsal_walks_the_cell():
    """``--rehearse-cpu`` at the harness's tiny rule beside the published
    window, heads and expert sizes: control flow only, traced."""
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
