"""The harness is driven by data: every cell's files are found by name, and
a configuration, a traffic mix, a per-layer metric and a new kind of cell
(another runner with end-to-end metrics of its own) can each be added as
new files plus new entries, with no edit to a file or an entry that exists."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import cells, program, result, trace

ROOT = cells.ROOT


def manifest():
    return cells.load_manifest()


def test_every_cell_loads_and_names_files_that_exist():
    m = manifest()
    assert m["command"] == ["python3", "benchmark/run.py"] and m["paths"] == ["benchmark"]
    for w in m["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.chips in (1, 4)
        assert os.path.isfile(os.path.join(cells.BENCH_DIR, "runners", f"{cell.runner}.py"))
        assert os.path.isfile(os.path.join(ROOT, cell.config["reference"]))
        names = {x.name for x in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports at least one per-layer metric"
        for metric in cell.per_layer:
            assert metric.moves in names
            assert callable(cells.load_reader(metric).read)
        # the reasons travel with the data
        for key in ("source", "assumed", "reduced", "why"):
            assert key in cell.config and key in cell.traffic, key


def test_configs_are_each_used_and_state_their_cuts():
    m = manifest()
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert c["name"] in used
        own = json.load(open(os.path.join(ROOT, c["file"])))
        assert own["source"] == c["source"] and own["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank")) and "hidden" not in key


def test_a_metric_limited_to_some_cells_is_left_out_elsewhere():
    one = cells.load_cell("five5v5-lstm4096.fused-selfplay")
    four = cells.load_cell("five5v5-lstm4096.fused-selfplay-4chip")
    assert "collective_share" not in {m.name for m in one.per_layer}
    assert "collective_share" in {m.name for m in four.per_layer}


def test_unknown_names_are_errors():
    with pytest.raises(cells.CellError):
        cells.load_cell("no-such-cell")


def test_a_mix_that_extends_another_is_that_mix_under_a_second_name(tmp_path):
    one = cells.load_cell("five5v5-lstm4096.fused-selfplay")
    four = cells.load_cell("five5v5-lstm4096.fused-selfplay-4chip")
    assert four.traffic["name"] == "fused-selfplay-4chip" and four.chips == 4
    for key in ("runner", "end_to_end", "params", "source", "reduced"):
        assert four.traffic[key] == one.traffic[key], key
    # its own keys are laid over the base's, a dict over a dict
    assert four.traffic["why"] != one.traffic["why"]
    assert set(one.traffic["assumed"]) < set(four.traffic["assumed"])
    # one level only: a mix may not extend a mix that extends
    traffic = tmp_path / "traffic"
    shutil.copytree(os.path.join(cells.BENCH_DIR, "traffic"), traffic)
    (traffic / "third.json").write_text(json.dumps({"name": "third", "extends": "fused-selfplay-4chip"}))
    with pytest.raises(cells.CellError):
        cells.load_traffic(str(tmp_path), "third")


class FakeChip:
    """What the readers ask of a device, with the v5e's name."""

    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 5 * 10 ** 9, "bytes_reserved": 10 ** 9}


def test_every_reader_of_a_training_cell_reads_the_recorded_trace():
    """The traced line of a training cell, made from the trace recorded on
    the v5e (``test_trace.py``) and the small cell's own sizes: every
    per-layer reader finds its number, and the line has what the driver
    reads."""
    tr = trace.load(os.path.join(cells.BENCH_DIR, "tests", "data", "tpu_v5e_1chip.xplane.pb"))
    w = tr.span("bench:traced_window")
    cell = cells.load_cell("dota5v5-lstm128.fused-selfplay")
    record = {
        "devices": [FakeChip()], "chips": 1, "rehearsal": False,
        "failures": [], "attempted": 3, "failed": 0,
        "run_config": program.merged_run_config(cell, rehearsal=False),
        "lanes": 20480, "opp_lanes": 20480, "rollout_len": 16,
        "setup": {"compile_s": 14.0},
        "window": {"programs_built": 0, "traced_dispatches": 3,
                   "traced_seconds": (w.end - w.start) * 1e-9, "frames_per_dispatch": 327680},
        "trace": tr, "trace_window": (w.start, w.end),
    }
    line = result.result_line(cell, record, traced=True)
    assert line["correct"], record["failures"]
    assert set(line["metrics"]) == {m.name for m in cell.per_layer}
    assert line["metrics"]["hbm_peak_gb"]["value"] == 6.0
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert line["breakdown"]["device_ops"] and line["breakdown"]["idle_gaps"]
    assert record["notes"]["policy_core_roofline"]["bound"] == "memory"


def test_a_core_without_counts_leaves_its_metrics_out_with_a_note():
    """``train_mfu`` and ``policy_core_roofline`` apply to every training
    cell, and a later configuration with another core cannot edit them: it
    gets no LSTM counts under those names, and a note saying why."""
    import jax

    cell = cells.load_cell("dota5v5-lstm128.fused-selfplay")
    rc = program.merged_run_config(cell, rehearsal=True)
    rc["model"]["core"] = "transformer"
    record = {
        "devices": jax.devices()[:1], "chips": 1, "rehearsal": True,
        "failures": [], "attempted": 2, "failed": 0, "run_config": rc,
        "lanes": 5, "opp_lanes": 5, "rollout_len": 16,
        "setup": {"compile_s": 1.0},
        "window": {"programs_built": 0, "traced_dispatches": 2,
                   "traced_seconds": 1.0, "frames_per_dispatch": 80},
    }
    line = result.result_line(cell, record, traced=True)
    assert line["correct"] and set(line["metrics"]) == {"compile_s", "compiles_in_window"}
    assert "LSTM" in record["notes"]["train_mfu"]


def _digests(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            out[os.path.relpath(path, top)] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def test_new_configuration_mix_and_metric_are_new_files_plus_one_entry(tmp_path):
    """In a temporary copy: add a dummy configuration, a dummy traffic mix
    and a dummy per-layer metric as new files, add their entries to
    ``BENCHMARK.json``, and rehearse the new cell. Nothing that existed is
    edited, and the run reports the new metric beside the old ones."""
    copy = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, copy / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dotaclient_tpu"), copy / "dotaclient_tpu")
    before = _digests(copy / "benchmark")

    config = json.load(open(copy / "benchmark/configs/dota5v5-lstm128.json"))
    config.update(name="dummy-config", n_envs_per_chip=8)
    config["run_config"]["env"]["team_size"] = 2
    (copy / "benchmark/configs/dummy-config.json").write_text(json.dumps(config))
    mix = json.load(open(copy / "benchmark/traffic/fused-selfplay.json"))
    mix.update(name="dummy-mix")
    mix["params"].update(warmup_dispatches=2, trace_after_dispatches=1, trace_dispatches=2)
    (copy / "benchmark/traffic/dummy-mix.json").write_text(json.dumps(mix))
    (copy / "benchmark/metrics/dummy_dispatches.json").write_text(json.dumps({
        "name": "dummy_dispatches", "unit": "dispatches", "better": "higher",
        "source": "program_counter", "layer": "learner_loop",
        "moves": "train_frames_per_s", "reader": "dummy_dispatches",
    }))
    (copy / "benchmark/readers/dummy_dispatches.py").write_text(
        "def read(record):\n    return record['window']['dispatches']\n"
    )
    m = manifest()
    m["configs"].append({
        "name": "dummy-config", "source": config["source"],
        "file": "benchmark/configs/dummy-config.json", "reduced": [], "why": "a test",
    })
    m["workloads"].append({
        "name": "dummy-config.dummy-mix", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a test",
    })
    m["per_layer"].append({
        "name": "dummy_dispatches", "unit": "dispatches", "better": "higher",
        "source": "program_counter", "layer": "learner_loop", "moves": "train_frames_per_s",
    })
    (copy / "BENCHMARK.json").write_text(json.dumps(m))

    after = _digests(copy / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/dummy-config.json", "metrics/dummy_dispatches.json",
        "readers/dummy_dispatches.py", "traffic/dummy-mix.json",
    ]

    cell = cells.load_cell("dummy-config.dummy-mix", root=str(copy))
    assert cell.config["n_envs_per_chip"] == 8 and cell.traffic["name"] == "dummy-mix"
    assert "dummy_dispatches" in {x.name for x in cell.per_layer}

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    run = subprocess.run(
        [sys.executable, str(copy / "benchmark/run.py"), "--workload",
         "dummy-config.dummy-mix", "--rehearse-cpu", "--trace", "1", "--seconds", "1"],
        cwd=str(copy), env=env, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
    ours = [ln for ln in run.stdout.splitlines() if "benchmark:" in ln]
    assert ours and all(ln.startswith("REHEARSAL(cpu) ") for ln in ours)
    would = [ln for ln in ours if "would print" in ln]
    assert len(would) == 1
    line = json.loads(would[0].split("would print ", 1)[1])
    assert line["correct"] is True
    assert line["metrics"]["dummy_dispatches"]["value"] >= 1
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # a rehearsal never prints the result line itself
    assert not run.stdout.rstrip().splitlines()[-1].startswith("{")


def test_a_new_kind_of_cell_only_appends(tmp_path):
    """A cell of a new kind (its own runner, its own end-to-end metric and a
    per-layer metric that moves it) is new files and new entries: no entry
    that exists gains a ``workloads`` list, the cells that exist report what
    they reported, and no reader of theirs runs on the new kind's record."""
    copy = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, copy / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dotaclient_tpu"), copy / "dotaclient_tpu")
    before = _digests(copy / "benchmark")
    old = manifest()
    reported = {
        w["name"]: (
            [x.name for x in cells.load_cell(w["name"]).end_to_end],
            [x.name for x in cells.load_cell(w["name"]).per_layer],
        )
        for w in old["workloads"]
    }

    (copy / "benchmark/runners/dummy_kind.py").write_text(
        "import jax\n\n\n"
        "def run(cell, args):\n"
        "    return {'devices': jax.devices(), 'chips': cell.chips, 'rehearsal': args.rehearse,\n"
        "            'failures': [], 'attempted': 7, 'failed': 0,\n"
        "            'setup': {'compile_s': 0.5}, 'dummy': {'layer': 3.0},\n"
        "            'end_to_end': {'dummy_rate': 11.0, 'setup_s': 1.5, 'train_frames_per_s': 1.0}}\n"
    )
    (copy / "benchmark/traffic/dummy-kind.json").write_text(json.dumps({
        "name": "dummy-kind", "runner": "dummy_kind", "end_to_end": ["dummy_rate"],
        "params": {}, "source": "a test", "assumed": {}, "reduced": [], "why": "a test",
    }))
    layer = {"name": "dummy_layer", "unit": "things", "better": "higher",
             "source": "program_counter", "layer": "dummy", "moves": "dummy_rate"}
    (copy / "benchmark/metrics/dummy_layer.json").write_text(json.dumps({**layer, "reader": "dummy_layer"}))
    (copy / "benchmark/readers/dummy_layer.py").write_text(
        "def read(record):\n    return record['dummy']['layer']\n"
    )
    cell_name = "dota5v5-lstm128.dummy-kind"
    new = json.loads(json.dumps(old))
    new["workloads"].append({"name": cell_name, "config": "dota5v5-lstm128",
                             "traffic": "dummy-kind", "chips": 1, "why": "a test"})
    new["end_to_end"].append({"name": "dummy_rate", "unit": "things/s", "better": "higher",
                              "bound": 0.05, "source": "host_clock", "workloads": [cell_name]})
    new["per_layer"].append({**layer, "workloads": [cell_name]})
    (copy / "BENCHMARK.json").write_text(json.dumps(new))

    # files and entries were added; none that existed was touched
    after = _digests(copy / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert new[key][:len(old[key])] == old[key], key
    for name, (e2e, layers) in reported.items():
        cell = cells.load_cell(name, root=str(copy))
        assert ([x.name for x in cell.end_to_end], [x.name for x in cell.per_layer]) == (e2e, layers)

    cell = cells.load_cell(cell_name, root=str(copy))
    assert [x.name for x in cell.end_to_end] == ["setup_s", "dummy_rate"]
    # of the per-layer metrics that exist only the one that moves setup_s
    assert [x.name for x in cell.per_layer] == ["compile_s", "dummy_layer"]

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    lines = {}
    for traced in ("0", "1"):
        run = subprocess.run(
            [sys.executable, str(copy / "benchmark/run.py"), "--workload", cell_name,
             "--rehearse-cpu", "--trace", traced],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=600,
        )
        assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
        would = [ln for ln in run.stdout.splitlines() if "would print" in ln]
        lines[traced] = json.loads(would[0].split("would print ", 1)[1])
    assert lines["0"]["correct"] and lines["0"]["attempted"] == 7
    assert {k: v["value"] for k, v in lines["0"]["metrics"].items()} == {"setup_s": 1.5, "dummy_rate": 11.0}
    assert {k: v["value"] for k, v in lines["1"]["metrics"].items()} == {"compile_s": 0.5, "dummy_layer": 3.0}


def test_a_mix_names_only_end_to_end_metrics_that_exist(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, copy / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.load(open(copy / "benchmark/traffic/fused-selfplay.json"))
    mix["end_to_end"] = ["train_frames_per_s", "no_such_metric"]
    (copy / "benchmark/traffic/fused-selfplay.json").write_text(json.dumps(mix))
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest()))
    with pytest.raises(cells.CellError, match="no_such_metric"):
        cells.load_cell("dota5v5-lstm128.fused-selfplay", root=str(copy))
