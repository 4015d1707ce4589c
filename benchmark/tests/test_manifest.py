"""``BENCHMARK.json`` against the limits the benchmark's contract states for
it: keys, names, counts and sizes. The driver refuses a file outside any of
them before a single run, so they are checked here, without the chip."""

import json
import os
import re
import subprocess

from benchmark.harness import cells

ROOT = cells.ROOT
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
LAYER = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_ENDINGS = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head", "expan")


def _under(path, dirs):
    return any(path == d or path.startswith(d.rstrip("/") + "/") for d in dirs)


def test_keys_counts_and_sizes():
    m = cells.load_manifest()
    assert set(m) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(m["paths"]) <= 16 and 1 <= len(m["command"]) <= 32
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    for arg in m["command"]:
        assert isinstance(arg, str) and not arg.startswith("/") and ".." not in arg.split("/")
        if os.path.exists(os.path.join(ROOT, arg)):
            assert _under(arg, m["paths"]), f"command names {arg}, outside paths"
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 2 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    # 2 + 14 runs a cell, a minute each beyond the window, two compiles a cell,
    # 1200 s spare, with the full 24 cells
    assert 2 * (m["run_seconds"] + 60) + 24 * (14 * (m["run_seconds"] + 60) + 180) + 1200 <= 43200


def test_names_are_plain_and_used_once():
    m = cells.load_manifest()
    names = [
        x["name"]
        for key in ("configs", "workloads", "end_to_end", "per_layer")
        for x in m[key]
    ]
    for name in names:
        assert NAME.match(name), name
    assert len(names) == len(set(names))
    for x in m["configs"] + m["workloads"]:
        assert 0 < len(x["why"]) <= 200, (x["name"], len(x["why"]))
    tracked = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "--", *m["paths"]],
        cwd=ROOT, capture_output=True, text=True,
    )
    if tracked.returncode == 0:  # the driver's checkout is not a repository
        for path in tracked.stdout.split():
            assert PATH.match(path), path


def test_configs_and_workloads():
    m = cells.load_manifest()
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _under(c["file"], m["paths"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert isinstance(json.load(open(os.path.join(ROOT, c["file"]))), dict)
        assert c["source"]
        for key in c["reduced"]:
            low = key.lower()
            assert not low.endswith(("_dim", "_rank")), key
            assert not any(word in low for word in WIDTH_WORDS), key
    configs = {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in m["workloads"]} == configs
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        mixes = [
            f for f in os.listdir(os.path.join(cells.BENCH_DIR, "traffic"))
            if os.path.splitext(f)[0] == w["traffic"]
        ]
        assert len(mixes) == 1 and mixes[0].endswith(TRAFFIC_ENDINGS), w["traffic"]
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)


def test_metrics():
    m = cells.load_manifest()
    cell_names = {w["name"] for w in m["workloads"]}
    end = {x["name"]: x for x in m["end_to_end"]}
    assert end["setup_s"]["bound"] <= 0.1 and end["setup_s"]["unit"] == "s"
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert x["source"] in SOURCES
        assert LAYER.match(x["layer"]), (x["name"], x["layer"])
        assert x["moves"] in end, (x["name"], x["moves"])
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"
    for x in m["end_to_end"] + m["per_layer"]:
        assert x["better"] in ("higher", "lower")
        assert x["unit"] and len(x["unit"]) <= 16
        assert set(x.get("workloads", cell_names)) <= cell_names
    # every layer a metric names is a row of PERF.md's section 3
    perf = open(os.path.join(ROOT, "PERF.md")).read() if os.path.isfile(
        os.path.join(ROOT, "PERF.md")) else None
    if perf is not None:
        for x in m["per_layer"]:
            assert f"`{x['layer']}`" in perf, x["layer"]
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    for name in cell_names:
        cell = cells.load_cell(name)
        judged = {e.name for e in cell.end_to_end}
        assert "setup_s" in judged and len(judged) >= 2 and cell.per_layer
