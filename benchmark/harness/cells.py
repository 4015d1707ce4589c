"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix and nothing else. The
configuration's sizes are in the file ``BENCHMARK.json`` gives for it; the
traffic mix is ``benchmark/traffic/<mix>.json`` and names its runner
(``benchmark/runners/<runner>.py``) and the end-to-end metrics that runner
produces; each per-layer metric is ``benchmark/metrics/<metric>.json`` and
names its reader (``benchmark/readers/<reader>.py``).

A cell reports ``setup_s``, the end-to-end metrics its mix names, and the
per-layer metrics that move one of those; a ``workloads`` list on an entry
narrows that further. So a configuration, a mix, a metric, and a new kind of
cell (another runner with end-to-end metrics of its own) are each new files
plus new entries: no entry that exists has to change, no reader of one kind
of cell runs on another kind's record, and nothing here is edited.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
PACKAGE = os.path.basename(BENCH_DIR)


class CellError(ValueError):
    """The manifest or one of a cell's files is missing or inconsistent."""


@dataclasses.dataclass(frozen=True)
class Metric:
    """One entry of ``end_to_end`` or ``per_layer``."""

    name: str
    unit: str
    better: str
    source: str
    bound: Optional[float] = None      # end-to-end only
    layer: Optional[str] = None        # per-layer only
    moves: Optional[str] = None        # per-layer only
    reader: Optional[str] = None       # per-layer only, from its own file


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int

    @property
    def runner(self) -> str:
        return self.traffic["runner"]


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise CellError(f"{path} is not JSON: {e}") from None


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _in_cell(entry: Dict[str, Any], cell: str) -> bool:
    """A metric with a ``workloads`` list exists only in those cells."""
    return "workloads" not in entry or cell in entry["workloads"]


def load_traffic(bench: str, name: str) -> Dict[str, Any]:
    """``traffic/<name>.json``. A mix that ``extends`` another is that mix
    with its own keys laid over it (a dict over a dict, one level deep):
    ``BENCHMARK.json`` admits a pair of configuration and traffic once, so
    the same mix on another number of chips needs a second name, not a
    second copy of the parameters."""
    own = _read_json(os.path.join(bench, "traffic", f"{name}.json"))
    if "extends" not in own:
        return own
    base = _read_json(os.path.join(bench, "traffic", f"{own.pop('extends')}.json"))
    if "extends" in base:
        raise CellError(f"traffic mix {name!r} extends a mix that extends another")
    for key, value in own.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            value = {**base[key], **value}
        base[key] = value
    return base


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with every file it names read and cross-checked."""
    manifest = load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise CellError(
            f"no workload {name!r} in BENCHMARK.json (has: {sorted(cells)})"
        )
    return build_cell(manifest, cells[name], root)


def build_cell(manifest: Dict[str, Any], w: Dict[str, Any], root: str = ROOT) -> Cell:
    """The cell of one ``workloads`` entry ``w`` under ``manifest``."""
    name = w["name"]
    bench = os.path.join(root, PACKAGE)
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise CellError(f"{name}: unknown configuration {w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_traffic(bench, w["traffic"])
    for key in ("runner", "end_to_end"):
        if key not in traffic:
            raise CellError(f"traffic mix {w['traffic']!r} names no {key}")
    # what the mix's runner produces, and the set-up time that every cell has
    produced = {"setup_s", *traffic["end_to_end"]}
    unknown = produced - {m["name"] for m in manifest["end_to_end"]}
    if unknown:
        raise CellError(
            f"traffic mix {w['traffic']!r} names end-to-end metric(s) "
            f"{sorted(unknown)} that BENCHMARK.json does not have"
        )
    end_to_end = [
        Metric(
            name=m["name"], unit=m["unit"], better=m["better"],
            source=m["source"], bound=m["bound"],
        )
        for m in manifest["end_to_end"]
        if m["name"] in produced and _in_cell(m, name)
    ]
    judged = {m.name for m in end_to_end}
    per_layer = []
    for m in manifest["per_layer"]:
        # a per-layer metric is reported only where the metric it moves is
        if not _in_cell(m, name) or m["moves"] not in judged:
            continue
        own = _read_json(os.path.join(bench, "metrics", f"{m['name']}.json"))
        for key in ("unit", "layer", "moves", "source"):
            if own.get(key) != m[key]:
                raise CellError(
                    f"metric {m['name']}: {key!r} is {own.get(key)!r} in its "
                    f"own file and {m[key]!r} in BENCHMARK.json"
                )
        per_layer.append(
            Metric(
                name=m["name"], unit=m["unit"], better=m["better"],
                source=m["source"], layer=m["layer"], moves=m["moves"],
                reader=own["reader"],
            )
        )
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=end_to_end, per_layer=per_layer,
        run_seconds=int(manifest["run_seconds"]),
    )


def load_runner(cell: Cell):
    """``benchmark/runners/<runner>.py``, found by the traffic file's name
    for it. It has ``run(cell, args) -> dict`` (see ``run.py``)."""
    return importlib.import_module(f"{PACKAGE}.runners.{cell.runner}")


def load_reader(metric: Metric):
    """``benchmark/readers/<reader>.py`` with ``read(record) -> float |
    None``; ``None`` means there was nothing to read."""
    return importlib.import_module(f"{PACKAGE}.readers.{metric.reader}")
