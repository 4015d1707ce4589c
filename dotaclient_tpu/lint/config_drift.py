"""config-drift pass: CLI flags and override knobs match the operator docs.

Two operator contracts drift the same way telemetry keys do:

* **Override knobs.** Every dataclass field reachable through the
  ``--ppo/--reward/--league/--buffer/--health/--learner K=V`` override
  flags (``utils/overrides.py``) is a public tuning surface. The
  docs/OPERATIONS.md "Config override knobs" tables must list every such
  field, and every field the tables list must exist — a renamed field
  silently orphans its row; an undocumented field is a knob operators
  cannot find during an incident.
* **CLI flags.** Every ``--flag`` OPERATIONS.md mentions must exist in
  some entrypoint (a doc'd flag that argparse rejects is a broken
  runbook), and every flag the learner/actor CLIs define must appear in
  OPERATIONS.md (those two are the operator-facing surfaces; one-off
  scripts document themselves).

Everything is extracted statically: ``config.py`` dataclass fields via
AST, ``add_argument("--x", ...)`` calls via AST, documented flags via a
regex that rejects ``--xla_...``-style env-var fragments, knob tables via
the ``### --flag (ClassName)`` heading + first-column-backtick convention.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from dotaclient_tpu.lint.core import Diagnostic, FileCtx, Rule

CONFIG_PY = "dotaclient_tpu/config.py"
OPERATIONS_MD = "docs/OPERATIONS.md"

# override flag → the dataclass it reaches (train/learner.py main();
# scripts/train_demo.py shares --ppo/--reward/--league via the same parser)
OVERRIDE_FLAGS: Dict[str, str] = {
    "--ppo": "PPOConfig",
    "--reward": "RewardConfig",
    "--league": "LeagueConfig",
    "--buffer": "BufferConfig",
    "--health": "HealthConfig",
    "--learner": "LearnerConfig",
    "--mesh": "MeshConfig",
    "--serve": "ServeConfig",
}

# CLIs whose full flag surface must be documented in OPERATIONS.md
OPERATOR_CLIS = (
    "dotaclient_tpu/train/learner.py",
    "dotaclient_tpu/actor/__main__.py",
)

# every entrypoint a documented flag may legitimately belong to
ALL_CLIS = OPERATOR_CLIS + (
    "dotaclient_tpu/league/__main__.py",
    "dotaclient_tpu/lint/__main__.py",
    "dotaclient_tpu/serve/__main__.py",
    "dotaclient_tpu/serve/router.py",
    "scripts/serve_loadgen.py",
    "scripts/chaos_run.py",
    "scripts/fleet_status.py",
    "scripts/run_multichip.py",
    "scripts/train_demo.py",
    "scripts/curriculum_5v5.py",
    "scripts/bench_configs.py",
    "scripts/check_telemetry_schema.py",
    "scripts/check_host_sync.py",
    "benchmark/run.py",
)

# `--flag` mention: lowercase-dashed word; a trailing [_a-z0-9] after the
# match would mean we clipped a longer token (e.g. --xla_force_...), and
# a leading '-' would mean we are inside a '---' rule line.
_DOC_FLAG_RE = re.compile(r"(?<!-)--([a-z][a-z0-9]*(?:-[a-z0-9]+)*)(?![a-z0-9_-])")

_KNOB_HEADING_RE = re.compile(r"^###\s+`?(--[a-z-]+)`?\s+\((\w+)\)\s*$")
_KNOB_ROW_RE = re.compile(r"^\|\s*`([a-z0-9_]+)`\s*\|")


def dataclass_fields(config_source: str) -> Dict[str, List[str]]:
    """class name → annotated field names, via AST (no import)."""
    tree = ast.parse(config_source)
    out: Dict[str, List[str]] = {}
    for node in tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        fields = [
            stmt.target.id
            for stmt in node.body
            if isinstance(stmt, ast.AnnAssign)
            and isinstance(stmt.target, ast.Name)
        ]
        out[node.name] = fields
    return out


def cli_flags(py_source: str) -> Set[str]:
    """Every literal ``--flag`` passed to an add_argument call."""
    tree = ast.parse(py_source)
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "add_argument"):
            continue
        for arg in node.args:
            if (
                isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)
                and arg.value.startswith("--")
            ):
                out.add(arg.value)
    return out


def documented_flags(doc_text: str) -> Dict[str, int]:
    """--flag mentions in the doc → first line number."""
    out: Dict[str, int] = {}
    for i, line in enumerate(doc_text.splitlines(), 1):
        for m in _DOC_FLAG_RE.finditer(line):
            out.setdefault(f"--{m.group(1)}", i)
    return out


def knob_tables(doc_text: str) -> Dict[str, Tuple[str, Dict[str, int]]]:
    """Parse the "Config override knobs" tables:
    flag → (ClassName, {knob: line})."""
    out: Dict[str, Tuple[str, Dict[str, int]]] = {}
    current: str = ""
    for i, line in enumerate(doc_text.splitlines(), 1):
        stripped = line.strip()
        m = _KNOB_HEADING_RE.match(stripped)
        if m:
            current = m.group(1)
            out[current] = (m.group(2), {})
            continue
        if stripped.startswith("#"):
            # any other heading closes the table: a later unrelated
            # backticked-first-column table must not be misattributed to
            # the last knob table
            current = ""
            continue
        if current:
            row = _KNOB_ROW_RE.match(stripped)
            if row and row.group(1) not in ("knob",):
                out[current][1].setdefault(row.group(1), i)
    return out


def drift_findings(
    fields_by_class: Dict[str, List[str]],
    flags_by_cli: Dict[str, Set[str]],
    doc_text: str,
    rule_id: str = "config-drift",
    doc_path: str = OPERATIONS_MD,
    config_path: str = CONFIG_PY,
) -> List[Diagnostic]:
    """Pure cross-check (unit-testable with synthetic inputs)."""
    out: List[Diagnostic] = []
    tables = knob_tables(doc_text)
    doc_flags = documented_flags(doc_text)
    # 1. override-reachable fields ⊆ knob tables; table rows ⊆ fields;
    #    and the flag itself must exist on the learner CLI (a knob table
    #    for a flag argparse rejects is a broken runbook)
    learner_flags = flags_by_cli.get(OPERATOR_CLIS[0])
    for flag, cls in sorted(OVERRIDE_FLAGS.items()):
        if learner_flags is not None and flag not in learner_flags:
            out.append(
                Diagnostic(
                    OPERATOR_CLIS[0], 0, rule_id,
                    f"override flag {flag} (→ {cls}) is declared in "
                    f"OVERRIDE_FLAGS but the learner CLI does not define "
                    f"it — add the add_argument or drop the mapping",
                    context=flag,
                )
            )
        fields = fields_by_class.get(cls)
        if fields is None:
            continue
        table = tables.get(flag)
        if table is None:
            out.append(
                Diagnostic(
                    doc_path, 0, rule_id,
                    f"no '### {flag} ({cls})' knob table in OPERATIONS.md "
                    f"'Config override knobs' — every {flag} K=V-reachable "
                    f"field must be documented there",
                    context=flag,
                )
            )
            continue
        doc_cls, knobs = table
        if doc_cls != cls:
            out.append(
                Diagnostic(
                    doc_path, 0, rule_id,
                    f"knob table for {flag} names {doc_cls} but the CLI "
                    f"maps it to {cls}",
                    context=flag,
                )
            )
        for field in fields:
            if field not in knobs:
                out.append(
                    Diagnostic(
                        config_path, 0, rule_id,
                        f"{cls}.{field} is reachable via '{flag} "
                        f"{field}=V' but missing from the OPERATIONS.md "
                        f"{flag} knob table — document it",
                        context=f"{flag}.{field}",
                    )
                )
        for knob, line in sorted(knobs.items()):
            if knob not in fields:
                out.append(
                    Diagnostic(
                        doc_path, line, rule_id,
                        f"OPERATIONS.md documents {flag} knob {knob!r} "
                        f"but {cls} has no such field — stale docs or a "
                        f"renamed field",
                        context=f"{flag}.{knob}",
                    )
                )
    # 2. documented flags must exist somewhere
    all_flags: Set[str] = set()
    for flags in flags_by_cli.values():
        all_flags |= flags
    for flag, line in sorted(doc_flags.items()):
        if flag not in all_flags and flag not in OVERRIDE_FLAGS:
            out.append(
                Diagnostic(
                    doc_path, line, rule_id,
                    f"OPERATIONS.md mentions {flag} but no entrypoint "
                    f"defines it — broken runbook command",
                    context=flag,
                )
            )
    # 3. operator-facing CLI flags must be documented
    for cli in OPERATOR_CLIS:
        for flag in sorted(flags_by_cli.get(cli, ())):
            if flag not in doc_flags:
                out.append(
                    Diagnostic(
                        cli, 0, rule_id,
                        f"{flag} is defined by {cli} but never mentioned "
                        f"in OPERATIONS.md — operators cannot discover "
                        f"it; add it to the topology/debugging sections "
                        f"or the CLI flag table",
                        context=flag,
                    )
                )
    return out


class ConfigCliDriftRule(Rule):
    id = "config-drift"
    summary = (
        "override-reachable config fields and CLI flags match the "
        "OPERATIONS.md tables"
    )

    def paths(self) -> Iterable[str]:
        return (CONFIG_PY, OPERATIONS_MD) + ALL_CLIS

    def check(self, files: Dict[str, FileCtx]) -> List[Diagnostic]:
        cfg = files.get(CONFIG_PY)
        doc = files.get(OPERATIONS_MD)
        if cfg is None or doc is None:
            return []
        flags_by_cli = {
            rel: cli_flags(files[rel].source)
            for rel in ALL_CLIS
            if rel in files
        }
        return drift_findings(
            dataclass_fields(cfg.source),
            flags_by_cli,
            doc.source,
            self.id,
        )
