"""telemetry-drift pass: code, schema tiers, and docs agree on every key.

Three places claim to know the telemetry key set: the code that emits it
(``.counter("...")`` / ``.gauge("...")`` / ``.span("...")`` /
``.timer("span/...")`` sites), the hand-maintained tier lists in
``scripts/check_telemetry_schema.py`` (the CI contract), and the
docs/ARCHITECTURE.md "Observability" tables (the operator contract). They
drift independently: a renamed counter silently orphans its runbook row, a
documented key that was never wired ships a false promise, and the schema
checker only notices keys it already knows about.

This pass extracts all three sets statically and fails on:

* **documented-but-never-emitted** — a key in a schema tier list (or in
  ARCHITECTURE.md) with no emission site in the package;
* **emitted-but-undocumented** — an emission site whose key appears
  nowhere in ARCHITECTURE.md (span stages may be documented bare, e.g.
  ``actor/collect``, or rooted, ``span/actor/collect``);
* **unresolvable emission** — a key built from an expression the
  extractor cannot expand (see below), which would silently escape both
  checks.

Extraction handles the idioms the codebase actually uses: literal
strings; ``for key in ("a", "b"): ....gauge(key)`` eager-creation loops
(the loop literals are expanded); and f-string keys whose (prefix,
suffix) pair is declared in ``DYNAMIC_KEY_EXPANSIONS`` (e.g.
``f"snapshot/{kind}_coalesced"``). Anything else flags — add the
expansion or use a literal. Doc keys support ``{a,b,c}`` brace expansion
and ``*``/``<var>`` wildcards (wildcards document families and satisfy
emitted-key lookups; they are not themselves required to be emitted).

``utils/telemetry.py`` (the registry mechanism — its internal key
composition is not an emission) is excluded from extraction.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from dotaclient_tpu.lint.core import (
    Diagnostic,
    FileCtx,
    Rule,
    package_py_files,
)

ARCHITECTURE_MD = "docs/ARCHITECTURE.md"
SCHEMA_SCRIPT = "scripts/check_telemetry_schema.py"

# The registry mechanism itself: composes keys generically, emits nothing.
EXCLUDED_FILES = ("dotaclient_tpu/utils/telemetry.py",)

_EMIT_METHODS = ("counter", "gauge", "timer", "span")

# Declared expansions for f-string keys: (constant prefix, constant
# suffix) → the values the formatted hole takes. Keep in sync with the
# emitting site's comment.
_INSTRUMENTED_PROGRAMS = (
    # every instrument_jit(..., name) site in the package (ISSUE 12):
    # learner, buffer, and serve jit entry points. A NEW instrumented
    # program must be added here (its per-program compile keys are
    # f-strings in utils/tracing.py) and is covered by the
    # `compile/<program>/...` wildcard rows in ARCHITECTURE.md.
    "train_step", "epoch_step", "fused_step", "minibatch_gather",
    "snap_copy", "buffer_scatter", "buffer_scatter_dev", "buffer_gather",
    "serve_dispatch", "advantage_pass",
)

DYNAMIC_KEY_EXPANSIONS: Dict[Tuple[str, str], Tuple[str, ...]] = {
    # train/snapshot.py: one coalesce counter per job slot kind (_KINDS)
    ("snapshot/", "_coalesced"): ("publish", "checkpoint", "metrics"),
    # utils/tracing.py InstrumentedJit: per-program compile accounting
    ("compile/", "/compiles_total"): _INSTRUMENTED_PROGRAMS,
    ("compile/", "/retraces_total"): _INSTRUMENTED_PROGRAMS,
    ("compile/", "/last_compile_s"): _INSTRUMENTED_PROGRAMS,
    # utils/fleet.py FleetAggregator rollups: fleet/agg/<metric>/<stat>
    # gauges across live peers — keep in sync with fleet.AGG_SOURCES ×
    # AGG_STATS and the FLEET_KEYS schema tier
    ("fleet/agg/", ""): (
        "weight_staleness/min", "weight_staleness/max",
        "weight_staleness/mean",
        "env_fps/min", "env_fps/max", "env_fps/mean",
        "reconnects/min", "reconnects/max", "reconnects/mean",
        "corrupt_frames/min", "corrupt_frames/max", "corrupt_frames/mean",
        "ship_wait/min", "ship_wait/max", "ship_wait/mean",
    ),
    # utils/fleet.py per-peer mirror keys: fleet/<peer>/<shipped metric>
    # (peer labels are runtime values — representative members here; the
    # family is documented as the `fleet/<peer>/*` wildcard row)
    ("fleet/", ""): (
        "a0/actor/env_steps", "a0/env_fps",
    ),
    # serve/router.py per-backend session gauges (ISSUE 19): backend
    # indices are runtime values — representative members; documented as
    # the `router/backend/<i>/sessions` wildcard row
    ("router/backend/", "/sessions"): ("0", "1"),
    # train/learner.py: a looped core's exit mass per loop step
    # (model.loop_steps is a runtime value: representative members;
    # documented as the `looplm/exit_mass/<r>` wildcard row)
    ("looplm/exit_mass/", ""): ("0", "1"),
    # train/learner.py: a block-decoding core's entropy per pass
    # (model.diffusion_steps is a runtime value: representative members;
    # documented as the `diffusion/stage_entropy/<s>` wildcard row)
    ("diffusion/stage_entropy/", ""): ("1", "2", "3"),
    # Outcome attribution plane (ISSUE 15; dotaclient_tpu/outcome/).
    # Keep the value tuples in sync with outcome.records BUCKETS / SIDES
    # / REWARD_TERMS / N_LEN_BUCKETS and the OUTCOME_KEYS schema tier.
    ("outcome/episodes/", ""): (
        "vs_scripted", "vs_league", "vs_selfplay",
    ),
    ("outcome/wins/", ""): (
        "vs_scripted", "vs_league", "vs_selfplay",
    ),
    ("outcome/win_rate/", ""): (
        "vs_scripted", "vs_league", "overall",
    ),
    ("outcome/episodes_side/", ""): ("radiant", "dire"),
    ("outcome/ep_len_hist/", ""): (
        "00", "01", "02", "03", "04", "05",
        "06", "07", "08", "09", "10", "11",
    ),
    ("outcome/reward_sum/", ""): (
        "xp", "gold", "hp", "enemy_hp", "last_hits", "denies", "kills",
        "deaths", "tower_damage", "own_tower", "win",
    ),
    ("outcome/reward/", ""): (
        "xp", "gold", "hp", "enemy_hp", "last_hits", "denies", "kills",
        "deaths", "tower_damage", "own_tower", "win",
    ),
}

# Token shape of a telemetry key in backticked doc text: slash-separated
# lowercase segments, optional {a,b}/<var>/* holes; no dots (dots mean a
# file path or config field, not a key).
_DOC_KEY_RE = re.compile(
    r"^[a-z][a-z0-9_]*(?:/[a-z0-9_{},<>*]+)+$"
)

# Namespaces telemetry keys live in. Doc tokens outside these are
# key-shaped but not keys (rollout leaf names like `obs/hero_id`,
# `carry0/*`) — never treated as documented-telemetry claims. A NEW
# namespace must be added here when its first key is minted.
KEY_PREFIXES = (
    "actor/", "advantage/", "alerts/", "buffer/", "checkpoint/",
    "compile/", "diffusion/", "faults/", "fleet/", "fused/", "health/", "league/",
    "kda/", "learner/", "looplm/", "mem/", "mesh/", "moe/", "outcome/", "router/", "sdar/", "serve/",
    "shm/", "shortconv/", "snapshot/", "span/", "startup/", "trace/", "transport/", "util/",
)
# single-line inline code only: multi-line matches would mispair across
# ``` fence lines (odd backtick count flips pairing for the whole doc)
_BACKTICK_RE = re.compile(r"`([^`\n]+)`")


# -- emitted-key extraction -------------------------------------------------


def _loop_literal_bindings(func: ast.AST) -> Dict[int, Dict[str, List[str]]]:
    """For every ``for NAME in (<str literals>):`` in ``func``, map the
    loop body's line span to {NAME: literals} so a ``.gauge(NAME)`` call
    inside resolves."""
    out: Dict[int, Dict[str, List[str]]] = {}
    for node in ast.walk(func):
        if not isinstance(node, ast.For):
            continue
        if not isinstance(node.target, ast.Name):
            continue
        lits = _str_literals(node.iter)
        if lits is None:
            continue
        for line in range(node.lineno, (node.end_lineno or node.lineno) + 1):
            out.setdefault(line, {})[node.target.id] = lits
    return out


def _str_literals(node: ast.AST) -> Optional[List[str]]:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return out
    return None


def extract_emitted(
    files: Dict[str, FileCtx],
) -> Tuple[Set[str], List[Tuple[str, int, str]], List[Diagnostic]]:
    """→ (emitted keys, [(key, line, path)] sites, unresolvable-site
    diagnostics). Span/timer keys are normalized to ``span/<stage>``."""
    keys: Set[str] = set()
    sites: List[Tuple[str, int, str]] = []
    problems: List[Diagnostic] = []
    for rel in sorted(files):
        ctx = files[rel]
        if ctx.tree is None or rel in EXCLUDED_FILES:
            continue
        if not rel.startswith("dotaclient_tpu/"):
            continue
        loop_bindings = _loop_literal_bindings(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute) or fn.attr not in _EMIT_METHODS:
                continue
            if not node.args:
                continue
            arg = node.args[0]
            resolved = _resolve_key_arg(arg, node.lineno, loop_bindings)
            if resolved is None:
                problems.append(
                    Diagnostic(
                        rel,
                        node.lineno,
                        "telemetry-drift",
                        f".{fn.attr}(...) key is not statically "
                        f"resolvable — use a literal, the "
                        f"for-over-literals idiom, or declare the "
                        f"f-string in DYNAMIC_KEY_EXPANSIONS "
                        f"(lint/telemetry_drift.py)",
                    )
                )
                continue
            for key in resolved:
                if fn.attr == "span":
                    key = f"span/{key}"
                keys.add(key)
                sites.append((key, node.lineno, rel))
    return keys, sites, problems


def _resolve_key_arg(
    arg: ast.AST, line: int, loop_bindings: Dict[int, Dict[str, List[str]]]
) -> Optional[List[str]]:
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [arg.value]
    if isinstance(arg, ast.Name):
        lits = loop_bindings.get(line, {}).get(arg.id)
        if lits is not None:
            return lits
        return None
    if isinstance(arg, ast.JoinedStr):
        prefix = suffix = ""
        holes = 0
        for part in arg.values:
            if isinstance(part, ast.Constant):
                if holes == 0:
                    prefix += str(part.value)
                else:
                    suffix += str(part.value)
            else:
                holes += 1
        if holes == 1:
            values = DYNAMIC_KEY_EXPANSIONS.get((prefix, suffix))
            if values is not None:
                return [f"{prefix}{v}{suffix}" for v in values]
        return None
    return None


# -- documented-key extraction ----------------------------------------------


def extract_doc_keys(doc_text: str) -> Tuple[Set[str], List[re.Pattern]]:
    """Backticked key tokens in doc text → (exact keys, wildcard
    patterns). ``{a,b}`` expands; ``*`` and ``<var>`` become wildcards."""
    exact: Set[str] = set()
    patterns: List[re.Pattern] = []
    for m in _BACKTICK_RE.finditer(doc_text):
        token = m.group(1).strip()
        if not _DOC_KEY_RE.match(token):
            continue
        if not token.startswith(KEY_PREFIXES):
            continue
        for expanded in _expand_braces(token):
            if "*" in expanded or "<" in expanded:
                rx = re.escape(expanded)
                rx = rx.replace(r"\*", r"[a-z0-9_/]+")
                rx = re.sub(r"<[a-z0-9_\\]+>", r"[a-z0-9_]+", rx)
                patterns.append(re.compile(f"^{rx}$"))
            else:
                exact.add(expanded)
    return exact, patterns


def _expand_braces(token: str) -> List[str]:
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    head, tail = token[: m.start()], token[m.end():]
    out: List[str] = []
    for alt in m.group(1).split(","):
        out.extend(_expand_braces(head + alt.strip() + tail))
    return out


def _documented(
    key: str, exact: Set[str], patterns: List[re.Pattern]
) -> bool:
    candidates = [key]
    if key.startswith("span/"):
        candidates.append(key[len("span/"):])  # stages documented bare
    for c in candidates:
        if c in exact or any(p.match(c) for p in patterns):
            return True
    return False


# -- schema tier lists ------------------------------------------------------


def extract_schema_tiers(script_source: str) -> Dict[str, List[str]]:
    """Module-level ``*_KEYS``/``REQUIRED_KEYS`` tuple assignments of the
    schema checker, literal-evaluated (no import)."""
    tree = ast.parse(script_source)
    tiers: Dict[str, List[str]] = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        if not (target.id.endswith("_KEYS") or target.id == "REQUIRED_KEYS"):
            continue
        try:
            value = ast.literal_eval(node.value)
        except ValueError:
            continue
        if isinstance(value, (tuple, list)):
            tiers[target.id] = [v for v in value if isinstance(v, str)]
    return tiers


_TIMER_LEAVES = ("count", "total_s", "last_s", "mean_s", "ema_s", "p95_s")


def _tier_key_emitted(key: str, emitted: Set[str]) -> bool:
    if key in emitted:
        return True
    # span-leaf form: span/<stage>/<leaf> is emitted iff its root span is
    parts = key.split("/")
    if parts[0] == "span" and parts[-1] in _TIMER_LEAVES:
        return "/".join(parts[:-1]) in emitted
    return False


# -- the pass ---------------------------------------------------------------


def drift_findings(
    emitted: Set[str],
    sites: List[Tuple[str, int, str]],
    doc_text: str,
    tiers: Dict[str, List[str]],
    rule_id: str = "telemetry-drift",
    doc_path: str = ARCHITECTURE_MD,
    schema_path: str = SCHEMA_SCRIPT,
) -> List[Diagnostic]:
    """Pure cross-check (unit-testable: feed synthetic inputs)."""
    out: List[Diagnostic] = []
    exact, patterns = extract_doc_keys(doc_text)
    # 1. schema tiers: documented-but-never-emitted (the CI contract
    #    promises presence the code cannot deliver)
    for tier, keys in sorted(tiers.items()):
        for key in keys:
            if not _tier_key_emitted(key, emitted):
                out.append(
                    Diagnostic(
                        schema_path,
                        0,
                        rule_id,
                        f"{key!r} is required by schema tier {tier} but "
                        f"no emission site exists in the package — the "
                        f"tier would fail every run; fix the emitter or "
                        f"the tier list",
                        context=key,
                    )
                )
    # 2. ARCHITECTURE.md: documented-but-never-emitted
    for key in sorted(exact):
        if not (key in emitted or f"span/{key}" in emitted):
            out.append(
                Diagnostic(
                    doc_path,
                    0,
                    rule_id,
                    f"{key!r} is documented in ARCHITECTURE.md but no "
                    f"emission site exists in the package — stale docs "
                    f"or a renamed key",
                    context=key,
                )
            )
    # 3. emitted-but-undocumented (one finding per key, at its first site)
    first_site: Dict[str, Tuple[int, str]] = {}
    for key, line, rel in sites:
        first_site.setdefault(key, (line, rel))
    for key in sorted(emitted):
        if _documented(key, exact, patterns):
            continue
        line, rel = first_site.get(key, (0, doc_path))
        out.append(
            Diagnostic(
                rel,
                line,
                rule_id,
                f"telemetry key {key!r} is emitted here but absent from "
                f"the docs/ARCHITECTURE.md 'Observability' tables — "
                f"document it (operators grep those tables during "
                f"incidents) or rename/remove the emission",
                context=key,
            )
        )
    return out


class TelemetryDriftRule(Rule):
    id = "telemetry-drift"
    summary = (
        "emitted telemetry keys, schema tier lists, and ARCHITECTURE.md "
        "tables agree"
    )

    def paths(self) -> Iterable[str]:
        return package_py_files() + [ARCHITECTURE_MD, SCHEMA_SCRIPT]

    def check(self, files: Dict[str, FileCtx]) -> List[Diagnostic]:
        emitted, sites, problems = extract_emitted(files)
        doc = files.get(ARCHITECTURE_MD)
        schema = files.get(SCHEMA_SCRIPT)
        tiers = (
            extract_schema_tiers(schema.source) if schema is not None else {}
        )
        out = list(problems)
        out.extend(
            drift_findings(
                emitted,
                sites,
                doc.source if doc is not None else "",
                tiers,
                self.id,
            )
        )
        return out
