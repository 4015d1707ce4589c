"""Every path a document names exists in the tree.

A deletion that leaves a citation behind (a runbook command for a script
that is gone, a Dockerfile COPY of a removed file) fails here, in the
case of the document that still names it. All text, no JAX.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    "docs/ARCHITECTURE.md",
    "docs/OPERATIONS.md",
    "PARITY.md",
    "scripts/ci_gate.sh",
    "deploy/Dockerfile",
    ".claude/skills/verify/SKILL.md",
)

# a path under one of the repo's directories, not the tail of a longer one
_UNDER_DIR = re.compile(
    r"(?<![\w/.<>-])"
    r"((?:dotaclient_tpu|scripts|tests|benchmark|docs|deploy)/[\w./*-]*)"
)
# a bare `name.py`: a root script, or shorthand for a file of that name
_BARE_PY = re.compile(r"(?<![\w/.*<>-])(\w+\.py)\b")

# named in a document as NOT in git: g++ builds it on first use
_BUILT_ON_FIRST_USE = {"dotaclient_tpu/native/libdota_native.so"}
# the reference project's files, which PARITY.md maps to this repo's
_REFERENCE_PROJECT = {"agent.py", "optimizer.py"}


def _basenames():
    names = set()
    for top in ("dotaclient_tpu", "scripts", "tests", "benchmark", "deploy"):
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    names.update(f for f in os.listdir(ROOT) if f.endswith(".py"))
    return names


def _named_paths(text):
    # the character class stops at ':' (`path.py:123`, `path.py::test`);
    # a sentence's full stop or dash is not part of the path either
    return [m.group(1).rstrip(".-") for m in _UNDER_DIR.finditer(text)]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_path_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = []
    for path in _named_paths(text):
        if path in _BUILT_ON_FIRST_USE:
            continue
        full = os.path.join(ROOT, path)
        found = glob.glob(full) if "*" in path else os.path.exists(full)
        if not found:
            missing.append(path)
    basenames = _basenames()
    for m in _BARE_PY.finditer(text):
        if m.group(1) not in basenames | _REFERENCE_PROJECT:
            missing.append(m.group(1))
    assert not missing, (
        f"{document} names paths that do not exist: {sorted(set(missing))}"
    )
