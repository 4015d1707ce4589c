"""From a cell's data files to the program's own configuration tree.

The one place the harness touches ``dotaclient_tpu.config``: a
configuration file's ``run_config`` (and a traffic file's, which is applied
after it) is laid over the program's defaults section by section, the lane
count is the configuration's per-chip figure times the cell's chips, and
every seed the program takes is ``--seed``.

A rehearsal (``run.py --rehearse-cpu``) walks the control flow at a tiny
size by one rule for every cell: one game per chip, and no core wider than
256. Nothing it prints is a result.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, Mapping

REHEARSAL_HIDDEN = 256
REHEARSAL_ENVS_PER_CHIP = 1


def merged_run_config(cell: Any, rehearsal: bool) -> Dict[str, Dict[str, Any]]:
    """``run_config`` of the configuration with the traffic mix's laid over
    it, as plain dicts (what ``flops`` and ``obs`` read)."""
    rc = copy.deepcopy(cell.config.get("run_config", {}))
    for section, over in cell.traffic.get("run_config", {}).items():
        rc.setdefault(section, {}).update(over)
    if rehearsal:
        model = rc.setdefault("model", {})
        model["hidden_dim"] = min(
            model.get("hidden_dim", REHEARSAL_HIDDEN), REHEARSAL_HIDDEN
        )
    return rc


def n_envs(cell: Any, rehearsal: bool) -> int:
    per_chip = (
        REHEARSAL_ENVS_PER_CHIP if rehearsal else cell.config["n_envs_per_chip"]
    )
    return int(per_chip) * cell.chips


def build_run_config(
    cell: Any, seed: int, rehearsal: bool,
    top_level: Mapping[str, Any] = (),
):
    """The program's ``RunConfig`` for this cell. ``top_level`` sets fields
    of the tree's root (``steps_per_dispatch``, ``log_every``)."""
    from dotaclient_tpu.config import default_config

    cfg = default_config()
    rc = merged_run_config(cell, rehearsal)
    rc.setdefault("env", {}).update(n_envs=n_envs(cell, rehearsal), seed=seed)
    for section, over in rc.items():
        over = dict(over)
        if "hero_pool" in over:
            over["hero_pool"] = tuple(over["hero_pool"])
        cfg = dataclasses.replace(
            cfg, **{section: dataclasses.replace(getattr(cfg, section), **over)}
        )
    return dataclasses.replace(cfg, seed=seed, **dict(top_level))
