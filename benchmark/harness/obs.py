"""Seeded observations: inputs for the comparison with the plain reference.

``synthetic_obs`` is copied from ``scripts/serve_loadgen.py`` (one plausible
random observation, unbatched leaves, the program's dtypes and shapes) and
``masked_obs`` from ``chip_smoke.py`` (random legality masks with at least
one legal entry each, and some unit slots padded out, so that the masked
pools and heads are exercised). The sizes come from a configuration file's
``run_config``; numpy only.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np

MASKS = ("mask_action_type", "mask_target_unit", "mask_cast_target",
         "mask_ability")


def synthetic_obs(rc: Mapping[str, Mapping[str, Any]], rng: np.random.Generator) -> Dict[str, np.ndarray]:
    obs, act, model = rc["obs"], rc["actions"], rc["model"]
    U = obs["max_units"]
    return {
        "units": rng.normal(size=(U, obs["unit_features"])).astype(np.float32),
        "unit_mask": np.ones((U,), bool),
        "unit_handles": rng.integers(0, U, size=(U,)).astype(np.int32),
        "globals": rng.normal(size=(obs["global_features"],)).astype(np.float32),
        "hero_id": np.asarray(rng.integers(0, model["n_hero_ids"]), np.int32),
        "mask_action_type": np.ones((act["n_action_types"],), bool),
        "mask_target_unit": np.ones((act["max_units"],), bool),
        "mask_cast_target": np.ones((act["max_units"],), bool),
        "mask_ability": np.ones((act["max_abilities"],), bool),
    }


def masked_obs(rc: Mapping[str, Mapping[str, Any]], rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """``synthetic_obs`` with about half of every mask cleared, and some
    unit slots padded out, never all of either."""
    obs = synthetic_obs(rc, rng)
    for key in MASKS + ("unit_mask",):
        mask = rng.random(obs[key].shape) < 0.5
        mask[rng.integers(mask.size)] = True
        obs[key] = mask
    return obs


def batch_of(rc: Mapping[str, Mapping[str, Any]], rng: np.random.Generator, *lead: int) -> Dict[str, np.ndarray]:
    """``masked_obs`` stacked to leading axes ``lead`` (lanes, or lanes and
    steps)."""
    n = int(np.prod(lead))
    rows = [masked_obs(rc, rng) for _ in range(n)]
    return {
        k: np.stack([r[k] for r in rows]).reshape(lead + rows[0][k].shape)
        for k in rows[0]
    }
