"""``reference/policy_ref.py`` against the program's ``Policy`` at a tiny
width on the CPU: training and inference variants, step and sequence mode,
with mid-chunk resets."""

import dataclasses
import pathlib

import jax
import numpy as np
import pytest

from benchmark.harness import compare, obs as obs_mod
from benchmark.reference import policy_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import init_params
from dotaclient_tpu.models.policy import Policy

HIDDEN = 32


def tiny(value_head: bool):
    cfg = default_config()
    model = dataclasses.replace(cfg.model, hidden_dim=HIDDEN)
    rc = {
        "model": dataclasses.asdict(model),
        "obs": dataclasses.asdict(cfg.obs),
        "actions": dataclasses.asdict(cfg.actions),
    }
    policy = Policy(model, cfg.obs, cfg.actions, value_head=value_head)
    params = init_params(policy, jax.random.PRNGKey(7))
    return policy, params, rc


def test_reference_imports_nothing_from_the_models():
    text = pathlib.Path(policy_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


@pytest.mark.parametrize("value_head", [True, False], ids=["training", "inference"])
def test_policy_agrees_with_reference(value_head):
    policy, params, rc = tiny(value_head)
    report = compare.policy_agreement(policy, params, rc, seed=3, lanes=6, steps=5)
    assert report["ok"], report
    # float32 against float32 on one backend: rounding order only
    assert max(report["exact_sequence"], report["exact_step"]) < 1e-5
    assert ("head_value" in params["params"]) == value_head


def test_resets_matter_and_are_where_the_program_puts_them():
    """Dropping the resets, or moving them one step, must show: otherwise
    the comparison could not catch a wrong reset."""
    policy, params, rc = tiny(True)
    rng = np.random.default_rng(0)
    lanes, steps = 4, 6
    obs = obs_mod.batch_of(rc, rng, lanes, steps)
    carry = tuple((rng.normal(size=(lanes, HIDDEN)) * 0.5).astype(np.float32) for _ in range(2))
    dones = np.zeros((lanes, steps), np.float32)
    dones[:, 2] = 1.0
    exact = policy.clone(model=dataclasses.replace(policy.model, dtype="float32"))
    got = exact.apply(params, obs, carry, dones, method="sequence")
    want = policy_ref.sequence(params, obs, carry, dones)
    none = policy_ref.sequence(params, obs, carry, None)
    shifted = policy_ref.sequence(params, obs, carry, np.roll(dones, 1, axis=1))
    assert policy_ref.max_abs_diff(got[0], want[0]) < 1e-5
    assert policy_ref.max_abs_diff(got[0], none[0]) > 1e-3
    assert policy_ref.max_abs_diff(got[0], shifted[0]) > 1e-3
    # before the reset every variant agrees
    first = {k: v[:, :3] for k, v in got[0].items()}
    assert policy_ref.max_abs_diff(first, {k: v[:, :3] for k, v in none[0].items()}) < 1e-5


def test_a_lower_precision_than_stated_fails():
    """A float32-stated policy computed in bfloat16 exceeds the exact
    tolerance (so the tolerance is tight enough to catch it)."""
    policy, params, rc = tiny(True)
    rc = {**rc, "model": {**rc["model"], "dtype": "float32"}}
    report = compare.policy_agreement(policy, params, rc, seed=3, lanes=6, steps=5)
    assert report["tol_stated"] == compare.TOL_EXACT
    assert report["stated_sequence"] > compare.TOL_EXACT and not report["ok"]


def test_a_nan_anywhere_is_not_agreement():
    good = {"a": np.zeros(3, np.float32), "b": np.zeros(3, np.float32)}
    bad = {"a": np.zeros(3, np.float32), "b": np.array([0, np.nan, 0], np.float32)}
    assert np.isnan(policy_ref.max_abs_diff(good, bad))
    assert np.isnan(policy_ref.max_abs_diff(bad, good))
    assert not (policy_ref.max_abs_diff(good, bad) <= compare.TOL_EXACT)
