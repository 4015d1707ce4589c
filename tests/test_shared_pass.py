"""One pass of the policy a rollout step where both teams play the same
parameters (ISSUE 33): the rollout handed ``opp_params=None`` steps the
learner's and the opponent's lanes together, each team's rings and states
touched where they lie (``lanes.LaneBlocks``, ``lanes.by_lane_block``), and
gives what two passes give. The compiled form of it, for a described v5e, is
in ``tests/test_shared_pass_hlo.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.actor.device_rollout import DeviceActor
from dotaclient_tpu.models.lanes import LaneBlocks
from dotaclient_tpu.models.policy import dummy_obs_batch, init_params, make_policy
from tests.test_fused import tiny_cfg
from tests.test_fused_afmoe import afmoe_cfg
from tests.test_fused_kimilinear import kimilinear_cfg
from tests.test_fused_lfm2moe import lfm2moe_cfg
from tests.test_fused_looplm import looplm_cfg


def lstm_cfg():
    return tiny_cfg(opponent="league")


CORES = {"afmoe": afmoe_cfg, "looplm": looplm_cfg, "kimilinear": kimilinear_cfg, "lfm2moe": lfm2moe_cfg, "lstm": lstm_cfg}


def short_episodes(cfg):
    """Episodes of 6 steps under chunks of 4: the second chunk holds an end."""
    return dataclasses.replace(cfg, env=dataclasses.replace(cfg.env, max_dota_time=1.0))


def assert_trees_agree(got, want, what):
    """Integers and booleans equal; floats to 1e-5 (a product's last bit may
    follow the number of rows in the call, nothing else may differ)."""
    assert jax.tree.structure(got) == jax.tree.structure(want), what
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        where = f"{what}{jax.tree_util.keystr(path)}"
        if np.issubdtype(g.dtype, np.floating):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=where)
        else:
            np.testing.assert_array_equal(g, w, err_msg=where)


@pytest.mark.parametrize("core", sorted(CORES))
def test_the_shared_pass_equals_the_two_passes(core):
    """``_rollout_impl(params, state, None)`` against ``(params, state,
    params)`` from one actor state over three chunks with an episode end
    inside the second: the same actions, episode ends, counters and games;
    log-probabilities, rings, states and histories to 1e-5."""
    cfg = short_episodes(CORES[core]())
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = init_params(policy, jax.random.PRNGKey(5))
    actor = DeviceActor(cfg, policy, seed=3)
    assert actor.one_pass_when_live and actor.state.opp_carry is not None
    one = jax.jit(lambda p, s: actor._rollout_impl(p, s, None))
    two = jax.jit(lambda p, s: actor._rollout_impl(p, s, p))
    shared = apart = actor.state
    ends_inside = False
    for n in range(3):
        shared, chunk_s, stats_s = one(params, shared)
        apart, chunk_a, stats_a = two(params, apart)
        dones = np.asarray(chunk_a["dones"])
        ends_inside |= bool(dones[:, :-1].any()) and n > 0
        assert_trees_agree(chunk_s, chunk_a, f"chunk {n}: ")
        assert_trees_agree(stats_s, stats_a, f"stats {n}: ")
        assert_trees_agree(shared, apart, f"actor state after chunk {n}: ")
    assert ends_inside
    # the carries moved, both teams', and not in step with each other
    for c in (apart.carry, apart.opp_carry):
        assert any(float(jnp.abs(x.astype(jnp.float32)).max()) > 0 for x in jax.tree.leaves(c))
    big = [(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(apart.carry), jax.tree.leaves(apart.opp_carry))]
    assert any(a.shape == b.shape and np.abs(a - b).max() > 1e-3 for a, b in big if a.dtype.kind == "f")


@pytest.mark.parametrize("core", sorted(CORES))
def test_a_lane_set_s_carry_is_its_own_in_whatever_order_the_sets_come(core):
    """``Policy.step`` over two lane sets of different sizes in one call: each
    set's new carry and logits are what a step of that set alone gives, and
    with the sets handed over in the other order the leaves swap with them."""
    cfg = CORES[core]()
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = init_params(policy, jax.random.PRNGKey(7))
    rng = np.random.default_rng(0)

    def obs_of(lanes):
        obs = dummy_obs_batch(lanes, cfg.obs, cfg.actions)
        return {
            **obs,
            "units": jnp.asarray(rng.normal(size=obs["units"].shape), jnp.float32),
            "unit_mask": jnp.asarray(rng.random(obs["unit_mask"].shape) < 0.7),
            "globals": jnp.asarray(rng.normal(size=obs["globals"].shape), jnp.float32),
        }

    step = jax.jit(lambda o, c: policy.apply(params, o, c, method="step"))

    @jax.jit
    def both(oa, ca, ob, cb):
        logits, value, carries = policy.apply(
            params, jax.tree.map(lambda x, y: jnp.concatenate([x, y]), oa, ob), LaneBlocks((ca, cb)), method="step"
        )
        return logits, value, tuple(carries)     # a ``LaneBlocks`` does not leave a ``jit``

    a, b = policy.initial_state(3), policy.initial_state(5)
    for n in range(6):      # past the toy convolution's taps, with a reset on the way
        obs_a, obs_b = obs_of(3), obs_of(5)
        logits_a, _, a1 = step(obs_a, a)
        logits_b, _, b1 = step(obs_b, b)
        logits_ab, _, (a2, b2) = both(obs_a, a, obs_b, b)
        logits_ba, _, (b3, a3) = both(obs_b, b, obs_a, a)
        assert_trees_agree(jax.tree.map(lambda x: x[:3], logits_ab), logits_a, f"step {n}, logits of the first set: ")
        assert_trees_agree(jax.tree.map(lambda x: x[3:], logits_ab), logits_b, f"step {n}, logits of the second set: ")
        assert_trees_agree(jax.tree.map(lambda x: x[5:], logits_ba), logits_a, f"step {n}, swapped: ")
        for got, want, what in ((a2, a1, "first"), (b2, b1, "second"), (a3, a1, "first, swapped"), (b3, b1, "second, swapped")):
            assert_trees_agree(got, want, f"step {n}, carry of the {what} set: ")
        a, b = a1, b1
        if n == 3:
            a = policy.reset_carry(a, jnp.asarray([1.0, 0.0, 1.0]))
    assert jax.tree.leaves(a)[0].shape[0] == 3 and jax.tree.leaves(b)[0].shape[0] == 5


def test_a_dispatch_of_several_iterations_keeps_its_opponent_and_two_passes(monkeypatch):
    """``steps_per_dispatch`` > 1: a live opponent is the parameters the
    dispatch STARTED from, for all its iterations, in the donated live program
    as in the undonated one. From the second iteration on they are not the
    learner's, so no pass is shared: ``live_shares_pass`` is false, and the
    live program gives what the frozen program gives when handed a copy of
    the starting parameters."""
    from dotaclient_tpu.parallel import make_mesh
    from dotaclient_tpu.train import fused
    from dotaclient_tpu.train.ppo import init_train_state

    monkeypatch.setattr(fused, "DONATE_ABOVE_BYTES", 0)   # toy states as "most of the chip"
    cfg = lstm_cfg()
    mesh = make_mesh(cfg.mesh, devices=jax.devices()[:1])
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    params = init_params(policy, jax.random.PRNGKey(5))
    actor = DeviceActor(cfg, policy, seed=3)
    one = fused.make_fused_step(policy, cfg, mesh, actor)
    assert one.donate and actor.one_pass_when_live and one.live_shares_pass
    several = fused.make_fused_step(policy, dataclasses.replace(cfg, steps_per_dispatch=2), mesh, actor)
    assert several.donate and not several.live_shares_pass

    fresh = lambda: (init_train_state(jax.tree.map(jnp.copy, params), cfg.ppo), jax.tree.map(jnp.copy, actor.state))
    live = several(*fresh())
    frozen = several(*fresh(), jax.tree.map(jnp.copy, params))
    assert set(several._programs) == {"frozen", "live"}
    assert_trees_agree(live, frozen, "live against frozen at the starting parameters: ")
