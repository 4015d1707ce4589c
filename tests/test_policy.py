"""Policy model tests (SURVEY.md §4: mask correctness, LSTM state-carry
equivalence scan-vs-steps, distribution consistency)."""

import collections
import functools
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.envs.lane_sim import LaneSim, TEAM_DIRE, TEAM_RADIANT
from dotaclient_tpu.features import featurize, stack_observations
from dotaclient_tpu.models import (
    distributions as D,
    dummy_obs_batch,
    init_params,
    make_policy,
)
from dotaclient_tpu.protos import dota_pb2 as pb

CFG = RunConfig()
# float32 end-to-end in tests so scan-vs-step comparisons are tight.
MODEL = CFG.model.__class__(dtype="float32")


@pytest.fixture(scope="module")
def policy_and_params():
    policy = make_policy(MODEL, CFG.obs, CFG.actions)
    params = init_params(policy, jax.random.PRNGKey(0))
    # jit once per shape signature; shared across tests (module scope).
    policy.jstep = jax.jit(lambda p, o, c: policy.apply(p, o, c, method="step"))
    policy.jseq = jax.jit(lambda p, o, c: policy.apply(p, o, c, method="sequence"))
    return policy, params


def sim_obs_batch(batch: int, steps: int = 0):
    """Batch of real (featurized) observations from perturbed sims."""
    obs = []
    for i in range(batch):
        cfg = pb.GameConfig(
            seed=i,
            hero_picks=[
                pb.HeroPick(team_id=TEAM_RADIANT, hero_id=1 + i % 3,
                            control_mode=pb.CONTROL_AGENT),
                pb.HeroPick(team_id=TEAM_DIRE, hero_id=1,
                            control_mode=pb.CONTROL_SCRIPTED_EASY),
            ],
        )
        sim = LaneSim(cfg)
        for _ in range(steps + i):
            sim.step({})
        obs.append(featurize(sim.world_state(TEAM_RADIANT), 0, CFG.obs, CFG.actions))
    return {k: jnp.asarray(v) for k, v in stack_observations(obs).items()}


class TestForward:
    def test_step_shapes_and_finiteness(self, policy_and_params):
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits, value, carry = policy.jstep(params, obs, policy.initial_state(4))
        for head, size in CFG.actions.head_sizes.items():
            assert logits[head].shape == (4, size)
            assert np.isfinite(np.asarray(logits[head])).all()
        assert value.shape == (4,)
        assert np.isfinite(np.asarray(value)).all()

    def test_scan_equals_repeated_steps(self, policy_and_params):
        """Sequence mode must reproduce T single steps exactly (the
        truncated-BPTT contract the learner relies on, SURVEY.md §5.7)."""
        policy, params = policy_and_params
        B, T = 4, 5
        rng = np.random.default_rng(0)
        seq = dummy_obs_batch(B, CFG.obs, CFG.actions, time=T)
        seq = dict(seq)
        seq["units"] = jnp.asarray(
            rng.normal(size=seq["units"].shape).astype(np.float32)
        )
        seq["unit_mask"] = jnp.asarray(np.ones(seq["unit_mask"].shape, bool))

        carry = policy.initial_state(B)
        logits_seq, value_seq, final_seq = policy.jseq(params, seq, carry)

        carry_s = policy.initial_state(B)
        step_values = []
        step_type_logits = []
        for t in range(T):
            obs_t = {k: v[:, t] for k, v in seq.items()}
            logits_t, value_t, carry_s = policy.jstep(params, obs_t, carry_s)
            step_values.append(value_t)
            step_type_logits.append(logits_t["action_type"])

        np.testing.assert_allclose(
            np.asarray(value_seq), np.stack([np.asarray(v) for v in step_values], 1),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(logits_seq["action_type"]),
            np.stack([np.asarray(l) for l in step_type_logits], 1),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(final_seq[0]), np.asarray(carry_s[0]), rtol=2e-5, atol=2e-5
        )

    def test_padding_slots_do_not_affect_output(self, policy_and_params):
        """Garbage in masked-out unit slots must be invisible to the model."""
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits_a, value_a, _ = policy.jstep(params, obs, policy.initial_state(4))
        units = np.asarray(obs["units"]).copy()
        mask = np.asarray(obs["unit_mask"])
        units[~mask] = 1e6  # poison the padding
        obs_b = dict(obs)
        obs_b["units"] = jnp.asarray(units)
        logits_b, value_b, _ = policy.jstep(params, obs_b, policy.initial_state(4))
        np.testing.assert_allclose(np.asarray(value_a), np.asarray(value_b), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(logits_a["action_type"]), np.asarray(logits_b["action_type"]), rtol=1e-5
        )


def _take_by_gather(logp, idx):
    """The lookup as it was until ISSUE 27: the reference the
    compare-select-reduce ``D._take`` is held to."""
    return jnp.take_along_axis(
        logp, idx[..., None].astype(jnp.int32), axis=-1
    )[..., 0]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _dist_inputs(lead, seed=0, fully_masked_target=False):
    """Random logits, masks with illegal entries (so the log-softmaxes hold
    ``NEG_INF``-sized values in unselected slots) and sampled actions over
    the leading shape ``lead``."""
    sizes = CFG.actions.head_sizes
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    logits = {
        h: 3.0 * jax.random.normal(ks[i], lead + (n,), jnp.float32)
        for i, (h, n) in enumerate(sizes.items())
    }

    def mask(k, p, n):
        return jax.random.bernoulli(k, p, lead + (n,)).at[..., 0].set(True)

    obs = {
        "mask_action_type": mask(ks[6], 0.7, sizes["action_type"]),
        "mask_target_unit": mask(ks[7], 0.3, sizes["target_unit"]),
        "mask_cast_target": mask(ks[8], 0.2, sizes["target_unit"]),
        "mask_ability": mask(ks[9], 0.5, sizes["ability"]),
    }
    if fully_masked_target:
        obs["mask_target_unit"] = jnp.zeros_like(obs["mask_target_unit"])
        obs["mask_cast_target"] = jnp.zeros_like(obs["mask_cast_target"])
    actions, _ = D.sample(ks[10], logits, obs)
    return logits, obs, actions


class TestDistributions:
    def test_illegal_actions_never_sampled(self, policy_and_params):
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits, _, _ = policy.jstep(params, obs, policy.initial_state(4))
        mask_type = np.asarray(obs["mask_action_type"])
        mask_target = np.asarray(obs["mask_target_unit"])
        mask_cast = np.asarray(obs["mask_cast_target"])
        sample_jit = jax.jit(lambda rng: D.sample(rng, logits, obs)[0])
        for i in range(200):
            actions = sample_jit(jax.random.PRNGKey(i))
            a_type = np.asarray(actions["action_type"])
            target = np.asarray(actions["target_unit"])
            for b in range(4):
                assert mask_type[b, a_type[b]], "illegal action type sampled"
                if a_type[b] == D.A_ATTACK:
                    assert mask_target[b, target[b]]
                elif a_type[b] == D.A_CAST:
                    assert mask_cast[b, target[b]]

    def test_logprob_matches_sample(self, policy_and_params):
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits, _, _ = policy.jstep(params, obs, policy.initial_state(4))
        actions, logp = D.sample(jax.random.PRNGKey(7), logits, obs)
        lp = D.log_prob(logits, obs, actions)
        np.testing.assert_allclose(np.asarray(logp), np.asarray(lp), rtol=1e-5)
        assert (np.asarray(logp) <= 0).all()

    def test_irrelevant_heads_do_not_change_logprob(self, policy_and_params):
        """NOOP's joint log-prob must ignore move/target/ability heads."""
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits, _, _ = policy.jstep(params, obs, policy.initial_state(4))
        actions = {
            "action_type": jnp.zeros((4,), jnp.int32),  # NOOP
            "move_x": jnp.zeros((4,), jnp.int32),
            "move_y": jnp.zeros((4,), jnp.int32),
            "target_unit": jnp.zeros((4,), jnp.int32),
            "ability": jnp.zeros((4,), jnp.int32),
        }
        lp_a = D.log_prob(logits, obs, actions)
        actions2 = dict(actions)
        actions2["move_x"] = jnp.full((4,), 5, jnp.int32)
        actions2["target_unit"] = jnp.full((4,), 3, jnp.int32)
        lp_b = D.log_prob(logits, obs, actions2)
        np.testing.assert_allclose(np.asarray(lp_a), np.asarray(lp_b), rtol=1e-6)

    def test_entropy_nonnegative_and_finite(self, policy_and_params):
        policy, params = policy_and_params
        obs = sim_obs_batch(4)
        logits, _, _ = policy.jstep(params, obs, policy.initial_state(4))
        ent = np.asarray(D.entropy(logits, obs))
        assert np.isfinite(ent).all()
        assert (ent >= 0).all()

    def test_fully_masked_head_stays_finite(self):
        """A head with zero legal entries must not poison logp/entropy."""
        logits = {h: jnp.zeros((2, n)) for h, n in CFG.actions.head_sizes.items()}
        obs = dummy_obs_batch(2, CFG.obs, CFG.actions)
        obs = dict(obs)
        obs["mask_target_unit"] = jnp.zeros_like(obs["mask_target_unit"])  # none legal
        obs["mask_cast_target"] = jnp.zeros_like(obs["mask_cast_target"])
        actions, logp = D.sample(jax.random.PRNGKey(0), logits, obs)
        assert np.isfinite(np.asarray(logp)).all()
        assert np.isfinite(np.asarray(D.entropy(logits, obs))).all()

    # -- ISSUE 27: a chosen action's log-probability is looked up by
    # compare-select-reduce over the head's axis, and equals the gather --

    @pytest.mark.parametrize("lead", ["B", "BT", "vmap"])
    @pytest.mark.parametrize("head", list(CFG.actions.head_sizes))
    def test_take_equals_gather_exactly(self, head, lead):
        """Every in-range index of the head's width, with ``NEG_INF`` in
        unselected slots: bit-equal values (``assert_array_equal``)."""
        k = CFG.actions.head_sizes[head]
        shape = {"B": (3 * k,), "BT": (k, 3), "vmap": (2, k, 2)}[lead]
        n = int(np.prod(shape))
        idx = (jnp.arange(n, dtype=jnp.int32) % k).reshape(shape)
        logp = jax.random.normal(jax.random.PRNGKey(k), shape + (k,), jnp.float32)
        # every slot but the chosen one and its right neighbour is masked
        col = jnp.arange(k)
        keep = (col == idx[..., None]) | (col == (idx[..., None] + 1) % k)
        logp = jnp.where(keep, logp, D.NEG_INF)
        take, ref = D._take, _take_by_gather
        if lead == "vmap":       # as device_rollout.sample_per_game maps it
            take, ref = jax.vmap(take), jax.vmap(ref)
        got, want = take(logp, idx), ref(logp, idx)
        assert got.dtype == want.dtype == jnp.float32
        assert np.isfinite(np.asarray(want)).all()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(          # narrow stored actions too
            np.asarray(take(logp, idx.astype(jnp.int8))), np.asarray(want)
        )

    @pytest.mark.parametrize("case", ["B", "BT", "fully_masked_head"])
    def test_log_prob_and_its_gradient_equal_the_gather_formulation(
        self, case, monkeypatch
    ):
        lead = (6, 5) if case == "BT" else (32,)
        logits, obs, actions = _dist_inputs(
            lead, seed=3, fully_masked_target=case == "fully_masked_head"
        )

        def joint_and_grad():     # jitted anew: traced with the _take of the moment
            def summed(lg):
                lp = D.log_prob(lg, obs, actions)
                return lp.sum(), lp

            (_, lp), g = jax.jit(jax.value_and_grad(summed, has_aux=True))(logits)
            return lp, g

        got_lp, got_g = joint_and_grad()
        monkeypatch.setattr(D, "_take", _take_by_gather)
        want_lp, want_g = joint_and_grad()
        np.testing.assert_array_equal(np.asarray(got_lp), np.asarray(want_lp))
        for h in logits:
            g = np.asarray(got_g[h])
            assert np.isfinite(g).all(), h
            np.testing.assert_array_max_ulp(g, np.asarray(want_g[h]), maxulp=1)
        # the heads that took part have a gradient at all
        assert np.abs(np.asarray(got_g["action_type"])).max() > 0

    def test_sample_equals_the_gather_formulation(self, monkeypatch):
        """Sampling is an argmax over logits plus Gumbel noise and never
        looked anything up: same actions for a key, and the same joint
        log-probability, bit for bit."""
        logits, obs, _ = _dist_inputs((4, 10), seed=5)
        key = jax.random.PRNGKey(11)
        got_a, got_lp = jax.jit(lambda k: D.sample(k, logits, obs))(key)
        monkeypatch.setattr(D, "_take", _take_by_gather)
        want_a, want_lp = jax.jit(lambda k: D.sample(k, logits, obs))(key)
        for h in want_a:
            np.testing.assert_array_equal(np.asarray(got_a[h]), np.asarray(want_a[h]))
        np.testing.assert_array_equal(np.asarray(got_lp), np.asarray(want_lp))

    @pytest.mark.parametrize(
        "heads,plus", [(0, -1), (-1, 0), (-1, -1), (1, 0), (1, 5), (0, 127)],
        ids=["-1", "-K", "-K-1", "K", "K+5", "127"],
    )
    def test_out_of_range_index_is_loud(self, heads, plus):
        """The contract ``_take``'s docstring states: an index outside
        ``[0, K)`` reads ``NaN`` (the gather wrapped ``-K..-1``; the ingest
        door holds actions to int8's range, not to the head's), also in a
        head the action type makes irrelevant, so a corrupt stored action
        still reaches the non-finite-loss latch."""
        k = CFG.actions.head_sizes["ability"]
        bad = heads * k + plus
        logp = jax.nn.log_softmax(jnp.arange(2.0 * k).reshape(2, k))
        got = np.asarray(D._take(logp, jnp.array([1, bad], jnp.int32)))
        assert got[0] == np.asarray(logp)[0, 1] and np.isnan(got[1])
        logits, obs, actions = _dist_inputs((4,), seed=7)
        actions = dict(actions, action_type=jnp.zeros((4,), jnp.int32))  # NOOP
        actions["ability"] = actions["ability"].at[2].set(bad)
        lp = np.asarray(D.log_prob(logits, obs, actions))
        assert np.isnan(lp[2]) and np.isfinite(np.delete(lp, 2)).all()

    @pytest.mark.parametrize("program", ["sample", "log_prob", "grad_log_prob"])
    def test_distribution_compiles_to_no_gather_and_no_scatter(self, program):
        """The gathers must not come back (nor, in the backward pass, their
        scatter-adds): on the TPU they were a fifth of the small cell's step
        (ISSUE 27; PERF.md section 6). The optimised HLO of the jitted
        distribution holds neither."""
        logits, obs, actions = jax.eval_shape(lambda: _dist_inputs((16, 4)))
        if program == "sample":
            fn, args = D.sample, (jax.random.PRNGKey(0), logits, obs)
        elif program == "log_prob":
            fn, args = D.log_prob, (logits, obs, actions)
        else:
            fn = jax.grad(lambda lg, o, a: D.log_prob(lg, o, a).sum())
            args = (logits, obs, actions)
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        kinds = collections.Counter(
            re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(", hlo, re.M)
        )
        assert kinds["select"] > 0              # the text IS the optimised HLO
        found = {k: n for k, n in kinds.items() if "gather" in k or "scatter" in k}
        assert not found, found
