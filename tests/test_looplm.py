"""The looped core (``models/looplm.py``) against its plain reference
(``benchmark/reference/looplm_ref.py``) at tiny widths on the CPU: hidden 32,
two layers run three times, 4 heads of 8, full_context 24, chunks of T = 4,
float32, seeded weights.

The program runs chunk by chunk (or step by step) through its rings, one
ring for every layer and loop step; the reference takes each lane's whole
history at once. Every loop step's logits and values and the exit gates are
compared. The reference made wrong in one way at a time must DISAGREE: a
comparison that would pass with a loop step skipped, one cache for all loop
steps, untied weights, no norm between loop steps or a last exit that is
not the remainder pins nothing.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import obs as obs_mod
from benchmark.reference import looplm_ref, policy_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import afmoe, distributions as D, init_params, looplm
from dotaclient_tpu.models.policy import Policy, require_carry_stays
from dotaclient_tpu.train.ppo import exit_weighted_loss, ppo_loss
from tests.test_afmoe import _scaled, run_config

B, S, T, R, L = 3, 20, 4, 3, 2
AGREE, DIFFER = 2e-5, 1e-3


# two layers run three times: what a toy looped core switches off of the afmoe layer
SIZES = dict(
    core="looplm", hidden_dim=32, n_layers=L, loop_steps=R, n_heads=4, n_kv_heads=4,
    head_dim=8, full_context=24, rollout_chunk=T, global_attn_every=1, n_dense_layers=0,
    dense_ffn_dim=48, moe_experts=0, attn_qk_norm=False, attn_out_gate=False,
    rope_full_layers=True, mup_enabled=False, rope_theta=1e6, rms_norm_eps=1e-6,
    dtype="float32",
)


def tiny_model(**over):
    return dataclasses.replace(default_config().model, **{**SIZES, **over})


def perturbed(params, seed=11):
    """Seeded weights with every norm scale and the gate's bias moved off
    their initial 1 and 0, so that a test can see them."""
    rng = np.random.default_rng(seed)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(leaf.shape).astype(np.float32))
        if "exit_gate']['bias" in name:
            return leaf + 0.5
        return leaf

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def world():
    cfg = default_config()
    model = tiny_model()
    policy = Policy(model, cfg.obs, cfg.actions)
    params = perturbed(init_params(policy, jax.random.PRNGKey(0)))
    rc = run_config(model)
    rng = np.random.default_rng(0)
    obs = obs_mod.batch_of(rc, rng, B, S)
    dones = np.zeros((B, S), np.float32)
    dones[0, 9] = 1                    # mid-chunk
    dones[1, 13] = dones[1, 14] = 1    # two ends in a row, inside a chunk
    return {"policy": policy, "params": params, "rc": rc, "obs": obs, "dones": dones}


def through_chunks(policy, params, obs, dones, chunk=T):
    """The program, chunk by chunk, as the actor and the learner drive it: a
    reset through the core between chunks, ``dones`` inside them ->
    (logits [R, B, S, n], values [R, B, S], gate logits [B, S, R], carry)."""
    seq = jax.jit(lambda p, o, c, d: policy.apply(p, o, c, d, method="sequence", mutable=["losses"]))
    carry = policy.initial_state(obs["units"].shape[0])
    logits, values, gates = [], [], []
    for c0 in range(0, obs["units"].shape[1], chunk):
        if c0:
            carry = policy.reset_carry(carry, 1.0 - dones[:, c0 - 1])
        (lg, v, carry), sown = seq(
            params, {k: x[:, c0:c0 + chunk] for k, x in obs.items()}, carry,
            dones[:, c0:c0 + chunk],
        )
        logits.append(lg), values.append(v), gates.append(sown["losses"]["core"]["exit_logits"][0])
    cat = lambda xs: jnp.concatenate(xs, axis=2)
    return (
        jax.tree.map(lambda *xs: cat(xs), *logits), cat(values),
        jnp.concatenate(gates, axis=1), carry,
    )


def step_by_step(policy, params, obs, dones):
    """The actor's path: the LAST loop step's logits and value, a step at a time."""
    step = jax.jit(lambda p, o, c: policy.apply(p, o, c, method="step"))
    carry = policy.initial_state(obs["units"].shape[0])
    logits, values = [], []
    for t in range(obs["units"].shape[1]):
        if t:
            carry = policy.reset_carry(carry, 1.0 - dones[:, t - 1])
        lg, v, carry = step(params, {k: x[:, t] for k, x in obs.items()}, carry)
        logits.append(lg), values.append(v)
    stack = lambda xs: jnp.stack(xs, axis=1)
    return jax.tree.map(lambda *xs: stack(xs), *logits), stack(values), carry


def worst(got, want):
    """Largest difference over every loop step's logits and values and the gates."""
    return policy_ref.max_abs_diff(
        {"logits": got[0], "value": got[1], "gates": got[2]},
        {"logits": want[0], "value": want[1], "gates": want[2]},
    )


@pytest.fixture(scope="module")
def program_out(world):
    return through_chunks(world["policy"], world["params"], world["obs"], world["dones"])


@pytest.fixture(scope="module")
def reference_out(world):
    return looplm_ref.history(world["params"], world["obs"], world["dones"], world["rc"]["model"])


# -- step, sequence and reference agree ------------------------------------------


def test_sequence_agrees_with_the_reference_for_every_loop_step_and_gate(world, reference_out, program_out):
    """20 steps in chunks of 4, one lane ends an episode mid-chunk and one
    twice in a row: logits and values of all R loop steps and the R gates."""
    assert program_out[1].shape == (R, B, S) and program_out[2].shape == (B, S, R)
    assert worst(program_out, reference_out) < AGREE
    carry = program_out[3]
    np.testing.assert_array_equal(np.asarray(carry["cursor"]), S % afmoe._cursor_modulus(world["policy"].model))
    np.testing.assert_array_equal(np.asarray(carry["pos"]), [S - 10, S - 15, S])


def test_chunks_of_eight_steps_or_more_take_the_plain_products_and_agree(world, reference_out):
    """The learner's chunks at the cell's sizes (17 steps) go through
    ``afmoe._attend``, a step and the short chunks above through
    ``_attend_few_rows``: both are the reference's attention."""
    assert T < afmoe._MXU_ROWS <= 10
    got = through_chunks(world["policy"], world["params"], world["obs"], world["dones"], chunk=10)
    assert worst(got, reference_out) < AGREE


def test_step_answers_from_the_last_loop_step(world, reference_out):
    logits, values, carry = step_by_step(world["policy"], world["params"], world["obs"], world["dones"])
    last = ({k: v[-1] for k, v in reference_out[0].items()}, reference_out[1][-1])
    assert policy_ref.max_abs_diff({"l": logits, "v": values}, {"l": last[0], "v": last[1]}) < AGREE
    # ... and not from an earlier one
    early = ({k: v[0] for k, v in reference_out[0].items()}, reference_out[1][0])
    assert policy_ref.max_abs_diff({"l": logits, "v": values}, {"l": early[0], "v": early[1]}) > DIFFER
    np.testing.assert_array_equal(np.asarray(carry["pos"]), [S - 10, S - 15, S])


def test_the_loop_steps_differ_and_the_weights_are_one_set(world, program_out):
    """R outputs from ONE layer stack: the parameter tree holds L layers, a
    norm and a gate, whatever R is; the carry holds L x R pairs of rings."""
    core = world["params"]["params"]["core"]
    assert sorted(core) == ["exit_gate", "layer_0", "layer_1", "out_norm"]
    assert sorted(core["layer_0"]["attn"]) == ["wk", "wo", "wq", "wv"]
    values = np.asarray(program_out[1])
    assert np.abs(values[0] - values[1]).max() > DIFFER and np.abs(values[1] - values[2]).max() > DIFFER
    carry = world["policy"].initial_state(B)
    assert len(carry["kv"]) == L * R
    assert all(k.shape == (B, 24, 4 * 8) for pair in carry["kv"] for k in pair)
    assert afmoe.carry_bytes_per_lane(world["policy"].model) == 8 + L * R * 2 * 24 * 32 * 4


def test_reference_imports_nothing_from_the_program():
    import pathlib

    text = pathlib.Path(looplm_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


def test_resets_are_where_the_program_puts_them(world, program_out):
    model = world["rc"]["model"]
    none = looplm_ref.history(world["params"], world["obs"], 0 * world["dones"], model)
    shifted = looplm_ref.history(world["params"], world["obs"], np.roll(world["dones"], 1, axis=1), model)
    assert worst(program_out, none) > DIFFER and worst(program_out, shifted) > DIFFER


# -- the reference made wrong in one way must disagree ----------------------------


@pytest.mark.parametrize("fault", [f for f in looplm_ref.FAULTS if f != "last_exit_not_the_remainder"])
def test_reference_wrong_in_one_way_disagrees(world, program_out, fault):
    wrong = looplm_ref.history(world["params"], world["obs"], world["dones"], world["rc"]["model"], fault=fault)
    assert worst(program_out, wrong) > DIFFER, fault


ABLATIONS = {
    # (change to the reference's sizes or weights, function of the reference patched)
    "rope_on_every_layer": (None, ("rope", lambda x, pos, theta: x)),
    "rope_theta": (lambda model, params: ({**model, "rope_theta": 1e4}, params), None),
    "one_loop_step_fewer": (lambda model, params: ({**model, "loop_steps": R - 1}, params), None),
    "assumed_post_sublayer_norm": (_scaled("post_attn_norm", 2.0), None),
    "assumed_norm_between_loop_steps": (_scaled("out_norm", 2.0), None),
    "assumed_gate_bias": (_scaled("exit_gate']['bias", 0.0), None),
}


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_reference_without_the_mechanism_disagrees(world, program_out, monkeypatch, name):
    change, patch = ABLATIONS[name]
    model, params = world["rc"]["model"], world["params"]
    if change is not None:
        model, params = change(model, params)
    if patch is not None:
        monkeypatch.setattr(looplm_ref, *patch)
    without = looplm_ref.history(params, world["obs"], world["dones"], model)
    if name == "one_loop_step_fewer":
        # the outputs that both have agree; the last loop step's is missing
        got = tuple(jax.tree.map(lambda x: x[:R - 1], program_out[i]) for i in (0, 1)) + (program_out[2][..., :R - 1],)
        assert worst(got, without) < AGREE
        return
    assert worst(program_out, without) > DIFFER, name


# -- the exit distribution ---------------------------------------------------------


@pytest.mark.parametrize("scale", [0.0, 1.0, 30.0])
def test_exit_masses_sum_to_one_and_are_the_reference_s(scale):
    g = scale * jax.random.normal(jax.random.PRNGKey(3), (5, 7, 4))
    p = looplm.exit_distribution(g)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-6)
    assert float(p.min()) >= 0.0
    np.testing.assert_allclose(np.asarray(p), np.asarray(looplm_ref.exit_distribution(g)), atol=1e-6)
    # the last loop step takes the remainder whatever its own gate says
    np.testing.assert_array_equal(np.asarray(looplm.exit_distribution(g.at[..., -1].set(-50.0))), np.asarray(p))
    wrong = looplm_ref.exit_distribution(g, fault="last_exit_not_the_remainder")
    assert float(jnp.abs(wrong.sum(-1) - 1.0).max()) > 1e-3 or scale == 30.0


def test_one_loop_step_is_one_exit_of_mass_one():
    np.testing.assert_array_equal(np.asarray(looplm.exit_distribution(jnp.asarray([[0.3], [-4.0]]))), [[1.0], [1.0]])


# -- the carry: reset, chunk-start view, what must fit ------------------------------


def test_reset_through_the_core_touches_no_cache_leaf(world, program_out):
    policy, carry = world["policy"], program_out[3]
    keep = jnp.asarray([1.0, 0.0, 1.0])
    after = policy.reset_carry(carry, keep)
    for before_l, after_l in zip(carry["kv"], after["kv"]):
        assert before_l[0] is after_l[0] and before_l[1] is after_l[1]
    assert after["cursor"] is carry["cursor"]
    np.testing.assert_array_equal(np.asarray(after["pos"]), np.asarray(carry["pos"]) * np.asarray([1, 0, 1]))
    # under jit: the rings leave as they came, no operation reads them
    jaxpr = jax.make_jaxpr(policy.reset_carry)(carry, keep).jaxpr
    rings = {v for v in jaxpr.invars if len(v.aval.shape) == 3}
    assert len(rings) == 2 * L * R and sum(v in rings for v in jaxpr.outvars) == 2 * L * R
    ring_ids = {id(v) for v in rings}
    assert not any(id(v) in ring_ids for eqn in jaxpr.eqns for v in eqn.invars)


def test_chunk_start_view_is_the_start_without_a_copy(world):
    """What the learner is handed: the start's counters beside the END's
    rings. A chunk read from it equals the chunk read from the real start,
    mid-chunk reset included; the rings are the end's own buffers."""
    policy, params = world["policy"], world["params"]
    obs, dones = world["obs"], world["dones"]
    start = through_chunks(policy, params, {k: v[:, :12] for k, v in obs.items()}, dones[:, :12])[3]
    start = policy.reset_carry(start, 1.0 - dones[:, 11])
    chunk = {k: v[:, 12:16] for k, v in obs.items()}                 # lane 1 ends at 13 and 14
    seq = jax.jit(lambda c: policy.apply(params, chunk, c, dones[:, 12:16], method="sequence"))
    lg, v, end = seq(start)
    view = policy.chunk_start_carry(start, end)
    assert all(a is b for a, b in zip(jax.tree.leaves(view["kv"]), jax.tree.leaves(end["kv"])))
    lg2, v2, _ = seq(view)
    assert policy_ref.max_abs_diff({"l": lg2, "v": v2}, {"l": lg, "v": v}) < 1e-6


def test_episode_must_fit_the_ring_and_the_refusals_name_the_core():
    model = tiny_model()
    afmoe.require_episode_fits(model, episode_steps=20, rollout_len=T)
    with pytest.raises(ValueError, match="'looplm'.*full_context"):
        afmoe.require_episode_fits(model, episode_steps=21, rollout_len=T)
    with pytest.raises(ValueError, match="'looplm'.*rollout_chunk"):
        afmoe.require_episode_fits(model, episode_steps=10, rollout_len=T + 1)
    with pytest.raises(ValueError, match="core 'looplm' carries 36,872 bytes"):
        require_carry_stays(model, "actor mode 'device'")
    require_carry_stays(default_config().model, "actor mode 'device'")       # the LSTM's rows travel
    assert model.carry_stays_on_chip and tiny_model(core="afmoe").carry_stays_on_chip
    assert not default_config().model.carry_stays_on_chip


@pytest.mark.parametrize("over", [
    {"n_dense_layers": 1}, {"global_attn_every": 2}, {"moe_experts": 8, "experts_per_token": 2}, {"loop_steps": 0},
])
def test_a_layer_that_is_not_full_attention_and_dense_is_refused(over):
    cfg = default_config()
    policy = Policy(tiny_model(**over), cfg.obs, cfg.actions)
    with pytest.raises(ValueError, match="looplm"):
        init_params(policy, jax.random.PRNGKey(0))


# -- one PPO step: the exit-weighted loss and its gradients -------------------------


def _ppo_case():
    """A learner's batch: the LAST chunk of a history (carry0 = the rings the
    earlier chunks left, T + 1 observations)."""
    cfg = default_config()
    ppo = dataclasses.replace(cfg.ppo, rollout_len=T, exit_entropy_coef=0.05)
    model_cfg = tiny_model()
    policy = Policy(model_cfg, cfg.obs, cfg.actions)
    params = perturbed(init_params(policy, jax.random.PRNGKey(1)))
    rc = run_config(model_cfg)
    rng = np.random.default_rng(5)
    lanes, P = 2, 12
    hist = P + T + 1
    obs = obs_mod.batch_of(rc, rng, lanes, hist)
    dones = np.zeros((lanes, hist), np.float32)
    dones[0, 9] = dones[1, P + 1] = 1            # one in the data, one inside the chunk
    carry0 = through_chunks(policy, params, {k: v[:, :P] for k, v in obs.items()}, dones[:, :P])[3]
    heads = cfg.actions.head_sizes
    batch = {
        "obs": {k: v[:, P:] for k, v in obs.items()},
        "actions": {h: rng.integers(0, n, size=(lanes, T)).astype(np.int32) for h, n in heads.items()},
        "behavior_logp": (-3.0 + 0.1 * rng.standard_normal((lanes, T))).astype(np.float32),
        "rewards": rng.standard_normal((lanes, T)).astype(np.float32),
        "dones": dones[:, P:P + T],
        "valid": np.ones((lanes, T), np.float32),
        "carry0": carry0,
    }
    batch["valid"][1, -1] = 0.0
    # legal actions only, so that no log-probability is a mask's -1e9
    for h, mask in (("action_type", "mask_action_type"), ("target_unit", "mask_target_unit"), ("ability", "mask_ability")):
        batch["actions"][h] = np.asarray(batch["obs"][mask][:, :T]).argmax(axis=-1).astype(np.int32)
    return ppo, policy, params, rc["model"], obs, dones, batch


def _lpe(logits, o, actions):
    return D.log_prob(logits, o, actions), D.entropy(logits, o)


KNOBS = ("gamma", "gae_lambda", "clip_eps", "entropy_coef", "value_coef", "exit_entropy_coef")


@pytest.fixture(scope="module")
def ppo_case():
    ppo, policy, params, model, obs, dones, batch = _ppo_case()
    got = jax.jit(jax.value_and_grad(lambda p: ppo_loss(policy, p, batch, ppo), has_aux=True))(params)
    return ppo, policy, params, model, obs, dones, batch, got


def test_exit_weighted_loss_and_gradients_agree_with_the_reference(ppo_case):
    """The learner's pass (``ppo_loss`` hands a looped core to
    ``exit_weighted_loss``) against ``jax.grad`` of the reference's loss over
    the whole history with the earlier steps as data. Every weight's gradient
    is the sum over its R uses: the reference has no other way to make it."""
    ppo, policy, params, model, obs, dones, batch, ((got_loss, metrics), got_grads) = ppo_case
    knobs = {k: getattr(ppo, k) for k in KNOBS}
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: looplm_ref.ppo_loss(p, obs, dones, batch, model, knobs, _lpe)
    ))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * max(1.0, abs(float(want_loss)))
    flat_got = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_want = jax.tree.leaves(want_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, jax.tree_util.keystr(path)
    tied = got_grads["params"]["core"]["layer_0"]["attn"]["wq"]["kernel"]
    gate = got_grads["params"]["core"]["exit_gate"]["kernel"]
    assert float(jnp.abs(tied).max()) > 1e-6 and float(jnp.abs(gate).max()) > 1e-6
    # the step's metrics: exit masses that sum to 1, the passes made, the entropy
    mass = [float(metrics[f"looplm_exit_mass_{r}"]) for r in range(R)]
    assert abs(sum(mass) - 1.0) < 1e-5 and min(mass) > 0.0
    assert float(metrics["looplm_loop_passes"]) == R
    assert abs(float(metrics["looplm_expected_exit_step"]) - sum((r + 1) * m for r, m in enumerate(mass))) < 1e-5
    assert 0.0 < float(metrics["looplm_exit_entropy"]) <= np.log(R) + 1e-6


@pytest.mark.parametrize("fault", looplm_ref.FAULTS)
def test_the_loss_of_a_reference_wrong_in_one_way_disagrees(ppo_case, fault):
    ppo, policy, params, model, obs, dones, batch, ((got_loss, _), got_grads) = ppo_case
    knobs = {k: getattr(ppo, k) for k in KNOBS}
    wrong_loss, wrong_grads = jax.jit(jax.value_and_grad(
        lambda p: looplm_ref.ppo_loss(p, obs, dones, batch, model, knobs, _lpe, fault=fault)
    ))(params)
    flat_want = jax.tree.leaves(wrong_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    worst_grad = max(
        float(jnp.abs(g - w).max()) for g, w in zip(jax.tree.leaves(got_grads), flat_want)
    )
    assert abs(float(got_loss) - float(wrong_loss)) > 1e-3 or worst_grad > 1e-2 * scale, fault


def test_the_exit_entropy_bonus_is_in_the_loss(ppo_case):
    ppo, policy, params, model, obs, dones, batch, ((got_loss, metrics), _) = ppo_case
    without, _ = jax.jit(
        lambda q: ppo_loss(policy, q, batch, dataclasses.replace(ppo, exit_entropy_coef=0.0))
    )(params)
    np.testing.assert_allclose(
        float(without) - float(got_loss), 0.05 * float(metrics["looplm_exit_entropy"]), rtol=1e-4
    )


@pytest.mark.parametrize("over", [{"anchor_kl_coef": 0.1}, {"kl_target": 0.01}])
def test_what_reads_one_set_of_head_outputs_is_refused(ppo_case, over):
    ppo, policy, params, model, obs, dones, batch, _ = ppo_case
    with pytest.raises(ValueError, match="'looplm'"):
        ppo_loss(policy, params, batch, dataclasses.replace(ppo, **over), anchor_params=params)


@pytest.mark.parametrize("advantage", ["gae", "vtrace"])
def test_one_loop_step_and_no_gate_is_todays_loss_bit_for_bit(advantage):
    """A core that is not looped is one exit of mass 1: the exit-weighted
    loss of the LSTM policy IS ``ppo_loss``: the loss and every metric bit for bit."""
    from dotaclient_tpu.train.ppo import example_batch

    cfg = default_config()
    cfg = dataclasses.replace(cfg, ppo=dataclasses.replace(cfg.ppo, rollout_len=T, advantage=advantage))
    policy = Policy(cfg.model, cfg.obs, cfg.actions)
    params = init_params(policy, jax.random.PRNGKey(2))
    rng = np.random.default_rng(7)
    batch = dict(example_batch(cfg, 3))
    batch["obs"] = obs_mod.batch_of(run_config(cfg.model), rng, 3, T + 1)
    batch["rewards"] = rng.standard_normal((3, T)).astype(np.float32)
    batch["behavior_logp"] = (-3.0 + 0.1 * rng.standard_normal((3, T))).astype(np.float32)
    batch["dones"] = (rng.random((3, T)) < 0.3).astype(np.float32)
    batch["valid"] = np.ones((3, T), np.float32)
    batch["valid"][2, -1] = 0.0
    today = jax.jit(jax.value_and_grad(lambda p: ppo_loss(policy, p, batch, cfg.ppo), has_aux=True))(params)
    mixed = jax.jit(jax.value_and_grad(lambda p: exit_weighted_loss(policy, p, batch, cfg.ppo), has_aux=True))(params)
    (loss_a, metrics_a), grads_a = today
    (loss_b, metrics_b), grads_b = mixed
    assert np.asarray(loss_a).tobytes() == np.asarray(loss_b).tobytes()
    assert sorted(metrics_a) == sorted(metrics_b)
    for key in metrics_a:
        assert np.asarray(metrics_a[key]).tobytes() == np.asarray(metrics_b[key]).tobytes(), key
    # the gradients to rounding: a sum over [1, B, T] may be taken in another order than over [B, T]
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads_a)[0], jax.tree.leaves(grads_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-8, err_msg=jax.tree_util.keystr(path))


# -- the step's products against the rings, compiled for the chip (no chip needed) ----


@pytest.fixture(scope="module")
def one_chip():
    """A described, unattached v5e chip (the TPU's compiler is installed
    beside JAX); described inside the fixture, never at import."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _ring_sized_detours(text, lanes, model):
    """Arrays of a ring's size in float32, or in the layout a ring is copied
    into for a batched product, in a compiled step's text."""
    ring = lanes * model.full_context * model.n_kv_heads * model.head_dim
    widened = [
        s for s in re.findall(r"= f32\[([0-9,]+)\]", text) if np.prod([int(n) for n in s.split(",")]) >= ring
    ]
    moved = re.findall(rf"= bf16\[{lanes},{model.full_context},{model.n_kv_heads},{model.head_dim}\]{{3,1,2,0", text)
    return len(widened), len(moved)


@pytest.mark.parametrize("few_rows", [True, False])
def test_a_step_at_the_cells_widths_multiplies_the_rings_as_they_lie(one_chip, monkeypatch, few_rows):
    """``Policy.step`` at Ouro's widths, 5 lanes, compiled for the v5e: with
    ``afmoe._attend_few_rows`` no ring is widened to float32 or copied into
    another layout; through ``_attend`` alone (one row a head) each is, every
    step: what made 77% of the cell's device time (PERF.md section 6)."""
    from benchmark.harness import cells, program
    from dotaclient_tpu.models.policy import dummy_obs_batch, make_policy

    if not few_rows:
        monkeypatch.setattr(afmoe, "_MXU_ROWS", 0)
    cfg = program.build_run_config(cells.load_cell("ouro-2.6b-5v5-ut4.fused-selfplay-anycore"), seed=0, rehearsal=False)
    policy, lanes = make_policy(cfg.model, cfg.obs, cfg.actions), 5
    shapes = jax.eval_shape(lambda: (
        init_params(policy, jax.random.PRNGKey(0)), dummy_obs_batch(lanes, cfg.obs, cfg.actions),
        policy.initial_state(lanes),
    ))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    text = jax.jit(
        lambda p, o, c: policy.apply(p, o, c, method="step"), donate_argnums=(2,)
    ).lower(*args).compile().as_text()
    widened, moved = _ring_sized_detours(text, lanes, cfg.model)
    if few_rows:
        assert (widened, moved) == (0, 0)
    else:
        assert widened + moved >= cfg.model.n_layers * cfg.model.loop_steps
