"""The afmoe core (``models/afmoe.py``) against its plain reference
(``benchmark/reference/afmoe_ref.py``) at tiny widths on the CPU: hidden 32,
window 8 (ring 12), full_context 24, 8 experts 2 a token, chunks of T = 4,
float32, seeded weights.

The program runs chunk by chunk (or step by step) through its rings; the
reference takes each lane's whole history at once. Every mechanism has a
case that removes it from the reference and must then DISAGREE: a
comparison that would pass with the mechanism left out pins nothing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import obs as obs_mod
from benchmark.reference import afmoe_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import afmoe, distributions as D, init_params
from dotaclient_tpu.models.policy import Policy
from dotaclient_tpu.train.ppo import ppo_loss

B, S, T = 3, 20, 4
AGREE, DIFFER = 2e-5, 1e-3


def tiny_model(**over):
    cfg = default_config()
    sizes = dict(
        core="afmoe", hidden_dim=32, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=8, context_window=8, full_context=24, rollout_chunk=T,
        n_dense_layers=1, dense_ffn_dim=48, expert_ffn_dim=16, moe_experts=8,
        experts_per_token=2, dtype="float32",
    )
    return dataclasses.replace(cfg.model, **{**sizes, **over})


def run_config(model):
    cfg = default_config()
    return {
        "model": dataclasses.asdict(model), "obs": dataclasses.asdict(cfg.obs),
        "actions": dataclasses.asdict(cfg.actions),
    }


def perturbed(params, seed=11):
    """Seeded weights with every norm scale and the selection bias moved off
    their initial 1 and 0, so that a test can see them."""
    rng = np.random.default_rng(seed)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(leaf.shape).astype(np.float32))
        if "select_bias" in name:
            return leaf + 0.2 * rng.standard_normal(leaf.shape).astype(np.float32)
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
    dones[0, 9] = 1          # mid-chunk, after the window ring (12) is in use
    dones[1, 13] = dones[1, 14] = 1   # two ends in a row, one on a chunk's edge... and inside
    return {"policy": policy, "params": params, "rc": rc, "obs": obs, "dones": dones}


def through_chunks(policy, params, obs, dones, chunk=T):
    """The program, chunk by chunk, as the actor and the learner drive it:
    a reset through the core between chunks, ``dones`` inside them."""
    seq = jax.jit(lambda p, o, c, d: policy.apply(p, o, c, d, method="sequence"))
    carry = policy.initial_state(obs["units"].shape[0])
    logits, values = [], []
    for c0 in range(0, obs["units"].shape[1], chunk):
        if c0:
            carry = policy.reset_carry(carry, 1.0 - dones[:, c0 - 1])
        lg, v, carry = seq(
            params, {k: x[:, c0:c0 + chunk] for k, x in obs.items()}, carry,
            dones[:, c0:c0 + chunk],
        )
        logits.append(lg)
        values.append(v)
    cat = lambda xs: jnp.concatenate(xs, axis=1)
    return jax.tree.map(lambda *xs: cat(xs), *logits), cat(values), carry


def step_by_step(policy, params, obs, dones):
    step = jax.jit(lambda p, o, c: policy.apply(p, o, c, method="step"))
    carry = policy.initial_state(obs["units"].shape[0])
    logits, values = [], []
    for t in range(obs["units"].shape[1]):
        if t:
            carry = policy.reset_carry(carry, 1.0 - dones[:, t - 1])
        lg, v, carry = step(params, {k: x[:, t] for k, x in obs.items()}, carry)
        logits.append(lg)
        values.append(v)
    stack = lambda xs: jnp.stack(xs, axis=1)
    return jax.tree.map(lambda *xs: stack(xs), *logits), stack(values), carry


def worst(got, want):
    return afmoe_ref.policy_ref.max_abs_diff(
        {"logits": got[0], "value": got[1]}, {"logits": want[0], "value": want[1]}
    )


@pytest.fixture(scope="module")
def program_out(world):
    return through_chunks(world["policy"], world["params"], world["obs"], world["dones"])


@pytest.fixture(scope="module")
def reference_out(world):
    return afmoe_ref.history(world["params"], world["obs"], world["dones"], world["rc"]["model"])


# -- step, sequence and reference agree ------------------------------------------


@pytest.mark.parametrize("mode", ["sequence", "step"])
def test_program_agrees_with_reference_over_a_wrapped_ring_and_resets(world, reference_out, program_out, mode):
    """20 steps through a ring of 12: the window layers' rings have wrapped,
    one lane ends an episode mid-chunk and one twice in a row."""
    assert S > afmoe.ring_len(world["policy"].model, 0) > world["policy"].model.context_window
    got = program_out if mode == "sequence" else step_by_step(
        world["policy"], world["params"], world["obs"], world["dones"]
    )
    assert worst(got, reference_out) < AGREE
    # both ways end with the same counters
    carry = got[2]
    np.testing.assert_array_equal(np.asarray(carry["cursor"]), S % afmoe._cursor_modulus(world["policy"].model))
    np.testing.assert_array_equal(np.asarray(carry["pos"]), [S - 10, S - 15, S])


def test_reference_imports_nothing_from_the_program():
    import pathlib

    text = pathlib.Path(afmoe_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


def test_resets_are_where_the_program_puts_them(world, program_out):
    none = afmoe_ref.history(world["params"], world["obs"], 0 * world["dones"], world["rc"]["model"])
    shifted = afmoe_ref.history(
        world["params"], world["obs"], np.roll(world["dones"], 1, axis=1), world["rc"]["model"]
    )
    assert worst(program_out, none) > DIFFER and worst(program_out, shifted) > DIFFER


# -- one case a mechanism: the reference WITHOUT it must disagree -----------------


def _model(**over):
    return lambda model, params: ({**model, **over}, params)


def _scaled(leaf_name, factor):
    def change(model, params):
        def f(path, leaf):
            return leaf * factor if leaf_name in jax.tree_util.keystr(path) else leaf
        return model, jax.tree_util.tree_map_with_path(f, params)
    return change


ABLATIONS = {
    # (change to the reference's sizes or weights, function of the reference patched)
    "window_mask": (_model(context_window=10 ** 6), None),
    "full_layers_see_the_whole_episode": (_model(global_attn_every=10 ** 6), None),
    "rope_on_window_layers": (None, ("rope", lambda x, pos, theta: x)),
    "no_rope_on_full_layers": (_model(context_window=10 ** 6, global_attn_every=10 ** 6), None),
    "gqa_grouping": (None, ("expand_kv", lambda x, g: jnp.tile(x, (1, 1, g, 1)))),
    "route_norm": (_model(route_norm=False), None),
    "route_scale": (_model(route_scale=1.0), None),
    "shared_expert": (_scaled("shared']['down_proj", 0.0), None),
    "assumed_qk_norm": (_scaled("q_norm", 2.0), None),
    "assumed_output_gate": (_scaled("wgate", 0.0), None),
    "assumed_post_sublayer_norm": (_scaled("post_attn_norm", 2.0), None),
    "assumed_selection_bias": (_scaled("select_bias", 0.0), None),
    "assumed_mup_input_scale": (_model(mup_enabled=False), None),
    "held_experts_only": (_model(held_experts=4), None),
}


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_reference_without_the_mechanism_disagrees(world, program_out, monkeypatch, name):
    change, patch = ABLATIONS[name]
    model, params = world["rc"]["model"], world["params"]
    if change is not None:
        model, params = change(model, params)
    if patch is not None:
        monkeypatch.setattr(afmoe_ref, *patch)
    without = afmoe_ref.history(params, world["obs"], world["dones"], model)
    assert worst(program_out, without) > DIFFER, name


def test_selection_bias_picks_experts_and_weighs_nothing(world):
    """Forced onto expert 0 by its bias, every token takes expert 0; the
    bias has no gradient, and the weights are the scores' alone."""
    policy, params = world["policy"], world["params"]

    def biased(path, leaf):
        if "select_bias" in jax.tree_util.keystr(path):
            return leaf.at[0].set(10.0)
        return leaf

    forced = jax.tree_util.tree_map_with_path(biased, params)
    got = through_chunks(policy, forced, world["obs"], world["dones"])
    want = afmoe_ref.history(forced, world["obs"], world["dones"], world["rc"]["model"])
    assert worst(got, want) < AGREE
    assert all(bool((r["chosen"] == 0).any(axis=-1).all()) for r in want[2])

    layer, lp, m = _layer_params(policy.model)
    grads = jax.grad(lambda p: jnp.square(layer.apply({"params": p}, m)).sum())(lp)
    assert not np.asarray(grads["select_bias"]).any()
    assert np.asarray(grads["router"]).any() and np.asarray(grads["expert_down"]).any()


# -- the carry: reset, chunk-start view ------------------------------------------


def test_reset_through_the_core_touches_no_cache_leaf(world):
    policy = world["policy"]
    carry = through_chunks(policy, world["params"], world["obs"], world["dones"])[2]
    keep = jnp.asarray([1.0, 0.0, 1.0])
    after = policy.reset_carry(carry, keep)
    for before_l, after_l in zip(carry["kv"], after["kv"]):
        assert before_l[0] is after_l[0] and before_l[1] is after_l[1]
    assert after["cursor"] is carry["cursor"]
    np.testing.assert_array_equal(np.asarray(after["pos"]), np.asarray(carry["pos"]) * np.asarray([1, 0, 1]))
    # under jit: the rings leave as they came, no operation reads them
    jaxpr = jax.make_jaxpr(policy.reset_carry)(carry, keep).jaxpr
    rings = {v for v in jaxpr.invars if len(v.aval.shape) == 3}
    assert len(rings) == 10 and sum(v in rings for v in jaxpr.outvars) == 10
    ring_ids = {id(v) for v in rings}
    assert not any(id(v) in ring_ids for eqn in jaxpr.eqns for v in eqn.invars)


def test_chunk_start_view_is_the_start_without_a_copy(world):
    """What the learner is handed: the start's counters beside the END's
    rings. A chunk read from it equals the chunk read from the real start,
    mid-chunk reset included; the rings are the end's own buffers."""
    policy, params = world["policy"], world["params"]
    obs, dones = world["obs"], world["dones"]
    start = through_chunks(
        policy, params, {k: v[:, :12] for k, v in obs.items()}, dones[:, :12]
    )[2]
    start = policy.reset_carry(start, 1.0 - dones[:, 11])
    chunk = {k: v[:, 12:16] for k, v in obs.items()}                 # lane 1 ends at 13 and 14
    seq = jax.jit(lambda c: policy.apply(params, chunk, c, dones[:, 12:16], method="sequence"))
    lg, v, end = seq(start)
    view = policy.chunk_start_carry(start, end)
    assert all(a is b for a, b in zip(jax.tree.leaves(view["kv"]), jax.tree.leaves(end["kv"])))
    lg2, v2, _ = seq(view)
    assert worst((lg2, v2), (lg, v)) < 1e-6
    # and a ring with no slack would not do: the chunk's own writes would hide
    # what its first query sees (this is what rollout_chunk is for)
    assert afmoe.ring_len(policy.model, 0) == policy.model.context_window + T


def test_episode_must_fit_the_full_ring():
    model = tiny_model()
    afmoe.require_episode_fits(model, episode_steps=20, rollout_len=T)
    with pytest.raises(ValueError, match="full_context"):
        afmoe.require_episode_fits(model, episode_steps=21, rollout_len=T)
    with pytest.raises(ValueError, match="rollout_chunk"):
        afmoe.require_episode_fits(model, episode_steps=10, rollout_len=T + 1)


# -- the expert layer: shares, no drop ---------------------------------------------


def _layer_params(model, seed=3):
    layer = afmoe.RoutedExperts(model)
    m = jax.random.normal(jax.random.PRNGKey(seed), (B, T, model.hidden_dim))
    params = layer.init(jax.random.PRNGKey(seed + 1), m)["params"]
    return layer, params, m


def _held(params, held, offset):
    """The layer's parameters as the chip that holds ``held`` experts from
    ``offset`` has them."""
    return {
        **params,
        **{k: params[k][offset:offset + held] for k in ("expert_gate", "expert_up", "expert_down")},
    }


def _share(model, params, m, held, offset):
    cut = dataclasses.replace(model, held_experts=held, expert_offset=offset)
    return afmoe.RoutedExperts(cut).apply(
        {"params": _held(params, held, offset)}, m, mutable=["losses"]
    )


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of eight: what they
    compute, the shared expert counted once, is the whole layer's output."""
    model = tiny_model()
    _, params, m = _layer_params(model)
    whole = afmoe_ref.experts(params, m, dataclasses.asdict(model))[0]
    shared = afmoe_ref.swiglu(params["shared"], m)
    parts = [_share(model, params, m, 2, off)[0] for off in (0, 2, 4, 6)]
    total = sum(part - shared for part in parts) + shared
    assert float(jnp.abs(total - whole).max()) < AGREE
    # each share is its own reference's, and no share is the whole
    for off, part in zip((0, 2, 4, 6), parts):
        cut = {**dataclasses.asdict(model), "held_experts": 2, "expert_offset": off}
        assert float(jnp.abs(part - afmoe_ref.experts(_held(params, 2, off), m, cut)[0]).max()) < AGREE
        assert float(jnp.abs(part - whole).max()) > DIFFER
    # the uncut program layer (held_experts 0 = all) is the whole layer too
    full = afmoe.RoutedExperts(model).apply({"params": params}, m)
    assert float(jnp.abs(full - whole).max()) < AGREE


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 0), (2, 6)])
def test_no_assignment_is_dropped_with_the_router_forced_onto_one_expert(held, offset):
    """Every token takes expert 0 (and one more): the busiest load a layer
    can see. Nothing is dropped, and the counters say what was computed."""
    model = tiny_model()
    _, params, m = _layer_params(model)
    params = {**params, "select_bias": params["select_bias"].at[0].set(10.0)}
    out, sown = _share(model, params, m, held, offset)
    losses = sown["losses"]
    cut = {**dataclasses.asdict(model), "held_experts": held, "expert_offset": offset}
    want, routed = afmoe_ref.experts(_held(params, held, offset), m, cut)
    chosen = routed["chosen"]
    assert float(jnp.abs(out - want).max()) < AGREE
    here = (chosen >= offset) & (chosen < offset + held)
    assert float(losses["moe_dropped"][0]) == 0.0
    assert float(losses["moe_local"][0]) == float(here.sum())
    load = np.asarray(losses["moe_load"][0])
    assert load.sum() == float(here.sum())
    if offset == 0:
        assert load[0] == B * T          # all twelve tokens on expert 0


# -- one PPO step: loss and gradients ---------------------------------------------


def _ppo_case():
    """A learner's batch: the LAST chunk of a history (carry0 = the rings the
    earlier chunks left, T + 1 observations). Two layers hold every kind:
    dense with window, experts with full."""
    cfg = default_config()
    ppo = dataclasses.replace(cfg.ppo, rollout_len=T, moe_aux_coef=0.05)
    model_cfg = tiny_model(n_layers=2, global_attn_every=2)
    policy = Policy(model_cfg, cfg.obs, cfg.actions)
    params = perturbed(init_params(policy, jax.random.PRNGKey(1)))
    rc = run_config(model_cfg)
    model = rc["model"]
    rng = np.random.default_rng(5)
    lanes, P = 2, 12
    hist = P + T + 1
    obs = obs_mod.batch_of(rc, rng, lanes, hist)
    dones = np.zeros((lanes, hist), np.float32)
    dones[0, 9] = dones[1, P + 1] = 1            # one in the data, one inside the chunk
    carry0 = through_chunks(policy, params, {k: v[:, :P] for k, v in obs.items()}, dones[:, :P])[2]
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
    # legal actions only, so that no log-probability is a mask's -1e9
    for h, mask in (("action_type", "mask_action_type"), ("target_unit", "mask_target_unit"), ("ability", "mask_ability")):
        batch["actions"][h] = np.asarray(batch["obs"][mask][:, :T]).argmax(axis=-1).astype(np.int32)
    return cfg, ppo, policy, params, model, obs, dones, batch, lanes, P


def test_ppo_loss_and_gradients_agree_with_the_reference():
    """The learner's pass against ``jax.grad`` of the reference's loss over
    the whole history with the earlier steps as data."""
    cfg, ppo, policy, params, model, obs, dones, batch, lanes, P = _ppo_case()
    (got_loss, metrics), got_grads = jax.jit(jax.value_and_grad(
        lambda p: ppo_loss(policy, p, batch, ppo), has_aux=True
    ))(params)

    def lpe(logits, o, actions):
        return D.log_prob(logits, o, actions), D.entropy(logits, o)

    knobs = {k: getattr(ppo, k) for k in ("gamma", "gae_lambda", "clip_eps", "entropy_coef", "value_coef", "moe_aux_coef")}
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: afmoe_ref.ppo_loss(p, obs, dones, batch, model, knobs, lpe)
    ))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * max(1.0, abs(float(want_loss)))
    flat_got = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_want = jax.tree.leaves(want_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale, jax.tree_util.keystr(path)
    # the auxiliary loss rides the existing moe_aux path, and is the reference's
    routing = afmoe_ref.history(params, obs, dones, model)[2]
    tail = [{k: v[:, P:P + T] for k, v in r.items()} for r in routing]
    want_aux = float(afmoe_ref.aux_loss(tail, batch["valid"], model))
    assert want_aux > 0.5 and abs(float(metrics["moe_aux"]) - want_aux) < 1e-5
    # and the step's metrics carry the layer's counts: one expert layer, all held
    assert float(metrics["moe_dropped_assignments"]) == 0.0
    assert float(metrics["moe_local_assignments"]) == lanes * (T + 1) * 2
    assert float(metrics["moe_max_over_mean_load"]) >= 1.0


@pytest.mark.parametrize("rate", [0.001, 0.0])
def test_the_balancing_update_moves_the_selection_bias_against_the_load(rate):
    """No gradient reaches the selection bias; one optimizer step moves it by
    ``rate`` (centred) away from the experts that took more tokens than the
    mean and towards the others, and with a rate of 0 leaves it alone."""
    from dotaclient_tpu.train.ppo import _train_step, init_train_state

    cfg, ppo, policy, params, model, obs, dones, batch, lanes, P = _ppo_case()
    ppo = dataclasses.replace(ppo, select_bias_rate=rate)
    state, metrics = jax.jit(lambda s, b: _train_step(policy, ppo, s, b))(init_train_state(params, ppo), batch)
    assert "_select_bias_err" not in metrics
    moe0, moe1 = (p["params"]["core"]["layer_1"]["moe"] for p in (params, state.params))
    moved = np.asarray(moe1["select_bias"] - moe0["select_bias"])
    assert float(jnp.abs(moe1["router"] - moe0["router"]).max()) > 0      # Adam's, as ever
    if rate == 0.0:
        assert not moved.any()
        return
    routing = afmoe_ref.history(params, obs, dones, model)[2]
    chosen = np.asarray(routing[0]["chosen"][:, P:])                       # the pass's T + 1 steps
    tokens = np.bincount(chosen.ravel(), minlength=8)
    assert tokens.sum() == lanes * (T + 1) * 2 and tokens.max() > tokens.mean() > tokens.min()
    step = -np.sign(tokens - tokens.mean())
    np.testing.assert_allclose(moved, rate * (step - step.mean()), atol=1e-7)
    assert (moved[tokens > tokens.mean()] < 0).all() and (moved[tokens < tokens.mean()] > 0).all()
