"""The LFM2 core (``models/lfm2moe.py``) against its plain reference
(``benchmark/reference/lfm2moe_ref.py``) at tiny widths on the CPU: hidden 32,
five layers (convolution dense, attention, three convolutions with experts),
4 query heads over 2 KV heads of 8, 3 taps, 16 experts 2 a token with 2 held
and none shared, full_context 24, chunks of T = 4, float32, seeded weights.

The program runs chunk by chunk (the convolution over the two carried rows
and the chunk's own, attention over the ring) or step by step (T = 1)
through its carry; the reference takes each lane's whole history at once.
The reference made wrong in one way at a time must DISAGREE: a comparison
that would pass with the taps reversed or shifted, a history kept across an
episode's end, a gate, the rotation or the head norm left out pins nothing.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import flops, flops_lfm2moe, obs as obs_mod
from benchmark.reference import lfm2moe_ref, policy_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import afmoe, distributions as D, init_params, lfm2moe, shortconv
from dotaclient_tpu.models.lanes import LaneBlocks, by_lane_block
from dotaclient_tpu.models.policy import Policy, require_carry_stays, require_episode_fits
from dotaclient_tpu.train.ppo import _shortconv_gauges, ppo_loss
from tests.test_afmoe import _scaled, perturbed, run_config, step_by_step, through_chunks

B, S, T = 3, 40, 4
AGREE, DIFFER = 1e-4, 1e-2
CONV_LAYERS, ATTN_LAYERS = 4, 1

SIZES = dict(
    core="lfm2moe", hidden_dim=32, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=8, shortconv_taps=3,
    full_context=24, rollout_chunk=T, global_attn_every=4, global_attn_offset=2, n_dense_layers=1,
    dense_ffn_dim=48, expert_ffn_dim=16, moe_experts=16, experts_per_token=2, held_experts=2, expert_offset=0,
    n_shared_experts=0, route_scale=1.0, rope_theta=1e6, mup_enabled=False, attn_qk_norm=True,
    attn_out_gate=False, rope_full_layers=True, dtype="float32",
)


def tiny_model(**over):
    return dataclasses.replace(default_config().model, **{**SIZES, **over})


def seeded_params(policy, seed):
    """``init_params`` as ONE program (eagerly it is 19 s of one-operation compiles here)."""
    return jax.jit(lambda key: init_params(policy, key))(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def world():
    cfg = default_config()
    model = tiny_model()
    policy = Policy(model, cfg.obs, cfg.actions)
    params = perturbed(seeded_params(policy, 0))
    rc = run_config(model)
    rng = np.random.default_rng(0)
    obs = obs_mod.batch_of(rc, rng, B, S)
    dones = np.zeros((B, S), np.float32)
    dones[0, [9, 29]] = 1                     # mid-chunk
    dones[1, [13, 14, 33, 39]] = 1            # two ends in a row; one on the history's last step
    dones[2, [19]] = 1                        # on a chunk's last step: the next chunk starts void
    return {"policy": policy, "params": params, "rc": rc, "obs": obs, "dones": dones}


def worst(got, want):
    """Largest difference of logits and values, relative to the outputs' size
    (as ``compare_lfm2moe`` reports it)."""
    want = {"l": want[0], "v": want[1]}
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    return policy_ref.max_abs_diff({"l": got[0], "v": got[1]}, want) / scale


QUICK = {"xla_backend_optimization_level": 0, "xla_llvm_disable_expensive_passes": True}


def quickly(fn, *args):
    """``fn(*args)`` compiled once without the backend's expensive passes: these
    programs run once, and op by op a reference is seconds a call."""
    return jax.jit(fn).lower(*args).compile(compiler_options=QUICK)(*args)


def reference(params, obs, dones, model, fault=None):
    return quickly(lambda p, o, d: lfm2moe_ref.history(p, o, d, model, fault=fault)[:2], params, obs, dones)


@pytest.fixture(scope="module")
def program_out(world):
    return through_chunks(world["policy"], world["params"], world["obs"], world["dones"])


@pytest.fixture(scope="module")
def reference_out(world):
    return reference(world["params"], world["obs"], world["dones"], world["rc"]["model"])


# -- step, sequence and reference agree ------------------------------------------


@pytest.mark.parametrize("chunk", [T, 3, 10])
def test_chunks_agree_with_the_reference_over_resets(world, reference_out, program_out, chunk):
    """40 steps in chunks of 4 (episode ends mid-chunk, twice in a row and on a
    chunk's last step; the histories handed through ten chunks), of 3 (fewer
    than 8 rows a KV head: ``afmoe._attend_few_rows`` at G = 2) and of 10
    (``_attend``; a chunk that holds two ends in a row)."""
    got = program_out if chunk == T else through_chunks(
        world["policy"], world["params"], world["obs"], world["dones"], chunk=chunk
    )
    assert worst(got, reference_out) < AGREE
    if chunk == T:
        carry = got[2]
        np.testing.assert_array_equal(np.asarray(carry["cursor"]), S % 24)
        np.testing.assert_array_equal(np.asarray(carry["pos"]), [S - 30, S - 34, S - 20])
        assert len(carry["kv"]) == ATTN_LAYERS and len(carry["conv"]) == CONV_LAYERS


def test_steps_of_one_agree_with_the_reference_and_leave_the_chunks_carry(world, reference_out, program_out):
    """T = 1 is the chunk form: same outputs, and the same two rows a layer."""
    logits, values, carry = step_by_step(world["policy"], world["params"], world["obs"], world["dones"])
    assert worst((logits, values), reference_out) < AGREE
    for a, b in zip(carry["conv"], program_out[2]["conv"]):
        assert a.shape == (B, 2, 32) and float(jnp.abs(a - b).max()) < 1e-5
    np.testing.assert_array_equal(np.asarray(carry["pos"]), np.asarray(program_out[2]["pos"]))


def test_reference_imports_nothing_from_the_program():
    import pathlib

    text = pathlib.Path(lfm2moe_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


def test_resets_are_where_the_program_puts_them(world, program_out):
    model = world["rc"]["model"]
    none = reference(world["params"], world["obs"], 0 * world["dones"], model)
    shifted = reference(world["params"], world["obs"], np.roll(world["dones"], 1, axis=1), model)
    assert worst(program_out, none) > DIFFER and worst(program_out, shifted) > DIFFER


# -- the reference made wrong in one way must disagree ----------------------------


@pytest.mark.parametrize("fault", lfm2moe_ref.FAULTS)
def test_reference_wrong_in_one_way_disagrees(world, program_out, fault):
    wrong = reference(world["params"], world["obs"], world["dones"], world["rc"]["model"], fault=fault)
    assert worst(program_out, wrong) > DIFFER, fault


def _model(**over):
    return lambda model, params: ({**model, **over}, params)


ABLATIONS = {
    "assumed_taps": _scaled("conv']['conv", 0.5),
    "assumed_head_norm": _scaled("q_norm", 2.0),
    "route_norm": _model(route_norm=False),
    "route_scale": _model(route_scale=2.0),
    "assumed_selection_bias": _scaled("select_bias", 0.0),
    "held_experts_only": _model(held_experts=1),
    "the_attention_layer_is_a_convolution": _model(global_attn_every=10 ** 6),
}


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_reference_without_the_mechanism_disagrees(world, program_out, name):
    model, params = ABLATIONS[name](world["rc"]["model"], world["params"])
    if name == "the_attention_layer_is_a_convolution":
        with pytest.raises(KeyError):                       # layer 1's weights are an attention layer's
            reference(params, world["obs"], world["dones"], model)
        return
    without = reference(params, world["obs"], world["dones"], model)
    assert worst(program_out, without) > DIFFER, name


# -- the carry: reset, void reads, chunk-start view, what must fit -------------------


def test_reset_through_the_core_touches_no_leaf(world, program_out):
    policy, carry = world["policy"], program_out[2]
    keep = jnp.asarray([1.0, 0.0, 1.0])
    after = policy.reset_carry(carry, keep)
    for before_l, after_l in zip(jax.tree.leaves((carry["kv"], carry["conv"])), jax.tree.leaves((after["kv"], after["conv"]))):
        assert before_l is after_l
    assert after["cursor"] is carry["cursor"]
    np.testing.assert_array_equal(np.asarray(after["pos"]), np.asarray(carry["pos"]) * np.asarray([1, 0, 1]))
    # under jit: rings and histories leave as they came, no operation reads them
    jaxpr = jax.make_jaxpr(policy.reset_carry)(carry, keep).jaxpr
    big = {v for v in jaxpr.invars if len(v.aval.shape) >= 3}
    assert len(big) == 2 * ATTN_LAYERS + CONV_LAYERS and sum(v in big for v in jaxpr.outvars) == len(big)
    ids = {id(v) for v in big}
    assert not any(id(v) in ids for eqn in jaxpr.eqns for v in eqn.invars)


def test_the_next_read_of_a_history_after_a_reset_is_void(world, program_out):
    """A lane at position 0 reads its histories as void: NaN in every row of
    that lane (and garbage in its ring) changes nothing, outputs, gradients and
    the new histories; the same poison in a lane that carries on is seen."""
    policy, params, carry = world["policy"], world["params"], program_out[2]
    chunk = {k: v[:, :T] for k, v in world["obs"].items()}
    poison = lambda lanes: {
        **carry, "conv": jax.tree.map(lambda x: x.at[lanes].set(jnp.nan), carry["conv"]),
        "kv": jax.tree.map(lambda x: x.at[lanes].set(1e4), carry["kv"]),
    }

    def value_sum(p, c):
        _, v, end = policy.apply(p, chunk, c, method="sequence")
        return v.sum(), (v, end)

    run = jax.jit(jax.value_and_grad(value_sum, has_aux=True))
    reset = lambda c: policy.reset_carry(c, jnp.asarray([1.0, 0.0, 1.0]))
    (_, (clean, clean_end)), clean_grads = run(params, reset(carry))
    (_, (dirty, dirty_end)), dirty_grads = run(params, reset(poison(1)))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    for a, b in zip(jax.tree.leaves(clean_grads), jax.tree.leaves(dirty_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(clean_end["conv"], dirty_end["conv"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))       # and the new history is clean
    (_, (seen, _)), _ = run(params, reset(poison(0)))
    assert np.isnan(np.asarray(seen[0])).all() and np.isfinite(np.asarray(seen[1:])).all()


def test_chunk_start_view_is_the_start_s_histories_beside_the_end_s_ring(world):
    """What the learner is handed: the start's counters and histories (its own
    buffers) beside the END's ring pair (no copy). A chunk read from it equals
    the chunk read from the real start, mid-chunk resets included."""
    policy, params = world["policy"], world["params"]
    obs, dones = world["obs"], world["dones"]
    start = through_chunks(policy, params, {k: v[:, :12] for k, v in obs.items()}, dones[:, :12])[2]
    start = policy.reset_carry(start, 1.0 - dones[:, 11])
    chunk = {k: v[:, 12:16] for k, v in obs.items()}                 # lane 1 ends at 13 and 14
    seq = jax.jit(lambda c: policy.apply(params, chunk, c, dones[:, 12:16], method="sequence"))
    lg, v, end = seq(start)
    view = policy.chunk_start_carry(start, end)
    assert all(a is b for a, b in zip(jax.tree.leaves(view["kv"]), jax.tree.leaves(end["kv"])))
    assert all(a is b for a, b in zip(view["conv"], start["conv"]))
    assert view["pos"] is start["pos"] and view["cursor"] is start["cursor"]
    lg2, v2, _ = seq(view)
    assert policy_ref.max_abs_diff({"l": lg2, "v": v2}, {"l": lg, "v": v}) < 1e-6
    # the end's histories would NOT do: they were overwritten
    lg3, v3, _ = seq({**view, "conv": end["conv"]})
    assert policy_ref.max_abs_diff({"l": lg3, "v": v3}, {"l": lg, "v": v}) > DIFFER


def test_what_the_carry_holds_what_must_fit_and_the_refusals_name_the_core():
    model = tiny_model()
    assert lfm2moe.carry_bytes_per_lane(model) == 8 + ATTN_LAYERS * 24 * 2 * 16 * 4 + CONV_LAYERS * 2 * 32 * 4
    assert flops_lfm2moe.carry_bytes_per_lane(dataclasses.asdict(model)) == lfm2moe.carry_bytes_per_lane(model)
    carry = lfm2moe.initial_state(model, B)
    assert [(k.shape, v.shape) for k, v in carry["kv"]] == [((B, 24, 16), (B, 24, 16))]
    assert [h.shape for h in carry["conv"]] == [(B, 2, 32)] * 4
    assert lfm2moe.attn_layers(model) == [1] and lfm2moe.conv_layers(model) == [0, 2, 3, 4]
    require_episode_fits(model, episode_steps=20, rollout_len=T)
    with pytest.raises(ValueError, match="'lfm2moe'.*full_context"):
        require_episode_fits(model, episode_steps=21, rollout_len=T)
    with pytest.raises(ValueError, match="'lfm2moe'.*rollout_chunk"):
        require_episode_fits(model, episode_steps=10, rollout_len=T + 1)
    with pytest.raises(ValueError, match=f"core 'lfm2moe' carries {lfm2moe.carry_bytes_per_lane(model):,} bytes"):
        require_carry_stays(model, "actor mode 'device'")
    assert model.carry_stays_on_chip


@pytest.mark.parametrize("over", [
    {"mup_enabled": True}, {"loop_steps": 2}, {"shortconv_taps": 1}, {"rope_full_layers": False}, {"experts_per_token": 32},
])
def test_a_configuration_the_core_does_not_run_is_refused(over):
    cfg = default_config()
    policy = Policy(tiny_model(**over), cfg.obs, cfg.actions)
    with pytest.raises(ValueError, match="lfm2moe"):
        init_params(policy, jax.random.PRNGKey(0))


def test_the_parameters_are_the_published_layers_at_toy_widths(world):
    core = world["params"]["params"]["core"]
    assert sorted(core) == ["layer_0", "layer_1", "layer_2", "layer_3", "layer_4", "out_norm"]
    assert sorted(core["layer_0"]) == ["conv", "ffn", "ffn_norm", "operator_norm"]           # dense, no post-norm
    assert sorted(core["layer_1"]) == ["attn", "ffn_norm", "moe", "operator_norm"]
    assert sorted(core["layer_4"]) == ["conv", "ffn_norm", "moe", "operator_norm"]
    assert sorted(core["layer_2"]["conv"]) == ["conv", "in_proj", "out_proj"]
    assert core["layer_2"]["conv"]["conv"].shape == (3, 32) and core["layer_2"]["conv"]["in_proj"]["kernel"].shape == (32, 96)
    assert sorted(core["layer_1"]["attn"]) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]   # no gate
    assert core["layer_1"]["attn"]["q_norm"]["scale"].shape == (8,)
    # no shared expert: no parameter, and no bias anywhere
    assert sorted(core["layer_1"]["moe"]) == ["expert_down", "expert_gate", "expert_up", "router", "select_bias"]
    assert not [p for p, _ in jax.tree_util.tree_flatten_with_path(core)[0] if "bias" in jax.tree_util.keystr(p).replace("select_bias", "")]


# -- the expert layer without a shared expert: the shares add up -----------------------


def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer_with_no_shared_expert():
    """Eight chips hold experts 0-7, 8-15, ... of 64 (the cell: 8 chips, 8 of
    64, 4 a token): the routed terms they compute add up to the uncut
    reference layer's output, there being no shared expert to count once; and
    the layer told ``n_shared_experts`` 0 holds no ``shared`` parameter and
    writes nothing under ``core_expert_shared``."""
    model = tiny_model(moe_experts=64, experts_per_token=4, held_experts=0)
    layer = afmoe.RoutedExperts(model)
    m = jax.random.normal(jax.random.PRNGKey(3), (B, T, model.hidden_dim))
    params = jax.jit(layer.init)(jax.random.PRNGKey(4), m)["params"]
    assert sorted(params) == ["expert_down", "expert_gate", "expert_up", "router", "select_bias"]
    whole = quickly(lambda p, x: lfm2moe_ref.experts(p, x, dataclasses.asdict(model))[0], params, m)

    def share(offset):
        cut = dataclasses.replace(model, held_experts=8, expert_offset=offset)
        held = {**params, **{k: params[k][offset:offset + 8] for k in ("expert_gate", "expert_up", "expert_down")}}
        return afmoe.RoutedExperts(cut).apply({"params": held}, m, mutable=["losses"])[0]

    parts = quickly(lambda: [share(off) for off in range(0, 64, 8)])
    assert float(jnp.abs(sum(parts) - whole).max()) < 2e-5
    assert all(float(jnp.abs(part - whole).max()) > 1e-3 for part in parts)
    # the uncut layer of the PROGRAM is the reference's too, and a shared expert, where there is one, is still added
    uncut = layer.apply({"params": params}, m, mutable=["losses"])[0]
    assert float(jnp.abs(uncut - whole).max()) < 2e-5
    text = jax.jit(lambda p: layer.apply({"params": p}, m, mutable=["losses"])[0]).lower(params).as_text(debug_info=True)
    assert "core_experts_routed" in text and "core_expert_shared" not in text
    with_shared = afmoe.RoutedExperts(dataclasses.replace(model, n_shared_experts=1))
    assert "shared" in jax.eval_shape(with_shared.init, jax.random.PRNGKey(4), m)["params"]


@pytest.mark.parametrize("tokens,offset", [("alike", 0), ("alike", 8), ("apart", 0), ("apart", 56)])
def test_the_padded_buffer_is_the_unpadded_layer_at_a_fixed_amount_of_work(monkeypatch, tokens, offset):
    """``pad_expert_groups``: the grouped products are handed groups that hold
    ALL N k rows of the buffer, every held expert's group its pairs and an
    even share of the zero rows (whether the tokens choose alike, so that a
    chip's pairs are all or none, or apart), and outputs, gradients and every
    sown count are the unpadded layer's."""
    model = tiny_model(moe_experts=64, experts_per_token=4, held_experts=8, expert_offset=offset)
    m = jax.random.normal(jax.random.PRNGKey(3), (B, T, model.hidden_dim))
    if tokens == "alike":
        m = m[:1, :1] + 1e-3 * m
    plain, padded = afmoe.RoutedExperts(model), afmoe.RoutedExperts(dataclasses.replace(model, pad_expert_groups=True))
    params = jax.jit(plain.init)(jax.random.PRNGKey(4), m)["params"]
    sizes = []
    ragged = jax.lax.ragged_dot
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda x, w, g, **kw: (sizes.append(g), ragged(x, w, g, **kw))[1])

    def run(layer):
        def out(p, x):
            y, sown = layer.apply({"params": p}, x, mutable=["losses"])
            return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=y.dtype).reshape(y.shape))), (y, sown["losses"])
        # eagerly, through the jitted products too: each ``ragged_dot`` call is seen with its groups
        with jax.disable_jit():
            return jax.value_and_grad(out, argnums=(0, 1), has_aux=True)(params, m)

    ((_, (y0, sown0)), grads0), groups0 = run(plain), [np.asarray(g) for g in sizes]
    del sizes[:]
    ((_, (y1, sown1)), grads1), groups1 = run(padded), [np.asarray(g) for g in sizes]
    pairs = B * T * model.experts_per_token
    assert len(groups0) == len(groups1) == 3
    for g0, g1 in zip(groups0, groups1):
        assert g0.sum() == int(sown0["moe_local"][0]) < pairs == g1.sum()
        assert (g1 >= g0).all() and (g1 - g0).max() - (g1 - g0).min() <= 1
    if tokens == "alike":        # all or none: every pair that lands here lands on the same few experts
        assert set(groups0[0].tolist()) <= {0, B * T}
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    for a, b in zip(jax.tree.leaves(sown0), jax.tree.leaves(sown1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(grads0), jax.tree.leaves(grads1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)


# -- the convolution two cores share ------------------------------------------------------


def _kimi_convolve_until_pr_36(taps, K, T):
    """``KDA.__call__``'s closure as it stood before the function was shared,
    word for word."""

    def convolve(history, x, carried, seg):
        rows = jnp.concatenate([jnp.where(carried[:, None, None], history, 0), x], axis=1)
        row_seg = jnp.concatenate([jnp.zeros((x.shape[0], K - 1), seg.dtype), seg], axis=1)
        y = sum(
            taps[j] * jnp.where(
                (row_seg[:, K - 1 - j:K - 1 - j + T] == seg)[..., None],
                rows[:, K - 1 - j:K - 1 - j + T].astype(jnp.float32), 0.0,
            )
            for j in range(K)
        )
        return y, jnp.where((row_seg[:, T:] == seg[:, -1:])[..., None], rows[:, T:], 0)

    return convolve


@pytest.mark.parametrize("K,steps,dtype,blocks", [(4, 1, "bfloat16", False), (4, 5, "bfloat16", True), (3, 17, "float32", False), (2, 1, "float32", True)])
def test_the_shared_convolution_is_kimi_s_closure_bit_for_bit(K, steps, dtype, blocks):
    """Seeded, small: a step and a chunk with episode starts inside it, a void
    lane, one lane block and two (``by_lane_block``): outputs and histories
    EQUAL, not close."""
    rng = np.random.default_rng(K * 100 + steps)
    lanes, C = 6, 24
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    taps, x, history = f(K, C), f(lanes, steps, C).astype(dtype), f(lanes, K - 1, C).astype(dtype)
    carried = jnp.asarray([True, False, True, True, False, True])
    seg = jnp.cumsum(jnp.asarray(rng.random((lanes, steps)) < 0.3, jnp.int32), axis=1)
    if blocks:
        history = LaneBlocks((history[:2], history[2:]))
    want_y, want_h = by_lane_block(_kimi_convolve_until_pr_36(taps, K, steps), history, x, carried, seg)
    got_y, got_h = by_lane_block(functools.partial(shortconv.causal_conv, taps), history, x, carried, seg)
    np.testing.assert_array_equal(np.asarray(got_y), np.asarray(want_y))
    for got, want in zip(*((h if blocks else (h,)) for h in (got_h, want_h))):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))


# -- one PPO step: loss, gradients and the gauges ------------------------------------


@pytest.fixture(scope="module")
def ppo_case():
    """A learner's batch: the LAST chunk of a history (carry0 = the histories
    and the ring the earlier chunks left, as the fused program hands them:
    the chunk-start view; T + 1 observations)."""
    cfg = default_config()
    ppo = dataclasses.replace(cfg.ppo, rollout_len=T, moe_aux_coef=0.0)
    model_cfg = tiny_model()
    policy = Policy(model_cfg, cfg.obs, cfg.actions)
    params = perturbed(seeded_params(policy, 1))
    rc = run_config(model_cfg)
    rng = np.random.default_rng(5)
    lanes, P = 3, 12
    hist = P + T + 1
    obs = obs_mod.batch_of(rc, rng, lanes, hist)
    dones = np.zeros((lanes, hist), np.float32)
    dones[0, 9] = dones[1, P + 1] = dones[2, P - 1] = 1     # in the data, inside the chunk, on the chunk's edge
    carry0 = through_chunks(policy, params, {k: v[:, :P] for k, v in obs.items()}, dones[:, :P])[2]
    carry0 = policy.reset_carry(carry0, 1.0 - dones[:, P - 1])
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
    for h, mask in (("action_type", "mask_action_type"), ("target_unit", "mask_target_unit"), ("ability", "mask_ability")):
        batch["actions"][h] = np.asarray(batch["obs"][mask][:, :T]).argmax(axis=-1).astype(np.int32)
    got = quickly(jax.value_and_grad(lambda p: ppo_loss(policy, p, batch, ppo), has_aux=True), params)
    return ppo, policy, params, rc["model"], obs, dones, batch, got


def _lpe(logits, o, actions):
    return D.log_prob(logits, o, actions), D.entropy(logits, o)


KNOBS = ("gamma", "gae_lambda", "clip_eps", "entropy_coef", "value_coef", "moe_aux_coef")


def test_ppo_loss_and_gradients_agree_with_the_reference(ppo_case):
    """The learner's pass against ``jax.grad`` of the reference's loss over
    the whole history with what the earlier steps left as data."""
    ppo, policy, params, model, obs, dones, batch, ((got_loss, metrics), got_grads) = ppo_case
    knobs = {k: getattr(ppo, k) for k in KNOBS}
    want_loss, want_grads = quickly(jax.value_and_grad(
        lambda p: lfm2moe_ref.ppo_loss(p, obs, dones, batch, model, knobs, _lpe)
    ), params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * max(1.0, abs(float(want_loss)))
    flat_got = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_want = jax.tree.leaves(want_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    conv = got_grads["params"]["core"]["layer_2"]["conv"]
    for name in ("conv", "in_proj", "out_proj"):
        assert float(jnp.abs(jax.tree.leaves(conv[name])[0]).max()) > 1e-7, name
    moe = got_grads["params"]["core"]["layer_2"]["moe"]
    assert float(jnp.abs(moe["select_bias"]).max()) == 0.0 < float(jnp.abs(moe["router"]).max())


def test_the_step_s_metrics_carry_the_shortconv_gauges_and_the_layer_s_counts(ppo_case):
    ppo, policy, params, model, obs, dones, batch, ((_, metrics), _) = ppo_case
    assert float(metrics["shortconv_history_rms"]) > 0.0 and float(metrics["shortconv_gate_mean"]) > 0.0
    # lane 1 starts an episode at step 2 of the chunk, lane 2 at its step 0: two starts x 4 convolution layers
    assert float(metrics["shortconv_void_reads"]) == 2 * CONV_LAYERS
    assert float(metrics["moe_dropped_assignments"]) == 0.0
    assert 0.0 < float(metrics["moe_local_assignments"]) < 3 * (T + 1) * 2 * 4
    assert "kda_void_reads" not in metrics
    # a core that sows none of them gets none
    assert _shortconv_gauges({}, batch["valid"]) == {}
    assert _shortconv_gauges({"core": {"layer_1": {"moe": {"moe_load": (jnp.ones(2),)}}}}, batch["valid"]) == {}


# -- the counts, by hand -------------------------------------------------------------


@pytest.fixture(scope="module")
def cell_rc():
    from benchmark.harness import cells, program

    return program.merged_run_config(cells.load_cell("lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore"), False)


def test_the_weights_counted_are_the_published_layers(cell_rc):
    """A convolution mixer 16.78 M, the attention mixer 10.49 M, the dense FFN
    72.35 M, an expert 9.437 M, a router 0.13 M: 452.5 M in the core (ISSUE
    37's arithmetic), and the program's tree holds them (plus norms and taps:
    under a thousandth) and no shared expert."""
    w = flops_lfm2moe.core_weight_count(cell_rc["model"])
    assert w["conv"] == 4 * 4 * 2048 * 2048 and round(w["conv"] / 4e6, 2) == 16.78
    assert w["attn"] == 2 * 2048 * 2048 + 2 * 2048 * 512 and round(w["attn"] / 1e6, 2) == 10.49
    assert w["dense_ffn"] == 3 * 2048 * 11776 and round(w["dense_ffn"] / 1e6, 2) == 72.35
    assert w["router"] == 4 * 2048 * 64 and w["shared"] == 0
    assert w["routed"] == 4 * 8 * 3 * 2048 * 1536 and round(w["routed"] / 32e6, 3) == 9.437
    assert round(sum(w.values()) / 1e6, 1) == 452.5
    from benchmark.harness import cells, program
    from dotaclient_tpu.models import make_policy

    cfg = program.build_run_config(cells.load_cell("lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore"), seed=0, rehearsal=False)
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    tree = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))["params"]["core"]
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert 0 < held - sum(w.values()) < 1e-3 * held
    # a lane: ONE ring pair of 6.0 MiB and four histories of 8 KiB
    assert lfm2moe.carry_bytes_per_lane(cfg.model) == 8 + 3072 * 2 * 512 * 2 + 4 * 2 * 2048 * 2 == 6_324_232


def test_the_operations_follow_the_position_and_the_pairs_and_the_passes_follow_the_program(cell_rc):
    near, far = (flops_lfm2moe.step_flops(cell_rc, p, 1.0) for p in (99.0, 1999.0))
    assert near["conv"] == far["conv"] == 2 * 4 * 4 * 2048 * 2048
    assert near["attn"] - 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) == 2 * 32 * 100 * 2 * 64
    assert far["attn"] - near["attn"] == 2 * 32 * 1900 * 2 * 64
    assert near["routed_experts"] == 2 * 3 * 2048 * 1536 and near["router_and_shared"] == 2 * 4 * 2048 * 64
    lanes = opp = 80
    steps = 160 * 16 + 80 * 17 + 2 * 80 * 16
    assert flops_lfm2moe.train_flops_per_frame(cell_rc, lanes, opp, 16, 99.0, 1.0) == sum(near.values()) * steps / (80 * 16)
    # the weights' bytes: 16 passes + 3 where every dispatch shares its pass, 32 + 3 where none does
    assert flops_lfm2moe.weight_passes(16, 1.0) == 19.0 and flops_lfm2moe.weight_passes(16, 0.0) == 35.0
    whole = flops_lfm2moe.weight_bytes_per_dispatch(cell_rc, 16, 1.0)
    assert round(whole / 19 / 1e9, 3) == 0.905                     # ISSUE 37: "0.9 GB of bfloat16 a pass"
    assert flops_lfm2moe.weight_bytes_per_dispatch(cell_rc, 16, 1.0, held_touched=0.0) == whole - 19 * 2 * 4 * 8 * 3 * 2048 * 1536
    with pytest.raises(flops.UnsupportedShape, match="LFM2"):
        flops_lfm2moe.step_flops({**cell_rc, "model": {**cell_rc["model"], "core": "afmoe"}}, 1.0, 1.0)
