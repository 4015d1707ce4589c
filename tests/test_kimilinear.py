"""The Kimi-Linear core (``models/kimilinear.py``) against its plain reference
(``benchmark/reference/kimilinear_ref.py``) at tiny widths on the CPU: hidden
32, five layers (KDA dense, three KDA and one MLA with experts), 2 heads of
8, a latent of 16 + 4, 16 experts 2 a token with 2 held, full_context 24,
chunks of T = 4, float32, seeded weights.

The program runs chunk by chunk (the delta rule in closed form, the states
handed on) or step by step (the recurrence) through its carry; the reference
takes each lane's whole history at once, the recurrence a position at a
time and latent attention expanded a head. The reference made wrong in one
way at a time must DISAGREE: a comparison that would pass with the decay or
beta left out, a tap shifted, a state kept across an episode's end, the
latent unnormalised or a rotation applied pins nothing.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import flops, flops_kimilinear, obs as obs_mod
from benchmark.reference import afmoe_ref, kimilinear_ref, policy_ref
from dotaclient_tpu.config import default_config
from dotaclient_tpu.models import afmoe, distributions as D, init_params, kimilinear
from dotaclient_tpu.models.policy import Policy, require_carry_stays, require_episode_fits
from dotaclient_tpu.train.ppo import ppo_loss
from tests.test_afmoe import _scaled, run_config, step_by_step, through_chunks

B, S, T = 3, 72, 4
AGREE, DIFFER = 1e-4, 1e-2
KDA_LAYERS, MLA_LAYERS = 4, 1

SIZES = dict(
    core="kimilinear", hidden_dim=32, n_layers=5, n_heads=2, kda_head_dim=8, kda_conv_kernel=4,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, full_context=24,
    rollout_chunk=T, global_attn_every=4, global_attn_offset=3, n_dense_layers=1, dense_ffn_dim=48,
    expert_ffn_dim=16, moe_experts=16, experts_per_token=2, held_experts=2, expert_offset=0,
    route_scale=2.446, mup_enabled=False, dtype="float32",
)


def tiny_model(**over):
    return dataclasses.replace(default_config().model, **{**SIZES, **over})


def perturbed(params, seed=11):
    """Seeded weights with every norm scale, the selection bias and the decay's
    bias moved off their initial values, so that a test can see them."""
    rng = np.random.default_rng(seed)

    def move(path, leaf):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1.0 + 0.3 * rng.standard_normal(leaf.shape).astype(np.float32))
        if "select_bias" in name:
            return leaf + 0.2 * rng.standard_normal(leaf.shape).astype(np.float32)
        if "dt_bias" in name:
            return leaf + 3.0          # a decay far enough from 1 to matter over four steps
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
    dones[0, [9, 29, 49, 69]] = 1             # mid-chunk
    dones[1, [13, 14, 33, 52, 71]] = 1        # two ends in a row; one on the history's last step
    dones[2, [19, 39, 59]] = 1                # on a chunk's last step: the next chunk starts void
    return {"policy": policy, "params": params, "rc": rc, "obs": obs, "dones": dones}


def worst(got, want):
    """Largest difference of logits and values, relative to the outputs' size
    (as ``compare_kimilinear`` reports it)."""
    want = {"l": want[0], "v": want[1]}
    scale = max(1.0, policy_ref.max_abs_diff(want, jax.tree.map(jnp.zeros_like, want)))
    return policy_ref.max_abs_diff({"l": got[0], "v": got[1]}, want) / scale


@pytest.fixture(scope="module")
def program_out(world):
    return through_chunks(world["policy"], world["params"], world["obs"], world["dones"])


@pytest.fixture(scope="module")
def reference_out(world):
    return kimilinear_ref.history(world["params"], world["obs"], world["dones"], world["rc"]["model"])


# -- the delta rule: the closed form over a chunk is the recurrence ------------------


def _recurrence(q, k, v, log_alpha, beta, S0, starts):
    """The KDA recurrence a step at a time, in plain ``jnp``: ``starts [B,
    T]`` 1 where an episode starts AT step t (the state is void there)."""
    S, outs = S0, []
    for t in range(q.shape[1]):
        S = jnp.where(starts[:, t, None, None, None] > 0, 0.0, S)
        S = jnp.exp(log_alpha[:, t])[..., None] * S
        seen = jnp.einsum("bhk,bhkv->bhv", k[:, t], S)
        S = S + beta[:, t, :, None, None] * k[:, t, :, :, None] * (v[:, t] - seen)[:, :, None, :]
        outs.append(jnp.einsum("bhk,bhkv->bhv", q[:, t], S))
    return jnp.stack(outs, axis=1), S


def _delta_case(steps, seed=0):
    rng = np.random.default_rng(seed)
    lanes, heads, d = 2, 2, 8
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    q, k = f(lanes, steps, heads, d), f(lanes, steps, heads, d)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_alpha = -jnp.exp(f(lanes, steps, heads, d))          # some channels forget almost everything
    beta = jax.nn.sigmoid(f(lanes, steps, heads))
    return q, k, f(lanes, steps, heads, d), log_alpha, beta, f(lanes, heads, d, d)


@pytest.mark.parametrize("steps,resets", [
    (16, ()), (16, (0,)), (16, (7,)), (16, (15,)), (16, (3, 9)), (17, (5,)), (3, (1,)), (1, ()), (1, (0,)),
])
def test_the_closed_form_over_a_chunk_is_the_recurrence_outputs_state_and_gradients(steps, resets):
    """Resets at t = 0, mid-chunk, t = 15 and twice in a chunk; the learner's
    17 steps; the rollout's T = 1 (few rows: the state is read where it lies)."""
    args = _delta_case(steps)
    starts = np.zeros((2, steps), np.float32)
    starts[0, list(resets)] = 1.0                            # lane 1 runs unbroken
    seg = jnp.cumsum(jnp.asarray(starts, jnp.int32), axis=1)
    weigh = _delta_case(steps, seed=1)

    def scalar(fn, *a):
        o, S_end = fn(*a)
        return (o * weigh[2]).sum() + (S_end * weigh[5]).sum()

    closed = lambda *a: kimilinear.delta_rule_chunk(*a, seg, jnp.ones((2,), bool))
    plain = lambda *a: _recurrence(*a, jnp.asarray(starts))
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(lambda *b: scalar(fn, *b), argnums=range(6))(*a)))
    (got_out, got_grads), (want_out, want_grads) = both(closed)(*args), both(plain)(*args)
    for got, want in zip(got_out, want_out):
        assert float(jnp.abs(got - want).max()) < 2e-5
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta", "S0"), got_grads, want_grads):
        assert float(jnp.abs(g - w).max()) < 1e-4 * max(1.0, float(jnp.abs(w).max())), name
    if 0 in resets:
        assert float(jnp.abs(got_grads[5][0]).max()) == 0.0 < float(jnp.abs(got_grads[5][1]).max())


# -- step, sequence and reference agree ------------------------------------------


def test_sequence_agrees_with_the_reference_over_resets_and_eighteen_chunks(world, reference_out, program_out):
    """72 steps in chunks of 4: episode ends mid-chunk, twice in a row and on a
    chunk's last step; the states are handed through 18 chunks."""
    assert worst(program_out, reference_out) < AGREE
    carry = program_out[2]
    np.testing.assert_array_equal(np.asarray(carry["cursor"]), S % 24)
    np.testing.assert_array_equal(np.asarray(carry["pos"]), [S - 70, S - 53, S - 60])
    assert len(carry["latent"]) == MLA_LAYERS and len(carry["kda"]) == KDA_LAYERS


def test_chunks_of_eight_steps_or_more_take_the_products_and_agree(world, reference_out):
    """The learner's chunks at the cell's sizes (17 steps) multiply the state
    on the MXU, a step and the short chunks above reduce it where it lies."""
    assert 2 * T >= afmoe._MXU_ROWS > 2 * 1 and 2 * 9 >= afmoe._MXU_ROWS
    got = through_chunks(world["policy"], world["params"], world["obs"], world["dones"], chunk=9)
    assert worst(got, reference_out) < AGREE


def test_step_by_step_agrees_with_the_reference_and_with_the_chunks(world, reference_out, program_out):
    logits, values, carry = step_by_step(world["policy"], world["params"], world["obs"], world["dones"])
    assert worst((logits, values), reference_out) < AGREE
    for a, b in zip(jax.tree.leaves(carry["kda"]), jax.tree.leaves(program_out[2]["kda"])):
        assert float(jnp.abs(a - b).max()) < 1e-4
    np.testing.assert_array_equal(np.asarray(carry["pos"]), np.asarray(program_out[2]["pos"]))


def test_reference_imports_nothing_from_the_program():
    import pathlib

    text = pathlib.Path(kimilinear_ref.__file__).read_text()
    assert "import dotaclient_tpu" not in text and "from dotaclient_tpu" not in text


def test_resets_are_where_the_program_puts_them(world, program_out):
    model = world["rc"]["model"]
    none = kimilinear_ref.history(world["params"], world["obs"], 0 * world["dones"], model)
    shifted = kimilinear_ref.history(world["params"], world["obs"], np.roll(world["dones"], 1, axis=1), model)
    assert worst(program_out, none) > DIFFER and worst(program_out, shifted) > DIFFER


# -- the reference made wrong in one way must disagree ----------------------------


@pytest.mark.parametrize("fault", kimilinear_ref.FAULTS)
def test_reference_wrong_in_one_way_disagrees(world, program_out, fault):
    wrong = kimilinear_ref.history(world["params"], world["obs"], world["dones"], world["rc"]["model"], fault=fault)
    assert worst(program_out, wrong) > DIFFER, fault


def _model(**over):
    return lambda model, params: ({**model, **over}, params)


ABLATIONS = {
    "assumed_convolution": _scaled("conv", 0.5),
    "assumed_decay_rate": _scaled("A_log", 0.0),
    "assumed_decay_bias": _scaled("dt_bias", 0.0),
    "assumed_output_norm": _scaled("o_norm", 2.0),
    "assumed_output_gate": _scaled("wg_up", 0.0),
    "assumed_latent_norm": _scaled("kv_norm", 2.0),
    "one_kda_layer_is_mla": _model(global_attn_every=10 ** 6),
    "route_norm": _model(route_norm=False),
    "route_scale": _model(route_scale=1.0),
    "shared_expert": _scaled("shared']['down_proj", 0.0),
    "assumed_selection_bias": _scaled("select_bias", 0.0),
    "held_experts_only": _model(held_experts=1),
}


@pytest.mark.parametrize("name", sorted(ABLATIONS))
def test_reference_without_the_mechanism_disagrees(world, program_out, name):
    model, params = ABLATIONS[name](world["rc"]["model"], world["params"])
    if name == "one_kda_layer_is_mla":
        with pytest.raises(KeyError):                       # no MLA layer: layer 4's weights are a KDA layer's
            kimilinear_ref.history(params, world["obs"], world["dones"], model)
        return
    without = kimilinear_ref.history(params, world["obs"], world["dones"], model)
    assert worst(program_out, without) > DIFFER, name


def test_absorbed_latent_attention_is_the_expanded_reference_s(world):
    """One MLA layer alone, chunk by chunk through its ring against
    ``kimilinear_ref.mla`` over the whole history, and the ring holds the
    normalised latent and the unrotated shared key part, 20 numbers a row."""
    model, rc = world["policy"].model, world["rc"]["model"]
    layer = kimilinear.LatentAttention(model)
    a = jax.random.normal(jax.random.PRNGKey(5), (B, 20, model.hidden_dim))
    ring = jnp.zeros((B, 24, 20))
    pos0 = cursor0 = jnp.zeros((B,), jnp.int32)
    seg = jnp.zeros((B, T), jnp.int32)
    p = perturbed(layer.init(jax.random.PRNGKey(6), a[:, :T], ring, pos0, cursor0, seg))
    outs = []
    for c0 in range(0, 20, T):
        out, ring = layer.apply(p, a[:, c0:c0 + T], ring, pos0 + c0, cursor0 + c0, seg)
        outs.append(out)
    episode, pos = afmoe_ref.episodes(jnp.zeros((B, 20)))
    want = kimilinear_ref.mla(p["params"], a, episode, pos, rc)
    assert float(jnp.abs(jnp.concatenate(outs, axis=1) - want).max()) < 2e-5
    kv = a @ p["params"]["wkv_a"]["kernel"]
    latent = afmoe_ref.rms_norm(p["params"]["kv_norm"], kv[..., :16], rc["rms_norm_eps"])
    np.testing.assert_allclose(np.asarray(ring[:, :20]), np.asarray(jnp.concatenate([latent, kv[..., 16:]], -1)), atol=1e-5)


# -- the carry: reset, void reads, chunk-start view, what must fit -------------------


def test_reset_through_the_core_touches_no_leaf(world, program_out):
    policy, carry = world["policy"], program_out[2]
    keep = jnp.asarray([1.0, 0.0, 1.0])
    after = policy.reset_carry(carry, keep)
    for before_l, after_l in zip(jax.tree.leaves((carry["latent"], carry["kda"])), jax.tree.leaves((after["latent"], after["kda"]))):
        assert before_l is after_l
    assert after["cursor"] is carry["cursor"]
    np.testing.assert_array_equal(np.asarray(after["pos"]), np.asarray(carry["pos"]) * np.asarray([1, 0, 1]))
    # under jit: rings, states and convolution rows leave as they came, no operation reads them
    jaxpr = jax.make_jaxpr(policy.reset_carry)(carry, keep).jaxpr
    big = {v for v in jaxpr.invars if len(v.aval.shape) >= 3}
    assert len(big) == MLA_LAYERS + 2 * KDA_LAYERS and sum(v in big for v in jaxpr.outvars) == len(big)
    ids = {id(v) for v in big}
    assert not any(id(v) in ids for eqn in jaxpr.eqns for v in eqn.invars)


def test_a_void_read_ignores_a_poisoned_state(world, program_out):
    """A lane at position 0 reads its states, its convolution rows and its ring
    as void: NaN in every state and row of that lane (and garbage in its ring)
    changes nothing, outputs and gradients; the same poison in a lane that
    carries on is seen."""
    policy, params, carry = world["policy"], world["params"], program_out[2]
    chunk = {k: v[:, :T] for k, v in world["obs"].items()}
    # (the ring's rows are hidden by the mask and weighed 0, as the afmoe core's: finite garbage, not NaN)
    poison = lambda lanes: {
        **carry, "kda": jax.tree.map(lambda x: x.at[lanes].set(jnp.nan), carry["kda"]),
        "latent": jax.tree.map(lambda x: x.at[lanes].set(1e4), carry["latent"]),
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
    for a, b in zip(jax.tree.leaves(clean_end["kda"]), jax.tree.leaves(dirty_end["kda"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))       # and the new state is clean
    (_, (seen, _)), _ = run(params, reset(poison(0)))
    assert np.isnan(np.asarray(seen[0])).all() and np.isfinite(np.asarray(seen[1:])).all()


def test_chunk_start_view_is_the_start_s_states_beside_the_end_s_ring(world):
    """What the learner is handed: the start's counters, states and
    convolution rows (its own buffers) beside the END's latent ring (no copy). A
    chunk read from it equals the chunk read from the real start, mid-chunk
    resets included."""
    policy, params = world["policy"], world["params"]
    obs, dones = world["obs"], world["dones"]
    start = through_chunks(policy, params, {k: v[:, :12] for k, v in obs.items()}, dones[:, :12])[2]
    start = policy.reset_carry(start, 1.0 - dones[:, 11])
    chunk = {k: v[:, 12:16] for k, v in obs.items()}                 # lane 1 ends at 13 and 14
    seq = jax.jit(lambda c: policy.apply(params, chunk, c, dones[:, 12:16], method="sequence"))
    lg, v, end = seq(start)
    view = policy.chunk_start_carry(start, end)
    assert all(a is b for a, b in zip(view["latent"], end["latent"]))
    assert all(a is b for a, b in zip(jax.tree.leaves(view["kda"]), jax.tree.leaves(start["kda"])))
    assert view["pos"] is start["pos"] and view["cursor"] is start["cursor"]
    assert all(x.dtype == jnp.float32 for x, _ in view["kda"])
    lg2, v2, _ = seq(view)
    assert policy_ref.max_abs_diff({"l": lg2, "v": v2}, {"l": lg, "v": v}) < 1e-6
    # the end's states would NOT do: they were overwritten
    lg3, v3, _ = seq({**view, "kda": end["kda"]})
    assert policy_ref.max_abs_diff({"l": lg3, "v": v3}, {"l": lg, "v": v}) > DIFFER


def test_what_the_carry_holds_what_must_fit_and_the_refusals_name_the_core():
    model = tiny_model()
    state = 2 * 8 * 8 * 4 + 3 * 3 * 16 * 4
    assert kimilinear.carry_bytes_per_lane(model) == 8 + KDA_LAYERS * state + MLA_LAYERS * 24 * 20 * 4
    assert flops_kimilinear.carry_bytes_per_lane(dataclasses.asdict(model)) == kimilinear.carry_bytes_per_lane(model)
    carry = kimilinear.initial_state(model, B)
    assert [x.shape for x in carry["latent"]] == [(B, 24, 20)]
    assert [(s.shape, s.dtype, h.shape) for s, h in carry["kda"]] == [((B, 2, 8, 8), jnp.float32, (B, 3, 48))] * 4
    require_episode_fits(model, episode_steps=20, rollout_len=T)
    with pytest.raises(ValueError, match="'kimilinear'.*full_context"):
        require_episode_fits(model, episode_steps=21, rollout_len=T)
    with pytest.raises(ValueError, match="'kimilinear'.*rollout_chunk"):
        require_episode_fits(model, episode_steps=10, rollout_len=T + 1)
    with pytest.raises(ValueError, match=f"core 'kimilinear' carries {kimilinear.carry_bytes_per_lane(model):,} bytes"):
        require_carry_stays(model, "actor mode 'device'")
    assert model.carry_stays_on_chip and model.carry_is_rings      # the old name, for benchmark/tests
    # at the published widths: four states of 2 MiB and their rows, one ring of 576 numbers a position
    from benchmark.harness import cells, program

    cfg = program.build_run_config(cells.load_cell("kimi-linear-5v5-ep32.fused-selfplay-anycore"), seed=0, rehearsal=False)
    assert kimilinear.carry_bytes_per_lane(cfg.model) == 8 + 4 * (2 ** 21 + 73_728) + 3072 * 1152 == 12_222_472
    assert kimilinear.kda_layers(cfg.model) == [0, 1, 2, 3] and kimilinear.mla_layers(cfg.model) == [4]


@pytest.mark.parametrize("over", [{"mup_enabled": True}, {"loop_steps": 2}, {"kda_conv_kernel": 1}, {"experts_per_token": 32}])
def test_a_configuration_the_core_does_not_run_is_refused(over):
    cfg = default_config()
    policy = Policy(tiny_model(**over), cfg.obs, cfg.actions)
    with pytest.raises(ValueError, match="kimilinear"):
        init_params(policy, jax.random.PRNGKey(0))


def test_the_parameters_are_the_published_layers_at_toy_widths(world):
    core = world["params"]["params"]["core"]
    assert sorted(core) == ["layer_0", "layer_1", "layer_2", "layer_3", "layer_4", "out_norm"]
    assert sorted(core["layer_0"]) == ["ffn", "in_norm", "kda", "pre_mlp_norm"]           # dense, no post-norm
    assert sorted(core["layer_3"]) == ["in_norm", "kda", "moe", "pre_mlp_norm"]
    assert sorted(core["layer_4"]) == ["attn", "in_norm", "moe", "pre_mlp_norm"]
    assert sorted(core["layer_1"]["kda"]) == [
        "A_log", "conv", "dt_bias", "o_norm", "wb", "wf_down", "wf_up", "wg_down", "wg_up", "wk", "wo", "wq", "wv",
    ]
    assert sorted(core["layer_4"]["attn"]) == ["kv_norm", "wkv_a", "wo", "wq", "wuk", "wuv"]
    assert core["layer_4"]["moe"]["router"].shape == (32, 16) and core["layer_4"]["moe"]["expert_gate"].shape == (2, 32, 16)
    alpha = np.exp(-np.exp(np.asarray(core["layer_1"]["kda"]["A_log"]))[:, None] * np.log1p(np.exp(
        np.asarray(init_params(world["policy"], jax.random.PRNGKey(0))["params"]["core"]["layer_1"]["kda"]["dt_bias"]).reshape(2, 8)
    )))
    assert 0.15 < alpha.min() and alpha.max() < 0.9995          # seeded: a step keeps 20% to 99.9% of a channel


# -- the expert layer at 16 outputs: the shares add up --------------------------------


def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Eight chips hold experts 0-1, 2-3, ... of sixteen (the cell: 32 chips,
    8 of 256): what they compute, the shared expert counted once, is the
    whole layer's output, at Kimi's scale 2.446."""
    model = tiny_model(held_experts=0)
    layer = afmoe.RoutedExperts(model)
    m = jax.random.normal(jax.random.PRNGKey(3), (B, T, model.hidden_dim))
    params = layer.init(jax.random.PRNGKey(4), m)["params"]
    whole = afmoe_ref.experts(params, m, dataclasses.asdict(model))[0]
    shared = afmoe_ref.swiglu(params["shared"], m)

    def share(offset):
        cut = dataclasses.replace(model, held_experts=2, expert_offset=offset)
        held = {**params, **{k: params[k][offset:offset + 2] for k in ("expert_gate", "expert_up", "expert_down")}}
        return afmoe.RoutedExperts(cut).apply({"params": held}, m, mutable=["losses"])[0]

    parts = [share(off) for off in range(0, 16, 2)]
    assert float(jnp.abs(sum(part - shared for part in parts) + shared - whole).max()) < 2e-5
    assert all(float(jnp.abs(part - whole).max()) > 1e-3 for part in parts)


# -- one PPO step: loss, gradients and the gauges ------------------------------------


@pytest.fixture(scope="module")
def ppo_case():
    """A learner's batch: the LAST chunk of a history (carry0 = the states and
    the ring the earlier chunks left, as the fused program hands them: the
    chunk-start view; T + 1 observations)."""
    cfg = default_config()
    ppo = dataclasses.replace(cfg.ppo, rollout_len=T, moe_aux_coef=0.0)
    model_cfg = tiny_model()
    policy = Policy(model_cfg, cfg.obs, cfg.actions)
    params = perturbed(init_params(policy, jax.random.PRNGKey(1)))
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
    got = jax.jit(jax.value_and_grad(lambda p: ppo_loss(policy, p, batch, ppo), has_aux=True))(params)
    return ppo, policy, params, rc["model"], obs, dones, batch, got


def _lpe(logits, o, actions):
    return D.log_prob(logits, o, actions), D.entropy(logits, o)


KNOBS = ("gamma", "gae_lambda", "clip_eps", "entropy_coef", "value_coef", "moe_aux_coef")


def test_ppo_loss_and_gradients_agree_with_the_reference(ppo_case):
    """The learner's pass (the closed form's backward, rematerialised) against
    ``jax.grad`` of the reference's loss over the whole history with what the
    earlier steps left as data."""
    ppo, policy, params, model, obs, dones, batch, ((got_loss, metrics), got_grads) = ppo_case
    knobs = {k: getattr(ppo, k) for k in KNOBS}
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: kimilinear_ref.ppo_loss(p, obs, dones, batch, model, knobs, _lpe)
    ))(params)
    assert abs(float(got_loss) - float(want_loss)) < 1e-4 * max(1.0, abs(float(want_loss)))
    flat_got = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_want = jax.tree.leaves(want_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, jax.tree_util.keystr(path)
    kda = got_grads["params"]["core"]["layer_1"]["kda"]
    for name in ("A_log", "dt_bias", "conv", "wb"):
        assert float(jnp.abs(jax.tree.leaves(kda[name])[0]).max()) > 1e-7, name
    moe = got_grads["params"]["core"]["layer_2"]["moe"]
    assert float(jnp.abs(moe["select_bias"]).max()) == 0.0 < float(jnp.abs(moe["router"]).max())


@pytest.mark.parametrize("fault", ["no_decay", "state_kept_across_reset", "rotation_applied"])
def test_the_loss_of_a_reference_wrong_in_one_way_disagrees(ppo_case, fault):
    ppo, policy, params, model, obs, dones, batch, ((got_loss, _), got_grads) = ppo_case
    knobs = {k: getattr(ppo, k) for k in KNOBS}
    wrong_loss, wrong_grads = jax.jit(jax.value_and_grad(
        lambda p: kimilinear_ref.ppo_loss(p, obs, dones, batch, model, knobs, _lpe, fault=fault)
    ))(params)
    flat_want = jax.tree.leaves(wrong_grads)
    scale = max(float(jnp.abs(w).max()) for w in flat_want)
    worst_grad = max(float(jnp.abs(g - w).max()) for g, w in zip(jax.tree.leaves(got_grads), flat_want))
    assert abs(float(got_loss) - float(wrong_loss)) > 1e-3 or worst_grad > 1e-2 * scale, fault


def test_the_step_s_metrics_carry_the_kda_gauges_and_the_layer_s_counts(ppo_case):
    ppo, policy, params, model, obs, dones, batch, ((_, metrics), _) = ppo_case
    assert 0.0 < float(metrics["kda_decay_mean"]) < 1.0 and 0.0 < float(metrics["kda_beta_mean"]) < 1.0
    assert float(metrics["kda_state_rms"]) > 0.0
    # lane 1 starts an episode at step 2 of the chunk, lane 2 at its step 0: two starts x 4 KDA layers
    assert float(metrics["kda_void_reads"]) == 2 * KDA_LAYERS
    assert float(metrics["moe_dropped_assignments"]) == 0.0
    assert 0.0 < float(metrics["moe_local_assignments"]) < 3 * (T + 1) * 2 * 4
    # a core that sows none of them gets none
    from dotaclient_tpu.train.ppo import _kda_gauges

    assert _kda_gauges({}, batch["valid"]) == {}
    assert _kda_gauges({"core": {"layer_1": {"moe": {"moe_load": (jnp.ones(2),)}}}}, batch["valid"]) == {}


# -- the step against states and ring, compiled for the chip (no chip needed) ----------


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


def _detours(text, lanes, model):
    """In a compiled step's text: a KDA state in another type or layout than it
    is kept in (a rounded copy for a product, a transpose), a copy of one, and
    lane-major arrays of the ring's size in float32. (Not counted: the ring's
    own layout. Compiled alone for a described chip the step's PARAMETER
    layout is the compiler's to choose, and it takes the ring position-minor
    with a copy either side of the scatter; the fused program's loop carries
    the ring as the program lays it out and copies nothing: PERF.md section 6.)"""
    nh, d = model.n_heads, model.kda_head_dim
    state = rf"\[{lanes},{nh},{d},{d}\]"
    rounded = re.findall(rf"= bf16{state}", text)
    moved = re.findall(rf"= f32{state}{{(?!3,2,1,0)", text)
    copied = re.findall(rf"= f32{state}{{[0-9,]+[^}}]*}} copy\(", text)
    ring = lanes * model.full_context * kimilinear.latent_width(model)
    widened = [
        s for s in re.findall(rf"= f32\[({lanes},[0-9,]+)\]", text) if np.prod([int(n) for n in s.split(",")]) >= ring
    ]
    return {"rounded": len(rounded), "moved": len(moved), "copied": len(copied), "widened": len(widened)}


@pytest.mark.parametrize("few_rows", [True, False])
def test_a_step_at_the_cells_widths_reads_states_and_ring_as_they_lie(one_chip, monkeypatch, few_rows):
    """``Policy.step`` at Kimi-Linear's widths, 5 lanes, compiled for the v5e:
    no state is rounded into a second buffer, transposed or copied, and the
    latent ring is not widened to float32. Through the product
    alone (two rows a head) every KDA layer's state is rounded to bfloat16 into
    a new buffer, every step."""
    from benchmark.harness import cells, program
    from dotaclient_tpu.models.policy import dummy_obs_batch, make_policy

    if not few_rows:
        monkeypatch.setattr(afmoe, "_MXU_ROWS", 0)
        jax.clear_caches()               # ``jax.checkpoint`` keeps the trace made with the real constant
    cfg = program.build_run_config(cells.load_cell("kimi-linear-5v5-ep32.fused-selfplay-anycore"), seed=0, rehearsal=False)
    policy, lanes = make_policy(cfg.model, cfg.obs, cfg.actions), 5
    shapes = jax.eval_shape(lambda: (
        init_params(policy, jax.random.PRNGKey(0)), dummy_obs_batch(lanes, cfg.obs, cfg.actions),
        policy.initial_state(lanes),
    ))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    text = jax.jit(
        lambda p, o, c: policy.apply(p, o, c, method="step"), donate_argnums=(2,)
    ).lower(*args).compile().as_text()
    found = _detours(text, lanes, cfg.model)
    if few_rows:
        assert found == {"rounded": 0, "moved": 0, "copied": 0, "widened": 0}
    else:
        assert found["rounded"] >= KDA_LAYERS


def test_the_step_compiled_for_the_chip_is_one_kernel_a_kda_layer_under_the_state_s_scope(one_chip):
    """ISSUE 36: at the cell's widths a step lowered for the v5e calls the
    Pallas kernel once a KDA layer, each call named under ``core_kda_state``
    (what ``kda_state_roofline``'s reader matches as a whole path segment:
    an unscoped call would leave the roofline without its time), writes the
    state where it read it, and leaves no multiply-and-reduce over a state;
    the chunk the learner runs keeps the closed form."""
    from benchmark.harness import cells, program
    from dotaclient_tpu.models.policy import dummy_obs_batch, make_policy

    cfg = program.build_run_config(cells.load_cell("kimi-linear-5v5-ep32.fused-selfplay-anycore"), seed=0, rehearsal=False)
    assert kimilinear.step_takes_kernel(cfg.model, "tpu") and not kimilinear.step_takes_kernel(cfg.model, "cpu")
    policy, lanes = make_policy(cfg.model, cfg.obs, cfg.actions), 5
    shapes = jax.eval_shape(lambda: (
        init_params(policy, jax.random.PRNGKey(0)), dummy_obs_batch(lanes, cfg.obs, cfg.actions),
        policy.initial_state(lanes),
    ))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    text = jax.jit(
        lambda p, o, c: policy.apply(p, o, c, method="step"), donate_argnums=(2,)
    ).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln and "kda_step" in ln]
    assert len(calls) == KDA_LAYERS
    nh, d = cfg.model.n_heads, cfg.model.kda_head_dim
    for ln in calls:
        assert "/core_kda_state/" in re.search(r'op_name="([^"]*)"', ln).group(1)
        assert "output_to_operand_aliasing={{1}: (6, {})}" in ln, ln[-400:]
    assert not re.findall(rf"f32\[{lanes},{nh},{d},{d}\][^ ]* (?:reduce|fusion)\(", text)
    assert " conditional(" not in text


def test_the_kernel_steps_counter_moves_by_layers_x_steps_a_dispatch_where_the_kernel_ran(monkeypatch):
    """``kda/kernel_steps_total`` is in the logged step's telemetry and moves
    by KDA layers x rollout steps a dispatch where the model's predicate says
    kernel (patched to leave the platform out: the kernel interpreted, one
    head of 128); by nothing on this CPU, where the closed form runs, nor for
    a core without such layers."""
    from dotaclient_tpu.parallel import make_mesh
    from dotaclient_tpu.train import learner as learner_mod
    from dotaclient_tpu.utils import telemetry
    from tests.test_fused_kimilinear import kimilinear_cfg
    from tests.test_kda_step_kernel import _kernel_everywhere

    cfg = kimilinear_cfg(selfplay_prob=1.0)
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, kda_head_dim=128, n_heads=1), env=dataclasses.replace(cfg.env, n_envs=1)
    )
    mesh = make_mesh(cfg.mesh)
    assert learner_mod._kda_kernel_steps(cfg, mesh) == 0                     # the real predicate, a CPU
    assert learner_mod._kda_kernel_steps(default_config(), mesh) == 0        # the LSTM: no such layers
    monkeypatch.setattr(kimilinear, "step_takes_kernel", _kernel_everywhere)
    assert learner_mod._kda_kernel_steps(cfg, mesh) == KDA_LAYERS * cfg.ppo.rollout_len
    before = telemetry.get_registry().snapshot()
    out = learner_mod.Learner(cfg, actor="fused", seed=1).train(3)
    assert np.isfinite(out["loss"]) and out["health_ok"] == 1.0
    snap = telemetry.get_registry().snapshot()
    assert snap["learner/dispatches_total"] - before.get("learner/dispatches_total", 0.0) == 3
    assert snap["kda/kernel_steps_total"] - before.get("kda/kernel_steps_total", 0.0) == 3 * KDA_LAYERS * cfg.ppo.rollout_len


# -- the counts, by hand -------------------------------------------------------------


@pytest.fixture(scope="module")
def cell_rc():
    from benchmark.harness import cells, program

    return program.merged_run_config(cells.load_cell("kimi-linear-5v5-ep32.fused-selfplay-anycore"), False)


def test_the_weights_counted_are_the_published_layers(cell_rc):
    """KDA mixer 39.5 M, MLA mixer 29.1 M, an expert 7.08 M, the dense FFN
    63.7 M, a router 0.59 M (ISSUE 32's arithmetic), and the program's tree
    holds them (plus norms, taps and biases: under a thousandth)."""
    w = flops_kimilinear.core_weight_count(cell_rc["model"])
    assert w["kda"] == 4 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32) and round(w["kda"] / 4e6, 1) == 39.5
    assert w["mla"] == 2304 * 32 * 192 + 2304 * 576 + 4096 * 2304 + 32 * 512 * 256 and round(w["mla"] / 1e6, 1) == 29.1
    assert w["dense_ffn"] == 3 * 2304 * 9216 and w["router"] == 4 * 2304 * 256
    assert w["shared"] == 4 * 3 * 2304 * 1024 and w["routed"] == 8 * w["shared"]
    from benchmark.harness import cells, program
    from dotaclient_tpu.models import make_policy

    cfg = program.build_run_config(cells.load_cell("kimi-linear-5v5-ep32.fused-selfplay-anycore"), seed=0, rehearsal=False)
    policy = make_policy(cfg.model, cfg.obs, cfg.actions)
    tree = jax.eval_shape(lambda: init_params(policy, jax.random.PRNGKey(0)))["params"]["core"]
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert 0 < held - sum(w.values()) < 1e-3 * held and round(held / 1e6) == 508


def test_the_recurrence_s_work_is_three_products_and_two_passes_over_the_states(cell_rc):
    lanes = opp = 40
    work = flops_kimilinear.kda_state_work(cell_rc, lanes, opp, 16)
    steps = 80 * 16 + 40 * 17 + 2 * 40 * 16                      # forward-pass equivalents
    assert work["flops"] == 2 * (4 * 3 * 32 * 128 * 128) * steps
    state = 32 * 128 * 128 * 4
    assert work["state_bytes_per_lane_layer"] == state == 2 ** 21
    assert work["state_bytes"] == 4 * state * (2 * 80 * 16 + 2 * 40)      # read and written a rollout step; read twice by the update
    assert work["row_bytes"] == 4 * (5 * 4096 + 32) * 4 * steps
    assert round(work["state_bytes"] / 1e9, 1) == 22.1                    # ISSUE 32: "KDA states ... 22 GB"
    peaks = flops.peaks_for("TPU v5 lite")
    least = flops.roofline_seconds(work, peaks, "bfloat16")
    assert least["bound"] == "memory" and least["compute_s"] < 0.05 * least["memory_s"]


def test_the_latent_products_follow_the_lanes_position(cell_rc):
    near, far = (flops_kimilinear.latent_attend_work(cell_rc, 40, 40, 16, p) for p in (99.0, 1999.0))
    steps = 80 * 16 + 40 * 17 + 2 * 40 * 16
    assert near["flops"] == 2 * 32 * 100 * (2 * 512 + 64) * steps and far["flops"] == 20 * near["flops"]
    assert near["seen_ring_bytes_per_lane"] == 100 * 576 * 2
    assert near["ring_bytes"] == 100 * 576 * 2 * (80 * 16 + 2 * 40)
    assert near["row_bytes"] == far["row_bytes"] == 32 * 576 * 6 * steps
    per_frame = flops_kimilinear.train_flops_per_frame(cell_rc, 40, 40, 16, 1999.0, 1.0)
    parts = flops_kimilinear.step_flops(cell_rc, 1999.0, 1.0)
    assert per_frame == sum(parts.values()) * steps / (40 * 16)
    assert parts["kda"] > parts["mla"] > parts["routed_experts"] > 0 and parts["dense_ffn"] == 2 * 3 * 2304 * 9216


def test_the_counts_refuse_another_core(cell_rc):
    other = {**cell_rc, "model": {**cell_rc["model"], "core": "afmoe"}}
    with pytest.raises(flops.UnsupportedShape, match="Kimi-Linear"):
        flops_kimilinear.kda_state_work(other, 40, 40, 16)
