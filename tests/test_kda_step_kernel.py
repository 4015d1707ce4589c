"""The KDA step kernel (``ops/pallas/kda_step.py``, ISSUE 36) in interpreter
mode on the CPU, as ``tests/test_pallas.py`` holds the LSTM kernel: one step
of the delta rule against its specification, ``kimilinear.delta_rule_chunk``
at T = 1, at heads of 128 x 128; through ``KDA`` over a plain carry and over
a ``LaneBlocks`` of two lane sets; sixteen steps in a row against one chunk
of sixteen; the gradient through the ``custom_vjp``; and which path a
configuration, a chunk length and a platform get. (Compiled by Mosaic: the
step at the cell's widths in ``tests/test_kimilinear.py``, for a described
v5e, and ``chip_smoke.py`` phase f on the chip.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.models import kimilinear
from dotaclient_tpu.models.kimilinear import delta_rule_chunk, delta_rule_step
from dotaclient_tpu.models.lanes import LaneBlocks
from dotaclient_tpu.ops.pallas import kda_step_pallas
from tests.test_kimilinear import tiny_model

D = 128
CLOSE = dict(rtol=1e-5, atol=1e-6)


def rows(B, T, h, d=D, seed=0):
    """``(q, k, v, log_alpha, beta, S0)`` as a KDA layer hands them to its
    recurrence: ``q``, ``k`` normalised, decays in (0, 1), a state of size 1."""
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, k = unit(f(B, T, h, d)) / np.sqrt(d), unit(f(B, T, h, d))
    log_alpha = -np.abs(f(B, T, h, d)) * 0.3
    beta = 1.0 / (1.0 + np.exp(-f(B, T, h)))
    return tuple(jnp.asarray(x) for x in (q, k, f(B, T, h, d), log_alpha, beta, f(B, h, d, d) * 0.5))


def interpreted(*args):
    """The kernel's path of the model, interpreted: this suite runs on the CPU."""
    return delta_rule_step(True, *args)


CASES = {
    # (episode segment of the step a lane, whether the lane carries a state, what a void lane's state holds)
    "all_lanes_carried": ([0, 0, 0, 0], [True, True, True, True], None),
    "void_lanes_hold_1e30": ([0, 0, 0, 0], [True, False, True, False], 1e30),
    "void_lanes_hold_nan": ([0, 0, 0, 0], [False, True, True, False], np.nan),
    "a_step_starts_an_episode": ([0, 1, 0, 1], [True, True, False, True], 1e30),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_kernel_step_is_the_closed_form_at_one_step(case):
    seg, carried, poison = CASES[case]
    q, k, v, log_alpha, beta, S0 = rows(4, 1, 3)
    seg, carried = jnp.asarray(seg, jnp.int32)[:, None], jnp.asarray(carried)
    if poison is not None:
        # whatever a state that the step must not see holds: never multiplied, only selected away
        S0 = jnp.where((carried & (seg[:, 0] == 0))[:, None, None, None], S0, poison)
    o_want, S_want = delta_rule_chunk(q, k, v, log_alpha, beta, S0, seg, carried)
    o, S = interpreted(q, k, v, log_alpha, beta, S0, seg, carried)
    assert o.shape == o_want.shape == (4, 1, 3, D) and S.shape == S0.shape
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want), **CLOSE)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_want), **CLOSE)
    # heads a block: eight at a time where they divide, else all of them; the same numbers either way
    q8, k8, v8, la8, b8, S8 = rows(2, 1, 16, seed=1)
    keep = jnp.asarray([True, False])
    by_eight = kda_step_pallas(q8[:, 0], k8[:, 0], v8[:, 0], la8[:, 0], b8[:, 0], S8, keep, interpret=True, heads_per_block=8)
    at_once = kda_step_pallas(q8[:, 0], k8[:, 0], v8[:, 0], la8[:, 0], b8[:, 0], S8, keep, interpret=True)
    for a, b in zip(by_eight, at_once):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _kernel_everywhere(cfg, platform):
    """``step_takes_kernel`` with the platform taken out: what the tests patch in
    to run the kernel's path (interpreted) where there is no TPU."""
    return cfg.kda_head_dim % D == 0


@pytest.mark.parametrize("blocks", ["one_plain_block", "two_unequal_lane_blocks"])
def test_a_kda_layer_through_the_kernel_is_the_layer_through_the_closed_form(monkeypatch, blocks):
    model = tiny_model(kda_head_dim=D)
    layer = kimilinear.KDA(model)
    B, K, W = 5, model.kda_conv_kernel, model.n_heads * D
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((B, 1, model.hidden_dim)).astype(np.float32))
    S0 = jnp.asarray(rng.standard_normal((B, model.n_heads, D, D)).astype(np.float32) * 0.5)
    history = jnp.asarray(rng.standard_normal((B, K - 1, 3 * W)).astype(np.float32))
    pos0 = jnp.asarray([3, 0, 7, 2, 0], jnp.int32)               # lanes 1 and 4 read a void state
    seg = jnp.asarray([0, 0, 1, 0, 0], jnp.int32)[:, None]       # lane 2 starts an episode at this step
    S0 = S0.at[1].set(1e30).at[4].set(jnp.nan)
    params = layer.init(jax.random.PRNGKey(0), a, (S0, history), pos0, seg)

    def run(state):
        (mix, (S, hist)), _ = layer.apply(params, a, state, pos0, seg, mutable=["losses"])
        return mix, S, hist

    assert not kimilinear.step_takes_kernel(model, "cpu")
    want = run((S0, history))
    monkeypatch.setattr(kimilinear, "step_takes_kernel", _kernel_everywhere)
    if blocks == "one_plain_block":
        got = run((S0, history))
    else:
        mix, S, hist = run((LaneBlocks((S0[:2], S0[2:])), LaneBlocks((history[:2], history[2:]))))
        assert isinstance(S, LaneBlocks) and [s.shape[0] for s in S] == [2, 3]
        got = mix, jnp.concatenate(S), jnp.concatenate(hist)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **CLOSE)


def test_sixteen_kernel_steps_in_a_row_are_one_chunk_of_sixteen():
    B, T, h = 3, 16, 2
    q, k, v, log_alpha, beta, S0 = rows(B, T, h, seed=5)
    starts = np.zeros((B, T), bool)
    starts[1, 6] = starts[2, 0] = True                           # an episode starts inside the chunk, and at its head
    seg = jnp.asarray(np.cumsum(starts, axis=1), jnp.int32)
    carried = jnp.asarray([True, True, False])
    o_want, S_want = delta_rule_chunk(q, k, v, log_alpha, beta, S0, seg, carried)
    step = jax.jit(interpreted)
    S, outs = S0, []
    for t in range(T):
        at = lambda x: x[:, t:t + 1]
        o, S = step(at(q), at(k), at(v), at(log_alpha), at(beta), S, jnp.asarray(starts[:, t:t + 1], jnp.int32), carried)
        carried = jnp.ones((B,), bool)                           # after a step every lane stands past position 0
        outs.append(o)
    # sixteen roundings of a state against the closed form's one sum: float32 still holds the step's tolerance
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(o_want), **CLOSE)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_want), **CLOSE)


def test_the_gradient_of_a_kernel_step_is_the_closed_form_s():
    q, k, v, log_alpha, beta, S0 = rows(3, 1, 2, seed=7)
    seg, carried = jnp.asarray([[0], [1], [0]], jnp.int32), jnp.asarray([True, True, False])

    def loss(fn):
        def inner(*floats):
            o, S = fn(*floats, seg, carried)
            return jnp.sum(o ** 2) + jnp.sum(S * jnp.cos(S))
        return jax.grad(inner, argnums=tuple(range(6)))(q, k, v, log_alpha, beta, S0)

    for got, want in zip(loss(interpreted), loss(delta_rule_chunk)):
        assert np.abs(np.asarray(want)).max() > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5 * np.abs(np.asarray(want)).max())


@pytest.mark.parametrize("d,T,kernel", [(16, 1, False), (D, 2, False), (D, 1, True), (2 * D, 1, True)])
def test_which_steps_take_the_kernel_is_told_by_width_chunk_length_and_platform(d, T, kernel):
    """No backend needed: the predicate, and the traced recurrence itself (the
    kernel is a ``pallas_call`` in the branch a TPU lowering keeps; a CPU
    lowering keeps the closed form, which is why the traced text names both)."""
    model = tiny_model(kda_head_dim=d)
    assert kimilinear.step_takes_kernel(model, "tpu") == (d % D == 0)
    assert not kimilinear.step_takes_kernel(model, "cpu") and not kimilinear.step_takes_kernel(model, "cuda")
    q, k, v, log_alpha, beta, S0 = rows(2, T, model.n_heads, d=d)
    seg, carried = jnp.zeros((2, T), jnp.int32), jnp.ones((2,), bool)
    text = str(jax.make_jaxpr(kimilinear._recurrence(model, T))(S0, q, k, v, log_alpha, beta, seg, carried))
    assert ("pallas_call" in text) == kernel
    assert ("platform_index" in text) == kernel
    # and on this CPU the traced program is the closed form's numbers, bit for bit
    o, S = jax.jit(kimilinear._recurrence(model, T))(S0, q, k, v, log_alpha, beta, seg, carried)
    o_want, S_want = jax.jit(delta_rule_chunk)(q, k, v, log_alpha, beta, S0, seg, carried)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_want))
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S_want))


def test_the_kernel_refuses_heads_it_does_not_take():
    q, k, v, log_alpha, beta, S0 = rows(2, 1, 2, d=16)
    with pytest.raises(ValueError, match="square heads of whole lanes"):
        kda_step_pallas(q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], S0, jnp.ones((2,), bool), interpret=True)
