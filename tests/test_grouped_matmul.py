"""The grouped-matmul kernels (``ops/pallas/grouped_matmul.py``) in interpreter
mode on the CPU against their specification, ``jax.lax.ragged_dot``: the
product a group, the rows' gradient and the weights' gradient, over a full
(padded) buffer, a buffer whose rows past the groups hold NaN, empty groups,
every row in one group and no row in any; ``afmoe.RoutedExperts`` through the
kernels against itself through ``ragged_dot``; which configuration and
platform take the kernels, and what the learner counts of them. (Compiled by
Mosaic for a described v5e: the rollout loops of ``tests/test_shared_pass_hlo.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.models import afmoe
from dotaclient_tpu.ops.pallas import grouped_matmul
from dotaclient_tpu.train import learner
from tests.test_lfm2moe import tiny_model

ROWS, C, D = 90, 128, 256
# group sizes over 8 experts, a buffer of 90 rows cut in tiles of 16 (the last
# one partial): groups that start and end inside tiles, share tiles, skip them
SIZES = {
    "full": [5, 17, 9, 20, 11, 13, 6, 9],
    "rows_past_the_groups": [5, 17, 9, 0, 11, 3, 6, 1],
    "empty_groups": [0, 30, 0, 0, 16, 0, 19, 0],
    "one_group": [0, 0, 0, 70, 0, 0, 0, 0],
    "no_row": [0] * 8,
}


def _operands(sizes, dtype, seed=0):
    """(buffer [ROWS, C] with NaN past the groups, weights [8, C, D], a
    cotangent [ROWS, D])."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = int(np.sum(sizes))
    x = jax.random.normal(k[0], (ROWS, C), jnp.float32).at[n:].set(jnp.nan)
    w = jax.random.normal(k[1], (8, C, D), jnp.float32) / np.sqrt(C)
    g = jax.random.normal(k[2], (ROWS, D), jnp.float32)
    return x.astype(dtype), w.astype(dtype), g.astype(dtype)


def _through_masks(product, x, w, g, n):
    """``product(x, w)``'s value and both gradients read as ``RoutedExperts``
    reads them: rows past the groups SELECTED away, on the way in and out."""
    inside = (jnp.arange(ROWS) < n)[:, None]

    def loss(x, w):
        y = jnp.where(inside, product(jnp.where(inside, x, 0), w), 0)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32)), y

    (_, y), (dx, dw) = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(x, w)
    return y, dx, dw


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernels_are_ragged_dot_a_group_forward_and_both_gradients(case, dtype):
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    n = int(sizes.sum())
    x, w, g = _operands(SIZES[case], jnp.dtype(dtype))
    p = grouped_matmul.plan(sizes, ROWS, interpret=True, tm=16)
    kernel = lambda x, w: grouped_matmul.gmm(x, w, p, True)
    ragged = lambda x, w: jax.lax.ragged_dot(x, w, sizes, precision=jax.lax.Precision.HIGHEST)
    # raw, the rows inside the groups: the same products in the same type
    raw = kernel(x, w)
    assert raw.shape == (ROWS, D) and raw.dtype == x.dtype
    want = ragged(x, w)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.float32(raw[:n]), np.float32(want[:n]), **tol)
    # read through the layer's selects: finite, and the gradients the specification's
    got, want = _through_masks(kernel, x, w, g, n), _through_masks(ragged, x, w, g, n)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.isfinite(np.float32(a)).all()
        np.testing.assert_allclose(np.float32(a), np.float32(b), **tol)
    # an empty group's weight gradient is zeros, written
    empty = np.asarray(SIZES[case]) == 0
    assert not np.float32(got[2])[empty].any()


def test_the_plan_visits_the_tiles_the_groups_meet_and_no_other():
    """Visits in order, a shared tile twice, an empty group never (``gmm``)
    and once (``tgmm``), no tile past the groups; ``visited_rows`` is the
    rows of those tiles."""
    p = grouped_matmul.plan(jnp.asarray([20, 0, 37, 30], jnp.int32), 90, interpret=True, tm=16)
    assert np.asarray(p.tables).tolist() == [0, 20, 20, 57, 87] + [2, 2, 5, 8] + [2, 3, 6, 9]
    assert p.row_tiles == 6 and np.asarray(p.counts).tolist() == [8, 9]

    def visits(weights):
        return [
            tuple(int(x) for x in grouped_matmul._visit(p, p.tables, v, weights))
            for v in range(int(p.counts[int(weights)]))
        ]

    # (group, row tile, the group's first visit, its last): a shared tile twice, the empty group never
    assert visits(False) == [
        (0, 0, 0, 1), (0, 1, 0, 1), (2, 1, 2, 4), (2, 2, 2, 4), (2, 3, 2, 4), (3, 3, 5, 7), (3, 4, 5, 7), (3, 5, 5, 7),
    ]
    # ... and the weights' kernel visits the empty group once, to write its zeros
    assert visits(True) == [
        (0, 0, 0, 1), (0, 1, 0, 1), (1, 1, 2, 2), (2, 1, 3, 5), (2, 2, 3, 5), (2, 3, 3, 5), (3, 3, 6, 8), (3, 4, 6, 8),
        (3, 5, 6, 8),
    ]
    # the default tile: 128 rows where a group holds at most 256 on average, 256 above, the whole buffer where smaller
    assert grouped_matmul.row_tile(40, 8) == 48 and grouped_matmul.row_tile(640, 8) == 128
    assert grouped_matmul.row_tile(2048, 8) == 128 and grouped_matmul.row_tile(2049, 8) == 256
    assert int(grouped_matmul.visited_rows(jnp.asarray([300, 0, 3, 0, 0, 0, 0, 0]), 1000)) == 384
    assert int(grouped_matmul.visited_rows(jnp.asarray([600, 0, 3, 0, 0, 0, 0, 0]), 1000)) == 640
    assert int(grouped_matmul.visited_rows(jnp.asarray([640, 360]), 1000)) == 1000     # tiles of 256: the last one partial
    assert int(grouped_matmul.visited_rows(jnp.zeros(8, jnp.int32), 1000)) == 0


def _kernel_everywhere(cfg, platform):
    """``grouped_takes_kernel`` with the platform taken out: the kernels'
    path of the model, interpreted on this CPU."""
    return grouped_matmul.takes(cfg.hidden_dim, cfg.expert_ffn_dim, afmoe._dtype(cfg.dtype))


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("tokens", ["alike", "apart", "elsewhere"])
def test_routed_experts_through_the_kernels_is_the_layer_through_ragged_dot(monkeypatch, tokens, padded):
    """The whole layer, value and gradients, at widths of whole lanes: tokens
    that choose alike (a chip's pairs on one held expert), apart, or all
    elsewhere (no pair held here); the buffer padded or not."""
    model = tiny_model(
        hidden_dim=128, expert_ffn_dim=128, moe_experts=32, experts_per_token=2, held_experts=8,
        expert_offset=0, pad_expert_groups=padded,
    )
    m = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 128))
    if tokens != "apart":
        m = m[:1, :1] + 1e-3 * m
    layer = afmoe.RoutedExperts(model)
    params = jax.jit(layer.init)(jax.random.PRNGKey(4), m)["params"]
    # the selection bias moves every token's first choice onto held expert 3, or every choice off the held eight
    bias = params["select_bias"]
    bias = {"alike": bias.at[3].set(1e3), "apart": bias, "elsewhere": bias.at[:8].set(-1e3)}[tokens]
    params = {**params, "select_bias": bias}

    def run():
        def out(p, x):
            y, sown = layer.apply({"params": p}, x, mutable=["losses"])
            return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=y.dtype).reshape(y.shape))), (y, sown["losses"])
        return jax.jit(jax.value_and_grad(out, argnums=(0, 1), has_aux=True))(params, m)

    (_, (y0, sown0)), grads0 = run()
    monkeypatch.setattr(afmoe, "grouped_takes_kernel", _kernel_everywhere)
    (_, (y1, sown1)), grads1 = run()
    local = int(sown1["moe_local"][0])
    assert (local == 0) == (tokens == "elsewhere") and (local >= 10 or tokens != "alike")
    # 20 rows are one tile: visited whole where a pair is held or the buffer is padded
    assert float(sown1["moe_kernel_rows_share"][0]) == (1.0 if padded or local else 0.0)
    assert float(sown0["moe_kernel_rows_share"][0]) == float(sown1["moe_kernel_rows_share"][0])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(grads1), jax.tree.leaves(grads0)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)     # summed in another order
    assert float(sown1["moe_dropped"][0]) == 0.0


CELLS = [
    "trinity-mini-5v5-ep16.fused-selfplay-anycore", "kimi-linear-5v5-ep32.fused-selfplay-anycore",
    "lfm2-24b-a2b-5v5-ep8.fused-selfplay-anycore", "sdar-30b-a3b-5v5-ep16.fused-selfplay-anycore",
]


def _cell_config(name):
    from benchmark.harness import cells, program

    return program.build_run_config(cells.load_cell(name), seed=0, rehearsal=False)


class _Mesh:
    """What ``_grouped_kernel_calls`` reads of a mesh: its first device's platform."""

    def __init__(self, platform):
        self.devices = np.asarray([type("Device", (), {"platform": platform})()])


@pytest.mark.parametrize("cell,calls", list(zip(CELLS, [68, 68, 68, 260])))
def test_the_routed_cells_take_the_kernels_on_a_tpu_alone_and_count_their_passes(cell, calls):
    """Published widths on a TPU take the kernels, a CPU and toy widths keep
    ``ragged_dot``; a dispatch counts routed layers x (passes a rollout step x
    16 steps + 1 optimizer step): 4 x 17 in the one-pass cores, 4 x (4 x 16 +
    1) in SDAR's three denoising passes and commit."""
    cfg = _cell_config(cell)
    assert afmoe.grouped_takes_kernel(cfg.model, "tpu")
    assert not afmoe.grouped_takes_kernel(cfg.model, "cpu")
    assert learner._grouped_kernel_calls(cfg, _Mesh("tpu")) == calls
    assert learner._grouped_kernel_calls(cfg, _Mesh("cpu")) == 0
    toy = dataclasses.replace(cfg, model=tiny_model())
    assert not afmoe.grouped_takes_kernel(toy.model, "tpu")
    assert learner._grouped_kernel_calls(toy, _Mesh("tpu")) == 0


def test_cores_without_routed_layers_count_no_kernel_pass():
    for cell in ("ouro-2.6b-5v5-ut4.fused-selfplay-anycore", "five5v5-lstm4096.fused-selfplay"):
        assert learner._grouped_kernel_calls(_cell_config(cell), _Mesh("tpu")) == 0
