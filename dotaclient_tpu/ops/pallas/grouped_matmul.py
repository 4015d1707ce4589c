"""Pallas kernels for a routed expert layer's grouped products: the rows of a
buffer sorted by expert, each group multiplied by its own expert's weights.

Called by ``models/afmoe.py RoutedExperts`` where a program is lowered for a
TPU at widths that fill whole lanes (``afmoe.grouped_takes_kernel``);
``jax.lax.ragged_dot`` stays the path everywhere else and this module's
specification in ``tests/``. The form is megablox's
(``jax.experimental.pallas.ops.tpu.megablox``): a grid whose row-tile axis is
as long as the groups need, steered by scalar-prefetched tables.

``lhs [M, C]`` holds the groups' rows in order from row 0: group g is rows
``offsets[g]:offsets[g + 1]``, and rows past ``offsets[G]`` belong to none.
Rows are cut into tiles of ``tm`` (``row_tile``; the last one partial where
``tm`` does not divide M); a VISIT is one (group, row tile) pair whose rows
meet, in order.
``gmm`` multiplies each visit's tile by its group's weights and stores the
group's rows of it, so a tile that two groups share is visited twice, an
empty group never, and a tile past the groups never: the work follows the
group sizes as ``ragged_dot``'s does (``visited_rows``). Rows past the groups
are left as the output's memory held them: the caller selects them away (a
select, never a product, so that a NaN there reaches nothing).

``tgmm`` is the weights' gradient ``lhs_g^T d_g`` a group: the same visits
with every group visited at least once, so that an empty group's gradient is
written as zeros; rows outside a visit's group are selected to zero before
the product. ``gmm`` carries both as its ``custom_vjp``: the rows' gradient is
``gmm`` against the transposed weights, the weights' is ``tgmm``.

The tables (``Plan``) are computed once a layer call, by one call of a
kernel on the scalar unit, and handed to every product of it and to their
backward: the groups' row offsets and each kernel's visits counted up to and
with each group, ``3 G + 1`` numbers. A grid step finds its visit's group and
tile from them on the scalar unit (``_visit``), so the program around the
kernels holds no table of visits and no other operation (a step of the
rollout costs by the operation).

Products run in the operands' type with float32 accumulation (at the ambient
``jax.default_matmul_precision``, as an XLA product), outputs in the left
operand's type, as ``ragged_dot`` gives them. As in ``kda_step.py`` nothing
here looks at the backend: the caller names ``interpret=``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # the lane width of a vector register: weight widths are whole multiples of it
DTYPES = (jnp.bfloat16, jnp.float32)

# Row tiles: 128 where a group holds at most 256 rows on average (a rollout
# step's buffers), 256 above (the learner's). A row tile of 256 multiplies a
# tile of bfloat16 weights in about the time the v5e reads it (197 TFLOP/s over
# 819 GB/s is 240 rows); where groups are short, the smaller tile wastes fewer
# rows of a visit. Measured on a v5e (PERF.md section 6): LFM2's rollout buffer
# (640 rows, 8 groups) 331 against 384 microseconds at 256, SDAR's (1,440)
# 198 against 226; the learner's (5,440 and 41,040 rows) 2.61 and 8.42 ms at
# 256 against 3.33 and 8.74 at 128.
ROW_TILES = (128, 256)
# bytes of a weight tile: 4 MiB, where 2 MiB read 6-18% slower in every
# buffer measured (bfloat16): a grid step costs some 0.35 microseconds of its own
_WEIGHT_TILE_BYTES = 4 << 20
_VMEM_DEFAULT = 16 << 20
_VMEM_CEILING = 100 << 20


def takes(hidden: int, ffn: int, dtype) -> bool:
    """The widths and type these kernels run: whole lanes, bfloat16 or float32."""
    return hidden % LANES == 0 and ffn % LANES == 0 and jnp.dtype(dtype) in DTYPES


def row_tile(rows: int, groups: int) -> int:
    """The row tile for a buffer of ``rows`` in ``groups``: by the rows a
    group holds on average (``ROW_TILES``), or the whole buffer rounded up to
    16 rows (a bfloat16 tile's sublanes) where it is smaller."""
    short, tall = ROW_TILES
    tm = tall if rows > tall * groups else short
    return tm if rows > tm else -(-rows // 16) * 16


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of ``LANES`` that divides ``dim`` and is at most ``cap``."""
    if dim <= cap:
        return dim
    return next(t for t in range(cap - cap % LANES, 0, -LANES) if dim % t == 0)


@functools.partial(jax.tree_util.register_dataclass, data_fields=["tables", "counts"], meta_fields=["groups", "rows", "tm"])
@dataclasses.dataclass(frozen=True)
class Plan:
    """``tables [3 G + 1]``: the groups' row offsets ``[G + 1]`` (group g is
    rows ``offsets[g]:offsets[g + 1]``), then for ``gmm`` and for ``tgmm``
    ``[G]`` the visits up to and with group g; ``counts [2]`` how many
    visits each runs; a buffer of ``rows`` cut in tiles of ``tm``."""

    tables: jnp.ndarray
    counts: jnp.ndarray
    groups: int
    rows: int
    tm: int

    @property
    def row_tiles(self) -> int:
        return -(-self.rows // self.tm)


def plan(group_sizes: jnp.ndarray, rows: int, *, interpret: bool, tm: int | None = None) -> Plan:
    """The tables for a buffer of ``rows`` whose groups hold ``group_sizes
    [G]`` rows from row 0 (summing to at most ``rows``); ``tm`` defaults to
    ``row_tile(rows, G)``. ONE call of a kernel on the scalar unit: the groups
    walked in order."""
    G = group_sizes.shape[0]
    tm = tm or row_tile(rows, G)

    def kernel(sizes_ref, tables_ref, counts_ref):
        end, done, done_t = jnp.int32(0), jnp.int32(0), jnp.int32(0)
        tables_ref[0] = end
        for g in range(G):
            start, end = end, lax.add(end, sizes_ref[g])
            # row tiles the group's rows meet (``lax``, as in ``_visit``)
            meet = lax.add(lax.sub(lax.div(lax.sub(end, 1), tm), lax.div(start, tm)), 1)
            span = lax.select(lax.gt(end, start), meet, jnp.int32(0))
            done, done_t = lax.add(done, span), lax.add(done_t, lax.max(span, 1))
            tables_ref[1 + g], tables_ref[1 + G + g], tables_ref[1 + 2 * G + g] = end, done, done_t
        counts_ref[0], counts_ref[1] = done, done_t

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tables, counts = pl.pallas_call(
        kernel,
        in_specs=[smem],
        out_specs=(smem, smem),
        out_shape=(jax.ShapeDtypeStruct((3 * G + 1,), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32)),
        interpret=interpret,
        name="grouped_matmul_plan",
    )(group_sizes.astype(jnp.int32))
    return Plan(tables, counts, G, rows, tm)


def visited_rows(group_sizes: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Rows of a buffer of ``rows`` that lie in a tile ``gmm`` visits: the
    tiles that the groups' rows, from row 0, meet."""
    tm = row_tile(rows, group_sizes.shape[0])
    return jnp.minimum((group_sizes.sum() + tm - 1) // tm * tm, rows)


def _visit(p: Plan, tables, v, weights: bool):
    """(group, row tile, the group's first visit, its last) of visit ``v``:
    scalar reads of the tables, the groups counted in order. Written in
    ``lax`` (a ``jnp`` function or operator is a ``jit`` of its own, and a
    grid's index maps trace this a dozen times a product: 30 ms each)."""
    done = (2 if weights else 1) * p.groups + 1
    group = jnp.int32(0)
    for g in range(p.groups - 1):
        group = lax.add(group, lax.convert_element_type(lax.le(tables[done + g], v), jnp.int32))
    before = tables[lax.add(lax.max(lax.sub(group, 1), 0), done)]
    first = lax.select(lax.gt(group, 0), before, jnp.int32(0))
    tile = lax.min(lax.add(lax.div(tables[group], p.tm), lax.sub(v, first)), p.row_tiles - 1)
    return group, tile, first, lax.sub(tables[lax.add(group, done)], 1)


def _rows_of(p: Plan, tables, group, tile, shape):
    """``[tm, n]`` true on the tile's rows that belong to ``group``."""
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    start, end = (lax.sub(tables[lax.add(group, i)], lax.mul(tile, p.tm)) for i in (0, 1))
    return lax.bitwise_and(lax.ge(row, lax.broadcast(start, shape)), lax.lt(row, lax.broadcast(end, shape)))


def _select(mask, x, y):
    """``where`` in float32 on ``lax`` alone (see ``_visit``)."""
    f32 = lambda z: lax.convert_element_type(z, jnp.float32)
    return lax.select(mask, f32(x), f32(y))


def _vmem_limit(need: int) -> int:
    return max(_VMEM_DEFAULT, min(need + (8 << 20), _VMEM_CEILING))


def _gmm_call(lhs, rhs, p: Plan, transpose_rhs: bool, out_dtype, interpret: bool):
    """``out[r] = lhs[r] @ rhs[g]`` (``rhs[g].T`` where ``transpose_rhs``) for
    every row r of every group g."""
    M, C = lhs.shape
    D = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, td = p.tm, _tile(D, 2048)
    tc = _tile(C, max(LANES, _WEIGHT_TILE_BYTES // (td * rhs.dtype.itemsize)))
    n_d, n_c = D // td, C // tc

    def kernel(tables, lhs_ref, rhs_ref, out_ref, acc_ref):
        v, c = pl.program_id(1), pl.program_id(2)

        @pl.when(lax.eq(c, 0))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(lhs_ref[...], rhs_ref[...], dims, preferred_element_type=jnp.float32))

        @pl.when(lax.eq(c, n_c - 1))
        def _():
            group, tile, _, _ = _visit(p, tables, v, False)
            mine = _rows_of(p, tables, group, tile, (tm, td))
            out_ref[...] = lax.convert_element_type(_select(mine, acc_ref[...], out_ref[...]), out_ref.dtype)

    def rows(d, v, c, tables):
        return _visit(p, tables, v, False)[1], c

    def weights(d, v, c, tables):
        group = _visit(p, tables, v, False)[0]
        return (group, d, c) if transpose_rhs else (group, c, d)

    def out(d, v, c, tables):
        return _visit(p, tables, v, False)[1], d

    item, out_item = jnp.dtype(lhs.dtype).itemsize, jnp.dtype(out_dtype).itemsize
    need = 2 * (tm * tc + tc * td) * item + 2 * tm * td * out_item + tm * td * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_d, p.counts[0], n_c),
            in_specs=[
                pl.BlockSpec((tm, tc), rows),
                pl.BlockSpec((None, td, tc) if transpose_rhs else (None, tc, td), weights),
            ],
            out_specs=pl.BlockSpec((tm, td), out),
            scratch_shapes=[pltpu.VMEM((tm, td), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"), vmem_limit_bytes=_vmem_limit(need)
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * C * D, transcendentals=0,
            bytes_accessed=n_d * M * C * item + rhs.size * rhs.dtype.itemsize + M * D * out_item,
        ),
        interpret=interpret,
        name="grouped_matmul",
    )(p.tables, lhs, rhs)


def _tgmm_call(lhs, grad, p: Plan, out_dtype, interpret: bool):
    """``out[g] = lhs[rows of g].T @ grad[rows of g]`` ``[G, C, D]`` for
    ``lhs [M, C]``, ``grad [M, D]``; zeros for an empty group."""
    M, C = lhs.shape
    D = grad.shape[1]
    tm, td = p.tm, _tile(D, 2048)
    tc = _tile(C, max(LANES, _WEIGHT_TILE_BYTES // (td * jnp.dtype(out_dtype).itemsize)))
    n_d, n_c = D // td, C // tc

    def kernel(tables, lhs_ref, grad_ref, out_ref, acc_ref):
        v = pl.program_id(2)
        group, tile, first, last = _visit(p, tables, v, True)

        @pl.when(lax.eq(v, first))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(lax.gt(tables[lax.add(group, 1)], tables[group]))
        def _():
            x = _select(_rows_of(p, tables, group, tile, (tm, tc)), lhs_ref[...], jnp.zeros((tm, tc)))
            g = _select(_rows_of(p, tables, group, tile, (tm, td)), grad_ref[...], jnp.zeros((tm, td)))
            x = lax.convert_element_type(lax.transpose(x, (1, 0)), lhs_ref.dtype)
            g = lax.convert_element_type(g, grad_ref.dtype)
            acc_ref[...] = lax.add(acc_ref[...], lax.dot(x, g, preferred_element_type=jnp.float32))

        @pl.when(lax.eq(v, last))
        def _():
            out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)

    def rows(d, c, v, tables):
        return _visit(p, tables, v, True)[1], c

    def grads(d, c, v, tables):
        return _visit(p, tables, v, True)[1], d

    def out(d, c, v, tables):
        return _visit(p, tables, v, True)[0], c, d

    item, out_item = jnp.dtype(lhs.dtype).itemsize, jnp.dtype(out_dtype).itemsize
    need = 2 * (tm * tc + tm * td) * item + 2 * tc * td * out_item + tc * td * 4 + 2 * tm * (tc + td) * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_d, n_c, p.counts[1]),
            in_specs=[pl.BlockSpec((tm, tc), rows), pl.BlockSpec((tm, td), grads)],
            out_specs=pl.BlockSpec((None, tc, td), out),
            scratch_shapes=[pltpu.VMEM((tc, td), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((p.groups, C, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_vmem_limit(need)
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * C * D, transcendentals=0,
            bytes_accessed=n_d * M * C * item + n_c * M * D * grad.dtype.itemsize + p.groups * C * D * out_item,
        ),
        interpret=interpret,
        name="grouped_matmul_weights",
    )(p.tables, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, p: Plan, interpret: bool) -> jnp.ndarray:
    """``lhs [M, C]`` by ``rhs [G, C, D]`` a group -> ``[M, D]`` in ``lhs``'
    type; rows past the groups undefined."""
    return _gmm_call(lhs, rhs, p, False, lhs.dtype, interpret)


def _gmm_fwd(lhs, rhs, p, interpret):
    return gmm(lhs, rhs, p, interpret), (lhs, rhs, p)


@functools.partial(jax.jit, static_argnums=0)
def _gradients(interpret, lhs, rhs, p, grad):
    return _gmm_call(grad, rhs, p, True, lhs.dtype, interpret), _tgmm_call(lhs, grad, p, rhs.dtype, interpret)


def _gmm_bwd(interpret, residuals, grad):
    # under ``jit``: a transposition traces the two kernels once a shape, not once a call site
    return (*_gradients(interpret, *residuals, grad), None)


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_swiglu(
    xs: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray, wd: jnp.ndarray, group_sizes: jnp.ndarray, *, interpret: bool
) -> jnp.ndarray:
    """``silu(xs Wg) * (xs Wu) Wd`` a group over ``xs [rows, H]`` by ``wg, wu
    [G, H, F]`` and ``wd [G, F, H]``: the three products over one plan. Rows
    past the groups undefined."""
    p = plan(group_sizes, xs.shape[0], interpret=interpret)
    mid = jax.nn.silu(gmm(xs, wg, p, interpret)) * gmm(xs, wu, p, interpret)
    return gmm(mid, wd, p, interpret)
