"""Pallas kernel for a rollout pass's attention against its lanes' rings: a
lane's grid steps visit only the ring blocks that hold the rows it can see.

Called by ``models/sdar.py BlockAttention`` where a rollout pass (a few rows
a lane, every one of the lane's current episode) is lowered for a TPU at
heads of whole lanes (``sdar.pass_takes_kernel``); ``afmoe._attend`` stays
the path everywhere else and this kernel's specification in ``tests/``.

A ring of ``R`` rows holds a lane's keys (or values) of one KV head; the lane
has written ``cursor`` rows modulo ``R`` and its episode is ``pos`` rows old,
so it sees the ``n = min(pos, R)`` rows that end at ``cursor - 1`` modulo
``R``: a range that may wrap past ``R`` (a reset sets ``pos`` to 0 and leaves
the cursor running). Cut into blocks of ``block_rows``, the range meets
``ceil((start % block_rows + n) / block_rows)`` blocks from the one that holds
its first row on, at most all of them (``visited_blocks``). The grid is ONE
axis of visits, (lane, block) pairs lane by lane, as many as the lanes' ranges
meet (a lane that sees none has one visit, for its own rows): its length is a
traced number, so no grid step is spent on a block nobody sees and no block
is fetched that a lane cannot see. The visits are a table prefetched to the
scalar unit (``_schedule``: a lane and a block an entry).

A lane's first visit opens the softmax with the pass's own rows (``see_own``,
the caller's static ``N x N`` mask; a query always sees itself); each visit
adds a block's rows, the lane's rows inside the block it cannot see masked;
the last divides. Every KV head's keys and values are in one call, a ring a
head as its own operand (the carry's layout: ``[lanes, R, D]``). The softmax
is ``afmoe._attend``'s: float32 scores, the exponentials cast to the compute
type before the value product, float32 sums, one softmax over the ring and
the pass's rows; online over the blocks, so the maximum an exponential is
taken against is the running one.

As in ``kda_step.py`` nothing here looks at the backend: the caller names
``interpret=``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128        # the lane width of a vector register: head widths are whole multiples of it
BLOCK_ROWS = 1024  # ring rows a visit reads (a sweep on the chip: 1024 beat 256 and 512 at every position)
_NEG = -1e30       # ``afmoe._NEG``: a score nobody sees


def takes(head_dim: int, dtype, ring_rows: int) -> bool:
    """The shapes this kernel runs: heads of whole lanes, a compute type of
    bfloat16 or float32, a ring of whole blocks."""
    return head_dim % LANES == 0 and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)) and ring_rows % BLOCK_ROWS == 0


def visited_blocks(pos: jnp.ndarray, cursor: jnp.ndarray, ring_rows: int, block_rows: int = BLOCK_ROWS):
    """``(first block [B], blocks [B])``: the ring blocks that hold the rows a
    lane sees, ``blocks`` of them from ``first`` on modulo the ring."""
    nblk = ring_rows // block_rows
    n = jnp.minimum(pos, ring_rows)
    start = (cursor - n) % ring_rows
    count = jnp.where(n > 0, jnp.minimum((start % block_rows + n - 1) // block_rows + 1, nblk), 0)
    return start // block_rows, count


def _schedule(pos, cursor, ring_rows: int, block_rows: int):
    """``(visits [B * nblk + 1], length)``: visit i is ``lane * nblk + block``,
    lane by lane, a lane's blocks in ring order; entries from ``length`` on
    are ``B * nblk``, a lane past the last (so a visit's successor says
    whether it is its lane's last)."""
    B, nblk = pos.shape[0], ring_rows // block_rows
    first, count = visited_blocks(pos, cursor, ring_rows, block_rows)
    steps = jnp.maximum(count, 1)
    ends = jnp.cumsum(steps)
    i = jnp.arange(B * nblk + 1, dtype=jnp.int32)
    lane = jnp.sum(i[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    at = jnp.minimum(lane, B - 1)
    block = (first[at] + i - (ends - steps)[at]) % nblk
    return jnp.where(lane < B, lane * nblk + block, B * nblk).astype(jnp.int32), ends[-1]


def _kernel(visits_ref, pos_ref, cursor_ref, q_ref, k_ref, v_ref, *refs, kv, G, own, nblk, block_rows, ring_rows):
    """One visit: ``q [1, kv, M, D]`` (rows n-major, ``M`` padded), the pass's
    own ``k, v [1, kv, 8, D]`` float32, a block ``[1, block_rows, D]`` of each
    head's keys then values -> ``o [1, kv, M, D]`` float32; ``m``, ``l``
    ``[kv, M, LANES]`` the running maximum and sum."""
    rings, (o_ref, m_ref, l_ref) = refs[:2 * kv], refs[2 * kv:]
    i = pl.program_id(0)
    code = visits_ref[i]
    lane, block = code // nblk, code % nblk
    opens = (i == 0) | (visits_ref[jnp.maximum(i - 1, 0)] // nblk != lane)
    closes = visits_ref[i + 1] // nblk != lane
    M, dtype = q_ref.shape[2], q_ref.dtype
    N = len(own)
    row = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // G            # a query row's place in the pass

    @pl.when(opens)
    def _():
        for h in range(kv):
            q = q_ref[0, h].astype(jnp.float32)
            k, v = k_ref[0, h], v_ref[0, h]
            scores = []
            for j in range(N):
                sees = row < 0
                for n in range(N):
                    if own[n][j]:
                        sees = sees | (row == n)
                s = jnp.sum(q * k[j:j + 1], axis=1, keepdims=True)
                scores.append(jnp.where(sees, s, _NEG))
            top = scores[0]
            for s in scores[1:]:
                top = jnp.maximum(top, s)
            e = [jnp.exp(s - top) for s in scores]
            total, acc = e[0], e[0].astype(dtype).astype(jnp.float32) * v[0:1]
            for j in range(1, N):
                total = total + e[j]
                acc = acc + e[j].astype(dtype).astype(jnp.float32) * v[j:j + 1]
            m_ref[h] = jnp.broadcast_to(top, (M, LANES))
            l_ref[h] = jnp.broadcast_to(total, (M, LANES))
            o_ref[0, h] = acc

    @pl.when(pos_ref[lane] > 0)
    def _():
        r = block * block_rows + jax.lax.broadcasted_iota(jnp.int32, (1, block_rows), 1)
        age = cursor_ref[lane] - 1 - r
        age = jnp.where(age < 0, age + ring_rows, age)
        sees = age < jnp.minimum(pos_ref[lane], ring_rows)                # [1, block_rows]
        for h in range(kv):
            s = jax.lax.dot_general(
                q_ref[0, h], rings[h][0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )                                                               # [M, block_rows]
            s = jnp.where(sees, s, _NEG)
            m_prev, l_prev = m_ref[h][:, :1], l_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            pv = jnp.dot(p.astype(dtype), rings[kv + h][0], preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, (M, LANES))
            l_ref[h] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), (M, LANES))
            o_ref[0, h] = alpha * o_ref[0, h] + pv

    @pl.when(closes)
    def _():
        for h in range(kv):
            o_ref[0, h] = o_ref[0, h] / l_ref[h][:, :1]


def ring_attend(
    q: jnp.ndarray,                  # [B, N, kv, G, D] the pass's queries, scaled, in the compute type
    k: jnp.ndarray,                  # [B, N, kv, D] the pass's own keys
    v: jnp.ndarray,                  # [B, N, kv, D] and values
    ring_k: Sequence[jnp.ndarray],   # kv rings [B, R, D] of keys
    ring_v: Sequence[jnp.ndarray],   # kv rings [B, R, D] of values
    pos: jnp.ndarray,                # [B] int32: the lane's episode, in rows
    cursor: jnp.ndarray,             # [B] int32: the ring slot the next row goes to
    own: np.ndarray,                 # [N, N] bool, static: what a row sees of the pass's rows
    *,
    interpret: bool,
    block_rows: int = BLOCK_ROWS,
) -> jnp.ndarray:
    """``afmoe._attend`` of a pass whose rows are all in their lane's current
    episode (module docstring) -> float32 ``[B, N, kv, G, D]``."""
    B, N, kv, G, D = q.shape
    R = ring_k[0].shape[1]
    if R % block_rows or N > 8:
        raise ValueError(f"ring_attend takes rings of whole blocks of {block_rows} and at most 8 rows a pass, got {R}, {N}")
    nblk = R // block_rows
    dtype = q.dtype
    sub = 8 * 4 // dtype.itemsize                            # the rows of a packed tile of the compute type
    M = -(-N * G // sub) * sub
    qh = jnp.pad(jnp.moveaxis(q, 2, 1).reshape(B, kv, N * G, D), ((0, 0), (0, 0), (0, M - N * G), (0, 0)))
    mine = lambda x: jnp.pad(jnp.moveaxis(x, 2, 1).astype(jnp.float32), ((0, 0), (0, 0), (0, 8 - N), (0, 0)))
    visits, length = _schedule(pos.astype(jnp.int32), cursor.astype(jnp.int32), R, block_rows)
    lane_spec = lambda rows: pl.BlockSpec((1, kv, rows, D), lambda i, visits, pos, cur: (visits[i] // nblk, 0, 0, 0))
    ring_spec = pl.BlockSpec((1, block_rows, D), lambda i, visits, pos, cur: (visits[i] // nblk, visits[i] % nblk, 0))
    kernel = functools.partial(
        _kernel, kv=kv, G=G, own=np.asarray(own, bool).tolist(), nblk=nblk, block_rows=block_rows, ring_rows=R
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(length,),
            in_specs=[lane_spec(M), lane_spec(8), lane_spec(8)] + [ring_spec] * (2 * kv),
            out_specs=lane_spec(M),
            scratch_shapes=[pltpu.VMEM((kv, M, LANES), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((B, kv, M, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ring_attend",
    )(visits, pos.astype(jnp.int32), cursor.astype(jnp.int32), qh, mine(k), mine(v), *ring_k, *ring_v)
    return jnp.moveaxis(out[:, :, :N * G].reshape(B, kv, N, G, D), 1, 2)
