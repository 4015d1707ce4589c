"""Pallas kernel for ONE step of a delta-rule linear-attention (KDA) layer:
each head's state read from HBM once and written once.

Called by ``models/kimilinear.py`` where a rollout step (T = 1) runs on a
TPU at head widths that fill whole lanes; the closed form over a chunk
(``kimilinear.delta_rule_chunk``) stays the learner's path, the step's path
everywhere else, and this kernel's specification in ``tests/``.

With ``keep`` = the lane carries a state, a head of a lane, all float32:

    G  = exp(log_alpha)                         [d_k]
    ks = keep * S0^T (G * k)                    [d_v]
    u  = beta * (v - ks)                        [d_v]
    S1 = keep * G[:, None] * S0 + k[:, None] * u[None, :]
    o  = S1^T q                                 [d_v]

XLA makes this two passes over ``S0`` and one over ``S1``: the update needs
the whole reduce ``ks`` before its first element, and a head's 64 KiB do
not stay on the chip between two fusions. Here a grid step holds one lane's
block of heads in VMEM: read, scale, reduce over rows, update, read out,
write, and ``S1`` lies where ``S0`` lay (``input_output_aliases``). Every
COLUMN of ``S1`` depends on the same column of ``S0`` only, and the rows of a
head are its sublanes, so both reduces are multiply-and-adds down the
sublanes on the vector unit; no product runs on the MXU and nothing is
rounded below float32. ``G``, ``k`` and ``q`` multiply ROWS of a state, so
the kernel wants them down the sublanes: it stacks the block's ``[heads,
d_k]`` rows, transposes the stack once a grid step and broadcasts a column
along the lanes for each head.

A void lane's state (``keep`` false) is selected away before anything
multiplies it, whatever it holds; the state written is then ``k u^T``.

As in ``lstm.py`` nothing here looks at the backend: the caller names
``interpret=`` (``True`` only where there is no TPU to compile for).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # the lane width of a vector register: head widths are whole multiples of it


def takes(d_k: int, d_v: int) -> bool:
    """The head shapes this kernel runs: square, whole lanes."""
    return d_k == d_v and d_k % LANES == 0


# Mosaic's default scoped-VMEM limit on the v5e and the most this kernel asks for (``lstm.py``'s two numbers)
_VMEM_DEFAULT = 16 << 20
_VMEM_CEILING = 100 << 20


def _vmem_limit(state_block_bytes: int) -> int:
    """A grid step's state block is in VMEM four times (read and written, each
    double-buffered) beside a few vectors: 4 MiB at 16 heads of 128 x 128, far
    under the default. Wider heads ask for what they need."""
    return max(_VMEM_DEFAULT, min(4 * state_block_bytes + (4 << 20), _VMEM_CEILING))


def _kernel(keep_ref, la_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, s_out_ref):
    """One lane, ``hb`` heads: ``la, k, q, v, beta [1, hb, d]`` (``beta``
    repeated along the lanes), ``s [1, hb, d, d]`` -> ``o [1, hb, d]``, ``s``."""
    hb, d = k_ref.shape[1], k_ref.shape[2]
    keep = keep_ref[pl.program_id(0)] != 0
    rows = jnp.concatenate([jnp.exp(la_ref[0]), k_ref[0], q_ref[0]], axis=0)      # [3 hb, d]
    pad = -rows.shape[0] % LANES
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), jnp.float32)], axis=0)
    cols = rows.T                                        # [d, R]: column j hb + i is head i's vector j
    outs = []
    for i in range(hb):
        G, k, q = (cols[:, j * hb + i:j * hb + i + 1] for j in range(3))         # [d, 1] each
        S = G * jnp.where(keep, s_ref[0, i], 0.0)                                # [d, d]
        ks = jnp.sum(S * k, axis=0, keepdims=True)                               # [1, d]
        u = beta_ref[0, i:i + 1, :] * (v_ref[0, i:i + 1, :] - ks)
        S = S + k * u
        s_out_ref[0, i] = S
        outs.append(jnp.sum(S * q, axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(outs, axis=0)


def kda_step_pallas(
    q: jnp.ndarray,            # f32 [B, h, d]   (normalised)
    k: jnp.ndarray,            # f32 [B, h, d]
    v: jnp.ndarray,            # f32 [B, h, d]
    log_alpha: jnp.ndarray,    # f32 [B, h, d]   (<= 0)
    beta: jnp.ndarray,         # f32 [B, h]
    S0: jnp.ndarray,           # f32 [B, h, d, d]
    keep: jnp.ndarray,         # bool [B]: false where the lane's state is void (read and ignored)
    *,
    interpret: bool,
    heads_per_block: int = 16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the delta rule (module docstring) -> ``(o [B, h, d], S1 [B,
    h, d, d])``; ``S1`` takes ``S0``'s buffer where the caller lets it go."""
    B, h, d, d_v = S0.shape
    if not takes(d, d_v):
        raise ValueError(f"kda_step_pallas takes square heads of whole lanes ({LANES}), got {d} x {d_v}")
    # a block's heads are the second-minor axis of the row operands: eight at a time, or all of them
    hb = heads_per_block if heads_per_block % 8 == 0 and h % heads_per_block == 0 else h
    rows = pl.BlockSpec((1, hb, d), lambda b, g, keep: (b, g, 0))
    state = pl.BlockSpec((1, hb, d, d), lambda b, g, keep: (b, g, 0, 0))
    o, S1 = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, h // hb),
            in_specs=[rows, rows, rows, rows, rows, state], out_specs=[rows, state],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, h, d), jnp.float32), jax.ShapeDtypeStruct(S0.shape, jnp.float32)),
        input_output_aliases={6: 1},                     # operands count the prefetched scalars: S0 is the seventh
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_vmem_limit(hb * d * d * 4)
        ),
        interpret=interpret,
        name="kda_step",
    )(keep.astype(jnp.int32), log_alpha, k, q, v, jnp.broadcast_to(beta[..., None], (B, h, d)), S0)
    return o, S1
