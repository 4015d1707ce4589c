"""Expert parallelism: explicit shard_map + all_to_all MoE dispatch.

The library-level EP primitive, sibling to the ring/Ulysses SP modules
(SURVEY.md §2.3 row 6 — the reference has no MoE; the rebuild ships EP
first-class). Layout is the classic GShard/Switch plan:

* tokens are batch-sharded over the ``axis`` (each device holds ``B/n``);
* experts are sharded over the SAME axis (each device owns ``E/n`` whole
  expert FFNs, weights ``[E/n, D, F]`` local);
* routing is capacity-limited top-1; the dispatched token blocks cross the
  mesh twice per layer via ``all_to_all`` (token-shard → expert-shard and
  back), riding ICI.

``models/moe.py`` is the other half of the story: the same math written as
plain sharded einsums for GSPMD to partition automatically inside the
policy's ``jit``. This module is the explicit form — useful when the
schedule must be pinned by hand and as the executable spec the GSPMD path
is tested against.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "data"


def expert_capacity(n_tokens: int, n_experts: int, capacity_factor: float) -> int:
    """Token slots per expert per routing call (shared by the shard_map and
    GSPMD MoE forms so the two can never drift)."""
    return max(1, math.ceil(n_tokens / n_experts * capacity_factor))


def route_top1(
    x: jnp.ndarray, gate_w: jnp.ndarray, n_experts: int, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Capacity-limited top-1 routing for local tokens ``x [Bl, D]``.

    Returns (dispatch [Bl, E, C] 0/1, combine [Bl, E, C] = dispatch ×
    gate-prob, probs [Bl, E] — the full pre-drop gate softmax, for aux
    load-balancing losses). Overflow tokens beyond ``capacity`` per expert
    are dropped (Switch semantics — static shapes for XLA).
    """
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    prob = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
    keep = pos < capacity
    dispatch = (
        onehot[..., None]
        * keep[..., None]
        * jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    )
    combine = dispatch * prob[:, None, None]
    return dispatch, combine, probs


def moe_shard(
    x: jnp.ndarray,
    gate_w: jnp.ndarray,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
    axis_name: str = AXIS,
    capacity_factor: float = 2.0,
) -> jnp.ndarray:
    """Per-shard MoE body (call under shard_map).

    x: LOCAL token shard [Bl, D]; gate_w [D, E] replicated; w1/b1/w2/b2
    LOCAL expert shard [El, ...] where El = E / axis size. Output [Bl, D]:
    sum over each token's selected expert output × gate prob (zeros for
    capacity-dropped tokens).
    """
    n = jax.lax.psum(1, axis_name)
    Bl, D = x.shape
    El = w1.shape[0]
    E = El * n
    capacity = expert_capacity(Bl, E, capacity_factor)

    dispatch, combine, _ = route_top1(x, gate_w, E, capacity)

    # [Bl, E, C] × [Bl, D] → [E, C, D]: this device's contribution to every
    # expert's queue
    xin = jnp.einsum("bec,bd->ecd", dispatch, x.astype(jnp.float32))
    # token-shard → expert-shard: each device keeps its E/n experts' queues
    # from ALL devices; [E, C, D] = [n·El, C, D] → [n, El·C? ...] — tiled
    # all_to_all splits axis 0 (experts) and concats on a fresh leading
    # device axis, giving [n·local? ...]. Concretely: split E into n groups
    # of El, exchange, concat along C: [El, n·C, D].
    xin = jax.lax.all_to_all(
        xin, axis_name, split_axis=0, concat_axis=1, tiled=True
    )                                                        # [El, n·C, D]

    h = jnp.einsum("ecd,edf->ecf", xin, w1.astype(jnp.float32)) + b1[:, None]
    h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32)) + b2[:, None]

    # expert-shard → token-shard: inverse exchange
    out = jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=0, tiled=True
    )                                                        # [E, C, D]
    y = jnp.einsum("bec,ecd->bd", combine, out)
    return y.astype(x.dtype)


def make_expert_dispatch(
    mesh: Mesh, axis: str = AXIS, capacity_factor: float = 2.0
):
    """jitted MoE layer over ``axis``: (x [B, D], gate_w [D, E], w1 [E, D, F],
    b1 [E, F], w2 [E, F, D], b2 [E, D]) → [B, D], tokens batch-sharded and
    experts expert-sharded over the same mesh axis."""
    tok = P(axis)          # tokens: leading dim sharded
    exp = P(axis)          # experts: leading dim sharded
    rep = P()
    wrapped = shard_map(
        functools.partial(
            moe_shard, axis_name=axis, capacity_factor=capacity_factor
        ),
        mesh=mesh,
        in_specs=(tok, rep, exp, exp, exp, exp),
        out_specs=tok,
    )
    return jax.jit(wrapped)
