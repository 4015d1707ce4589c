"""Sequence parallelism primitives: ring attention and Ulysses all-to-all.

The reference handles long horizons by truncated rollouts with carried
recurrent state — it has no sequence parallelism (SURVEY.md §2.3 row 5,
§5.7). The rebuild ships SP as first-class library modules so a transformer
core can scale context length across the mesh (SURVEY.md §7 step 8):

* **Ring attention** — K/V shards rotate around the sequence-axis ring via
  ``ppermute`` while each device accumulates its queries' attention with an
  online (log-sum-exp) softmax; memory per device stays O(T/n), and the
  rotation rides ICI neighbor links.
* **Ulysses** — ``all_to_all`` reshards [seq-sharded, all heads] →
  [full seq, head-sharded], runs dense local attention, and reshards back;
  two collectives per layer, best when heads ≥ mesh axis size.

Both are written as *per-shard* functions to be wrapped in ``shard_map``
(the ``make_*`` helpers below do so) — no hand-written comm beyond the
collectives themselves, per the SURVEY §5.8 design rule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "data"  # default mesh axis to shard the sequence over


def reference_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = False
) -> jnp.ndarray:
    """Plain softmax attention (single-device oracle). [B, T, h, d] in/out."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _block_attend(q, k, v, bias):
    """Unnormalized block attention with running-max bookkeeping.

    Returns (o, m, l): o = sum_j exp(s - m) v_j, m = rowmax(s), l = rowsum
    of exp(s - m); shapes o [B, Tq, h, d], m/l [B, h, Tq].
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = s + bias
    m = s.max(axis=-1)
    # fully-masked rows (causal: a block entirely in the future) have
    # m = -inf; exp(s - m) would be NaN — use a finite baseline there so
    # exp(-inf - 0) = 0 and the block contributes nothing
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    l = p.sum(axis=-1)
    return o, m, l


def ring_attention_shard(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = AXIS,
    causal: bool = False,
) -> jnp.ndarray:
    """Per-shard ring attention body (call under shard_map).

    q/k/v: the LOCAL sequence shard [B, T_local, h, d]; the global sequence
    is the concatenation over the axis in device order. Exact same math as
    full attention (online-softmax accumulation is exact, not approximate).
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tl, h, d = q.shape
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))

    q_pos = my * Tl + jnp.arange(Tl)                       # global query rows

    def bias_for(block_owner):
        if not causal:
            return jnp.zeros((1, 1, Tl, Tl), jnp.float32)
        k_pos = block_owner * Tl + jnp.arange(Tl)
        allowed = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(allowed, 0.0, -jnp.inf)[None, None]

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        acc_o, acc_m, acc_l, kb, vb = carry
        owner = (my - i) % n                               # whose block we hold
        o, m, l = _block_attend(q32, kb, vb, bias_for(owner))
        new_m = jnp.maximum(acc_m, m)
        # exp(-inf - -inf) guards: where both are -inf the block contributed
        # nothing; the scales become 0 via the where
        sc_old = jnp.where(
            jnp.isneginf(acc_m), 0.0, jnp.exp(acc_m - new_m)
        )
        sc_new = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - new_m))
        acc_o = (
            acc_o * sc_old.transpose(0, 2, 1)[..., None]
            + o * sc_new.transpose(0, 2, 1)[..., None]
        )
        acc_l = acc_l * sc_old + l * sc_new
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return acc_o, new_m, acc_l, kb, vb

    def varying(x):
        # constants are axis-invariant; the loop outputs are axis-varying —
        # mark the init carries varying so the fori_loop types match
        return jax.lax.pcast(x, (axis_name,), to="varying")

    init = (
        varying(jnp.zeros((B, Tl, h, d), jnp.float32)),
        varying(jnp.full((B, h, Tl), -jnp.inf, jnp.float32)),
        varying(jnp.zeros((B, h, Tl), jnp.float32)),
        k32,
        v32,
    )
    acc_o, _, acc_l, _, _ = jax.lax.fori_loop(0, n, body, init)
    denom = jnp.maximum(acc_l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc_o / denom).astype(q.dtype)


def ulysses_attention_shard(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = AXIS,
    causal: bool = False,
) -> jnp.ndarray:
    """Per-shard Ulysses attention body (call under shard_map).

    q/k/v: LOCAL sequence shard [B, T_local, h, d] with h divisible by the
    axis size. all_to_all → [B, T_full, h_local, d], dense local attention,
    all_to_all back.
    """
    n = jax.lax.psum(1, axis_name)
    # [B, Tl, h, d] → heads scatter / sequence gather → [B, T, h/n, d]
    def to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qs, ks, vs = to_seq(q), to_seq(k), to_seq(v)
    out = reference_attention(
        qs.astype(jnp.float32), ks.astype(jnp.float32), vs.astype(jnp.float32),
        causal=causal,
    )
    return to_heads(out).astype(q.dtype)


def _make_sp(fn, mesh: Mesh, axis: str, causal: bool):
    spec = P(None, axis)  # [B, T(sharded), h, d]
    wrapped = shard_map(
        functools.partial(fn, axis_name=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return jax.jit(wrapped)


def make_ring_attention(mesh: Mesh, axis: str = AXIS, causal: bool = False):
    """jitted [B, T, h, d] → [B, T, h, d] ring attention over ``axis``
    (inputs/outputs globally shaped; sharding handled inside)."""
    return _make_sp(ring_attention_shard, mesh, axis, causal)


def make_ulysses_attention(mesh: Mesh, axis: str = AXIS, causal: bool = False):
    """jitted Ulysses attention over ``axis`` (h must divide by axis size)."""
    return _make_sp(ulysses_attention_shard, mesh, axis, causal)
