"""The "afmoe" block (Arcee Trinity's architecture) as a recurrent policy core.

``ModelConfig.core = "afmoe"``. A stack of pre-norm blocks on a stream of
width ``hidden_dim``: grouped-query attention of two kinds in one stack
(sliding-window layers with RoPE, every ``global_attn_every``-th layer full
attention with no positional encoding, never a leading dense layer;
``global_attn_offset`` shifts the count where a cut keeps a later period of
the published stack), then a SwiGLU feed-forward that is
dense in the leading ``n_dense_layers`` and a routed mixture with a shared
expert in the rest. One layer, on the stream ``h``, the query at position
``p`` counted from its episode's start:

  a = RMSNorm(h); q = RMSNorm_head(a Wq); k = RMSNorm_head(a Wk); v = a Wv
  window layers: q, k rotated by RoPE(theta) at p
  scores = q . k / sqrt(head_dim), ``n_heads / n_kv_heads`` query heads a KV
  head, over the keys of the SAME episode with p_k <= p_q, and in a window
  layer p_k > p_q - context_window; float32 softmax
  attn = ((softmax v) * sigmoid(a Wgate)) Wo;   h = h + RMSNorm(attn)
  m = RMSNorm(h)
  dense:   f = (silu(m Wg) * (m Wu)) Wd
  experts: s = sigmoid(m Wr) over ``moe_experts``; the ``experts_per_token``
           largest of s + select_bias (the bias has no gradient: its
           balancing update follows the load, see below);
           w = route_scale * s_top / sum(s_top);
           f = SwiGLU_shared(m) + sum over the chosen e of w_e SwiGLU_e(m)
  h = h + RMSNorm(f);   after the last layer y = RMSNorm(h)

The stream enters scaled by sqrt(hidden_dim) (``mup_enabled``). A core that is
a stack of these layers with less in each (``models/looplm.py``) says so in its
configuration: ``attn_qk_norm``, ``attn_out_gate``, ``rope_full_layers`` (RoPE on
full layers too), ``moe_experts`` 0 (every FFN dense), ``n_shared_experts`` 0 (no shared expert), ``loop_steps`` rings a layer.

**One function for a step and for a chunk.** ``AfmoeCore(carry, x, resets)``
takes ``x [B, T, H]``: the learner's pass is ONE pass over the chunk (T
queries against the carried keys and the chunk's own), and the actor's step
is the same function at T = 1, so step/sequence parity is structural.
``resets [B, T]`` (1 where an episode starts AT step t) cut attention by
episode inside the chunk and restart ``p``.

**The carry** is ``{"pos": [B] i32, "cursor": [B] i32, "kv": per layer (K, V)
[B, R, n_kv_heads * head_dim]}`` in the compute type (one position is one
contiguous row: on the v5e a step's read of that layout runs at 646 GB/s and
its scatter costs 4% more, where ``[B, n_kv_heads, R, head_dim]`` reads at
743 GB/s and pays 47% for the scatter: my chip run, PR 26). ``pos`` is the position
of the lane's next query in its episode, which is also how many of the
ring's newest keys belong to that episode. ``cursor`` is the slot the next
key is written to (mod R, kept mod the rings' common multiple); it never
resets, so a chunk's T keys always land in T consecutive slots. The slot
``s`` holds the key written ``age = (cursor - 1 - s) mod R`` steps before
the newest, and is visible iff ``age < pos`` (same episode) and, in a window
layer, inside the window: the mask is arithmetic on the two counters, and
``reset`` only zeroes ``pos``: no cache leaf is touched. A write is a
scatter of T rows at ``cursor``, never a copy of the cache.

R is ``context_window + rollout_chunk`` for a window layer and
``full_context`` for a full layer. The slack is what lets the learner be
handed a chunk's START without a copy: the T keys the rollout wrote lie
further back than a window layer's first query can see, and in a full layer
further back than the episode is long (``require_episode_fits``), so
``chunk_start_view`` is the START's two counters beside the END's rings.

**Several lane sets in one step.** A rollout whose two teams play the same
parameters steps both in ONE call (``models/lanes.py``): the counters and
the activations are concatenated, every product against a weight sees all
rows, and each set's rings stay the set's own arrays: the products against a
ring, its masks and its write run a block at a time on the block's rows
(``lanes.by_lane_block``), so no cache is copied or joined.

**Experts held here.** ``held_experts`` and ``expert_offset`` say which of
the ``moe_experts`` routed experts this chip holds (guide: one chip's share
of a layer divided over several). The router keeps its whole width and its
experts per token; the layer adds the terms of the experts it holds and the
shared expert. Every token-expert pair is sorted by held expert (pairs of
absent experts last) and multiplied in groups (``_grouped_swiglu``: on a TPU
at widths of whole lanes the Pallas kernels of ``ops/pallas/grouped_matmul.py``,
chosen by ``grouped_takes_kernel``; ``jax.lax.ragged_dot`` elsewhere); the
buffer has a row for every pair, so none can be dropped, and the layer
counts that from the buffer (``moe_dropped``). A step therefore costs what
the router chose: the kernels visit a tile of rows once for each held
expert whose group meets it and read that expert's weights each visit, an
empty group never and a tile past the groups never, and early in training
lanes that watch like game states choose like experts, so a chip's share of
the pairs is a draw of the weights (PERF.md section 6). With
``held_experts`` 0 or ``moe_experts`` the layer is the whole layer.

**The selection bias** is the one parameter no gradient reaches: the layer
sows each expert's tokens minus the mean (``select_bias_err``) and the optimizer
step moves the bias against it (``train/ppo._balance_select_bias``). Scopes inside
``policy_core``: ``core_attn_window``, ``core_attn_full``, ``core_cache_write``,
``core_router``, ``core_experts_routed``, ``core_expert_shared``, ``core_dense_ffn``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dotaclient_tpu.config import ModelConfig
from dotaclient_tpu.models.lanes import by_lane_block
from dotaclient_tpu.ops.pallas import grouped_matmul

_NEG = -1e30


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# -- the static plan of a configuration --------------------------------------


def layer_is_full(cfg: ModelConfig, layer: int) -> bool:
    return (
        layer >= cfg.n_dense_layers
        and (layer + 1 + cfg.global_attn_offset) % cfg.global_attn_every == 0
    )


def layer_is_dense(cfg: ModelConfig, layer: int) -> bool:
    return cfg.moe_experts == 0 or layer < cfg.n_dense_layers


def ring_len(cfg: ModelConfig, layer: int) -> int:
    if layer_is_full(cfg, layer):
        return cfg.full_context
    return cfg.context_window + cfg.rollout_chunk


def held_experts(cfg: ModelConfig) -> Tuple[int, int]:
    """(how many routed experts live here, index of the first)."""
    return (cfg.held_experts or cfg.moe_experts), cfg.expert_offset


def _cursor_modulus(cfg: ModelConfig) -> int:
    return math.lcm(*(ring_len(cfg, l) for l in range(cfg.n_layers)))


def carry_bytes_per_lane(cfg: ModelConfig) -> int:
    width = 2 * cfg.n_kv_heads * cfg.head_dim * _dtype(cfg.dtype).dtype.itemsize
    return 8 + cfg.loop_steps * sum(ring_len(cfg, l) * width for l in range(cfg.n_layers))


def check_config(cfg: ModelConfig) -> None:
    if cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(f"n_heads {cfg.n_heads} not a multiple of n_kv_heads {cfg.n_kv_heads}")
    if cfg.head_dim % 2:
        raise ValueError(f"head_dim {cfg.head_dim} must be even for RoPE")
    check_routing(cfg)


def check_routing(cfg: ModelConfig) -> None:
    """What ``RoutedExperts`` needs of a configuration with expert layers."""
    held, offset = held_experts(cfg)
    if cfg.moe_experts and cfg.n_dense_layers < cfg.n_layers and not (
        0 < cfg.experts_per_token <= cfg.moe_experts
        and 0 <= offset and offset + held <= cfg.moe_experts
    ):
        raise ValueError(
            f"{cfg.core} routing: {cfg.experts_per_token} of {cfg.moe_experts} experts a token, "
            f"experts {offset}..{offset + held - 1} held"
        )


def require_episode_fits(cfg: ModelConfig, episode_steps: int, rollout_len: int) -> None:
    """A full layer sees its whole episode only while the episode, and the
    chunk a learner is handed the start of, fit its ring."""
    if rollout_len > cfg.rollout_chunk:
        raise ValueError(
            f"core {cfg.core!r}: ppo.rollout_len {rollout_len} exceeds model.rollout_chunk "
            f"{cfg.rollout_chunk}, the slack its window rings keep for one chunk"
        )
    if episode_steps + cfg.rollout_chunk > cfg.full_context:
        raise ValueError(
            f"core {cfg.core!r}: an episode of {episode_steps} steps and a chunk of "
            f"{cfg.rollout_chunk} do not fit model.full_context {cfg.full_context}"
        )


# -- the carry ------------------------------------------------------------------


def initial_state(cfg: ModelConfig, batch_size: int) -> Dict[str, Any]:
    dtype = _dtype(cfg.dtype)

    def ring(layer: int):
        return jnp.zeros((batch_size, ring_len(cfg, layer), cfg.n_kv_heads * cfg.head_dim), dtype)

    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "cursor": jnp.zeros((batch_size,), jnp.int32),
        "kv": tuple((ring(l), ring(l)) for _ in range(cfg.loop_steps) for l in range(cfg.n_layers)),
    }


def reset(carry: Dict[str, Any], keep: jnp.ndarray) -> Dict[str, Any]:
    """Start a new episode in the rows where ``keep`` is 0: the position
    returns to 0 and the mask hides what the ring still holds."""
    return {**carry, "pos": jnp.where(keep > 0, carry["pos"], 0)}


def chunk_start_view(start: Dict[str, Any], end: Dict[str, Any]) -> Dict[str, Any]:
    """The carry as it stood when a chunk of at most ``rollout_chunk`` steps
    began, read from the rings as the chunk left them."""
    return {"pos": start["pos"], "cursor": start["cursor"], "kv": end["kv"]}


def chunk_positions(pos0: jnp.ndarray, resets: Optional[jnp.ndarray], T: int):
    """(episode segment [B, T], position in the episode [B, T]) of a chunk's
    steps. Segment 0 continues the carry's episode."""
    idx = jnp.arange(T, dtype=jnp.int32)[None, :]
    if resets is None:
        return jnp.zeros((pos0.shape[0], T), jnp.int32), pos0[:, None] + idx
    starts = resets > 0
    seg = jnp.cumsum(starts.astype(jnp.int32), axis=1)
    first = jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    return seg, jnp.where(seg == 0, pos0[:, None] + idx, idx - first)


def ring_masks(pos0: jnp.ndarray, cursor0: jnp.ndarray, seg: jnp.ndarray, R: int):
    """What a chunk's T queries may see of a ring of ``R`` rows and of the
    chunk's own rows, from the two counters and the chunk's segments:
    (``age [B, R]`` of each slot's row in steps before the newest,
    ``see_ring [B, T, R]``: the row is of the carry's episode and so is the
    query, ``see_chunk [B, T, T]``: causal and of the same segment)."""
    t = jnp.arange(seg.shape[1], dtype=jnp.int32)
    age = (cursor0[:, None] - 1 - jnp.arange(R, dtype=jnp.int32)[None, :]) % R
    in_episode = age < pos0[:, None]                               # [B, R]
    see_ring = (seg == 0)[:, :, None] & in_episode[:, None, :]    # [B, T, R]
    see_chunk = (t[:, None] >= t[None, :])[None] & (seg[:, :, None] == seg[:, None, :])
    return age, see_ring, see_chunk


def write_rows(ring: jnp.ndarray, cursor0: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """``ring [B, R, C]`` with a chunk's rows ``new [B, T, C]`` in the T slots
    from ``cursor0`` on: a scatter of T rows, never a copy of the ring."""
    B, R, _ = ring.shape
    slots = (cursor0[:, None] + jnp.arange(new.shape[1], dtype=jnp.int32)[None, :]) % R
    return ring.at[jnp.arange(B)[:, None], slots].set(new.astype(ring.dtype))


# -- pieces ---------------------------------------------------------------------


class RMSNorm(nn.Module):
    config: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), _dtype(cfg.param_dtype)
        )
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + cfg.rms_norm_eps)
        return x * scale.astype(jnp.float32)


def _dense(cfg: ModelConfig, features: int, name: str) -> nn.Dense:
    return nn.Dense(
        features, use_bias=False, dtype=_dtype(cfg.dtype),
        param_dtype=_dtype(cfg.param_dtype), name=name,
    )


def rope(x: jnp.ndarray, p: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate ``x [B, T, ..., D]`` (float32) by position ``p [B, T]``: pairs
    (i, i + D/2) turn by ``p * theta ** (-2i / D)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = p.astype(jnp.float32)[..., None] * freq                # [B, T, D/2]
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class SwiGLU(nn.Module):
    config: ModelConfig
    width: int

    @nn.compact
    def __call__(self, m: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        g = _dense(cfg, self.width, "gate_proj")(m)
        u = _dense(cfg, self.width, "up_proj")(m)
        return _dense(cfg, cfg.hidden_dim, "down_proj")(nn.silu(g) * u)


@jax.checkpoint
def _attend(q, k, v, ring_k, ring_v, see_ring, see_chunk):
    """Softmax attention of ``q [B, T, kv, G, D]`` (already scaled) over the
    ring's keys ``[B, R, kv, D]`` and the chunk's own ``[B, T, kv, D]``, one
    softmax over both parts (a query always sees itself) -> float32
    ``[B, T, kv, G, D]``. Rematerialised in a backward pass: the scores of a
    learner's chunk against its rings are a third of a gigabyte a layer, and
    recomputing them costs one more read of the ring."""
    s_ring = jnp.einsum("btkgd,brkd->bkgtr", q, ring_k, preferred_element_type=jnp.float32)
    s_own = jnp.einsum("btkgd,bjkd->bkgtj", q, k, preferred_element_type=jnp.float32)
    s_ring = jnp.where(see_ring[:, None, None], s_ring, _NEG)
    s_own = jnp.where(see_chunk[:, None, None], s_own, _NEG)
    top = jnp.maximum(s_ring.max(-1), s_own.max(-1))[..., None]
    e_ring, e_own = jnp.exp(s_ring - top), jnp.exp(s_own - top)
    total = e_ring.sum(-1) + e_own.sum(-1)                             # [B, kv, G, T]
    out = jnp.einsum(
        "bkgtr,brkd->btkgd", e_ring.astype(q.dtype), ring_v,
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bkgtj,bjkd->btkgd", e_own.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out / jnp.moveaxis(total, 3, 1)[..., None]


class Attention(nn.Module):
    config: ModelConfig
    full: bool

    @nn.compact
    def __call__(self, a, ring, pos0, cursor0, seg, p):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        B, T, _ = a.shape
        nh, kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        G, W = nh // kv, cfg.context_window
        with jax.named_scope("core_attn_full" if self.full else "core_attn_window"):
            q = _dense(cfg, nh * D, "wq")(a).reshape(B, T, kv, G, D)
            k = _dense(cfg, kv * D, "wk")(a).reshape(B, T, kv, D)
            v = _dense(cfg, kv * D, "wv")(a).reshape(B, T, kv, D)
            gate = _dense(cfg, nh * D, "wgate")(a) if cfg.attn_out_gate else None
            q = RMSNorm(cfg, name="q_norm")(q) if cfg.attn_qk_norm else q.astype(jnp.float32)
            k = RMSNorm(cfg, name="k_norm")(k) if cfg.attn_qk_norm else k.astype(jnp.float32)
            if cfg.rope_full_layers or not self.full:
                q, k = rope(q, p, cfg.rope_theta), rope(k, p, cfg.rope_theta)
            q = (q / math.sqrt(D)).astype(dtype)
            k = k.astype(dtype)
            t = jnp.arange(T, dtype=jnp.int32)

            def attend(ring, q, k, v, pos0, cursor0, seg):
                R = ring[0].shape[1]
                ring_k, ring_v = (r.reshape(-1, R, kv, D) for r in ring)   # stored [B, R, kv D]
                age, see_ring, see_chunk = ring_masks(pos0, cursor0, seg, R)
                if not self.full:
                    see_ring &= (t[None, :, None] + 1 + age[:, None, :]) < W
                    see_chunk &= ((t[:, None] - t[None, :]) < W)[None]
                few = G * T < _MXU_ROWS      # too few rows a KV head for a product against the ring as [R, kv, D]
                return (_attend_few_rows if few and G == 1 else _attend_few_rows_grouped if few else _attend)(q, k, v, ring_k, ring_v, see_ring, see_chunk), None

            out, _ = by_lane_block(attend, ring, q, k, v.astype(dtype), pos0, cursor0, seg)
            out = out.reshape(B, T, nh * D) * nn.sigmoid(gate.astype(jnp.float32)) if cfg.attn_out_gate else out.reshape(B, T, nh * D)
            attn = _dense(cfg, cfg.hidden_dim, "wo")(out.astype(dtype))
        with jax.named_scope("core_cache_write"):
            _, ring = by_lane_block(
                lambda ring, k, v, cursor0: (None, tuple(write_rows(r, cursor0, new) for r, new in zip(ring, (k, v)))),
                ring, k.reshape(B, T, kv * D), v.reshape(B, T, kv * D), cursor0,
            )
        return attn, ring


@jax.custom_vjp
def _take_rows(x, idx, back):
    """``x[idx]`` whose transpose is ``g[back]`` summed over each row's
    copies: a gather both ways, where autodiff would scatter-add."""
    return x[idx]


def _take_rows_fwd(x, idx, back):
    return x[idx], (back, x.shape[0])


def _take_rows_bwd(res, g):
    back, n = res
    return g[back].reshape((n, -1) + g.shape[1:]).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


class RoutedExperts(nn.Module):
    """Router, the held experts' terms and the shared expert: ``[B, T, H]``
    -> ``[B, T, H]``."""

    config: ModelConfig

    @nn.compact
    def __call__(self, m: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        B, T, H = m.shape
        E, k, F = cfg.moe_experts, cfg.experts_per_token, cfg.expert_ffn_dim
        held, offset = held_experts(cfg)
        N = B * T
        x = m.reshape(N, H)

        with jax.named_scope("core_router"):
            wr = self.param("router", nn.initializers.lecun_normal(), (H, E), pdtype)
            bias = self.param("select_bias", nn.initializers.zeros, (E,), pdtype)
            # the router multiplies in float32 whatever the compute type:
            # its 128 scores pick the experts, and a rounded score picks others
            s = nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), wr.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )) if cfg.route_score == "sigmoid" else _softmax_scores(x, wr)  # [N, E]
            _, top = jax.lax.top_k(s + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
            w = jnp.take_along_axis(s, top, axis=1)                         # [N, k]
            if cfg.route_norm:
                w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
            w = w * cfg.route_scale
            local = top - offset
            here = (local >= 0) & (local < held)                            # [N, k]
            # pairs sorted by held expert, pairs of absent experts last
            key = jnp.where(here, local, _pad_groups(here, held) if cfg.pad_expert_groups else held).reshape(N * k)
            order = jnp.argsort(key, stable=True)
            back = jnp.argsort(order)
            load = (key[:, None] == jnp.arange(held)[None, :]).sum(axis=0).astype(jnp.int32)
            n_here = load.sum()
            row_here = here.reshape(N * k)[order] if cfg.pad_expert_groups else jnp.arange(N * k) < n_here

        def experts(name, shape, fan_in_axis):
            return self.param(
                name, nn.initializers.lecun_normal(in_axis=fan_in_axis, out_axis=-1, batch_axis=(0,)),
                shape, pdtype,
            ).astype(dtype)

        with jax.named_scope("core_experts_routed"):
            wg = experts("expert_gate", (held, H, F), 1)
            wu = experts("expert_up", (held, H, F), 1)
            wd = experts("expert_down", (held, F, H), 1)
            # row j of the buffer is pair order[j], of token order[j] // k
            xs = _take_rows(x.astype(dtype), order // k, back)              # [N k, H]
            xs = jnp.where(row_here[:, None], xs, 0)
            ys = _grouped_swiglu(cfg, xs, wg, wu, wd, load)                 # [N k, H]
            ys = _take_rows(ys, back, order).reshape(N, k, H)
            ys = jnp.where(here[:, :, None], ys, 0)
            routed = jnp.einsum(
                "nkh,nk->nh", ys, w.astype(dtype), preferred_element_type=jnp.float32
            )
        with jax.named_scope("core_expert_shared"):
            shared = SwiGLU(cfg, cfg.n_shared_experts * F, name="shared")(x.astype(dtype)) if cfg.n_shared_experts else None

        # per token, time-major like the scanned cores' sows (train/ppo.py
        # masks the bootstrap step out of the auxiliary loss by time index)
        def time_major(z):
            return jnp.swapaxes(z.reshape(B, T, E), 0, 1)

        # the choice itself, for whoever asks (``mutable=["routing"]``): a
        # comparison with a reference has to know which experts were taken
        self.sow("routing", "chosen", top.reshape(B, T, k))
        chosen = jax.nn.one_hot(top, E, dtype=jnp.float32).sum(axis=1)      # [N, E]
        self.sow("losses", "moe_probs", time_major(s / s.sum(axis=-1, keepdims=True)))
        self.sow("losses", "moe_frac", time_major(chosen / k))
        # a pair is multiplied iff its row of the buffer lies inside the groups' rows
        covered = here if cfg.pad_expert_groups else (back.reshape(N, k) < n_here) & here
        self.sow("losses", "moe_local", here.sum().astype(jnp.float32))
        self.sow("losses", "moe_dropped", (here.sum() - covered.sum()).astype(jnp.float32))
        self.sow("losses", "moe_load", (_pairs_held(here, local, held) if cfg.pad_expert_groups else load).astype(jnp.float32))
        if grouped_takes_kernel(cfg, "tpu"):
            self.sow("losses", "moe_kernel_rows_share", grouped_matmul.visited_rows(load, N * k) / (N * k))
        # what the balancing update of the selection bias reads (train/ppo.py
        # _balance_select_bias): each expert's tokens minus the mean, over
        # the whole router, whatever share of it is held here
        tokens = jax.lax.stop_gradient(chosen.sum(axis=0))
        self.sow("losses", "select_bias_err", tokens - tokens.mean())
        return (routed + shared.astype(jnp.float32)).reshape(B, T, H) if cfg.n_shared_experts else routed.reshape(B, T, H)


class Block(nn.Module):
    config: ModelConfig
    layer: int

    @nn.compact
    def __call__(self, h, ring, pos0, cursor0, seg, p):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        a = RMSNorm(cfg, name="in_norm")(h).astype(dtype)
        attn, ring = Attention(cfg, layer_is_full(cfg, self.layer), name="attn")(
            a, ring, pos0, cursor0, seg, p
        )
        h = h + RMSNorm(cfg, name="post_attn_norm")(attn)
        m = RMSNorm(cfg, name="pre_mlp_norm")(h).astype(dtype)
        if layer_is_dense(cfg, self.layer):
            with jax.named_scope("core_dense_ffn"):
                f = SwiGLU(cfg, cfg.dense_ffn_dim, name="ffn")(m)
        else:
            f = RoutedExperts(cfg, name="moe")(m)
        return h + RMSNorm(cfg, name="post_mlp_norm")(f), ring


class AfmoeCore(nn.Module):
    """``(carry, x [B, T, H], resets [B, T] | None) -> (carry, y [B, T, H])``."""

    config: ModelConfig

    @nn.compact
    def __call__(self, carry, x, resets=None):
        cfg = self.config
        check_config(cfg)
        T = x.shape[1]
        pos0, cursor0 = carry["pos"], carry["cursor"]
        seg, p = chunk_positions(pos0, resets, T)
        h = x.astype(jnp.float32)                  # the residual stream stays float32
        if cfg.mup_enabled:
            h = h * math.sqrt(cfg.hidden_dim)
        rings = []
        for layer in range(cfg.n_layers):
            h, ring = Block(cfg, layer, name=f"layer_{layer}")(
                h, carry["kv"][layer], pos0, cursor0, seg, p
            )
            rings.append(ring)
        y = RMSNorm(cfg, name="out_norm")(h).astype(_dtype(cfg.dtype))
        carry = {
            "pos": p[:, -1] + 1,
            "cursor": (cursor0 + T) % _cursor_modulus(cfg),
            "kv": tuple(rings),
        }
        return carry, y


# -- a step of a layer without grouped queries -------------------------------------

_MXU_ROWS = 8


@jax.checkpoint
def _attend_few_rows(q, k, v, ring_k, ring_v, see_ring, see_chunk):
    """``_attend`` where a KV head serves ONE query head (G = 1) and the chunk
    is a few steps: the rollout's T = 1 of a layer with as many KV heads as
    query heads. Each of ``_attend``'s products against a ring then has T < 8
    rows a head, and the TPU compiler does not multiply so few on the MXU: it
    widened the ring to float32, copied it into another layout and reduced it
    on the vector unit, every step, 77% of the device's time at the looped
    cell's widths (my chip run, PR 30; PERF.md section 6). Here every head's
    query is a column of ONE block-diagonal ``[kv D, kv]`` matrix, so the
    scores of all heads are one product with the ring as it lies, ``[R, kv D]``
    rows, and the values one product ``[kv, R] x [R, kv D]`` of which head k
    keeps its own D columns: kv times the operations a head needs, which are
    a hundredth of what the ring's bytes cost. Same softmax as ``_attend``."""
    B, T, kv, _, D = q.shape
    R = ring_k.shape[1]
    heads = jnp.eye(kv, dtype=q.dtype)
    q_blocks = (q[:, :, :, 0, :, None] * heads[:, None, :]).reshape(B, T, kv * D, kv)
    s_ring = jnp.einsum(
        "brc,btck->bktr", ring_k.reshape(B, R, kv * D), q_blocks, preferred_element_type=jnp.float32
    )
    s_own = jnp.einsum("btkgd,bjkd->bkgtj", q, k, preferred_element_type=jnp.float32)[:, :, 0]
    s_ring = jnp.where(see_ring[:, None], s_ring, _NEG)
    s_own = jnp.where(see_chunk[:, None], s_own, _NEG)
    top = jnp.maximum(s_ring.max(-1), s_own.max(-1))[..., None]
    e_ring, e_own = jnp.exp(s_ring - top), jnp.exp(s_own - top)
    total = e_ring.sum(-1) + e_own.sum(-1)                               # [B, kv, T]
    # (a batched matmul and not an einsum that moves axes: XLA:CPU has no
    # bfloat16 kernel for the latter, and the rehearsals run there)
    every = jnp.matmul(
        e_ring.astype(q.dtype).reshape(B, kv * T, R), ring_v.reshape(B, R, kv * D),
        preferred_element_type=jnp.float32,
    ).reshape(B, kv, T, kv, D)
    out = jnp.einsum("bktkd->btkd", every) + jnp.einsum(
        "bktj,bjkd->btkd", e_own.astype(q.dtype), v, preferred_element_type=jnp.float32
    )
    return (out / jnp.moveaxis(total, 2, 1)[..., None])[:, :, :, None]


@jax.checkpoint
def _attend_few_rows_grouped(q, k, v, ring_k, ring_v, see_ring, see_chunk):
    """``_attend_few_rows`` where a KV head serves G > 1 query heads and G T is
    still under 8 rows a KV head: the rollout's T = 1 of LFM2's 32 query heads
    over 8 KV heads of 64 (G = 4). Through ``_attend`` the TPU compiler copied
    every ring into a position-minor layout, every step (four copies of 126 MB
    a rollout step at 40 lanes a team: the fused program's optimised HLO, PR
    37); with every query head a column of ONE block-diagonal ``[kv D, kv G]``
    matrix the scores are one product with the ring as it lies and the values
    one product ``[kv G, R] x [R, kv D]`` of which a head keeps its own KV
    head's D columns. The G = 1 case of this is ``_attend_few_rows``, kept
    word for word: the looped cell's loop lowers to another program through
    this form (182 instructions fewer in its frozen loop, speed not measured:
    ROADMAP D15 folds the two when a PR can measure that cell)."""
    B, T, kv, G, D = q.shape
    R = ring_k.shape[1]
    heads = jnp.eye(kv, dtype=q.dtype)
    # column (k', g) holds query head (k', g) in the D rows of its KV head k' and zeros in the others'
    q_blocks = (jnp.swapaxes(q, 3, 4)[..., None, :] * heads[:, None, :, None]).reshape(B, T, kv * D, kv * G)
    s_ring = jnp.einsum(
        "brc,btcn->bntr", ring_k.reshape(B, R, kv * D), q_blocks, preferred_element_type=jnp.float32
    )
    s_own = jnp.einsum("btkgd,bjkd->bkgtj", q, k, preferred_element_type=jnp.float32).reshape(B, kv * G, T, T)
    s_ring = jnp.where(see_ring[:, None], s_ring, _NEG)
    s_own = jnp.where(see_chunk[:, None], s_own, _NEG)
    top = jnp.maximum(s_ring.max(-1), s_own.max(-1))[..., None]
    e_ring, e_own = jnp.exp(s_ring - top), jnp.exp(s_own - top)
    total = (e_ring.sum(-1) + e_own.sum(-1)).reshape(B, kv, G, T)
    every = jnp.matmul(
        e_ring.astype(q.dtype).reshape(B, kv * G * T, R), ring_v.reshape(B, R, kv * D),
        preferred_element_type=jnp.float32,
    ).reshape(B, kv, G, T, kv, D)
    out = jnp.einsum("bkgtkd->btkgd", every) + jnp.einsum(
        "bkgtj,bjkd->btkgd", e_own.astype(q.dtype).reshape(B, kv, G, T, T), v, preferred_element_type=jnp.float32
    )
    return out / jnp.moveaxis(total, 3, 1)[..., None]


# -- the routed buffer multiplied whole (``ModelConfig.pad_expert_groups``) ---------


def _pad_groups(here: jnp.ndarray, held: int) -> jnp.ndarray:
    """``[N, k]``: the held expert whose group the ZERO row of an absent pair
    pads. ``RoutedExperts``' buffer has N k rows whatever the router chose, and
    the grouped products take time by the row tiles inside the groups and by
    the groups that are not empty: where lanes choose alike a layer's pairs
    land on held experts all or none, and a step's time follows the router's
    draw (the LFM2 cell: 3% between seeds, PERF.md section 6). Padded, every
    group holds its pairs and an even share of the zero rows (the absent pairs
    dealt round the held experts in order), the N k rows are all multiplied
    (``moe_kernel_rows_share`` 1), every held expert's weights are read in
    every pass, and the time is the buffer's: a fixed shape at a fixed cost,
    as a ring is read whole. The zero rows stand for the tokens of the chips
    the deployment's other experts lie on: they are work the configuration
    states, not rows a faster layer may skip. Outputs, gradients and the sown
    counts are the unpadded layer's (a zero row gives a zero row and is masked
    besides)."""
    absent = ~here.reshape(-1)
    return ((jnp.cumsum(absent) - 1) % held).reshape(here.shape)


def _pairs_held(here: jnp.ndarray, local: jnp.ndarray, held: int) -> jnp.ndarray:
    """``[held]``: the pairs each held expert was chosen for (without padding)."""
    return ((local[..., None] == jnp.arange(held)) & here[..., None]).sum(axis=(0, 1)).astype(jnp.int32)


# -- the grouped products: a kernel on the TPU -------------------------------------


def grouped_takes_kernel(cfg: ModelConfig, platform: str) -> bool:
    """Whether ``RoutedExperts``' grouped products are the Pallas kernels of
    ``ops/pallas/grouped_matmul.py`` in a program lowered for ``platform``: the
    ONE predicate, the model's and the learner's for
    ``moe/grouped_kernel_calls_total``. Mosaic compiles for the TPU alone, and
    the kernels take widths of whole lanes in the configuration's compute type
    (the published widths, not the toy widths of ``tests/``)."""
    return platform == "tpu" and grouped_matmul.takes(cfg.hidden_dim, cfg.expert_ffn_dim, _dtype(cfg.dtype))


def _swiglu_ragged(xs, wg, wu, wd, load):
    mid = nn.silu(jax.lax.ragged_dot(xs, wg, load)) * jax.lax.ragged_dot(xs, wu, load)
    return jax.lax.ragged_dot(mid, wd, load)


# ``platform_dependent`` traces BOTH paths at every call (a layer has one, and a start traces the core
# many times): under ``jit`` a path is traced once a shape and lowered once a program (``kimilinear._recurrence``'s)
def _swiglu_kernel(interpret, xs, wg, wu, wd, load):
    return grouped_matmul.grouped_swiglu(xs, wg, wu, wd, load, interpret=interpret)


_ragged_traced_once = jax.jit(_swiglu_ragged)
# a branch per ``interpret``, made once: a branch made anew at each call is traced anew
_kernel_traced_once = {i: functools.partial(jax.jit(_swiglu_kernel, static_argnums=0), i) for i in (False, True)}


def _grouped_swiglu(cfg: ModelConfig, xs, wg, wu, wd, load):
    """``silu(xs Wg) * (xs Wu) Wd`` over the sorted buffer, a group of
    ``load`` rows a held expert from row 0: the Pallas kernels where the
    program is lowered for a platform on which ``grouped_takes_kernel``
    (interpreted anywhere but on a TPU: a test's case), ``ragged_dot``
    elsewhere. One traced program serves the CPU and the chip. Rows past the
    groups are undefined on the kernel's path: ``RoutedExperts`` selects them away."""
    kernel = {p: _kernel_traced_once[p != "tpu"] for p in ("tpu", "cpu") if grouped_takes_kernel(cfg, p)}
    if not kernel:
        return _swiglu_ragged(xs, wg, wu, wd, load)
    return jax.lax.platform_dependent(xs, wg, wu, wd, load, default=_ragged_traced_once, **kernel)


# -- a router that scores by softmax (``ModelConfig.route_score``) -------------------


def _softmax_scores(x: jnp.ndarray, wr: jnp.ndarray) -> jnp.ndarray:
    """``RoutedExperts``' scores where ``route_score`` is "softmax" (the
    Qwen3-MoE router): ``softmax(x W_r)`` over the experts, in float32 at the
    product's highest precision as the sigmoid router's. The experts per token
    are then the largest scores and their weights the scores renormalised over
    the chosen (``route_norm``): ``norm_topk_prob``."""
    logits = jnp.dot(x.astype(jnp.float32), wr.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


Core = AfmoeCore      # what ``models/policy.py resident_core`` constructs
