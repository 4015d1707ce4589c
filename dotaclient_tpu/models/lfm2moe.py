"""LFM2's block (``lfm2_moe``) as a recurrent policy core: gated short
convolutions beside grouped-query attention, routed experts after both.

``ModelConfig.core = "lfm2moe"``. A stack of pre-norm layers on a float32
stream ``h`` of width ``hidden_dim``, two residual adds a layer, no
post-norm, no input scale, no bias anywhere:

  h = h + Mix(RMSNorm(h));  h = h + FFN(RMSNorm(h));  after the last layer
  y = RMSNorm(h)

Which layer is which comes from the fields the afmoe core's cut uses: layer
l is an ATTENTION layer iff ``afmoe.layer_is_full`` (``global_attn_every``,
``global_attn_offset``, never a leading dense layer) and a CONVOLUTION layer
otherwise; its FFN is a dense SwiGLU in the leading ``n_dense_layers`` and
``afmoe.RoutedExperts`` (sigmoid top-k of score + bias, the held share; no
shared expert where ``n_shared_experts`` is 0) in the rest. With ``a =
RMSNorm(h)`` a mixer's input:

**Convolution layer** (the mixer; ``shortconv_taps`` = K taps a channel):

  [B, C, x] = split3(a W_in)           hidden -> 3 x hidden
  u = B * x                            the input gate
  c_t = sum_{j<K} w_j * u_{t-(K-1)+j}  causal, depthwise: the K - 1 earlier
                                       rows from the lane's history, zero
                                       before its episode's first position
  out = (C * c) W_out                  the output gate, then hidden -> hidden

The carry is the last K - 1 rows of ``u`` a lane and layer (kilobytes: 2 x
2,048 bfloat16 at the published width), in the compute type. A step is T =
1 of the chunk form: ``shortconv.causal_conv`` (the function the Kimi-Linear
core's KDA layer convolves q, k and v with) over the history and the chunk's
own rows, no tap across an episode start inside the chunk.

**Attention layer**: ``afmoe.Attention`` as a full layer with what LFM2 has
switched on in the configuration (``attn_qk_norm``: RMSNorm over each head's
query and key, one scale vector each; ``rope_full_layers``: RoPE on both,
rotate-half; ``attn_out_gate`` off): ``n_heads`` query heads over
``n_kv_heads`` KV heads of ``head_dim``, causal softmax at ``head_dim ** -0.5``
over the lane's ring of ``full_context`` rows and the chunk. Its ring
functions, masks and cache write are that module's code, not a copy.

**The carry** is ``{"pos", "cursor"}`` as the afmoe core's, ``"kv"``: a pair
of rings ``[B, full_context, n_kv_heads * head_dim]`` for each attention
layer, and ``"conv"``: the history ``[B, K - 1, hidden_dim]`` of each
convolution layer. ``reset`` only zeroes ``pos`` and touches no leaf: a
convolution layer whose lane stands at ``pos == 0`` reads its history as
void (a select on rows it reads anyway), as the ring's mask hides what the
ring still holds. A history is overwritten every step, so a chunk's START
cannot be read back from its end: ``chunk_start_view`` is the start's
counters and histories (the start's own buffers) beside the END's rings
(the Kimi-Linear core's rule, with a leaf 250 times smaller).

Scopes inside ``policy_core``: ``core_conv`` (a convolution mixer whole) with
``core_conv_state`` inside it (history read, taps, history write); the shared
layers' own: ``core_attn_full``, ``core_cache_write``, ``core_router``,
``core_experts_routed``, ``core_dense_ffn``. Sown into ``losses`` for the
learner's gauges (``train/ppo._shortconv_gauges``): ``shortconv_history_sq``
(the mean square of ``u``, the rows the histories carry) and
``shortconv_gate`` (the mean magnitude of the output gate C) a convolution
layer, and ``shortconv_void_reads`` ``[B, T]``, the convolution layers that
read a void history at each step.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import flax.linen as nn
import jax
import jax.numpy as jnp

from dotaclient_tpu.config import ModelConfig
from dotaclient_tpu.models import afmoe
from dotaclient_tpu.models.afmoe import (
    Attention, RMSNorm, RoutedExperts, SwiGLU, _dense, _dtype, chunk_positions, layer_is_dense, layer_is_full, reset,
)
from dotaclient_tpu.models.lanes import by_lane_block
from dotaclient_tpu.models.shortconv import causal_conv


# -- the static plan of a configuration --------------------------------------


def attn_layers(cfg: ModelConfig) -> List[int]:
    return [l for l in range(cfg.n_layers) if layer_is_full(cfg, l)]


def conv_layers(cfg: ModelConfig) -> List[int]:
    return [l for l in range(cfg.n_layers) if not layer_is_full(cfg, l)]


def check_config(cfg: ModelConfig) -> None:
    afmoe.check_config(cfg)
    if cfg.mup_enabled or cfg.loop_steps != 1 or cfg.shortconv_taps < 2 or not cfg.rope_full_layers:
        raise ValueError(
            "core 'lfm2moe' has no input scale, one pass a position, a convolution of at least two taps and "
            "no window layer (every attention layer is full, with RoPE): set mup_enabled false, loop_steps 1, "
            f"rope_full_layers true (got {cfg.mup_enabled}, {cfg.loop_steps}, {cfg.rope_full_layers}, "
            f"shortconv_taps {cfg.shortconv_taps})"
        )


def carry_bytes_per_lane(cfg: ModelConfig) -> int:
    item = _dtype(cfg.dtype).dtype.itemsize
    ring = cfg.full_context * 2 * cfg.n_kv_heads * cfg.head_dim * item
    history = (cfg.shortconv_taps - 1) * cfg.hidden_dim * item
    return 8 + len(attn_layers(cfg)) * ring + len(conv_layers(cfg)) * history


# An episode, and the chunk a learner is handed the start of, have to fit the
# attention rings: the afmoe core's rule for its full layers, word for word.
require_episode_fits = afmoe.require_episode_fits


# -- the carry ------------------------------------------------------------------


def initial_state(cfg: ModelConfig, batch_size: int) -> Dict[str, Any]:
    dtype = _dtype(cfg.dtype)
    ring = lambda: jnp.zeros((batch_size, cfg.full_context, cfg.n_kv_heads * cfg.head_dim), dtype)
    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "cursor": jnp.zeros((batch_size,), jnp.int32),
        "kv": tuple((ring(), ring()) for _ in attn_layers(cfg)),
        "conv": tuple(
            jnp.zeros((batch_size, cfg.shortconv_taps - 1, cfg.hidden_dim), dtype) for _ in conv_layers(cfg)
        ),
    }


def chunk_start_view(start: Dict[str, Any], end: Dict[str, Any]) -> Dict[str, Any]:
    """The carry as it stood when a chunk of at most ``rollout_chunk`` steps
    began: the START's counters and histories (overwritten since; its own
    buffers, kept) beside the rings as the chunk left them
    (``afmoe.chunk_start_view``'s argument holds for them)."""
    return {**start, "kv": end["kv"]}


# -- the mixer ----------------------------------------------------------------------


class ShortConv(nn.Module):
    """``(a [B, T, H], history [B, K - 1, H], pos0 [B], seg [B, T]) -> (mix
    [B, T, H], history)``."""

    config: ModelConfig

    @nn.compact
    def __call__(self, a, history, pos0, seg):
        cfg = self.config
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        H, K = cfg.hidden_dim, cfg.shortconv_taps
        carried = pos0 > 0                           # at position 0 the history is void
        with jax.named_scope("core_conv"):
            gate_in, gate_out, x = jnp.split(_dense(cfg, 3 * H, "in_proj")(a), 3, axis=-1)
            u = gate_in * x
            # published order: w_j weighs the row K - 1 - j steps back (a Conv1d's, padded on the left)
            taps = self.param(
                "conv", nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (K, H), pdtype,
            ).astype(jnp.float32)
            with jax.named_scope("core_conv_state"):
                c, history = by_lane_block(functools.partial(causal_conv, taps[::-1]), history, u, carried, seg)
            mix = _dense(cfg, H, "out_proj")((gate_out.astype(jnp.float32) * c).astype(dtype))
        self.sow("losses", "shortconv_history_sq", jnp.square(u.astype(jnp.float32)).mean())
        self.sow("losses", "shortconv_gate", jnp.abs(gate_out.astype(jnp.float32)).mean())
        return mix, history


class Block(nn.Module):
    config: ModelConfig
    layer: int

    @nn.compact
    def __call__(self, h, leaf, pos0, cursor0, seg, p):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        a = RMSNorm(cfg, name="operator_norm")(h).astype(dtype)
        if layer_is_full(cfg, self.layer):
            mix, leaf = Attention(cfg, True, name="attn")(a, leaf, pos0, cursor0, seg, p)
        else:
            mix, leaf = ShortConv(cfg, name="conv")(a, leaf, pos0, seg)
        h = h + mix.astype(jnp.float32)
        m = RMSNorm(cfg, name="ffn_norm")(h).astype(dtype)
        if layer_is_dense(cfg, self.layer):
            with jax.named_scope("core_dense_ffn"):
                f = SwiGLU(cfg, cfg.dense_ffn_dim, name="ffn")(m)
        else:
            f = RoutedExperts(cfg, name="moe")(m)
        return h + f.astype(jnp.float32), leaf


class Lfm2MoeCore(nn.Module):
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
        leaves = {True: list(carry["kv"]), False: list(carry["conv"])}
        seen = {True: 0, False: 0}
        for layer in range(cfg.n_layers):
            full = layer_is_full(cfg, layer)
            h, leaves[full][seen[full]] = Block(cfg, layer, name=f"layer_{layer}")(
                h, leaves[full][seen[full]], pos0, cursor0, seg, p
            )
            seen[full] += 1
        y = RMSNorm(cfg, name="out_norm")(h).astype(_dtype(cfg.dtype))
        self.sow("losses", "shortconv_void_reads", seen[False] * (p == 0).astype(jnp.float32))
        carry = {
            "pos": p[:, -1] + 1,
            "cursor": (cursor0 + T) % cfg.full_context,
            "kv": tuple(leaves[True]),
            "conv": tuple(leaves[False]),
        }
        return carry, y


Core = Lfm2MoeCore      # what ``models/policy.py resident_core`` constructs
