"""Kimi-Linear's block as a recurrent policy core: delta-rule linear
attention (KDA) beside latent attention (MLA), routed experts after both.

``ModelConfig.core = "kimilinear"``. A stack of pre-norm layers on a float32
stream ``h`` of width ``hidden_dim``, two residual adds a layer and no
post-norm, no input scale:

  h = h + Mix(RMSNorm(h));  h = h + FFN(RMSNorm(h));  after the last layer
  y = RMSNorm(h)

Which layer is which comes from the fields the afmoe core's cut uses:
layer l is an MLA layer iff ``afmoe.layer_is_full`` (``global_attn_every``,
``global_attn_offset``, never a leading dense layer) and a KDA layer
otherwise; its FFN is a dense SwiGLU in the leading ``n_dense_layers`` and
``afmoe.RoutedExperts`` (shared expert, sigmoid top-k of score + bias, the
held share) in the rest. With ``a = RMSNorm(h)`` a mixer's input:

**KDA layer** (``n_heads`` heads of ``kda_head_dim`` = d_k = d_v):

  q~, k~, v~ = a Wq, a Wk, a Wv
  q, k, v = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))   causal depthwise
            convolution of ``kda_conv_kernel`` taps: out_t = sum_j w_j in_{t-j}
  q_h = L2norm(q_h) / sqrt(d_k),  k_h = L2norm(k_h)          per head
  log alpha_t = -exp(A_log_h) softplus((a Wf_down) Wf_up + dt_bias)
            per CHANNEL of d_k through a rank-d_k projection; alpha in (0, 1)
  beta_t = sigmoid(a Wb)                                     a scalar a head
  S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
            S in R^{d_k x d_v} a head, float32
  o_t = S_t^T q_t
  out = (RMSNorm_head(o_t) * sigmoid((a Wg_down) Wg_up)) Wo

A new episode starts from S = 0 and an empty convolution history. A chunk of
T steps is the SAME recurrence in closed form (``delta_rule_chunk``). With
``g_t`` the running sum of ``log alpha`` inside the chunk and ``G_t =
exp(g_t)``, the pseudo-values ``u`` solve a unit lower-triangular system,

  u_i = beta_i (v_i - S_0^T (G_i * k_i) - sum_{j<i} ((G_i / G_j) * k_i . k_j) u_j)
  o_t = S_0^T (G_t * q_t) + sum_{i<=t} ((G_t / G_i) * q_t . k_i) u_i
  S_T = Diag(G_T) S_0 + sum_i (G_T / G_i * k_i) u_i^T

and an episode start AT step t is ``alpha_t = 0``: the ``S_0`` terms of the
later steps and every pair (i, j) of different episodes drop out by
``afmoe.chunk_positions``' segment, and the convolution's taps do not cross
it. ``G_i / G_j`` is ``exp(g_i - g_j)`` a pair and channel, never a quotient of
two exponentials (``G_j`` underflows inside a chunk where a channel forgets
fast).

**One step** (T = 1) is the same formula with no pair left in it. With
``keep`` = the lane carries a state (``pos0 > 0`` and the step starts no
episode) and ``G = exp(log alpha)``, a head of a lane, float32 throughout:

  ks = keep * S_0^T (G * k)                      [d_v]
  u  = beta * (v - ks)                           [d_v]
  S_1 = keep * Diag(G) S_0 + k u^T
  o  = keep * S_0^T (G * q) + (q . k) u          ( = S_1^T q )

**Where each path runs** (``_recurrence``; two needs, two paths that share
this formula and no code): a chunk wants the closed form and its gradient,
so the learner's pass, and every T > 1, is ``delta_rule_chunk``; a rollout
step wants each state touched once, so where T = 1, the heads are square
and fill whole lanes (``d_k`` a multiple of 128: the published 128, not the
toy widths of ``tests/`` and of ``serve/engine.py``'s tests) and the
program is lowered for a TPU, it is ``delta_rule_step``: the Pallas kernel
of ``ops/pallas/kda_step.py``, which reads a head's 64 KiB into VMEM, does
all four lines there and writes ``S_1`` where ``S_0`` lay (XLA's form reads
every state twice and writes it once: the update needs the whole of ``ks``
first). ``step_takes_kernel`` is that choice as a predicate, made from what
the code can see (the widths, the platform of the lowering through
``jax.lax.platform_dependent``: ONE traced program serves a CPU rehearsal
and the chip) and from no option; everywhere else a step is
``delta_rule_chunk`` at T = 1. The learner counts the kernel's layer-steps
by the same predicate (``kda/kernel_steps_total``).

**MLA layer** (no query compression, no rotation: ``q_lora_rank`` null,
``mla_use_nope``):

  q_h = a Wq                        n_heads x (qk_nope_head_dim + qk_rope_head_dim)
  c, k_pe = split(a Wkv_a);         c = RMSNorm(c)  (kv_lora_rank), k_pe shared by all heads
  k_h = [c Wuk_h, k_pe],  v_h = c Wuv_h
  attn = softmax(q_h . k_h / sqrt(qk_nope + qk_rope)) v_h over the same
         episode's positions <= t;  out = attn Wo

in the absorbed form: the ring keeps ``[c, k_pe]`` a position (one row of
``kv_lora_rank + qk_rope_head_dim`` numbers, in the compute type), the query
is ``[q_nope_h Wuk_h^T, q_pe_h]`` against that row, the values are the row
itself and ``Wuv_h`` comes after the softmax. That is one KV head of the
row's width serving ``n_heads`` query heads, so it runs through
``afmoe._attend`` over the whole row (the k_pe columns of the value product
are dropped after it: a slice of the ring would be a second copy of it a
step), for a step and for a chunk alike.

**The carry** is ``{"pos", "cursor"}`` as the afmoe core's, ``"latent"``: a
ring ``[B, full_context, kv_lora_rank + qk_rope_head_dim]`` for each MLA
layer, and ``"kda"``: for each KDA layer ``(S [B, n_heads, d_k, d_v]
float32, the last kda_conv_kernel - 1 pre-convolution rows [B, K - 1, 3
n_heads d_k])``. ``reset`` only zeroes ``pos`` and touches no leaf: a KDA
layer whose lane stands at ``pos == 0`` reads its state and its history as
void (a select on what it reads anyway), as the ring's mask hides what the
ring still holds. A state is overwritten every step, so a chunk's START
cannot be read back from its end: ``chunk_start_view`` is the start's
counters, states and convolution rows (the start's own buffers, float32
already) beside the END's latent rings.

Scopes inside ``policy_core``: ``core_kda`` (projections, convolution,
gates, output) with ``core_kda_state`` (the recurrence and its readout; the
kernel's call carries it in its name) inside it; ``core_attn_latent`` with
``core_latent_attend`` (the products against the ring) inside it;
``core_cache_write``; the FFNs' ``core_router``,
``core_experts_routed``, ``core_expert_shared``, ``core_dense_ffn``. Sown
into ``losses`` for the learner's gauges (``train/ppo._kda_gauges``):
``kda_decay``, ``kda_beta``, ``kda_state_sq`` a KDA layer, and
``kda_void_reads`` ``[B, T]``, the KDA layers that read a void state at each
step.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import flax.linen as nn
import jax
import jax.numpy as jnp

from dotaclient_tpu.config import ModelConfig
from dotaclient_tpu.models import afmoe
from dotaclient_tpu.models.afmoe import (
    RMSNorm, RoutedExperts, SwiGLU, _attend, _dense, _dtype, chunk_positions,
    layer_is_dense, layer_is_full, reset, ring_masks, write_rows,
)
from dotaclient_tpu.models.lanes import by_lane_block
from dotaclient_tpu.models.shortconv import causal_conv
from dotaclient_tpu.ops.pallas import kda_step

_NEG = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


# -- the static plan of a configuration --------------------------------------


def mla_layers(cfg: ModelConfig) -> List[int]:
    return [l for l in range(cfg.n_layers) if layer_is_full(cfg, l)]


def kda_layers(cfg: ModelConfig) -> List[int]:
    return [l for l in range(cfg.n_layers) if not layer_is_full(cfg, l)]


def latent_width(cfg: ModelConfig) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def check_config(cfg: ModelConfig) -> None:
    afmoe.check_routing(cfg)
    if cfg.mup_enabled or cfg.loop_steps != 1 or cfg.kda_conv_kernel < 2:
        raise ValueError(
            "core 'kimilinear' has no input scale, one pass a position and a convolution of "
            f"at least two taps: set mup_enabled false, loop_steps 1 (got {cfg.mup_enabled}, "
            f"{cfg.loop_steps}, kda_conv_kernel {cfg.kda_conv_kernel})"
        )


def carry_bytes_per_lane(cfg: ModelConfig) -> int:
    item = _dtype(cfg.dtype).dtype.itemsize
    width = cfg.n_heads * cfg.kda_head_dim
    state = cfg.n_heads * cfg.kda_head_dim ** 2 * 4 + (cfg.kda_conv_kernel - 1) * 3 * width * item
    ring = cfg.full_context * latent_width(cfg) * item
    return 8 + len(kda_layers(cfg)) * state + len(mla_layers(cfg)) * ring


# An episode, and the chunk a learner is handed the start of, have to fit the
# latent rings: the afmoe core's rule for its full layers, word for word.
require_episode_fits = afmoe.require_episode_fits


# -- the carry ------------------------------------------------------------------


def initial_state(cfg: ModelConfig, batch_size: int) -> Dict[str, Any]:
    dtype = _dtype(cfg.dtype)
    nh, D, K = cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "cursor": jnp.zeros((batch_size,), jnp.int32),
        "latent": tuple(
            jnp.zeros((batch_size, cfg.full_context, latent_width(cfg)), dtype) for _ in mla_layers(cfg)
        ),
        "kda": tuple(
            (jnp.zeros((batch_size, nh, D, D), jnp.float32), jnp.zeros((batch_size, K - 1, 3 * nh * D), dtype))
            for _ in kda_layers(cfg)
        ),
    }


def chunk_start_view(start: Dict[str, Any], end: Dict[str, Any]) -> Dict[str, Any]:
    """The carry as it stood when a chunk of at most ``rollout_chunk`` steps
    began: the START's counters, states and convolution rows (overwritten
    since; its own buffers, kept) beside the latent rings as the chunk left
    them (``afmoe.chunk_start_view``'s argument holds for them)."""
    return {**start, "latent": end["latent"]}


# -- the delta rule over a chunk -------------------------------------------------


@jax.checkpoint
def delta_rule_chunk(q, k, v, log_alpha, beta, S0, seg, carried):
    """The KDA recurrence over a chunk in closed form (module docstring).

    ``q, k [B, T, h, d_k]`` (normalised), ``v [B, T, h, d_v]``, ``log_alpha
    [B, T, h, d_k]`` (<= 0), ``beta [B, T, h]``, all float32; ``S0 [B, h,
    d_k, d_v]`` float32; ``seg [B, T]`` the steps' episode segments (0
    continues the carry's episode); ``carried [B]`` false where the lane's
    state is void (``S0`` is then read and ignored) -> (``o [B, T, h,
    d_v]``, ``S_T [B, h, d_k, d_v]``), float32.

    Rematerialised in a backward pass: what it would save is the pairs'
    decays, ``[B, h, T, T, d_k]``, several times over. The pair sums and the
    triangular inverse are float32 on the vector unit and in ``[T, T]``
    products at full precision; the products against the state and the
    pseudo-values take the caller's matmul precision."""
    T = q.shape[1]
    t = jnp.arange(T, dtype=jnp.int32)
    heads_first = lambda x: jnp.moveaxis(x, 2, 1)                       # [B, h, T, ...]
    g = jnp.cumsum(log_alpha, axis=1)
    gh, kh, qh, bh = heads_first(g), heads_first(k), heads_first(q), heads_first(beta)
    same = seg[:, :, None] == seg[:, None, :]                              # [B, i, j]
    lower = same & (t[:, None] >= t[None, :])[None]
    strict = same & (t[:, None] > t[None, :])[None]
    # decay[i, j] = prod of alpha over steps j+1..i, a channel; 0 outside the episode's causal pairs
    decay = jnp.exp(jnp.where(
        lower[:, None, :, :, None], gh[:, :, :, None, :] - gh[:, :, None, :, :], _NEG
    ))                                                                     # [B, h, i, j, d_k]
    kd = kh[:, :, None, :, :] * decay
    kk = (kh[:, :, :, None, :] * kd).sum(-1)                               # [B, h, i, j]
    qk = (qh[:, :, :, None, :] * kd).sum(-1)
    # (I + A)^-1 for the strictly lower A = Diag(beta) kk: A is nilpotent, so the
    # inverse is sum_n (-A)^n = (I - A)(I + A^2)(I + A^4)...
    power = -jnp.where(strict[:, None], bh[..., None] * kk, 0.0)
    inverse = jnp.eye(T, dtype=jnp.float32) + power
    n = 1
    while 2 * n < T:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
        n *= 2
    first = ((seg == 0) & carried[:, None])[:, :, None, None]              # the step still sees S0
    G = jnp.exp(g)
    into_state = jnp.concatenate([k * G, q * G], axis=1)
    if 2 * T < afmoe._MXU_ROWS:
        # a step's two rows a head are no product on the TPU: the compiler rounds the whole
        # state to the compute type into a new buffer for it, every step, and a state masked
        # ahead of its two readers is a third buffer (the step's HLO, PERF.md section 6).
        # Multiply and reduce where the state lies, in float32, and mask what comes of it.
        from_state = (into_state[..., None] * S0[:, None]).sum(axis=-2)
    else:
        # the learner's chunk: one masked copy a chunk, so that no void state (whatever it
        # holds) reaches a product or a gradient
        S0 = jnp.where(carried[:, None, None, None], S0, 0.0)
        from_state = jnp.einsum("bthk,bhkv->bthv", into_state, S0)
    k_state, q_state = (jnp.where(first, x, 0.0) for x in (from_state[:, :T], from_state[:, T:]))
    u = jnp.einsum("bhij,bjhv->bihv", inverse, beta[..., None] * (v - k_state))
    o = q_state + jnp.einsum("bhij,bjhv->bihv", qk, u)
    to_end = jnp.exp(jnp.where((seg == seg[:, -1:])[:, :, None, None], g[:, -1:] - g, _NEG)) * k
    S = jnp.where(first[:, -1, :, :, None], G[:, -1, :, :, None] * S0, 0.0) + jnp.einsum(
        "bjhk,bjhv->bhkv", to_end, u
    )
    return o, S


# -- the delta rule over one step: the kernel's path ---------------------------------


def step_takes_kernel(cfg: ModelConfig, platform: str) -> bool:
    """Whether a single step (T = 1) of this configuration's KDA layers is the
    Pallas kernel in a program lowered for ``platform``: the ONE predicate, the
    model's (``_recurrence``) and the learner's for ``kda/kernel_steps_total``.
    Mosaic compiles for the TPU alone, the kernel takes square heads of whole
    lanes, and it stands where the closed form takes its few-rows branch (a
    step's two rows a head are no product there)."""
    return platform == "tpu" and kda_step.takes(cfg.kda_head_dim, cfg.kda_head_dim) and 2 < afmoe._MXU_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def delta_rule_step(interpret, q, k, v, log_alpha, beta, S0, seg, carried):
    """``delta_rule_chunk`` at T = 1 through ``ops/pallas/kda_step.py``
    (``interpret`` only where there is no TPU to compile for): the same
    arguments and results, the state read once and written once where it
    lies. The program never differentiates a step; where a test does, the
    backward is the closed form's."""
    keep = carried & (seg[:, 0] == 0)                    # the step sees S0: a carried state, no episode start
    o, S = kda_step.kda_step_pallas(
        q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], S0, keep, interpret=interpret
    )
    return o[:, None], S


def _step_fwd(interpret, *args):
    return delta_rule_step(interpret, *args), args


def _step_bwd(interpret, residuals, cotangents):
    *floats, seg, carried = residuals
    _, vjp = jax.vjp(lambda *f: delta_rule_chunk(*f, seg, carried), *floats)
    return (*vjp(cotangents), None, None)


delta_rule_step.defvjp(_step_fwd, _step_bwd)


# ``platform_dependent`` traces BOTH paths at every call (a step has one a KDA layer and lane set, and a
# start traces the rollout several times): under ``jit`` a path is traced once a shape and lowered once a program
_step_traced_once = jax.jit(delta_rule_step, static_argnums=0)
_chunk_traced_once = jax.jit(delta_rule_chunk)


def _recurrence(cfg: ModelConfig, T: int):
    """``(S0, q, k, v, log_alpha, beta, seg, carried) -> (o, S)`` over one lane
    block: the closed form; for a single step the kernel where the program is
    lowered for a platform on which ``step_takes_kernel`` (interpreted
    anywhere but on a TPU: a test's case). One traced program serves the CPU
    and the chip."""

    def recur(S0, *rows):
        args = (*rows[:5], S0, *rows[5:])
        kernel = {
            p: functools.partial(_step_traced_once, p != "tpu") for p in ("tpu", "cpu") if T == 1 and step_takes_kernel(cfg, p)
        }
        if not kernel:
            return delta_rule_chunk(*args)
        return jax.lax.platform_dependent(*args, default=_chunk_traced_once, **kernel)

    return recur


def _l2norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _decay_rate_init(key, shape, dtype):
    """``A_log``: the log of a rate drawn uniformly from [1, 16) a head."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """``dt_bias``: softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


# -- the two mixers ----------------------------------------------------------------


class KDA(nn.Module):
    """``(a [B, T, H], (S, history), pos0 [B], seg [B, T]) -> (mix [B, T, H],
    (S, history))``."""

    config: ModelConfig

    @nn.compact
    def __call__(self, a, state, pos0, seg):
        cfg = self.config
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        B, T, _ = a.shape
        nh, D, K = cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
        W = nh * D
        S0, history = state
        carried = pos0 > 0                           # at position 0 state and history are void
        with jax.named_scope("core_kda"):
            x = jnp.concatenate([_dense(cfg, W, n)(a) for n in ("wq", "wk", "wv")], axis=-1)
            taps = self.param(
                "conv", nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (K, 3 * W), pdtype,
            ).astype(jnp.float32)
            # the convolution over the carried rows is the one `models/lfm2moe.py`'s mixer runs (`shortconv.causal_conv`)
            y, history = by_lane_block(functools.partial(causal_conv, taps), history, x, carried, seg)
            q, k, v = (z.reshape(B, T, nh, D) for z in jnp.split(nn.silu(y), 3, axis=-1))
            q, k = _l2norm(q) / math.sqrt(D), _l2norm(k)
            f = _dense(cfg, W, "wf_up")(_dense(cfg, D, "wf_down")(a)).astype(jnp.float32)
            f = f + self.param("dt_bias", _dt_bias_init, (W,), pdtype).astype(jnp.float32)
            rate = jnp.exp(self.param("A_log", _decay_rate_init, (nh,), pdtype).astype(jnp.float32))
            log_alpha = -rate[:, None] * nn.softplus(f.reshape(B, T, nh, D))
            beta = nn.sigmoid(_dense(cfg, nh, "wb")(a).astype(jnp.float32))
            gate = _dense(cfg, W, "wg_up")(_dense(cfg, D, "wg_down")(a)).astype(jnp.float32)
            with jax.named_scope("core_kda_state"):
                o, S = by_lane_block(_recurrence(cfg, T), S0, q, k, v, log_alpha, beta, seg, carried)
            out = RMSNorm(cfg, name="o_norm")(o) * nn.sigmoid(gate.reshape(B, T, nh, D))
            mix = _dense(cfg, cfg.hidden_dim, "wo")(out.reshape(B, T, W).astype(dtype))
        self.sow("losses", "kda_decay", jnp.exp(log_alpha).mean())
        self.sow("losses", "kda_beta", beta.mean())
        # a lane's sum a block, outside `core_kda_state` (that scope times the recurrence alone); a per-lane
        # `mean` here left 96 more instructions in the rollout's loop (tests/test_shared_pass_hlo.py)
        state_sq, _ = by_lane_block(lambda S: (jnp.square(S).sum(axis=(1, 2, 3)), None), S)
        self.sow("losses", "kda_state_sq", state_sq.sum() / (B * W * D))
        return mix, (S, history)


class LatentAttention(nn.Module):
    """``(a [B, T, H], ring [B, R, C + r], pos0, cursor0, seg) -> (attn [B, T,
    H], ring)``, absorbed."""

    config: ModelConfig

    @nn.compact
    def __call__(self, a, ring, pos0, cursor0, seg):
        cfg = self.config
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        B, T, _ = a.shape
        nh, C = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        Wl = latent_width(cfg)

        def per_head(name, width):
            return self.param(
                name, nn.initializers.lecun_normal(in_axis=1, out_axis=2, batch_axis=(0,)),
                (nh, C, width), pdtype,
            ).astype(dtype)

        with jax.named_scope("core_attn_latent"):
            q = _dense(cfg, nh * (dn + dr), "wq")(a).reshape(B, T, nh, dn + dr)
            kv = _dense(cfg, Wl, "wkv_a")(a)
            c = RMSNorm(cfg, name="kv_norm")(kv[..., :C])
            row = jnp.concatenate([c, kv[..., C:].astype(jnp.float32)], axis=-1).astype(dtype)
            q_latent = jnp.einsum("bthn,hcn->bthc", q[..., :dn], per_head("wuk", dn))
            q = jnp.concatenate([q_latent, q[..., dn:]], axis=-1).astype(jnp.float32)
            q = (q / math.sqrt(dn + dr)).astype(dtype)[:, :, None]           # one KV head: [B, T, 1, nh, Wl]
            with jax.named_scope("core_latent_attend"):

                def attend(ring, q, row, pos0, cursor0, seg):
                    _, see_ring, see_chunk = ring_masks(pos0, cursor0, seg, ring.shape[1])
                    own, held = row[:, :, None], ring[:, :, None]             # [B, T | R, 1, Wl]
                    return _attend(q, own, own, held, held, see_ring, see_chunk)[:, :, 0, :, :C], None

                out, _ = by_lane_block(attend, ring, q, row, pos0, cursor0, seg)
            v = jnp.einsum("bthc,hcv->bthv", out.astype(dtype), per_head("wuv", dv))
            attn = _dense(cfg, cfg.hidden_dim, "wo")(v.reshape(B, T, nh * dv))
        with jax.named_scope("core_cache_write"):
            _, ring = by_lane_block(lambda ring, *rows: (None, write_rows(ring, *rows)), ring, cursor0, row)
        return attn, ring


class Block(nn.Module):
    config: ModelConfig
    layer: int

    @nn.compact
    def __call__(self, h, leaf, pos0, cursor0, seg):
        cfg = self.config
        dtype = _dtype(cfg.dtype)
        a = RMSNorm(cfg, name="in_norm")(h).astype(dtype)
        if layer_is_full(cfg, self.layer):
            mix, leaf = LatentAttention(cfg, name="attn")(a, leaf, pos0, cursor0, seg)
        else:
            mix, leaf = KDA(cfg, name="kda")(a, leaf, pos0, seg)
        h = h + mix.astype(jnp.float32)
        m = RMSNorm(cfg, name="pre_mlp_norm")(h).astype(dtype)
        if layer_is_dense(cfg, self.layer):
            with jax.named_scope("core_dense_ffn"):
                f = SwiGLU(cfg, cfg.dense_ffn_dim, name="ffn")(m)
        else:
            f = RoutedExperts(cfg, name="moe")(m)
        return h + f.astype(jnp.float32), leaf


class KimiLinearCore(nn.Module):
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
        leaves = {True: list(carry["latent"]), False: list(carry["kda"])}
        seen = {True: 0, False: 0}
        for layer in range(cfg.n_layers):
            full = layer_is_full(cfg, layer)
            h, leaves[full][seen[full]] = Block(cfg, layer, name=f"layer_{layer}")(
                h, leaves[full][seen[full]], pos0, cursor0, seg
            )
            seen[full] += 1
        y = RMSNorm(cfg, name="out_norm")(h).astype(_dtype(cfg.dtype))
        self.sow("losses", "kda_void_reads", seen[False] * (p == 0).astype(jnp.float32))
        carry = {
            "pos": p[:, -1] + 1,
            "cursor": (cursor0 + T) % cfg.full_context,
            "latent": tuple(leaves[True]),
            "kda": tuple(leaves[False]),
        }
        return carry, y


Core = KimiLinearCore      # what ``models/policy.py resident_core`` constructs
