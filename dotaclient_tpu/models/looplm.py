"""A looped stack (Ouro's "LoopLM") as a recurrent policy core.

``ModelConfig.core = "looplm"``. A stack of ``n_layers`` pre- and post-norm
layers on a float32 stream of width ``hidden_dim`` is run ``loop_steps``
(R) times over the same position with ONE set of weights. One layer, in
loop step r, the query at position ``p`` of its episode:

  a = RMSNorm_1(h);  q, k, v = a Wq, a Wk, a Wv     (no bias, no head norm)
  q, k rotated by RoPE(theta) at p
  scores = q . k / sqrt(head_dim) over the keys OF LOOP STEP r AND THIS
  LAYER, same episode, p_k <= p_q; float32 softmax
  attn = (softmax v) Wo;                              h = h + RMSNorm_2(attn)
  m = RMSNorm_3(h);  f = (silu(m Wg) * (m Wu)) Wd;    h = h + RMSNorm_4(f)

  after the last layer of loop step r:
  y_r = RMSNorm_out(h);  g_r = w_gate . y_r + b_gate;  loop step r + 1
  starts from h = y_r, through the SAME layers, norm and gate

The layer is ``models/afmoe.py``'s ``Block`` with what an afmoe layer has
besides switched off in the configuration (``attn_qk_norm``,
``attn_out_gate``, ``mup_enabled`` false; ``rope_full_layers`` true;
``moe_experts`` 0 and ``global_attn_every`` 1, so every layer is a dense
SwiGLU under full attention): its attention, rings, masks, RoPE, norms and
cache write are that module's code, not a copy. A weight's gradient is the
sum over its R uses.

**The carry** is the afmoe core's ``{"pos", "cursor", "kv"}`` with
``n_layers x loop_steps`` pairs of rings of ``full_context`` rows, pair
``r * n_layers + l`` for layer l in loop step r (``afmoe.initial_state``):
a loop step's keys are of that loop step's stream, so no two share a ring.
Reset and chunk start are the afmoe core's: a counter moves, nothing is
copied.

**The exit gate.** ``lam_r = sigmoid(g_r)`` is the probability of stopping
after loop step r given that r was reached, and ``exit_distribution`` turns
the gates into ``p_r = lam_r prod_{j<r} (1 - lam_j)``, the last loop step
taking the remainder. Every loop step always runs (``early_exit_threshold``
1): the rollout acts from ``y_{R-1}``, and the learner's loss is the
expectation under p of the loss at each exit, less an entropy bonus
(``train/ppo.exit_weighted_loss``).

**One function for a step and a chunk**, as ``AfmoeCore``: ``x [B, T, H]``,
a step is T = 1. It returns EVERY loop step's output, ``[R, B, T, H]``; the
gates' logits ``[B, T, R]`` (float32) and the number of passes it made are
sown into ``losses`` (``exit_logits``, ``loop_passes``).

Scopes inside ``policy_core``: ``core_loop`` around each loop step, with the
layer's ``core_attn_full``, ``core_cache_write``, ``core_dense_ffn`` in it,
and ``core_exit_gate``.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from dotaclient_tpu.config import ModelConfig
from dotaclient_tpu.models import afmoe


def check_config(cfg: ModelConfig) -> None:
    afmoe.check_config(cfg)
    if cfg.loop_steps < 1 or not all(
        afmoe.layer_is_full(cfg, l) and afmoe.layer_is_dense(cfg, l) for l in range(cfg.n_layers)
    ):
        raise ValueError(
            "core 'looplm' runs loop_steps >= 1 passes over layers of full attention and a "
            "dense FFN: set global_attn_every 1, global_attn_offset 0, n_dense_layers 0, "
            f"moe_experts 0 (got {cfg.loop_steps}, {cfg.global_attn_every}, "
            f"{cfg.global_attn_offset}, {cfg.n_dense_layers}, {cfg.moe_experts})"
        )


def exit_distribution(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """``[..., R]`` gate logits -> ``[..., R]`` probabilities of leaving
    after each loop step: ``p_r = lam_r prod_{j<r} (1 - lam_j)``, and the
    last loop step takes what is left, so the sum is 1 whatever its gate."""
    g = gate_logits.astype(jnp.float32)
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-g[..., :-1]), axis=-1)   # log prod_{j<=r} (1 - lam_j)
    reached = jnp.concatenate([jnp.zeros_like(g[..., :1]), log_stay], axis=-1)
    leave = jnp.concatenate([jax.nn.log_sigmoid(g[..., :-1]), jnp.zeros_like(g[..., :1])], axis=-1)
    return jnp.exp(reached + leave)


class LoopLMCore(nn.Module):
    """``(carry, x [B, T, H], resets [B, T] | None) -> (carry, ys [R, B, T, H])``."""

    config: ModelConfig

    @nn.compact
    def __call__(self, carry, x, resets=None):
        cfg = self.config
        check_config(cfg)
        dtype = afmoe._dtype(cfg.dtype)
        T, L = x.shape[1], cfg.n_layers
        pos0, cursor0 = carry["pos"], carry["cursor"]
        seg, p = afmoe.chunk_positions(pos0, resets, T)
        # one set of modules: calling one again uses its parameters again
        layers = [afmoe.Block(cfg, l, name=f"layer_{l}") for l in range(L)]
        out_norm = afmoe.RMSNorm(cfg, name="out_norm")
        gate = nn.Dense(
            1, dtype=jnp.float32, param_dtype=afmoe._dtype(cfg.param_dtype), name="exit_gate"
        )
        h = x.astype(jnp.float32)                  # the residual stream stays float32
        if cfg.mup_enabled:
            h = h * math.sqrt(cfg.hidden_dim)
        rings, ys, gates = [], [], []
        for r in range(cfg.loop_steps):
            with jax.named_scope("core_loop"):
                for l, layer in enumerate(layers):
                    h, ring = layer(h, carry["kv"][r * L + l], pos0, cursor0, seg, p)
                    rings.append(ring)
                h = out_norm(h)
            with jax.named_scope("core_exit_gate"):
                gates.append(gate(h)[..., 0])
            ys.append(h.astype(dtype))
        self.sow("losses", "exit_logits", jnp.stack(gates, axis=-1))
        self.sow("losses", "loop_passes", jnp.asarray(len(ys), jnp.float32))
        carry = {
            "pos": p[:, -1] + 1,
            "cursor": (cursor0 + T) % afmoe._cursor_modulus(cfg),
            "kv": tuple(rings),
        }
        return carry, jnp.stack(ys)


# what ``models/policy.py resident_core`` asks of a core's module: the carry is the afmoe core's
Core = LoopLMCore
initial_state, reset, chunk_start_view = afmoe.initial_state, afmoe.reset, afmoe.chunk_start_view
carry_bytes_per_lane, require_episode_fits = afmoe.carry_bytes_per_lane, afmoe.require_episode_fits
