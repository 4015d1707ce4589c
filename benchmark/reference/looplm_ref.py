"""Plain reference of the policy with the looped core (Ouro's LoopLM), in
float32.

The trunk and the heads are ``policy_ref``'s (this repo's unit encoder stands
where a language model's embedding stands, its action and value heads where
the LM head stands). The core is written here from the equations, in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, importing nothing from ``dotaclient_tpu``: a lane's WHOLE
history ``[B, S]`` in one pass per loop step, no ring, no cursor. The norm,
SwiGLU, RoPE and the episode arithmetic are ``afmoe_ref``'s plain functions
(a reference's, not the program's).

Sizes come from the ``model`` section of a configuration's ``run_config`` (a
plain mapping: ``n_layers`` L, ``loop_steps`` R, ``n_heads``, ``head_dim``,
``rope_theta``, ``rms_norm_eps``), parameters are the program's own tree
(Flax names), ONE set for all R loop steps:

  core/layer_<l>/in_norm, post_attn_norm, pre_mlp_norm, post_mlp_norm /scale
  core/layer_<l>/attn/{wq,wk,wv,wo}/kernel
  core/layer_<l>/ffn/{gate_proj,up_proj,down_proj}/kernel
  core/out_norm/scale;  core/exit_gate/{kernel [H, 1], bias [1]}

One layer, in loop step r, on the stream h (float32), query at position p of
its episode:

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale
  a = RMSNorm_1(h);  q, k, v = a Wq, a Wk, a Wv  (n_heads heads of head_dim,
  as many KV heads);  q and k rotated by RoPE: pairs (i, i + D/2) by
  p theta^(-2i/D)
  scores = q . k / sqrt(D) over the keys that THIS layer made in THIS loop
  step, same episode, p_k <= p_q; softmax;  attn = (softmax v) Wo
  h = h + RMSNorm_2(attn);  m = RMSNorm_3(h)
  f = (silu(m Wg) * (m Wu)) Wd;  h = h + RMSNorm_4(f)

  after layer L-1 of loop step r:  y_r = RMSNorm_out(h),
  g_r = y_r . w_gate + b_gate, and loop step r + 1 starts from h = y_r with
  the same weights. Every loop step runs (early_exit_threshold 1); the
  heads read each y_r (the rollout acts from y_{R-1}).

  exit:  lam_r = sigmoid(g_r);  p_r = lam_r prod_{j<r} (1 - lam_j), r < R-1;
         p_{R-1} = prod_{j<R-1} (1 - lam_j)

Recalled from the public ``modeling_ouro.py`` and the paper (Ouro, "Scaling
Latent Reasoning via Looped Language Models", ByteDance, 2025) and not
verifiable here (no network): the four norms a layer, the norm between loop
steps, the gate's form, the entropy bonus's weight.

``ppo_loss`` is the exit-weighted loss: per valid frame sum_r p_r l_r -
beta H(p), l_r the clipped surrogate, the entropy bonus and the value loss
from loop step r's heads against one behaviour log-probability, advantage
and return (those from the last loop step's values). Departure: the task
loss is PPO's where the paper's is next-token cross-entropy.

``core``'s ``fault`` makes the mathematics wrong in ONE way, for the tests
that show the comparison sees each (``tests/test_looplm.py``).
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import policy_ref
from benchmark.reference.afmoe_ref import _kernel, episodes, rms_norm, rope, swiglu

PRECISION = "highest"
FAULTS = ("loop_step_skipped", "one_cache_for_all_loop_steps", "weights_not_tied",
          "no_norm_between_loop_steps", "last_exit_not_the_remainder")


def attention(p, a, episode, pos, model: Mapping[str, Any], grad_from: int = 0, kv=None):
    """``a [B, S, H]`` -> (attention output [B, S, H], the keys and values
    it attended over). ``kv`` given replaces them (a fault)."""
    B, S, _ = a.shape
    nh, D = model["n_heads"], model["head_dim"]
    q = rope(_kernel(p["wq"], a).reshape(B, S, nh, D), pos, model["rope_theta"])
    k = rope(_kernel(p["wk"], a).reshape(B, S, nh, D), pos, model["rope_theta"])
    v = _kernel(p["wv"], a).reshape(B, S, nh, D)
    if grad_from:
        # truncated backpropagation: keys and values of the steps before the
        # trained chunk are data (the program's cache)
        k, v = (
            jnp.concatenate([jax.lax.stop_gradient(t[:, :grad_from]), t[:, grad_from:]], axis=1)
            for t in (k, v)
        )
    if kv is not None:
        k, v = kv
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    see = (episode[:, :, None] == episode[:, None, :]) & (pos[:, None, :] <= pos[:, :, None])
    scores = jnp.where(see[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return _kernel(p["wo"], out.reshape(B, S, nh * D)), (k, v)


def core(
    p: Mapping[str, Any], x: jnp.ndarray, dones: jnp.ndarray, model: Mapping[str, Any],
    grad_from: int = 0, fault: Optional[str] = None,
):
    """x [B, S, H] (the trunk's output over a lane's whole history), dones
    [B, S] -> (ys [R, B, S, H], gate logits [B, S, R])."""
    eps, L, R = model["rms_norm_eps"], model["n_layers"], model["loop_steps"]
    episode, pos = episodes(dones)
    h, ys, gates, first_kv = x, [], [], {}
    for r in range(R):
        if fault == "loop_step_skipped" and r == 1:
            ys.append(ys[-1]), gates.append(gates[-1])
            continue
        for l in range(L):
            # a stack whose weights are not tied has other weights in a later loop step
            lp = p[f"layer_{(l + r) % L if fault == 'weights_not_tied' else l}"]
            a = rms_norm(lp["in_norm"], h, eps)
            shared = first_kv.get(l) if fault == "one_cache_for_all_loop_steps" else None
            attn, kv = attention(lp["attn"], a, episode, pos, model, grad_from, shared)
            first_kv.setdefault(l, kv)
            h = h + rms_norm(lp["post_attn_norm"], attn, eps)
            m = rms_norm(lp["pre_mlp_norm"], h, eps)
            h = h + rms_norm(lp["post_mlp_norm"], swiglu(lp["ffn"], m), eps)
        y = rms_norm(p["out_norm"], h, eps)
        gates.append(_kernel(p["exit_gate"], y)[..., 0] + p["exit_gate"]["bias"].astype(jnp.float32)[0])
        ys.append(y)
        if fault != "no_norm_between_loop_steps":
            h = y
    return jnp.stack(ys), jnp.stack(gates, axis=-1)


def exit_distribution(gate_logits: jnp.ndarray, fault: Optional[str] = None) -> jnp.ndarray:
    """[..., R] gate logits -> [..., R] exit probabilities."""
    lam = jax.nn.sigmoid(gate_logits)
    R = lam.shape[-1]
    p, stay = [], jnp.ones_like(lam[..., 0])
    for r in range(R):
        last = r == R - 1 and fault != "last_exit_not_the_remainder"
        p.append(stay if last else lam[..., r] * stay)
        stay = stay * (1.0 - lam[..., r])
    return jnp.stack(p, axis=-1)


def history(
    params: Mapping[str, Any], obs: Mapping[str, jnp.ndarray], dones: jnp.ndarray,
    model: Mapping[str, Any], grad_from: int = 0, fault: Optional[str] = None,
):
    """Whole lane histories: obs ``[B, S, ...]`` from each lane's first step
    (an episode start), ``dones [B, S]`` -> (logits [R, B, S, n], values
    [R, B, S], gate logits [B, S, R])."""
    p = params["params"]
    with jax.default_matmul_precision(PRECISION):
        x, e = policy_ref.trunk(p, obs)
        ys, gates = core(p["core"], x, jnp.asarray(dones, jnp.float32), model, grad_from, fault)
        logits, value = policy_ref.heads(p, ys, e)
    return logits, value, gates


# -- the exit-weighted PPO loss of one chunk, for the gradient comparison ---------

def ppo_loss(
    params, obs, dones, batch: Mapping[str, Any], model: Mapping[str, Any],
    ppo: Mapping[str, float], log_prob_and_entropy, fault: Optional[str] = None,
):
    """The loss of the LAST ``T`` steps of the histories (``batch``: actions,
    behavior_logp, rewards, dones, valid, each [B, T]; the history holds one
    step more, the bootstrap observation; the steps before the chunk are
    data, as the program's cache is). The joint log-probability and entropy
    of this repo's masked multi-head action distribution are handed in
    (``log_prob_and_entropy(logits, obs, actions)``, one loop step's): they
    are no part of the architecture."""
    T = batch["rewards"].shape[1]
    logits, values, gates = history(
        params, obs, dones, model, grad_from=dones.shape[1] - (T + 1), fault=fault
    )
    R = values.shape[0]
    tail = slice(-(T + 1), None)
    obs_t = {k: v[:, tail][:, :T] for k, v in obs.items()}
    valid = batch["valid"].astype(jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
    # GAE over the chunk from the LAST loop step's values, bootstrapped by
    # the trailing one; no gradient
    v = jax.lax.stop_gradient(values[-1][:, tail])
    adv, last = [], jnp.zeros_like(v[:, 0])
    for t in reversed(range(T)):
        nonterminal = 1.0 - batch["dones"][:, t]
        delta = batch["rewards"][:, t] + ppo["gamma"] * v[:, t + 1] * nonterminal - v[:, t]
        last = delta + ppo["gamma"] * ppo["gae_lambda"] * nonterminal * last
        adv.append(last)
    adv = jnp.stack(adv[::-1], axis=1)
    returns = adv + v[:, :T]
    adv = adv - (adv * valid).sum() / n
    adv = adv / jnp.sqrt((adv * adv * valid).sum() / n + 1e-8)
    p = exit_distribution(gates[:, tail][:, :T], fault)                       # [B, T, R]
    frame = ppo["exit_entropy_coef"] * (p * jnp.log(jnp.maximum(p, 1e-30))).sum(axis=-1)   # - beta H(p)
    for r in range(R):
        logits_r = {k: x[r][:, tail][:, :T] for k, x in logits.items()}
        logp, ent = log_prob_and_entropy(logits_r, obs_t, batch["actions"])
        ratio = jnp.exp(logp - batch["behavior_logp"])
        clipped = jnp.clip(ratio, 1.0 - ppo["clip_eps"], 1.0 + ppo["clip_eps"])
        l_r = (
            -jnp.minimum(ratio * adv, clipped * adv) - ppo["entropy_coef"] * ent
            + ppo["value_coef"] * 0.5 * jnp.square(values[r][:, tail][:, :T] - returns)
        )
        frame = frame + p[..., r] * l_r
    return (frame * valid).sum() / n
