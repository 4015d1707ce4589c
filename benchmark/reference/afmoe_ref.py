"""Plain reference of the policy with the afmoe core, in float32.

The trunk and the heads are ``policy_ref``'s (this repo's unit encoder
stands where a language model's embedding stands, its action and value heads
where the LM head stands). The core is written here from the equations, in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, importing nothing from ``dotaclient_tpu``: a lane's WHOLE
history ``[B, S]`` in one pass, no ring, no cursor, no grouping of tokens.
Masks are built from each step's episode id and its position in the episode;
the experts held here are a Python loop with a dense mask.

Sizes come from the ``model`` section of a configuration's ``run_config``
(a plain mapping), parameters are the program's own tree (Flax names):

  core/layer_<l>/in_norm, post_attn_norm, pre_mlp_norm, post_mlp_norm /scale
  core/layer_<l>/attn/{wq,wk,wv,wgate,wo}/kernel, {q_norm,k_norm}/scale
  core/layer_<l>/ffn/{gate_proj,up_proj,down_proj}/kernel          (dense)
  core/layer_<l>/moe/router [H, E], select_bias [E],
        expert_gate, expert_up [held, H, F], expert_down [held, F, H],
        shared/{gate_proj,up_proj,down_proj}/kernel                (experts)
  core/out_norm/scale

One layer, on the stream h (float32), query at position p of its episode:

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale
  a = RMSNorm(h);  q = RMSNorm(a Wq) per head, k = RMSNorm(a Wk) per head,
  v = a Wv;  in a window layer (the leading dense layers, and every layer l
  with (l + 1 + global_attn_offset) % global_attn_every != 0) q and k are
  rotated by RoPE: pairs (i, i + D/2) by p theta^(-2i/D)
  scores = q . k / sqrt(D), head j of the n_heads reads KV head
  j // (n_heads / n_kv_heads); a key is visible iff same episode, p_k <= p_q
  and (window layer) p_k > p_q - context_window; softmax
  attn = ((softmax v) * sigmoid(a Wgate)) Wo;     h = h + RMSNorm(attn)
  m = RMSNorm(h)
  dense (l < n_dense_layers): f = (silu(m Wg) * (m Wu)) Wd
  experts: s = sigmoid(m Wr); chosen = the experts_per_token largest of
      s + select_bias; w = route_scale * s_chosen / sum(s_chosen)
      (route_norm); f = SwiGLU_shared(m) + sum over the chosen AND held e of
      w_e SwiGLU_e(m). Expert e is held iff expert_offset <= e <
      expert_offset + held_experts; what the absent experts would add is left
      out, here as in the program (one chip's share of the layer).
  h = h + RMSNorm(f);      y = RMSNorm_out(h) after the last layer

The stream enters as x * sqrt(hidden_dim) when ``mup_enabled``.

Recalled from the public ``modeling_afmoe.py`` and not verifiable here (no
network): the per-head RMSNorm of q and k, the sigmoid output gate, the
second RMSNorm on each sub-layer's output, no positional encoding on full
layers, the selection bias without gradient, the sqrt(hidden) input scale.
The auxiliary load-balancing loss is ``aux_loss`` below: E * sum_e
(mean share of choices of e) * (mean normalised score of e), the Switch
form the program's ``train/ppo.py`` already has, over the router's whole
width.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import policy_ref

PRECISION = "highest"


def _kernel(p: Mapping[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, p["kernel"].astype(jnp.float32))


def rms_norm(p: Mapping[str, Any], x: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"].astype(jnp.float32)


def swiglu(p: Mapping[str, Any], m: jnp.ndarray) -> jnp.ndarray:
    return _kernel(p["down_proj"], jax.nn.silu(_kernel(p["gate_proj"], m)) * _kernel(p["up_proj"], m))


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [B, S, heads, D], pos [B, S]."""
    half = x.shape[-1] // 2
    freq = jnp.asarray([theta ** (-i / half) for i in range(half)], jnp.float32)
    angle = pos.astype(jnp.float32)[:, :, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * jnp.cos(angle) - x2 * jnp.sin(angle), x2 * jnp.cos(angle) + x1 * jnp.sin(angle)],
        axis=-1,
    )


def episodes(dones: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """From ``dones [B, S]`` (1 where step t ENDED its episode) to each
    step's episode id and its position in that episode."""
    B, S = dones.shape
    starts = jnp.concatenate([jnp.zeros((B, 1)), dones[:, :-1].astype(jnp.float32)], axis=1) > 0
    episode = jnp.cumsum(starts.astype(jnp.int32), axis=1)
    idx = jnp.arange(S)[None, :]
    first = jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    return episode, idx - first


def expand_kv(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """[B, S, kv, D] -> [B, S, kv * group, D]: query head j reads KV head
    j // group."""
    return jnp.repeat(x, group, axis=2)


def attention(p, a, episode, pos, model: Mapping[str, Any], full: bool, grad_from: int = 0) -> jnp.ndarray:
    B, S, _ = a.shape
    nh, kv, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = rms_norm(p["q_norm"], _kernel(p["wq"], a).reshape(B, S, nh, D), eps)
    k = rms_norm(p["k_norm"], _kernel(p["wk"], a).reshape(B, S, kv, D), eps)
    v = _kernel(p["wv"], a).reshape(B, S, kv, D)
    if not full:
        q, k = rope(q, pos, model["rope_theta"]), rope(k, pos, model["rope_theta"])
    if grad_from:
        # truncated backpropagation: keys and values of the steps before
        # the trained chunk are data (the program's cache), not functions
        # of the parameters
        k, v = (
            jnp.concatenate([jax.lax.stop_gradient(t[:, :grad_from]), t[:, grad_from:]], axis=1)
            for t in (k, v)
        )
    k, v = expand_kv(k, nh // kv), expand_kv(v, nh // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    see = (episode[:, :, None] == episode[:, None, :]) & (pos[:, None, :] <= pos[:, :, None])
    if not full:
        see &= pos[:, None, :] > pos[:, :, None] - model["context_window"]
    scores = jnp.where(see[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    out = out.reshape(B, S, nh * D) * jax.nn.sigmoid(_kernel(p["wgate"], a))
    return _kernel(p["wo"], out)


def route(p, m, model: Mapping[str, Any], chosen: Optional[jnp.ndarray] = None):
    """Scores [.., E], the experts taken [.., k] and their weights [.., k],
    and ``margin`` [..]: how far below the reference's own cut line (the
    k-th largest of scores + select_bias) the lowest taken expert lies, 0
    where the choice is the reference's own. ``chosen`` given replaces the
    choice (``compare_afmoe`` says when); the weights are always from the
    scores computed here."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(jnp.float32)))
    biased = s + p["select_bias"].astype(jnp.float32)
    line, own = jax.lax.top_k(biased, model["experts_per_token"])
    if chosen is None:
        chosen = own
    taken = jnp.take_along_axis(biased, chosen, axis=-1)
    margin = jnp.maximum(line[..., -1:] - taken, 0.0).max(axis=-1)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if model["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return {"scores": s, "chosen": chosen, "weights": w * model["route_scale"], "margin": margin}


def experts(p, m, model: Mapping[str, Any], routes: Optional[jnp.ndarray] = None):
    """The shared expert once, plus the terms of the experts held here."""
    r = route(p, m, model, routes)
    held = model["held_experts"] or model["moe_experts"]
    f = swiglu(p["shared"], m)
    for i in range(held):
        e = model["expert_offset"] + i
        weight = jnp.where(r["chosen"] == e, r["weights"], 0.0).sum(axis=-1)   # 0 where e was not taken
        g = jnp.matmul(m, p["expert_gate"][i].astype(jnp.float32))
        u = jnp.matmul(m, p["expert_up"][i].astype(jnp.float32))
        f = f + weight[..., None] * jnp.matmul(jax.nn.silu(g) * u, p["expert_down"][i].astype(jnp.float32))
    return f, r


def core(
    p: Mapping[str, Any], x: jnp.ndarray, dones: jnp.ndarray, model: Mapping[str, Any],
    routes: Optional[List[jnp.ndarray]] = None, grad_from: int = 0,
):
    """x [B, S, H] (the trunk's output over a lane's whole history), dones
    [B, S] -> (y [B, S, H], per expert layer what ``route`` returns)."""
    eps = model["rms_norm_eps"]
    episode, pos = episodes(dones)
    h = x * math.sqrt(model["hidden_dim"]) if model["mup_enabled"] else x
    routing = []
    for l in range(model["n_layers"]):
        lp = p[f"layer_{l}"]
        full = l >= model["n_dense_layers"] and (
            (l + 1 + model["global_attn_offset"]) % model["global_attn_every"] == 0
        )
        a = rms_norm(lp["in_norm"], h, eps)
        h = h + rms_norm(lp["post_attn_norm"], attention(lp["attn"], a, episode, pos, model, full, grad_from), eps)
        m = rms_norm(lp["pre_mlp_norm"], h, eps)
        if l < model["n_dense_layers"]:
            f = swiglu(lp["ffn"], m)
        else:
            f, r = experts(lp["moe"], m, model, None if routes is None else routes[len(routing)])
            routing.append(r)
        h = h + rms_norm(lp["post_mlp_norm"], f, eps)
    return rms_norm(p["out_norm"], h, eps), routing


def history(
    params: Mapping[str, Any], obs: Mapping[str, jnp.ndarray], dones: jnp.ndarray,
    model: Mapping[str, Any], routes: Optional[List[jnp.ndarray]] = None,
    grad_from: int = 0,
):
    """Whole lane histories: obs ``[B, S, ...]`` from each lane's first step
    (an episode start), ``dones [B, S]`` -> (logits, values [B, S], routing)."""
    p = params["params"]
    with jax.default_matmul_precision(PRECISION):
        x, e = policy_ref.trunk(p, obs)
        y, routing = core(p["core"], x, jnp.asarray(dones, jnp.float32), model, routes, grad_from)
        logits, value = policy_ref.heads(p, y, e)
    return logits, value, routing


def aux_loss(routing, valid: jnp.ndarray, model: Mapping[str, Any]) -> jnp.ndarray:
    """The load-balancing loss over the steps where ``valid [B, S]`` is 1."""
    E, k = model["moe_experts"], model["experts_per_token"]
    n = jnp.maximum(valid.sum(), 1.0)
    total = jnp.zeros(())
    for r in routing:
        share = jax.nn.one_hot(r["chosen"], E).sum(axis=-2) / k
        prob = r["scores"] / r["scores"].sum(axis=-1, keepdims=True)
        total = total + E * jnp.sum(
            (share * valid[..., None]).sum((0, 1)) / n * (prob * valid[..., None]).sum((0, 1)) / n
        )
    return total


# -- the PPO loss of one chunk, for the gradient comparison ----------------------

def ppo_loss(
    params, obs, dones, batch: Mapping[str, Any], model: Mapping[str, Any],
    ppo: Mapping[str, float], log_prob_and_entropy,
):
    """Clipped-surrogate PPO loss of the LAST ``T`` steps of the histories
    (``batch``: actions, behavior_logp, rewards, dones, valid, each [B, T];
    the history holds one step more, the bootstrap observation; the steps
    before the chunk are data, as the program's cache is). The joint
    log-probability and entropy of this repo's masked multi-head action
    distribution are handed in (``log_prob_and_entropy(logits, obs,
    actions)``): they are no part of the architecture."""
    T = batch["rewards"].shape[1]
    logits, values, routing = history(
        params, obs, dones, model, grad_from=dones.shape[1] - (T + 1)
    )
    tail = slice(-(T + 1), None)
    logits = {k: v[:, tail] for k, v in logits.items()}
    values = values[:, tail]
    obs_t = {k: v[:, tail][:, :T] for k, v in obs.items()}
    logits_t = {k: v[:, :T] for k, v in logits.items()}
    logp, ent = log_prob_and_entropy(logits_t, obs_t, batch["actions"])
    valid = batch["valid"].astype(jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
    # GAE over the chunk, bootstrapped by the trailing value; no gradient
    v = jax.lax.stop_gradient(values)
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
    ratio = jnp.exp(logp - batch["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - ppo["clip_eps"], 1.0 + ppo["clip_eps"])
    policy_loss = -(jnp.minimum(ratio * adv, clipped * adv) * valid).sum() / n
    value_loss = 0.5 * (jnp.square(values[:, :T] - returns) * valid).sum() / n
    entropy = (ent * valid).sum() / n
    tail_routing = [{k: v[:, tail][:, :T] for k, v in r.items()} for r in routing]
    aux = aux_loss(tail_routing, valid, model)
    return (
        policy_loss - ppo["entropy_coef"] * entropy + ppo["moe_aux_coef"] * aux
        + ppo["value_coef"] * value_loss
    )
