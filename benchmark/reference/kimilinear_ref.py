"""Plain reference of the policy with the Kimi-Linear core, in float32.

The trunk and the heads are ``policy_ref``'s (this repo's unit encoder stands
where a language model's embedding stands, its action and value heads where
the LM head stands). The core is written here from the equations, in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, importing nothing from ``dotaclient_tpu``: a lane's WHOLE
history ``[B, S]``, the delta rule ONE POSITION AT A TIME (a scan over the
positions that carries the state; no chunk, no closed form, no carry), the
convolution by its taps, latent attention EXPANDED to keys and values a head
over the episode's whole history (no ring, no absorbed product). The norm,
SwiGLU, RoPE (for a fault only), the episode arithmetic and the routed
experts with their held share are ``afmoe_ref``'s plain functions (a
reference's, not the program's: Kimi's router is Trinity's at another width
and scale).

Sizes come from the ``model`` section of a configuration's ``run_config`` (a
plain mapping), parameters are the program's own tree (Flax names):

  core/layer_<l>/in_norm, pre_mlp_norm /scale
  core/layer_<l>/kda/{wq,wk,wv,wf_down,wf_up,wb,wg_down,wg_up,wo}/kernel,
        conv [K, 3 n_heads d], dt_bias [n_heads d], A_log [n_heads], o_norm/scale [d]
  core/layer_<l>/attn/{wq,wkv_a,wo}/kernel, kv_norm/scale [C],
        wuk [n_heads, C, qk_nope], wuv [n_heads, C, v_head]
  core/layer_<l>/ffn/... (dense) or moe/... (experts), as ``afmoe_ref``
  core/out_norm/scale

Every layer, on the stream h (float32), two residual adds and no post-norm:

  a = RMSNorm(h);  h = h + Mix(a);  m = RMSNorm(h);  h = h + FFN(m);
  y = RMSNorm_out(h) after the last layer;  no input scale

Layer l is an MLA layer iff l >= n_dense_layers and (l + 1 +
global_attn_offset) % global_attn_every == 0, a KDA layer otherwise; its FFN
is dense iff l < n_dense_layers.

KDA (n_heads heads, d = kda_head_dim = d_k = d_v; position t of its episode):

  x~ = [a Wq, a Wk, a Wv];  x_t = SiLU(sum_{j<K} conv[j] * x~_{t-j}), the
  taps that would reach before the episode's first position read 0
  q_h = x^q_h / sqrt(|x^q_h|^2 + 1e-6) / sqrt(d);  k_h = x^k_h / sqrt(|x^k_h|^2 + 1e-6)
  alpha = exp(-exp(A_log_h) softplus((a Wf_down) Wf_up + dt_bias))   per channel of d_k
  beta = sigmoid(a Wb)                                                per head
  S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T;   S = 0 before an
  episode's first position;   o = S_t^T q
  Mix = (RMSNorm_head(o) * sigmoid((a Wg_down) Wg_up)) Wo

MLA (no rotation: mla_use_nope; no query compression: q_lora_rank null):

  q_h = a Wq  (qk_nope + qk_rope a head);  [c~, k_pe] = a Wkv_a;  c = RMSNorm(c~)
  k_h = [c Wuk_h, k_pe];  v_h = c Wuv_h
  Mix = softmax(q_h . k_h / sqrt(qk_nope + qk_rope)) v_h Wo over the keys of
  the same episode with p_k <= p_q

Recalled from the public ``modeling_kimi.py`` / the ``fla`` KDA layer and the
paper (Kimi Linear, Moonshot AI, 2025) and not verifiable here (no network):
the order convolution -> SiLU -> L2 norm, the 1/sqrt(d) on q, the rank-d
gate projections and ``dt_bias``, sigmoid beta, the gated per-head RMSNorm on
the output, no post-norms, the latent's norm before the up-projections.

``core``'s ``fault`` makes the mathematics wrong in ONE way, for the tests
that show the comparison sees each (``tests/test_kimilinear.py``).
"""

from __future__ import annotations

import math
from typing import Any, List, Mapping, Optional

import jax
import jax.numpy as jnp

from benchmark.reference import afmoe_ref, policy_ref
from benchmark.reference.afmoe_ref import _kernel, episodes, rms_norm, rope, swiglu

PRECISION = "highest"
FAULTS = (
    "no_decay", "no_beta", "tap_shifted", "state_kept_across_reset",
    "latent_unnormalised", "rotation_applied",
)


def _data_before(x: jnp.ndarray, grad_from: int) -> jnp.ndarray:
    """Truncated backpropagation: what the steps before the trained chunk
    left (the program's carry) is data, not a function of the parameters."""
    if not grad_from:
        return x
    return jnp.concatenate([jax.lax.stop_gradient(x[:, :grad_from]), x[:, grad_from:]], axis=1)


def kda(p, a, episode, pos, model: Mapping[str, Any], grad_from: int = 0, fault: Optional[str] = None):
    B, S, _ = a.shape
    nh, d, K = model["n_heads"], model["kda_head_dim"], model["kda_conv_kernel"]
    x = jnp.concatenate([_kernel(p[n], a) for n in ("wq", "wk", "wv")], axis=-1)      # [B, S, 3 nh d]
    x = _data_before(x, grad_from)
    taps = p["conv"].astype(jnp.float32)
    y = jnp.zeros_like(x)
    for j in range(K):
        back = j + 1 if fault == "tap_shifted" else j
        earlier = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
        y = y + taps[j] * jnp.where((pos >= back)[..., None], earlier, 0.0)
    q, k, v = (z.reshape(B, S, nh, d) for z in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / math.sqrt(d)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = _kernel(p["wf_up"], _kernel(p["wf_down"], a)) + p["dt_bias"].astype(jnp.float32)
    rate = jnp.exp(p["A_log"].astype(jnp.float32))
    alpha = jnp.exp(-rate[:, None] * jax.nn.softplus(f.reshape(B, S, nh, d)))
    beta = jax.nn.sigmoid(_kernel(p["wb"], a))                                           # [B, S, nh]
    if fault == "no_decay":
        alpha = jnp.ones_like(alpha)
    if fault == "no_beta":
        beta = jnp.ones_like(beta)
    starts = pos == 0
    if fault == "state_kept_across_reset":
        starts = starts & (episode == 0)
    cut = jnp.arange(S) == grad_from if grad_from else jnp.zeros((S,), bool)

    def position(state, inputs):
        q_t, k_t, v_t, alpha_t, beta_t, start_t, cut_t = inputs
        state = jnp.where(start_t[:, None, None, None], 0.0, state)
        state = jnp.where(cut_t, jax.lax.stop_gradient(state), state)
        decayed = alpha_t[..., None] * state                                             # Diag(alpha) S
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, decayed)
        state = decayed + beta_t[..., None, None] * k_t[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    time_first = lambda z: jnp.moveaxis(z, 1, 0)
    _, o = jax.lax.scan(
        position, jnp.zeros((B, nh, d, d), jnp.float32),
        (*(time_first(z) for z in (q, k, v, alpha, beta, starts)), cut),
    )
    o = rms_norm(p["o_norm"], jnp.moveaxis(o, 0, 1), model["rms_norm_eps"])
    gate = jax.nn.sigmoid(_kernel(p["wg_up"], _kernel(p["wg_down"], a))).reshape(B, S, nh, d)
    return _kernel(p["wo"], (o * gate).reshape(B, S, nh * d))


def mla(p, a, episode, pos, model: Mapping[str, Any], grad_from: int = 0, fault: Optional[str] = None):
    B, S, _ = a.shape
    nh, C = model["n_heads"], model["kv_lora_rank"]
    dn, dr, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    q = _kernel(p["wq"], a).reshape(B, S, nh, dn + dr)
    kv = _kernel(p["wkv_a"], a)
    c, k_pe = kv[..., :C], kv[..., C:]
    if fault != "latent_unnormalised":
        c = rms_norm(p["kv_norm"], c, model["rms_norm_eps"])
    if fault == "rotation_applied":
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, model["rope_theta"])], axis=-1)
        k_pe = rope(k_pe[:, :, None], pos, model["rope_theta"])[:, :, 0]
    c, k_pe = _data_before(c, grad_from), _data_before(k_pe, grad_from)
    k_nope = jnp.einsum("bsc,hcn->bshn", c, p["wuk"].astype(jnp.float32))
    v = jnp.einsum("bsc,hcv->bshv", c, p["wuv"].astype(jnp.float32))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, nh, dr))], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dn + dr)
    see = (episode[:, :, None] == episode[:, None, :]) & (pos[:, None, :] <= pos[:, :, None])
    scores = jnp.where(see[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return _kernel(p["wo"], out.reshape(B, S, nh * dv))


def layer_is_mla(model: Mapping[str, Any], l: int) -> bool:
    return l >= model["n_dense_layers"] and (l + 1 + model["global_attn_offset"]) % model["global_attn_every"] == 0


def core(
    p: Mapping[str, Any], x: jnp.ndarray, dones: jnp.ndarray, model: Mapping[str, Any],
    routes: Optional[List[jnp.ndarray]] = None, grad_from: int = 0, fault: Optional[str] = None,
):
    """x [B, S, H] (the trunk's output over a lane's whole history), dones
    [B, S] -> (y [B, S, H], per expert layer what ``afmoe_ref.route`` returns)."""
    eps = model["rms_norm_eps"]
    episode, pos = episodes(dones)
    h, routing = x, []
    for l in range(model["n_layers"]):
        lp = p[f"layer_{l}"]
        a = rms_norm(lp["in_norm"], h, eps)
        if layer_is_mla(model, l):
            h = h + mla(lp["attn"], a, episode, pos, model, grad_from, fault)
        else:
            h = h + kda(lp["kda"], a, episode, pos, model, grad_from, fault)
        m = rms_norm(lp["pre_mlp_norm"], h, eps)
        if l < model["n_dense_layers"]:
            f = swiglu(lp["ffn"], m)
        else:
            f, r = afmoe_ref.experts(lp["moe"], m, model, None if routes is None else routes[len(routing)])
            routing.append(r)
        h = h + f
    return rms_norm(p["out_norm"], h, eps), routing


def history(
    params: Mapping[str, Any], obs: Mapping[str, jnp.ndarray], dones: jnp.ndarray,
    model: Mapping[str, Any], routes: Optional[List[jnp.ndarray]] = None,
    grad_from: int = 0, fault: Optional[str] = None,
):
    """Whole lane histories: obs ``[B, S, ...]`` from each lane's first step
    (an episode start), ``dones [B, S]`` -> (logits, values [B, S], routing)."""
    p = params["params"]
    with jax.default_matmul_precision(PRECISION):
        x, e = policy_ref.trunk(p, obs)
        y, routing = core(p["core"], x, jnp.asarray(dones, jnp.float32), model, routes, grad_from, fault)
        logits, value = policy_ref.heads(p, y, e)
    return logits, value, routing


# -- the PPO loss of one chunk, for the gradient comparison ----------------------

def ppo_loss(
    params, obs, dones, batch: Mapping[str, Any], model: Mapping[str, Any],
    ppo: Mapping[str, float], log_prob_and_entropy, fault: Optional[str] = None,
):
    """Clipped-surrogate PPO loss of the LAST ``T`` steps of the histories
    (``batch``: actions, behavior_logp, rewards, dones, valid, each [B, T];
    the history holds one step more, the bootstrap observation; what the
    steps before the chunk left is data, as the program's carry is). The
    joint log-probability and entropy of this repo's masked multi-head
    action distribution are handed in (``log_prob_and_entropy(logits, obs,
    actions)``): they are no part of the architecture. The auxiliary
    load-balancing loss is ``afmoe_ref.aux_loss`` under ``ppo["moe_aux_coef"]``
    (0 in the cell: the selection bias balances)."""
    T = batch["rewards"].shape[1]
    logits, values, routing = history(
        params, obs, dones, model, grad_from=dones.shape[1] - (T + 1), fault=fault
    )
    tail = slice(-(T + 1), None)
    logits = {k: v[:, tail] for k, v in logits.items()}
    values = values[:, tail]
    obs_t = {k: v[:, tail][:, :T] for k, v in obs.items()}
    logp, ent = log_prob_and_entropy({k: v[:, :T] for k, v in logits.items()}, obs_t, batch["actions"])
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
    tail_routing = [{k: x[:, tail][:, :T] for k, x in r.items()} for r in routing]
    aux = afmoe_ref.aux_loss(tail_routing, valid, model)
    return (
        policy_loss - ppo["entropy_coef"] * entropy + ppo["moe_aux_coef"] * aux
        + ppo["value_coef"] * value_loss
    )
