"""Plain reference of the policy with the LFM2 (``lfm2_moe``) core, in float32.

The trunk and the heads are ``policy_ref``'s (this repo's unit encoder stands
where a language model's embedding stands, its action and value heads where
the LM head stands). The core is written here from the equations, in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, importing nothing from ``dotaclient_tpu``: a lane's WHOLE
history ``[B, S]`` in one pass, the convolution by its taps over the whole
history (no carried rows, no chunk), attention over the episode's whole
history (no ring, no cursor), the experts held here a Python loop with a
dense mask (no grouping of tokens, no lane batch beyond the caller's). The
norm, SwiGLU, RoPE and the episode arithmetic are ``afmoe_ref``'s plain
functions (a reference's, not the program's).

Sizes come from the ``model`` section of a configuration's ``run_config`` (a
plain mapping), parameters are the program's own tree (Flax names):

  core/layer_<l>/operator_norm, ffn_norm /scale
  core/layer_<l>/conv/{in_proj,out_proj}/kernel, conv [K, H]
  core/layer_<l>/attn/{wq,wk,wv,wo}/kernel, {q_norm,k_norm}/scale [head_dim]
  core/layer_<l>/ffn/{gate_proj,up_proj,down_proj}/kernel          (dense)
  core/layer_<l>/moe/router [H, E], select_bias [E],
        expert_gate, expert_up [held, H, F], expert_down [held, F, H]  (experts)
  core/out_norm/scale

Every layer, on the stream h (float32), two residual adds and no post-norm:

  a = RMSNorm_op(h);  h = h + Mix(a);  m = RMSNorm_ffn(h);  h = h + FFN(m);
  y = RMSNorm_out(h) after the last layer;  no input scale, no bias

Layer l is an attention layer iff l >= n_dense_layers and (l + 1 +
global_attn_offset) % global_attn_every == 0, a convolution layer otherwise;
its FFN is dense iff l < n_dense_layers.

Convolution mixer (K = shortconv_taps; position t of its episode):

  [B, C, x] = split3(a W_in);  u = B * x
  c_t = sum_{j=0..K-1} w_j * u_{t-(K-1)+j}  per channel, the rows that would
        reach before the episode's first position read 0
  Mix = (C * c) W_out

Attention mixer (n_heads query heads over n_kv_heads KV heads of head_dim D):

  q = RMSNorm_q(a Wq) per head,  k = RMSNorm_k(a Wk) per head,  v = a Wv
  q, k rotated by RoPE(theta) at t: pairs (i, i + D/2) by t theta^(-2i/D)
  Mix = softmax(q . k / sqrt(D)) v Wo over the keys of the same episode with
  t_k <= t_q; query head j reads KV head j // (n_heads / n_kv_heads); no gate

Experts: s = sigmoid(m Wr) over E outputs; chosen = the experts_per_token
largest of s + select_bias; w = route_scale * s_chosen / (sum(s_chosen) +
1e-6); FFN = sum over the chosen AND held e of w_e W2_e(silu(W1_e m) * W3_e m).
Expert e is held iff expert_offset <= e < expert_offset + held_experts; what
the absent experts would add is left out, here as in the program (one chip's
share of the layer). There is NO shared expert.

Recalled from the public ``modeling_lfm2_moe.py`` and not verifiable here (no
network): the order of the split (B, C, x), the gates without a
nonlinearity, the Conv1d's tap order, the per-head RMSNorm of q and k before
RoPE, no output gate on attention, the 1e-6 in the renormalisation, the
selection bias without gradient, no post-norms. Departure of the PROGRAM
noted here: its renormalisation adds 1e-20 where this adds the recalled
1e-6 (a relative 5e-7 on a sum of four sigmoids: under float32's rounding
of the products around it).

``core``'s ``fault`` makes the mathematics wrong in ONE way, for the tests
that show the comparison sees each (``tests/test_lfm2moe.py``).
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
    "tap_order_reversed", "tap_shifted", "history_kept_across_reset", "no_input_gate", "no_output_gate",
    "no_rope", "no_qk_norm", "shared_kv_head",
)


def _data_before(x: jnp.ndarray, grad_from: int) -> jnp.ndarray:
    """Truncated backpropagation: what the steps before the trained chunk
    left (the program's carry) is data, not a function of the parameters."""
    if not grad_from:
        return x
    return jnp.concatenate([jax.lax.stop_gradient(x[:, :grad_from]), x[:, grad_from:]], axis=1)


def conv_mixer(p, a, episode, pos, model: Mapping[str, Any], grad_from: int = 0, fault: Optional[str] = None):
    S, K = a.shape[1], model["shortconv_taps"]
    gate_in, gate_out, x = jnp.split(_kernel(p["in_proj"], a), 3, axis=-1)
    u = x if fault == "no_input_gate" else gate_in * x
    u = _data_before(u, grad_from)
    taps = p["conv"].astype(jnp.float32)
    if fault == "tap_order_reversed":
        taps = taps[::-1]
    if fault == "history_kept_across_reset":
        pos = jnp.broadcast_to(jnp.arange(S)[None, :], pos.shape)
    c = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j + (1 if fault == "tap_shifted" else 0)
        earlier = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
        c = c + taps[j] * jnp.where((pos >= back)[..., None], earlier, 0.0)
    return _kernel(p["out_proj"], c if fault == "no_output_gate" else gate_out * c)


def attention(p, a, episode, pos, model: Mapping[str, Any], grad_from: int = 0, fault: Optional[str] = None):
    B, S, _ = a.shape
    nh, kv, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = _kernel(p["wq"], a).reshape(B, S, nh, D)
    k = _kernel(p["wk"], a).reshape(B, S, kv, D)
    v = _kernel(p["wv"], a).reshape(B, S, kv, D)
    if fault != "no_qk_norm":
        q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)
    if fault != "no_rope":
        q, k = rope(q, pos, model["rope_theta"]), rope(k, pos, model["rope_theta"])
    k, v = _data_before(k, grad_from), _data_before(v, grad_from)
    see = (episode[:, :, None] == episode[:, None, :]) & (pos[:, None, :] <= pos[:, :, None])
    group, out = nh // kv, []
    # a KV head at a time: query heads j * group .. (j + 1) * group - 1 read KV head j
    for j in range(kv):
        kj, vj = (k[:, :, 0], v[:, :, 0]) if fault == "shared_kv_head" else (k[:, :, j], v[:, :, j])
        scores = jnp.einsum("bqhd,bkd->bhqk", q[:, :, j * group:(j + 1) * group], kj) / math.sqrt(D)
        scores = jnp.where(see[:, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(scores, axis=-1), vj))
    return _kernel(p["wo"], jnp.concatenate(out, axis=2).reshape(B, S, nh * D))


def route(p, m, model: Mapping[str, Any], chosen: Optional[jnp.ndarray] = None):
    """``afmoe_ref.route``'s scores, choice and margin, the weights
    renormalised with LFM2's recalled 1e-6."""
    r = afmoe_ref.route(p, m, {**model, "route_norm": False, "route_scale": 1.0}, chosen)
    w = r["weights"]
    if model["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return {**r, "weights": w * model["route_scale"]}


def experts(p, m, model: Mapping[str, Any], routes: Optional[jnp.ndarray] = None):
    """The terms of the experts held here; no shared expert."""
    r = route(p, m, model, routes)
    held = model["held_experts"] or model["moe_experts"]
    f = jnp.zeros_like(m)
    for i in range(held):
        e = model["expert_offset"] + i
        weight = jnp.where(r["chosen"] == e, r["weights"], 0.0).sum(axis=-1)   # 0 where e was not taken
        g = jnp.matmul(m, p["expert_gate"][i].astype(jnp.float32))
        u = jnp.matmul(m, p["expert_up"][i].astype(jnp.float32))
        f = f + weight[..., None] * jnp.matmul(jax.nn.silu(g) * u, p["expert_down"][i].astype(jnp.float32))
    return f, r


def layer_is_attention(model: Mapping[str, Any], l: int) -> bool:
    return l >= model["n_dense_layers"] and (l + 1 + model["global_attn_offset"]) % model["global_attn_every"] == 0


def core(
    p: Mapping[str, Any], x: jnp.ndarray, dones: jnp.ndarray, model: Mapping[str, Any],
    routes: Optional[List[jnp.ndarray]] = None, grad_from: int = 0, fault: Optional[str] = None,
):
    """x [B, S, H] (the trunk's output over a lane's whole history), dones
    [B, S] -> (y [B, S, H], per expert layer what ``route`` returns)."""
    eps = model["rms_norm_eps"]
    episode, pos = episodes(dones)
    h, routing = x, []
    for l in range(model["n_layers"]):
        lp = p[f"layer_{l}"]
        a = rms_norm(lp["operator_norm"], h, eps)
        if layer_is_attention(model, l):
            h = h + attention(lp["attn"], a, episode, pos, model, grad_from, fault)
        else:
            h = h + conv_mixer(lp["conv"], a, episode, pos, model, grad_from, fault)
        m = rms_norm(lp["ffn_norm"], h, eps)
        if l < model["n_dense_layers"]:
            f = swiglu(lp["ffn"], m)
        else:
            f, r = experts(lp["moe"], m, model, None if routes is None else routes[len(routing)])
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
    (0 in the cell: the selection bias balances). ``kimilinear_ref.ppo_loss``
    over this module's ``history``: files under ``benchmark/`` that exist
    are a `benchmark` PR's to edit, so the third copy waits for one."""
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
