"""Plain reference of the policy with the SDAR (``sdar_moe``) core, in float32.

The trunk and the heads are ``policy_ref``'s (this repo's unit encoder stands
where a language model's embedding stands, its action and value heads where
the LM head stands). The core is written here from the equations, in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``, importing nothing from ``dotaclient_tpu``: a lane's WHOLE
history as one explicit sequence of rows with an explicit mask (no ring, no
cursor, no passes: every row of every pass is a row of the sequence), the
experts held here a Python loop with a dense mask. The norm, SwiGLU and RoPE
are ``afmoe_ref``'s plain functions (a reference's, not the program's).

Sizes come from the ``model`` and ``actions`` sections of a configuration's
``run_config`` (plain mappings); parameters are the program's own tree:

  core/tokens [V, H]                      the block's token table
  core/layer_<l>/attn_norm, ffn_norm /scale
  core/layer_<l>/attn/{wq,wk,wv,wo}/kernel, {q_norm,k_norm}/scale [head_dim]
  core/layer_<l>/moe/router [H, E], select_bias [E], expert_gate,
        expert_up [held, H, F], expert_down [held, F, H]
  core/out_norm/scale

**The sequence.** Step t of a lane's history is six rows: the observation o_t
(the trunk's output) and its block of five slots in ``HEADS`` order (action
type, move x, move y, target, ability), each the table's row of its token:
head k's value a at ``offset_k + a``, ``[MASK]`` and NONE the table's last
two ids. A clean slot holds its committed value, or NONE where the action's
type leaves it out (``[MASK]`` in a step without a block: the bootstrap). Position (for RoPE) of row k of step t is ``6 p_t + k``,
``p_t`` the step's place in its episode. For each of the last ``steps``
steps, ``S = diffusion_steps`` noisy copies of the five slots follow: copy s
holds the slots committed before pass s (stage < s; the type is stage 1,
an argument the stage that committed it, 2..S), NONE for a left-out slot
from copy 2 on, ``[MASK]`` elsewhere.

**The mask** (block-causal, SDAR's): a row sees only rows of its own episode;
o_t sees the clean rows of earlier steps and itself; a clean slot of step t
sees the same, o_t and the five clean slots of t; noisy copy s of step t
sees the clean rows of earlier steps, o_t and its own five rows.

Every layer, on the stream h (float32), two residual adds and no post-norm:

  a = RMSNorm_attn(h); q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk) per head,
  v = a Wv; q, k rotated by RoPE(theta) at the row's position;
  h = h + softmax(q . k / sqrt(D) over the rows the mask allows) v Wo
      (query head j reads KV head j // (n_heads / n_kv_heads); no gate);
  m = RMSNorm_ffn(h); r = softmax(m Wr) over E; chosen = the
  experts_per_token largest of r + select_bias; w = r_chosen / sum(r_chosen);
  h = h + sum over the chosen AND held e of w_e W2_e(silu(W1_e m) * W3_e m)
  y = RMSNorm_out(h) after the last layer

The selection bias is the program's parameter that no gradient reaches; the
cell keeps it at 0 (``ppo.select_bias_rate`` 0: Qwen3-MoE has none), the
tests move it to see that both sides read it alike. Expert e is held iff expert_offset <= e < expert_offset + held_experts; what
the absent experts would add is left out, here as in the program. There is
NO shared expert. Outputs: the value from each o_t row; head k of pass s
from slot k's row of noisy copy s.

Recalled from the Qwen3-MoE layer SDAR is built on and from SDAR's
description, not verifiable here (no network): the per-head RMSNorm of q and
k before RoPE, the softmax router renormalised over the chosen
(norm_topk_prob), no bias, no post-norms, the mask's block structure. This
repo's own: the action as a block of five tokens, the order of commitment
and the NONE token (the configuration's ``assumed``).

``forward``'s ``fault`` makes the mathematics wrong in ONE way, for the tests
that show the comparison sees each (``tests/test_sdar.py``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import policy_ref
from benchmark.reference.afmoe_ref import _kernel, episodes, rms_norm, rope, swiglu

PRECISION = "highest"
HEADS = ("action_type", "move_x", "move_y", "target_unit", "ability")
A_MOVE, A_ATTACK, A_CAST = 1, 2, 3
FAULTS = ("o_sees_its_slots", "copy_sees_clean_slots", "causal_block", "no_rope", "no_qk_norm", "sigmoid_router")
QUERY_BLOCK = 512      # rows whose scores are computed at once


def head_sizes(actions: Mapping[str, Any]) -> Dict[str, int]:
    return {
        "action_type": actions["n_action_types"], "move_x": actions["move_bins"],
        "move_y": actions["move_bins"], "target_unit": actions["max_units"], "ability": actions["max_abilities"],
    }


def token_table_ids(actions: Mapping[str, Any]):
    """(offset of each head, ``[MASK]``, NONE)."""
    sizes, offsets, at = head_sizes(actions), {}, 0
    for h in HEADS:
        offsets[h] = at
        at += sizes[h]
    return offsets, at, at + 1


def relevant(a_type: jnp.ndarray) -> jnp.ndarray:
    """[..., 5]: the slots the type fills."""
    move = a_type == A_MOVE
    target = (a_type == A_ATTACK) | (a_type == A_CAST)
    return jnp.stack([jnp.ones_like(move), move, move, target, a_type == A_CAST], axis=-1)


def build_rows(actions_cfg, actions, act_stage, first: int, steps: int, S: int):
    """Token ids of a history of T steps with noisy copies of steps first..
    first + steps - 1: (clean ids [B, T, 5], noisy ids [B, steps, S, 5])."""
    offsets, mask, none = token_table_ids(actions_cfg)
    value = jnp.stack([offsets[h] + actions[h] for h in HEADS], axis=-1)     # [B, T, 5]
    stage = act_stage.astype(jnp.int32)
    # a step without a block (its type uncommitted: the bootstrap) holds [MASK]
    clean = jnp.where(stage[..., :1] == 0, mask, jnp.where(stage > 0, value, none))
    noisy = []
    for s in range(1, S + 1):
        st, v = stage[:, first:first + steps], value[:, first:first + steps]
        ids = jnp.where((st > 0) & (st < s), v, mask)
        noisy.append(jnp.where((st == 0) & (s > 1), none, ids))
    return clean, jnp.stack(noisy, axis=2)


def row_metadata(T: int, first: int, steps: int, S: int, again: bool = False):
    """Each row's (step, slot, copy, branch): the clean rows step by step,
    then the noisy copies of steps first.. (copy s = 1..S, slots 1..5), all
    of branch 0; with ``again``, the clean rows and noisy copies of steps
    first.. once more, as branch 1."""
    clean = [(t, k, 0, 0) for t in range(T) for k in range(6)]
    noisy = [(t, k, s, 0) for t in range(first, first + steps) for s in range(1, S + 1) for k in range(1, 6)]
    rows = clean + noisy
    if again:
        rows += [(t, k, c, 1) for t, k, c, _ in clean[6 * first:6 * (first + steps)] + noisy]
    return tuple(np.asarray(x, np.int32) for x in zip(*rows))


def visible(meta, rows: slice, keys: slice = slice(None), fault: Optional[str] = None):
    """``[B, n, m]``: whether the rows ``rows`` may see the rows ``keys``.
    ``meta``: each row's (step, slot, copy, branch, shared) ``[N]`` and
    episode ``[B, N]``; ``shared`` marks the branch-0 rows that branch 1
    sees too (the steps before the ones it reads again)."""
    step, slot, copy, branch, shared, ep = meta
    ti, tj = step[rows][:, None], step[keys][None, :]
    ki, kj = slot[rows][:, None], slot[keys][None, :]
    ci, cj = copy[rows][:, None], copy[keys][None, :]
    same_block = tj == ti
    o_j = kj == 0
    if fault == "o_sees_its_slots":
        clean_i_sees = (tj < ti) | same_block
    elif fault == "causal_block":
        clean_i_sees = (tj < ti) | (same_block & (kj <= ki))
    else:
        clean_i_sees = (tj < ti) | (same_block & (o_j | (ki > 0)))
    noisy_i_sees_clean = (tj < ti) | (same_block & (o_j | (fault == "copy_sees_clean_slots")))
    sees = jnp.where(cj == 0, jnp.where(ci == 0, clean_i_sees, noisy_i_sees_clean), (ci == cj) & same_block)
    sees = sees & ((branch[rows][:, None] == branch[keys][None, :]) | shared[keys][None, :])
    return sees[None] & (ep[:, rows][:, :, None] == ep[:, keys][:, None, :])


def attention(p, a, pos, meta, model: Mapping[str, Any], grad_rows=None, fault: Optional[str] = None, clean_rows: int = 0):
    """a [B, N, H], pos [B, N], ``meta`` what ``visible`` reads: the rows'
    attention, a block of queries at a time. The first ``clean_rows`` rows
    are the clean rows, step by step: a clean row sees no row of a later
    step and no noisy copy, so a block of them is scored against the rows up
    to its last step's block alone (what it leaves out the mask would zero)."""
    B, N, _ = a.shape
    nh, kv, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["rms_norm_eps"]
    q = _kernel(p["wq"], a).reshape(B, N, nh, D)
    k = _kernel(p["wk"], a).reshape(B, N, kv, D)
    v = _kernel(p["wv"], a).reshape(B, N, kv, D)
    if fault != "no_qk_norm":
        q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)
    if fault != "no_rope":
        q, k = rope(q, pos, model["rope_theta"]), rope(k, pos, model["rope_theta"])
    if grad_rows is not None:
        # truncated backpropagation: what the steps before the trained chunk
        # left in the program's ring is data, not a function of the parameters
        k = jnp.where(grad_rows[None, :, None, None], k, jax.lax.stop_gradient(k))
        v = jnp.where(grad_rows[None, :, None, None], v, jax.lax.stop_gradient(v))
    group = nh // kv
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = []
    blocks = [(q0, min(q0 + QUERY_BLOCK, clean_rows)) for q0 in range(0, clean_rows, QUERY_BLOCK)]
    blocks = [(q0, q1, 6 * -(-q1 // 6)) for q0, q1 in blocks]
    blocks += [(q0, min(q0 + QUERY_BLOCK, N), N) for q0 in range(clean_rows, N, QUERY_BLOCK)]
    for q0, q1, k1 in blocks:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q1], k[:, :k1]) / math.sqrt(D)
        scores = jnp.where(visible(meta, slice(q0, q1), slice(0, k1), fault)[:, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v[:, :k1]))
    return _kernel(p["wo"], jnp.concatenate(out, axis=1).reshape(B, N, nh * D))


def route(p, m, model: Mapping[str, Any], chosen: Optional[jnp.ndarray] = None, fault: Optional[str] = None):
    """Scores [.., E], the experts taken [.., k], their weights [.., k], and
    ``margin`` [..]: how far below the reference's own k-th largest score the
    lowest taken expert lies (0 where the choice is the reference's own),
    choices by score + select_bias.
    ``chosen`` given replaces the choice; the weights are always from the
    scores computed here."""
    logits = jnp.matmul(m, p["router"].astype(jnp.float32))
    s = jax.nn.sigmoid(logits) if fault == "sigmoid_router" else jax.nn.softmax(logits, axis=-1)
    biased = s + p["select_bias"].astype(jnp.float32)
    line, own = jax.lax.top_k(biased, model["experts_per_token"])
    if chosen is None:
        chosen = own
    margin = jnp.maximum(line[..., -1:] - jnp.take_along_axis(biased, chosen, axis=-1), 0.0).max(axis=-1)
    taken = jnp.take_along_axis(s, chosen, axis=-1)
    w = taken / taken.sum(axis=-1, keepdims=True) if model["route_norm"] else taken
    return {"scores": s, "chosen": chosen, "weights": w * model["route_scale"], "margin": margin}


def experts(p, m, model: Mapping[str, Any], routes=None, fault=None, held: Optional[range] = None):
    """The terms of the experts held here (``held`` another share of them,
    for the test that sums every chip's share); no shared expert."""
    r = route(p, m, model, routes, fault)
    f = jnp.zeros_like(m)
    first = model["expert_offset"]
    for i, e in enumerate(held if held is not None else range(first, first + (model["held_experts"] or model["moe_experts"]))):
        weight = jnp.where(r["chosen"] == e, r["weights"], 0.0).sum(axis=-1)
        g = jnp.matmul(m, p["expert_gate"][i].astype(jnp.float32))
        u = jnp.matmul(m, p["expert_up"][i].astype(jnp.float32))
        f = f + weight[..., None] * jnp.matmul(jax.nn.silu(g) * u, p["expert_down"][i].astype(jnp.float32))
    return f, r


def forward(
    params, obs, dones, actions, act_stage, model: Mapping[str, Any], actions_cfg: Mapping[str, Any],
    noisy_first: int, noisy_steps: int, routes: Optional[List[jnp.ndarray]] = None,
    grad_from: Optional[int] = None, fault: Optional[str] = None,
    again_routes: Optional[List[jnp.ndarray]] = None,
):
    """A lane's whole history: obs ``[B, T, ...]`` from an episode start,
    ``dones [B, T]``, the committed ``actions`` (each head ``[B, T]``) and
    ``act_stage [B, T, 5]`` (a step without a block, the bootstrap, may hold
    anything: nothing reads its slots). Returns (logits of each pass for
    steps ``noisy_first..`` + ``noisy_steps``: each head ``[S, B,
    noisy_steps, K]``; values ``[B, T]``; routing per layer over all rows).
    ``routes`` given (each layer ``[B, N, k]`` in the row order of
    ``row_metadata``) holds the choice of experts equal; ``grad_from`` makes
    the keys and values of the steps before it data.

    ``again_routes`` given (each layer ``[B, n, k]``: the clean rows of steps
    ``noisy_first..`` + ``noisy_steps``, then their noisy copies; ``routes``
    given too), the sequence reads those steps once more with these experts
    (``row_metadata``'s branch 1: its rows see the steps before
    ``noisy_first`` and each other by the same rules, and no other row sees
    them), and a fourth output holds their (logits, values ``[B,
    noisy_steps]``): the same steps under two choices of experts for the
    price of one history."""
    p = params["params"]
    core = p["core"]
    S = model["diffusion_steps"]
    eps = model["rms_norm_eps"]
    again = again_routes is not None
    with jax.default_matmul_precision(PRECISION):
        x, e = policy_ref.trunk(p, obs)                          # [B, T, H]
        B, T, H = x.shape
        first, steps = noisy_first, noisy_steps
        clean, noisy = build_rows(actions_cfg, actions, act_stage, first, steps, S)
        table = core["tokens"].astype(jnp.float32)
        clean_rows = jnp.concatenate([x[:, :, None], table[clean]], axis=2).reshape(B, T * 6, H)
        noisy_rows = table[noisy].reshape(B, steps * S * 5, H)
        rows = [clean_rows, noisy_rows]
        if again:
            rows += [clean_rows[:, 6 * first:6 * (first + steps)], noisy_rows]
        step, slot, copy, branch = (jnp.asarray(m) for m in row_metadata(T, first, steps, S, again))
        episode, place = episodes(jnp.asarray(dones, jnp.float32))
        meta = (step, slot, copy, branch, (branch == 0) & (step < first), episode[:, step])
        pos = 6 * place[:, step] + slot[None]
        grad_rows = None if grad_from is None else jnp.asarray(step >= grad_from)
        h, routing = jnp.concatenate(rows, axis=1), []
        for l in range(model["n_layers"]):
            lp = core[f"layer_{l}"]
            a = rms_norm(lp["attn_norm"], h, eps)
            h = h + attention(lp["attn"], a, pos, meta, model, grad_rows, fault, clean_rows=T * 6)
            m = rms_norm(lp["ffn_norm"], h, eps)
            chosen = None if routes is None else routes[l]
            if again:
                chosen = jnp.concatenate([chosen, again_routes[l]], axis=1)
            f, r = experts(lp["moe"], m, model, chosen, fault)
            routing.append(r)
            h = h + f
        y = rms_norm(core["out_norm"], h, eps)

        def outputs(y_clean, y_noisy, e_clean):
            # the value from each o_t row; head k of pass s from slot k's row of copy s
            _, values = policy_ref.heads(p, y_clean[:, ::6], e_clean)
            logits, _ = policy_ref.heads(p, y_noisy.reshape(B, steps, S, 5, H), e[:, first:first + steps, None, None])
            return {h_: jnp.moveaxis(logits[h_][:, :, :, j], 2, 0) for j, h_ in enumerate(HEADS)}, values

        n = steps * S * 5
        stage_logits, values = outputs(y[:, :T * 6], y[:, T * 6:T * 6 + n], e)
        if not again:
            return stage_logits, values, routing
        y2 = y[:, T * 6 + n:]
        logits2, values2 = outputs(y2[:, :6 * steps], y2[:, 6 * steps:], e[:, first:first + steps])
    return stage_logits, values, routing, (logits2, values2)


# -- the PPO loss of one chunk, for the gradient comparison ----------------------


def _masked_log_softmax(logits, mask):
    any_legal = mask.any(axis=-1, keepdims=True)
    return jax.nn.log_softmax(jnp.where(jnp.where(any_legal, mask, True), logits, -1e9), axis=-1)


def staged_log_prob_and_entropy(stage_logits, obs, actions, act_stage):
    """The log-probability of each step's action given its order (the type
    from pass 1, each relevant argument from the pass that committed it), and
    the entropy of the same heads on the same path."""
    st = act_stage.astype(jnp.int32)
    a_type = actions["action_type"]
    rel = relevant(a_type).astype(jnp.float32)
    logp = jnp.zeros(a_type.shape, jnp.float32)
    ent = jnp.zeros(a_type.shape, jnp.float32)
    masks = {
        "action_type": obs["mask_action_type"],
        "target_unit": jnp.where((a_type == A_CAST)[..., None], obs["mask_cast_target"], obs["mask_target_unit"]),
        "ability": obs["mask_ability"],
    }
    for j, h in enumerate(HEADS):
        lg = sum(jnp.where((st[..., j] == s + 1)[..., None], stage_logits[h][s], 0.0) for s in range(stage_logits[h].shape[0]))
        lp = _masked_log_softmax(lg, masks[h]) if h in masks else jax.nn.log_softmax(lg, axis=-1)
        taken = jnp.take_along_axis(lp, actions[h][..., None].astype(jnp.int32), axis=-1)[..., 0]
        logp = logp + rel[..., j] * taken
        ent = ent + rel[..., j] * -(jnp.exp(lp) * lp).sum(-1)
    return logp, ent


def ppo_loss(params, obs, dones, batch: Mapping[str, Any], model, actions_cfg, ppo: Mapping[str, float]):
    """Clipped-surrogate PPO loss of the LAST ``T`` steps of the histories
    (``batch``: actions, act_stage, behavior_logp, rewards, dones, valid, each
    ``[B, T]``; the history holds one step more, the bootstrap observation,
    and ``batch``'s actions are the whole history's). The auxiliary
    load-balancing loss is left out (the comparison sets its coefficient to
    0): it is a mean over the program's own rows."""
    T = batch["rewards"].shape[1]
    hist = dones.shape[1]
    stage_logits, values, _ = forward(
        params, obs, dones, batch["actions"], batch["act_stage"], model, actions_cfg,
        noisy_first=hist - (T + 1), noisy_steps=T, grad_from=hist - (T + 1),
    )
    values = values[:, -(T + 1):]
    obs_t = {k: v[:, -(T + 1):][:, :T] for k, v in obs.items()}
    acts = {h: v[:, -(T + 1):][:, :T] for h, v in batch["actions"].items()}
    logp, ent = staged_log_prob_and_entropy(stage_logits, obs_t, acts, batch["act_stage"][:, -(T + 1):][:, :T])
    valid = batch["valid"].astype(jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
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
    return policy_loss - ppo["entropy_coef"] * (ent * valid).sum() / n + ppo["value_coef"] * value_loss
