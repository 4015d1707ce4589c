"""Masked multi-head categorical action distribution.

The reference samples one categorical per head, masks invalid actions before
softmax, and sums per-head log-probs (SURVEY.md §3.3; reconstructed — the
reference checkout was an empty mount). Here the joint log-prob is the
*conditional* factorization: sub-heads only contribute when the sampled action
type makes them relevant (move bins for MOVE, target slot for ATTACK/CAST,
ability slot for CAST), so the surrogate ratio in PPO is exact.

The target-unit head's legality is itself conditional on the action type
(ATTACK may hit any enemy or a deniable allied creep; CAST only enemies in
cast range), so it carries two masks and the log-softmax is selected by the
sampled/stored action type — sampled actions are legal by construction, and
the sim never has to silently drop one.

All functions are shape-polymorphic over leading axes — they work for the
actor's ``[B, ...]`` step and the learner's ``[B, T, ...]`` sequences alike —
and are jit/vmap/grad-safe (no Python branching on data).
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

# Action-type enum values — must match protos (dota.proto ActionType).
A_NOOP, A_MOVE, A_ATTACK, A_CAST = 0, 1, 2, 3

# Large negative logit for illegal entries. Finite (not -inf) so that
# fully-masked rows still produce finite softmax output under bf16/f32.
NEG_INF = -1e9

HEADS = ("action_type", "move_x", "move_y", "target_unit", "ability")


def _safe_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """A mask with at least one legal entry per row.

    A head can be entirely illegal (e.g. no attackable target) — it is then
    never *used* (its action type is masked out too), but its log-softmax must
    stay finite so `0 × logp` stays 0, not NaN. Fully-illegal rows fall back
    to all-legal (uniform).
    """
    any_legal = jnp.any(mask, axis=-1, keepdims=True)
    return jnp.where(any_legal, mask, True)


def masked_log_softmax(logits: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    masked = jnp.where(_safe_mask(mask), logits, NEG_INF)
    return jax.nn.log_softmax(masked, axis=-1)


def _head_logps(
    logits: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray]
) -> Dict[str, jnp.ndarray]:
    """Masked, normalized log-probs per head. The target head appears twice,
    once per conditioning action type."""
    return {
        "action_type": masked_log_softmax(
            logits["action_type"], obs["mask_action_type"]
        ),
        # Move heads are always fully legal — no mask path needed.
        "move_x": jax.nn.log_softmax(logits["move_x"], axis=-1),
        "move_y": jax.nn.log_softmax(logits["move_y"], axis=-1),
        "target_attack": masked_log_softmax(
            logits["target_unit"], obs["mask_target_unit"]
        ),
        "target_cast": masked_log_softmax(
            logits["target_unit"], obs["mask_cast_target"]
        ),
        "ability": masked_log_softmax(logits["ability"], obs["mask_ability"]),
    }


def _select_target_logps(
    logps: Mapping[str, jnp.ndarray], action_type: jnp.ndarray
) -> jnp.ndarray:
    """Per-row target-head log-softmax conditioned on the action type."""
    is_cast = (action_type == A_CAST)[..., None]
    return jnp.where(is_cast, logps["target_cast"], logps["target_attack"])


def sample(
    rng: jax.Array,
    logits: Mapping[str, jnp.ndarray],
    obs: Mapping[str, jnp.ndarray],
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Sample every head; return (actions, joint conditional log-prob).

    The action type is sampled first; the target head then samples under the
    mask that type implies, so every emitted action is legal by construction.
    """
    logps = _head_logps(logits, obs)
    k_type, k_mx, k_my, k_tgt, k_ab = jax.random.split(rng, 5)
    a_type = jax.random.categorical(k_type, logps["action_type"], axis=-1)
    target_logps = _select_target_logps(logps, a_type)
    actions = {
        "action_type": a_type,
        "move_x": jax.random.categorical(k_mx, logps["move_x"], axis=-1),
        "move_y": jax.random.categorical(k_my, logps["move_y"], axis=-1),
        "target_unit": jax.random.categorical(k_tgt, target_logps, axis=-1),
        "ability": jax.random.categorical(k_ab, logps["ability"], axis=-1),
    }
    return actions, _joint_logp(logps, actions)


def log_prob(
    logits: Mapping[str, jnp.ndarray],
    obs: Mapping[str, jnp.ndarray],
    actions: Mapping[str, jnp.ndarray],
) -> jnp.ndarray:
    """Joint conditional log-prob of stored ``actions`` under ``logits``."""
    return _joint_logp(_head_logps(logits, obs), actions)


def _take(logp: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``logp[..., idx]`` along the head's (last) axis as compare-select-reduce,
    never an XLA ``gather``, and in the backward pass never a ``scatter-add``
    (the gradient of a select is a select).

    A data-dependent gather runs close to one element at a time on the TPU
    and takes its operand from HBM, so the log-softmax it reads was written
    out whole: the five lookups of ``_joint_logp`` were a fifth of the
    ``dota5v5-lstm128`` step (PERF.md section 6, PR 27). The select reads the
    head's columns once more inside the fusion that normalised them.

    Select BEFORE reduce, deliberately NOT a one-hot product: masked entries
    are ``NEG_INF`` and an ``einsum`` at default precision rounds float32
    through bfloat16 on the MXU. Nothing in an unselected slot reaches the
    sum, and a sum of one value and zeros is that value: for an index in
    ``[0, K)`` the result is the gather's bit for bit (a ``-0.0`` comes out
    ``+0.0``).

    An index outside ``[0, K)`` gives ``NaN``, negatives included (the
    gather filled ``NaN`` past the head and wrapped ``-K..-1``). That loud
    result is kept on purpose, at one compare a row: sampled actions are in
    range by construction (``jax.random.categorical``), but actions from
    external actors reach ``log_prob`` through an ingest door that holds
    integers to their narrow store dtype's range only
    (``transport/serialize.py _narrow_rollout_flat``,
    ``buffer/trajectory_buffer.py _payload_in_bounds``: int8 for every head,
    and no range check at all on a full-width wire), not to the head's
    ``size - 1``. A corrupt action therefore still poisons the loss and trips
    the non-finite-loss latch (``train/health.py``), as it did before.
    """
    k = logp.shape[-1]
    idx = idx.astype(jnp.int32)
    mask = idx[..., None] == jnp.arange(k, dtype=jnp.int32)
    picked = jnp.where(mask, logp, 0.0).sum(axis=-1)
    return jnp.where((idx >= 0) & (idx < k), picked, jnp.nan)


def _joint_logp(
    logps: Mapping[str, jnp.ndarray], actions: Mapping[str, jnp.ndarray]
) -> jnp.ndarray:
    a_type = actions["action_type"]
    move = (a_type == A_MOVE).astype(jnp.float32)
    target = ((a_type == A_ATTACK) | (a_type == A_CAST)).astype(jnp.float32)
    cast = (a_type == A_CAST).astype(jnp.float32)
    target_logps = _select_target_logps(logps, a_type)
    return (
        _take(logps["action_type"], a_type)
        + move * (_take(logps["move_x"], actions["move_x"])
                  + _take(logps["move_y"], actions["move_y"]))
        + target * _take(target_logps, actions["target_unit"])
        + cast * _take(logps["ability"], actions["ability"])
    )


def kl(
    logits_p: Mapping[str, jnp.ndarray],
    logits_q: Mapping[str, jnp.ndarray],
    obs: Mapping[str, jnp.ndarray],
) -> jnp.ndarray:
    """Exact KL(P ‖ Q) of the conditional factorization at the same state.

    Mirrors ``entropy``: per-head categorical KLs, with sub-heads weighted
    by P's probability of selecting their conditioning action type. Both
    policies see the same observation, so the legality masks (and therefore
    the supports) coincide — masked entries contribute exp(-1e9)·Δ ≈ 0.
    """
    lp = _head_logps(logits_p, obs)
    lq = _head_logps(logits_q, obs)

    def KLh(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(jnp.exp(a) * (a - b), axis=-1)

    p_type = jnp.exp(lp["action_type"])
    return (
        KLh(lp["action_type"], lq["action_type"])
        + p_type[..., A_MOVE]
        * (KLh(lp["move_x"], lq["move_x"]) + KLh(lp["move_y"], lq["move_y"]))
        + p_type[..., A_ATTACK] * KLh(lp["target_attack"], lq["target_attack"])
        + p_type[..., A_CAST]
        * (KLh(lp["target_cast"], lq["target_cast"]) + KLh(lp["ability"], lq["ability"]))
    )


def entropy(
    logits: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray]
) -> jnp.ndarray:
    """Exact entropy of the conditional factorization: masked per-head
    entropies with sub-heads weighted by the probability their conditioning
    action type is selected."""
    logps = _head_logps(logits, obs)
    p_type = jnp.exp(logps["action_type"])

    def H(lp: jnp.ndarray) -> jnp.ndarray:
        return -jnp.sum(jnp.exp(lp) * lp, axis=-1)

    p_move = p_type[..., A_MOVE]
    p_attack = p_type[..., A_ATTACK]
    p_cast = p_type[..., A_CAST]
    return (
        H(logps["action_type"])
        + p_move * (H(logps["move_x"]) + H(logps["move_y"]))
        + p_attack * H(logps["target_attack"])
        + p_cast * (H(logps["target_cast"]) + H(logps["ability"]))
    )


# -- an action decoded as a block over several passes (``models/sdar.py``) ----------
#
# The heads are the five slots of a block, in ``HEADS`` order. Pass 1 decodes
# the action type; the arguments the type makes relevant (``relevant``) are
# committed over passes 2..S, in an order drawn from the rollout's key alone
# (``commit_stages``); a slot the type leaves out is NONE. Logits come a pass
# at a time (``stage_logits``: each head ``[S, ..., K]``, pass s at index
# s - 1), and a slot's head is read from the pass that committed it
# (``act_stage [..., 5]``: 1 for the type, 2..S for an argument, 0 for NONE).
# The order is exogenous, so the log-probability of the action given the
# state and the order is the conditional factorization above with each head
# taken from its own pass, and the PPO ratio is exact given the order.


def relevant(a_type: jnp.ndarray) -> jnp.ndarray:
    """``[..., 5]`` bool: the slots an action of type ``a_type`` fills, as
    ``_joint_logp`` counts them (NOOP the type alone, MOVE x and y, ATTACK the
    target, CAST the target and the ability)."""
    move = a_type == A_MOVE
    target = (a_type == A_ATTACK) | (a_type == A_CAST)
    return jnp.stack([jnp.ones_like(move), move, move, target, a_type == A_CAST], axis=-1)


def commit_stages(key: jax.Array, a_type: jnp.ndarray, steps: int) -> jnp.ndarray:
    """``int8 [..., 5]``: the pass that commits each slot. The type is pass
    1; the n relevant arguments go over passes 2..``steps`` by LLaDA's static
    schedule (``n // (steps - 1)`` a pass, the remainder one more each in the
    first passes: two arguments over two passes are [1, 1], one is [1, 0]),
    in the order of priorities drawn from ``key`` alone (LLaDA's "random"
    remasking: which slot goes first never depends on the parameters); a
    slot the type leaves out is 0 (NONE)."""
    rel = relevant(a_type)
    u = jax.random.uniform(key, rel.shape)
    args = rel[..., 1:]
    # rank of each relevant argument among the relevant ones, by priority
    rank = (args[..., None, :] & (u[..., None, 1:] < u[..., 1:, None])).sum(-1)
    n, passes = args.sum(-1), steps - 1
    # how many arguments the passes 2..j + 2 commit together: rank r goes in
    # the first pass whose count exceeds it
    per, extra = n // passes, n % passes
    ends = jnp.stack([(j + 1) * per + jnp.minimum(j + 1, extra) for j in range(passes)], axis=-1)
    stage = 2 + (rank[..., None] >= ends[..., None, :]).sum(-1)
    stage = jnp.where(args, stage, 0)
    return jnp.concatenate([jnp.ones_like(stage[..., :1]), stage], axis=-1).astype(jnp.int8)


def pick_stage_logits(stage_logits: Mapping[str, jnp.ndarray], act_stage: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Each head's logits from the pass that committed its slot (a select
    over the passes, never a gather; zeros for a NONE slot, which no term
    reads)."""
    out = {}
    for j, h in enumerate(HEADS):
        lg, st = stage_logits[h], act_stage[..., j].astype(jnp.int32)
        out[h] = sum(jnp.where((st == s + 1)[..., None], lg[s], 0.0) for s in range(lg.shape[0]))
    return out


def stage_sample(
    key: jax.Array, logits: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray], a_type: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """One pass's draw of every argument head (the target under the mask the
    type implies); the caller keeps the slots this pass commits."""
    logps = _head_logps(logits, obs)
    k_mx, k_my, k_tgt, k_ab = jax.random.split(key, 4)
    return {
        "move_x": jax.random.categorical(k_mx, logps["move_x"], axis=-1),
        "move_y": jax.random.categorical(k_my, logps["move_y"], axis=-1),
        "target_unit": jax.random.categorical(k_tgt, _select_target_logps(logps, a_type), axis=-1),
        "ability": jax.random.categorical(k_ab, logps["ability"], axis=-1),
    }


def type_sample(key: jax.Array, logits: jnp.ndarray, obs: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
    """Pass 1's draw of the action type under its mask."""
    return jax.random.categorical(key, masked_log_softmax(logits, obs["mask_action_type"]), axis=-1)


def staged_log_prob(
    stage_logits: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray],
    actions: Mapping[str, jnp.ndarray], act_stage: jnp.ndarray,
) -> jnp.ndarray:
    """``log p^1(type) + sum over relevant k of log p^{stage(k)}(a_k)``:
    ``log_prob`` of the heads as their passes committed them."""
    return log_prob(pick_stage_logits(stage_logits, act_stage), obs, actions)


def _path_terms(lp: Mapping[str, jnp.ndarray], fn, a_type: jnp.ndarray, lq=None) -> jnp.ndarray:
    """``fn`` of the type's head plus ``fn`` of the heads the sampled type made
    relevant, each from the pass that committed it: the path the rollout took
    (passes 2..S exist for the sampled type only, so the other types' heads
    are not there to weigh by their probability as ``entropy`` does)."""
    q = lq if lq is not None else lp
    rel = relevant(a_type).astype(jnp.float32)
    cast = (a_type == A_CAST)[..., None]
    target = fn(jnp.where(cast, lp["target_cast"], lp["target_attack"]), jnp.where(cast, q["target_cast"], q["target_attack"]))
    return (
        fn(lp["action_type"], q["action_type"])
        + rel[..., 1] * fn(lp["move_x"], q["move_x"]) + rel[..., 2] * fn(lp["move_y"], q["move_y"])
        + rel[..., 3] * target + rel[..., 4] * fn(lp["ability"], q["ability"])
    )


def staged_entropy(
    stage_logits: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray],
    actions: Mapping[str, jnp.ndarray], act_stage: jnp.ndarray,
) -> jnp.ndarray:
    """Entropy in the path-wise conditional form: the type's, plus each
    relevant argument head's at the pass that committed it."""
    lp = _head_logps(pick_stage_logits(stage_logits, act_stage), obs)
    return _path_terms(lp, lambda a, _: -jnp.sum(jnp.exp(a) * a, axis=-1), actions["action_type"])


def staged_kl(
    logits_p: Mapping[str, jnp.ndarray], logits_q: Mapping[str, jnp.ndarray], obs: Mapping[str, jnp.ndarray],
    actions: Mapping[str, jnp.ndarray], act_stage: jnp.ndarray,
) -> jnp.ndarray:
    """KL(P || Q) in the same path-wise form, both read at the same passes."""
    lp = _head_logps(pick_stage_logits(logits_p, act_stage), obs)
    lq = _head_logps(pick_stage_logits(logits_q, act_stage), obs)
    return _path_terms(lp, lambda a, b: jnp.sum(jnp.exp(a) * (a - b), axis=-1), actions["action_type"], lq)
