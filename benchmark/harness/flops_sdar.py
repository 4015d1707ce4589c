"""Operations and bytes of the policy with the SDAR core, as functions of its
sizes: arithmetic on a configuration file's ``run_config`` and on things a
run counted, never a number from the program's own cost model. The
conventions are ``harness/flops_afmoe.py``'s: one multiply-add is two
operations; only products are counted (norms, RoPE, the softmax and the
block's mask are thousandths of the total).

**Rows.** A game step is six positions (the observation and its block of
five action tokens). A rollout step runs the core S + 1 times
(``diffusion_steps`` S): pass 1 over 6 rows (the observation and five
``[MASK]``), passes 2..S and the commit over the 5 slots: ``6 + 5 S`` rows.
The learner's pass is a chunk's clean rows (six a step, the bootstrap's
with them) and S noisy copies of each step's five slots: ``6 (T + 1) + 5 S
T`` rows a lane (342 at T = 16, S = 3). Heads run on the slot rows of every
pass and on each observation's row.

**Per row and layer** (H stream; nh query heads over kv KV heads of D;
expert width Fe, E router outputs, no shared expert):

  attention       H nh D + 2 H kv D + nh D H          q, k, v and o
                  + nh K 2 D                          scores and values over the K
                                                      rows the row sees
  experts         H E + 3 H Fe a                      router; a = token-expert
                                                      pairs a row that land on an
                                                      expert HELD here (counted by
                                                      the program: moe/local_assignments)

K is a row's visible rows: of the ring, the positions of its episode before
its step (6 p at step p of the episode, ``pos`` / 6 steps), and of its own
pass the rows the block mask allows (``own_visible``).

``block_attend_work`` is what ``core_block_attend`` holds: the products of
a pass's rows against the ring and the block, and the ring rows a query may
see read once a pass (keys and values, in the compute type); the learner
reads them forward and backward and multiplies three times (forward and the
backward's two products). No weight is read under that scope. One fused
dispatch runs ``passes_per_step`` passes a rollout step over both teams'
rows where ``league/shared_pass_dispatches_total`` says a dispatch's teams
share a pass, a pass a team otherwise, and reads the core's weights once a
pass (``weight_bytes_per_dispatch``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark.harness import flops

_BYTES = {"bfloat16": 2, "float32": 4}
ROWS, SLOTS = 6, 5


def _model(rc: Mapping[str, Mapping[str, Any]]) -> Mapping[str, Any]:
    model = rc["model"]
    if model.get("core") != "sdar":
        raise flops.UnsupportedShape(f"model.core is {model.get('core')!r}: these counts are the SDAR core's")
    if model["dtype"] not in _BYTES:
        raise flops.UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
    return model


def passes_per_step(model: Mapping[str, Any]) -> int:
    """Core passes a rollout step: S denoising passes and the commit."""
    return model["diffusion_steps"] + 1


def rollout_rows(model: Mapping[str, Any]) -> int:
    """Rows the core runs a lane a rollout step: 6 in pass 1, 5 in each other."""
    return ROWS + SLOTS * model["diffusion_steps"]


def learner_rows(model: Mapping[str, Any], T: int) -> int:
    """Rows of the learner's pass a lane: clean blocks of T steps and the
    bootstrap, S noisy copies of each step's slots."""
    return ROWS * (T + 1) + SLOTS * model["diffusion_steps"] * T


def own_visible(model: Mapping[str, Any], T: int) -> Dict[str, float]:
    """Rows of its own pass each row sees, summed over a pass's rows: a
    rollout step's passes (pass 1: the observation itself, each slot all six;
    the others: five each) and the learner's chunk (a clean row the clean rows
    of earlier steps and its block as the mask allows, a noisy row the clean
    rows of earlier steps, its observation and its own copy)."""
    S = model["diffusion_steps"]
    rollout = (1 + SLOTS * ROWS) + S * SLOTS * SLOTS
    learner = 0
    for t in range(T + 1):
        learner += ROWS * t + 1                         # the observation: earlier blocks and itself
        learner += SLOTS * (ROWS * t + ROWS)            # a clean slot: earlier blocks and its own six
    learner += sum(S * SLOTS * (ROWS * t + 1 + SLOTS) for t in range(T))      # a noisy slot: earlier blocks, o_t, its copy
    return {"rollout": float(rollout), "learner": float(learner)}


def core_weight_count(model: Mapping[str, Any]) -> Dict[str, float]:
    """Parameters of the core's matrices held on this chip, by part."""
    H, nh, kv, D = model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"]
    held = model["held_experts"] or model["moe_experts"]
    L = model["n_layers"]
    return {
        "attn": float(L * (2 * H * nh * D + 2 * H * kv * D)),
        "router": float(L * H * model["moe_experts"]),
        "routed": float(L * 3 * H * model["expert_ffn_dim"] * held),
    }


def carry_bytes_per_lane(model: Mapping[str, Any]) -> float:
    item = _BYTES[model["dtype"]]
    return float(8 + model["n_layers"] * model["full_context"] * 2 * model["n_kv_heads"] * model["head_dim"] * item)


def _attend_macs(model: Mapping[str, Any], rows: float, ring_rows: float, own: float) -> float:
    """Multiply-adds of ``rows`` rows seeing ``ring_rows`` ring positions each
    and ``own`` rows of their pass in all, all layers (scores and values)."""
    return float(model["n_layers"] * model["n_heads"] * 2 * model["head_dim"] * (rows * ring_rows + own))


def step_flops(rc: Mapping[str, Mapping[str, Any]], step: float, pairs_per_row: float) -> Dict[str, float]:
    """Forward operations of one lane's rollout step at step ``step`` of its
    episode, by part (all S + 1 passes)."""
    model, obs, act = _model(rc), rc["obs"], rc["actions"]
    E, H, U = model["unit_embed_dim"], model["hidden_dim"], obs["max_units"]
    S = model["diffusion_steps"]
    rows = rollout_rows(model)
    trunk = U * (obs["unit_features"] * E + E * E) + obs["global_features"] * E + (3 * E + model["hero_embed_dim"]) * H
    head_rows = 1 + SLOTS * S
    heads = head_rows * (H * (act["n_action_types"] + 2 * act["move_bins"] + act["max_abilities"] + E + 1) + U * E)
    w = core_weight_count(model)
    ring = ROWS * step
    return {
        "trunk": 2.0 * trunk, "heads": 2.0 * heads,
        "attn": 2.0 * (rows * w["attn"] + _attend_macs(model, rows, ring, own_visible(model, 1)["rollout"])),
        "router": 2.0 * rows * w["router"],
        "routed_experts": 2.0 * rows * 3 * H * model["expert_ffn_dim"] * pairs_per_row,
        "token_table": 2.0 * (rows - 1) * (sum(_head_sizes(act)) + 2) * H,
    }


def _head_sizes(act: Mapping[str, Any]):
    return (act["n_action_types"], act["move_bins"], act["move_bins"], act["max_units"], act["max_abilities"])


def learner_flops(rc: Mapping[str, Mapping[str, Any]], T: int, step: float, pairs_per_row: float) -> float:
    """Forward operations of one lane's learner pass over a chunk of T steps
    whose first step is at ``step`` of its episode."""
    model, obs, act = _model(rc), rc["obs"], rc["actions"]
    E, H, U = model["unit_embed_dim"], model["hidden_dim"], obs["max_units"]
    S = model["diffusion_steps"]
    N = learner_rows(model, T)
    trunk = (T + 1) * (U * (obs["unit_features"] * E + E * E) + obs["global_features"] * E + (3 * E + model["hero_embed_dim"]) * H)
    heads = (T + 1 + SLOTS * S * T) * (H * (act["n_action_types"] + 2 * act["move_bins"] + act["max_abilities"] + E + 1) + U * E)
    w = core_weight_count(model)
    ops = N * w["attn"] + _attend_macs(model, N, ROWS * step, own_visible(model, T)["learner"])
    ops += N * (w["router"] + 3 * H * model["expert_ffn_dim"] * pairs_per_row)
    ops += (N - (T + 1)) * (sum(_head_sizes(act)) + 2) * H + trunk + heads
    return 2.0 * ops


def train_flops_per_frame(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int,
    step: float, pairs_per_row: float,
) -> float:
    """Required forward and backward operations per TRAINED frame, whole
    policy, held experts only: the numerator of ``sdar_train_mfu``. The
    rollout decodes every lane of both teams; the learner passes its own
    lanes forward and backward (twice the forward's products)."""
    T = rollout_len
    rollout = sum(step_flops(rc, step, pairs_per_row).values()) * (lanes + opp_lanes) * T
    learner = 3.0 * learner_flops(rc, T, step, pairs_per_row) * lanes
    return (rollout + learner) / (lanes * T)


def block_attend_work(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int, step: float,
) -> Dict[str, float]:
    """One chip's ``core_block_attend`` work in one dispatch: operations of
    the products against the ring and the block, and bytes of the ring rows a
    query may see (keys and values) read once a rollout pass and twice in the
    learner (forward, backward); q, k, v and the outputs besides."""
    model = _model(rc)
    T, S, item = rollout_len, model["diffusion_steps"], _BYTES[model["dtype"]]
    kv, D, nh = model["n_kv_heads"], model["head_dim"], model["n_heads"]
    ring = ROWS * step                                   # positions of the episode before the step
    ring_row = model["n_layers"] * 2 * kv * D * item     # a position's keys and values, all layers
    own = own_visible(model, T)
    rollout_ops = 2.0 * _attend_macs(model, rollout_rows(model), ring, own["rollout"]) * (lanes + opp_lanes) * T
    learner_ops = 3 * 2.0 * _attend_macs(model, learner_rows(model, T), ring, own["learner"]) * lanes
    # a pass reads the visible ring rows (pass 1 before its own write, the rest one more)
    rollout_bytes = (S + 1) * (ring + 1) * ring_row * (lanes + opp_lanes) * T
    learner_bytes = 2 * ring * ring_row * lanes
    qkvo = model["n_layers"] * (2 * nh + 2 * kv) * D * item
    act_bytes = qkvo * (rollout_rows(model) * (lanes + opp_lanes) * T + 3 * learner_rows(model, T) * lanes)
    return {
        "flops": rollout_ops + learner_ops,
        "bytes": float(rollout_bytes + learner_bytes + act_bytes),
        "rollout_flops": rollout_ops, "learner_flops": learner_ops,
    }


def weight_bytes_per_dispatch(rc: Mapping[str, Mapping[str, Any]], rollout_len: int, shared_pass_share: float) -> float:
    """The core's weights in the compute type as one fused dispatch reads
    them: once a pass, S + 1 passes a rollout step, over both teams' rows in
    the share of dispatches whose teams share a pass
    (``league/shared_pass_dispatches_total`` over
    ``learner/dispatches_total``) and a pass a team otherwise; forward and the
    backward's two products in the update. Every held expert is read (the
    routed buffers are padded). No roofline is built on it: the compiler
    streams the weights under no policy scope (ROADMAP B3)."""
    model = _model(rc)
    passes = rollout_len * passes_per_step(model) * (2.0 - shared_pass_share) + 3.0
    return sum(core_weight_count(model).values()) * _BYTES[model["dtype"]] * passes
