"""Operations and bytes of the policy with the looped core, as functions of
its sizes: arithmetic on a configuration file's ``run_config`` and on the
lanes' position, never a number from the program's own cost model. The
conventions are ``harness/flops_afmoe.py``'s.

One multiply-add is two operations; only matrix multiplications are counted
(norms, RoPE, softmax and the exit gate's H products are thousandths of the
total). Forward, for one lane and one step (H stream, nh heads of D with as
many KV heads, dense width F, L layers run R times):

  trunk, heads    as ``harness/flops.py`` counts them (this repo's own); the
                  learner's pass puts the heads on every loop step's output
                  (the loss reads all R), the rollout's on the last
  attention       4 H nh D                  q, k, v and o
                  + 2 nh D K                scores and values over the K keys
                                            the query sees: ``p + 1`` at
                                            position p, every layer is full
  FFN             3 H F
  a loop step     L (attention + FFN);      the core: R loop steps

One fused dispatch runs the policy as ``harness/flops.dispatch_passes``
says: forward for (L_learner + L_opponent) T lane-steps in the rollout and
L_learner (T + 1) in the learner, backward (twice the forward's products)
for L_learner T.

Least bytes, for the roofline: what each pass must read and write once in
the compute type with nothing kept on the chip between passes OR BETWEEN
LOOP STEPS: the tied stack's weights are counted once a loop step a pass.
They are the same bytes four times, but 411 MB in bfloat16 at the published
widths is several times what a chip keeps outside HBM, and a loop step's
rings stream by in between: a program that reads them once a pass does not
exist, and so the least does not depend on how the loop is written. Of the
caches of the lanes it steps a pass reads the ``K`` rows a query may see in
each of the L R rings (a ring's other rows are read by the fixed-shape
program and masked: the program's cost, not the work's) and writes one row
to each; the backward pass reads weights and rows again and writes the
float32 weight gradient once (the sum over the R uses is one array).
Activations are hundredths of that and left out.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark.harness import flops

_BYTES = {"bfloat16": 2, "float32": 4}


def _model(rc: Mapping[str, Mapping[str, Any]]) -> Mapping[str, Any]:
    model = rc["model"]
    if model.get("core") != "looplm":
        raise flops.UnsupportedShape(f"model.core is {model.get('core')!r}: these counts are the looped core's")
    if model["dtype"] not in _BYTES:
        raise flops.UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
    return model


def stack_weight_count(model: Mapping[str, Any]) -> Dict[str, float]:
    """Parameters of the tied stack's matrices, by part: ONE set, whatever R."""
    H, nh, D = model["hidden_dim"], model["n_heads"], model["head_dim"]
    return {
        "attention": float(model["n_layers"] * 4 * H * nh * D),
        "ffn": float(model["n_layers"] * 3 * H * model["dense_ffn_dim"]),
    }


def _row_bytes(model: Mapping[str, Any]) -> int:
    """K and V of one position in one ring."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * _BYTES[model["dtype"]]


def rings(model: Mapping[str, Any]) -> int:
    return model["n_layers"] * model["loop_steps"]


def cache_bytes_per_lane(model: Mapping[str, Any]) -> float:
    return float(rings(model) * model["full_context"] * _row_bytes(model))


def seen_cache_bytes_per_lane(model: Mapping[str, Any], position: float) -> float:
    """K and V rows of one lane's rings that a query at ``position`` may see,
    every layer and loop step: the part of ``cache_bytes_per_lane`` a pass
    has to read."""
    return float(rings(model) * (position + 1.0) * _row_bytes(model))


def step_flops(rc: Mapping[str, Mapping[str, Any]], position: float, head_passes: float = 1.0) -> Dict[str, float]:
    """Forward operations of one lane for one step, by part. ``position`` is
    the step's position in its episode; ``head_passes`` how many loop steps'
    outputs the heads read (1 in the rollout, R in the learner)."""
    model, obs, act = _model(rc), rc["obs"], rc["actions"]
    E, H, U = model["unit_embed_dim"], model["hidden_dim"], obs["max_units"]
    trunk = U * (obs["unit_features"] * E + E * E) + obs["global_features"] * E + (3 * E + model["hero_embed_dim"]) * H
    heads = H * (act["n_action_types"] + 2 * act["move_bins"] + act["max_abilities"] + E + 1) + U * E
    w, R = stack_weight_count(model), model["loop_steps"]
    keys = 2 * model["n_heads"] * model["head_dim"] * (position + 1.0) * model["n_layers"]
    return {
        "trunk": 2.0 * trunk, "heads": 2.0 * heads * head_passes,
        "attention": 2.0 * R * (w["attention"] + keys), "ffn": 2.0 * R * w["ffn"],
    }


_CORE_PARTS = ("attention", "ffn")


def train_flops_per_frame(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int, position: float,
) -> float:
    """Required forward and backward operations per TRAINED frame, whole
    policy: the numerator of ``looplm_train_mfu``."""
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    rollout = sum(step_flops(rc, position).values())
    learner = sum(step_flops(rc, position, head_passes=_model(rc)["loop_steps"]).values())
    total = rollout * p["rollout_forward"] + learner * (p["learner_forward"] + 2.0 * p["learner_backward"])
    return total / (lanes * rollout_len)


def core_dispatch_work(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int, position: float,
) -> Dict[str, float]:
    """Operations and least bytes of every execution of the core in one
    fused dispatch: the numerator of ``looplm_core_roofline``."""
    model = _model(rc)
    b, T, R = _BYTES[model["dtype"]], rollout_len, model["loop_steps"]
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    parts = step_flops(rc, position)
    core = sum(parts[k] for k in _CORE_PARTS)
    ops = core * (p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"])
    weights = sum(stack_weight_count(model).values())
    a_pass = R * weights * b                                  # once a loop step a pass
    cache = seen_cache_bytes_per_lane(model, position)
    row = rings(model) * _row_bytes(model)                    # one step's K and V, every ring
    sides = [lanes] + ([opp_lanes] if opp_lanes else [])
    rollout = T * sum(a_pass + n * (cache + row) for n in sides)
    learner_forward = a_pass + lanes * cache
    learner_backward = a_pass + lanes * cache + weights * 4   # float32 weight gradient, one array
    return {
        "flops": ops, "bytes": rollout + learner_forward + learner_backward,
        "weight_bytes_a_pass": a_pass, "seen_cache_bytes_per_lane": cache,
        "cache_bytes_per_lane": cache_bytes_per_lane(model),
    }
