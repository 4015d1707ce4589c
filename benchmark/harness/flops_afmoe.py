"""Operations and bytes of the policy with the afmoe core, as functions of
its sizes: arithmetic on a configuration file's ``run_config`` and on two
things a run counted, never a number from the program's own cost model.

One multiply-add is two operations; only matrix multiplications are counted
(norms, RoPE, softmax and the gates are thousandths of the total). Forward,
for one lane and one step (H stream, nh query and kv KV heads of D, dense
width Fd, expert width Fe, E router outputs, Ns shared experts):

  trunk, heads    as ``harness/flops.py`` counts them (this repo's own)
  attention       H (2 nh D + 2 kv D) + nh D H        q, gate, k, v and o
                  + 2 nh D K                          scores and values over
                                                      the K keys the query sees
  dense FFN       3 H Fd                 (the leading ``n_dense_layers``)
  expert layer    H E + 3 H Fe Ns        router and shared expert
                  + 3 H Fe a             a = token-expert pairs a token that
                                         land on an expert HELD here

``a`` is counted by the program (``moe/local_assignments``: the pairs one
learner pass computed on this chip, over all expert layers), so only the
held experts' products count, as the cell's ``why`` says. ``K`` follows from
the lanes' positions: ``min(p + 1, context_window)`` in a window layer,
``p + 1`` in a full one; absent experts, masked ring slots and a token's
products through a held expert it did not choose (the program computes
them, weighted 0: ``models/afmoe.py``) are not required operations and are
not counted.

One fused dispatch runs the policy as ``harness/flops.dispatch_passes``
says: forward for (L + Lo) T lane-steps in the rollout and L (T + 1) in the
learner, backward (twice the forward's products) for L T.

Least bytes, for the roofline: what each pass must read and write once in
the compute type with nothing kept on the chip between passes: the core's
weights, of the caches of the lanes it steps the ``K`` rows a query may see
(the same ``K`` as the operations count: a ring's other rows are read by the
fixed-shape program and masked, which is the program's cost and not the
work's), the rows it writes to them, and in the backward pass the weights
again, those rows again and the float32 weight gradient. Activations are two
hundredths of that and left out, which makes the least time a little less
and the share a little lower.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark.harness import flops

_BYTES = {"bfloat16": 2, "float32": 4}


def _model(rc: Mapping[str, Mapping[str, Any]]) -> Mapping[str, Any]:
    model = rc["model"]
    if model.get("core") != "afmoe":
        raise flops.UnsupportedShape(f"model.core is {model.get('core')!r}: these counts are the afmoe core's")
    if model["dtype"] not in _BYTES:
        raise flops.UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
    return model


def layer_kinds(model: Mapping[str, Any]):
    """Per layer (full attention?, dense FFN?), as the program lays them."""
    return [
        (
            l >= model["n_dense_layers"]
            and (l + 1 + model["global_attn_offset"]) % model["global_attn_every"] == 0,
            l < model["n_dense_layers"],
        )
        for l in range(model["n_layers"])
    ]


def ring_len(model: Mapping[str, Any], full: bool) -> int:
    return model["full_context"] if full else model["context_window"] + model["rollout_chunk"]


def core_weight_count(model: Mapping[str, Any]) -> Dict[str, float]:
    """Parameters of the core's matrices held on this chip, by part."""
    H, D = model["hidden_dim"], model["head_dim"]
    nh, kv = model["n_heads"], model["n_kv_heads"]
    held = model["held_experts"] or model["moe_experts"]
    out = {"attention": 0.0, "dense_ffn": 0.0, "router": 0.0, "shared": 0.0, "routed": 0.0}
    for _, dense in layer_kinds(model):
        out["attention"] += H * (2 * nh * D + 2 * kv * D) + nh * D * H
        if dense:
            out["dense_ffn"] += 3 * H * model["dense_ffn_dim"]
        else:
            out["router"] += H * model["moe_experts"]
            out["shared"] += 3 * H * model["expert_ffn_dim"] * model["n_shared_experts"]
            out["routed"] += 3 * H * model["expert_ffn_dim"] * held
    return out


def cache_bytes_per_lane(model: Mapping[str, Any]) -> float:
    width = 2 * model["n_kv_heads"] * model["head_dim"] * _BYTES[model["dtype"]]
    return float(sum(ring_len(model, full) * width for full, _ in layer_kinds(model)))


def seen_cache_bytes_per_lane(model: Mapping[str, Any], position: float) -> float:
    """K and V rows of one lane's caches that a query at ``position`` may
    see, all layers: the part of ``cache_bytes_per_lane`` a pass has to read."""
    width = 2 * model["n_kv_heads"] * model["head_dim"] * _BYTES[model["dtype"]]
    keys = seen_keys(model, position)
    return float(sum(keys["full" if full else "window"] * width for full, _ in layer_kinds(model)))


def seen_keys(model: Mapping[str, Any], position: float) -> Dict[str, float]:
    """Keys a query at ``position`` of its episode sees, by kind of layer."""
    return {
        "window": min(position + 1.0, float(model["context_window"])),
        "full": position + 1.0,
    }


def step_flops(rc: Mapping[str, Mapping[str, Any]], position: float, pairs_per_token: float) -> Dict[str, float]:
    """Forward operations of one lane for one step, by part. ``position`` is
    the step's position in its episode, ``pairs_per_token`` the token-expert
    pairs a token that land on a held expert, summed over the expert layers."""
    model, obs, act = _model(rc), rc["obs"], rc["actions"]
    E, H, U = model["unit_embed_dim"], model["hidden_dim"], obs["max_units"]
    trunk = U * (obs["unit_features"] * E + E * E) + obs["global_features"] * E + (3 * E + model["hero_embed_dim"]) * H
    heads = H * (act["n_action_types"] + 2 * act["move_bins"] + act["max_abilities"] + E + 1) + U * E
    weights = core_weight_count(model)
    keys = seen_keys(model, position)
    nh, D = model["n_heads"], model["head_dim"]
    attention = weights["attention"] + sum(
        2 * nh * D * keys["full" if full else "window"] for full, _ in layer_kinds(model)
    )
    routed = 3 * H * model["expert_ffn_dim"] * pairs_per_token
    return {
        "trunk": 2.0 * trunk, "heads": 2.0 * heads, "attention": 2.0 * attention,
        "dense_ffn": 2.0 * weights["dense_ffn"],
        "router_and_shared": 2.0 * (weights["router"] + weights["shared"]),
        "routed_experts": 2.0 * routed,
    }


_CORE_PARTS = ("attention", "dense_ffn", "router_and_shared", "routed_experts")


def train_flops_per_frame(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int,
    position: float, pairs_per_token: float,
) -> float:
    """Required forward and backward operations per TRAINED frame, whole
    policy, held experts only: the numerator of ``afmoe_train_mfu``."""
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    per_step = sum(step_flops(rc, position, pairs_per_token).values())
    total = per_step * (p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"])
    return total / (lanes * rollout_len)


def core_dispatch_work(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int,
    position: float, pairs_per_token: float,
) -> Dict[str, float]:
    """Operations and least bytes of every execution of the core in one
    fused dispatch: the numerator of ``afmoe_core_roofline``."""
    model = _model(rc)
    b, T = _BYTES[model["dtype"]], rollout_len
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    parts = step_flops(rc, position, pairs_per_token)
    core = sum(parts[k] for k in _CORE_PARTS)
    ops = core * (p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"])
    weights = sum(core_weight_count(model).values())
    cache = seen_cache_bytes_per_lane(model, position)
    row = 2 * model["n_kv_heads"] * model["head_dim"] * b * model["n_layers"]   # one step's K and V
    sides = [lanes] + ([opp_lanes] if opp_lanes else [])
    rollout = T * sum(weights * b + n * (cache + row) for n in sides)
    learner_forward = weights * b + lanes * cache
    learner_backward = weights * b + lanes * cache + weights * 4               # float32 weight gradient
    return {
        "flops": ops, "bytes": rollout + learner_forward + learner_backward,
        "weight_bytes": weights * b, "seen_cache_bytes_per_lane": cache,
        "cache_bytes_per_lane": cache_bytes_per_lane(model),
    }
