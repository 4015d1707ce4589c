"""Operations and bytes of the policy with the LFM2 core, as functions of its
sizes: arithmetic on a configuration file's ``run_config`` and on things a
run counted, never a number from the program's own cost model. The
conventions are ``harness/flops_afmoe.py``'s.

One multiply-add is two operations; only products are counted (norms, the
convolution's K taps a channel, the two gates' element-wise products, RoPE
and the softmax are thousandths of the total). Forward, for one lane and one
step (H stream; nh query heads over kv KV heads of D; dense width Fd, expert
width Fe, E router outputs, Ns shared experts, 0 here):

  trunk, heads    as ``harness/flops.py`` counts them (this repo's own)
  conv layer      3 H H + H H              in_proj (B, C, x) and out_proj
  attention layer H nh D + 2 H kv D + nh D H          q, k, v and o
                  + nh K 2 D               scores and values against the K
                                           rows the query sees (K = p + 1 at
                                           position p: the layer attends fully)
  dense FFN       3 H Fd                   (the leading ``n_dense_layers``)
  expert layer    H E + 3 H Fe Ns          router (and a shared expert, if any)
                  + 3 H Fe a               a = token-expert pairs a token that
                                           land on an expert HELD here, counted
                                           by the program (``moe/local_assignments``)

One fused dispatch runs the policy as ``harness/flops.dispatch_passes``
says: forward for (L + Lo) T lane-steps in the rollout and L (T + 1) in the
learner, backward (twice the forward's products) for L T. The OPERATIONS do
not depend on how many passes carry those lane-steps; the weights' BYTES do:
``weight_passes`` counts a pass for what the program runs, one a rollout
step where both teams' rows ride in one pass
(``league/shared_pass_dispatches_total`` over ``learner/dispatches_total``),
two where the opponent is a frozen snapshot, and three for the update
(forward, and the backward's two products a weight). No roofline is built on
it: the compiler streams the weights under no policy scope (PERF.md section
7, ROADMAP B3), and ``core_conv_state`` is a handful of element-wise
operations the compiler fuses into its neighbours.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark.harness import flops, flops_afmoe

_BYTES = {"bfloat16": 2, "float32": 4}


def _model(rc: Mapping[str, Mapping[str, Any]]) -> Mapping[str, Any]:
    model = rc["model"]
    if model.get("core") != "lfm2moe":
        raise flops.UnsupportedShape(f"model.core is {model.get('core')!r}: these counts are the LFM2 core's")
    if model["dtype"] not in _BYTES:
        raise flops.UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
    return model


# per layer (attention?, dense FFN?): the afmoe core's plan of full layers and leading dense ones, "full" meaning attention
layer_kinds = flops_afmoe.layer_kinds


def n_conv(model: Mapping[str, Any]) -> int:
    return sum(1 for attn, _ in layer_kinds(model) if not attn)


def n_attn(model: Mapping[str, Any]) -> int:
    return sum(1 for attn, _ in layer_kinds(model) if attn)


def core_weight_count(model: Mapping[str, Any]) -> Dict[str, float]:
    """Parameters of the core's matrices held on this chip, by part."""
    H, nh, kv, D = model["hidden_dim"], model["n_heads"], model["n_kv_heads"], model["head_dim"]
    held = model["held_experts"] or model["moe_experts"]
    out = {"conv": 0.0, "attn": 0.0, "dense_ffn": 0.0, "router": 0.0, "shared": 0.0, "routed": 0.0}
    for attn, dense in layer_kinds(model):
        if attn:
            out["attn"] += 2 * H * nh * D + 2 * H * kv * D
        else:
            out["conv"] += 4 * H * H
        if dense:
            out["dense_ffn"] += 3 * H * model["dense_ffn_dim"]
        else:
            out["router"] += H * model["moe_experts"]
            out["shared"] += 3 * H * model["expert_ffn_dim"] * model["n_shared_experts"]
            out["routed"] += 3 * H * model["expert_ffn_dim"] * held
    return out


def carry_bytes_per_lane(model: Mapping[str, Any]) -> float:
    item = _BYTES[model["dtype"]]
    ring = model["full_context"] * 2 * model["n_kv_heads"] * model["head_dim"] * item
    history = (model["shortconv_taps"] - 1) * model["hidden_dim"] * item
    return float(8 + n_attn(model) * ring + n_conv(model) * history)


def attend_macs(model: Mapping[str, Any], position: float) -> float:
    """Multiply-adds against the rows a query at ``position`` sees, one lane-step, all attention layers."""
    return float(n_attn(model) * model["n_heads"] * (position + 1.0) * 2 * model["head_dim"])


def step_flops(rc: Mapping[str, Mapping[str, Any]], position: float, pairs_per_token: float) -> Dict[str, float]:
    """Forward operations of one lane for one step, by part. ``position`` is
    the step's position in its episode, ``pairs_per_token`` the token-expert
    pairs a token that land on a held expert, summed over the expert layers."""
    model, obs, act = _model(rc), rc["obs"], rc["actions"]
    E, H, U = model["unit_embed_dim"], model["hidden_dim"], obs["max_units"]
    trunk = U * (obs["unit_features"] * E + E * E) + obs["global_features"] * E + (3 * E + model["hero_embed_dim"]) * H
    heads = H * (act["n_action_types"] + 2 * act["move_bins"] + act["max_abilities"] + E + 1) + U * E
    w = core_weight_count(model)
    return {
        "trunk": 2.0 * trunk, "heads": 2.0 * heads,
        "conv": 2.0 * w["conv"],
        "attn": 2.0 * (w["attn"] + attend_macs(model, position)),
        "dense_ffn": 2.0 * w["dense_ffn"],
        "router_and_shared": 2.0 * (w["router"] + w["shared"]),
        "routed_experts": 2.0 * 3 * H * model["expert_ffn_dim"] * pairs_per_token,
    }


def train_flops_per_frame(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int,
    position: float, pairs_per_token: float,
) -> float:
    """Required forward and backward operations per TRAINED frame, whole
    policy, held experts only: the numerator of ``lfm2moe_train_mfu``."""
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    passes = p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"]
    return sum(step_flops(rc, position, pairs_per_token).values()) * passes / (lanes * rollout_len)


def weight_passes(rollout_len: int, shared_pass_share: float) -> float:
    """Times one fused dispatch reads the core's weights, as the program runs
    it: a rollout step is ONE pass over both teams' rows in a dispatch whose
    opponent is the learner's own parameters (``shared_pass_share`` of the
    dispatches: ``league/shared_pass_dispatches_total`` over
    ``learner/dispatches_total``) and two otherwise; the update reads them
    forward and twice backward."""
    return rollout_len * (2.0 - shared_pass_share) + 3.0


def weight_bytes_per_dispatch(
    rc: Mapping[str, Mapping[str, Any]], rollout_len: int, shared_pass_share: float, held_touched: float = 1.0,
) -> float:
    """The core's weights in the compute type times ``weight_passes``;
    ``held_touched`` is the share of the held experts a pass reads (a grouped
    product reads an expert only where a pair lands)."""
    model = _model(rc)
    w = core_weight_count(model)
    always = sum(v for k, v in w.items() if k != "routed")
    return (always + held_touched * w["routed"]) * _BYTES[model["dtype"]] * weight_passes(rollout_len, shared_pass_share)
