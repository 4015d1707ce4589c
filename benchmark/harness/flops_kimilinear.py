"""Operations and bytes of the policy with the Kimi-Linear core, as functions
of its sizes: arithmetic on a configuration file's ``run_config`` and on two
things a run counted, never a number from the program's own cost model. The
conventions are ``harness/flops_afmoe.py``'s.

One multiply-add is two operations; only products are counted (norms, the
convolution's K taps, softmax, the gates' element-wise arithmetic and the
decay are thousandths of the total). Forward, for one lane and one step (H
stream, nh heads; KDA: d = d_k = d_v, W = nh d; MLA: latent C, shared key
part r, head widths n = qk_nope, v; dense width Fd, expert width Fe, E
router outputs, Ns shared experts):

  trunk, heads    as ``harness/flops.py`` counts them (this repo's own)
  KDA layer       4 H W                    q, k, v and o
                  + 2 (H d + d W) + H nh   the two rank-d gates and beta
                  + 3 nh d^2               the recurrence in its ONE-STEP form:
                                           k^T (alpha S), the rank-one update,
                                           the readout S^T q. The learner's
                                           closed form over a chunk makes other
                                           products (pair sums, a triangular
                                           inverse, [T, d] x [d, d]); what the
                                           mathematics requires is the
                                           recurrence, so that is what counts
  MLA layer       H nh (n + r) + H (C + r) + nh v H      q, the latent row, o
                  + nh n C + nh C v        absorbing W_uk into the query and
                                           W_uv after the softmax
                  + nh K (2 C + r)         scores over the row's C + r columns
                                           and values over its C, against the K
                                           rows the query sees (K = p + 1 at
                                           position p: the layer attends fully).
                                           The program's value product runs
                                           over all C + r columns (no slice of
                                           the ring): 12% more, not required
  dense FFN       3 H Fd                   (the leading ``n_dense_layers``)
  expert layer    H E + 3 H Fe Ns          router and shared expert
                  + 3 H Fe a               a = token-expert pairs a token that
                                           land on an expert HELD here, counted
                                           by the program (``moe/local_assignments``)

One fused dispatch runs the policy as ``harness/flops.dispatch_passes``
says: forward for (L + Lo) T lane-steps in the rollout and L (T + 1) in the
learner, backward (twice the forward's products) for L T.

Two rooflines, each over a scope that holds NO weight streaming, so that
neither depends on how often a pass reads the weights:

``kda_state_work`` (scope ``core_kda_state``): the recurrence's 3 nh d^2
products a lane-step-layer, and least bytes: a rollout step reads and writes
each lane-layer's float32 state once (nothing can keep 80 x 4 x 2 MiB on the
chip between steps); the update reads each learner lane-layer's START state
once forward and once backward (the closed form needs no state in between,
and the end state is not used); and every pass reads q, k, v, the decay
(float32, [W] each), beta and writes o, the backward pass those again and
their gradients.

``latent_attend_work`` (scope ``core_latent_attend``): nh K (2 C + r)
products a lane-step, and least bytes: of the ring the K rows a query may
see, once a pass (a rollout step reads them once for both products; the
learner's pass reads them once for its T + 1 queries, the backward pass
again), the query rows in and the attended rows out.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

from benchmark.harness import flops, flops_afmoe

_BYTES = {"bfloat16": 2, "float32": 4}


def _model(rc: Mapping[str, Mapping[str, Any]]) -> Mapping[str, Any]:
    model = rc["model"]
    if model.get("core") != "kimilinear":
        raise flops.UnsupportedShape(f"model.core is {model.get('core')!r}: these counts are the Kimi-Linear core's")
    if model["dtype"] not in _BYTES:
        raise flops.UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
    return model


# per layer (MLA?, dense FFN?): the afmoe core's plan of full layers and leading dense ones, "full" meaning MLA
layer_kinds = flops_afmoe.layer_kinds


def n_kda(model: Mapping[str, Any]) -> int:
    return sum(1 for mla, _ in layer_kinds(model) if not mla)


def n_mla(model: Mapping[str, Any]) -> int:
    return sum(1 for mla, _ in layer_kinds(model) if mla)


def core_weight_count(model: Mapping[str, Any]) -> Dict[str, float]:
    """Parameters of the core's matrices held on this chip, by part."""
    H, nh, d = model["hidden_dim"], model["n_heads"], model["kda_head_dim"]
    W, C, r = nh * d, model["kv_lora_rank"], model["qk_rope_head_dim"]
    n, v = model["qk_nope_head_dim"], model["v_head_dim"]
    held = model["held_experts"] or model["moe_experts"]
    out = {"kda": 0.0, "mla": 0.0, "dense_ffn": 0.0, "router": 0.0, "shared": 0.0, "routed": 0.0}
    for mla, dense in layer_kinds(model):
        if mla:
            out["mla"] += H * nh * (n + r) + H * (C + r) + nh * v * H + nh * C * (n + v)
        else:
            out["kda"] += 4 * H * W + 2 * (H * d + d * W) + H * nh
        if dense:
            out["dense_ffn"] += 3 * H * model["dense_ffn_dim"]
        else:
            out["router"] += H * model["moe_experts"]
            out["shared"] += 3 * H * model["expert_ffn_dim"] * model["n_shared_experts"]
            out["routed"] += 3 * H * model["expert_ffn_dim"] * held
    return out


def state_bytes_per_lane_layer(model: Mapping[str, Any]) -> int:
    """One KDA layer's matrix states of one lane: float32 whatever the compute type."""
    return model["n_heads"] * model["kda_head_dim"] ** 2 * 4


def latent_row_bytes(model: Mapping[str, Any]) -> int:
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * _BYTES[model["dtype"]]


def carry_bytes_per_lane(model: Mapping[str, Any]) -> float:
    history = (model["kda_conv_kernel"] - 1) * 3 * model["n_heads"] * model["kda_head_dim"] * _BYTES[model["dtype"]]
    return float(
        8 + n_kda(model) * (state_bytes_per_lane_layer(model) + history)
        + n_mla(model) * model["full_context"] * latent_row_bytes(model)
    )


def recurrence_macs(model: Mapping[str, Any]) -> float:
    """Multiply-adds of the one-step recurrence and readout, one lane-step, all KDA layers."""
    return float(n_kda(model) * 3 * model["n_heads"] * model["kda_head_dim"] ** 2)


def attend_macs(model: Mapping[str, Any], position: float) -> float:
    """Multiply-adds against the latent rows a query at ``position`` sees, one lane-step, all MLA layers."""
    C, r = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return float(n_mla(model) * model["n_heads"] * (position + 1.0) * (2 * C + r))


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
        "kda": 2.0 * (w["kda"] + recurrence_macs(model)),
        "mla": 2.0 * (w["mla"] + attend_macs(model, position)),
        "dense_ffn": 2.0 * w["dense_ffn"],
        "router_and_shared": 2.0 * (w["router"] + w["shared"]),
        "routed_experts": 2.0 * 3 * H * model["expert_ffn_dim"] * pairs_per_token,
    }


def _passes(lanes: int, opp_lanes: int, rollout_len: int) -> float:
    """Forward-pass equivalents of lane-steps in one dispatch (a backward step counts twice)."""
    p = flops.dispatch_passes(lanes, opp_lanes, rollout_len)
    return p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"]


def train_flops_per_frame(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int,
    position: float, pairs_per_token: float,
) -> float:
    """Required forward and backward operations per TRAINED frame, whole
    policy, held experts only: the numerator of ``kimilinear_train_mfu``."""
    per_step = sum(step_flops(rc, position, pairs_per_token).values())
    return per_step * _passes(lanes, opp_lanes, rollout_len) / (lanes * rollout_len)


def kda_state_work(rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int) -> Dict[str, float]:
    """Operations and least bytes of every execution of the recurrence and its
    readout in one fused dispatch: the numerator of ``kda_state_roofline``."""
    model, T = _model(rc), rollout_len
    p = flops.dispatch_passes(lanes, opp_lanes, T)
    layers, state = n_kda(model), state_bytes_per_lane_layer(model)
    W, nh = model["n_heads"] * model["kda_head_dim"], model["n_heads"]
    io = (5 * W + nh) * 4                     # q, k, v, the decay in and o out, beta: float32, one lane-step-layer
    states = layers * state * (2.0 * p["rollout_forward"] + 2.0 * lanes)
    rows = layers * io * (p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"])
    return {
        "flops": 2.0 * recurrence_macs(model) * _passes(lanes, opp_lanes, T),
        "bytes": states + rows, "state_bytes": states, "row_bytes": rows,
        "state_bytes_per_lane_layer": float(state),
    }


def latent_attend_work(
    rc: Mapping[str, Mapping[str, Any]], lanes: int, opp_lanes: int, rollout_len: int, position: float,
) -> Dict[str, float]:
    """Operations and least bytes of every execution of the products against
    the latent ring in one fused dispatch: the numerator of ``latent_attend_roofline``."""
    model, T = _model(rc), rollout_len
    p = flops.dispatch_passes(lanes, opp_lanes, T)
    seen = n_mla(model) * (position + 1.0) * latent_row_bytes(model)          # one lane's visible rows
    width = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    io = n_mla(model) * model["n_heads"] * width * (_BYTES[model["dtype"]] + 4)    # the query rows in, the attended rows out
    ring = seen * (p["rollout_forward"] + 2.0 * lanes)      # once a rollout step a lane; once forward, once backward a learner lane
    rows = io * (p["rollout_forward"] + p["learner_forward"] + 2.0 * p["learner_backward"])
    return {
        "flops": 2.0 * attend_macs(model, position) * _passes(lanes, opp_lanes, T),
        "bytes": ring + rows, "ring_bytes": ring, "row_bytes": rows,
        "seen_ring_bytes_per_lane": seen,
    }
