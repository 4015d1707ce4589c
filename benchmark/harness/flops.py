"""Operations and bytes of the policy as functions of its shapes, and the
table of peaks.

Everything here is arithmetic on sizes read from a configuration file; no
number comes from the program or from XLA's cost analysis. One multiply-add
counts as two operations. Only matrix multiplications are counted (the
element-wise gate arithmetic, the pools and the softmaxes are some
thousandths of the total at either width).

The policy (``dotaclient_tpu/models/policy.py``), per lane and timestep:

  unit encoder   U x (F x E + E x E)        two dense layers per unit slot
  globals        G x E
  trunk          (3E + hero_embed) x H      mean pool, max pool, globals, hero
  core           (H + H) x 4H               one LSTM cell: input and hidden
                                            kernels to the four gates
  heads          H x (types + 2 x bins + abilities + E + 1) + U x E
                                            four dense heads, the target
                                            query, the value, the target dot

One fused dispatch (``train/fused.py``) runs the policy

  rollout        (L + Lo) x T      forward, learner and opponent lanes
  learner        L x (T + 1)       forward, teacher-forced with the bootstrap
                                   observation
                 L x T x 2         backward (weight and input gradients); the
                                   bootstrap step's output feeds only a
                                   stop-gradient value and needs none

and trains L x T frames. Recomputation, if the compiler chooses any, is not
counted: these are the operations the algorithm requires.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Mapping

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    """A device kind with no row in ``peaks.json``: an error, not a default."""


class UnsupportedShape(ValueError):
    """A configuration these counts or peaks do not cover: a core that is
    not the LSTM, or products in a type with no peak in the device's row. A
    new core or type brings counts, a peak and readers of its own; it does
    not inherit the LSTM's numbers under the LSTM's metric names."""


_BYTES = {"bfloat16": 2, "float32": 4}
_PEAK_KEY = {"bfloat16": "bf16_flops_per_s"}    # a type's peak, as peaks.json names it


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {_PEAKS_FILE} "
            f"(has: {sorted(table)}); add a row with its source"
        )
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class PolicyShape:
    """The sizes the counts depend on (``run_config`` of a configuration
    file: ``model``, ``obs`` and ``actions``)."""

    hidden: int          # H   model.hidden_dim
    unit_embed: int      # E   model.unit_embed_dim
    hero_embed: int      #     model.hero_embed_dim
    max_units: int       # U   obs.max_units
    unit_features: int   # F   obs.unit_features
    global_features: int  # G  obs.global_features
    action_types: int
    move_bins: int
    abilities: int
    dtype: str           # model.dtype: the type activations and weights are multiplied in

    @property
    def compute_bytes(self) -> int:
        return _BYTES[self.dtype]

    @classmethod
    def from_run_config(cls, rc: Mapping[str, Mapping[str, Any]]) -> "PolicyShape":
        model, obs, act = rc["model"], rc["obs"], rc["actions"]
        if model.get("core") != "lstm":
            raise UnsupportedShape(
                f"model.core is {model.get('core')!r}: the counts in "
                f"harness/flops.py are for the LSTM core only"
            )
        if model["dtype"] not in _BYTES:
            raise UnsupportedShape(f"model.dtype {model['dtype']!r} has no size here")
        return cls(
            hidden=model["hidden_dim"], unit_embed=model["unit_embed_dim"],
            hero_embed=model["hero_embed_dim"], max_units=obs["max_units"],
            unit_features=obs["unit_features"],
            global_features=obs["global_features"],
            action_types=act["n_action_types"], move_bins=act["move_bins"],
            abilities=act["max_abilities"], dtype=model["dtype"],
        )


def step_flops(s: PolicyShape) -> Dict[str, float]:
    """Forward operations of one lane for one timestep, by part."""
    E, H, U = s.unit_embed, s.hidden, s.max_units
    trunk = (
        U * (s.unit_features * E + E * E)
        + s.global_features * E
        + (3 * E + s.hero_embed) * H
    )
    core = (H + H) * 4 * H
    heads = (
        H * (s.action_types + 2 * s.move_bins + s.abilities + E + 1) + U * E
    )
    return {"trunk": 2.0 * trunk, "core": 2.0 * core, "heads": 2.0 * heads}


def dispatch_passes(lanes: int, opp_lanes: int, rollout_len: int) -> Dict[str, float]:
    """How many lane-timesteps of each kind one fused dispatch runs."""
    T = rollout_len
    return {
        "rollout_forward": float((lanes + opp_lanes) * T),
        "learner_forward": float(lanes * (T + 1)),
        "learner_backward": float(lanes * T),
    }


def train_flops_per_frame(
    s: PolicyShape, lanes: int, opp_lanes: int, rollout_len: int
) -> float:
    """Forward and backward matrix-multiplication operations per TRAINED
    frame, whole policy: the numerator of ``train_mfu``."""
    p = dispatch_passes(lanes, opp_lanes, rollout_len)
    per_step = sum(step_flops(s).values())
    total = per_step * (
        p["rollout_forward"] + p["learner_forward"]
        + 2.0 * p["learner_backward"]
    )
    return total / (lanes * rollout_len)


def core_dispatch_work(
    s: PolicyShape, lanes: int, opp_lanes: int, rollout_len: int
) -> Dict[str, float]:
    """Operations and least bytes of every execution of the LSTM core in one
    fused dispatch: the numerator of ``policy_core_roofline``.

    Bytes, per scan step over ``n`` lanes, in the compute type (``b`` bytes):
    the two kernels (8 H^2) are read once; a forward step reads x, h and c
    and writes h and c (5 n H); a learner forward step also writes the four
    gates for the backward pass (4 n H); a backward step reads the kernels,
    the saved gates and x, h, c (4 n H + 3 n H) and writes the three
    gradients (3 n H). The weight gradient (8 H^2, float32) is written once
    a dispatch. Anything kept in on-chip memory between steps would lower
    this, so it is a ceiling on the least traffic, stated as such.
    """
    H, b, T = s.hidden, s.compute_bytes, rollout_len
    kernels = 8 * H * H * b
    core = step_flops(s)["core"]
    p = dispatch_passes(lanes, opp_lanes, rollout_len)
    flops = core * (
        p["rollout_forward"] + p["learner_forward"]
        + 2.0 * p["learner_backward"]
    )

    def fwd(n: int, save_gates: bool) -> float:
        return kernels + n * H * b * (5 + (4 if save_gates else 0))

    def bwd(n: int) -> float:
        return kernels + n * H * b * (4 + 3 + 3)

    rollout_steps = T * (fwd(lanes, False) + (fwd(opp_lanes, False) if opp_lanes else 0.0))
    learner_steps = (T + 1) * fwd(lanes, True) + T * bwd(lanes)
    weight_grad = 8 * H * H * 4
    return {"flops": flops, "bytes": rollout_steps + learner_steps + weight_grad}


def peak_flops_per_s(peaks: Mapping[str, Any], dtype: str) -> float:
    """The device's peak for products in ``dtype``; an error where its row
    has none (float32 products are not held against the bfloat16 peak)."""
    key = _PEAK_KEY.get(dtype)
    if key not in peaks:
        raise UnsupportedShape(
            f"no peak FLOP/s for products in {dtype!r} in {_PEAKS_FILE} "
            f"({peaks.get('chip')}): add it to the row with its source"
        )
    return peaks[key]


def roofline_seconds(
    work: Mapping[str, float], peaks: Mapping[str, Any], dtype: str
) -> Dict[str, Any]:
    """The least time one chip could take for ``work`` multiplied in
    ``dtype``: the larger of operations over peak FLOP/s and bytes over peak
    bytes/s, and which."""
    t_flops = work["flops"] / peak_flops_per_s(peaks, dtype)
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "compute" if t_flops >= t_bytes else "memory",
        "compute_s": t_flops,
        "memory_s": t_bytes,
    }
