"""Plain reference of the policy: trunk, LSTM cell and heads in float32.

Written from the equations, in straightforward ``jax.numpy``, importing
nothing from ``dotaclient_tpu.models``. Every matrix multiplication runs
under ``jax.default_matmul_precision("highest")``: on a TPU a float32
product is otherwise computed in bfloat16 passes. It is the reference for
both configurations (they differ in ``hidden_dim`` only) and for the
inference policy (no value head: ``value`` is then zeros, as the program
returns).

The parameters are the program's own tree (Flax names), so the same seeded
weights go through both:

  unit_encoder/Dense_0, Dense_1   per-unit MLP, ReLU after each
  globals_proj, trunk_proj        dense + ReLU
  hero_embed/embedding            table lookup
  core/{ii,if,ig,io}/kernel       input kernels of the four gates, no bias
  core/{hi,hf,hg,ho}/{kernel,bias}  hidden kernels, with bias
  head_action_type, head_move_x, head_move_y, head_ability, head_value
  target_query                    query of the dot-product target head

Equations (one lane, one step; x the trunk output, (c, h) the carry):

  e_u   = relu(relu(units_u W0 + b0) W1 + b1) * mask_u
  mean  = sum_u e_u / max(sum_u mask_u, 1)
  mx    = max over valid u of e_u, 0 where no unit is valid
  x     = relu([mean, mx, relu(globals Wg + bg), embed[hero]] Wt + bt)
  i, f, o = sigmoid(x Wi* + h Wh* + bh*);  g = tanh(x Wig + h Whg + bhg)
  c'    = f c + i g;      h' = o tanh(c')
  logits: dense heads of h'; target_u = (h' Wq + bq) . e_u / sqrt(E)
  value = h' Wv + bv

In sequence mode the carry is zeroed before step t > 0 whenever step t-1
ended an episode (``dones[:, t-1]``); step 0 starts from the given carry.

Departure from the published models, noted once here: Berner et al.'s unit
processing, pooling and action heads are not reproduced; both
configurations use this repo's trunk and heads, and only the core's width
is the published one.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

PRECISION = "highest"
HEADS = ("action_type", "move_x", "move_y", "ability")


def _dense(p: Mapping[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    y = jnp.matmul(x, p["kernel"].astype(jnp.float32))
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def trunk(p: Mapping[str, Any], obs: Mapping[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """obs with any leading axes -> (core input [..., H], unit embeddings
    [..., U, E])."""
    units = obs["units"].astype(jnp.float32)
    mask = obs["unit_mask"].astype(jnp.float32)[..., None]      # [..., U, 1]
    enc = p["unit_encoder"]
    e = jax.nn.relu(_dense(enc["Dense_1"], jax.nn.relu(_dense(enc["Dense_0"], units))))
    e = e * mask
    n = mask.sum(axis=-2)                                        # [..., 1]
    mean = e.sum(axis=-2) / jnp.maximum(n, 1.0)
    mx = jnp.where(mask > 0, e, -jnp.inf).max(axis=-2)
    mx = jnp.where(n > 0, mx, 0.0)
    g = jax.nn.relu(_dense(p["globals_proj"], obs["globals"].astype(jnp.float32)))
    hero = p["hero_embed"]["embedding"].astype(jnp.float32)[obs["hero_id"]]
    x = jnp.concatenate([mean, mx, g, hero], axis=-1)
    return jax.nn.relu(_dense(p["trunk_proj"], x)), e


def lstm_cell(p: Mapping[str, Any], carry: Tuple[jnp.ndarray, jnp.ndarray], x: jnp.ndarray):
    c, h = carry

    def gate(name: str) -> jnp.ndarray:
        return _dense(p["i" + name], x) + _dense(p["h" + name], h)

    i, f, o = (jax.nn.sigmoid(gate(k)) for k in ("i", "f", "o"))
    g = jnp.tanh(gate("g"))
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return (c2, h2), h2


def heads(p: Mapping[str, Any], y: jnp.ndarray, e: jnp.ndarray):
    q = _dense(p["target_query"], y)
    target = jnp.einsum("...e,...ue->...u", q, e) / jnp.sqrt(
        jnp.asarray(q.shape[-1], jnp.float32)
    )
    logits = {name: _dense(p[f"head_{name}"], y) for name in HEADS}
    logits["target_unit"] = target
    if "head_value" in p:
        value = _dense(p["head_value"], y)[..., 0]
    else:
        value = jnp.zeros(y.shape[:-1], jnp.float32)
    return logits, value


def step(params: Mapping[str, Any], obs: Mapping[str, jnp.ndarray], carry):
    """One batched step: obs ``[B, ...]`` -> (logits, value, carry)."""
    p = params["params"]
    carry = jax.tree.map(lambda t: t.astype(jnp.float32), carry)
    with jax.default_matmul_precision(PRECISION):
        x, e = trunk(p, obs)
        carry, y = lstm_cell(p["core"], carry, x)
        logits, value = heads(p, y, e)
    return logits, value, carry


def sequence(
    params: Mapping[str, Any],
    obs: Mapping[str, jnp.ndarray],
    carry,
    dones: Optional[jnp.ndarray] = None,
):
    """Teacher-forced sequence: obs ``[B, T, ...]`` -> (logits, values,
    final carry), with the mid-chunk resets described above. A plain Python
    loop over the steps: T is small and nothing here needs to be fast."""
    p = params["params"]
    carry = jax.tree.map(lambda t: t.astype(jnp.float32), carry)
    with jax.default_matmul_precision(PRECISION):
        x, e = trunk(p, obs)                                     # [B, T, H]
        ys = []
        for t in range(x.shape[1]):
            if t > 0 and dones is not None:
                keep = 1.0 - dones[:, t - 1].astype(jnp.float32)
                carry = jax.tree.map(lambda c: c * keep[:, None], carry)
            carry, y = lstm_cell(p["core"], carry, x[:, t])
            ys.append(y)
        logits, value = heads(p, jnp.stack(ys, axis=1), e)
    return logits, value, carry


def max_abs_diff(a: Dict[str, jnp.ndarray], b: Dict[str, jnp.ndarray]) -> float:
    """Largest absolute difference over the leaves of two matching trees
    (NaN if any leaf holds one: ``jnp.max`` hands a NaN on)."""
    worst = [
        jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ]
    return float(jnp.max(jnp.stack(worst)))
