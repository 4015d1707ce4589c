"""The LSTM core over a learner's chunk, with a backward written by hand.

``Policy.sequence`` used to run ``nn.OptimizedLSTMCell`` under ``nn.scan``
with the parameters broadcast. Autodiff of such a scan adds each step's
weight gradient to an accumulator INSIDE the backward loop: two
``[H, 4H]`` float32 arrays read and written once a step, which at
H = 4,096 was 14% of the fused step in products that multiply nothing
(PERF.md section 6, PR 29). ``lstm_sequence`` is the same mathematics with
the weight gradient taken out of the loop:

* **Forward.** The four input kernels, the four hidden kernels and the four
  biases are concatenated once (gate order i, f, g, o: the cell's) and cast
  to the compute type once. The input half has no recurrence, so
  ``X @ Wx`` is one product before the loop. A step is the cell's own
  expressions in the cell's own order and types, ``(h @ Wh + b) + (x @ Wx)``
  then the gates, after the reset ``mask_carry`` applies: step mode
  (``Policy.step``, which still calls the cell) and sequence mode stay one
  function of the same parameters.
* **Backward.** A reverse ``lax.scan`` carries ``(dc, dh)``, does the one
  product the recurrence needs (``dz @ Wh^T``) and EMITS the pre-activation
  cotangent ``dz``. After it, ``dWh`` and ``dWx`` are one product each,
  contracting over time and batch with float32 accumulation and float32
  output, ``db`` one float32 sum and ``dX`` one product. No accumulator
  lives in the loop. The gates' cotangents are formed in the carry's type
  (float32 for a learner's ``carry0``) and rounded to the compute type
  once, where autodiff rounded every factor.

What the forward keeps for the backward is what the backward reads: the
masked ``c`` and ``tanh(c')`` in the carry's type, the masked ``h`` and the
four activated gates in the compute type.

``resets`` is data (it comes from ``dones``): its cotangent is zero.
"""

from __future__ import annotations

from typing import Any, Mapping, Tuple

import jax
import jax.numpy as jnp

from dotaclient_tpu.models.policy import mask_carry

GATES = ("i", "f", "g", "o")

CoreParams = Mapping[str, Mapping[str, jnp.ndarray]]


def _concat_weights(core_params: CoreParams, dtype) -> Tuple[jnp.ndarray, ...]:
    """``nn.OptimizedLSTMCell``'s tree -> (Wx [H, 4H], Wh [H, 4H], b [4H])
    in the compute type."""
    wx = jnp.concatenate([core_params[f"i{g}"]["kernel"] for g in GATES], axis=-1)
    wh = jnp.concatenate([core_params[f"h{g}"]["kernel"] for g in GATES], axis=-1)
    b = jnp.concatenate([core_params[f"h{g}"]["bias"] for g in GATES], axis=-1)
    return wx.astype(dtype), wh.astype(dtype), b.astype(dtype)


def _forward(core_params, carry, x, keep, save: bool):
    """``x`` ``[T, B, H]`` in the compute type, ``keep`` ``[T, B]``. With
    ``save``, also what ``_bwd`` reads."""
    dtype = x.dtype
    wx, wh, b = _concat_weights(core_params, dtype)
    zx = jnp.einsum("tbh,hg->tbg", x, wx)                         # [T, B, 4H]

    def step(carry_t, inp):
        zx_t, keep_t = inp
        c, h = mask_carry(carry_t, keep_t)
        h = h.astype(dtype)
        z = (jnp.dot(h, wh) + b) + zx_t
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i, f, g, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jnp.tanh(g), jax.nn.sigmoid(o)
        new_c = f * c + i * g
        tanh_c = jnp.tanh(new_c)
        new_h = o * tanh_c
        saved = (c, h, jnp.concatenate([i, f, g, o], axis=-1), tanh_c) if save else None
        return (new_c, new_h), (new_h, saved)

    carry, (ys, saved) = jax.lax.scan(step, carry, (zx, keep))
    return (carry, ys), (wx, wh, saved)


@jax.custom_vjp
def _sequence(core_params, carry, x, keep):
    return _forward(core_params, carry, x, keep, save=False)[0]


def _fwd(core_params, carry, x, keep):
    out, (wx, wh, saved) = _forward(core_params, carry, x, keep, save=True)
    # the parameters ride along for their types alone: nothing is copied
    return out, (core_params, x, keep, wx, wh, saved)


def _bwd(res, cotangents):
    core_params, x, keep, wx, wh, (c_in, h_in, gates, tanh_c) = res
    d_carry, d_ys = cotangents

    def step(d_carry_t, inp):
        dc, dh = d_carry_t
        dy, c, act, th, keep_t = inp
        i, f, g, o = jnp.split(act.astype(dc.dtype), 4, axis=-1)
        dh = dh + dy
        dc = dc + dh * o * (1.0 - th * th)
        dz = jnp.concatenate(
            [
                dc * g * i * (1.0 - i),
                dc * c * f * (1.0 - f),
                dc * i * (1.0 - g * g),
                dh * th * o * (1.0 - o),
            ],
            axis=-1,
        ).astype(x.dtype)
        dh_in = jnp.einsum("bg,hg->bh", dz, wh, preferred_element_type=dh.dtype)
        return mask_carry((dc * f, dh_in), keep_t), dz

    d_carry0, dz = jax.lax.scan(
        step, d_carry, (d_ys, c_in, gates, tanh_c, keep), reverse=True
    )
    f32 = jnp.float32
    d_wx = jnp.einsum("tbh,tbg->hg", x, dz, preferred_element_type=f32)
    d_wh = jnp.einsum("tbh,tbg->hg", h_in, dz, preferred_element_type=f32)
    d_b = dz.astype(f32).sum(axis=(0, 1))
    d_x = jnp.einsum("tbg,hg->tbh", dz, wx, preferred_element_type=x.dtype)

    grads = {
        ("i", "kernel"): jnp.split(d_wx, 4, axis=-1),
        ("h", "kernel"): jnp.split(d_wh, 4, axis=-1),
        ("h", "bias"): jnp.split(d_b, 4, axis=-1),
    }

    def of(path, p):
        module, leaf = (k.key for k in path)                      # "hi", "kernel"
        return grads[module[0], leaf][GATES.index(module[1])].astype(p.dtype)

    d_params = jax.tree_util.tree_map_with_path(of, core_params)
    return d_params, d_carry0, d_x, jnp.zeros_like(keep)


_sequence.defvjp(_fwd, _bwd)


def lstm_sequence(
    core_params: CoreParams,
    carry: Tuple[jnp.ndarray, jnp.ndarray],
    x: jnp.ndarray,
    resets: jnp.ndarray,
    dtype: Any,
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """The cell of ``core_params`` (``nn.OptimizedLSTMCell``'s tree) over
    ``x`` ``[B, T, H]`` from ``carry`` ``(c, h)``, each ``[B, H]``, with the
    carry's rows zeroed BEFORE step t where ``resets[:, t]`` is 1. Products
    in ``dtype``. Returns the final carry and the outputs ``[B, T, H]``."""
    # time-major inside, so that no product has a transpose in it (written
    # "bth,hg->tbg", X @ Wx came out transposed and every step copied its slice)
    keep = 1.0 - jnp.moveaxis(resets, 1, 0)                       # [T, B]
    carry, ys = _sequence(core_params, carry, jnp.moveaxis(x, 1, 0).astype(dtype), keep)
    return carry, jnp.moveaxis(ys, 0, 1)
