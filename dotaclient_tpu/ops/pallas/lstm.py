"""Pallas fused LSTM-sequence kernel (the first custom-kernel candidate,
SURVEY.md §2.2 row 1 / §7 hard-part 1; the second, `kda_step.py`, is on the
Kimi-Linear core's rollout path since PR 36).

The DEFAULT core stays `nn.scan` (BASELINE.md "Pallas decision"); this
kernel is still called by no path in the package. It exists as the
alternative for *wider* cores, where keeping the weights pinned in VMEM
across all T steps should pay: one `pallas_call` runs the whole sequence,
double-reading nothing from HBM. Its speed against the scan: not measured
on chip in this round.

On the chip (TPU v5e, jax 0.9.0, libtpu 0.0.34; chip_smoke.py phase e, PR
21): Mosaic accepts the kernel as written — no grid, no `BlockSpec`, the
1-D reset row `r_ref[t]` reshaped to 2-D, `gates` sliced along lanes at
multiples of H — and it matches the reference at B=32, T=16 for H=128 and
H=512 (max abs error 3e-4 at default matmul precision, 5e-6 at "highest").
H=512 was at first refused for VMEM; see `_vmem_limit`.

Cell math (gate order i, f, g, o — pinned by `lstm_sequence_reference`,
the spec the kernel is tested against):

    gates = x_t @ Wx + h @ Wh + b
    c' = σ(f)·c + σ(i)·tanh(g);  h' = σ(o)·tanh(c')
    (h, c) ← (h', c') · (1 - reset_t)   applied BEFORE the step

Gradients: `custom_vjp` with a recompute backward — the forward runs the
kernel, the backward re-runs the reference under `jax.vjp` (rematerialized
BPTT; residuals are just the inputs). Numerics parity is tested in
interpreter mode on CPU (tests/test_pallas.py) and compiled on the TPU
(chip_smoke.py, phase e).

There is no dispatcher: the caller names `lstm_sequence_pallas` (with
`interpret=` explicit — `True` only where there is no TPU to compile for) or
`lstm_sequence_reference`. Nothing here looks at the backend, so a run can
never quietly get the scan when it asked for the kernel.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default scoped-VMEM limit on the v5e, and the most this kernel
# will ask for (the chip has 128 MiB; leave room for XLA's own fusions).
_VMEM_DEFAULT = 16 << 20
_VMEM_CEILING = 100 << 20


def _cell(x_t, h, c, wx, wh, b, reset_t):
    keep = (1.0 - reset_t)[:, None]
    h = h * keep
    c = c * keep
    gates = x_t @ wx + h @ wh + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h, c


def lstm_sequence_reference(
    x: jnp.ndarray,        # f32 [B, T, D]
    h0: jnp.ndarray,       # f32 [B, H]
    c0: jnp.ndarray,       # f32 [B, H]
    wx: jnp.ndarray,       # f32 [D, 4H]
    wh: jnp.ndarray,       # f32 [H, 4H]
    b: jnp.ndarray,        # f32 [4H]
    resets: jnp.ndarray,   # f32 [B, T]
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Spec implementation: plain lax.scan. Returns (hs [B, T, H], (hT, cT))."""

    def step(carry, inp):
        h, c = carry
        x_t, r_t = inp
        h, c = _cell(x_t, h, c, wx, wh, b, r_t)
        return (h, c), h

    (hT, cT), hs = jax.lax.scan(
        step, (h0, c0), (jnp.moveaxis(x, 1, 0), jnp.moveaxis(resets, 1, 0))
    )
    return jnp.moveaxis(hs, 0, 1), (hT, cT)


def _kernel(x_ref, h0_ref, c0_ref, wx_ref, wh_ref, b_ref, r_ref,
            hs_ref, hT_ref, cT_ref):
    """Whole-sequence LSTM in one kernel: weights live in VMEM for all T
    steps; time-major refs so the sequential loop indexes the leading axis."""
    T = x_ref.shape[0]
    wx = wx_ref[:]
    wh = wh_ref[:]
    b = b_ref[:]

    def body(t, carry):
        h, c = carry
        x_t = x_ref[t]
        r_t = r_ref[t]
        keep = (1.0 - r_t)[:, None]
        h = h * keep
        c = c * keep
        gates = (
            jnp.dot(x_t, wx, preferred_element_type=jnp.float32)
            + jnp.dot(h, wh, preferred_element_type=jnp.float32)
            + b[None, :]
        )
        H = h.shape[-1]
        i = gates[:, :H]
        f = gates[:, H:2 * H]
        g = gates[:, 2 * H:3 * H]
        o = gates[:, 3 * H:]
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        hs_ref[t] = h
        return h, c

    h, c = jax.lax.fori_loop(0, T, body, (h0_ref[:], c0_ref[:]))
    hT_ref[:] = h
    cT_ref[:] = c


def _vmem_limit(wx, wh) -> int:
    """Scoped-VMEM request, sized from the two weight matrices.

    Measured on the v5e (PR 21): whatever B and T are, Mosaic allocates
    twice the weights plus under 1 MiB — the VMEM buffers and the values
    the kernel hoists out of its loop — 16.30M at D=H=512 ("Scoped
    allocation with size 16.30M and limit 16.00M exceeded scoped vmem
    limit by 308.0K" at the default) and 64.55M at D=H=1024. Asking for
    exactly that sits on an edge: XLA also places some of the call's
    operands in VMEM, and at H=512 a 24.5 MiB limit failed (26.28M) where
    24 MiB and 32 MiB passed. So ask for four times the weights: 32 MiB at
    H=512, where every B (32-256), T (16, 32) and matmul precision tried
    compiled. H=1024 hits the ceiling; there B=32 at "highest" precision
    still fails (103.86M) and needs a grid over the gate columns, which
    this kernel does not have."""
    weights = (wx.size + wh.size) * wx.dtype.itemsize
    return max(_VMEM_DEFAULT, min(4 * weights, _VMEM_CEILING))


def _pallas_forward(x, h0, c0, wx, wh, b, resets, interpret):
    B, T, D = x.shape
    H = h0.shape[-1]
    x_tm = jnp.moveaxis(x, 1, 0)          # [T, B, D]
    r_tm = jnp.moveaxis(resets, 1, 0)     # [T, B]
    hs_tm, hT, cT = pl.pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(wx, wh)
        ),
        interpret=interpret,
    )(x_tm, h0, c0, wx, wh, b, r_tm)
    return jnp.moveaxis(hs_tm, 0, 1), (hT, cT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def lstm_sequence_pallas(x, h0, c0, wx, wh, b, resets, interpret=False):
    """Fused-kernel LSTM sequence; same contract as the reference."""
    return _pallas_forward(x, h0, c0, wx, wh, b, resets, interpret)


def _fwd(x, h0, c0, wx, wh, b, resets, interpret):
    out = _pallas_forward(x, h0, c0, wx, wh, b, resets, interpret)
    return out, (x, h0, c0, wx, wh, b, resets)


def _bwd(interpret, residuals, cotangents):
    # recompute-backward: BPTT through the reference implementation — the
    # kernel is forward-only, gradients rematerialize in XLA
    x, h0, c0, wx, wh, b, resets = residuals
    _, vjp = jax.vjp(
        lambda x_, h0_, c0_, wx_, wh_, b_: lstm_sequence_reference(
            x_, h0_, c0_, wx_, wh_, b_, resets
        ),
        x, h0, c0, wx, wh, b,
    )
    grads = vjp(cotangents)
    return (*grads, None)  # resets are not differentiated


lstm_sequence_pallas.defvjp(_fwd, _bwd)
