"""A causal depthwise convolution of a few taps over a carried history.

What two resident cores share (``models/kimilinear.py``: the KDA layer's
convolution of q, k and v before its SiLU; ``models/lfm2moe.py``: the gated
short convolution that IS the mixer): a lane carries the last ``K - 1`` input
rows of a layer, a chunk of T rows is convolved against them and its own, no
tap crosses an episode's start, and a lane that stands at position 0 reads
its history as void, so a reset touches no leaf. A step is T = 1.
"""

from __future__ import annotations

import jax.numpy as jnp


def causal_conv(taps, history, x, carried, seg):
    """``out_t = sum_j taps[j] * in_{t-j}`` a channel, over the rows of
    the SAME episode: ``taps [K, C]`` float32, ``history [B, K - 1, C]`` (the
    rows before the chunk, oldest first, in the carry's type), ``x [B, T, C]``,
    ``carried [B]`` false where the lane's history is void (read and
    ignored), ``seg [B, T]`` the steps' episode segments (0 continues the
    carry's episode) -> (``y [B, T, C]`` float32, the new history: the last
    ``K - 1`` rows of the chunk's LAST episode, zero where that episode is
    shorter). One lane block's arrays: where a step serves several lane sets
    the caller goes through ``lanes.by_lane_block``."""
    K, T = taps.shape[0], x.shape[1]
    rows = jnp.concatenate([jnp.where(carried[:, None, None], history, 0), x], axis=1)
    row_seg = jnp.concatenate([jnp.zeros((x.shape[0], K - 1), seg.dtype), seg], axis=1)
    y = sum(
        taps[j] * jnp.where(
            (row_seg[:, K - 1 - j:K - 1 - j + T] == seg)[..., None],
            rows[:, K - 1 - j:K - 1 - j + T].astype(jnp.float32), 0.0,
        )
        for j in range(K)
    )
    # the rows a later step's taps may read: those of the chunk's last episode
    return y, jnp.where((row_seg[:, T:] == seg[:, -1:])[..., None], rows[:, T:], 0)
