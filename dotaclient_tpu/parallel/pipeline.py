"""Pipeline parallelism: GPipe-style microbatched stages over a mesh axis.

The reference has no PP — its core is a single LSTM(128) (SURVEY.md §2.3
row 4) — but the rebuild ships it as a first-class library primitive for
deep cores: the layer stack is split into S stages, one per device along the
``stage`` mesh axis; microbatches stream through the pipe with activations
hopped stage→stage by ``ppermute`` (ICI neighbor traffic, SURVEY.md §5.8 —
the collective is the only communication, emitted inside ``shard_map``).

Schedule: plain GPipe fill-and-drain — M microbatches take M + S - 1 ticks,
bubble fraction (S-1)/(M+S-1). Every device computes every tick (SPMD); the
masking is in which activations are kept, not in control flow.

Correctness contract (pinned by ``tests/test_parallel.py``): identical
output to applying the S stages sequentially on one device.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

StageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


def make_pipeline(
    stage_fn: StageFn,
    mesh: Mesh,
    axis: str,
    n_microbatches: int,
):
    """Build a jitted pipelined apply.

    ``stage_fn(stage_params, x) -> y`` must preserve ``x``'s shape (the
    classic homogeneous-stage regime). Returned callable:

        out = pipe(stacked_params, x)

    * ``stacked_params``: pytree whose leaves have a leading stage axis
      [S, ...] (stage s's params at index s);
    * ``x``: [B, ...] with B divisible by ``n_microbatches``;
    * ``out``: [B, ...] — stage S-1's outputs, replicated.
    """
    S = mesh.shape[axis]
    M = n_microbatches

    def _shard_body(params_local, x):            # params leaves [1, ...]; x [B,...]
        s = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params_local)
        xm = x.reshape((M, x.shape[0] // M) + x.shape[1:])   # [M, mb, ...]
        mb_shape = xm.shape[1:]

        perm_fwd = [(i, (i + 1) % S) for i in range(S)]
        # zero-constants are axis-invariant; the loop makes them varying —
        # pcast the initializers so the fori_loop carry types match
        out0 = jax.lax.pcast(jnp.zeros_like(xm), (axis,), to="varying")
        recv0 = jax.lax.pcast(
            jnp.zeros(mb_shape, x.dtype), (axis,), to="varying"
        )

        def tick(t, carry):
            recv, out = carry
            # stage 0 ingests microbatch t (when one remains); others take
            # the activation handed over by the previous stage
            fresh = xm[jnp.minimum(t, M - 1)]
            inp = jnp.where(s == 0, fresh, recv)
            act = stage_fn(params, inp)
            # my microbatch index this tick; valid while 0 <= t - s < M
            idx = t - s
            valid = (idx >= 0) & (idx < M)
            # last stage banks finished microbatches
            take = valid & (s == S - 1)
            out = jnp.where(
                take,
                out.at[jnp.clip(idx, 0, M - 1)].set(act),
                out,
            )
            # hand activations to the next stage (ring; stage S-1 -> 0 hop
            # is discarded by stage 0 reading fresh input)
            act = jnp.where(valid, act, jnp.zeros_like(act))
            recv = jax.lax.ppermute(act, axis, perm_fwd)
            return recv, out

        _, out = jax.lax.fori_loop(0, M + S - 1, tick, (recv0, out0))
        # outputs exist only on the last stage: replicate via psum of
        # one-hot contributions (correctness-first; a production variant
        # would keep them stage-sharded for the next pipelined consumer)
        out = jax.lax.psum(jnp.where(s == S - 1, out, jnp.zeros_like(out)), axis)
        return out.reshape(x.shape)

    wrapped = shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=(P(axis), P()),   # params stage-sharded, inputs replicated
        out_specs=P(),
    )
    return jax.jit(wrapped)


def stack_stage_params(params_list) -> Any:
    """[per-stage pytrees] → one pytree with a leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)
