"""Device-mesh construction and canonical shardings.

The reference's only learner-side parallelism was (at most) NCCL data-parallel
(SURVEY.md §2.3); here every distribution decision is a sharding annotation on
a `jax.sharding.Mesh` and XLA emits the collectives over ICI/DCN
(SURVEY.md §2.4, §5.8) — no hand-written communication.

Axes:
  * ``dcn``   — multi-slice axis: each index is one ICI-connected TPU slice;
    traffic over this axis rides the data-center network. Present only when
    ``dcn_slices > 1``.
  * ``data``  — batch dimension; gradients psum over it (and over ``dcn``
    when present — XLA lowers that to the hierarchical pattern:
    reduce-scatter/all-gather over ICI inside each slice, a slice-count
    all-reduce over DCN between them).
  * ``model`` — tensor-parallel axis for widened cores (unused at LSTM(128)
    scale but first-class per SURVEY.md §2.3). TP collectives must stay on
    ICI, so the model axis is always innermost (fastest-varying device
    order) and never crosses a slice boundary.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dotaclient_tpu.config import MeshConfig


def make_mesh(
    config: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build a (data, model) — or (dcn, data, model) — mesh over
    ``devices`` (default: all).

    Device order: JAX's ``jax.devices()`` enumerates multi-slice systems
    slice-major (all of slice 0, then slice 1, ...), so reshaping to
    ``(dcn, data, model)`` puts each slice's devices in one dcn index and
    keeps the model axis on ICI neighbors.

    An EXPLICIT layout (``data_parallel > 0``) smaller than the visible
    device set takes the first ``dcn×data×model`` devices: a 1-device mesh
    in an 8-device process is the degenerate case of the one sharded code
    path (`--mesh data_parallel=1`), not a separate fork — the parity
    tests in tests/test_multichip.py and tests/test_fused_multichip.py
    depend on both sizes coexisting in one process.
    """
    devices = list(devices if devices is not None else jax.devices())
    model = max(1, config.model_parallel)
    dcn = max(1, config.dcn_slices)
    data = config.data_parallel
    if data > 0 and dcn * data * model < len(devices):
        devices = devices[: dcn * data * model]
    if len(devices) % (model * dcn):
        raise ValueError(
            f"{len(devices)} devices not divisible by "
            f"dcn_slices×model_parallel={dcn}x{model}"
        )
    if data == -1:
        data = len(devices) // (model * dcn)
    if dcn * data * model != len(devices):
        raise ValueError(
            f"mesh {dcn}x{data}x{model} != {len(devices)} devices"
        )
    if dcn > 1:
        arr = np.asarray(devices).reshape(dcn, data, model)
        return Mesh(
            arr, (config.dcn_axis, config.data_axis, config.model_axis)
        )
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, (config.data_axis, config.model_axis))


def batch_axes(mesh: Mesh, config: MeshConfig) -> Tuple[str, ...]:
    """Mesh axes the batch dimension shards over: (dcn?, data)."""
    axes = []
    if config.dcn_axis in mesh.shape:
        axes.append(config.dcn_axis)
    axes.append(config.data_axis)
    return tuple(axes)


def batch_shard_count(mesh: Mesh, config: MeshConfig) -> int:
    """How many ways the batch dimension splits over this mesh — the
    divisibility unit for batch sizes, buffer capacity, and ingest-group
    padding. Shared by the learner and the trajectory buffer so their
    checks cannot drift."""
    n = 1
    for a in batch_axes(mesh, config):
        n *= mesh.shape[a]
    return n


def data_sharding(mesh: Mesh, config: MeshConfig) -> NamedSharding:
    """Batch-sharded over the (dcn×)data axes (leading dimension)."""
    return NamedSharding(mesh, P(batch_axes(mesh, config)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def row_sharding(mesh: Mesh, config: MeshConfig, n_rows: int) -> NamedSharding:
    """Leading-axis sharding for an ``n_rows``-row array: data-sharded when
    the rows split evenly over the batch shards, replicated otherwise.

    The single divisibility rule behind the lane-sharded actor state
    (actor.device_rollout.actor_state_sharding): a game/lane axis that
    divides the (dcn×)data shard count lives partitioned, anything else —
    true scalars, the sim's batch-wide PRNG key, degenerate tiny layouts —
    stays replicated rather than failing mid-compile."""
    n = batch_shard_count(mesh, config)
    if n_rows > 0 and n_rows % n == 0:
        return data_sharding(mesh, config)
    return replicated(mesh)
