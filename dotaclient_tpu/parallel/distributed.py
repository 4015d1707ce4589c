"""Multi-host / multi-slice runtime initialization.

The reference's cross-machine story was NCCL (at most, inside one learner)
plus RabbitMQ between processes (SURVEY.md §2.4); the TPU-native backend is
the XLA runtime itself: every host in a slice (and every slice in a
multi-slice job) joins one JAX distributed system, after which
``jax.devices()`` spans the whole job, a ``(dcn, data, model)`` mesh from
``make_mesh`` covers it, and every collective — gradient psum over
ICI+DCN, TP all-gathers, ring-attention ppermutes — is emitted by XLA
against the global mesh with zero user communication code (SURVEY.md §5.8).

Usage, one call per host process before any other jax op:

    from dotaclient_tpu.parallel import initialize_runtime
    initialize_runtime()                      # TPU pods: all auto-detected
    initialize_runtime("10.0.0.1:1234", 4, 2) # explicit (e.g. CPU fleets)

The learner CLI wires this as ``--multihost`` (plus ``--dcn-slices`` for the
mesh): on GKE TPU node pools the coordinator/process count/process id are
discovered from the TPU metadata server, so the no-arg form suffices on
every host; non-TPU fleets pass the three explicit values.
"""

from __future__ import annotations

from typing import Optional

import jax


def initialize_runtime(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join (or create) the job-wide JAX distributed system.

    No-arg on TPU pods/GKE: everything is discovered from the TPU metadata
    environment. Explicit args serve CPU fleets and tests. Idempotent —
    calling twice (e.g. test re-entry) is a no-op rather than an error.
    """
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_info() -> dict:
    """This host's coordinates in the job: {process_index, process_count,
    local_devices, global_devices}."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
