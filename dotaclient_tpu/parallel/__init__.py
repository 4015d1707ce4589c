"""Parallelism library: meshes, shardings, and the TP/PP/SP primitives.

Coverage vs SURVEY.md §2.3: data parallelism (mesh + batch sharding, grad
psum), tensor parallelism (``sharding.state_shardings``), pipeline
parallelism (``pipeline.make_pipeline``), sequence parallelism
(``sequence``: ring + Ulysses attention), and expert parallelism
(``expert``: shard_map + all_to_all Switch dispatch; the GSPMD einsum form
lives in ``models.moe`` and shards via the ``"expert"`` path rule in
``sharding``). The reference has none of TP/PP/SP/EP (its core is an
LSTM(128) on one GPU); the rebuild ships them first-class per SURVEY.md §7
step 8.
"""

from dotaclient_tpu.parallel.distributed import (
    initialize_runtime,
    process_info,
)
from dotaclient_tpu.parallel.expert import make_expert_dispatch
from dotaclient_tpu.parallel.mesh import (
    batch_axes,
    batch_shard_count,
    data_sharding,
    make_mesh,
    replicated,
    row_sharding,
)
from dotaclient_tpu.parallel.pipeline import make_pipeline, stack_stage_params
from dotaclient_tpu.parallel.sequence import (
    make_ring_attention,
    make_ulysses_attention,
)
from dotaclient_tpu.parallel.sharding import param_spec, state_shardings

__all__ = [
    "batch_axes",
    "batch_shard_count",
    "data_sharding",
    "initialize_runtime",
    "make_expert_dispatch",
    "process_info",
    "make_mesh",
    "make_pipeline",
    "make_ring_attention",
    "make_ulysses_attention",
    "param_spec",
    "replicated",
    "row_sharding",
    "stack_stage_params",
    "state_shardings",
]
