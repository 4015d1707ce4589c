"""Device tracing and debug-mode numerics checking.

SURVEY.md §5.1-5.2: the reference had wall-clock prints and TensorBoard
scalars only; the rebuild's observability is two complementary layers:

* **Pipeline telemetry** (``utils/telemetry.py`` — see the "Observability"
  section of docs/ARCHITECTURE.md): host-side per-stage spans, queue/
  staleness/occupancy gauges, and counters across
  actor→transport→buffer→learner, drained to console/JSONL/tensorboardX by
  ``MetricsLogger``. That layer answers *which stage* is slow or starved.
* **This module**: jax.profiler device traces viewable in TensorBoard
  (tensorboard-plugin-profile) and `checkify`-instrumented train steps for
  NaN/Inf hunting. This layer answers *why* a device stage is slow. The
  two meet in the trace: every telemetry span is a ``TraceAnnotation`` on
  the trace's host plane, and the fused step's phases are named scopes
  (``phase_rollout``, ``phase_update``, ``rollout_*``, ``update_*``,
  ``policy_*``) on its device plane.

A third layer joined in ISSUE 12: the pipeline TRACING plane
(``utils/tracing.py``, ``--trace-jsonl``) follows individual chunks and
weight versions ACROSS processes (hop timelines, critical-path and
staleness attribution via ``scripts/trace_report.py``) and wraps the jit
entry points with compile/retrace accounting. Spans say which stage,
tracing says which hop of which chunk, this module's profiler says why
the device program itself is slow.

Usage:
    with trace("runs/profile"):           # device trace of the block
        learner.train(100)

    python -m dotaclient_tpu.train.learner --profile-dir runs/profile
    python -m dotaclient_tpu.train.learner --checkify   # debug numerics
    python -m dotaclient_tpu.train.learner --metrics-jsonl run.jsonl  # spans
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def trace(logdir: Optional[str]) -> Iterator[None]:
    """jax.profiler device trace over the enclosed block (no-op when
    ``logdir`` is None). View: tensorboard --logdir <logdir>.

    The Python tracer is off, as in the benchmark's traced runs: every
    ``telemetry.Registry.span`` is an event of the trace's host plane, so
    the spans name the host's stretches, and Python frames make a trace of
    a real run too large to open."""
    if logdir is None:
        yield
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# The checkify-instrumented train step lives in train/ppo.py
# (make_train_step(debug_checkify=True)); named scopes are applied directly
# at the policy's phase boundaries (models/policy.py).
