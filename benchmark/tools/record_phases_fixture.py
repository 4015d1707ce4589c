"""Record ``benchmark/tests/data/tpu_v5e_1chip_phases.xplane.pb``: three
dispatches of a tiny fused learner on one chip, with the program's scopes
on the device plane and its spans on the host plane.

    chiprun -- python3 benchmark/tools/record_phases_fixture.py chiprun_out/phases

Writes ``<out>/tpu_v5e_1chip_phases.xplane.pb`` and ``<out>/metrics.jsonl``
(the program's own record of the same run). The learner is the program's
(``Learner(cfg, actor="fused")``, 8 games of 1v1 against league opponents,
2-step rollouts, a log boundary every second step); what the benchmark's
runner adds in a cell is added here too: two warm-up dispatches, a drained
device at both ends of the window, ``bench:traced_window`` around it, the
Python tracer off. The written file is the profiler's, reduced to what
``harness/trace.py`` and ``tools/host_spans.py`` read of it (the device's
``XLA Ops`` and ``XLA Modules`` lines with each operation's short name and
``tf_op``; of ``/host:CPU`` the threads that ran a span of the program or of
the benchmark, with each event's ``step``), so that it stays small enough to
keep beside the tests: an operation's full HLO text and its ``source`` are
most of the profiler's file.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import trace, xplane  # noqa: E402
from benchmark.tools import host_spans  # noqa: E402

NAME = "tpu_v5e_1chip_phases.xplane.pb"
KEPT_LINES = (trace.OPS_LINE, trace.MODULES_LINE)
KEPT_STATS = (trace.SCOPE_STAT, "step")


def _is_span(name):
    return name.startswith(trace.SPAN_PREFIX) or bool(host_spans.PROGRAM_SPAN.match(name))


def reduce_space(space):
    """A copy of ``space`` with only what the harness reads. Built field by
    field: a parsed message keeps the fields it does not know and would
    write them out again."""
    out = type(space)()
    for plane in space.planes:
        device = plane.name.startswith(trace.DEVICE_PLANE)
        if not device and plane.name != trace.HOST_PLANE:
            continue
        names = xplane.stat_names(plane)
        kept_stats = {i for i, n in names.items() if n in KEPT_STATS}
        meta = xplane.event_metadata(plane)
        new = out.planes.add(name=plane.name)
        used = set()
        for line in plane.lines:
            if device and line.name not in KEPT_LINES:
                continue
            if not device and not any(
                _is_span(meta.get(ev.metadata_id, {}).get("name", "")) for ev in line.events
            ):
                continue                    # a runtime thread: no span on it
            nl = new.lines.add(name=line.name, timestamp_ns=line.timestamp_ns)
            for ev in line.events:
                ne = nl.events.add(
                    metadata_id=ev.metadata_id, offset_ps=ev.offset_ps,
                    duration_ps=ev.duration_ps,
                )
                used.add(ev.metadata_id)
                for st in ev.stats:
                    if st.metadata_id in kept_stats:
                        ne.stats.add().CopyFrom(st)
        refs = set()
        for entry in plane.event_metadata:
            if entry.key not in used:
                continue
            md = entry.value
            nm = new.event_metadata.add(key=entry.key).value
            nm.id, nm.display_name = md.id, md.display_name
            if not (device and md.display_name):     # there the name is the whole HLO text
                nm.name = md.name
            for st in md.stats:
                if st.metadata_id in kept_stats:
                    nm.stats.add().CopyFrom(st)
                    refs.add(st.ref_value)
        for entry in plane.stat_metadata:
            if entry.key in kept_stats or entry.key in refs:
                new.stat_metadata.add(key=entry.key).value.CopyFrom(entry.value)
    return out


def main(argv=None) -> int:
    out_dir = (argv or sys.argv[1:])[0]
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_phases_fixture: needs a TPU; a CPU trace has no device plane")

    from dotaclient_tpu.config import default_config
    from dotaclient_tpu.train.learner import Learner
    from dotaclient_tpu.utils import compile_cache

    compile_cache.enable()
    os.makedirs(out_dir, exist_ok=True)
    jsonl = os.path.join(out_dir, "metrics.jsonl")
    if os.path.exists(jsonl):
        os.unlink(jsonl)
    cfg = default_config()
    cfg = dataclasses.replace(
        cfg,
        ppo=dataclasses.replace(cfg.ppo, rollout_len=2, batch_rollouts=8),
        env=dataclasses.replace(
            cfg.env, n_envs=8, team_size=1, opponent="league", max_dota_time=60.0,
        ),
        league=dataclasses.replace(
            cfg.league, enabled=True, snapshot_every=1, selfplay_prob=0.0,
        ),
        mesh=dataclasses.replace(cfg.mesh, data_parallel=-1),
        log_every=2,
    )
    learner = Learner(cfg, actor="fused", seed=0, metrics_jsonl=jsonl)
    learner.train(2)                                    # compile, fill the pool
    jax.block_until_ready((learner.state, learner.device_actor.state))

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:traced_window"):
        learner.train(3)
        jax.block_until_ready((learner.state, learner.device_actor.state))
    jax.profiler.stop_trace()

    found = trace.find_xplane(trace_dir)
    reduced = reduce_space(xplane.read(found))
    path = os.path.join(out_dir, NAME)
    with open(path, "wb") as f:
        f.write(reduced.SerializeToString())
    print(
        f"record_phases_fixture: {os.path.getsize(found)} -> "
        f"{os.path.getsize(path)} bytes at {path}"
    )
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
