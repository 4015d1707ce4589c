"""Tracing utility tests (SURVEY.md §5.1 — the reference had only wall-clock
prints; the rebuild's device tracing must actually produce a trace)."""

import os

import jax
import jax.numpy as jnp

from dotaclient_tpu.utils.profiling import trace


class TestTrace:
    def test_noop_without_logdir(self):
        with trace(None):
            x = jax.jit(lambda a: a * 2)(jnp.ones((4,)))
        assert float(x.sum()) == 8.0

    def test_writes_profile_artifacts(self, tmp_path):
        logdir = str(tmp_path / "prof")
        with trace(logdir):
            jax.block_until_ready(jax.jit(lambda a: a @ a)(jnp.ones((8, 8))))
        found = [
            os.path.join(root, f)
            for root, _dirs, files in os.walk(logdir)
            for f in files
        ]
        # the TensorBoard profile plugin layout: plugins/profile/<run>/...
        assert found, "trace() produced no files"
        assert any("plugins" in p and "profile" in p for p in found)

    def test_trace_holds_the_programs_spans_and_no_python_frames(self, tmp_path):
        """ISSUE 24: a `--profile-dir` trace names the host's stretches by
        the telemetry spans; the Python tracer is off (its frames made a
        trace of a real run too large to open, and this test a minute
        long)."""
        import glob

        from dotaclient_tpu.utils import telemetry

        logdir = str(tmp_path / "prof")
        with trace(logdir):
            with telemetry.Registry().span("learner/iteration", step=9):
                jax.block_until_ready(jax.jit(lambda a: a + 1)(jnp.ones((4,))))
        (path,) = glob.glob(
            os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
        )
        data = jax.profiler.ProfileData.from_file(path)
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        names = [ev.name for line in host.lines for ev in line.events]
        assert names.count("learner/iteration") == 1
        # a Python frame's event is named "<file>:<line> <function>"
        assert not any(".py:" in n for n in names), [
            n for n in names if ".py:" in n
        ][:5]

    def test_trace_closes_on_exception(self, tmp_path):
        logdir = str(tmp_path / "prof2")
        try:
            with trace(logdir):
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        # a second trace must start cleanly (stop_trace ran in finally)
        with trace(str(tmp_path / "prof3")):
            jax.block_until_ready(jnp.ones((2,)) + 1)
