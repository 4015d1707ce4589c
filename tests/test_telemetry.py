"""Telemetry-core + pipeline-wiring tests (ISSUE 1: unified telemetry).

Covers the registry primitives (counter/gauge/timer semantics, span
nesting), the JSONL sink round-trip, MetricsLogger's graceful degrade
without tensorboardX, the learner smoke run's staleness/queue-depth
gauges, the documented JSONL schema (via scripts/check_telemetry_schema),
and the sync discipline: telemetry must add ZERO host↔device syncs to the
train loop (device fetches happen only at log_every boundaries).
"""

import dataclasses
import importlib.util
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from dotaclient_tpu.config import RunConfig
from dotaclient_tpu.utils import telemetry
from dotaclient_tpu.utils.metrics import MetricsLogger


def tiny_config(**over) -> RunConfig:
    cfg = RunConfig()
    return dataclasses.replace(
        cfg,
        env=dataclasses.replace(cfg.env, n_envs=2, max_dota_time=30.0),
        ppo=dataclasses.replace(cfg.ppo, rollout_len=8, batch_rollouts=8),
        buffer=dataclasses.replace(cfg.buffer, capacity_rollouts=32, min_fill=8),
        checkpoint_every=10_000,
        **over,
    )


class TestRegistry:
    def test_counter_semantics(self):
        r = telemetry.Registry()
        r.counter("x").inc()
        r.counter("x").inc(2.5)
        assert r.snapshot()["x"] == pytest.approx(3.5)
        # create-or-get: same object by name
        assert r.counter("x") is r.counter("x")

    def test_gauge_last_write_wins(self):
        r = telemetry.Registry()
        r.gauge("g").set(1.0)
        r.gauge("g").set(7.0)
        assert r.snapshot()["g"] == 7.0

    def test_timer_stats(self):
        r = telemetry.Registry()
        t = r.timer("t")
        t.observe(0.1)
        t.observe(0.3)
        snap = r.snapshot()
        assert snap["t/count"] == 2
        assert snap["t/total_s"] == pytest.approx(0.4)
        assert snap["t/last_s"] == pytest.approx(0.3)
        assert snap["t/mean_s"] == pytest.approx(0.2)
        # EMA moves toward the last observation
        assert 0.1 < snap["t/ema_s"] < 0.3
        # approximate histogram quantile: within its 2x bucket bound
        assert 0.15 <= snap["t/p95_s"] <= 0.8

    def test_timer_time_contextmanager(self):
        r = telemetry.Registry()
        with r.timer("slept").time():
            time.sleep(0.01)
        assert r.snapshot()["slept/last_s"] >= 0.01

    def test_span_records_and_nests(self):
        r = telemetry.Registry()
        with r.span("outer"):
            time.sleep(0.002)
            with r.span("inner"):
                time.sleep(0.002)
        snap = r.snapshot()
        assert snap["span/outer/count"] == 1
        assert snap["span/outer/inner/count"] == 1
        # the outer span encloses the inner one
        assert snap["span/outer/last_s"] >= snap["span/outer/inner/last_s"]

    def test_span_nesting_depth_three(self):
        """Regression: stack entries are full names — joining the whole
        stack once duplicated prefixes ('span/a/a/b/c') at depth >= 3."""
        r = telemetry.Registry()
        with r.span("a"):
            with r.span("b"):
                with r.span("c"):
                    pass
        snap = r.snapshot()
        assert snap["span/a/b/c/count"] == 1
        assert "span/a/a/b/c/count" not in snap

    def test_span_absolute_names_do_not_nest(self):
        """Documented pipeline stages ('x/y' names) keep stable keys no
        matter which enclosing span is active."""
        r = telemetry.Registry()
        with r.span("learner/step"):
            with r.span("buffer/sample"):
                pass
        snap = r.snapshot()
        assert "span/buffer/sample/count" in snap
        assert "span/learner/step/buffer/sample/count" not in snap

    def test_span_stack_unwinds_on_exception(self):
        r = telemetry.Registry()
        with pytest.raises(RuntimeError):
            with r.span("boom"):
                raise RuntimeError()
        with r.span("after"):
            pass
        snap = r.snapshot()
        assert snap["span/boom/count"] == 1
        assert "span/after/count" in snap          # not nested under "boom"
        assert "span/boom/after/count" not in snap

    def test_span_is_a_profiler_event_under_a_session(self, tmp_path):
        """With a profiler session on, every span is an event of the host
        plane under its FULL name, with the attributes it was given, inside
        its parent's interval on the same thread; the timer's key never
        holds the attributes."""
        import glob
        import threading

        r = telemetry.Registry()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with r.span("learner/boundary", step=7):
                with r.span("gauges"):
                    time.sleep(0.002)
            def fetch():
                with r.span("snapshot/stats_fetch"):
                    pass

            other = threading.Thread(target=fetch)
            other.start()
            other.join()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        data = jax.profiler.ProfileData.from_file(path)
        (host,) = [p for p in data.planes if p.name == "/host:CPU"]
        found = {}
        for line_no, line in enumerate(host.lines):
            for ev in line.events:
                if ev.name.startswith(("learner/", "snapshot/")):
                    found[ev.name] = (
                        line_no, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats),
                    )
        assert set(found) == {
            "learner/boundary", "learner/boundary/gauges",
            "snapshot/stats_fetch",
        }
        p_line, p0, p1, p_stats = found["learner/boundary"]
        c_line, c0, c1, c_stats = found["learner/boundary/gauges"]
        assert p_stats == {"step": 7} and c_stats == {}
        assert c_line == p_line and p0 <= c0 < c1 <= p1
        assert c1 - c0 >= 2e6                       # the sleep, in ns
        assert found["snapshot/stats_fetch"][0] != p_line   # its own thread
        snap = r.snapshot()
        assert snap["span/learner/boundary/count"] == 1
        assert snap["span/learner/boundary/gauges/count"] == 1
        assert not any("step" in k for k in snap)

    def test_span_with_attributes_records_its_timer_with_no_session(self):
        r = telemetry.Registry()
        with r.span("learner/iteration", step=3):
            with r.span("learner/dispatch"):
                time.sleep(0.002)
        snap = r.snapshot()
        assert snap["span/learner/iteration/count"] == 1
        assert snap["span/learner/dispatch/total_s"] >= 0.002
        assert snap["span/learner/iteration/total_s"] >= snap["span/learner/dispatch/total_s"]

    def test_importing_telemetry_imports_no_jax(self):
        """jax-free tools load the module by path, keep the timers and get
        no annotation: a span must never be what imports JAX."""
        import subprocess

        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('t', {telemetry.__file__!r})\n"
            "t = importlib.util.module_from_spec(spec); spec.loader.exec_module(t)\n"
            "r = t.Registry()\n"
            "with r.span('a/b', step=1): pass\n"
            "assert r.snapshot()['span/a/b/count'] == 1\n"
            "assert t._trace_annotation() is None\n"
            "assert not any(m == 'jax' or m.startswith('jax.') for m in sys.modules)\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True, timeout=60)

    def test_clear(self):
        r = telemetry.Registry()
        r.counter("c").inc()
        r.clear()
        assert r.snapshot() == {}


class TestSinks:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        r = telemetry.Registry()
        r.gauge("depth").set(3.0)
        with r.span("stage/one"):
            pass
        logger = MetricsLogger(console=False, jsonl=path, registry=r)
        logger.log(1, {"loss": 0.25})
        logger.log(2, {"loss": float("nan")})
        logger.close()

        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 2
        for ln in lines:
            assert isinstance(ln["ts"], float)
            assert isinstance(ln["step"], int)
            assert isinstance(ln["scalars"], dict)
        assert lines[0]["step"] == 1
        assert lines[0]["scalars"]["loss"] == 0.25
        assert lines[0]["scalars"]["depth"] == 3.0
        assert lines[0]["scalars"]["span/stage/one/count"] == 1
        # non-finite values must not corrupt the stream: encoded as null
        assert lines[1]["scalars"]["loss"] is None

    def test_console_elides_telemetry_keys(self, capsys):
        r = telemetry.Registry()
        r.gauge("transport/queue_depth").set(5.0)
        logger = MetricsLogger(console=True, registry=r)
        logger.log(3, {"loss": 0.5})
        out = capsys.readouterr().out
        assert "loss=0.5" in out
        assert "queue_depth" not in out   # slashed keys are file-sink-only

    def test_log_returns_merged_dict(self):
        r = telemetry.Registry()
        r.gauge("buffer/occupancy").set(9.0)
        logger = MetricsLogger(console=False, registry=r)
        flat = logger.log(0, {"loss": 1.0})
        assert flat["loss"] == 1.0
        assert flat["buffer/occupancy"] == 9.0

    def test_metrics_logger_degrades_without_tensorboardx(self, monkeypatch, capsys):
        """logdir=... must warn and continue when tensorboardX is missing —
        never crash the run (ISSUE 1 satellite)."""
        monkeypatch.setitem(sys.modules, "tensorboardX", None)
        r = telemetry.Registry()
        logger = MetricsLogger(logdir="/tmp/never_created_tb", console=False, registry=r)
        assert "tensorboardX not installed" in capsys.readouterr().out
        logger.log(1, {"loss": 0.1})   # still works through remaining sinks
        logger.close()


class TestLearnerTelemetry:
    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~40s on the reference container
    def test_smoke_run_emits_pipeline_gauges_and_spans(self, tmp_path):
        """The acceptance contract: a tiny run's drained scalars carry the
        staleness/queue-depth/occupancy gauges, and the JSONL record carries
        per-stage span timings for every pipeline layer."""
        from dotaclient_tpu.train.learner import Learner

        path = str(tmp_path / "telemetry.jsonl")
        learner = Learner(
            tiny_config(log_every=1), metrics_jsonl=path
        )  # vec actor (host pool): staleness accounting does real work
        learner.train(2)

        scalars = learner._last_metrics
        assert "actor/weight_staleness" in scalars
        assert "transport/queue_depth" in scalars
        assert "buffer/occupancy" in scalars

        lines = [json.loads(l) for l in open(path)]
        assert lines, "no JSONL lines emitted"
        union = {}
        for ln in lines:
            union.update(ln["scalars"])
        for key in (
            "span/actor/step/mean_s",
            "span/actor/infer/mean_s",
            "span/buffer/insert/mean_s",
            "span/buffer/sample/mean_s",
            "span/learner/consume/mean_s",
            "span/learner/dispatch/mean_s",
            "span/learner/metrics_fetch/mean_s",
            "span/transport/publish_weights/mean_s",
            "actor/weight_refresh_lag",
            "buffer/batch_staleness",
            "actor/frames_shipped",
            "actor/rollouts_shipped",
        ):
            assert key in union, f"missing telemetry key {key}"
        # dispatch timings are real (the train step ran)
        assert union["span/learner/dispatch/count"] >= 2

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~175s on the reference container
    def test_no_added_device_syncs_in_train_loop(self, monkeypatch):
        """Telemetry must not break the sync discipline: with no log
        boundary in range, the number of device fetches is INDEPENDENT of
        how many optimizer steps run (fetches happen only at log_every
        boundaries and at end-of-run drain)."""
        from dotaclient_tpu.train.learner import Learner

        learner = Learner(tiny_config(log_every=100_000), actor="device")
        learner.train(1)   # compile + warm the pipeline

        calls = {"n": 0}
        real_device_get = jax.device_get

        def counting_device_get(x):
            calls["n"] += 1
            return real_device_get(x)

        monkeypatch.setattr(jax, "device_get", counting_device_get)
        learner.train(2)
        first = calls["n"]
        calls["n"] = 0
        learner.train(6)
        second = calls["n"]
        assert first == second, (
            f"device fetches scale with steps ({first} vs {second}) — "
            f"something inside the train loop is syncing"
        )

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~143s on the reference container
    def test_fetches_only_at_log_boundaries(self, monkeypatch):
        """With log_every=1 every step is a boundary: fetch count grows by
        exactly the per-boundary cost, pinning fetches TO the boundaries.
        Pinned on the SYNC snapshot path (--sync-snapshots): the async
        engine coalesces boundary jobs when it falls behind, so its fetch
        count is deliberately not per-boundary-deterministic —
        tests/test_snapshot.py covers that mode (the train thread performs
        no boundary fetches at all there)."""
        from dotaclient_tpu.config import LearnerConfig
        from dotaclient_tpu.train.learner import Learner

        learner = Learner(
            tiny_config(
                log_every=1, learner=LearnerConfig(async_snapshots=False)
            ),
            actor="device",
        )
        learner.train(1)

        calls = {"n": 0}
        real_device_get = jax.device_get

        def counting_device_get(x):
            calls["n"] += 1
            return real_device_get(x)

        monkeypatch.setattr(jax, "device_get", counting_device_get)
        learner.train(2)
        base = calls["n"]
        calls["n"] = 0
        learner.train(4)
        assert calls["n"] - base == 2 * 2, (
            "each extra optimizer step at log_every=1 should cost exactly "
            "two fetches (metrics dict + stats drain)"
        )


class TestHostSyncGuard:
    @pytest.fixture()
    def guard(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "check_host_sync",
            os.path.join(root, "scripts", "check_host_sync.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_hot_path_modules_are_clean(self, guard, capsys):
        """The CI tripwire end-to-end: the learner and buffer hot paths
        carry no unannotated host↔device sync patterns (ISSUE 2 satellite:
        the dispatch-only discipline cannot silently regress)."""
        assert guard.main([]) == 0
        assert "host-sync discipline OK" in capsys.readouterr().out

    def test_flags_unannotated_sync_patterns(self, guard):
        src = (
            "def hot(m):\n"
            "    a = float(m['loss'])\n"
            "    b = np.asarray(m['x'])\n"
            "    c = jax.device_get(m)\n"
            "    d = m['y'].item()\n"
            "    m['z'].block_until_ready()\n"
            "    return a, b, c, d\n"
        )
        violations = guard.check_source(src, set(), "x.py")
        assert len(violations) == 5
        assert any("float()" in v for v in violations)
        assert any(".item()" in v for v in violations)

    def test_annotation_and_allowlist_suppress(self, guard):
        src = (
            "def boundary(m):\n"
            "    return float(m)\n"
            "def hot(m):\n"
            "    # host-sync-ok: host integer\n"
            "    return float(m)\n"
        )
        assert guard.check_source(src, {"boundary"}, "x.py") == []
        # ... but only for the named function / annotated line
        assert len(guard.check_source(src, set(), "x.py")) == 1

    def test_closures_get_own_identity(self, guard):
        """A sync inside a closure of an allowed function is still flagged:
        the innermost named def is the unit of allowance."""
        src = (
            "def train():\n"
            "    def after_step(m):\n"
            "        return float(m)\n"
            "    return after_step\n"
        )
        violations = guard.check_source(src, {"train"}, "x.py")
        assert len(violations) == 1 and "after_step" in violations[0]


class TestSchemaChecker:
    @pytest.fixture()
    def checker(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "check_telemetry_schema",
            os.path.join(root, "scripts", "check_telemetry_schema.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_rejects_malformed_lines(self, checker):
        errors = checker.validate_lines(["not json"])
        assert errors and "not valid JSON" in errors[0]
        errors = checker.validate_lines(['{"ts": 1.0, "scalars": {}}'])
        assert any("step" in e for e in errors)
        errors = checker.validate_lines(
            ['{"ts": 1.0, "step": 0, "scalars": {"x": "oops"}}']
        )
        assert any("'x'" in e for e in errors)

    def test_rejects_missing_required_keys(self, checker):
        errors = checker.validate_lines(['{"ts": 1.0, "step": 0, "scalars": {}}'])
        assert any("required telemetry keys" in e for e in errors)

    def test_transport_keys_required_only_on_request(self, checker):
        """ISSUE 3: the socket/shm transport metrics are a separate
        requirement tier — absent from a smoke (in-proc) run's contract,
        enforced via extra_required for socket/shm runs — and the servers
        eager-create every one of them, so a real transport run always
        carries the full set."""
        base = {k: 1.0 for k in checker.REQUIRED_KEYS}
        # span roots spot-checked via /mean_s need the full leaf set
        for k in list(base):
            if k.startswith("span/"):
                root = k.rsplit("/", 1)[0]
                for leaf in checker.TIMER_LEAVES:
                    base[f"{root}/{leaf}"] = 1.0
        line = json.dumps({"ts": 1.0, "step": 0, "scalars": base})
        assert checker.validate_lines([line]) == []
        errors = checker.validate_lines(
            [line], extra_required=checker.SOCKET_TRANSPORT_KEYS
        )
        assert any("transport/fanout_lag_max" in e for e in errors)
        full = dict(base)
        for k in (*checker.SOCKET_TRANSPORT_KEYS, *checker.SHM_TRANSPORT_KEYS):
            full[k] = 0.0
        line2 = json.dumps({"ts": 1.0, "step": 0, "scalars": full})
        assert checker.validate_lines(
            [line2],
            extra_required=(
                *checker.SOCKET_TRANSPORT_KEYS, *checker.SHM_TRANSPORT_KEYS
            ),
        ) == []

    def test_transport_servers_emit_their_schema_keys(self):
        """Constructing the servers alone populates every pinned transport
        metric (eager creation — schema presence is deterministic)."""
        import importlib.util

        from dotaclient_tpu.transport import ShmTransportServer, TransportServer

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "cts", os.path.join(root, "scripts", "check_telemetry_schema.py")
        )
        checker = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(checker)
        reg = telemetry.get_registry()
        srv = TransportServer(port=0)
        shm = ShmTransportServer(
            name=f"tel-{os.getpid()}", slots=1, ring_bytes=1 << 14,
            weights_bytes=1 << 14,
        )
        try:
            snap = reg.snapshot()
            for key in (
                *checker.SOCKET_TRANSPORT_KEYS, *checker.SHM_TRANSPORT_KEYS
            ):
                assert key in snap, f"missing transport metric {key}"
        finally:
            srv.close()
            shm.close()

    @pytest.mark.slow   # tier-1 duration audit (ISSUE 6): ~62s on the reference container
    def test_smoke_run_passes_schema(self, checker, capsys):
        """The CI guard end-to-end: a --smoke learner run with the JSONL
        sink validates cleanly against the documented schema (tier-1
        coverage for the acceptance criterion)."""
        assert checker.main([]) == 0
        assert "telemetry schema OK" in capsys.readouterr().out
