"""Pipeline utilization plane tests (ISSUE 16): phase fractions sum to
~1.0 by construction, the duty cycle pinned against a synthetic dispatch
timeline, the throughput-regression sentinel's warmup arming, snapshot-
frame round trip + fleet ship_wait rollup, the two alert rules' arming
and debounce through the engine, the --require-utilization schema tier,
the off-path cost discipline (factories return None — one pointer test
per call site, the faults.get() pattern), and the report-console bugfix
sweep (trace_report / outcome_report degrade cleanly on fuzzed logs)."""

import json
import os
import time

import pytest

from dotaclient_tpu.utils import alerts, fleet, telemetry, utilization

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script_module(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# phase accounting arithmetic


class TestPhaseAccountant:
    def _acct(self, reg=None):
        reg = reg or telemetry.Registry()
        handles = utilization.ensure_learner_keys(reg)
        gauges = {
            p: handles[f"util/phase/{p}"]
            for p in utilization.LEARNER_PHASES
        }
        return utilization.PhaseAccountant(
            gauges, utilization.LEARNER_PHASES, residual="host_other",
            now=0.0,
        )

    def test_fractions_sum_to_one(self):
        acct = self._acct()
        acct.phase("dispatch_inflight", 6.0)
        acct.phase("ingest_wait", 2.0)
        acct.phase("gather", 1.0)
        fractions, window = acct.fold(now=10.0)
        assert window == 10.0
        assert abs(sum(fractions.values()) - 1.0) < 1e-9
        assert fractions["dispatch_inflight"] == pytest.approx(0.6)
        assert fractions["host_other"] == pytest.approx(0.1)

    def test_overaccounted_clamps_not_overflows(self):
        """Clock noise pushing accounted past the window must shrink the
        residual to 0, never the sum past 1 (the denominator contract)."""
        acct = self._acct()
        acct.phase("dispatch_inflight", 11.0)
        fractions, _ = acct.fold(now=10.0)
        assert fractions["host_other"] == 0.0
        assert abs(sum(fractions.values()) - 1.0) < 1e-9

    def test_zero_window_is_a_noop(self):
        acct = self._acct()
        assert acct.fold(now=0.0) == ({}, 0.0)

    def test_negative_and_zero_intervals_ignored(self):
        acct = self._acct()
        acct.phase("gather", -1.0)
        acct.phase("gather", 0.0)
        fractions, _ = acct.fold(now=4.0)
        assert fractions["gather"] == 0.0
        assert fractions["host_other"] == pytest.approx(1.0)

    def test_fold_resets_the_window(self):
        acct = self._acct()
        acct.phase("gather", 5.0)
        acct.fold(now=10.0)
        fractions, window = acct.fold(now=14.0)
        assert window == pytest.approx(4.0)
        assert fractions["gather"] == 0.0


class TestLearnerUtilization:
    def _lu(self):
        reg = telemetry.Registry()
        handles = utilization.ensure_learner_keys(reg)
        lu = utilization.LearnerUtilization(handles)
        lu._acct._window_start = 0.0   # pin the synthetic timeline origin
        return reg, lu

    def test_duty_cycle_pinned_against_synthetic_timeline(self):
        """10 s window in which the donated dispatch was in flight 7 s:
        duty cycle 0.7, armed flips, gauges carry the fractions."""
        reg, lu = self._lu()
        # pre-arm: neutral duty cycle, unarmed
        snap = reg.snapshot()
        assert snap["util/armed"] == 0.0
        assert snap["util/duty_cycle"] == 1.0
        lu.phase("dispatch_inflight", 7.0)
        lu.phase("ingest_wait", 1.5)
        lu.phase("publish_stall", 0.5)
        fractions = lu.fold(step=100, now=10.0)
        snap = reg.snapshot()
        assert snap["util/armed"] == 1.0
        assert snap["util/duty_cycle"] == pytest.approx(0.7)
        assert snap["util/phase/ingest_wait"] == pytest.approx(0.15)
        assert snap["util/phase/host_other"] == pytest.approx(0.1)
        assert abs(sum(fractions.values()) - 1.0) < 1e-9

    def test_sentinel_arms_after_warmup_then_latches_on_regression(self):
        reg, lu = self._lu()
        now, step = 0.0, 0
        # warmup + settle at 10 steps/s: first fold has no prior step
        for _ in range(5):
            now += 10.0
            step += 100
            lu.fold(step=step, now=now)
        snap = reg.snapshot()
        assert snap["util/steps_per_sec_ema"] == pytest.approx(10.0)
        assert snap["util/steps_per_sec_baseline"] == pytest.approx(10.0)
        assert snap["util/throughput_regression"] == 0.0
        # throughput collapses to ~0.1 steps/s; the fast EMA chases it
        # down while the slow baseline remembers 10 — the latch comes up
        for _ in range(3):
            now += 10.0
            step += 1
            lu.fold(step=step, now=now)
        snap = reg.snapshot()
        assert snap["util/steps_per_sec_ema"] < 0.7 * snap[
            "util/steps_per_sec_baseline"
        ]
        assert snap["util/throughput_regression"] == 1.0

    def test_same_step_refold_never_poisons_the_ema(self):
        """The end-of-run flush re-folds at the final step: a zero-step
        window must contribute NO rate sample (a rate-0 sample would drag
        the EMA down and spuriously latch the sentinel on every clean
        shutdown)."""
        reg, lu = self._lu()
        now, step = 0.0, 0
        for _ in range(6):
            now += 10.0
            step += 100
            lu.fold(step=step, now=now)
        before = reg.snapshot()
        lu.fold(step=step, now=now + 30.0)   # the final-flush double fold
        after = reg.snapshot()
        assert after["util/steps_per_sec_ema"] == before[
            "util/steps_per_sec_ema"
        ]
        assert after["util/throughput_regression"] == 0.0

    def test_no_rate_before_two_folds(self):
        """The first fold has no prior step — fractions publish but the
        EMA stays unarmed (no bogus rate from a half-open interval)."""
        reg, lu = self._lu()
        lu.fold(step=50, now=10.0)
        assert reg.snapshot()["util/steps_per_sec_ema"] == 0.0


class TestPoolUtilization:
    def test_cadence_gated_fold(self):
        reg = telemetry.Registry()
        pool = utilization.make_actor(reg, interval_s=100.0)
        t0 = pool._last_fold
        pool.phase("env_step", 1.0)
        assert pool.maybe_fold(now=t0 + 1.0) is None      # not due
        fractions = pool.maybe_fold(now=t0 + 101.0)       # due: folds
        assert fractions is not None
        assert abs(sum(fractions.values()) - 1.0) < 1e-9
        assert reg.snapshot()["util/actor/env_step"] > 0.0


# ---------------------------------------------------------------------------
# the factories: always on, keys eager


class TestFactories:
    def test_enabled_factories_return_accountants(self):
        """On a fresh registry every factory returns its accountant AND
        has eager-created its keys at their neutral values (the schema
        tier holds for ANY JSONL, before the first fold)."""
        reg = telemetry.Registry()
        assert isinstance(
            utilization.make_learner(reg), utilization.LearnerUtilization
        )
        assert isinstance(
            utilization.make_actor(reg), utilization.PoolUtilization
        )
        assert isinstance(
            utilization.make_serve(reg), utilization.PoolUtilization
        )
        snap = reg.snapshot()
        for key in (
            "util/armed", "util/duty_cycle", "util/steps_per_sec_ema",
            "util/phase/dispatch_inflight", "util/phase/host_other",
            "util/actor/ship_wait", "util/serve/window_wait",
        ):
            assert key in snap, key
        # the duty-cycle gauge reads its NEUTRAL 1.0, not a 0.0 that
        # would trip learner_duty_cycle_low before the first fold
        assert snap["util/duty_cycle"] == 1.0
        assert snap["util/armed"] == 0.0


# ---------------------------------------------------------------------------
# snapshot frames + fleet rollup


class TestFleetIntegration:
    def test_util_namespace_ships_on_snapshots(self):
        assert "util/" in fleet.SNAPSHOT_PREFIXES

    def test_snapshot_round_trip_carries_util_gauges(self):
        payload = fleet.encode_snapshot(
            3, "actor", 1, {},
            {"util/actor/ship_wait": 0.25, "util/actor/env_step": 0.5},
            pid=9,
        )
        snap = fleet.decode_snapshot(payload)
        assert snap["gauges"]["util/actor/ship_wait"] == 0.25
        assert snap["gauges"]["util/actor/env_step"] == 0.5

    def test_ship_wait_rollup_across_peers(self):
        reg = telemetry.Registry()
        agg = fleet.FleetAggregator(
            registry=reg, interval_s=0.1, emit_event=None
        )
        t = time.monotonic()
        agg.ingest(fleet.encode_snapshot(
            0, "actor", 0, {}, {"util/actor/ship_wait": 0.1}, pid=1))
        agg.ingest(fleet.encode_snapshot(
            1, "actor", 0, {}, {"util/actor/ship_wait": 0.3}, pid=2))
        agg.tick(now=t)
        snap = reg.snapshot()
        assert snap["fleet/agg/ship_wait/min"] == pytest.approx(0.1)
        assert snap["fleet/agg/ship_wait/max"] == pytest.approx(0.3)
        assert snap["fleet/agg/ship_wait/mean"] == pytest.approx(0.2)
        # per-peer mirrors exist for the utilization report's peer rows
        assert snap["fleet/a0/util/actor/ship_wait"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# alert rules


def _engine(rule_names):
    rules = tuple(r for r in alerts.RULES if r.name in rule_names)
    assert len(rules) == len(rule_names)
    events = []
    engine = alerts.AlertEngine(
        rules=rules, registry=telemetry.Registry(), emit=events.append
    )
    return engine, events


class TestAlertRules:
    def test_rules_exist_with_runbook_anchors(self):
        by_name = {r.name: r for r in alerts.RULES}
        duty = by_name["learner_duty_cycle_low"]
        assert duty.key == "util/duty_cycle"
        assert duty.runbook == "rb:duty-cycle-low"
        reg = by_name["throughput_regression"]
        assert reg.key == "util/throughput_regression"
        assert reg.runbook == "rb:throughput-regression"

    def test_duty_cycle_low_arms_and_debounces(self):
        engine, events = _engine(["learner_duty_cycle_low"])
        t = 1000.0
        # neutral pre-arm value: never fires
        fired, _ = engine.evaluate({"util/duty_cycle": 1.0}, now=t)
        assert fired == []
        # low duty cycle must HOLD for for_s before firing (debounce)
        fired, _ = engine.evaluate({"util/duty_cycle": 0.05}, now=t + 1)
        assert fired == []
        fired, _ = engine.evaluate({"util/duty_cycle": 0.05}, now=t + 122)
        assert fired == ["learner_duty_cycle_low"]
        # recovery resolves
        _, resolved = engine.evaluate({"util/duty_cycle": 0.8}, now=t + 123)
        assert resolved == ["learner_duty_cycle_low"]
        assert [e["state"] for e in events] == ["fired", "resolved"]

    def test_throughput_regression_latch_fires(self):
        engine, _ = _engine(["throughput_regression"])
        t = 2000.0
        fired, _ = engine.evaluate(
            {"util/throughput_regression": 0.0}, now=t)
        assert fired == []
        fired, _ = engine.evaluate(
            {"util/throughput_regression": 1.0}, now=t + 1)
        assert fired == []   # for_s=60 debounce
        fired, _ = engine.evaluate(
            {"util/throughput_regression": 1.0}, now=t + 62)
        assert fired == ["throughput_regression"]


# ---------------------------------------------------------------------------
# schema tier


class TestSchemaTier:
    def _line(self, extra=None):
        scalars = {k: 0.0 for k in _script_module(
            "check_telemetry_schema").UTILIZATION_KEYS}
        scalars["util/duty_cycle"] = 1.0
        if extra:
            scalars.update(extra)
        return json.dumps({"ts": 1.0, "step": 0, "scalars": scalars})

    def test_require_utilization_round_trip(self):
        schema = _script_module("check_telemetry_schema")
        errors = schema.validate_lines(
            [self._line()],
            extra_required=schema.UTILIZATION_KEYS,
            base_required=(),
        )
        assert errors == []

    def test_missing_key_is_a_violation(self):
        schema = _script_module("check_telemetry_schema")
        scalars = json.loads(self._line())
        del scalars["scalars"]["util/phase/ingest_wait"]
        errors = schema.validate_lines(
            [json.dumps(scalars)],
            extra_required=schema.UTILIZATION_KEYS,
            base_required=(),
        )
        assert any("util/phase/ingest_wait" in e for e in errors)


# ---------------------------------------------------------------------------
# utilization report console


class TestUtilizationReport:
    def _write(self, tmp_path, scalars):
        path = tmp_path / "learner.jsonl"
        path.write_text(
            json.dumps({"ts": time.time(), "step": 7, "scalars": scalars})
            + "\n"
        )
        return str(path)

    def test_armed_run_renders_table_and_ok(self, tmp_path, capsys):
        report = _script_module("utilization_report")
        scalars = {
            "util/armed": 1.0,
            "util/duty_cycle": 0.62,
            "util/steps_per_sec_ema": 9.5,
            "util/steps_per_sec_baseline": 10.0,
            "util/throughput_regression": 0.0,
            "util/phase/dispatch_inflight": 0.62,
            "util/phase/ingest_wait": 0.2,
            "util/phase/gather": 0.08,
            "util/phase/advantage_pass": 0.04,
            "util/phase/publish_stall": 0.02,
            "util/phase/checkpoint_stall": 0.0,
            "util/phase/host_other": 0.04,
            # an external actor peer's mirrored fractions
            "fleet/a0/util/actor/env_step": 0.5,
            "fleet/a0/util/actor/ship_wait": 0.3,
        }
        assert report.main([self._write(tmp_path, scalars)]) == 0
        out = capsys.readouterr().out
        assert "learner" in out and "a0" in out
        line = [
            l for l in out.splitlines()
            if l.startswith("UTILIZATION_STATUS ")
        ]
        status = json.loads(line[0][len("UTILIZATION_STATUS "):])
        assert status["ok"] is True
        assert status["duty_cycle"] == 0.62
        assert status["phases"]["ingest_wait"] == 0.2
        assert status["peers"]["a0"]["ship_wait"] == 0.3

    def test_unarmed_run_exits_nonzero(self, tmp_path, capsys):
        report = _script_module("utilization_report")
        scalars = {"util/armed": 0.0, "util/duty_cycle": 1.0}
        assert report.main([self._write(tmp_path, scalars)]) == 1
        assert "unarmed" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# bugfix sweep: report consoles must degrade cleanly on fuzzed logs


class TestReportConsolesDegradeCleanly:
    def test_trace_report_survives_fuzzed_events(self, tmp_path):
        """The four crash shapes from the sweep: a 1-element hop entry, a
        null publish version, a null hop timestamp, and a non-numeric
        publish_ts — each must degrade to 'evidence absent', not a
        ValueError/TypeError."""
        from scripts.trace_report import build_report

        lines = [
            {"event": "chunk", "tid": "t1", "origin_pid": 1, "actor": 0,
             "wv": 3, "hops": [["collect", 1.0], ["encode"]]},
            {"event": "publish", "version": None, "ts": 1.0},
            {"event": "chunk", "tid": "s1", "origin_pid": 2, "actor": 0,
             "wv": 3, "hops": [["encode", 1.0], ["done", None]]},
            {"event": "apply", "version": 3, "pid": 1,
             "publish_ts": "not-a-number", "ts": 2.0},
            {"event": "chunk", "tid": "t2", "origin_pid": 1, "actor": 0,
             "wv": None, "hops": [["encode", 1.0], ["dispatch", 2.0]]},
        ]
        p = tmp_path / "fuzz.trace.jsonl"
        p.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
        rep = build_report([str(tmp_path)])   # must not raise
        assert rep["chunks_seen"] >= 1

    def test_trace_report_zero_complete_chunks(self, tmp_path):
        from scripts.trace_report import main as report_main

        p = tmp_path / "sparse.trace.jsonl"
        p.write_text(
            json.dumps({"event": "chunk", "tid": "x",
                        "hops": [["collect", 1.0]]}) + "\n"
        )
        # no complete chunk → nonzero by design, but NO crash
        assert report_main(["--json", str(tmp_path)]) in (0, 1)

    def test_outcome_report_survives_non_numeric_ts(self, tmp_path, capsys):
        report = _script_module("outcome_report")
        p = tmp_path / "learner.jsonl"
        p.write_text(
            json.dumps({"ts": "not-a-number", "step": 4,
                        "scalars": {"outcome/episodes_total": 0.0}}) + "\n"
        )
        # zero episodes → rc 1 by design, but render must not TypeError
        assert report.main([str(p)]) == 1
        assert "OUTCOME_STATUS" in capsys.readouterr().out

    def test_fleet_status_survives_non_numeric_ts(self, tmp_path, capsys):
        status = _script_module("fleet_status")
        p = tmp_path / "learner.jsonl"
        p.write_text(
            json.dumps({"ts": None, "step": "x", "scalars": {}}) + "\n"
        )
        assert status.main([str(p)]) == 0
        assert "FLEET_STATUS" in capsys.readouterr().out
