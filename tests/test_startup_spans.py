"""A start told by the program itself (ISSUE 35): the spans of
``Learner.__init__`` and ``Learner.train``, a donated fused program's build,
the whole-process compile counters fed from JAX's own events, and the cost
analysis that runs only where a tracer reads it.

The registry is the process's one, and other tests of the same worker have
used it: every assertion is on what a step ADDED to a snapshot."""

import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from dotaclient_tpu.config import default_config
from dotaclient_tpu.utils import telemetry, tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INIT = "span/startup/learner_init"


def _gained(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def _schema_module():
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_schema", os.path.join(_REPO, "scripts", "check_telemetry_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    tracing.configure(None)
    yield
    tracing.configure(None)


@pytest.fixture(scope="module")
def start(tmp_path_factory):
    """One toy fused learner with league opponents, its states donated (toy
    states count as "most of the chip"), built and run for ``train(2)``:
    snapshots of the registry before the build, after it and after the call."""
    from dotaclient_tpu.train import fused
    from dotaclient_tpu.train.learner import Learner

    cfg = default_config()
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, dtype="float32"),
        ppo=dataclasses.replace(cfg.ppo, rollout_len=4, batch_rollouts=8),
        env=dataclasses.replace(cfg.env, n_envs=4, opponent="league", max_dota_time=60.0),
        league=dataclasses.replace(cfg.league, enabled=True),
        log_every=1,
    )
    jsonl = str(tmp_path_factory.mktemp("start") / "metrics.jsonl")
    reg = telemetry.get_registry()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fused, "DONATE_ABOVE_BYTES", 0)
        before = reg.snapshot()
        learner = Learner(cfg, actor="fused", seed=0, metrics_jsonl=jsonl)
        built = reg.snapshot()
        out = learner.train(2)
        trained = reg.snapshot()
        learner.metrics.close()
    assert learner.fused_step.donate and out["optimizer_steps"] == 2.0
    return {"before": before, "built": built, "trained": trained, "jsonl": jsonl}


def test_every_stage_of_the_constructor_that_ran_is_one_span_under_the_parent(start):
    gained = _gained(start["before"], start["built"])
    assert gained[f"{INIT}/count"] == 1.0
    stages = {
        k[len(INIT) + 1:-len("/total_s")]: v for k, v in gained.items()
        if k.startswith(INIT + "/") and k.endswith("/total_s") and k != f"{INIT}/total_s"
        and gained[k[:-len("total_s")] + "count"]
    }
    # a fused learner with league opponents: no restore, no ring, no host pool
    assert set(stages) == {
        "params", "train_state", "state_commit", "snapshot_engine",
        "device_actor", "fused_program", "league",
    }
    for stage in stages:
        assert gained[f"{INIT}/{stage}/count"] == 1.0, stage
    # what no child names is the constructor's self time (held under 5% on
    # the chip, PERF.md; a loaded CPU's share is not asserted)
    assert 0.0 < sum(stages.values()) <= gained[f"{INIT}/total_s"]
    # set at the constructor's first line: the interpreter's start and the
    # imports of this test process are in it
    assert start["built"]["startup/process_age_at_init_s"] > 0.0


def test_a_train_call_is_one_span_with_prepare_and_finish_and_the_loop_between(start):
    gained = _gained(start["built"], start["trained"])
    for key in ("span/learner/train", "span/learner/train/prepare", "span/learner/train/finish"):
        assert gained[f"{key}/count"] == 1.0, key
    call = gained["span/learner/train/total_s"]
    ends = gained["span/learner/train/prepare/total_s"] + gained["span/learner/train/finish/total_s"]
    assert gained["span/learner/iteration/count"] == 2.0
    assert ends + gained["span/learner/iteration/total_s"] <= call
    # nothing was added to the loop: the spans opened inside
    # `learner/iteration` (and by the snapshot thread beside it) are the
    # parent commit's, to the name
    iteration = {
        k for k, v in gained.items()
        if k.startswith("span/learner/") and k.endswith("/count") and v and "/train" not in k
    }
    assert iteration == {
        f"span/learner/{s}/count" for s in (
            "iteration", "league_draw", "dispatch", "league_report", "boundary",
            "boundary/flush_health", "boundary/league_fetch", "boundary/gauges",
            "boundary/stats_drain", "boundary/submit_metrics", "metrics_fetch",
        )
    }


def test_a_donated_program_is_built_once_a_kind_inside_the_first_dispatch(start):
    built = _gained(start["before"], start["built"])
    assert not built.get("span/fused/build/count")        # nothing ahead of the first call
    gained = _gained(start["built"], start["trained"])
    # opponent lanes: live and frozen, both at the first call, none at the second
    for key in ("span/fused/build", "span/fused/build/lower", "span/fused/build/compile"):
        assert gained[f"{key}/count"] == 2.0, key
    parts = gained["span/fused/build/lower/total_s"] + gained["span/fused/build/compile/total_s"]
    assert 0.0 < parts <= gained["span/fused/build/total_s"] <= gained["span/learner/train/total_s"]
    # with no tracer configured the wrapper lowered nothing a second time
    assert not gained.get("span/compile/cost_analysis/count")


def test_the_learners_record_validates_with_the_new_keys(start):
    schema = _schema_module()
    lines = telemetry.load_jsonl(start["jsonl"])
    assert schema.validate_lines(lines, extra_required=schema.TRACE_KEYS, base_required=()) == []
    last = json.loads(lines[-1])["scalars"]
    assert last["startup/process_age_at_init_s"] > 0.0
    assert last[f"{INIT}/count"] >= 1.0 and last["span/learner/train/finish/count"] >= 1.0
    # the whole process's programs, eager ones included: far more than the
    # two the instrumented wrappers saw
    assert last["compile/programs_total"] > last["compile/compiles_total"] >= 2.0
    assert last["compile/trace_s_total"] > 0.0 and last["compile/backend_s_total"] > 0.0


def test_the_listener_counts_a_new_shape_once_and_a_repeat_not_at_all():
    reg = telemetry.get_registry()
    # however often it is asked for (a learner and an engine each ask): one
    # listener, or a program would count twice
    for _ in range(3):
        tracing.ensure_metrics(reg)
    tracing.ensure_metrics(telemetry.Registry())
    fn = jax.jit(lambda x: x * 3 + 1)
    x = np.zeros((1237,), np.float32)            # a shape nothing else uses
    before = reg.snapshot()
    fn(x).block_until_ready()
    first = _gained(before, reg.snapshot())
    assert first["compile/programs_total"] == 1.0
    assert first["compile/backend_s_total"] > 0.0
    assert first["compile/trace_s_total"] > 0.0 and first["compile/lower_s_total"] > 0.0
    before = reg.snapshot()
    fn(x + 1).block_until_ready()
    again = _gained(before, reg.snapshot())
    for key in ("compile/programs_total", "compile/backend_s_total", "compile/trace_s_total"):
        assert again[key] == 0.0, key
    # the keys exist in any registry that asked, at zero where nothing feeds it
    own = telemetry.Registry()
    tracing.ensure_metrics(own)
    assert own.snapshot()["compile/cache_hits_total"] == 0.0


class CountsLowerings:
    """Stands in for a jitted callable: every first call "compiles"."""

    def __init__(self):
        self.fn = jax.jit(lambda x: x @ x)
        self.lowered = 0

    def __call__(self, x):
        return self.fn(x)

    def _cache_size(self):
        return self.fn._cache_size()

    def lower(self, *args, **kwargs):
        self.lowered += 1
        return self.fn.lower(*args, **kwargs)


def test_cost_analysis_runs_only_where_a_tracer_reads_it(tmp_path):
    reg = telemetry.Registry()
    x = np.ones((16, 16), np.float32)
    off = CountsLowerings()
    wrapped = tracing.instrument_jit(off, "train_step", reg)
    wrapped(x)
    snap = reg.snapshot()
    assert snap["compile/train_step/compiles_total"] == 1.0    # counted all the same
    assert off.lowered == 0 and "span/compile/cost_analysis/count" not in snap

    path = str(tmp_path / "trace.jsonl")
    tracing.configure(path, sample_n=1, registry=reg)
    on = CountsLowerings()
    wrapped = tracing.instrument_jit(on, "train_step", reg)
    wrapped(x)
    wrapped(x)                                                  # no compile, no lowering
    tracing.shutdown()
    assert on.lowered == 1
    assert reg.snapshot()["span/compile/cost_analysis/count"] == 1.0
    events = [json.loads(line) for line in telemetry.load_jsonl(path)]
    compiles = [e for e in events if e["event"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["program"] == "train_step"
    assert compiles[0]["flops"] >= 2 * 16 ** 3 - 16 ** 2 and compiles[0]["bytes_accessed"] > 0


def test_a_logged_step_is_in_the_record_when_train_returns(start):
    """The benchmark's runner reads the JSONL as soon as ``train()`` returns:
    both steps' losses are there, and the call's closing line after them."""
    lines = [json.loads(line) for line in telemetry.load_jsonl(start["jsonl"])]
    logged = [line for line in lines if "loss" in line["scalars"]]
    assert {line["step"] for line in logged} >= {1, 2}
    assert all(np.isfinite(line["scalars"]["loss"]) for line in logged)
    assert lines[-1]["step"] == 2 and lines[-1]["scalars"]["frames_trained"] > 0


@pytest.mark.parametrize("doc, keys", [
    ("docs/ARCHITECTURE.md", (
        "`startup/learner_init`", "`startup/learner_init/device_actor`", "`startup/process_age_at_init_s`",
        "`learner/train/prepare`", "`learner/train/finish`", "`fused/build/lower`", "`fused/build/compile`",
        "`compile/cost_analysis`", "`compile/programs_total`", "`compile/cache_misses_total`",
    )),
    ("docs/OPERATIONS.md", ("Which step recompiled", "Where did my restart go", "`compile/programs_total`")),
])
def test_the_new_keys_are_documented_and_the_drift_lint_is_clean(doc, keys):
    from dotaclient_tpu.lint import telemetry_drift
    from dotaclient_tpu.lint.core import FileCtx

    with open(os.path.join(_REPO, doc)) as f:
        text = f.read()
    for key in keys:
        assert key in text, key
    rule, files = telemetry_drift.TelemetryDriftRule(), {}
    for rel in rule.paths():
        if os.path.exists(os.path.join(_REPO, rel)):
            with open(os.path.join(_REPO, rel)) as f:
                files[rel] = FileCtx(rel, f.read())
    assert rule.check(files) == []
