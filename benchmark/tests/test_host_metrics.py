"""The eleven metrics of the host's two layers (PR 35): ``entry_points``' start by stage and
``learner_loop``'s host milliseconds, each read from the two snapshots of the program's registry
that the runners already put into the record (``record["counters"]``). Each reader over two
hand-made snapshots gives the hand-computed value and ``None``, never an exception, for five
degenerate records (no ``counters``: the fixed record of ``test_cells.py``; an empty snapshot;
a program without the key: the parent; a divisor of 0; a registry cleared between the
snapshots); the cells list what they should; a rehearsal of one LSTM and one ring cell prints
them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells

SMALL = "dota5v5-lstm128.fused-selfplay"
WIDE = ("five5v5-lstm4096.fused-selfplay", "five5v5-lstm4096.fused-selfplay-4chip")
RING = (
    "trinity-mini-5v5-ep16.fused-selfplay-anycore", "ouro-2.6b-5v5-ut4.fused-selfplay-anycore",
    "kimi-linear-5v5-ep32.fused-selfplay-anycore",
)
STARTUP = (
    "startup_process_s", "startup_learner_init_s", "startup_warmup_s", "startup_trace_lower_s",
    "startup_backend_s", "startup_cache_hit_share",
)
LOOP = ("loop_host_ms_per_dispatch", "boundary_host_ms", "league_fetch_ms", "stats_drain_ms")
ELEVEN = STARTUP + ("startup_fused_build_s",) + LOOP


def read(name, record):
    return cells.load_reader(cells.Metric(name, "s", "lower", "program_span", reader=name)).read(record)


def timer(total_s, count):
    return {"total_s": total_s, "count": float(count)}


def snapshot(counters, timers):
    out = dict(counters)
    for name, stats in timers.items():
        for stat, value in stats.items():
            out[f"span/{name}/{stat}"] = value
    return out


def hand_made():
    """A start of 40 s and a window of 100 dispatches and 10 log boundaries."""
    before = snapshot(
        {
            "startup/process_age_at_init_s": 27.5,
            "compile/trace_s_total": 3.0, "compile/lower_s_total": 1.5, "compile/backend_s_total": 9.25,
            "compile/programs_total": 240.0, "compile/cache_hits_total": 237.0, "compile/cache_misses_total": 3.0,
            "learner/dispatches_total": 3.0, "learner/boundaries_total": 0.0,
        },
        {
            "startup/learner_init": timer(5.5, 1), "startup/learner_init/params": timer(2.0, 1),
            "fused/build": timer(4.0, 2), "fused/build/lower": timer(1.0, 2),
            "learner/train": timer(7.0, 1),
            "learner/iteration": timer(6.0, 3), "learner/dispatch": timer(5.9, 3),
        },
    )
    after = snapshot(
        {**{k: v for k, v in before.items() if not k.startswith("span/")},
         "learner/dispatches_total": 103.0, "learner/boundaries_total": 10.0},
        {
            "startup/learner_init": timer(5.5, 1), "fused/build": timer(4.0, 2),
            "learner/train": timer(7.0, 1),                      # the window's call is still open
            "learner/iteration": timer(6.0 + 20.0, 103),         # 200 ms a dispatch
            "learner/dispatch": timer(5.9 + 15.0, 103),          # 150 of them the meter's wait
            "learner/league_draw": timer(0.1, 100),
            "learner/boundary": timer(4.0, 10),                  # 400 ms a boundary = 40 a dispatch
            "learner/boundary/league_fetch": timer(0.5, 10),
            "learner/boundary/stats_drain": timer(3.0, 10),
            "snapshot/stats_fetch": timer(9.0, 10),
        },
    )
    return {"counters": {"before": before, "after": after}}


HAND = {
    "startup_process_s": 27.5, "startup_learner_init_s": 5.5, "startup_fused_build_s": 4.0,
    "startup_warmup_s": 7.0, "startup_trace_lower_s": 4.5, "startup_backend_s": 9.25,
    "startup_cache_hit_share": 98.75, "loop_host_ms_per_dispatch": 10.0, "boundary_host_ms": 400.0,
    "league_fetch_ms": 50.0, "stats_drain_ms": 300.0,
}
# what each reader reads, for the record that lacks it
READS = {
    "startup_process_s": ("startup/process_age_at_init_s",),
    "startup_learner_init_s": ("span/startup/learner_init/total_s",),
    "startup_fused_build_s": ("span/fused/build/total_s",),
    "startup_warmup_s": ("span/learner/train/total_s",),
    "startup_trace_lower_s": ("compile/trace_s_total", "compile/lower_s_total"),
    "startup_backend_s": ("compile/backend_s_total",),
    "startup_cache_hit_share": ("compile/cache_hits_total", "compile/cache_misses_total"),
    "loop_host_ms_per_dispatch": ("span/learner/iteration/total_s", "span/learner/iteration/count"),
    "boundary_host_ms": ("span/learner/boundary/total_s", "span/learner/boundary/count"),
    "league_fetch_ms": ("span/learner/boundary/league_fetch/total_s", "span/learner/boundary/league_fetch/count"),
    "stats_drain_ms": ("span/learner/boundary/stats_drain/total_s", "span/learner/boundary/stats_drain/count"),
}


def no_counters(record, name):
    return {"setup": {"compile_s": 14.0}}          # the fixed record of test_cells.py has none


def empty_snapshots(record, name):
    side = "after" if name in LOOP else "before"
    return {"counters": {**record["counters"], side: {}}}


def without_its_key(record, name):
    """A program without the span or counter (the parent of PR 35 has the loop's spans, PR 24,
    and none of the start's)."""
    return {"counters": {
        side: {k: v for k, v in snap.items() if k not in READS[name]}
        for side, snap in record["counters"].items()
    }}


def divisor_of_zero(record, name):
    """A window that passed no dispatch and no log boundary; a start in which nothing asked the
    cache (the CPU) or fed an eager-created key (an undonated program has no ``fused/build``)."""
    before, after = dict(record["counters"]["before"]), dict(record["counters"]["after"])
    for key in ("learner/dispatches_total", "learner/boundaries_total"):
        after[key] = before[key]
    for key in READS[name]:
        if name not in LOOP:
            before[key] = 0.0
    return {"counters": {"before": before, "after": after}}


def negative_difference(record, name):
    """A registry cleared between the two snapshots: every timer and counter starts again."""
    before = dict(record["counters"]["before"])
    after = {k: 0.01 * v for k, v in record["counters"]["after"].items()}
    if name not in LOOP:                     # the start's readers take no difference: one snapshot,
        before = {k: -v for k, v in before.items()}    # and a negative reading in it is no reading
    return {"counters": {"before": before, "after": after}}


@pytest.mark.parametrize("name", ELEVEN)
def test_each_reader_gives_the_hand_computed_value(name):
    assert read(name, hand_made()) == pytest.approx(HAND[name])


def test_the_three_stages_partition_the_start():
    record = hand_made()
    assert sum(read(n, record) for n in ("startup_process_s", "startup_learner_init_s", "startup_warmup_s")) == 40.0


@pytest.mark.parametrize("degenerate", [
    no_counters, empty_snapshots, without_its_key, divisor_of_zero, negative_difference,
], ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ELEVEN)
def test_a_degenerate_record_is_nothing_to_read_and_never_an_exception(name, degenerate):
    """A reader that raises ends the run with exit 1, and the driver records only the code."""
    assert read(name, degenerate(hand_made(), name)) is None


def test_a_program_without_the_start_s_spans_still_gives_the_loop_its_numbers():
    record = hand_made()
    for name in STARTUP + ("startup_fused_build_s",):
        record = without_its_key(record, name)
    for name in LOOP:
        assert read(name, record) == pytest.approx(HAND[name]), name
    # a league with no report pending still has its span; a cell without a league has none
    assert read("league_fetch_ms", without_its_key(record, "league_fetch_ms")) is None
    assert read("stats_drain_ms", without_its_key(record, "league_fetch_ms")) == pytest.approx(300.0)


def test_the_cells_list_what_the_issue_gave_them():
    names = {c: {m.name for m in cells.load_cell(c).per_layer} for c in (SMALL,) + WIDE + RING}
    assert not names[SMALL] & set(ELEVEN)        # its fixed record in test_cells.py has no counters
    for cell in WIDE:
        assert names[cell] & set(ELEVEN) == set(STARTUP + LOOP), cell
    for cell in RING:
        assert names[cell] & set(ELEVEN) == set(ELEVEN), cell
    manifest = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    for name in ELEVEN:
        m = manifest[name]
        assert (m["layer"], m["moves"]) == (
            ("learner_loop", "train_frames_per_s") if name in LOOP else ("entry_points", "setup_s")
        ), name
        assert m["source"] in ("program_span", "program_counter")
        assert SMALL not in m["workloads"]


@pytest.mark.parametrize("cell", [WIDE[0], RING[2]])
def test_a_rehearsal_prints_every_metric_the_cell_lists(cell):
    """Control flow only. The window is left to end where a traced run's does, after its
    fourteenth dispatch, so it passes a log boundary (ten steps, the warm-up's three among
    them) however slow this CPU is. The CPU backend is left without a persistent compile cache
    (``utils/compile_cache.py``), so nothing asks it and the hit share has nothing to read."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--rehearse-cpu", "--trace", "1", "--seconds", "600"],
        cwd=cells.ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    would = [l for l in out.stdout.splitlines() if "would print" in l][-1]
    line = json.loads(would.split("would print ", 1)[1])
    listed = {m.name for m in cells.load_cell(cell).per_layer} & set(ELEVEN)
    # nor is a rehearsal's toy state past the 4 GB at which the fused program is donated
    assert listed - set(line["metrics"]) == {"startup_cache_hit_share"} | ({"startup_fused_build_s"} & listed)
    values = {n: line["metrics"][n]["value"] for n in listed & set(line["metrics"])}
    assert all(v >= 0 for v in values.values()), values
    detail = next(l for l in out.stdout.splitlines() if "benchmark: detail " in l)
    setup_s = json.loads(detail.split("detail ", 1)[1])["setup"]["setup_s"]
    stages = values["startup_process_s"] + values["startup_learner_init_s"] + values["startup_warmup_s"]
    assert 0.9 * setup_s < stages <= setup_s
