"""Run one cell of the benchmark and print one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, runner, per-layer metrics and their readers are
found by name (``benchmark/harness/cells.py``). The last line of standard
output is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (traced: also ``breakdown``). With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a short profiler trace of the steady window.

It refuses to run, exits non-zero and prints no result, unless JAX reports
``tpu`` devices, as many as the cell's ``chips``, and the program
(``dotaclient_tpu``) is in the checkout.

    JAX_PLATFORMS=cpu python3 benchmark/run.py --workload <cell> --rehearse-cpu

walks the same control flow at a tiny size on the CPU (as many forced host
devices as the cell has chips), labels every line of its own a rehearsal
and never prints the result line: nothing a CPU run times is a result.

What a run writes (the program's metrics record, the profiler's trace) goes
under ``benchmark_out/<cell>/`` in the checkout; JAX's persistent compile
cache is the program's own (``<checkout>/.jax_cache``, or where
``JAX_COMPILATION_CACHE_DIR`` says).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None, help="default: run_seconds of BENCHMARK.json")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", dest="rehearse", action="store_true")
    p.add_argument("--keep-trace", action="store_true", help="leave the .xplane.pb under benchmark_out/")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark.harness import cells

    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        sys.exit(f"benchmark: {e}")
    if not os.path.isdir(os.path.join(ROOT, "dotaclient_tpu")):
        sys.exit(
            "benchmark: the program (dotaclient_tpu/) is not in this checkout: "
            "no phase run, no result printed"
        )
    tag = ""
    if args.rehearse:
        tag = "REHEARSAL(cpu) "
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit("benchmark: --rehearse-cpu needs JAX_PLATFORMS=cpu")
        # before JAX starts its backend: one host device per chip of the cell
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        ).strip()
        if args.seconds is None:
            args.seconds = 2.0
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    args.out = os.path.join(ROOT, "benchmark_out", cell.name)

    def say(text: str) -> None:
        print(f"{tag}benchmark: {text}", flush=True)

    say(
        f"cell {cell.name} (configuration {cell.config_name}, traffic "
        f"{cell.traffic_name}, runner {cell.runner}, {cell.chips} chip(s)) "
        f"seed={args.seed} seconds={args.seconds} trace={args.trace}"
    )
    from benchmark.harness import result

    record = cells.load_runner(cell).run(cell, args)
    line = result.result_line(cell, record, traced=bool(args.trace))
    say("detail " + json.dumps(result.detail(record), sort_keys=True, default=str))
    if args.rehearse:
        say("would print " + json.dumps(line, default=str))
        say("rehearsal complete: control flow only, nothing here is a device result")
        return 0 if line["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
