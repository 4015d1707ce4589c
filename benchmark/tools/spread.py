"""Medians and spreads of a cell's runs, as the driver reads them.

    python3 benchmark/tools/spread.py run_1.log run_2.log ... [--sets 2]

Each file is the output of one ``benchmark/run.py --trace 0`` run (its last
line is the result). The runs are split, in the order given, into ``--sets``
equal sets; for each end-to-end metric this prints each set's median and
spread (the distance between the quartiles over the median) and how far
the second median is from the first. A bound is set to about five times
the widest spread over the cells, never under 1%.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.harness import stats  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("logs", nargs="+")
    p.add_argument("--sets", type=int, default=2)
    args = p.parse_args(argv)
    lines = []
    for path in args.logs:
        with open(path) as f:
            lines.append(json.loads(f.read().rstrip().splitlines()[-1]))
    if not all(line["correct"] for line in lines):
        sys.exit("spread: a run is not correct")
    size = len(lines) // args.sets
    for name in lines[0]["metrics"]:
        values = [line["metrics"][name]["value"] for line in lines]
        sets = [values[i * size:(i + 1) * size] for i in range(args.sets)]
        medians = [stats.median(s) for s in sets]
        print(json.dumps({
            "metric": name, "runs": len(values), "medians": medians,
            "spreads": [stats.spread(s) for s in sets],
            "second_over_first": [m / medians[0] - 1.0 for m in medians[1:]],
            "min": min(values), "max": max(values),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
