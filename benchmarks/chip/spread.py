#!/usr/bin/env python3
"""Medians and spreads of a cell's runs, as the driver reads them.

  python3 benchmarks/chip/spread.py chiprun_out/chipbench/<cell>-s*-t0

Reads each directory's ``run.json`` and prints, for every metric the runs
reported, the values, their median and their spread (the distance between
the quartiles over the median).  A bound is about five times the widest
spread over the cells, and never under 1%.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib.stats import median, spread  # noqa: E402


def main() -> int:
    by_metric: dict[str, list[float]] = {}
    for run_dir in sys.argv[1:]:
        with open(os.path.join(run_dir, "run.json"), encoding="utf-8") as f:
            run = json.load(f)
        for name, row in run["all_metrics"].items():
            by_metric.setdefault(name, []).append(row["value"])
    for name, values in by_metric.items():
        print(f"{name}: n={len(values)} median={median(values):.6g} "
              f"spread={100 * spread(values):.3f}% values="
              f"{[float(f'{v:.6g}') for v in values]}")
    return 0 if by_metric else 2


if __name__ == "__main__":
    sys.exit(main())
