#!/usr/bin/env python3
"""Spreads of a set of runs, as the bounds are set from them.

    python3 benchmarks/tools/spreads.py set1.out [set2.out ...]

Each file holds the standard output of some runs of ONE cell (the result
line is the last line of each run). Prints, per metric and file, the median
and the distance between the quartiles as a share of the median.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness.stats import iqr_share  # noqa: E402


def result_lines(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f
                if line.startswith('{"correct"')]


def main(paths: list[str]) -> int:
    for path in paths:
        lines = result_lines(path)
        print(f"{path}: {len(lines)} runs, correct "
              f"{sum(x['correct'] for x in lines)}")
        for name in lines[0]["metrics"]:
            values = [x["metrics"][name]["value"] for x in lines
                      if name in x["metrics"]]
            spread = iqr_share(values) if len(values) >= 2 else float("nan")
            print(f"  {name}: median {statistics.median(values):.6g} "
                  f"spread {100 * spread:.3f} %  "
                  f"{[round(v, 4) for v in values]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
