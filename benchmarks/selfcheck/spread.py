#!/usr/bin/env python3
"""Spreads of the end-to-end metrics over two sets of runs, as the bounds'
rule reads them: for each set the distance between the first and the third
quartile (`statistics.quantiles(values, n=4)`) as a share of the median; the
wider of the two sets; five times that, and never under 1 %, is the bound.

    python3 benchmarks/selfcheck/spread.py <dir with A.<seed>.out and B.<seed>.out>

Each `.out` file is one run's standard output; its last line is the result.
"""

import glob
import json
import os
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def spread(values) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(out_dir: str) -> int:
    sets = {}
    for name in ("A", "B"):
        runs = [last_line(p) for p in sorted(
            glob.glob(os.path.join(out_dir, f"{name}.*.out")))]
        if any(not r["correct"] for r in runs):
            print(f"set {name}: a run is not correct")
        sets[name] = runs
    metrics = sorted({m for runs in sets.values() for r in runs
                      for m in r["metrics"]})
    for m in metrics:
        row = {}
        for name, runs in sets.items():
            values = [r["metrics"][m]["value"] for r in runs]
            row[name] = {"n": len(values),
                         "median": statistics.median(values),
                         "spread": spread(values),
                         "min": min(values), "max": max(values)}
        widest = max(v["spread"] for v in row.values())
        drift = row["B"]["median"] / row["A"]["median"] - 1
        print(json.dumps({"metric": m, "sets": row, "widest_spread": widest,
                          "bound_by_rule": max(0.01, 5 * widest),
                          "second_median_over_first": drift}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
