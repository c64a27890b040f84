#!/usr/bin/env python3
"""Spreads of each end-to-end metric over the two sets of runs that
``prove_cell.sh`` writes (``set<A|B>_<i>.out``), for the bounds.

    python3 chipbench/spread.py <out dir>

A spread is the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; a
metric's bound is about five times the wider of the two sets' spreads,
and never under 1 %.
"""

import glob
import json
import re
import statistics
import sys


def sets(out_dir: str) -> dict:
    found = {}
    for path in sorted(glob.glob(f"{out_dir}/set*_*.out")):
        name = re.search(r"set(\w+)_\d+\.out$", path).group(1)
        lines = [l for l in open(path).read().splitlines()
                 if l.startswith("{")]
        if lines:
            found.setdefault(name, []).append(json.loads(lines[-1]))
    return found


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(out_dir: str) -> None:
    runs = sets(out_dir)
    names = sorted({k for rs in runs.values() for r in rs
                    for k in r["metrics"]})
    for k in names:
        cols = {s: [r["metrics"][k]["value"] for r in rs
                    if k in r["metrics"]] for s, rs in sorted(runs.items())}
        widest = max(spread(v) for v in cols.values())
        print(json.dumps({"metric": k, "widest_spread": widest,
                          "bound_5x": max(5 * widest, 0.01),
                          "medians": {s: statistics.median(v)
                                      for s, v in cols.items()},
                          "values": cols}))
    for s, rs in sorted(runs.items()):
        print(json.dumps({"set": s, "correct": [r["correct"] for r in rs],
                          "compared": [r["compared"]["widest"]["value"]
                                       for r in rs]}))


if __name__ == "__main__":
    main(sys.argv[1])
