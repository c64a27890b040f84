#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip, in one process:
the program's number on each seed and, on the first ``--controls`` of
them, the float8 control's number on the same served tokens.

    python chipbench/readings.py --workload <cell> --seconds <s> \\
        --seeds 101,102,... [--controls 3] [--set-limit]

The window is short and at the cell's own load; every seed makes its
own weights and traffic.  One JSON line per seed on standard output,
then the two readings: the lower (the program's largest) and the upper
(the control's smallest).  ``--set-limit`` writes the limit into
``chipbench/cells/<cell>.json``, ``SHARE`` of the way from the lower
reading to the upper, where the upper is at least ``SEPARATION`` times
the lower; otherwise it writes nothing and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

#: the limit lies this share of the way from the lower reading to the
#: upper: more room above the lower (fresh seeds read higher) than below
#: the upper
SHARE = 0.6
SEPARATION = 3.0


def limit_between(lower: float, upper: float):
    """The limit from the two readings, or None where they do not
    separate."""
    if upper < SEPARATION * lower or upper <= 0:
        return None
    return lower + SHARE * (upper - lower)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--set-limit", action="store_true")
    args = ap.parse_args(argv)

    import harness

    program, control = [], []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             control=i < args.controls)
        program.append(r["compared"]["widest"]["value"])
        if r.get("control") is not None:
            control.append(r["control"])
        print(json.dumps({"seed": seed,
                          "program": program[-1],
                          "control": r.get("control"),
                          "tokens": r["compared"]["tokens_compared"]["value"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    lower = max(program)
    upper = min(control) if control else None
    limit = limit_between(lower, upper) if upper is not None else None
    print(json.dumps({"lower": lower, "upper": upper, "limit": limit}),
          flush=True)
    if args.set_limit:
        if limit is None:
            print("readings: the control does not separate from the "
                  "program; no limit written", file=sys.stderr)
            return 1
        path = HERE / "cells" / f"{args.workload}.json"
        cell = json.loads(path.read_text())
        cell["check"]["limit"] = round(limit, 4)
        path.write_text(json.dumps(cell, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
