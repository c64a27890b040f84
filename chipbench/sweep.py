#!/usr/bin/env python3
"""Find a rate cell's knee once, on the chip: run the cell at each
offered rate, in ascending order, and print its end-to-end metrics and
how many requests were due and not yet admitted when the window closed.

    python chipbench/sweep.py --workload <cell> --seconds <s> \\
        --rates 4,8,12 [--seed n] [--set-rate]

A rate is sustained when no request failed, at most ``BACKLOG_SHARE``
of the window's requests still wait for a slot at its close, and both
tails stay within the cell's ``chat_limits``.  The knee is the highest
rate sustained below the first that is not.  ``--set-rate`` writes 0.8
of the knee into ``chipbench/cells/<cell>.json`` as the cell's fixed
rate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

BACKLOG_SHARE = 0.05
LOAD = 0.8


def sustained(r: dict, limits: dict) -> bool:
    m = r["metrics"]
    return (r["failed"] == 0
            and r["waiting_at_close"] <= BACKLOG_SHARE * r["attempted"]
            and m["ttft_p95_ms"] <= limits["ttft_p95_ms"]
            and m["tpot_p95_ms"] <= limits["tpot_p95_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--set-rate", action="store_true")
    args = ap.parse_args(argv)

    import harness

    limits = harness.load_cell(args.workload).cell["chat_limits"]
    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        def at_rate(cell, rate=rate):
            cell.cell["rate_per_s"] = rate

        r = harness.run_cell(args.workload, args.seed, args.seconds, False,
                             config_override=at_rate)
        row = {"rate_per_s": rate, "failed": r["failed"],
               "attempted": r["attempted"],
               "waiting_at_close": r["window"]["waiting_at_close"],
               "metrics": {k: v["value"] for k, v in r["metrics"].items()}}
        row["sustained"] = sustained(row, limits)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)
    if args.set_rate and knee is not None:
        path = HERE / "cells" / f"{args.workload}.json"
        cell = json.loads(path.read_text())
        cell["rate_per_s"] = round(LOAD * knee, 3)
        path.write_text(json.dumps(cell, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
