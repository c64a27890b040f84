#!/usr/bin/env python3
"""One traced window of a cell, reduced by the program's own names: the
device's self time by named scope, its idle time by engine phase, and
the per-layer numbers read from them, beside the cell's per-layer
metrics as the benchmark reads them.

    python chipbench/scope_trace.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep <out.xplane.pb>]

The window is driven exactly as a traced benchmark run drives it
(``harness.drive``); no check runs.  The last line of standard output
is one JSON object.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import jax

    import harness
    import scopes
    import traffic
    import work

    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    vocab = harness.model_config(cell.config).vocab_size
    items = traffic.generate(cell.mix, args.seed, args.seconds, vocab,
                             cell.cell.get("rate_per_s"))
    _, eng = harness.build_engine(cell, args.seed)
    harness.warm_up(eng, items, vocab)
    jax.block_until_ready(eng.dev.tokens)
    setup_s = time.perf_counter() - STARTED
    probe = harness.Probe(eng)
    d = tempfile.mkdtemp(prefix="chipbench-scopes-")
    win = harness.drive(eng, cell, items, args.seconds, clock, probe, d)
    if args.keep:
        shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0],
                    args.keep)
    reduced, sc = harness.read_trace(d)

    run = harness.Run(cell, win, probe, reduced, cell.arch.shape(cell.config),
                      work.peaks(devs[0].device_kind), eng.spans, sc)
    layer = {}
    for m in cell.per_layer:
        v = harness.load_reader(m["name"]).read(run)
        if v is not None:
            layer[m["name"]] = v
    t0, t1 = win.trace_p
    traced = [s for s in probe.steps if t0 <= s["t"] <= t1]
    own = {
        "host_ms_per_step": scopes.host_ms_per_step(
            eng.phases.recent, (win.p0, win.p0 + win.seconds),
            win.trace_p),
        "decode_device_ms_per_step": scopes.decode_device_ms_per_step(
            sc, len(traced)),
        "chunk_lane_device_ms_per_chunk":
            scopes.chunk_lane_device_ms_per_chunk(
                sc, sum(1 for s in traced if s["chunk"])),
        "kv_write_device_share": scopes.kv_write_device_share(sc),
    }
    top_ops = sorted(sc.op_scope_seconds.items(), key=lambda x: -x[1])
    out = {
        "cell": args.workload, "seed": args.seed, "setup_s": setup_s,
        "steps": win.steps, "traced_steps": len(traced),
        "window_compiles": win.compiles,
        "busy_s": reduced.busy_s, "window_s": reduced.window_s,
        "scoped_share": sc.scoped_share,
        "scope_busy_s": sum(sc.scope_seconds.values()),
        "metrics": layer, "scope_metrics": own,
        "device_scopes": sc.top_scopes(40),
        "idle_by_phase": sc.top_gaps(),
        "device_ops": reduced.top_ops(),
        "idle_gaps": reduced.top_gaps(),
        "scope_ops": [[k[0], k[1], v] for k, v in top_ops[:40]],
        "phase_ms": eng.phases.ms(),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
