#!/usr/bin/env python3
"""Record a small TPU trace for a test of trace_reduce on a recorded
trace: the tiny qwen2 cell of the CPU tests, served on one chip for a
dozen engine steps with the benchmark's host spans, profiled.

    python chipbench/tests/record_trace.py <out.xplane.pb>
"""

import glob
import shutil
import sys
import tempfile

import _paths  # noqa: F401
import harness
import traffic
from _tiny import tiny
from trace_reduce import WINDOW


def main(out: str) -> None:
    import jax

    harness.require_chips(1)
    harness.enable_compile_cache()
    cell = harness.load_cell("qwen2-0.5b.chat")
    tiny(cell)
    items = traffic.generate(cell.mix, 5, 4.0, 512, 2.0)
    _, eng = harness.build_engine(cell, 5)
    harness.warm_up(eng, items, 512)
    for it in items[:4]:
        eng.submit(it.prompt, max_new_tokens=it.max_new,
                   sample_key=it.sample_key)
    for _ in range(3):
        eng.step()
    jax.block_until_ready(eng.dev.tokens)
    d = tempfile.mkdtemp()
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(d)
    with ann(WINDOW):
        for _ in range(12):
            with ann("engine.step"):
                eng.step()
        jax.block_until_ready(eng.dev.tokens)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], out)


if __name__ == "__main__":
    main(sys.argv[1])
