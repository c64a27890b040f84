#!/usr/bin/env python3
"""Record the small TPU trace that test_chipbench_scopes.py reduces: the
tiny qwen2 cell of the CPU tests on one chip, profiled for a few engine
steps that carry prefill chunks and decode, with the program's own
``serve.*`` phase spans and named scopes in it.  The Python tracer is
off and the ``/host:metadata`` plane (the serialized HLO modules, which
no reduction reads) is left out, so that the file stays well under
1 MB.

    python chipbench/tests/record_scoped_trace.py <out.xplane.pb>
"""

import glob
import shutil
import sys
import tempfile

import _paths  # noqa: F401
import harness
import traffic
from _tiny import tiny
from scopes import _fields
from trace_reduce import WINDOW

STEPS = 6
DROP = ("/host:metadata",)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def drop_planes(space: bytes, names=DROP) -> bytes:
    """The serialized XSpace without the planes named ``names`` (every
    field of an XSpace is length-delimited: planes, errors, warnings,
    hostnames)."""
    out = bytearray()
    for field, v in _fields(space):
        if field == 1 and dict(_fields(v)).get(2, b"").decode() in names:
            continue
        out += _varint(field << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


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
    eng.step()  # admits; the profiled steps carry the first chunks
    jax.block_until_ready(eng.dev.tokens)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW):
        for _ in range(STEPS):
            eng.step()
        jax.block_until_ready(eng.dev.tokens)
    jax.profiler.stop_trace()
    with open(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0],
              "rb") as f:
        space = f.read()
    with open(out, "wb") as f:
        f.write(drop_planes(space))
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
