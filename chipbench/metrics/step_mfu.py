"""The whole fused step's share of the chip's bf16 peak: model FLOPs of
every token that the window's steps processed (work.step_flops) over
the summed host wall time of those engine steps (host clock; at
pipeline depth 2 a step's wall time is its dispatch and the wait on an
older step's tokens, so the sum covers the device's work of the steps
between the first and the last).  Steps in the profiled stretch of a
traced run are left out on both sides."""

import work


def read(run):
    p, win = run.probe, run.window
    if p is None or win.step_s <= 0:
        return None
    t0, t1 = win.trace_p
    flops = sum(work.step_flops(run.shape, s["ctx"], s["chunk"])
                for s in p.steps if not t0 <= s["t"] <= t1)
    if not flops:
        return None
    return 100.0 * flops / (win.step_s * run.peak["bf16_flops"])
