"""Shared by the kernel roofline readers: a kernel's device time in the
traced window against the least time its required work allows."""

from __future__ import annotations

import re
from typing import Callable, Optional

import work


def kernel_seconds(run, pattern: str) -> tuple:
    """(seconds, calls) of trace ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    secs = sum(v for k, v in run.reduced.op_seconds.items() if rx.search(k))
    calls = sum(v for k, v in run.reduced.op_counts.items() if rx.search(k))
    return secs, calls


def traced_steps(run) -> list:
    """The steps dispatched while the profiler ran."""
    t0, t1 = run.window.trace_p
    return [s for s in run.probe.steps if t0 <= s["t"] <= t1]


def roofline(run, pattern: str,
             work_of: Callable[[dict], Optional[tuple]]) -> Optional[float]:
    """Mean least time per call over mean measured time per call, in %.
    ``work_of(step)`` gives one layer's (flops, bytes) of a step, or
    ``None`` where the step does not call the kernel."""
    if run.reduced is None or run.probe is None:
        return None
    secs, calls = kernel_seconds(run, pattern)
    per_step = [work_of(s) for s in traced_steps(run)]
    per_step = [w for w in per_step if w is not None]
    if not calls or not per_step or secs <= 0:
        return None
    least = sum(work.least_seconds(f, b, run.peak) for f, b in per_step)
    return 100.0 * (least / len(per_step)) / (secs / calls)
