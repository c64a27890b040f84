"""Scheduler queue: 95th percentile over the window's requests of the
time from due to admission (the engine's SpanRecorder ``queue`` span
ends at admission; the request's due time is on the same clock)."""

import math

from harness import p95


def read(run):
    spans = run.spans
    if spans is None or not spans.enabled:
        return None
    admitted = {}
    for s in spans.spans:
        if s.name == "queue" and s.end_ts is not None:
            admitted[s.rid] = s.end_ts
    win = run.window
    waits = []
    for _, req, due in win.submitted:
        rid = getattr(req, "_span_rid", None)
        at = admitted.get(rid)
        # due is on time.time(); spans on perf_counter: shift by the
        # window's start on both clocks
        waits.append(math.inf if at is None else
                     (at - win.p0) - (due - win.t0))
    if not waits:
        return None
    return 1e3 * p95(waits)
