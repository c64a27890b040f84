"""Per-request lifecycle spans: submit -> admit -> chunk(i) ->
first-token -> handoff(export/import/commit) -> finish.

A :class:`SpanRecorder` collects a flat list of :class:`Span` records
(phases with begin/end, plus instantaneous events), each carrying BOTH
a wall-clock timestamp (``perf_counter``, for Chrome-trace export) and
the recorder's step clock (engine steps for single-engine phases,
cluster steps for handoffs) — so a disaggregated request's TTFT
decomposes into queue / prefill / handoff / decode with step
granularity.

Requests migrate across replicas (tier handoff re-submits under a new
rid), so spans key on a *stable* request identity: the engine stamps
``req._span_rid`` at first submit and every later phase reuses it.
The recorder is shared group-wide (one per ReplicaGroup, one per
standalone engine), so the export/import halves of a handoff land in
the same trace row.

Disabled recorders (built from a disabled registry) drop everything at
the method guard — same zero-cost discipline as the tracer.

:class:`StepPhases` is the engine's host phase clock: per named phase of
an engine step a ``perf_counter_ns`` sum and a count, always on, and a
``jax.profiler.TraceAnnotation`` of the same name, so a profiled run
shows each phase on the profiler's clock beside the device ops.
"""

from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

#: canonical phase order for TTFT decomposition
PHASES = ("queue", "prefill", "handoff", "decode")


@dataclass
class Span:
    rid: str                      # stable request identity
    name: str                     # phase or event name
    replica: int
    start_step: int
    start_ts: float               # perf_counter seconds
    end_step: Optional[int] = None
    end_ts: Optional[float] = None
    meta: Dict[str, object] = field(default_factory=dict)

    @property
    def open(self) -> bool:
        return self.end_ts is None

    @property
    def duration_steps(self) -> Optional[int]:
        if self.end_step is None:
            return None
        return self.end_step - self.start_step

    @property
    def duration_s(self) -> Optional[float]:
        if self.end_ts is None:
            return None
        return self.end_ts - self.start_ts

    def to_dict(self) -> dict:
        return {
            "rid": self.rid, "name": self.name, "replica": self.replica,
            "start_step": self.start_step, "end_step": self.end_step,
            "start_ts": self.start_ts, "end_ts": self.end_ts,
            "duration_steps": self.duration_steps,
            "duration_s": self.duration_s, "meta": dict(self.meta),
        }


class SpanRecorder:
    """Flat span store with (rid, name)-keyed open phases."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        self.spans: List[Span] = []
        self._open: Dict[tuple, Span] = {}

    # -- recording ------------------------------------------------------
    def begin(self, rid: str, name: str, *, step: int, replica: int = 0,
              **meta) -> None:
        if not self.enabled:
            return
        key = (rid, name)
        if key in self._open:      # re-entered phase (e.g. re-admit)
            self.end(rid, name, step=step)
        span = Span(rid, name, replica, step, time.perf_counter(),
                    meta=meta)
        self._open[key] = span
        self.spans.append(span)

    def end(self, rid: str, name: str, *, step: int, **meta) -> None:
        if not self.enabled:
            return
        span = self._open.pop((rid, name), None)
        if span is None:
            return
        span.end_step = step
        span.end_ts = time.perf_counter()
        span.meta.update(meta)

    def event(self, rid: str, name: str, *, step: int, replica: int = 0,
              **meta) -> None:
        """Instantaneous point event (chunk staged, token emitted...)."""
        if not self.enabled:
            return
        ts = time.perf_counter()
        self.spans.append(
            Span(rid, name, replica, step, ts, step, ts, meta))

    def end_open(self, rid: str, *, step: int, **meta) -> None:
        """Close every open phase of ``rid`` (finish / branch kill)."""
        if not self.enabled:
            return
        for (r, name) in [k for k in self._open if k[0] == rid]:
            self.end(rid, name, step=step, **meta)

    # -- reads ----------------------------------------------------------
    def for_request(self, rid: str) -> List[Span]:
        return [s for s in self.spans if s.rid == rid]

    def merge(self, other: "SpanRecorder") -> None:
        self.spans.extend(other.spans)

    def ttft_breakdown(self, rid: str) -> Dict[str, float]:
        """Wall-clock seconds per phase up to the first token, from this
        request's closed phase spans.  A phase absent from the request
        (no handoff, say) reports 0.0."""
        out = {p: 0.0 for p in PHASES}
        for s in self.for_request(rid):
            if s.name in out and s.duration_s is not None:
                out[s.name] += s.duration_s
        return out

    def to_dicts(self) -> List[dict]:
        return [s.to_dict() for s in self.spans]


class _Phase:
    """One open phase: the profiler annotation and the start time."""

    __slots__ = ("clock", "name", "ann", "t0")

    def __init__(self, clock: "StepPhases", name: str) -> None:
        self.clock = clock
        self.name = name
        self.ann = TraceAnnotation(name)

    def __enter__(self) -> None:
        self.ann.__enter__()
        if self.name == self.clock.root:
            self.clock._waited = 0
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        c, dt = self.clock, t1 - self.t0
        c.ns[self.name] = c.ns.get(self.name, 0) + dt
        c.count[self.name] = c.count.get(self.name, 0) + 1
        if self.name in c.waits:
            c._waited += dt
        elif self.name == c.root:
            c.own_ns += dt - c._waited
            c.recent.append((t1 / 1e9, dt - c._waited))


class StepPhases:
    """Host phase clock of one engine.

    ``with phases("serve.plan"): ...`` adds the block's wall time to
    ``ns["serve.plan"]`` and one to ``count["serve.plan"]``, and opens a
    profiler annotation of that name for the block.  Sums are inclusive:
    a phase opened inside another counts in both.  The ``root`` phase
    (the whole step) less the ``waits`` nested in it (blocking reads of
    device results) is the host's own time: ``own_ns`` sums it over the
    steps, and ``recent`` keeps it per step, with the step's end on
    ``perf_counter`` seconds, for the last ``HISTORY`` steps, so that a
    window of steps can be read without a profiler.
    """

    HISTORY = 4096

    def __init__(self, root: str, waits: Sequence[str] = ()) -> None:
        self.root = root
        self.waits = frozenset(waits)
        self.ns: Dict[str, int] = {}
        self.count: Dict[str, int] = {}
        self.own_ns = 0
        self.recent: Deque[Tuple[float, int]] = collections.deque(
            maxlen=self.HISTORY)
        self._waited = 0  # wait time inside the open root phase

    def __call__(self, name: str) -> _Phase:
        return _Phase(self, name)

    def seconds(self, name: str) -> float:
        return self.ns.get(name, 0) / 1e9

    def ms(self) -> Dict[str, float]:
        """Phase -> total milliseconds."""
        return {k: v / 1e6 for k, v in self.ns.items()}
