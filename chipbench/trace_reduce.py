"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* Device busy time: the union of the intervals in which an operation of
  the device's ``XLA Ops`` line runs, inside the traced window, averaged
  over the devices traced.  Idle share is ``1 - busy / window``.
* Per-operation device time, summed by a stable name: the op's HLO
  category for fusions and XLA ops, the kernel's own name for Pallas
  calls (a custom call whose name or ``long_name`` names the kernel).
* Idle gaps: each stretch of the window with no operation running on a
  device, attributed to the innermost host span (a
  ``jax.profiler.TraceAnnotation`` of the benchmark) that covers its
  midpoint, and summed by that span's name.

The window is the host span ``WINDOW`` that the benchmark opens right
after the trace starts and closes before it stops; host and device
events share the profiler's clock.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    op_seconds: Dict[str, float]  # summed over devices
    op_counts: Dict[str, int]
    gap_seconds: Dict[str, float]  # averaged over devices
    busy_by_device: List[float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[list]:
        items = sorted(self.op_seconds.items(), key=lambda x: -x[1])[:n]
        return [[k, v / self.devices] for k, v in items]

    def top_gaps(self, n: int = 10) -> List[list]:
        items = sorted(self.gap_seconds.items(), key=lambda x: -x[1])[:n]
        return [[k, v] for k, v in items]


def _stats(event) -> dict:
    out = {}
    for s in event.stats:
        try:
            k, v = s
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def op_name(event) -> str:
    """A name that survives recompiles: the custom call's kernel name
    where the event carries one, otherwise the HLO op name with its
    numeric suffix cut (``fusion.123`` -> ``fusion``)."""
    name = event.name
    st = _stats(event)
    for key in ("long_name", "hlo_op", "tf_op"):
        v = st.get(key)
        if isinstance(v, str) and "custom-call" in name and v:
            name = v
            break
    base = name.split(" ")[0].split("=")[0]
    head, _, tail = base.rpartition(".")
    if head and tail.isdigit():
        base = head
    return base


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _host_spans(planes, names: Optional[set]):
    spans = []
    for plane in planes:
        if plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            for e in line.events:
                if names is None or e.name in names or e.name == WINDOW:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def reduce_planes(planes, host_names: Optional[set] = None) -> Reduced:
    planes = list(planes)
    spans = _host_spans(planes, host_names)
    win = [s for s in spans if s[2] == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    w0, w1, _ = max(win, key=lambda s: s[1] - s[0])
    inner = sorted((s for s in spans if s[2] != WINDOW),
                   key=lambda s: s[0])
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    gaps: Dict[str, float] = defaultdict(float)
    busy: List[float] = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        line = next((l for l in plane.lines if l.name == OPS_LINE), None)
        if line is None:
            continue
        iv = []
        for e in line.events:
            s, t = e.start_ns, e.start_ns + e.duration_ns
            s, t = max(s, w0), min(t, w1)
            if t <= s:
                continue
            iv.append((s, t))
            name = op_name(e)
            op_s[name] += (t - s) / 1e9
            op_n[name] += 1
        merged = _merge(iv)
        busy.append(sum(t - s for s, t in merged) / 1e9)
        edge = w0
        for s, t in merged + [(w1, w1)]:
            if s > edge:
                gaps[_cover(inner, (edge + s) / 2)] += (s - edge) / 1e9
            edge = max(edge, t)
    if not busy:
        raise ValueError("the trace holds no TPU device plane with ops")
    n = len(busy)
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / n, devices=n,
        op_seconds=dict(op_s), op_counts=dict(op_n),
        gap_seconds={k: v / n for k, v in gaps.items()},
        busy_by_device=busy)


def _cover(spans, t: float) -> str:
    """The innermost (latest-starting) host span covering ``t``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "host outside benchmark spans"


def reduce_file(path: str, host_names: Optional[set] = None) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, host_names)
