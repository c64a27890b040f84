"""Reduce a profiler trace by the program's own names: device self time
by named scope, and device idle time by engine phase.

* **Self time.**  The events of a device's ``XLA Ops`` line nest (a
  ``while`` holds the ops of its body); an op's self time is its
  duration less the time its nested ops cover, so that summed self
  times count every device nanosecond once.
* **Scopes.**  The program runs its fused step under ``jax.named_scope``
  (``step.reset``, ``step.chunk_lane``, ``step.admit``, ``step.grow``,
  ``step.decode``, ``step.sample``) and the model's layer under
  ``attn.qkv``, ``attn.kv_write``, ``attn.kernel``, ``mlp`` and
  ``unembed``.  The compiler keeps each op's name stack in its
  metadata, and a TPU profile carries it as the ``tf_op`` stat of the
  op's event *metadata* (``XEventMetadata.stats``), which
  ``jax.profiler.ProfileData`` does not expose: :func:`name_stacks`
  reads it from the file.  An op's scope path is the program scopes in
  its name stack, outermost first (``step.decode/attn.kv_write``); an op
  without one takes its enclosing op's, and an op whose path holds no
  ``step.*`` scope takes its enclosing op's ``step.*`` scope in front.
  An op with no name stack of its own (a scan's ``while`` on a TPU)
  takes the ``step.*`` scope of its first op that has one.  Ops with no
  scope anywhere (on a TPU: the weight casts the compiler hoists out of
  the layer scans, and whole-pool copies between steps) are
  ``UNSCOPED``.
* **Scan slices.**  ``lax.scan`` over the stacked layers slices each
  layer's inputs out of their stack (``.../while/body/dynamic_slice``)
  and writes its outputs back (``.../while/body/dynamic_update_slice``);
  the KV pool rides the serving scans this way.  Those ops, and the
  layout copies the compiler gives them, end their path with the
  pseudo-scopes ``SCAN_XS`` and ``SCAN_YS``.
* **Idle by phase.**  Each stretch of the window with no op running on
  a device goes to the innermost ``serve.*`` host span (the engine's
  phase clock, ``repro.obs.StepPhases``) that covers its midpoint.

The window is the benchmark's ``bench.window`` host span, as in
:mod:`trace_reduce`, which this module leaves as it is.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from trace_reduce import OPS_LINE, WINDOW, _merge, op_name

#: a program scope: a name-stack component the program gives by
#: jax.named_scope
SCOPE = re.compile(r"^(?:(?:step|attn)\.[a-z_]+|mlp|unembed)$")
#: the stat that carries an op's name stack in a TPU profile
NAME_STACK_STAT = "tf_op"
SCAN_XS = "scan.xs"
SCAN_YS = "scan.ys"
_SCAN = {"dynamic_slice": SCAN_XS, "dynamic_update_slice": SCAN_YS}
UNSCOPED = "unscoped"
PHASE_PREFIX = "serve."
NO_PHASE = "outside serve spans"


@dataclasses.dataclass
class Scoped:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    scope_seconds: Dict[str, float]  # self time, averaged over devices
    gap_seconds: Dict[str, float]  # idle time by serve.* phase, averaged
    op_scope_seconds: Dict[tuple, float]  # (scope path, op name) -> s

    def under(self, scope: str) -> float:
        """Self seconds of every op inside ``scope`` (at any depth)."""
        return sum(v for k, v in self.scope_seconds.items()
                   if scope in k.split("/"))

    @property
    def scoped_share(self) -> float:
        """Share of the busy time under a ``step.*`` scope."""
        if self.busy_s <= 0:
            return 0.0
        return sum(v for k, v in self.scope_seconds.items()
                   if k.startswith("step.")) / self.busy_s

    def top_scopes(self, n: int = 16) -> List[list]:
        items = sorted(self.scope_seconds.items(), key=lambda x: -x[1])
        return [[k, v] for k, v in items[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        items = sorted(self.gap_seconds.items(), key=lambda x: -x[1])
        return [[k, v] for k, v in items[:n]]


def scopes_of(stack: str) -> List[str]:
    """The program scopes of a name stack, outermost first, and the scan
    pseudo-scope its last component names.  A ``tf_op`` stat ends in
    ``:<op type>`` (empty for JAX ops), which is cut."""
    head, _, tail = stack.rpartition("/")
    parts = (head + "/" + tail.split(":")[0] if head else
             stack.split(":")[0]).split("/")
    out = [c for c in parts if SCOPE.match(c)]
    if len(parts) >= 3 and parts[-3:-1] == ["while", "body"] and (
            parts[-1] in _SCAN):
        out.append(_SCAN[parts[-1]])
    return out


def _path(own: List[str], parent: Optional[List[str]]) -> List[str]:
    if not parent:
        return own
    if not own:
        return parent
    if not any(c.startswith("step.") for c in own):
        steps = [c for c in parent if c.startswith("step.")]
        return steps[-1:] + own
    return own


def reduce_scopes(planes, stacks: Dict[str, str]) -> Scoped:
    """``stacks``: event name -> the op's name stack
    (``jit(f)/step.decode/while/body/...``; :func:`name_stacks`)."""
    planes = list(planes)
    host = []
    win = []
    for plane in planes:
        if plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if e.name == WINDOW:
                    win.append(span)
                elif e.name.startswith(PHASE_PREFIX):
                    host.append(span)
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    w0, w1, _ = max(win, key=lambda s: s[1] - s[0])
    host.sort(key=lambda s: s[0])
    scope_s: Dict[str, float] = defaultdict(float)
    op_scope_s: Dict[tuple, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    busy: List[float] = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        line = next((l for l in plane.lines if l.name == OPS_LINE), None)
        if line is None:
            continue
        evs = []
        for e in line.events:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if t > s:
                evs.append((s, t, e))
        evs.sort(key=lambda x: (x[0], -x[1]))
        stack: list = []  # open ops: [end, path, self_ns, name]
        done = []
        for s, t, e in evs:
            while stack and stack[-1][0] <= s:
                done.append(stack.pop())
            parent = stack[-1] if stack else None
            if parent is not None:
                parent[2] -= min(t, parent[0]) - s
            own = scopes_of(stacks.get(e.name, ""))
            path = _path(own, parent[1] if parent is not None else None)
            if parent is not None and not parent[1]:
                parent[1] = [c for c in own if c.startswith("step.")][:1]
            stack.append([t, path, t - s, op_name(e)])
        done.extend(stack)
        for _, path, self_ns, name in done:
            key = "/".join(path) or UNSCOPED
            scope_s[key] += self_ns / 1e9
            op_scope_s[(key, name)] += self_ns / 1e9
        merged = _merge([(s, t) for s, t, _ in evs])
        busy.append(sum(t - s for s, t in merged) / 1e9)
        edge = w0
        for s, t in merged + [(w1, w1)]:
            if s > edge:
                gaps[_cover(host, (edge + s) / 2)] += (s - edge) / 1e9
            edge = max(edge, t)
    if not busy:
        raise ValueError("the trace holds no TPU device plane with ops")
    n = len(busy)
    return Scoped(
        window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / n, devices=n,
        scope_seconds={k: v / n for k, v in scope_s.items()},
        gap_seconds={k: v / n for k, v in gaps.items()},
        op_scope_seconds={k: v / n for k, v in op_scope_s.items()})


def _cover(spans, t: float) -> str:
    """The innermost (latest-starting) serve.* span covering ``t``."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or NO_PHASE


def reduce_file(path: str) -> Scoped:
    from jax.profiler import ProfileData

    return reduce_scopes(ProfileData.from_file(path).planes,
                         name_stacks(path))


# ---------------------------------------------------------------------------
# the name stacks, read from the profile's event metadata
# ---------------------------------------------------------------------------
def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of a protobuf message: ints for varints,
    bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def name_stacks(path: str) -> Dict[str, str]:
    """Event name -> the ``tf_op`` string of its event metadata, for the
    TPU planes of an ``.xplane.pb`` (XSpace: planes 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata: name 2, stats 5;
    XStatMetadata: name 2; XStat: metadata_id 1, str_value 5)."""
    out: Dict[str, str] = {}
    with open(path, "rb") as f:
        space = f.read()
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_id = "", [], None
        for g, v in _fields(plane):
            if g == 2:
                name = v.decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                if meta.get(2, b"").decode() == NAME_STACK_STAT:
                    stat_id = meta.get(1, 0)
        if not name.startswith("/device:TPU:") or stat_id is None:
            continue
        for entry in events:
            meta = dict(_fields(entry)).get(2, b"")
            ev_name, value = "", None
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = v.decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) == stat_id and 5 in st:
                        value = st[5].decode()
            if value:
                out[ev_name] = value
    return out


# ---------------------------------------------------------------------------
# the per-layer numbers a traced run reads from the reduction
# ---------------------------------------------------------------------------
def decode_device_ms_per_step(sc: Scoped, steps: int) -> Optional[float]:
    """Self time under ``step.decode`` per traced engine step."""
    v = sc.under("step.decode")
    return 1e3 * v / steps if steps and v > 0 else None


def chunk_lane_device_ms_per_chunk(sc: Scoped,
                                   chunk_steps: int) -> Optional[float]:
    """Self time under ``step.chunk_lane`` per traced step that carried a
    prefill chunk."""
    v = sc.under("step.chunk_lane")
    return 1e3 * v / chunk_steps if chunk_steps and v > 0 else None


def kv_write_device_share(sc: Scoped) -> Optional[float]:
    """Self time under ``attn.kv_write``, and under the scans' slices of
    their stacked layer inputs and outputs (``scan.xs``, ``scan.ys``: on
    the serving scans, the KV pool's layer slabs and their layout
    copies), over the device's busy time, in %."""
    v = sum(sc.under(s) for s in ("attn.kv_write", SCAN_XS, SCAN_YS))
    return 100.0 * v / sc.busy_s if sc.busy_s > 0 and v > 0 else None


def host_ms_per_step(recent: Sequence[tuple], window: tuple,
                     skip: tuple) -> Optional[float]:
    """Mean host own time (ms) of the steps that ended inside ``window``
    and outside ``skip`` (both ``(start, end)`` on ``perf_counter``
    seconds); ``recent`` is ``StepPhases.recent``."""
    lo, hi = window
    ns = [v for t, v in recent
          if lo <= t <= hi and not skip[0] <= t <= skip[1]]
    return 1e-6 * sum(ns) / len(ns) if ns else None
