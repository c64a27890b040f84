"""One run of one cell: set-up, the measured window, the metrics and the
check.  Everything a cell needs is data found by name:

* ``BENCHMARK.json``: the cell (configuration, traffic, chips) and the
  metrics;
* ``chipbench/configs/<config>.json``: the model (published keys), its
  engine settings;
* ``chipbench/traffic/<mix>.json``: the traffic mix, read by
  :mod:`chipbench.traffic`;
* ``chipbench/cells/<workload>.json``: the cell's offered rate, engine
  settings of its own, and the limit of its check;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

The window drives the program's served path only: ``ServingEngine.submit``
and ``ServingEngine.step``.  Requests are timed from the moment they were
due.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

#: published key -> ModelConfig field of the program
MODEL_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}
#: seconds after the window to wait for the first token of a request
#: that was due inside it; a request without one by then has failed
GRACE_S = 60.0
#: the part of a traced run's window that the profiler records
TRACE_S = 2.0
HOST_SPANS = {"engine.step", "policy.begin_step", "policy.complete_step",
              "engine.read_back", "bench.submit", "bench.wait"}


class NoChip(SystemExit):
    pass


# ---------------------------------------------------------------------------
# the cell, as data
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    cell: dict
    chips: int
    per_layer: List[dict]
    end_to_end: List[dict]

    @property
    def engine_kw(self) -> dict:
        kw = dict(self.config["engine"])
        kw.update(self.cell.get("engine", {}))
        return kw


def _json(path: Path) -> dict:
    if not path.is_file():
        raise ValueError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ValueError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name,
        config=_json(root / cfg["file"]),
        mix=traffic.load_mix(entry["traffic"]),
        cell=_json(HERE / "cells" / f"{name}.json"),
        chips=entry["chips"],
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])],
        end_to_end=[m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])],
    )


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: its ``arch``
    with every published key the file gives; refuses a mismatch."""
    from repro.configs import ARCHS

    over = {MODEL_KEYS[k]: v for k, v in config.items() if k in MODEL_KEYS}
    cfg = ARCHS[config["arch"]].scaled(**over)
    for k, field in MODEL_KEYS.items():
        if k in config and getattr(cfg, field) != config[k]:
            raise ValueError(f"{k}: program runs {getattr(cfg, field)}, "
                             f"the configuration states {config[k]}")
    if cfg.resolved_head_dim * cfg.num_heads != cfg.d_model:
        raise ValueError("head_dim is not hidden_size / heads")
    return cfg


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------
def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX backend is "
                     f"{devs[0].platform!r}); the benchmark runs on the "
                     f"chip only")
    if len(devs) < n:
        raise NoChip(f"chipbench: the cell needs {n} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (a fixed path inside
    the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says), caching
    every program so that only a cell's first run in a checkout
    compiles."""
    import jax

    from repro.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts executables built or loaded (XLA compiles and persistent
    cache loads) and seconds spent, from jax.monitoring."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def build_engine(cell: Cell, seed: int, device=None):
    import jax

    from repro.models import Model
    from repro.serving import ServingEngine
    import weights as W

    cfg = model_config(cell.config)
    model = Model(cfg)
    params = W.make_weights(model.abstract_params(), seed, cfg.d_model,
                            device)
    jax.block_until_ready(params)
    s = cell.mix["sampling"]
    eng = ServingEngine(model, params=params, temperature=s["temperature"],
                        top_p=s["top_p"], device=device, **cell.engine_kw)
    return model, eng


def _serve_all(eng, prompts, max_new) -> None:
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    while not all(r.done for r in reqs):
        eng.step()


def warm_up(eng, items: List[traffic.Item], vocab: int) -> None:
    """Run, through the served path, one request per KV-sweep bucket the
    cell's traffic reaches (each bucket with and without a prefill chunk
    riding the step) and, where the prefix cache is on, one prefix hit
    per hit length the traffic can make; then drain the engine so that
    the window starts from an empty pool and an empty cache."""
    rng = np.random.default_rng(0)
    block = eng.block
    longest = max(len(it.prompt) + it.max_new for it in items)
    need = (longest - 1) // block + 1
    top = min(1 << (need - 1).bit_length(), eng.mb)
    b = 1
    while b <= top:
        n = min(block * b - block // 2, eng.max_seq - 8)
        _serve_all(eng, [rng.integers(1, vocab, n).tolist()], 4)
        b *= 2
    if eng.prefix_cache.max_entries:
        hits = (max(len(it.prompt) for it in items) - 1) // block
        base = rng.integers(1, vocab, hits * block + 8).tolist()
        _serve_all(eng, [base], 1)
        for k in range(1, hits + 1, eng.max_slots):
            _serve_all(eng, [base[: j * block] + rng.integers(
                1, vocab, 16).tolist()
                for j in range(k, min(k + eng.max_slots, hits + 1))], 4)
    eng.drain()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
class Probe:
    """What a traced run records beside the program: host spans around
    the engine's step, the policy's calls and the token read-back (on
    the instance; the program is not edited), and per step the work it
    dispatched."""

    def __init__(self, eng) -> None:
        import jax

        self.eng = eng
        self.active = True  # cleared when the window closes
        self.tracing = False  # set while the profiler records
        self.policy_s = 0.0  # outside the profiled stretch
        self.steps: List[dict] = []
        self.unreclaimed: List[int] = []
        ann = jax.profiler.TraceAnnotation
        pool, dev = eng.pool, eng.dev

        def timed(fn, name, acc):
            def wrapper(*a, **k):
                t = time.perf_counter()
                with ann(name):
                    out = fn(*a, **k)
                if acc and self.active and not self.tracing:
                    self.policy_s += time.perf_counter() - t
                return out
            return wrapper

        pool.begin_step = timed(pool.begin_step, "policy.begin_step", True)
        pool.complete_step = timed(pool.complete_step,
                                   "policy.complete_step", True)
        eng._complete_oldest = timed(eng._complete_oldest,
                                     "engine.read_back", False)
        dispatch = dev.dispatch

        def record(tf, grow, n_kv):
            if self.active:
                sched = eng.sched
                chunk = None
                ck = dev._pending_chunk  # (slot, tokens, start, row,
                if ck is not None:       #  pages, is_last, last_index, .)
                    start, width = int(ck[2]), len(ck[1])
                    chunk = (start, start + (int(ck[6]) + 1 if ck[5]
                                             else width))
                self.steps.append({
                    "t": time.perf_counter(), "chunk": chunk, "n_kv": n_kv,
                    "ctx": [int(sched.lengths[s]) + 1
                            for s in sched.active]})
            return dispatch(tf, grow, n_kv)

        dev.dispatch = record


@dataclasses.dataclass
class Window:
    seconds: float
    t0: float  # time.time() at the window's start
    p0: float  # perf_counter() at the window's start
    submitted: List[tuple]  # (item, request, due wall time)
    steps: int = 0
    step_s: float = 0.0  # engine.step wall time outside the profiled stretch
    compiles: int = 0
    late_s: float = 0.0  # how late the generator submitted, at most
    waiting_at_close: int = 0  # requests due and not yet admitted
    # the profiled stretch, from before start_trace to after stop_trace
    # (perf_counter), and the engine steps taken in it: the host-timed
    # per-layer metrics leave both out, so that the profiler's cost does
    # not show in them
    trace_p: tuple = (0.0, 0.0)
    traced_steps: int = 0

    @property
    def untraced(self) -> tuple:
        """(seconds, engine steps) of the window outside the profiled
        stretch."""
        return (self.seconds - (self.trace_p[1] - self.trace_p[0]),
                self.steps - self.traced_steps)


def drive(eng, cell: Cell, items: List[traffic.Item], seconds: float,
          clock: CompileClock, probe: Optional[Probe],
          trace_dir: Optional[str]) -> Window:
    import jax

    ann = jax.profiler.TraceAnnotation
    kind = cell.mix["kind"]
    outstanding = (cell.mix.get("outstanding_per_slot", 0)
                   * eng.max_slots)
    c0 = clock.count
    t0, p0 = time.time(), time.perf_counter()
    win = Window(seconds, t0, p0, [])
    s0 = eng.steps
    pending = list(items)
    nxt = 0
    trace_at = seconds / 2 - TRACE_S / 2 if trace_dir else None
    win_ann = None  # open while the profiler records
    traced_from = (0.0, 0)

    def submit(it, due_wall):
        req = eng.submit(it.prompt, max_new_tokens=it.max_new,
                         sample_key=it.sample_key)
        win.submitted.append((it, req, due_wall))

    def stop_trace():
        win_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        win.trace_p = (traced_from[0], time.perf_counter())
        win.traced_steps = eng.steps - traced_from[1]
        if probe is not None:
            probe.tracing = False

    if kind == "backlog":
        for it in pending[:outstanding]:
            submit(it, t0)
        nxt = outstanding
    done_seen = len(eng.sched.finished)
    while True:
        now = time.time()
        el = now - t0
        if el >= seconds:
            break
        if trace_at is not None and el >= trace_at:
            trace_at = None
            traced_from = (time.perf_counter(), eng.steps)
            if probe is not None:
                probe.tracing = True
            jax.profiler.start_trace(trace_dir)
            win_ann = ann("bench.window")
            win_ann.__enter__()
            trace_end = el + TRACE_S
        if win_ann is not None and el >= trace_end:
            stop_trace()
            win_ann = None
        if kind == "backlog":
            fin = len(eng.sched.finished)
            while done_seen < fin and nxt < len(pending):
                submit(pending[nxt], now)
                nxt += 1
                done_seen += 1
            done_seen = fin
        else:
            while nxt < len(pending) and pending[nxt].due <= el:
                with ann("bench.submit"):
                    win.late_s = max(win.late_s, el - pending[nxt].due)
                    submit(pending[nxt], t0 + pending[nxt].due)
                nxt += 1
        if eng.sched.has_work():
            ts = time.perf_counter()
            with ann("engine.step"):
                eng.step()
            if win_ann is None:
                win.step_s += time.perf_counter() - ts
            if probe is not None:
                probe.unreclaimed.append(eng.pool.unreclaimed())
        else:
            wake = pending[nxt].due if nxt < len(pending) else seconds
            with ann("bench.wait"):
                time.sleep(max(0.0, min(wake, seconds) - el))
    win.steps = eng.steps - s0
    win.compiles = clock.count - c0
    win.waiting_at_close = len(eng.sched.waiting)
    if probe is not None:
        probe.active = False
    if win_ann is not None:  # a window shorter than the traced part
        stop_trace()
    # every request due in the window gets its first token or fails
    end = t0 + seconds + GRACE_S
    while (any(not r.first_token_at for _, r, _ in win.submitted)
           and eng.sched.has_work() and time.time() < end):
        eng.step()
    return win


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def p95(values) -> float:
    """The 95th percentile, linear between order statistics; a failed
    request (inf) counts as later than every other."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.inf
    x = (v.size - 1) * 0.95
    lo, hi = v[int(math.floor(x))], v[int(math.ceil(x))]
    if math.isinf(hi):
        return math.inf
    return float(lo + (hi - lo) * (x - math.floor(x)))


def end_to_end(win: Window) -> Dict[str, float]:
    lo, hi = win.t0, win.t0 + win.seconds
    tokens = 0
    tpot, ttft = [], []
    for _, req, due in win.submitted:
        times = [t for t in req.token_times if lo <= t <= hi]
        tokens += len(times)
        if len(times) >= 2:
            tpot.append((times[-1] - times[0]) / (len(times) - 1))
        ttft.append(req.first_token_at - due if req.first_token_at
                    else math.inf)
    return {"tokens_per_s": tokens / win.seconds,
            "ttft_p95_ms": p95(ttft) * 1e3,
            "tpot_p95_ms": p95(tpot) * 1e3}


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What the per-layer readers read."""

    cell: Cell
    window: Window
    probe: Optional[Probe]
    reduced: Any
    shape: work.Shape
    peak: dict
    spans: Any


def load_reader(name: str):
    return importlib.import_module(f"metrics.{name}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             chips_required: bool = True, config_override=None,
             fault=None, control: bool = False, started=None,
             out=sys.stderr) -> dict:
    """One run; returns the result line's object.  ``config_override``
    and ``fault`` are for the benchmark's own tests and tools (a smaller
    model, another rate, a broken timed path); ``control`` also reads the
    float8 control on the same served tokens (``result["control"]``).
    ``started`` is the process's start on ``perf_counter``: set-up is
    counted from there."""
    t_start = time.perf_counter() if started is None else started
    cell = load_cell(name)
    if config_override:
        config_override(cell)
    import jax

    devs = (require_chips(cell.chips) if chips_required
            else jax.devices()[: cell.chips])
    enable_compile_cache()
    clock = CompileClock()
    cfg = model_config(cell.config)
    vocab = cfg.vocab_size
    items = traffic.generate(cell.mix, seed, seconds, vocab,
                             cell.cell.get("rate_per_s"))
    model, eng = build_engine(cell, seed)
    if fault is not None:
        fault(eng)
    warm_up(eng, items, vocab)
    jax.block_until_ready(eng.dev.tokens)
    setup_s = time.perf_counter() - t_start
    stats0 = eng.stats()
    probe = Probe(eng) if trace else None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    win = drive(eng, cell, items, seconds, clock, probe, trace_dir)
    grace_s = time.perf_counter() - win.p0 - seconds
    stats1 = eng.stats()
    e2e = end_to_end(win)
    e2e["setup_s"] = setup_s
    dev = devs[0]
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devs)

    reduced = None
    if trace and chips_required:
        import glob
        import shutil

        from trace_reduce import reduce_file

        files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
        if files:
            reduced = reduce_file(files[0], HOST_SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the check: a sample of finished requests against the reference,
    # once the program's state is freed
    sample = check.pick(win.submitted, seed, cell.cell["check"]["requests"])
    served = [(list(it.prompt), list(map(int, req.generated)),
               it.sample_key) for it, req, _ in sample]
    spans = eng.spans
    run = Run(cell, win, probe, reduced,
              work.Shape.from_config(cell.config),
              work.peaks(dev.device_kind) if chips_required else
              {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}, spans)
    layer_values = {}
    if trace:
        for m in cell.per_layer:
            v = load_reader(m["name"]).read(run)
            if v is not None:
                layer_values[m["name"]] = (v, m["unit"])
    del eng, model, run, probe
    gc.collect()
    s = cell.mix["sampling"]
    reading = check.widest(cell, seed, served, s["temperature"],
                           s["top_p"])
    check_s = time.perf_counter() - win.p0 - seconds - grace_s
    limit = cell.cell["check"]["limit"]
    ctl = (check.widest(cell, seed, served, s["temperature"], s["top_p"],
                        precision="fp8") if control else None)
    failed = sum(1 for _, r, _ in win.submitted if not r.first_token_at)
    attempted = len(win.submitted)
    correct = (limit is not None and reading["tokens"] > 0
               and reading["widest"] <= limit)
    compared = {"widest": {"value": reading["widest"], "limit": limit},
                "tokens_compared": {"value": reading["tokens"],
                                    "limit": "> 0"}}

    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if trace:
        metrics = {k: {"value": v, "unit": u}
                   for k, (v, u) in layer_values.items()}
    else:
        metrics = {k: {"value": e2e[k], "unit": units[k]} for k in units}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.top_gaps()}
    if ctl is not None:
        result["control"] = ctl["widest"]
    result["window"] = {"steps": win.steps, "compiles": win.compiles,
                        "waiting_at_close": win.waiting_at_close,
                        "generator_late_s_max": win.late_s}
    result["compared"] = compared
    print(f"[run] cell={name} seed={seed} seconds={seconds} trace={trace} "
          f"setup_s={setup_s:.3f} grace_s={grace_s:.3f} check_s={check_s:.3f} "
          f"steps={win.steps} "
          f"window_compiles={win.compiles} generator_late_s_max="
          f"{win.late_s:.4f} waiting_at_close={win.waiting_at_close} "
          f"attempted={attempted} failed={failed} "
          f"e2e={json.dumps(e2e)} stats_delta="
          f"{json.dumps(_delta(stats0, stats1))}", file=out)
    if reduced is not None:
        ops = sorted(reduced.op_seconds.items(), key=lambda x: -x[1])
        print("[trace] window_s=%r busy_s=%r ops=%s" % (
            reduced.window_s, reduced.busy_s, json.dumps(
                [[k, v, reduced.op_counts[k]] for k, v in ops])), file=out)
    print(f"[check] widest={reading['widest']!r} limit={limit!r} "
          f"tokens_compared={reading['tokens']} "
          f"requests={len(served)} kind={reading['kind']}", file=out,
          flush=True)
    return result


def _delta(a: dict, b: dict) -> dict:
    keys = ("steps", "admissions", "prefill_chunks", "backpressure_syncs",
            "prefix_hits", "prefix_misses", "tokens_emitted",
            "scan_steps")
    return {k: b[k] - a[k] for k in keys if k in a and k in b}
