"""scopes.reduce_scopes: device self time by the program's named scopes
and idle time by engine phase, on a hand-made trace where every number
is known, and the numbers read from it."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import _paths  # noqa: F401
import scopes as SC
import trace_reduce as TR

MS = 1_000_000  # ns
STEP = "jit(_step_fn)/jit(main)"


def ev(name, start_ms, dur_ms, stack=None):
    e = NS(name=name, start_ns=int(start_ms * MS),
           duration_ns=int(dur_ms * MS), stats=[])
    if stack is not None:
        STACKS[name] = stack
    return e


#: event name -> name stack, as scopes.name_stacks reads them from a file
STACKS = {}


def planes(devices=1):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(TR.WINDOW, 0, 100),
        ev("serve.step", 0, 50),
        ev("serve.read_back", 0, 20),
        ev("serve.read_back.wait", 2, 15),
        ev("engine.step", 0, 50),  # the benchmark's own span: not a phase
    ])])
    body = f"{STEP}/step.decode/while/body"
    ops = [
        # the layer scan: 40 ms, of which its body covers 30; a TPU
        # profile gives a scan's while no name stack of its own
        ev("while.3", 10, 40),
        ev("fusion.7", 12, 4, f"{body}/closed_call/attn.kv_write/scatter:"),
        ev("copy.2", 16, 2),  # no name stack: the enclosing op's scope
        ev("copy.5", 18, 4, f"{body}/dynamic_slice:"),  # the scan's xs
        ev("fusion.8", 22, 8, f"{body}/dynamic_update_slice:"),  # its ys
        ev("paged_attention.1", 30, 6,
           f"{body}/closed_call/attn.kernel/paged_attention/pallas_call:"),
        # a called computation's op whose stack lost the step scope
        ev("fusion.9", 36, 6, "jit(_where)/mlp/add:"),
        ev("argmax.1", 55, 5, f"{STEP}/step.sample/argmax:"),
        ev("convert.4", 90, 20),  # starts in the window, runs past it
    ]
    dev = [NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Modules", events=[ev("jit__step_fn", 0, 200)]),
        NS(name=TR.OPS_LINE, events=list(ops))]) for i in range(devices)]
    return [host] + dev


@pytest.mark.parametrize("devices", [1, 2])
def test_self_time_by_scope(devices):
    sc = SC.reduce_scopes(planes(devices), STACKS)
    assert sc.devices == devices
    assert sc.window_s == pytest.approx(0.100)
    # busy: [10, 50] + [55, 60] + [90, 100]
    assert sc.busy_s == pytest.approx(0.055)
    assert sc.scope_seconds == pytest.approx({
        "step.decode": 0.010 + 0.002,  # the while's own 10 + the copy
        "step.decode/attn.kv_write": 0.004,
        "step.decode/scan.xs": 0.004,
        "step.decode/scan.ys": 0.008,
        "step.decode/attn.kernel": 0.006,
        "step.decode/mlp": 0.006,
        "step.sample": 0.005,
        SC.UNSCOPED: 0.010,
    })
    # self times count each busy nanosecond once
    assert sum(sc.scope_seconds.values()) == pytest.approx(sc.busy_s)
    assert sc.under("step.decode") == pytest.approx(0.040)
    assert sc.under("attn.kv_write") == pytest.approx(0.004)
    assert sc.scoped_share == pytest.approx(0.045 / 0.055)
    assert sc.op_scope_seconds[("step.decode", "copy")] == pytest.approx(
        0.002)
    assert sc.op_scope_seconds[
        ("step.decode/attn.kernel", "paged_attention")] == pytest.approx(
        0.006)
    assert sc.top_scopes(1) == [["step.decode", pytest.approx(0.012)]]


def test_idle_goes_to_the_innermost_serve_phase():
    sc = SC.reduce_scopes(planes(), STACKS)
    # gaps: [0, 10] (midpoint 5: serve.read_back.wait), [50, 55]
    # (52.5: no serve span), [60, 90] (75: none)
    assert sc.gap_seconds == pytest.approx({
        "serve.read_back.wait": 0.010, SC.NO_PHASE: 0.035})
    assert sc.top_gaps()[0][0] == SC.NO_PHASE


@pytest.mark.parametrize("stack,scopes", [
    (f"{STEP}/step.grow/add:", ["step.grow"]),
    (f"{STEP}/step.chunk_lane/while/body/closed_call/attn.qkv/dot_general:",
     ["step.chunk_lane", "attn.qkv"]),
    (f"{STEP}/step.chunk_lane/while/body/dynamic_slice:",
     ["step.chunk_lane", SC.SCAN_XS]),
    (f"{STEP}/step.decode/while/body/dynamic_update_slice",
     ["step.decode", SC.SCAN_YS]),
    # a dynamic slice of the model's own is no scan slice
    (f"{STEP}/step.decode/while/body/closed_call/dynamic_slice:",
     ["step.decode"]),
    ("jit(f)/mlpx/add:", []),
    ("", []),
])
def test_scopes_of_a_name_stack(stack, scopes):
    assert SC.scopes_of(stack) == scopes


def test_a_trace_without_the_window_is_refused():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        SC.reduce_scopes(p, STACKS)


def test_numbers_read_from_the_scopes():
    sc = SC.reduce_scopes(planes(), STACKS)
    assert SC.decode_device_ms_per_step(sc, 4) == pytest.approx(10.0)
    assert SC.chunk_lane_device_ms_per_chunk(sc, 2) is None  # no lane
    # attn.kv_write 4, scan.xs 4, scan.ys 8 of 55 busy
    assert SC.kv_write_device_share(sc) == pytest.approx(100 * 16 / 55)
    assert SC.decode_device_ms_per_step(sc, 0) is None


def test_host_ms_per_step_reads_the_window_outside_the_profiled_part():
    recent = [(0.5, 9_000_000), (1.5, 2_000_000), (2.5, 50_000_000),
              (3.5, 4_000_000), (9.0, 7_000_000)]
    # window [1, 5]; the profiled stretch [2, 3] is left out
    assert SC.host_ms_per_step(recent, (1.0, 5.0), (2.0, 3.0)) == (
        pytest.approx(3.0))
    assert SC.host_ms_per_step(recent, (5.0, 6.0), (0.0, 0.0)) is None


def test_host_ms_per_step_reader_reads_the_engine_phase_clock():
    from metrics import host_ms_per_step

    win = NS(p0=1.0, seconds=4.0, trace_p=(2.0, 3.0))
    eng = NS(phases=NS(recent=[(1.5, 2_000_000), (2.5, 9_000_000)]))
    assert host_ms_per_step.read(NS(probe=NS(eng=eng), window=win)) == (
        pytest.approx(2.0))
    # a program without the phase clock (or an untraced run): nothing
    assert host_ms_per_step.read(NS(probe=NS(eng=NS()), window=win)) is None
    assert host_ms_per_step.read(NS(probe=None, window=win)) is None


@pytest.mark.parametrize("reader,kernel,other", [
    ("paged_attention_roofline", "paged_attention", "flash_attention"),
    ("flash_attention_roofline", "flash_attention", "paged_attention"),
])
def test_kernel_rooflines_find_their_kernel_by_its_stable_name(
        reader, kernel, other):
    import importlib

    import work

    mod = importlib.import_module(f"metrics.{reader}")
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(TR.WINDOW, 0, 100)])])
    ops = [ev(f"%{kernel}.3", 10, 4), ev(f"{kernel}.3", 20, 4),
           ev(f"{other}.1", 30, 6), ev("closed_call.2", 40, 6)]
    dev = NS(name="/device:TPU:0", lines=[NS(name=TR.OPS_LINE, events=ops)])
    run = NS(reduced=TR.reduce_planes([host, dev]),
             window=NS(trace_p=(0.0, 1.0)),
             probe=NS(steps=[{"t": 0.5, "ctx": [100, 300],
                              "chunk": (0, 128)}]),
             shape=work.Shape(layers=2, d_model=896, heads=14,
                              kv_heads=2, head_dim=64, vocab=151936,
                              matmul_params=0),
             peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    f, b = (work.paged_attention(run.shape, [100, 300])
            if kernel == "paged_attention" else
            work.flash_prefill(run.shape, 0, 128))
    assert mod.read(run) == pytest.approx(
        100 * work.least_seconds(f, b, run.peak) / 0.004)


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat", "phi3-mini-4k.l8.batch"])
def test_a_traced_run_reports_the_engine_host_time(cell):
    """host_ms_per_step is a program span: the CPU run reads it (the
    kernel rooflines need the chip's trace and stay silent here)."""
    import harness
    from _tiny import as_cell

    name, override = as_cell(cell)
    r = harness.run_cell(name, 2**33 + 7, 3.0, True, chips_required=False,
                         config_override=override)
    m = r["metrics"]
    assert m["host_ms_per_step"]["value"] > 0
    assert m["host_ms_per_step"]["unit"] == "ms"
    assert "paged_attention_roofline" not in m


# ---------------------------------------------------------------------------
# a trace recorded on a TPU v5e (record_scoped_trace.py): six steps of the
# tiny chat cell, the first ones carrying prefill chunks
# ---------------------------------------------------------------------------
RECORDED = Path(__file__).resolve().parent / "data" / "tiny_scoped.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return SC.reduce_file(str(RECORDED))


def test_the_recorded_trace_is_small():
    assert RECORDED.stat().st_size < 1_000_000


def test_recorded_self_times_cover_the_busy_time(recorded):
    assert recorded.busy_s > 0
    assert sum(recorded.scope_seconds.values()) == pytest.approx(
        recorded.busy_s)
    # what no scope names on the chip: the weight casts hoisted out of
    # the scans and whole-pool copies and zero fills between them
    assert recorded.scoped_share > 0.8


@pytest.mark.parametrize("scope", [
    "step.decode", "step.chunk_lane", "step.grow", "attn.qkv",
    "attn.kv_write", "attn.kernel", "mlp", "unembed", SC.SCAN_XS,
    SC.SCAN_YS])
def test_recorded_trace_has_each_scope(recorded, scope):
    assert recorded.under(scope) > 0


@pytest.mark.parametrize("path,kernel", [
    ("step.decode/attn.kernel", "%paged_attention"),
    ("step.chunk_lane/attn.kernel", "%flash_attention"),
])
def test_recorded_kernels_run_by_name_in_their_scope(recorded, path, kernel):
    assert recorded.op_scope_seconds[(path, kernel)] > 0
    # the benchmark's own reduction finds them by name as well
    reduced = TR.reduce_file(str(RECORDED))
    assert reduced.op_counts[kernel] > 0


def test_recorded_idle_time_falls_in_engine_phases(recorded):
    gaps = recorded.gap_seconds
    assert set(gaps) <= {SC.NO_PHASE} | {
        "serve.step", "serve.read_back", "serve.read_back.wait",
        "serve.admit", "serve.chunk", "serve.plan", "serve.policy.begin",
        "serve.policy.complete", "serve.stage", "serve.dispatch"}
    named = sum(v for k, v in gaps.items() if k != SC.NO_PHASE)
    assert named > gaps.get(SC.NO_PHASE, 0.0)


def test_name_stacks_come_from_the_event_metadata():
    stacks = SC.name_stacks(str(RECORDED))
    kernels = {k: v for k, v in stacks.items()
               if k.startswith("%paged_attention")}
    assert kernels and all(
        "/step.decode/" in v and "/attn.kernel/" in v
        for v in kernels.values())


def test_a_run_keeps_the_traces_scope_reduction_for_readers(tmp_path):
    """harness.read_trace reduces the profile both ways before the
    directory goes, so that a reader finds device time under its own
    scope on ``Run.scoped``."""
    import shutil

    import harness

    d = tmp_path / "trace"
    (d / "plugins" / "profile" / "run").mkdir(parents=True)
    shutil.copy(RECORDED, d / "plugins" / "profile" / "run" / "h.xplane.pb")
    reduced, scoped = harness.read_trace(str(d))
    assert not d.exists()
    want = SC.reduce_file(str(RECORDED))
    assert scoped.scope_seconds == want.scope_seconds
    assert scoped.under("mlp") > 0
    assert reduced.op_seconds == TR.reduce_file(
        str(RECORDED), harness.HOST_SPANS).op_seconds
    run = harness.Run(*[None] * 7, scoped=scoped)
    assert run.scoped.under("step.decode") == want.under("step.decode")
    (tmp_path / "empty").mkdir()
    assert harness.read_trace(str(tmp_path / "empty")) == (None, None)
    assert not (tmp_path / "empty").exists()
