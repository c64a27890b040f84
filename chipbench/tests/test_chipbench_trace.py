"""trace_reduce on a hand-made trace, where every number is known."""

from types import SimpleNamespace as NS

import pytest

import _paths  # noqa: F401
import trace_reduce as TR

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms, stats=()):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=list(stats))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(TR.WINDOW, 10, 100),
        ev("engine.step", 10, 40),
        ev("policy.begin_step", 20, 5),
        ev("bench.wait", 60, 30),
    ])])
    ops = [
        ev("fusion.12", 5, 10),        # starts before the window: 5 ms in
        ev("custom-call.3", 30, 10,
           [("long_name", "_paged_kernel.7")]),
        ev("fusion.40", 35, 10),       # overlaps the kernel: union counts
        ev("convert.2", 95, 30),       # runs past the window: 15 ms in
    ]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
        ev("jit_step", 0, 200)]), NS(name=TR.OPS_LINE, events=ops)])
    return [host, dev]


def test_busy_idle_and_window():
    r = TR.reduce_planes(planes())
    assert r.window_s == pytest.approx(0.100)
    # busy: [10,15] + [30,45] + [95,110] = 5 + 15 + 15 ms
    assert r.busy_s == pytest.approx(0.035)
    assert r.idle_share == pytest.approx(0.65)


def test_ops_are_named_stably_and_clipped_to_the_window():
    r = TR.reduce_planes(planes())
    assert r.op_seconds["fusion"] == pytest.approx(0.015)
    assert r.op_seconds["_paged_kernel"] == pytest.approx(0.010)
    assert r.op_seconds["convert"] == pytest.approx(0.015)
    assert r.op_counts["fusion"] == 2


def test_gaps_go_to_the_innermost_host_span():
    r = TR.reduce_planes(planes())
    # gaps: [15,30] inside engine.step (policy.begin_step covers 20-25
    # but the midpoint 22.5 falls in it), [45,95]: midpoint 70 in
    # bench.wait
    assert r.gap_seconds == pytest.approx(
        {"policy.begin_step": 0.015, "bench.wait": 0.050})
    assert r.top_gaps()[0][0] == "bench.wait"


def test_a_trace_without_the_window_span_is_refused():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        TR.reduce_planes(p)


def test_each_roofline_counts_only_its_own_kernel():
    import work
    from metrics import flash_prefill_roofline as flash
    from metrics import paged_attn_roofline as paged
    from metrics._kernels import kernel_seconds

    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(TR.WINDOW, 0, 100)])])
    ops = [
        ev("custom-call.1", 10, 4, [("long_name", "_paged_kernel.3")]),
        ev("custom-call.2", 20, 6, [("long_name", "_kernel.5")]),
        ev("custom-call.3", 30, 4, [("long_name", "_paged_kernel.3")]),
        ev("custom-call.4", 40, 2, [("long_name", "_decode_kernel.1")]),
    ]
    dev = NS(name="/device:TPU:0", lines=[NS(name=TR.OPS_LINE, events=ops)])
    run = NS(reduced=TR.reduce_planes([host, dev]),
             window=NS(trace_p=(0.0, 1.0)),
             probe=NS(steps=[{"t": 0.5, "ctx": [100, 300],
                              "chunk": (0, 128)}]),
             shape=work.Shape(2, 896, 14, 2, 64, 4864, 151936),
             peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert kernel_seconds(run, paged.PATTERN) == (pytest.approx(0.008), 2)
    assert kernel_seconds(run, flash.PATTERN) == (pytest.approx(0.006), 1)
    # one step of each kernel's work over its own mean time per call
    f, b = work.paged_attention(run.shape, [100, 300])
    assert paged.read(run) == pytest.approx(
        100 * work.least_seconds(f, b, run.peak) / 0.004)
    f, b = work.flash_prefill(run.shape, 0, 128)
    assert flash.read(run) == pytest.approx(
        100 * work.least_seconds(f, b, run.peak) / 0.006)
