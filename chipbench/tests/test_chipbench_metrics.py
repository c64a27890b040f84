"""End-to-end metrics are taken over every request of the window: a
stall injected into a synthetic window moves them, and a request that
never gets a token counts as infinitely late."""

import math
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
import harness


def _req(due, first, gap, n):
    times = [first + i * gap for i in range(n)] if first else []
    return SimpleNamespace(token_times=times,
                           first_token_at=first or 0.0, done=bool(first))


def _window(reqs, seconds=10.0):
    win = harness.Window(seconds, t0=1000.0, p0=0.0, submitted=[])
    for due, first, gap, n in reqs:
        win.submitted.append((None, _req(due, first, gap, n), due))
    return win


def _steady(n=40, stall_at=None, stall=0.0):
    out = []
    for i in range(n):
        due = 1000.0 + 0.2 * i
        first = due + 0.05 + (stall if stall_at is not None
                              and i >= stall_at else 0.0)
        out.append((due, first, 0.01, 20))
    return out


def test_steady_window():
    e = harness.end_to_end(_window(_steady()))
    assert e["ttft_p95_ms"] == pytest.approx(50.0)
    assert e["tpot_p95_ms"] == pytest.approx(10.0)
    # 40 requests x 20 tokens, all inside the 10 s window
    assert e["tokens_per_s"] == pytest.approx(80.0)


def test_a_stall_moves_the_tail_and_the_rate():
    base = harness.end_to_end(_window(_steady()))
    # the last 10% of requests wait 2 s behind a stall
    hit = harness.end_to_end(_window(_steady(stall_at=36, stall=2.0)))
    assert hit["ttft_p95_ms"] > base["ttft_p95_ms"] + 1000
    # their tokens now land after the window closes
    assert hit["tokens_per_s"] < base["tokens_per_s"]


def test_a_failed_request_is_infinitely_late():
    reqs = _steady(19) + [(1003.0, 0.0, 0.0, 0)]
    e = harness.end_to_end(_window(reqs))
    assert math.isinf(e["ttft_p95_ms"])
    assert harness.p95([1.0] * 99 + [math.inf]) == 1.0


def test_tokens_outside_the_window_are_not_counted():
    e = harness.end_to_end(_window([(1000.0, 1009.5, 0.1, 10)]))
    # 1009.5 .. 1010.0 inclusive: 6 of the 10 tokens
    assert e["tokens_per_s"] == pytest.approx(0.6)
