"""The engine step traced from inside the program: the host phase clock
(``StepPhases``: per-phase perf_counter sums and profiler annotations)
and the named scopes every op of the fused step carries in its
metadata."""

import re
import time

import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import Model
from repro.obs import StepPhases
from repro.serving import ServingEngine

#: every host phase of a step that serves chunked prompts
PHASES = ("serve.step", "serve.read_back", "serve.read_back.wait",
          "serve.admit", "serve.chunk", "serve.plan", "serve.policy.begin",
          "serve.policy.complete", "serve.stage", "serve.dispatch")


@pytest.fixture(scope="module")
def model():
    return Model(smoke_config(ARCHS["qwen2-0.5b"]))


def serve(model, n=4, **kw):
    eng = ServingEngine(model, max_slots=2, max_seq=512, **kw)
    reqs = [eng.submit(list(range(1, 150 + 20 * i)), max_new_tokens=6)
            for i in range(n)]
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return eng


@pytest.fixture(scope="module")
def served(model):
    return serve(model)


@pytest.mark.parametrize("phase", PHASES)
def test_each_phase_is_timed_and_nests_in_the_step(served, phase):
    ph = served.phases
    assert ph.count[phase] > 0
    assert 0 < ph.ns[phase] <= ph.ns["serve.step"]
    assert served.stats()["phase_ms"][phase] == pytest.approx(
        ph.ns[phase] / 1e6)


def test_the_step_clock_counts_steps_and_keeps_the_busy_clock(served):
    ph = served.phases
    assert ph.count["serve.step"] == served.steps
    assert served.busy_s == pytest.approx(ph.ns["serve.step"] / 1e9)
    # per-step own time: the step less its token read-back waits
    assert len(ph.recent) == served.steps
    assert sum(ns for _, ns in ph.recent) == ph.own_ns
    assert ph.own_ns == (ph.ns["serve.step"]
                         - ph.ns["serve.read_back.wait"])
    ends = [t for t, _ in ph.recent]
    assert ends == sorted(ends)
    for req in served.finished:
        assert req.token_busy == sorted(req.token_busy)


def test_host_us_per_step_covers_the_policy(model):
    """A policy whose begin_step is made slow shows in host_us_per_step:
    the host's own time now includes the policy's calls."""
    slow = ServingEngine(model, max_slots=2, max_seq=512)
    begin = slow.pool.begin_step

    def slow_begin(refs):
        time.sleep(0.002)
        return begin(refs)

    slow.pool.begin_step = slow_begin
    for i in range(2):
        slow.submit(list(range(1, 150 + 20 * i)), max_new_tokens=6)
    slow.run_until_done()
    s = slow.stats()
    assert s["phase_ms"]["serve.policy.begin"] >= 2.0 * slow.decode_steps
    assert (s["host_us_per_step"] * slow.steps
            >= 2000.0 * slow.decode_steps)


def test_waits_outside_the_step_leave_its_own_time_alone():
    ph = StepPhases("root", waits=("wait",))
    with ph("wait"):  # between steps (an engine drain)
        time.sleep(0.002)
    with ph("root"):
        with ph("wait"):
            time.sleep(0.001)
        time.sleep(0.001)
    ((_, own),) = ph.recent
    assert own == ph.own_ns
    assert own >= 1_000_000
    # only the wait inside the step is taken off it
    assert ph.ns["root"] - own <= ph.ns["wait"] - 2_000_000


#: (outer, inner) program scopes the compiled fused step must carry;
#: inner None: the outer scope alone
SCOPES = [("step.reset", None), ("step.admit", None), ("step.grow", None),
          ("step.sample", None), ("step.chunk_lane", "unembed")] + [
    (lane, inner) for lane in ("step.decode", "step.chunk_lane")
    for inner in ("attn.qkv", "attn.kv_write", "attn.kernel", "mlp")] + [
    ("step.decode", "unembed")]


@pytest.fixture(scope="module")
def step_op_names(model):
    eng = ServingEngine(model, max_slots=2, max_seq=512)
    text = eng.dev.compiled_step_text(2, True)
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("outer,inner", SCOPES)
def test_fused_step_ops_carry_their_scopes(step_op_names, outer, inner):
    path = (re.compile(rf"/{re.escape(outer)}/") if inner is None else
            re.compile(rf"/{re.escape(outer)}/(.*/)?{re.escape(inner)}/"))
    assert any(path.search(n) for n in step_op_names), (outer, inner)
