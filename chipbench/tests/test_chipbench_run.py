"""The whole run, on the CPU at a tiny size with the harness's look for
a chip skipped: a sound run comes out correct, and a timed path broken
underneath (a token altered where the engine produces it) comes out
not correct.  And without a TPU, run.py exits non-zero and prints no
result."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import _paths  # noqa: F401
import harness
from _tiny import as_cell

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 101


def _run(cell, fault=None, trace=False, seconds=3.0):
    name, override = as_cell(cell)
    return harness.run_cell(name, SEED, seconds, trace,
                            chips_required=False, config_override=override,
                            fault=fault)


def _alter_tokens(eng):
    """Every token the engine emits is one off from what it computed."""
    emit = eng._emit

    def altered(req, tok):
        emit(req, (tok + 1) % eng.cfg.vocab_size)

    eng._emit = altered


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat",
                                  "phi3-mini-4k.l8.batch",
                                  "qwen2-0.5b.sessions"])
def test_sound_run_is_correct_and_reports_the_cells_metrics(cell):
    r = _run(cell)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["tokens_compared"]["value"] > 0
    want = {m["name"] for m in harness.load_cell(as_cell(cell)[0]).end_to_end}
    assert set(r["metrics"]) == want
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat",
                                  "phi3-mini-4k.l8.batch"])
def test_altered_tokens_are_not_correct(cell):
    r = _run(cell, fault=_alter_tokens)
    assert r["correct"] is False
    assert r["compared"]["widest"]["value"] > 1e-3


def test_traced_run_reports_per_layer_metrics():
    r = _run("qwen2-0.5b.sessions", trace=True)
    # host-side readers have something to read on the CPU; the device
    # readers (trace) need the chip and stay silent here
    for name in ("step_ms", "policy_ms_per_step", "unreclaimed_pages_peak",
                 "step_mfu", "queue_wait_p95_ms"):
        assert name in r["metrics"], r["metrics"]
    assert "device_idle_share" not in r["metrics"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2-0.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
