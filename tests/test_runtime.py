"""Training-stack runtime tests: data pipeline determinism + guarded buffer
reuse, async checkpoint roundtrip, trainer with failure injection /
checkpoint-restart replay, straggler flagging."""

import numpy as np
import pytest

from repro.configs import ARCHS, ShapeConfig, smoke_config
from repro.launch.mesh import make_debug_mesh
from repro.data.pipeline import SyntheticDataPipeline
from repro.memory.stamp_ledger import StampLedger
from repro.models import Model
from repro.training import CheckpointManager, Trainer, inject_failure_at

SHAPE = ShapeConfig("t", "train", 32, 2)


def test_pipeline_deterministic_and_guarded():
    cfg = smoke_config(ARCHS["qwen2-0.5b"])
    ledger = StampLedger()
    p1 = SyntheticDataPipeline(cfg, SHAPE, seed=1, ledger=ledger)
    try:
        # a long-lived hold (in-flight step) blocks buffer reuse
        with ledger.hold("inflight"):
            batches = [p1.next() for _ in range(2)]
            assert ledger.unreclaimed() >= 1
        ledger.reclaim()
        assert ledger.unreclaimed() == 0
    finally:
        p1.stop()
    p2 = SyntheticDataPipeline(cfg, SHAPE, seed=1)
    try:
        again = [p2.next() for _ in range(2)]
    finally:
        p2.stop()
    for a, b in zip(batches, again):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_pipeline_resume_from_step():
    cfg = smoke_config(ARCHS["qwen2-0.5b"])
    p1 = SyntheticDataPipeline(cfg, SHAPE, seed=2)
    try:
        seq = [p1.next()["tokens"] for _ in range(5)]
    finally:
        p1.stop()
    p2 = SyntheticDataPipeline(cfg, SHAPE, seed=2, start_step=3)
    try:
        resumed = p2.next()["tokens"]
    finally:
        p2.stop()
    np.testing.assert_array_equal(seq[3], resumed)


def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp

    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4),
                   "b": jnp.ones((4,))},
        "opt": {"mu": {"w": jnp.zeros((3, 4))}, "step": jnp.int32(7)},
    }
    mgr.save(5, state)
    mgr.save(9, state)
    mgr.wait()
    assert mgr.available_steps() == [5, 9]
    restored, step = mgr.restore()
    assert step == 9
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.arange(12.0).reshape(3, 4),
    )
    # gc keeps only `keep` newest
    mgr.save(11, state)
    mgr.wait()
    assert mgr.available_steps() == [9, 11]


def test_trainer_runs_and_loss_finite(tmp_path):
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    mesh = make_debug_mesh()
    tr = Trainer(model, SHAPE, mesh, ckpt_dir=str(tmp_path / "ck"),
                 ckpt_every=3, seed=0)
    out = tr.run(5)
    assert out["final_step"] == 5
    losses = [h["loss"] for h in out["history"]]
    assert all(np.isfinite(l) for l in losses)
    assert len(tr.ckpt.available_steps()) >= 1


def test_trainer_failure_restart_replays_identically(tmp_path):
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    mesh = make_debug_mesh()

    base = Trainer(model, SHAPE, mesh, ckpt_dir=str(tmp_path / "a"),
                   ckpt_every=2, seed=1)
    ref = base.run(6)

    crashy = Trainer(model, SHAPE, mesh, ckpt_dir=str(tmp_path / "b"),
                     ckpt_every=2, seed=1,
                     failure_hook=inject_failure_at({4}))
    out = crashy.run(6)
    assert out["restarts"] == 1
    # deterministic pipeline + checkpoint restore => identical tail losses
    ref_by_step = {h["step"]: h["loss"] for h in ref["history"]}
    got_by_step = {h["step"]: h["loss"] for h in out["history"]}
    for s in (4, 5):
        np.testing.assert_allclose(got_by_step[s], ref_by_step[s],
                                   rtol=1e-4, atol=1e-5)


def test_trainer_straggler_flagging():
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    mesh = make_debug_mesh()
    tr = Trainer(model, SHAPE, mesh, step_deadline_s=1e-9, seed=0)
    out = tr.run(2)
    assert out["stragglers"] == [0, 1]  # every step exceeds a 1ns deadline


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """Entry points keep JAX's compile cache in $JAX_COMPILATION_CACHE_DIR
    when it is set and in <checkout>/.jax_cache otherwise."""
    from pathlib import Path

    import jax

    from repro.compile_cache import enable_compile_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
