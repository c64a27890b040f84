"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps on CPU with the full stack — pjit step, stamp-guarded data
pipeline, async checkpointing, simulated failure + restart.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, ShapeConfig
from repro.launch.mesh import make_debug_mesh
from repro.models import Model
from repro.training import AdamWConfig, Trainer, inject_failure_at


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--inject-failure", type=int, default=-1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--small", action="store_true",
                    help="~47M variant (1-core CPU friendly: ~6s/step "
                         "vs ~20s/step for the default ~100M)")
    args = ap.parse_args()

    if args.small:  # ~47M
        cfg = ARCHS["qwen2-0.5b"].scaled(
            name="qwen2-47m", num_layers=8, d_model=512, num_heads=8,
            num_kv_heads=2, d_ff=2048, head_dim=64, vocab_size=32768,
            dtype="float32",
        )
    else:  # ~100M (the end-to-end driver scale)
        cfg = ARCHS["qwen2-0.5b"].scaled(
            name="qwen2-100m", num_layers=10, d_model=768, num_heads=12,
            num_kv_heads=2, d_ff=2304, head_dim=64, vocab_size=32768,
            dtype="float32",
        )
    model = Model(cfg)
    print(f"model: {model.n_params()/1e6:.1f}M params")

    shape = ShapeConfig("train_tiny", "train", seq_len=128, global_batch=8)
    mesh = make_debug_mesh()
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro-ckpt-")
    hook = (inject_failure_at({args.inject_failure})
            if args.inject_failure >= 0 else None)
    trainer = Trainer(
        model, shape, mesh, ckpt_dir=ckpt_dir, ckpt_every=50,
        adamw=AdamWConfig(lr=3e-3, warmup_steps=20), seed=0,
        failure_hook=hook,
    )
    out = trainer.run(args.steps)
    h = out["history"]
    k = max(len(h) // 5, 1)
    first = sum(x["loss"] for x in h[:k]) / k
    last = sum(x["loss"] for x in h[-k:]) / k
    print(f"steps: {out['final_step']}  restarts: {out['restarts']}")
    print(f"loss: first~{first:.3f} last~{last:.3f} "
          f"(final {h[-1]['loss']:.3f})")
    if args.steps >= 100:
        assert last < first, "loss should decrease over a real run"
    print(f"checkpoints: {trainer.ckpt.available_steps()} in {ckpt_dir}")


if __name__ == "__main__":
    main()
