"""Numerical equivalence of the shard_map distributed paths (§Perf iters
2/4b/9) against the single-device references, on a real 2x4 host-device
mesh.  Runs in a subprocess because the forced device count must be set
before jax initializes."""

import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.kernels import ref
from repro.kernels.distributed import (
    paged_attention_dist, rolling_attention_dist, moe_block_dist)

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
rs = np.random.RandomState(0)

# ---------------- paged flash-decode ----------------
B, MB, blk, Hkv, H, D, MBl = 4, 8, 16, 2, 4, 32, 6
q  = jnp.asarray(rs.randn(B, H, D), jnp.float32) * 0.3
kp = jnp.asarray(rs.randn(B, MB, Hkv, blk, D), jnp.float32) * 0.3
vp = jnp.asarray(rs.randn(B, MB, Hkv, blk, D), jnp.float32) * 0.3
k1 = jnp.asarray(rs.randn(B, Hkv, D), jnp.float32) * 0.3
v1 = jnp.asarray(rs.randn(B, Hkv, D), jnp.float32) * 0.3
table = jnp.asarray(
    np.stack([rs.permutation(MB)[:MBl] for _ in range(B)]), jnp.int32)
lengths = jnp.asarray(rs.randint(1, MBl * blk - 1, (B,)), jnp.int32)

bar = jnp.arange(B)
page, slot = table[bar, lengths // blk], lengths % blk
kp_ref = kp.at[bar, page, :, slot].set(k1)
vp_ref = vp.at[bar, page, :, slot].set(v1)
want = ref.paged_attention(q, kp_ref, vp_ref, table, lengths + 1)
with mesh:
    got, kp2, vp2 = jax.jit(
        lambda *a: paged_attention_dist(*a, mesh=mesh, batch_part="data")
    )(q, kp, vp, table, lengths, k1, v1)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=3e-4, atol=3e-4)
np.testing.assert_allclose(np.asarray(kp2), np.asarray(kp_ref), rtol=0,
                           atol=0)
print("PAGED_DIST_OK")

# ---------------- rolling flash-decode ----------------
W = 32
kc = jnp.asarray(rs.randn(B, W, Hkv, D), jnp.float32) * 0.3
vc = jnp.asarray(rs.randn(B, W, Hkv, D), jnp.float32) * 0.3
lengths_r = jnp.asarray([5, 31, 32, 77], jnp.int32)  # pre/at/past wrap
slot = lengths_r % W
kc_ref = kc.at[bar, slot].set(k1)
vc_ref = vc.at[bar, slot].set(v1)
want = ref.decode_attention(q, kc_ref, vc_ref,
                            jnp.minimum(lengths_r + 1, W))
with mesh:
    got, kc2, vc2 = jax.jit(
        lambda *a: rolling_attention_dist(*a, mesh=mesh, batch_part="data")
    )(q, kc, vc, lengths_r, k1, v1)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=3e-4, atol=3e-4)
np.testing.assert_allclose(np.asarray(kc2), np.asarray(kc_ref), rtol=0,
                           atol=0)
print("ROLLING_DIST_OK")

# ---------------- distributed MoE block ----------------
from repro.configs import ARCHS, smoke_config
from repro.models import layers as L
from repro.models.param import init_params

cfg = smoke_config(ARCHS["mixtral-8x7b"]).scaled(d_ff=64)
S = 16  # divides model axis (4): psum_scatter path
p = init_params(L.moe_specs(cfg, 0), seed=3)
x = jnp.asarray(rs.randn(4, S, cfg.d_model), jnp.float32) * 0.3
want = L.apply_moe(p, x, cfg)  # dist config not set -> local path
with mesh:
    got = jax.jit(lambda pp, xx: moe_block_dist(
        pp, xx, cfg, mesh=mesh, batch_part="data"))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-3, atol=2e-3)
print("MOE_DIST_OK")
'''


def test_distributed_paths_match_reference():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=480,
        env={"PYTHONPATH": str(Path(__file__).parents[1] / "src"),
             "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    for marker in ("PAGED_DIST_OK", "ROLLING_DIST_OK", "MOE_DIST_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr[-1500:])
