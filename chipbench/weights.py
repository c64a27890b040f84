"""Weights from the seed, made on the device in one jitted call.

The tree has the program's layout (its abstract parameter shapes and
names, in the float32 the program declares) and the benchmark's own
values, so the reference reads the same numbers the program serves and
nothing the program made.  Scales keep a random deep model well
conditioned: every matrix is N(0, 1/fan_in), the head (or the tied
embedding) has std ``LOGIT_STD / sqrt(d_model)`` so that logits have a
std of about ``LOGIT_STD``, q/k/v biases N(0, ``BIAS_STD``^2) and norm
scales 1 + N(0, ``NORM_STD``^2).  The configuration's architecture
module is asked first (``init_std``), so that it names the scales of
parameters these rules do not know.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_STD = 2.0
BIAS_STD = 0.5
NORM_STD = 0.1


def base_key(seed: int):
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed) & (2**64 - 1)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _std(path: str, shape, d_model: int, init_std) -> float:
    own = init_std(path, shape, d_model)
    if own is not None:
        return own
    name = path.rsplit("/", 1)[-1]
    if path in ("embed/tok", "embed/unembed"):
        return LOGIT_STD / np.sqrt(d_model)
    if name in ("wq", "wk", "wv"):  # (..., M, H, D)
        return 1.0 / np.sqrt(shape[-3])
    if name == "wo" and "/attn/" in f"/{path}/":  # (..., H, D, M)
        return 1.0 / np.sqrt(shape[-3] * shape[-2])
    if name in ("wi_gate", "wi_up", "wo"):  # (..., K, N)
        return 1.0 / np.sqrt(shape[-2])
    if name in ("bq", "bk", "bv"):
        return BIAS_STD
    if name == "scale":
        return NORM_STD
    raise ValueError(f"no initialiser for parameter {path} {shape}: "
                     f"neither the shared rules nor the architecture "
                     f"module's init_std name it")


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _paths(v, f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def make_weights(abstract, seed: int, d_model: int, init_std, device=None):
    """``abstract``: the program's parameter tree of ShapeDtypeStructs;
    ``init_std``: the architecture module's ``init_std``."""
    leaves = list(_paths(abstract))

    def build(key):
        out = {}
        for path, a in leaves:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            x = jax.random.normal(k, a.shape, jnp.float32)
            x = x * _std(path, a.shape, d_model, init_std)
            if path.endswith("scale"):
                x = x + 1.0
            node = out
            *parents, leaf = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[leaf] = x.astype(a.dtype)
        return out

    with jax.default_device(device):
        return jax.jit(build)(base_key(seed))
