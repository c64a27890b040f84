"""The dense pre-norm decoder of Qwen2 and Phi-3: its keys, its work
counts and its plain float32 reference.

The reference follows the published equations of Qwen2 and Phi-3 (pre-norm
RMSNorm, rotary embedding on rotated halves, grouped-query or multi-head
causal attention with optional q/k/v biases, SwiGLU MLP, final norm, a
tied or untied head), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program: the weights are the tree the benchmark drew from the seed
(:mod:`chipbench.weights`), read by name.

It runs in blocks so that it fits beside nothing else on one chip: one
layer at a time, queries in blocks of ``Q_BLOCK`` rows, and the head
only at the rows asked for.

``precision="fp8"`` is the control: the same pass with every matrix
product's operands rounded through float8 e4m3 (weights per output
column, activations per row, each scaled by its absolute maximum), the
step below the configuration's bfloat16 that a later change could be
tempted to take.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import work

#: no published key beyond the shared ones
KEYS: Dict[str, str] = {}
#: the equations below: a SiLU-gated MLP, every layer full attention
VALUES = {"hidden_act": "silu", "use_sliding_window": False}

Q_BLOCK = 512
HEAD_ROWS = 256
F8_MAX = 448.0  # largest finite float8_e4m3fn


def init_std(path: str, shape, d_model: int):
    """Every parameter of the dense decoder has a shared rule."""
    return None


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def shape(c: dict) -> work.Shape:
    """A token runs through q and o (M x H*D each), k and v (M x K*D
    each) and the gated MLP's three M x F matrices in every layer."""
    L, M, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, K, D = (c["num_attention_heads"], c["num_key_value_heads"],
               _head_dim(c))
    return work.Shape(layers=L, d_model=M, heads=H, kv_heads=K, head_dim=D,
                      vocab=c["vocab_size"],
                      matmul_params=L * (2 * M * H * D + 2 * M * K * D
                                         + 3 * M * F))


def tiny(c: dict) -> dict:
    """Nothing beyond the shared widths."""
    return {}


@dataclasses.dataclass(frozen=True)
class Spec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        return cls(c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], _head_dim(c),
                   c["intermediate_size"], c["vocab_size"],
                   float(c["rms_norm_eps"]), float(c["rope_theta"]),
                   bool(c["tie_word_embeddings"]))


def _qdq(x, axis):
    """Round through float8 e4m3 with an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    """``x @ w`` with x (rows, k) and w (k, n)."""
    if fp8:
        x, w = _qdq(x, -1), _qdq(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, D): rotate the two halves of each head (HF rotate_half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _layer(h, lw: Dict[str, jax.Array], *, spec: Spec, fp8: bool):
    S, M = h.shape
    H, Hkv, D = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pos = jnp.arange(S)
    a = lw["attn"]
    x = _rms(h, lw["norm1"]["scale"], spec.eps)
    q = _mm(x, a["wq"].reshape(M, H * D), fp8).reshape(S, H, D)
    k = _mm(x, a["wk"].reshape(M, Hkv * D), fp8).reshape(S, Hkv, D)
    v = _mm(x, a["wv"].reshape(M, Hkv * D), fp8).reshape(S, Hkv, D)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, pos, spec.theta), _rope(k, pos, spec.theta)
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)  # (S, H, D): kv head j serves q heads jg..
    v = jnp.repeat(v, g, axis=1)
    if fp8:
        q, k, v = _qdq(q, -1), _qdq(k, -1), _qdq(v, -1)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(D)
        qpos = pos[s0:s0 + Q_BLOCK]
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = _qdq(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(outs, 0).reshape(S, H * D)
    h = h + _mm(o, a["wo"].reshape(H * D, M), fp8)
    m = lw["mlp"]
    x = _rms(h, lw["norm2"]["scale"], spec.eps)
    gate = _mm(x, m["wi_gate"], fp8)
    up = _mm(x, m["wi_up"], fp8)
    return h + _mm(jax.nn.silu(gate) * up, m["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _head(h, final_scale, head_w, *, spec: Spec, fp8: bool):
    x = _rms(h, final_scale, spec.eps)
    return _mm(x, head_w, fp8)


def _pow2(n: int, least: int = Q_BLOCK) -> int:
    return max(least, 1 << (n - 1).bit_length())


def logits_at(weights, spec: Spec, tokens, rows, *, precision: str = "f32",
              whole_blocks: bool = False) -> jax.Array:
    """Logits (len(rows), vocab) of a causal pass over ``tokens`` (one
    sequence), at the positions ``rows``.  The sequence is padded to a
    power of two of at least ``Q_BLOCK`` (pads sit after every position
    read, so causality keeps them out) to bound the compiled shapes.
    ``whole_blocks``: return every head block whole, ``HEAD_ROWS`` rows
    each (rows past ``len(rows)`` are the logits of position 0), so that
    the shapes a caller computes on are few."""
    fp8 = {"f32": False, "fp8": True}[precision]
    tokens = np.asarray(tokens, np.int32)
    S = _pow2(len(tokens))
    padded = np.zeros((S,), np.int32)
    padded[: len(tokens)] = tokens
    rows = np.asarray(rows, np.int32)
    with jax.default_matmul_precision("highest"):
        emb = weights["embed"]["tok"]
        h = emb[jnp.asarray(padded)].astype(jnp.float32)
        layers = weights["layers"]
        n_layers = layers["norm1"]["scale"].shape[0]
        for i in range(n_layers):
            lw = jax.tree.map(lambda a, i=i: a[i], layers)
            h = _layer(h, lw, spec=spec, fp8=fp8)
        head_w = emb.T if spec.tied else weights["embed"]["unembed"]
        out = []
        for r0 in range(0, len(rows), HEAD_ROWS):
            r = rows[r0:r0 + HEAD_ROWS]
            idx = np.zeros((HEAD_ROWS,), np.int32)
            idx[: len(r)] = r
            lg = _head(h[jnp.asarray(idx)], weights["final_norm"]["scale"],
                       head_w, spec=spec, fp8=fp8)
            out.append(lg if whole_blocks else lg[: len(r)])
    return jnp.concatenate(out, 0)
