"""Pallas TPU flash attention (prefill / training forward).

TPU-native design (not a CUDA port): the kernel is tiled for the MXU with
128-aligned (block_q x d) @ (d x block_k) score tiles; the online-softmax
accumulator, running max and normalizer live in VMEM scratch that persists
across the sequential innermost grid dimension (the KV blocks), so the
S x S score matrix never exists in HBM.  GQA is expressed in the index
maps (q head h reads kv head h // group); causal + sliding-window masks
are built from 2-D iotas (TPU requires >=2D iota).

Grid: (B, H, n_q_blocks, n_kv_blocks) with the last dimension marked
"arbitrary" (sequential) so the scratch carries across KV blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_off_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_k: int, n_kv: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]  # (bq, D) — storage dtype into the MXU
    k = k_ref[...]  # (bk, D)
    v = v_ref[...]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (bq, bk)

    iq = pl.program_id(2)
    q_pos = (
        q_off_ref[0] + iq * block_q
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )
    kv_pos = (
        ik * block_k
        + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    )
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = mask & (kv_pos <= q_pos)
    if window:
        mask = mask & (kv_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, S_q, H, D)
    k: jax.Array,  # (B, S_kv, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset=0,  # int or traced int32 scalar (chunked prefill)
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """The kernel runs head-major — ``(bq, D)`` / ``(bk, D)`` tiles, the
    only per-head block shape the TPU lowering accepts for head counts
    that are not multiples of 8 — so the wrapper transposes in and out.
    ``q_offset`` rides in SMEM as a scalar-prefetch operand, so a traced
    offset compiles to one program for every chunk position."""
    B, S_q, H, D = q.shape
    _, S_kv, Hkv, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    block_q = min(block_q, S_q)
    block_k = min(block_k, S_kv)
    assert S_q % block_q == 0 and S_kv % block_k == 0, (
        "pad sequences to block multiples before calling the kernel"
    )
    n_q, n_kv = S_q // block_q, S_kv // block_k
    scale = float(1.0 / (D ** 0.5))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv,
    )
    q_spec = pl.BlockSpec((None, None, block_q, D),
                          lambda b, h, iq, ik, *_: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, iq, ik, *_, g=group: (b, h // g, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n_q, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S_q, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(jnp.asarray(q_offset, jnp.int32).reshape(1),
      q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
