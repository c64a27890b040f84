"""Pallas TPU decode attention: contiguous cache and paged (block-table)
variants.

The paged kernel is the serving-layer payoff of the stamped BlockPool:
pages recycled by the reclaimer are *physically scattered* in the pool, and
the kernel streams them HBM->VMEM in table order via **scalar prefetch**
(pltpu.PrefetchScalarGridSpec) — the block table is read by the index_map,
so the gathered KV never materializes in HBM (the pure-jnp oracle gathers;
numerics identical).

Pools are HEAD-MAJOR: a page is ``(Hkv, block, D)``, so one kv head's
slice of a page is a contiguous ``(block, D)`` tile — the only KV block
shape the TPU lowering accepts at head counts that are neither 1 nor a
multiple of 8 (the last two block dims must be (8k, 128k) or full).

Grid: (B, Hkv, n_kv_blocks), innermost sequential; the online-softmax
state (acc / m / l) for the GQA query group persists in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, n_kv: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]  # (G, D) — storage dtype into the MXU
    k = k_ref[...]  # (bk, D)
    v = v_ref[...]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (G, bk)

    # positions are *logical*: block ik covers [ik*bk, (ik+1)*bk)
    length = lengths_ref[b]
    kv_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kv_pos < length, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]  # (G, 1)
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


def _paged_kernel(table_ref, lengths_ref, *refs, **kw):
    # the block table is consumed by the index maps only
    _decode_kernel(lengths_ref, *refs, **kw)


def _attend(kernel, scalars, q, k_op, v_op, kv_spec, *, Hkv, n_kv, block,
            interpret):
    """Shared pallas_call: grid (B, Hkv, n_kv) over GQA query groups."""
    B, H, D = q.shape
    G = H // Hkv
    q_spec = pl.BlockSpec((None, None, G, D), lambda b, h, ik, *_: (b, h, 0, 0))
    out = pl.pallas_call(
        functools.partial(kernel, scale=float(1.0 / (D ** 0.5)),
                          block_k=block, n_kv=n_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, Hkv, n_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((G, D), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, q.reshape(B, Hkv, G, D), k_op, v_op)
    return out.reshape(B, H, D)


def decode_attention_pallas(
    q: jax.Array,        # (B, H, D)
    k_cache: jax.Array,  # (B, S_max, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) int32
    *,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Contiguous-cache decode.  The cache is token-major, so the wrapper
    transposes it head-major for the kernel (one HBM pass per call)."""
    _, S, Hkv, D = k_cache.shape
    assert q.shape[1] % Hkv == 0
    block_k = min(block_k, S)
    assert S % block_k == 0
    kv_spec = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, ik, *_: (b, h, ik, 0))
    return _attend(
        _decode_kernel, (lengths,), q, k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3), kv_spec, Hkv=Hkv,
        n_kv=S // block_k, block=block_k, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Paged variant: the block table drives the k/v index maps (scalar prefetch)
# ---------------------------------------------------------------------------
def paged_attention_pallas(
    q: jax.Array,            # (B, H, D)
    k_pool: jax.Array,       # (B, N_pool, Hkv, block, D) per-seq pools
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, max_blocks) int32 (local page ids)
    lengths: jax.Array,      # (B,) int32
    *,
    n_kv: int | None = None,
    global_pages: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``n_kv`` (static) bounds the KV sweep: the grid iterates only the
    first ``n_kv`` table columns instead of all ``max_blocks``.  Callers
    pass a bucketed bound >= ceil(max(lengths)/block); positions past a
    sequence's length are masked to NEG_INF either way, so any valid bound
    is bit-identical to the full sweep — it just skips pages no active
    sequence can reach.

    ``global_pages``: table entries are GLOBAL ids ``slot * N_pool + page``
    into the slot-flattened pool, so a row may stream pages owned by
    another slot (copy-on-write prefix forks).  Same grid, same scratch;
    only the k/v index maps change (page id selects the flattened leading
    axis directly instead of (slot, page))."""
    if n_kv is not None and n_kv < block_table.shape[1]:
        block_table = block_table[:, :n_kv]
    B = q.shape[0]
    _, n_pool, Hkv, block, D = k_pool.shape
    if global_pages:
        k_op = k_pool.reshape(B * n_pool, Hkv, block, D)
        v_op = v_pool.reshape(B * n_pool, Hkv, block, D)
        kv_spec = pl.BlockSpec(
            (None, None, block, D),
            lambda b, h, ik, table, lens: (table[b, ik], h, 0, 0),
        )
    else:
        k_op, v_op = k_pool, v_pool
        kv_spec = pl.BlockSpec(
            (None, None, None, block, D),
            lambda b, h, ik, table, lens: (b, table[b, ik], h, 0, 0),
        )
    return _attend(
        _paged_kernel, (block_table, lengths), q, k_op, v_op, kv_spec,
        Hkv=Hkv, n_kv=block_table.shape[1], block=block,
        interpret=interpret,
    )
