"""Pure-jnp oracles for every Pallas kernel.

These are *also* the XLA path the models use when Pallas is disabled (the
CPU container cannot lower Mosaic/TPU kernels), so they are written to be
memory-sane at production shapes:

  * ``flash_attention``   — chunked online-softmax attention (lax.scan over
                            KV chunks; O(S * chunk) memory, never S^2)
  * ``decode_attention``  — single-token attention against a (possibly
                            padded) KV cache with per-sequence lengths
  * ``paged_attention``   — decode attention over a paged KV pool + block
                            tables (gathers pages; the Pallas kernel streams
                            them through VMEM instead)
  * ``ssd_chunk_scan``    — Mamba2 state-space-duality chunked scan
  * ``block_gather``      — KV page gather/compaction (pool defrag hot path)

All functions are shape-polymorphic in batch/heads and take explicit
numeric-stability dtypes so the Pallas kernels can be validated bit-closely
against them (tests/test_kernels.py sweeps shapes & dtypes).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Flash attention (training / prefill)
# ---------------------------------------------------------------------------
def flash_attention(
    q: jax.Array,  # (B, S_q, H, D)
    k: jax.Array,  # (B, S_kv, Hkv, D)
    v: jax.Array,  # (B, S_kv, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = full; >0 = sliding window (causal only)
    q_offset: int = 0,  # absolute position of q[0] relative to kv[0]
    chunk: int = 0,   # 0 -> default from REPRO_FLASH_CHUNK (2048)
) -> jax.Array:
    """Chunked online-softmax attention (the flash recurrence, in jnp).

    The scan carry (acc/m/l) spills to HBM once per KV chunk under XLA —
    traffic the Pallas kernel keeps in VMEM.  Larger chunks cut that spill
    linearly at the cost of a bigger transient score tile (§Perf).

    GQA: H must be a multiple of Hkv; kv heads are broadcast.
    Returns (B, S_q, H, D) in q.dtype.
    """
    B, S_q, H, D = q.shape
    _, S_kv, Hkv, _ = k.shape
    if not chunk:
        import os

        chunk = int(os.environ.get("REPRO_FLASH_CHUNK", "2048"))
    chunk = min(chunk, S_kv)
    assert H % Hkv == 0
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    # group query heads with their kv head: (B, Hkv, G, S_q, D).
    # Operands stay in their storage dtype; matmuls accumulate in f32 via
    # preferred_element_type (MXU-faithful: bf16 x bf16 -> f32), so no f32
    # copies of K/V are materialized in HBM.
    qg = q.transpose(0, 2, 1, 3).reshape(B, Hkv, G, S_q, D)
    kT = k.transpose(0, 2, 1, 3)  # (B,Hkv,Skv,D)
    vT = v.transpose(0, 2, 1, 3)

    n_chunks = (S_kv + chunk - 1) // chunk
    pad = n_chunks * chunk - S_kv
    if pad:
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = kT.reshape(B, Hkv, n_chunks, chunk, D)
    vc = vT.reshape(B, Hkv, n_chunks, chunk, D)

    q_pos = q_offset + jnp.arange(S_q)  # absolute q positions

    def body(carry, inputs):
        acc, m, l = carry  # (B,Hkv,G,Sq,D), (B,Hkv,G,Sq), (B,Hkv,G,Sq)
        kj, vj, j = inputs
        kv_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        mask = kv_pos[None, :] <= S_kv - 1  # drop padding
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Hkv, G, S_q, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, S_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S_q), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body,
        (acc0, m0, l0),
        (
            kc.transpose(2, 0, 1, 3, 4),
            vc.transpose(2, 0, 1, 3, 4),
            jnp.arange(n_chunks),
        ),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(B, H, S_q, D).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (contiguous cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jax.Array,        # (B, H, D) — one new token per sequence
    k_cache: jax.Array,  # (B, S_max, Hkv, D)
    v_cache: jax.Array,  # (B, S_max, Hkv, D)
    lengths: jax.Array,  # (B,) int32 — valid cache prefix per sequence
) -> jax.Array:
    B, H, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qg = q.reshape(B, Hkv, G, D)
    kT = k_cache.transpose(0, 2, 1, 3)  # (B,Hkv,S,D) storage dtype
    vT = v_cache.transpose(0, 2, 1, 3)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, kT,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # (B,S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p.astype(vT.dtype), vT,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention (block tables)
# ---------------------------------------------------------------------------
def to_pages(kv: jax.Array, block: int) -> jax.Array:
    """Token-major KV ``(..., n*block, Hkv, D)`` -> pool pages
    ``(..., n, Hkv, block, D)``.  Pools are head-major so one kv head's
    slice of a page is a contiguous ``(block, D)`` tile (the Pallas
    kernel's block; see :mod:`repro.kernels.paged_attention`)."""
    *lead, S, Hkv, D = kv.shape
    kv = kv.reshape(*lead, S // block, block, Hkv, D)
    return jnp.swapaxes(kv, -3, -2)


def from_pages(pages: jax.Array) -> jax.Array:
    """Inverse of :func:`to_pages`: ``(..., n, Hkv, block, D)`` ->
    ``(..., n*block, Hkv, D)``."""
    *lead, n, Hkv, block, D = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, n * block, Hkv, D)


def paged_attention(
    q: jax.Array,            # (B, H, D)
    k_pool: jax.Array,       # (B, N_blocks, Hkv, block, D) — per-seq pool
    v_pool: jax.Array,       # (B, N_blocks, Hkv, block, D)
    block_table: jax.Array,  # (B, max_blocks) int32 — local page ids
    lengths: jax.Array,      # (B,) int32
    *,
    n_kv: Optional[int] = None,
    global_pages: bool = False,
) -> jax.Array:
    """Oracle: gather the pages then run decode attention.

    Pools are per-sequence-local (pages of a sequence live on the shard
    that owns the sequence — the TPU adaptation of vLLM's global pool; see
    DESIGN.md), so the gather never crosses shards.  The Pallas kernel
    streams pages HBM->VMEM without materializing the gathered cache;
    numerics are identical.

    ``global_pages`` flattens the slot axis away: table entries are then
    GLOBAL ids ``slot * N_blocks + page`` and a row may reference pages
    owned by *another* slot — the copy-on-write fork substrate (a forked
    prefix is one physical set of pages referenced by N block-table rows).

    ``n_kv`` (static) bounds the sweep to the first ``n_kv`` table columns;
    past-length positions mask to exp-underflow zero either way, so any
    bound >= ceil(max(lengths)/block) is bit-identical to the full sweep.
    """
    if n_kv is not None and n_kv < block_table.shape[1]:
        block_table = block_table[:, :n_kv]
    B = q.shape[0]
    if global_pages:
        flat = (B * k_pool.shape[1],) + k_pool.shape[2:]
        k = jnp.take(k_pool.reshape(flat), block_table, axis=0)
        v = jnp.take(v_pool.reshape(flat), block_table, axis=0)
    else:
        idx = block_table[:, :, None, None, None]
        k = jnp.take_along_axis(k_pool, idx, axis=1)
        v = jnp.take_along_axis(v_pool, idx, axis=1)
    # (B, MB, Hkv, block, D) -> (B, MB*block, Hkv, D)
    return decode_attention(q, from_pages(k), from_pages(v), lengths)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan
# ---------------------------------------------------------------------------
def ssd_chunk_scan(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)   — positive step sizes (post-softplus)
    a: jax.Array,    # (H,)        — negative state decay rates
    b: jax.Array,    # (B, S, G, N)
    c: jax.Array,    # (B, S, G, N)
    *,
    chunk: int = 128,
    d_skip: Optional[jax.Array] = None,  # (H,) skip connection
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """State-space duality (Dao & Gu 2024), chunked form.

    Returns (y: (B,S,H,P), final_state: (B,H,P,N)).  Heads are grouped over
    b/c (G groups; H % G == 0).
    """
    Bb, S, H, P = x.shape
    _, _, G, N = b.shape
    assert H % G == 0
    hg = H // G
    assert S % chunk == 0, f"seq {S} must be a multiple of chunk {chunk}"
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(Bb, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bb, nc, chunk, H)
    bf = b.astype(jnp.float32).reshape(Bb, nc, chunk, G, N)
    cf = c.astype(jnp.float32).reshape(Bb, nc, chunk, G, N)
    af = a.astype(jnp.float32)

    # broadcast groups to heads
    bh = jnp.repeat(bf, hg, axis=3)  # (B,nc,L,H,N)
    ch = jnp.repeat(cf, hg, axis=3)

    da = dtf * af[None, None, None, :]          # (B,nc,L,H)  (negative)
    cum = jnp.cumsum(da, axis=2)                # within-chunk cumulative
    total = cum[:, :, -1, :]                    # (B,nc,H)

    # ---- intra-chunk (quadratic within the chunk) ----
    # decay[i,j] = exp(cum_i - cum_j) for j <= i
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,L,L,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("bclhn,bcshn->bclsh", ch, bh)     # C_i . B_j
    w = scores * decay * dtf[:, :, None, :, :]            # weight x_j by dt_j
    y_intra = jnp.einsum("bclsh,bcshp->bclhp", w, xf)

    # ---- chunk states ----
    # state_c = sum_j exp(total - cum_j) * dt_j * B_j (x) x_j
    to_end = jnp.exp(total[:, :, None, :] - cum)          # (B,nc,L,H)
    sw = to_end * dtf                                     # (B,nc,L,H)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn", bh, sw, xf)

    # ---- inter-chunk recurrence over nc ----
    def step(s_prev, inputs):
        st_c, tot_c = inputs  # (B,H,P,N), (B,H)
        s_new = s_prev * jnp.exp(tot_c)[:, :, None, None] + st_c
        return s_new, s_prev  # emit the state *entering* the chunk

    s0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((Bb, H, P, N), jnp.float32)
    )
    final_state, entering = jax.lax.scan(
        step,
        s0,
        (states.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
    )
    entering = entering.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # ---- inter-chunk contribution ----
    from_start = jnp.exp(cum)                             # (B,nc,L,H)
    y_inter = jnp.einsum(
        "bclhn,bclh,bchpn->bclhp", ch, from_start, entering
    )

    y = y_intra + y_inter
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, None, None, :, None] * xf
    return (
        y.reshape(Bb, S, H, P).astype(x.dtype),
        final_state.astype(jnp.float32),
    )


def ssd_decode_step(
    x: jax.Array,      # (B, H, P)
    dt: jax.Array,     # (B, H)
    a: jax.Array,      # (H,)
    b: jax.Array,      # (B, G, N)
    c: jax.Array,      # (B, G, N)
    state: jax.Array,  # (B, H, P, N)
    *,
    d_skip: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence: s' = s*exp(dt*a) + dt * (B (x) x)."""
    Bb, H, P = x.shape
    G = b.shape[1]
    hg = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bh = jnp.repeat(b.astype(jnp.float32), hg, axis=1)  # (B,H,N)
    ch = jnp.repeat(c.astype(jnp.float32), hg, axis=1)
    decay = jnp.exp(dtf * a.astype(jnp.float32)[None, :])  # (B,H)
    upd = dtf[:, :, None, None] * xf[:, :, :, None] * bh[:, :, None, :]
    state_new = state * decay[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", state_new, ch)
    if d_skip is not None:
        y = y + d_skip.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x.dtype), state_new


# ---------------------------------------------------------------------------
# Block gather (pool compaction / defrag)
# ---------------------------------------------------------------------------
def block_gather(
    pool: jax.Array,     # (N_blocks, Hkv, block, D)
    indices: jax.Array,  # (M,) int32 — source block ids
) -> jax.Array:
    """Gather M pages out of the pool (reclaimer compaction hot path)."""
    return pool[indices]
