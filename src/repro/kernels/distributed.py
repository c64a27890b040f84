"""Distributed (context-parallel) paged decode attention — §Perf iteration 2.

When a paged pool's pages are striped over the `model` axis (kv heads not
divisible by the TP width), the naive gather makes GSPMD all-gather the
whole pool every layer (~GBs/step).  Flash-decoding across shards instead:

  * each model shard attends over its LOCAL pages only, producing a
    partial (acc, m, l) online-softmax state for ALL heads;
  * partials combine with one tiny psum/pmax of (B, H, D) + 2x(B, H)
    (~4 MB/layer vs ~GB/layer of pool all-gathers);
  * the new token's KV is written predicated on page ownership, so the
    scatter also stays local.

Page -> logical-position mapping is rebuilt per shard with an inverse
scatter of the block table (pages are physically scattered by the stamped
BlockPool reclaimer; logical order lives only in the table).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from .ref import from_pages


NEG_INF = -1e30


def _partial_flash(q, k, v, pos, valid):
    """Online-softmax partial over the local pages.

    q (B,H,D); k/v (B,S_loc,Hkv,D); pos (B,S_loc) logical positions;
    valid (B,S_loc).  Returns acc (B,H,D) f32, m (B,H), l (B,H).
    """
    B, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qg = q.reshape(B, Hkv, G, D)
    kT = k.transpose(0, 2, 1, 3)  # storage dtype (no f32 pool copies)
    vT = v.transpose(0, 2, 1, 3)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, kT,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bhsd->bhgd", p.astype(vT.dtype), vT,
                     preferred_element_type=jnp.float32)
    return (
        acc.reshape(B, H, D),
        m.reshape(B, H),
        l.reshape(B, H),
    )


def _shard_body(q, k_loc, v_loc, table, lengths, k1, v1, *,
                axis: str, block: int):
    idx = jax.lax.axis_index(axis)
    B = q.shape[0]
    mb_loc = k_loc.shape[1]
    barange = jnp.arange(B)

    # ---- predicated write of the new token's KV ----
    page = table[barange, lengths // block]          # (B,) global page id
    local_page = page - idx * mb_loc
    own = (local_page >= 0) & (local_page < mb_loc)
    lp = jnp.clip(local_page, 0, mb_loc - 1)
    slot = lengths % block
    old_k = k_loc[barange, lp, :, slot]  # (B, Hkv, D)
    old_v = v_loc[barange, lp, :, slot]
    k_loc = k_loc.at[barange, lp, :, slot].set(
        jnp.where(own[:, None, None], k1.astype(k_loc.dtype), old_k)
    )
    v_loc = v_loc.at[barange, lp, :, slot].set(
        jnp.where(own[:, None, None], v1.astype(v_loc.dtype), old_v)
    )

    # ---- inverse map: local page -> logical block (or -1) ----
    mb_logical = table.shape[1]
    tpage = table - idx * mb_loc                     # (B, MBlog) local ids
    t_own = (tpage >= 0) & (tpage < mb_loc)
    tclip = jnp.where(t_own, tpage, mb_loc)          # overflow row dropped
    inv = jnp.full((B, mb_loc + 1), -1, jnp.int32)
    inv = inv.at[barange[:, None], tclip].set(
        jnp.broadcast_to(
            jnp.arange(mb_logical, dtype=jnp.int32)[None], tclip.shape
        )
    )
    inv = inv[:, :mb_loc]                            # (B, mb_loc)

    # ---- logical positions + validity of every local cache slot ----
    offs = jnp.arange(block, dtype=jnp.int32)
    pos = inv[:, :, None] * block + offs[None, None, :]   # (B, mb_loc, bl)
    valid = (inv[:, :, None] >= 0) & (pos < (lengths + 1)[:, None, None])
    S_loc = mb_loc * block
    k_flat = from_pages(k_loc)  # (B, S_loc, Hkv, D)
    v_flat = from_pages(v_loc)

    acc, m, l = _partial_flash(
        q, k_flat, v_flat, pos.reshape(B, S_loc), valid.reshape(B, S_loc)
    )

    # ---- combine partials across the model axis (flash-decoding) ----
    m_g = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis)
    acc_g = jax.lax.psum(acc * corr[..., None], axis)
    out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
    return out.astype(q.dtype), k_loc, v_loc


def paged_attention_dist(
    q: jax.Array,        # (B, H, D)  — replicated over `model`
    k_pool: jax.Array,   # (B, MB, Hkv, block, D) — MB sharded over `model`
    v_pool: jax.Array,
    table: jax.Array,    # (B, MB_logical) int32
    lengths: jax.Array,  # (B,)
    k1: jax.Array,       # (B, Hkv, D) — new token's kv
    v1: jax.Array,
    *,
    mesh: Mesh,
    batch_part,          # mesh axes carrying the batch dim (or None)
    axis: str = "model",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    block = k_pool.shape[3]
    bp = batch_part
    pool_spec = P(bp, axis, None, None, None)
    body = functools.partial(_shard_body, axis=axis, block=block)
    # replicate over any mesh axis not named in the specs
    other = tuple(a for a in mesh.axis_names
                  if a != axis and a != bp
                  and not (isinstance(bp, tuple) and a in bp))
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bp, None, None),            # q
            pool_spec, pool_spec,         # pools
            P(bp, None),                  # table
            P(bp),                        # lengths
            P(bp, None, None),            # k1
            P(bp, None, None),            # v1
        ),
        out_specs=(
            P(bp, None, None),
            pool_spec,
            pool_spec,
        ),
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, table, lengths, k1, v1)


# ---------------------------------------------------------------------------
# Distributed MoE block (§Perf MoE iteration 2)
# ---------------------------------------------------------------------------
# GSPMD all-reduces the per-ASSIGNMENT down-projection output (E*C slots =
# k*capacity_factor x the token count — 60 GB/layer f32 for granite-moe
# top-8) because it cannot sink the reduction through the combine
# scatter-add.  Inside shard_map we keep the down-projection PARTIAL over
# the model axis, combine locally (gather + weighted scatter-add), and
# reduce the final (B, S, M) once — with psum_scatter onto the
# sequence-parallel layout when S divides the axis.


def _moe_body(x, router, wi_gate, wi_up, wo, *, cfg, axis: str):
    import jax.numpy as jnp

    from ..models import layers as L

    B, S, M = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(int(cfg.moe_capacity_factor * S * k / E), k)
    C = min(C, S * k)
    dt = x.dtype
    b_ix = jnp.arange(B)[:, None]

    logits = jnp.einsum("bsm,me->bse", x, router.astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_w, ids = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_ids = ids.reshape(B, S * k)
    tok_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)
    order = jnp.argsort(flat_ids, axis=-1, stable=True)
    sorted_ids = jnp.take_along_axis(flat_ids, order, -1)
    sorted_tok = jnp.take_along_axis(
        jnp.broadcast_to(tok_of[None], (B, S * k)), order, -1
    )
    sorted_w = jnp.take_along_axis(gate_w.reshape(B, S * k), order, -1)

    counts = jnp.zeros((B, E), jnp.int32).at[b_ix, flat_ids].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((B, 1), jnp.int32), jnp.cumsum(counts, -1)[:, :-1]], -1
    )
    pos = (
        jnp.arange(S * k, dtype=jnp.int32)[None]
        - jnp.take_along_axis(starts, sorted_ids, -1)
    )
    valid = pos < C
    pos_c = jnp.where(valid, pos, C)

    gathered = jnp.take_along_axis(x, sorted_tok[..., None], axis=1)
    buf = jnp.zeros((B, E, C + 1, M), dt)
    buf = buf.at[b_ix, sorted_ids, pos_c].set(gathered)
    buf = buf[:, :, :C]

    # expert FFN with F sharded over `axis`: y stays a PARTIAL sum
    g = jnp.einsum("becm,emf->becf", buf, wi_gate.astype(dt))
    u = jnp.einsum("becm,emf->becf", buf, wi_up.astype(dt))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("becf,efm->becm", h, wo.astype(dt))  # partial over axis

    y = jnp.pad(y, ((0, 0), (0, 0), (0, 1), (0, 0)))
    contrib = y[b_ix, sorted_ids, pos_c] * (
        sorted_w * valid.astype(jnp.float32)
    ).astype(dt)[..., None]
    out = jnp.zeros((B, S, M), dt).at[b_ix, sorted_tok].add(contrib)

    # single reduction of the COMBINED activations
    n = jax.lax.axis_size(axis)
    if S % n == 0 and S > 1:
        return jax.lax.psum_scatter(out, axis, scatter_dimension=1,
                                    tiled=True)
    return jax.lax.psum(out, axis)


def moe_block_dist(p, x, cfg, *, mesh: Mesh, batch_part, axis: str = "model"):
    """shard_map MoE: per-row dispatch, partial down-projection, one
    psum_scatter of the combined output (SP layout) per layer."""
    import functools as ft

    B, S, M = x.shape
    n = mesh.shape[axis]
    sp = S % n == 0 and S > 1
    body = ft.partial(_moe_body, cfg=cfg, axis=axis)
    bp = batch_part
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bp, None, None),       # x (replicated over model)
            P(None, None),           # router
            P(None, None, axis),     # wi_gate (F sharded)
            P(None, None, axis),     # wi_up
            P(None, axis, None),     # wo (F sharded)
        ),
        out_specs=P(bp, axis if sp else None, None),
        check_vma=False,
    )
    return fn(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


# ---------------------------------------------------------------------------
# Distributed rolling-window (SWA) decode attention
# ---------------------------------------------------------------------------
# The rolling ring buffer is sharded over `model` on the window dim; naive
# decode attention makes GSPMD all-gather the ring every layer.  Ring order
# is softmax-irrelevant (positions are baked into K via RoPE at write
# time), so each shard attends over its local slots and partials combine
# exactly like the paged flash-decode.


def _rolling_body(q, k_loc, v_loc, lengths, k1, v1, *, axis: str, W: int):
    idx = jax.lax.axis_index(axis)
    B = q.shape[0]
    w_loc = k_loc.shape[1]
    barange = jnp.arange(B)

    # predicated write: global ring slot -> owning shard
    slot = lengths % W
    local = slot - idx * w_loc
    own = (local >= 0) & (local < w_loc)
    lp = jnp.clip(local, 0, w_loc - 1)
    k_loc = k_loc.at[barange, lp].set(
        jnp.where(own[:, None, None], k1.astype(k_loc.dtype),
                  k_loc[barange, lp]))
    v_loc = v_loc.at[barange, lp].set(
        jnp.where(own[:, None, None], v1.astype(v_loc.dtype),
                  v_loc[barange, lp]))

    # validity: global slot id < number of filled slots
    n_valid = jnp.minimum(lengths + 1, W)  # (B,)
    gslot = idx * w_loc + jnp.arange(w_loc)  # (w_loc,)
    valid = gslot[None, :] < n_valid[:, None]
    pos = jnp.zeros((B, w_loc), jnp.int32)  # unused (no position mask)

    acc, m, l = _partial_flash(q, k_loc, v_loc, pos, valid)
    m_g = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis)
    acc_g = jax.lax.psum(acc * corr[..., None], axis)
    out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
    return out.astype(q.dtype), k_loc, v_loc


def rolling_attention_dist(q, k_cache, v_cache, lengths, k1, v1, *,
                           mesh: Mesh, batch_part, axis: str = "model"):
    """k_cache/v_cache: (B, W, Hkv, D) ring sharded over `axis` on W."""
    W = k_cache.shape[1]
    bp = batch_part
    spec = P(bp, axis, None, None)
    body = functools.partial(_rolling_body, axis=axis, W=W)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(bp, None, None), spec, spec, P(bp),
                  P(bp, None, None), P(bp, None, None)),
        out_specs=(P(bp, None, None), spec, spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, lengths, k1, v1)
