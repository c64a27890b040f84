"""Transformer building blocks shared by the architecture zoo.

All layers are plain functions over parameter dicts (pytrees of arrays or
ShapeDtypeStructs via :mod:`repro.models.param`), so a single definition
serves training, prefill and decode, and lowers cleanly under pjit on the
production meshes.

Attention supports GQA (+ optional QKV bias, sliding window) and three KV
cache layouts:
  * contiguous — (B, S_max, Hkv, D), classic serving cache
  * paged      — (N_blocks, Hkv, block, D) pool + (B, max_blocks) block
                 tables; pages are recycled through the stamped BlockPool
                 (the paper's technique at the serving layer)
  * rolling    — (B, window, Hkv, D) ring buffer for sliding-window models
                 (mixtral long-context decode)
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..kernels.ref import from_pages, to_pages
from .param import ParamSpec

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_specs(cfg: ModelConfig, layered: bool = True) -> ParamSpec:
    lead = (cfg.num_layers,) if layered else ()
    lead_ax = ("layers",) if layered else ()
    return {
        "scale": ParamSpec(lead + (cfg.d_model,), lead_ax + ("embed",),
                           init="ones")
    }


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        xf = xf - mu
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(
        jnp.float32
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with positions (..., S) — rotate pairs."""
    D = x.shape[-1]
    half = D // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., None, :]  # (...,S,1,half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def attention_specs(
    cfg: ModelConfig, layers: int, heads: Optional[int] = None
) -> Dict[str, ParamSpec]:
    H = heads or cfg.num_heads
    Hkv = cfg.num_kv_heads or H
    D = cfg.resolved_head_dim
    M = cfg.d_model
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    # fan-in scaling over the contracted dims: d_model into q/k/v, heads x
    # head_dim into wo.  (init="scaled" reads shape[-2], a head axis here:
    # the random scores then saturate every softmax and a random deep
    # model turns bf16 rounding into different tokens.)
    w_in, w_out = M ** -0.5, (H * D) ** -0.5
    specs = {
        "wq": ParamSpec(lead + (M, H, D), la + ("embed", "heads", None),
                        scale=w_in),
        "wk": ParamSpec(lead + (M, Hkv, D), la + ("embed", "kv_heads", None),
                        scale=w_in),
        "wv": ParamSpec(lead + (M, Hkv, D), la + ("embed", "kv_heads", None),
                        scale=w_in),
        "wo": ParamSpec(lead + (H, D, M), la + ("heads", None, "embed"),
                        scale=w_out),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec(lead + (H, D), la + ("heads", None),
                                init="zeros")
        specs["bk"] = ParamSpec(lead + (Hkv, D), la + ("kv_heads", None),
                                init="zeros")
        specs["bv"] = ParamSpec(lead + (Hkv, D), la + ("kv_heads", None),
                                init="zeros")
    return specs


def _project_qkv(p, x, cfg: ModelConfig):
    dt = x.dtype
    q = jnp.einsum("bsm,mhd->bshd", x, p["wq"].astype(dt))
    k = jnp.einsum("bsm,mhd->bshd", x, p["wk"].astype(dt))
    v = jnp.einsum("bsm,mhd->bshd", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def attention_full(
    p,
    x: jax.Array,  # (B, S, M)
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,  # (S,) absolute positions
    causal: bool = True,
    kv_x: Optional[jax.Array] = None,  # cross-attention source (B, S_kv, M)
    use_rope: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (train / prefill / encoder / cross).

    Returns (out (B,S,M), (k, v)) so prefill can populate the cache.
    """
    B, S, M = x.shape
    dt = x.dtype
    src = kv_x if kv_x is not None else x
    q = jnp.einsum("bsm,mhd->bshd", x, p["wq"].astype(dt))
    k = jnp.einsum("bsm,mhd->bshd", src, p["wk"].astype(dt))
    v = jnp.einsum("bsm,mhd->bshd", src, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if use_rope and kv_x is None:
        pos = positions if positions is not None else jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = ops.flash_attention(
        q, k, v, causal=causal, window=cfg.sliding_window
    )
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"].astype(dt))
    return out, (k, v)


def attention_decode(
    p,
    x: jax.Array,  # (B, 1, M) — one new token per sequence
    cfg: ModelConfig,
    cache: Dict[str, jax.Array],  # per-layer slice (no leading L dim)
    lengths: jax.Array,  # (B,) tokens already in cache
    *,
    block_table: Optional[jax.Array] = None,  # (B, max_blocks) for paged
    n_kv: Optional[int] = None,  # static bound on the paged KV sweep
    global_pages: bool = False,  # table holds slot-flattened global ids
    use_rope: bool = True,
    cross: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token decode against a KV cache (contiguous/paged/rolling).

    Cross-attention decode reads a fixed cache and writes nothing.
    ``n_kv`` bounds the paged-attention page sweep (local path only; the
    context-parallel distributed path always sweeps its stripe).
    ``global_pages`` switches the paged path to slot-flattened GLOBAL page
    ids (``slot * N_pool + page``): a block-table row may then reference
    pages physically owned by another slot — how copy-on-write forks share
    one prompt prefix across N branches.
    """
    B, S1, M = x.shape
    assert S1 == 1
    dt = x.dtype
    q = jnp.einsum("bsm,mhd->bshd", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if use_rope and not cross:
        q = apply_rope(q, lengths[:, None], cfg.rope_theta)
    q1 = q[:, 0]  # (B, H, D)

    if cross:
        out = ops.decode_attention(q1, cache["k"], cache["v"], cache["len"])
        out = jnp.einsum("bhd,hdm->bm", out, p["wo"].astype(dt))
        return out[:, None], cache

    k_new = jnp.einsum("bsm,mhd->bshd", x, p["wk"].astype(dt))
    v_new = jnp.einsum("bsm,mhd->bshd", x, p["wv"].astype(dt))
    if "bk" in p:
        k_new = k_new + p["bk"].astype(dt)
        v_new = v_new + p["bv"].astype(dt)
    if use_rope:
        k_new = apply_rope(k_new, lengths[:, None], cfg.rope_theta)
    k1, v1 = k_new[:, 0], v_new[:, 0]  # (B, Hkv, D)

    if block_table is not None:
        dist = ops.dist_decode_config()
        hkv = cfg.num_kv_heads or cfg.num_heads
        if dist is not None and hkv % 16 != 0:
            # §Perf iteration 2: context-parallel flash-decode over the
            # page-striped pool (no pool all-gathers).  The striped kernel
            # addresses (slot, page) pairs, so cross-slot CoW refs are not
            # representable: global ids fold back to local — correct only
            # while every row references its own slot's pages (the engine
            # keeps forking off when page striping is active).
            from ..kernels.distributed import paged_attention_dist

            n_pool = cache["k_pool"].shape[1]
            dist_table = (block_table % n_pool if global_pages
                          else block_table)
            out, k_pool, v_pool = paged_attention_dist(
                q1, cache["k_pool"], cache["v_pool"], dist_table,
                lengths, k1, v1, mesh=dist["mesh"],
                batch_part=dist["batch_part"], axis=dist["axis"],
            )
            out = jnp.einsum("bhd,hdm->bm", out, p["wo"].astype(dt))
            return out[:, None], dict(cache, k_pool=k_pool, v_pool=v_pool)
        block = cache["k_pool"].shape[3]
        barange = jnp.arange(B)
        offs = lengths % block
        if global_pages:
            # ---- paged cache, slot-flattened global ids (CoW forks) ----
            n_pool = cache["k_pool"].shape[1]
            page_g = block_table[barange, lengths // block]  # (B,) global
            flat = (B * n_pool,) + cache["k_pool"].shape[2:]
            # inactive slots' zero rows all land on global page 0 (slot
            # 0's scratch page) — never read, same contract as the local
            # path's per-slot scratch page
            k_pool = cache["k_pool"].reshape(flat).at[page_g, :, offs].set(
                k1).reshape(cache["k_pool"].shape)
            v_pool = cache["v_pool"].reshape(flat).at[page_g, :, offs].set(
                v1).reshape(cache["v_pool"].shape)
        else:
            # ---- paged cache (per-sequence-local pools) ----
            page = block_table[barange, lengths // block]  # (B,) local id
            k_pool = cache["k_pool"].at[barange, page, :, offs].set(k1)
            v_pool = cache["v_pool"].at[barange, page, :, offs].set(v1)
        out = ops.paged_attention(
            q1, k_pool, v_pool, block_table, lengths + 1, n_kv=n_kv,
            global_pages=global_pages,
        )
        new_cache = dict(cache, k_pool=k_pool, v_pool=v_pool)
    elif cfg.sliding_window and cache["k"].shape[1] == cfg.sliding_window:
        # ---- rolling (sliding-window) cache ----
        W = cfg.sliding_window
        dist = ops.dist_decode_config()
        if dist is not None and W % 16 == 0:
            from ..kernels.distributed import rolling_attention_dist

            out, k_c, v_c = rolling_attention_dist(
                q1, cache["k"], cache["v"], lengths, k1, v1,
                mesh=dist["mesh"], batch_part=dist["batch_part"],
                axis=dist["axis"],
            )
            out = jnp.einsum("bhd,hdm->bm", out, p["wo"].astype(dt))
            return out[:, None], dict(cache, k=k_c, v=v_c)
        slot = lengths % W
        k_c = cache["k"].at[jnp.arange(B), slot].set(k1)
        v_c = cache["v"].at[jnp.arange(B), slot].set(v1)
        valid = jnp.minimum(lengths + 1, W)
        out = ops.decode_attention(q_rolling(q1, cfg), k_c, v_c, valid)
        new_cache = dict(cache, k=k_c, v=v_c)
    else:
        # ---- contiguous cache ----
        k_c = cache["k"].at[jnp.arange(B), lengths].set(k1)
        v_c = cache["v"].at[jnp.arange(B), lengths].set(v1)
        out = ops.decode_attention(q1, k_c, v_c, lengths + 1)
        new_cache = dict(cache, k=k_c, v=v_c)

    out = jnp.einsum("bhd,hdm->bm", out, p["wo"].astype(dt))
    return out[:, None], new_cache


def attention_chunk(
    p,
    x: jax.Array,  # (1, C, M) — one prefill chunk for one slot
    cfg: ModelConfig,
    cache: Dict[str, jax.Array],  # per-layer slice (no leading L dim)
    *,
    slot: jax.Array,       # scalar int32 — the admitting slot
    row: jax.Array,        # (mb,) int32 — block-table row incl. this chunk
    pages: jax.Array,      # (nc,) int32 — pages this chunk writes
    positions: jax.Array,  # (C,) int32 — absolute token positions
    n_kv: int,             # static bound on the prior-KV page sweep
    global_pages: bool = False,  # row/pages hold slot-flattened global ids
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked-prefill attention against a paged KV cache.

    The chunk's K/V are scattered into the slot's pool pages at the chunk
    offsets FIRST, then the queries attend causally (``q_offset`` masking)
    over the first ``n_kv`` pages of the slot's block-table row — which
    now hold every earlier chunk AND this one.  Padded / unallocated
    positions sit past the causal horizon, so their (garbage) keys mask to
    exact zeros: the output at every valid position is bit-identical to a
    whole-prompt prefill of the same tokens (asserted in
    tests/test_chunked_prefill.py).

    With ``global_pages`` the ``row``/``pages`` operands carry global ids
    into the slot-flattened pool (``slot`` is then only the scratch-row
    owner); writes and the row gather address the flat pool directly.
    """
    dt = x.dtype
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    block = cache["k_pool"].shape[3]
    kc = to_pages(k[0], block).astype(cache["k_pool"].dtype)  # (nc,Hkv,bl,D)
    vc = to_pages(v[0], block).astype(cache["v_pool"].dtype)
    if global_pages:
        flat = (-1,) + cache["k_pool"].shape[2:]
        kfl = cache["k_pool"].reshape(flat).at[pages].set(kc)
        vfl = cache["v_pool"].reshape(flat).at[pages].set(vc)
        gk = from_pages(kfl[row[:n_kv]])[None]  # (1, n_kv*block, Hkv, D)
        gv = from_pages(vfl[row[:n_kv]])[None]
        kp = kfl.reshape(cache["k_pool"].shape)
        vp = vfl.reshape(cache["v_pool"].shape)
    else:
        kp = cache["k_pool"].at[slot, pages].set(kc)
        vp = cache["v_pool"].at[slot, pages].set(vc)
        gk = from_pages(kp[slot][row[:n_kv]])[None]
        gv = from_pages(vp[slot][row[:n_kv]])[None]
    out = ops.flash_attention(q, gk, gv, causal=True, q_offset=positions[0])
    out = jnp.einsum("bshd,hdm->bsm", out, p["wo"].astype(dt))
    return out, dict(cache, k_pool=kp, v_pool=vp)


def q_rolling(q1: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rolling caches lose absolute slot order; attention over a ring is
    order-invariant under softmax (positions already baked into k via
    RoPE), so q passes through unchanged."""
    return q1


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU)
# ---------------------------------------------------------------------------
def mlp_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    M, F = cfg.d_model, cfg.d_ff
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    return {
        "wi_gate": ParamSpec(lead + (M, F), la + ("embed", "mlp"),
                             init="scaled"),
        "wi_up": ParamSpec(lead + (M, F), la + ("embed", "mlp"),
                           init="scaled"),
        "wo": ParamSpec(lead + (F, M), la + ("mlp", "embed"), init="scaled"),
    }


def apply_mlp(p, x, cfg: ModelConfig):
    dt = x.dtype
    g = jnp.einsum("bsm,mf->bsf", x, p["wi_gate"].astype(dt))
    u = jnp.einsum("bsm,mf->bsf", x, p["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fm->bsm", h, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# MoE (top-k, scatter-based dropping dispatch — no one-hot einsum FLOPs)
# ---------------------------------------------------------------------------
def moe_specs(cfg: ModelConfig, layers: int) -> Dict[str, ParamSpec]:
    M, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    lead = (layers,) if layers else ()
    la = ("layers",) if layers else ()
    return {
        "router": ParamSpec(lead + (M, E), la + ("embed", None),
                            init="scaled"),
        "wi_gate": ParamSpec(lead + (E, M, F),
                             la + ("experts", "embed", "mlp"), init="scaled"),
        "wi_up": ParamSpec(lead + (E, M, F),
                           la + ("experts", "embed", "mlp"), init="scaled"),
        "wo": ParamSpec(lead + (E, F, M),
                        la + ("experts", "mlp", "embed"), init="scaled"),
    }


def apply_moe(p, x, cfg: ModelConfig):
    """Top-k MoE with capacity-bounded, batched PER-ROW scatter dispatch.

    All dispatch bookkeeping (top-k, counts, ranks, scatter/gather) is
    batched over the leading batch dim and never mixes tokens across rows,
    so under GSPMD it partitions cleanly on the data axis with NO global
    sort / resharding collectives (§Perf iteration on the MoE cells; the
    earlier flat-token formulation forced TB-scale all-reduces).  Gather/
    scatter are memory ops, so HLO FLOPs stay equal to the *active*
    expert FLOPs (no GShard one-hot einsum fake-FLOPs).
    """
    dist = ops.dist_moe_config()
    if dist is not None:
        from ..kernels.distributed import moe_block_dist

        return moe_block_dist(p, x, cfg, mesh=dist["mesh"],
                              batch_part=dist["batch_part"],
                              axis=dist["axis"])
    B, S, M = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    # per-row, per-expert capacity (dropless for S == 1 decode)
    C = max(int(cfg.moe_capacity_factor * S * k / E), k)
    C = min(C, S * k)
    dt = x.dtype
    b_ix = jnp.arange(B)[:, None]

    logits = jnp.einsum("bsm,me->bse", x, p["router"].astype(dt))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_w, ids = jax.lax.top_k(probs, k)          # (B, S, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_ids = ids.reshape(B, S * k)
    tok_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)  # (S*k,)
    order = jnp.argsort(flat_ids, axis=-1, stable=True)     # per-row sort
    sorted_ids = jnp.take_along_axis(flat_ids, order, -1)
    sorted_tok = jnp.broadcast_to(tok_of[None], (B, S * k))
    sorted_tok = jnp.take_along_axis(sorted_tok, order, -1)
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
    pos_c = jnp.where(valid, pos, C)               # overflow slot (dropped)

    gathered = jnp.take_along_axis(
        x, sorted_tok[..., None], axis=1
    )                                              # (B, S*k, M)
    buf = jnp.zeros((B, E, C + 1, M), dt)
    buf = buf.at[b_ix, sorted_ids, pos_c].set(gathered)
    buf = buf[:, :, :C]

    g = jnp.einsum("becm,emf->becf", buf, p["wi_gate"].astype(dt))
    u = jnp.einsum("becm,emf->becf", buf, p["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("becf,efm->becm", h, p["wo"].astype(dt))

    y = jnp.pad(y, ((0, 0), (0, 0), (0, 1), (0, 0)))
    contrib = y[b_ix, sorted_ids, pos_c] * (
        sorted_w * valid.astype(jnp.float32)
    ).astype(dt)[..., None]
    out = jnp.zeros((B, S, M), dt).at[b_ix, sorted_tok].add(contrib)
    return out


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    specs = {
        "tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), init="scaled"
        )
    return specs


def embed_tokens(p, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return p["tok"].astype(jnp.dtype(cfg.dtype))[tokens]


def unembed(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if cfg.tie_embeddings:
        return jnp.einsum("b...m,vm->b...v", x, p["tok"].astype(dt))
    return jnp.einsum("b...m,mv->b...v", x, p["unembed"].astype(dt))
