"""Architecture-zoo model definitions: decoder-only (dense/MoE/VLM),
Mamba2 (SSM), Zamba2-style hybrid, and encoder-decoder stacks.

Design:
  * One ParamSpec tree per config (``build_specs``): layer params stacked
    over a leading ``layers`` axis and run with ``lax.scan`` (keeps HLO and
    compile time O(1) in depth — essential for 33 dry-run cells x 2 meshes).
  * Training bodies are wrapped in ``jax.checkpoint`` (full remat by
    default, policy configurable for the §Perf hillclimb).
  * An optional ``constrain(x)`` hook applies sequence-parallel sharding
    constraints on the residual stream between layers (Megatron-SP): the
    saved remat carries are then sharded over the `model` axis, which is
    what makes 34B-scale training fit HBM.
  * Caches are declared as ParamSpec trees too, so dry-run abstract values
    and shardings come from the same machinery as params.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import ssm as S
from .param import ParamSpec

Constrain = Callable[[jax.Array], jax.Array]
_id: Constrain = lambda x: x

#: KV page size (TPU lane-aligned)
BLOCK_SIZE = 128
#: production tensor-parallel width (both meshes use model=16)
TP_WIDTH = 16


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------
def _decoder_layer_specs(cfg: ModelConfig, n: int) -> Dict[str, Any]:
    specs = {
        "norm1": {"scale": ParamSpec((n, cfg.d_model), ("layers", "embed"),
                                     init="ones")},
        "attn": L.attention_specs(cfg, n),
        "norm2": {"scale": ParamSpec((n, cfg.d_model), ("layers", "embed"),
                                     init="ones")},
    }
    if cfg.family == "moe":
        specs["moe"] = L.moe_specs(cfg, n)
    else:
        specs["mlp"] = L.mlp_specs(cfg, n)
    return specs


def build_specs(cfg: ModelConfig) -> Dict[str, Any]:
    specs: Dict[str, Any] = {"embed": L.embed_specs(cfg)}
    if cfg.family in ("dense", "moe", "vlm"):
        specs["layers"] = _decoder_layer_specs(cfg, cfg.num_layers)
    elif cfg.family == "ssm":
        specs["layers"] = {
            "norm": {"scale": ParamSpec((cfg.num_layers, cfg.d_model),
                                        ("layers", "embed"), init="ones")},
            "mamba": S.mamba_specs(cfg, cfg.num_layers),
        }
    elif cfg.family == "hybrid":
        specs["layers"] = {
            "norm": {"scale": ParamSpec((cfg.num_layers, cfg.d_model),
                                        ("layers", "embed"), init="ones")},
            "mamba": S.mamba_specs(cfg, cfg.num_layers),
        }
        # one shared attention block, applied every attn_period layers
        shared = {
            "norm1": {"scale": ParamSpec((cfg.d_model,), ("embed",),
                                         init="ones")},
            "attn": L.attention_specs(cfg, 0),
            "norm2": {"scale": ParamSpec((cfg.d_model,), ("embed",),
                                         init="ones")},
            "mlp": L.mlp_specs(cfg, 0),
        }
        specs["shared_attn"] = shared
    elif cfg.family == "encdec":
        ne, nd = cfg.encoder_layers, cfg.num_layers
        specs["enc_layers"] = {
            "norm1": {"scale": ParamSpec((ne, cfg.d_model),
                                         ("layers", "embed"), init="ones")},
            "attn": L.attention_specs(cfg, ne),
            "norm2": {"scale": ParamSpec((ne, cfg.d_model),
                                         ("layers", "embed"), init="ones")},
            "mlp": L.mlp_specs(cfg, ne),
        }
        specs["enc_norm"] = {
            "scale": ParamSpec((cfg.d_model,), ("embed",), init="ones")
        }
        specs["dec_layers"] = {
            "norm1": {"scale": ParamSpec((nd, cfg.d_model),
                                         ("layers", "embed"), init="ones")},
            "self_attn": L.attention_specs(cfg, nd),
            "norm_x": {"scale": ParamSpec((nd, cfg.d_model),
                                          ("layers", "embed"), init="ones")},
            "cross_attn": L.attention_specs(cfg, nd),
            "norm2": {"scale": ParamSpec((nd, cfg.d_model),
                                         ("layers", "embed"), init="ones")},
            "mlp": L.mlp_specs(cfg, nd),
        }
    else:  # pragma: no cover
        raise ValueError(f"unknown family {cfg.family}")
    specs["final_norm"] = {
        "scale": ParamSpec((cfg.d_model,), ("embed",), init="ones")
    }
    return specs


# ---------------------------------------------------------------------------
# Cache specs (decode / prefill-output)
# ---------------------------------------------------------------------------
def cache_layout(cfg: ModelConfig) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.sliding_window > 0:
        return "rolling"
    return "paged"


def paged_blocks_sharded_cfg(cfg: ModelConfig) -> bool:
    """True when the paged pool stripes PAGES over `model` (kv heads do
    not divide the TP width, so head-sharding is unavailable)."""
    hkv = cfg.num_kv_heads or cfg.num_heads
    return hkv % TP_WIDTH != 0


def n_shared_attn(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_period if cfg.attn_period else 0


def _attn_cache_specs(cfg, n_layers, batch, max_seq, layout, dtype,
                      pool_slack: int = 0):
    Hkv = cfg.num_kv_heads or cfg.num_heads
    D = cfg.resolved_head_dim
    if layout == "rolling":
        W = min(cfg.sliding_window, max_seq)
        return {
            "k": ParamSpec((n_layers, batch, W, Hkv, D),
                           ("layers", "batch", "window", "kv_heads", None),
                           dtype=dtype, init="zeros"),
            "v": ParamSpec((n_layers, batch, W, Hkv, D),
                           ("layers", "batch", "window", "kv_heads", None),
                           dtype=dtype, init="zeros"),
        }
    if layout == "contiguous":
        return {
            "k": ParamSpec((n_layers, batch, max_seq, Hkv, D),
                           ("layers", "batch", "kv_seq", "kv_heads", None),
                           dtype=dtype, init="zeros"),
            "v": ParamSpec((n_layers, batch, max_seq, Hkv, D),
                           ("layers", "batch", "kv_seq", "kv_heads", None),
                           dtype=dtype, init="zeros"),
        }
    # paged (per-sequence-local pools).  Sharding choice (§Perf iter 1/1b):
    #   * kv_heads divisible by the TP width -> shard kv heads (gathers
    #     stay local, no pool collectives);
    #   * otherwise stripe the PAGES over `model` (pool page count rounded
    #     to a TP_WIDTH multiple so the dim divides) and use the
    #     distributed flash-decode (kernels/distributed.py) to avoid pool
    #     all-gathers.
    # Pages are head-major (Hkv, block, D): see kernels.ref.to_pages.
    mb = -(-max_seq // BLOCK_SIZE) + 1 + pool_slack
    if pool_slack == 0 and paged_blocks_sharded_cfg(cfg):
        mb = -(-mb // TP_WIDTH) * TP_WIDTH
    blocks_ax = "blocks" if paged_blocks_sharded_cfg(cfg) else None
    return {
        "k_pool": ParamSpec(
            (n_layers, batch, mb, Hkv, BLOCK_SIZE, D),
            ("layers", "batch", blocks_ax, "kv_heads", None, None),
            dtype=dtype, init="zeros"),
        "v_pool": ParamSpec(
            (n_layers, batch, mb, Hkv, BLOCK_SIZE, D),
            ("layers", "batch", blocks_ax, "kv_heads", None, None),
            dtype=dtype, init="zeros"),
    }


def _ssm_cache_specs(cfg, n_layers, batch):
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    W = cfg.ssm_conv_width
    DI = cfg.ssm_inner
    return {
        "state": ParamSpec((n_layers, batch, H, P, N),
                           ("layers", "batch", "ssm_heads", None, None),
                           dtype=jnp.float32, init="zeros"),
        "conv_x": ParamSpec((n_layers, batch, W - 1, DI),
                            ("layers", "batch", None, "ssm_inner"),
                            dtype=jnp.float32, init="zeros"),
        "conv_b": ParamSpec((n_layers, batch, W - 1, G * N),
                            ("layers", "batch", None, None),
                            dtype=jnp.float32, init="zeros"),
        "conv_c": ParamSpec((n_layers, batch, W - 1, G * N),
                            ("layers", "batch", None, None),
                            dtype=jnp.float32, init="zeros"),
    }


def cache_specs(
    cfg: ModelConfig, batch: int, max_seq: int, enc_len: int = 0,
    pool_slack: int = 0,
) -> Dict[str, Any]:
    """ParamSpec tree for the decode cache of this architecture.

    ``pool_slack`` adds spare pages per sequence beyond ceil(max_seq/block)
    (the serving engine's recycling headroom; the BlockPool hands out ids
    over the SAME range, asserted in the engine).
    """
    dtype = jnp.dtype(cfg.dtype)
    layout = cache_layout(cfg)
    if layout == "ssm":
        return {"layers": _ssm_cache_specs(cfg, cfg.num_layers, batch)}
    if layout == "hybrid":
        na = n_shared_attn(cfg)
        return {
            "layers": _ssm_cache_specs(cfg, cfg.num_layers, batch),
            "attn": _attn_cache_specs(cfg, na, batch, max_seq, "paged",
                                      dtype, pool_slack),
        }
    if cfg.is_encdec:
        Hkv = cfg.num_kv_heads or cfg.num_heads
        D = cfg.resolved_head_dim
        return {
            "self": _attn_cache_specs(cfg, cfg.num_layers, batch, max_seq,
                                      "paged", dtype, pool_slack),
            "cross_k": ParamSpec(
                (cfg.num_layers, batch, enc_len, Hkv, D),
                ("layers", "batch", "kv_seq", "kv_heads", None),
                dtype=dtype, init="zeros"),
            "cross_v": ParamSpec(
                (cfg.num_layers, batch, enc_len, Hkv, D),
                ("layers", "batch", "kv_seq", "kv_heads", None),
                dtype=dtype, init="zeros"),
            "enc_len": ParamSpec((batch,), ("batch",), dtype=jnp.int32,
                                 init="zeros"),
        }
    return {"layers": _attn_cache_specs(cfg, cfg.num_layers, batch, max_seq,
                                        layout, dtype, pool_slack)}


# ---------------------------------------------------------------------------
# Decoder-only stacks (dense / moe / vlm)
# ---------------------------------------------------------------------------
def _remat(body, policy: Optional[str]):
    if policy is None or policy == "none":
        return body
    if policy == "full":
        return jax.checkpoint(body)
    if policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    raise ValueError(policy)


def run_decoder_stack(
    params, x, cfg: ModelConfig, *,
    constrain: Constrain = _id,
    remat: Optional[str] = None,
    emit_kv: bool = False,
    positions=None,
):
    """Full-sequence pass over stacked decoder layers via lax.scan."""

    def body(h, lp):
        h = constrain(h)
        a_in = L.apply_norm(lp["norm1"], h, cfg)
        a, kv = L.attention_full(
            lp["attn"], a_in, cfg, causal=True, positions=positions
        )
        h = h + a
        m_in = L.apply_norm(lp["norm2"], h, cfg)
        if cfg.family == "moe":
            m = L.apply_moe(lp["moe"], m_in, cfg)
        else:
            m = L.apply_mlp(lp["mlp"], m_in, cfg)
        h = h + m
        return h, (kv if emit_kv else None)

    x, kvs = jax.lax.scan(_remat(body, remat), x, params["layers"])
    return constrain(x), kvs


def run_ssm_stack(
    params, x, cfg: ModelConfig, *,
    constrain: Constrain = _id,
    remat: Optional[str] = None,
    emit_cache: bool = False,
):
    def body(h, lp):
        h = constrain(h)
        m_in = L.apply_norm(lp["norm"], h, cfg)
        m, cache = S.mamba_full(lp["mamba"], m_in, cfg)
        h = h + m
        return h, (cache if emit_cache else None)

    x, caches = jax.lax.scan(_remat(body, remat), x, params["layers"])
    return constrain(x), caches


def run_hybrid_stack(
    params, x, cfg: ModelConfig, *,
    constrain: Constrain = _id,
    remat: Optional[str] = None,
    emit_cache: bool = False,
    positions=None,
):
    """Zamba2-style: scan `attn_period`-sized groups of mamba layers, each
    followed by the *shared* attention block; trailing mamba layers after."""
    period = cfg.attn_period
    n_attn = n_shared_attn(cfg)
    n_grouped = n_attn * period
    shared = params["shared_attn"]

    def mamba_layer(h, lp):
        h = constrain(h)
        m_in = L.apply_norm(lp["norm"], h, cfg)
        m, cache = S.mamba_full(lp["mamba"], m_in, cfg)
        return h + m, (cache if emit_cache else None)

    def group_body(h, lp_group):
        h, caches = jax.lax.scan(mamba_layer, h, lp_group)
        a_in = L.apply_norm(shared["norm1"], h, cfg)
        a, kv = L.attention_full(shared["attn"], a_in, cfg, causal=True,
                                 positions=positions)
        h = h + a
        m_in = L.apply_norm(shared["norm2"], h, cfg)
        h = h + L.apply_mlp(shared["mlp"], m_in, cfg)
        return h, (caches, (kv if emit_cache else None))

    grouped = jax.tree.map(
        lambda a: a[:n_grouped].reshape((n_attn, period) + a.shape[1:]),
        params["layers"],
    )
    trailing = jax.tree.map(lambda a: a[n_grouped:], params["layers"])

    x, (gcaches, kvs) = jax.lax.scan(_remat(group_body, remat), x, grouped)
    n_trail = cfg.num_layers - n_grouped
    tcaches = None
    if n_trail:
        x, tcaches = jax.lax.scan(_remat(mamba_layer, remat), x, trailing)
    if not emit_cache:
        return constrain(x), None
    # flatten grouped caches (n_attn, period, B, ...) -> (L_grouped, B, ...)
    flat = jax.tree.map(
        lambda a: a.reshape((n_grouped,) + a.shape[2:]), gcaches
    )
    if n_trail:
        merged = jax.tree.map(
            lambda g, t: jnp.concatenate([g, t], 0), flat, tcaches
        )
    else:
        merged = flat
    return constrain(x), (merged, kvs)


def run_encoder_stack(params, x, cfg: ModelConfig, *,
                      constrain: Constrain = _id, remat=None):
    def body(h, lp):
        h = constrain(h)
        a_in = L.apply_norm(lp["norm1"], h, cfg)
        a, _ = L.attention_full(lp["attn"], a_in, cfg, causal=False)
        h = h + a
        m_in = L.apply_norm(lp["norm2"], h, cfg)
        h = h + L.apply_mlp(lp["mlp"], m_in, cfg)
        return h, None

    x, _ = jax.lax.scan(_remat(body, remat), x, params["enc_layers"])
    return constrain(x)


def run_decoder_xattn_stack(params, x, enc_out, cfg: ModelConfig, *,
                            constrain: Constrain = _id, remat=None,
                            emit_kv: bool = False):
    def body(h, lp):
        h = constrain(h)
        a_in = L.apply_norm(lp["norm1"], h, cfg)
        a, self_kv = L.attention_full(lp["self_attn"], a_in, cfg,
                                      causal=True)
        h = h + a
        x_in = L.apply_norm(lp["norm_x"], h, cfg)
        xa, cross_kv = L.attention_full(lp["cross_attn"], x_in, cfg,
                                        causal=False, kv_x=enc_out)
        h = h + xa
        m_in = L.apply_norm(lp["norm2"], h, cfg)
        h = h + L.apply_mlp(lp["mlp"], m_in, cfg)
        return h, ((self_kv, cross_kv) if emit_kv else None)

    x, kvs = jax.lax.scan(_remat(body, remat), x, params["dec_layers"])
    return constrain(x), kvs


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
def _embed_inputs(params, batch, cfg: ModelConfig):
    """Token (+ frontend stub) embedding -> (B, S, M) residual stream."""
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
    if cfg.family == "vlm" and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(x.dtype)
        x = jnp.concatenate([fe, x], axis=1)
    return x


def forward_train(params, batch, cfg: ModelConfig, *,
                  constrain: Constrain = _id,
                  remat: Optional[str] = "full"):
    """Next-token LM loss (enc-dec: seq2seq loss on the decoder)."""
    if cfg.is_encdec:
        enc = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
        enc_out = run_encoder_stack(params, enc, cfg, constrain=constrain,
                                    remat=remat)
        enc_out = L.apply_norm(params["enc_norm"], enc_out, cfg)
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
        x, _ = run_decoder_xattn_stack(params, x, enc_out, cfg,
                                       constrain=constrain, remat=remat)
    elif cfg.family == "ssm":
        x = _embed_inputs(params, batch, cfg)
        x, _ = run_ssm_stack(params, x, cfg, constrain=constrain,
                             remat=remat)
    elif cfg.family == "hybrid":
        x = _embed_inputs(params, batch, cfg)
        x, _ = run_hybrid_stack(params, x, cfg, constrain=constrain,
                                remat=remat)
    else:
        x = _embed_inputs(params, batch, cfg)
        x, _ = run_decoder_stack(params, x, cfg, constrain=constrain,
                                 remat=remat)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x, cfg)

    labels = batch["labels"]
    if cfg.family == "vlm" and "frontend_embeds" in batch:
        # loss only on text positions (labels already text-aligned)
        n_front = batch["frontend_embeds"].shape[1]
        logits = logits[:, n_front:]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, {"loss": loss, "ntokens": mask.sum()}


def forward_prefill(params, batch, cfg: ModelConfig, *,
                    constrain: Constrain = _id):
    """Prefill: full-sequence pass emitting last-position logits + the KV /
    state caches (contiguous; the engine pages them into the BlockPool).

    ``batch["last_index"]`` (B,) optionally selects the per-sequence logit
    position (padded prompts in the serving engine); default: position -1.
    """
    if cfg.is_encdec:
        enc = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
        enc_out = run_encoder_stack(params, enc, cfg, constrain=constrain)
        enc_out = L.apply_norm(params["enc_norm"], enc_out, cfg)
        x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
        x, kvs = run_decoder_xattn_stack(params, x, enc_out, cfg,
                                         constrain=constrain, emit_kv=True)
        cache = {
            "self_k": kvs[0][0], "self_v": kvs[0][1],
            "cross_k": kvs[1][0], "cross_v": kvs[1][1],
        }
    elif cfg.family == "ssm":
        x = _embed_inputs(params, batch, cfg)
        x, caches = run_ssm_stack(params, x, cfg, constrain=constrain,
                                  emit_cache=True)
        cache = caches
    elif cfg.family == "hybrid":
        x = _embed_inputs(params, batch, cfg)
        x, (mcache, kvs) = run_hybrid_stack(params, x, cfg,
                                            constrain=constrain,
                                            emit_cache=True)
        cache = {"mamba": mcache, "attn_k": kvs[0], "attn_v": kvs[1]}
    else:
        x = _embed_inputs(params, batch, cfg)
        x, kvs = run_decoder_stack(params, x, cfg, constrain=constrain,
                                   emit_kv=True)
        cache = {"k": kvs[0], "v": kvs[1]}
    x = L.apply_norm(params["final_norm"], x, cfg)
    last_index = batch.get("last_index")
    if last_index is None:
        x_last = x[:, -1]
    else:
        x_last = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
    logits_last = L.unembed(params["embed"], x_last, cfg)
    return logits_last, cache


def prefill_chunk(params, cache, batch, cfg: ModelConfig, *,
                  n_kv: Optional[int] = None,
                  global_pages: bool = False):
    """One chunk of an incremental (chunked) prefill for paged layouts.

    Processes ``C = tokens.shape[1]`` prompt positions starting at absolute
    position ``start`` for one slot: every layer scatters the chunk's K/V
    into the slot's pool pages (``pages``, chunk-offset blocks; spare
    entries point at the scratch page 0) and attends the chunk's queries
    causally over the slot's paged prior KV + the chunk itself
    (:func:`repro.models.layers.attention_chunk`).  Numerics are
    bit-identical to a whole-prompt :func:`forward_prefill` of the same
    tokens at every valid position — chunking changes the schedule, never
    the math.

    ``batch``: {"tokens": (1, C) int32, "start": scalar int32,
                "slot": scalar int32, "row": (mb,) int32 block-table row,
                "pages": (C // BLOCK_SIZE,) int32,
                "last_index": scalar int32 — position of the final prompt
                token WITHIN the chunk (only read on the last chunk)}
    ``n_kv`` (static) bounds the prior-KV page sweep, exactly as in
    :func:`decode_step`.  Returns (logits (1, V) at ``last_index``,
    new_cache).
    """
    assert cache_layout(cfg) == "paged", "chunked prefill is paged-only"
    tokens = batch["tokens"]
    C = tokens.shape[1]
    slot, row, pages = batch["slot"], batch["row"], batch["pages"]
    if n_kv is None:
        n_kv = row.shape[0]
    positions = batch["start"] + jnp.arange(C, dtype=jnp.int32)
    x = L.embed_tokens(params["embed"], tokens, cfg)

    def body(h, xs):
        lp, cl = xs
        a_in = L.apply_norm(lp["norm1"], h, cfg)
        a, new_c = L.attention_chunk(
            lp["attn"], a_in, cfg, cl, slot=slot, row=row, pages=pages,
            positions=positions, n_kv=n_kv, global_pages=global_pages)
        h = h + a
        m_in = L.apply_norm(lp["norm2"], h, cfg)
        if cfg.family == "moe":
            m = L.apply_moe(lp["moe"], m_in, cfg)
        else:
            m = L.apply_mlp(lp["mlp"], m_in, cfg)
        return h + m, new_c

    x, new_layers = jax.lax.scan(body, x,
                                 (params["layers"], cache["layers"]))
    x = L.apply_norm(params["final_norm"], x, cfg)
    last = jnp.reshape(batch["last_index"], (1, 1, 1)).astype(jnp.int32)
    x_last = jnp.take_along_axis(x, last, axis=1)[:, 0]
    logits = L.unembed(params["embed"], x_last, cfg)
    return logits, dict(cache, layers=new_layers)


# ---------------------------------------------------------------------------
# Decode steps
# ---------------------------------------------------------------------------
def decode_step(params, cache, batch, cfg: ModelConfig, *,
                n_kv: Optional[int] = None,
                global_pages: bool = False):
    """One token for every sequence in the batch against the cache.

    ``batch``: {"tokens": (B,1) int32, "lengths": (B,) int32,
                "block_table": (B, MB) int32 (paged layouts only)}
    ``n_kv`` (static) bounds the paged KV sweep (see kernels/ops.py).
    ``global_pages``: block-table entries are slot-flattened global page
    ids (copy-on-write forks; see layers.attention_decode).
    Returns (logits (B, V), new_cache).
    """
    lengths = batch["lengths"]
    block_table = batch.get("block_table")
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg)

    layout = cache_layout(cfg)
    if cfg.is_encdec:
        def body(h, xs):
            lp, cl = xs
            a_in = L.apply_norm(lp["norm1"], h, cfg)
            a, new_self = L.attention_decode(
                lp["self_attn"], a_in, cfg,
                {"k_pool": cl["sk"], "v_pool": cl["sv"]}, lengths,
                block_table=block_table, n_kv=n_kv,
                global_pages=global_pages)
            h = h + a
            x_in = L.apply_norm(lp["norm_x"], h, cfg)
            xa, _ = L.attention_decode(
                lp["cross_attn"], x_in, cfg,
                {"k": cl["ck"], "v": cl["cv"], "len": cache["enc_len"]},
                lengths, cross=True)
            h = h + xa
            m_in = L.apply_norm(lp["norm2"], h, cfg)
            h = h + L.apply_mlp(lp["mlp"], m_in, cfg)
            return h, {"sk": new_self["k_pool"], "sv": new_self["v_pool"]}

        xs = (params["dec_layers"], {
            "sk": cache["self"]["k_pool"], "sv": cache["self"]["v_pool"],
            "ck": cache["cross_k"], "cv": cache["cross_v"]})
        x, new = jax.lax.scan(body, x, xs)
        new_cache = dict(cache)
        new_cache["self"] = {"k_pool": new["sk"], "v_pool": new["sv"]}
    elif layout == "ssm":
        def body(h, xs):
            lp, cl = xs
            m_in = L.apply_norm(lp["norm"], h, cfg)
            m, new_c = S.mamba_decode(lp["mamba"], m_in, cfg, cl)
            return h + m, new_c

        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache = dict(cache, layers=new_layers)
    elif layout == "hybrid":
        period = cfg.attn_period
        n_attn = n_shared_attn(cfg)
        n_grouped = n_attn * period
        shared = params["shared_attn"]

        def mamba_body(h, xs):
            lp, cl = xs
            m_in = L.apply_norm(lp["norm"], h, cfg)
            m, new_c = S.mamba_decode(lp["mamba"], m_in, cfg, cl)
            return h + m, new_c

        def group_body(h, xs):
            lp_group, cl_group, acl = xs
            h, new_mc = jax.lax.scan(mamba_body, h, (lp_group, cl_group))
            a_in = L.apply_norm(shared["norm1"], h, cfg)
            a, new_ac = L.attention_decode(
                shared["attn"], a_in, cfg, acl, lengths,
                block_table=block_table, n_kv=n_kv,
                global_pages=global_pages)
            h = h + a
            m_in = L.apply_norm(shared["norm2"], h, cfg)
            h = h + L.apply_mlp(shared["mlp"], m_in, cfg)
            return h, (new_mc, new_ac)

        lp_g = jax.tree.map(
            lambda a: a[:n_grouped].reshape((n_attn, period) + a.shape[1:]),
            params["layers"])
        cl_g = jax.tree.map(
            lambda a: a[:n_grouped].reshape((n_attn, period) + a.shape[1:]),
            cache["layers"])
        x, (new_mc_g, new_ac) = jax.lax.scan(
            group_body, x, (lp_g, cl_g, cache["attn"]))
        n_trail = cfg.num_layers - n_grouped
        new_mc_g = jax.tree.map(
            lambda a: a.reshape((n_grouped,) + a.shape[2:]), new_mc_g)
        if n_trail:
            lp_t = jax.tree.map(lambda a: a[n_grouped:], params["layers"])
            cl_t = jax.tree.map(lambda a: a[n_grouped:], cache["layers"])
            x, new_mc_t = jax.lax.scan(mamba_body, x, (lp_t, cl_t))
            new_mc = jax.tree.map(
                lambda g, t: jnp.concatenate([g, t], 0), new_mc_g, new_mc_t)
        else:
            new_mc = new_mc_g
        new_cache = dict(cache, layers=new_mc, attn=new_ac)
    else:
        def body(h, xs):
            lp, cl = xs
            a_in = L.apply_norm(lp["norm1"], h, cfg)
            a, new_c = L.attention_decode(lp["attn"], a_in, cfg, cl,
                                          lengths, block_table=block_table,
                                          n_kv=n_kv,
                                          global_pages=global_pages)
            h = h + a
            m_in = L.apply_norm(lp["norm2"], h, cfg)
            if cfg.family == "moe":
                m = L.apply_moe(lp["moe"], m_in, cfg)
            else:
                m = L.apply_mlp(lp["mlp"], m_in, cfg)
            return h + m, new_c

        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        new_cache = dict(cache, layers=new_layers)

    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x[:, 0], cfg)
    return logits, new_cache
