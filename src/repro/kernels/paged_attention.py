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
Where D is not a multiple of 128, XLA's default TPU layout keeps that
tile as ``(D, block)`` in HBM (the 128-token axis minor, so nothing is
padded); the paged kernel then reads the tile in that order, a bitcast
of the pool, rather than have XLA copy the pool to its logical order.

Grid: (B, Hkv, n_kv_blocks), innermost sequential; the online-softmax
state (acc / m / l) for the GQA query group persists in VMEM scratch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_gather import block_gather_pallas

NEG_INF = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, n_kv: int,
                   tiles_t: bool = False):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]  # (G, D) — storage dtype into the MXU
    k = k_ref[...]  # (bk, D), or (D, bk) with tiles_t
    v = v_ref[...]
    kv_d = 0 if tiles_t else 1  # the head axis of a k/v tile

    s = jax.lax.dot_general(
        q, k, (((1,), (kv_d,)), ((), ())),
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
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (1 - kv_d,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finish():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _paged_kernel(table_ref, lengths_ref, layer_ref, *refs, **kw):
    # the block table and the layer index are consumed by the index maps
    _decode_kernel(lengths_ref, *refs, **kw)


def _attend(kernel, scalars, q, k_op, v_op, kv_spec, *, Hkv, n_kv, block,
            interpret, name, tiles_t=False):
    """Shared pallas_call: grid (B, Hkv, n_kv) over GQA query groups.
    ``name`` is the kernel's stable name: the compiled custom call and
    its events in a profile carry it."""
    B, H, D = q.shape
    G = H // Hkv
    q_spec = pl.BlockSpec((None, None, G, D), lambda b, h, ik, *_: (b, h, 0, 0))
    out = pl.pallas_call(
        functools.partial(kernel, scale=float(1.0 / (D ** 0.5)),
                          block_k=block, n_kv=n_kv, tiles_t=tiles_t),
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
        name=name,
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
        name="decode_attention",
    )


# ---------------------------------------------------------------------------
# Paged variant: the block table drives the k/v index maps (scalar prefetch)
# ---------------------------------------------------------------------------
def _pool_view(k_pool, v_pool, layer, global_pages):
    """The kernels' view of stacked pools ``(L, B, N_pool, Hkv, block,
    D)``: every page of every layer on one axis, ``(L * B * N_pool, Hkv,
    *tile)``, a bitcast of the pools.  ``tile`` is ``(D, block)`` where
    HBM holds the head tiles in that order (``tiles_t``, see
    :func:`tiles_transposed`), else ``(block, D)``.  ``layer`` (``None``:
    the pools are one layer's, a stack of one) becomes the (1,) int32
    scalar-prefetch operand, and ``page(b, p, layer)`` is the view's index
    of slot ``b``'s page ``p`` (a global id with ``global_pages``, whose
    pools may come slot-flattened) in layer ``layer[0]``.  Returns
    ``(k_op, v_op, layer, page, tiles_t)``."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    Hkv, block, D = k_pool.shape[-3:]
    per_layer = math.prod(k_pool.shape[1:-3])
    n_pool = k_pool.shape[-4]

    def page(b, p, layer):
        return layer[0] * per_layer + (p if global_pages else b * n_pool + p)

    tiles_t = tiles_transposed(block, D)

    def view(pool):
        pool = pool.reshape(-1, Hkv, block, D)
        return jnp.swapaxes(pool, -1, -2) if tiles_t else pool

    return view(k_pool), view(v_pool), layer, page, tiles_t


def _unview(op, shape, tiles_t):
    return (jnp.swapaxes(op, -1, -2) if tiles_t else op).reshape(shape)


def tiles_transposed(block: int, D: int) -> bool:
    """True where a page's ``(block, D)`` head tile lies in HBM as
    ``(D, block)``: XLA's default TPU layout makes a dimension that is a
    multiple of 128 the minor one, so a 128-token page with a head size
    off the 128 lanes keeps its token axis minor."""
    return block % 128 == 0 and D % 128 != 0


def paged_attention_pallas(
    q: jax.Array,            # (B, H, D)
    k_pool: jax.Array,       # (L, B, N_pool, Hkv, block, D) stacked pools
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, max_blocks) int32 (local page ids)
    lengths: jax.Array,      # (B,) int32
    *,
    layer=None,              # int32 scalar: the layer of the stack to read
    n_kv: int | None = None,
    global_pages: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``k_pool``/``v_pool`` hold every layer's pool; ``layer`` (the
    third scalar-prefetch operand) selects the one this call reads, in
    the k/v index maps, so the layer's pages stream from the whole stack
    without a slice of it in HBM.  ``layer=None``: the pools are one
    layer's, without the leading axis.

    ``n_kv`` (static) bounds the KV sweep: the grid iterates only the
    first ``n_kv`` table columns instead of all ``max_blocks``.  Callers
    pass a bucketed bound >= ceil(max(lengths)/block); positions past a
    sequence's length are masked to NEG_INF either way, so any valid bound
    is bit-identical to the full sweep — it just skips pages no active
    sequence can reach.

    ``global_pages``: table entries are GLOBAL ids ``slot * N_pool + page``
    into the slot-flattened pool, so a row may stream pages owned by
    another slot (copy-on-write prefix forks).  The pools may then come
    slot-flattened already, ``(L, B * N_pool, Hkv, block, D)``.  Same
    grid, same scratch; only the k/v index maps change (page id selects
    the flattened page axis directly instead of (slot, page))."""
    if n_kv is not None and n_kv < block_table.shape[1]:
        block_table = block_table[:, :n_kv]
    Hkv, block = k_pool.shape[-3:-1]
    k_op, v_op, layer, page, tiles_t = _pool_view(k_pool, v_pool, layer,
                                                  global_pages)
    kv_spec = pl.BlockSpec(
        (None, None) + k_op.shape[-2:],
        lambda b, h, ik, table, lens, layer: (
            page(b, table[b, ik], layer), h, 0, 0),
    )
    return _attend(
        _paged_kernel, (block_table, lengths, layer), q, k_op, v_op,
        kv_spec, Hkv=Hkv, n_kv=block_table.shape[1], block=block,
        interpret=interpret, name="paged_attention",
        tiles_t=tiles_t,
    )


# ---------------------------------------------------------------------------
# One token row per slot into stacked pools, in place (aliased operands)
# ---------------------------------------------------------------------------
def _row_write_kernel(pages_ref, offs_ref, layer_ref, k_new_ref, v_new_ref,
                      k_in_ref, v_in_ref, k_out_ref, v_out_ref, *,
                      token_axis: int):
    # a page's head tiles (Hkv, block, D), or (Hkv, D, block): the slot's
    # new row replaces the token at its offset
    off = offs_ref[pl.program_id(0)]
    for new, src, dst in ((k_new_ref, k_in_ref, k_out_ref),
                          (v_new_ref, v_in_ref, v_out_ref)):
        tiles = src[...]
        at = jax.lax.broadcasted_iota(jnp.int32, tiles.shape,
                                      token_axis) == off
        dst[...] = jnp.where(at, jnp.broadcast_to(new[...], tiles.shape),
                             tiles)


def write_rows_pallas(
    k_pool: jax.Array,   # (L, B, N_pool, Hkv, block, D) stacked pools
    v_pool: jax.Array,
    k_new: jax.Array,    # (B, Hkv, D) one token row per slot
    v_new: jax.Array,
    pages: jax.Array,    # (B,) int32 page of each slot's row
    offs: jax.Array,     # (B,) int32 token offset in that page
    *,
    layer=None,
    global_pages: bool = False,
    interpret: bool = False,
):
    """Write slot b's row into ``pages[b]`` of layer ``layer`` at token
    ``offs[b]``, in place: the pools are aliased to the outputs and only
    the B pages' head tiles move (grid (B,), one page's tiles a step).
    The same view as :func:`paged_attention_pallas` (``global_pages``
    ids, the stored tile order), so XLA has no layout of its own to give
    the pools.  Returns the updated ``(k_pool, v_pool)``, shaped as
    given."""
    Hkv, D = k_new.shape[1:]
    B = k_new.shape[0]
    k_op, v_op, layer, page, tiles_t = _pool_view(k_pool, v_pool, layer,
                                                  global_pages)
    new_shape = (B, Hkv, D, 1) if tiles_t else (B, Hkv, 1, D)
    new_spec = pl.BlockSpec((None,) + new_shape[1:],
                            lambda b, pages, offs, layer: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(
        (None,) + k_op.shape[1:],
        lambda b, pages, offs, layer: (page(b, pages[b], layer), 0, 0, 0))
    k_out, v_out = pl.pallas_call(
        functools.partial(_row_write_kernel,
                          token_axis=2 if tiles_t else 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_op.shape, k_op.dtype),
                   jax.ShapeDtypeStruct(v_op.shape, v_op.dtype)],
        # operands count the three scalar-prefetch ones first
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_kv_write",
    )(pages, offs, layer, k_new.astype(k_op.dtype).reshape(new_shape),
      v_new.astype(v_op.dtype).reshape(new_shape), k_op, v_op)
    return (_unview(k_out, k_pool.shape, tiles_t),
            _unview(v_out, v_pool.shape, tiles_t))


# ---------------------------------------------------------------------------
# Whole pages of one slot into / out of stacked pools (the prefill chunk)
# ---------------------------------------------------------------------------
def _page_write_kernel(ids_ref, slot_ref, layer_ref, k_src, v_src,
                       k_in_ref, v_in_ref, k_dst, v_dst):
    # the pool inputs are aliased to the outputs and not read
    k_dst[...] = k_src[...]
    v_dst[...] = v_src[...]


def write_pages_pallas(
    k_pool: jax.Array,   # (L, B, N_pool, Hkv, block, D) stacked pools
    v_pool: jax.Array,
    k_pages: jax.Array,  # (n, Hkv, block, D) whole pages
    v_pages: jax.Array,
    pages: jax.Array,    # (n,) int32 where they go
    *,
    slot,                # int32 scalar: the slot whose pages these are
    layer=None,
    global_pages: bool = False,
    interpret: bool = False,
):
    """Write ``n`` whole pages of slot ``slot`` (with ``global_pages``:
    global ids) into layer ``layer`` of the pools, in place, as
    :func:`write_rows_pallas` writes rows.  Returns the updated
    ``(k_pool, v_pool)``, shaped as given."""
    k_op, v_op, layer, page, tiles_t = _pool_view(k_pool, v_pool, layer,
                                                  global_pages)
    if tiles_t:  # the chunk's pages in the stored tile order
        k_pages = jnp.swapaxes(k_pages, -1, -2)
        v_pages = jnp.swapaxes(v_pages, -1, -2)
    new_spec = pl.BlockSpec((None,) + k_op.shape[1:],
                            lambda i, ids, slot, layer: (i, 0, 0, 0))
    pool_spec = pl.BlockSpec(
        (None,) + k_op.shape[1:],
        lambda i, ids, slot, layer: (page(slot[0], ids[i], layer), 0, 0, 0))
    k_out, v_out = pl.pallas_call(
        _page_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pages.shape[0],),
            in_specs=[new_spec, new_spec, pool_spec, pool_spec],
            out_specs=[pool_spec, pool_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_op.shape, k_op.dtype),
                   jax.ShapeDtypeStruct(v_op.shape, v_op.dtype)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_kv_write",
    )(pages, jnp.reshape(slot, (1,)).astype(jnp.int32), layer,
      k_pages.astype(k_op.dtype), v_pages.astype(v_op.dtype), k_op, v_op)
    return (_unview(k_out, k_pool.shape, tiles_t),
            _unview(v_out, v_pool.shape, tiles_t))


def read_pages_pallas(
    k_pool: jax.Array,   # (L, B, N_pool, Hkv, block, D) stacked pools
    v_pool: jax.Array,
    pages: jax.Array,    # (n,) int32 the pages to read, in order
    *,
    slot,                # int32 scalar: the slot whose pages these are
    layer=None,
    global_pages: bool = False,
    interpret: bool = False,
):
    """Gather ``n`` whole pages of slot ``slot`` out of layer ``layer``
    of the pools through :func:`block_gather_pallas` on the pool view:
    ``(k_pages, v_pages)``, each ``(n, Hkv, block, D)``."""
    k_op, v_op, layer, page, tiles_t = _pool_view(k_pool, v_pool, layer,
                                                  global_pages)
    ids = page(slot, pages, layer)
    shape = pages.shape + k_pool.shape[-3:]
    return tuple(_unview(block_gather_pallas(op, ids, interpret=interpret),
                         shape, tiles_t) for op in (k_op, v_op))
