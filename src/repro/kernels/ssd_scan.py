"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD "dual form": the quadratic intra-chunk part is a
pair of MXU matmuls per (chunk x head-block) tile; the inter-chunk state
recurrence (H, P, N) lives in VMEM scratch carried across the sequential
chunk dimension of the grid, so chunk states never round-trip HBM.

Grid: (B, H / block_h, n_chunks) — chunks innermost (sequential).
Assumes ssm_groups == 1 (true for all assigned configs).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
            y_ref, state_out_ref, state_ref, *,
            chunk: int, n_chunks: int, block_h: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)      # (chunk, bh, P)
    dt = dt_ref[0].astype(jnp.float32)    # (chunk, bh)
    a = a_ref[...].astype(jnp.float32)    # (bh,)
    bmat = b_ref[0, :, 0, :].astype(jnp.float32)  # (chunk, N)
    cmat = c_ref[0, :, 0, :].astype(jnp.float32)  # (chunk, N)
    d = d_ref[...].astype(jnp.float32)    # (bh,)

    da = dt * a[None, :]                  # (chunk, bh)
    cum = jnp.cumsum(da, axis=0)
    total = cum[-1]                       # (bh,)

    # ---- intra-chunk: per head-in-block matmul pair on the MXU ----
    # scores[i,j] = C_i . B_j   (shared across heads of the group)
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (chunk, chunk)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = ii >= jj

    bh = x.shape[1]
    y_acc = jnp.zeros_like(x)  # (chunk, bh, P)
    # decay(i,j,h) = exp(cum_i - cum_j); weight x_j by dt_j
    diff = cum[:, None, :] - cum[None, :, :]            # (chunk, chunk, bh)
    decay = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    w = scores[:, :, None] * decay * dt[None, :, :]     # (chunk, chunk, bh)
    y_intra = jnp.einsum("ijh,jhp->ihp", w, x)

    # ---- inter-chunk: contribution of the carried state ----
    state = state_ref[...]                               # (bh, P, N)
    from_start = jnp.exp(cum)                            # (chunk, bh)
    y_inter = jnp.einsum("in,hpn,ih->ihp", cmat, state, from_start)

    y = y_intra + y_inter + d[None, :, None] * x
    y_ref[0] = y.astype(y_ref.dtype)

    # ---- state update ----
    to_end = jnp.exp(total[None, :] - cum) * dt          # (chunk, bh)
    new_contrib = jnp.einsum("in,ih,ihp->hpn", bmat, to_end, x)
    state_ref[...] = (
        state * jnp.exp(total)[:, None, None] + new_contrib
    )

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...]


def ssd_chunk_scan_pallas(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)
    a: jax.Array,    # (H,)
    b: jax.Array,    # (B, S, G=1, N)
    c: jax.Array,    # (B, S, 1, N)
    *,
    chunk: int = 128,
    d_skip: Optional[jax.Array] = None,
    init_state: Optional[jax.Array] = None,
    block_h: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert G == 1, "kernel assumes a single B/C group (all assigned configs)"
    assert init_state is None, "prefill-from-state uses the jnp path"
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk
    block_h = block_h or min(H, 8)
    assert H % block_h == 0
    n_h = H // block_h
    d = d_skip if d_skip is not None else jnp.zeros((H,), jnp.float32)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                               block_h=block_h)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, n_h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_h, P),
                         lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, chunk, block_h),
                         lambda bb, hh, cc: (bb, cc, hh)),
            pl.BlockSpec((block_h,), lambda bb, hh, cc: (hh,)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bb, hh, cc: (bb, cc, 0, 0)),
            pl.BlockSpec((1, chunk, 1, N),
                         lambda bb, hh, cc: (bb, cc, 0, 0)),
            pl.BlockSpec((block_h,), lambda bb, hh, cc: (hh,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_h, P),
                         lambda bb, hh, cc: (bb, cc, hh, 0)),
            pl.BlockSpec((1, block_h, P, N),
                         lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, a, b, c, d)
    return y, state
