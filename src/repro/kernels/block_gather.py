"""Pallas TPU kernel: KV-page gather / compaction.

The BlockPool reclaimer's defrag/compaction hot path: copy M pages (page =
(Hkv, block, D)) selected by an index vector out of a pool.  The page ids
drive the input index_map via scalar prefetch — a pure HBM->HBM streaming
copy through VMEM with zero wasted traffic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, pool_ref, out_ref):
    out_ref[0] = pool_ref[0]


def block_gather_pallas(
    pool: jax.Array,     # (N_pool, Hkv, block, D)
    indices: jax.Array,  # (M,) int32
    *,
    interpret: bool = False,
) -> jax.Array:
    n_pool, hkv, block, d = pool.shape
    m = indices.shape[0]
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[
                pl.BlockSpec((1, hkv, block, d),
                             lambda i, idx: (idx[i], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hkv, block, d),
                                   lambda i, idx: (i, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, hkv, block, d), pool.dtype),
        interpret=interpret,
        name="block_gather",
    )(indices, pool)
