"""Dispatching wrappers for the compute hot-spots.

Each op has three execution paths:
  * ``ref``        — pure-jnp oracle (:mod:`repro.kernels.ref`); the XLA
                     path on every backend but the TPU.
  * ``pallas``     — the TPU kernel (``interpret=False``, target hardware).
  * ``interpret``  — the same Pallas kernel body executed in Python on CPU
                     (correctness validation; see tests/test_kernels.py).

Selection: explicit ``impl=`` argument > ``REPRO_KERNEL_IMPL`` env var >
the backend: ``pallas`` on a TPU, ``ref`` elsewhere (Mosaic kernels lower
for the TPU only).  ``interpret`` runs only when asked for by name, and
an unknown name raises.  The SSD scan kernel does not lower for the TPU
yet, so that op keeps ``ref`` unless asked by name.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref

IMPLS = ("ref", "pallas", "interpret")


def _impl(impl: Optional[str], *, tpu_kernel: bool = True) -> str:
    mode = impl or os.environ.get("REPRO_KERNEL_IMPL")
    if not mode:
        on_tpu = tpu_kernel and jax.default_backend() == "tpu"
        mode = "pallas" if on_tpu else "ref"
    if mode not in IMPLS:
        raise ValueError(f"unknown kernel impl {mode!r}; expected one of "
                         f"{IMPLS}")
    return mode


# ---------------------------------------------------------------------------
# Distributed decode configuration (set by the launcher; §Perf iteration 2)
# ---------------------------------------------------------------------------
_DIST = {"mesh": None, "batch_part": None, "axis": "model"}


def configure_dist_decode(mesh, batch_part, axis: str = "model") -> None:
    _DIST.update(mesh=mesh, batch_part=batch_part, axis=axis)


def clear_dist_decode() -> None:
    _DIST.update(mesh=None, batch_part=None)


def dist_decode_config():
    if _DIST["mesh"] is None or os.environ.get("REPRO_DIST_DECODE") == "0":
        return None
    return dict(_DIST)


_DIST_MOE = {"mesh": None, "batch_part": None, "axis": "model"}


def configure_dist_moe(mesh, batch_part, axis: str = "model") -> None:
    _DIST_MOE.update(mesh=mesh, batch_part=batch_part, axis=axis)


def clear_dist_moe() -> None:
    _DIST_MOE.update(mesh=None, batch_part=None)


def dist_moe_config():
    if _DIST_MOE["mesh"] is None or os.environ.get("REPRO_DIST_MOE") == "0":
        return None
    return dict(_DIST_MOE)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    chunk=0, impl: Optional[str] = None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            chunk=chunk,
        )
    return _flash_pallas(q, k, v, jnp.asarray(q_offset, jnp.int32), causal,
                         window, mode == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_pallas(q, k, v, q_offset, causal, window, interpret):
    """Pallas forward; the backward pass differentiates the jnp oracle
    (pallas_call has no transpose rule), so training graphs stay
    differentiable when the kernel is selected."""
    from .flash_attention import flash_attention_pallas

    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset, interpret=interpret)


def _flash_fwd(q, k, v, q_offset, causal, window, interpret):
    out = _flash_pallas(q, k, v, q_offset, causal, window, interpret)
    return out, (q, k, v, q_offset)


def _flash_bwd(causal, window, interpret, res, g):
    q, k, v, q_offset = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention(
            q_, k_, v_, causal=causal, window=window, q_offset=q_offset),
        q, k, v,
    )
    return (*vjp(g), None)


_flash_pallas.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     impl: Optional[str] = None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.decode_attention(q, k_cache, v_cache, lengths)
    from .paged_attention import decode_attention_pallas

    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, interpret=(mode == "interpret")
    )


def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    n_kv: Optional[int] = None,
                    global_pages: bool = False,
                    impl: Optional[str] = None):
    """``n_kv`` statically bounds the KV-page sweep (see the Pallas
    kernel's docstring); ``None`` sweeps the full table width.
    ``global_pages`` switches table entries to slot-flattened GLOBAL page
    ids (``slot * N_pool + page``) so rows may reference pages owned by
    other slots — the copy-on-write fork substrate."""
    mode = _impl(impl)
    if mode == "ref":
        return ref.paged_attention(q, k_pool, v_pool, block_table, lengths,
                                   n_kv=n_kv, global_pages=global_pages)
    from .paged_attention import paged_attention_pallas

    return paged_attention_pallas(
        q, k_pool, v_pool, block_table, lengths, n_kv=n_kv,
        global_pages=global_pages, interpret=(mode == "interpret"),
    )


def ssd_chunk_scan(x, dt, a, b, c, *, chunk=128, d_skip=None,
                   init_state=None, impl: Optional[str] = None):
    mode = _impl(impl, tpu_kernel=False)
    if mode == "ref":
        return ref.ssd_chunk_scan(
            x, dt, a, b, c, chunk=chunk, d_skip=d_skip,
            init_state=init_state,
        )
    from .ssd_scan import ssd_chunk_scan_pallas

    return ssd_chunk_scan_pallas(
        x, dt, a, b, c, chunk=chunk, d_skip=d_skip, init_state=init_state,
        interpret=(mode == "interpret"),
    )


def ssd_decode_step(x, dt, a, b, c, state, *, d_skip=None,
                    impl: Optional[str] = None):
    # single-token recurrence is bandwidth-trivial; always the jnp path
    return ref.ssd_decode_step(x, dt, a, b, c, state, d_skip=d_skip)


def block_gather(pool, indices, *, impl: Optional[str] = None):
    mode = _impl(impl)
    if mode == "ref":
        return ref.block_gather(pool, indices)
    from .block_gather import block_gather_pallas

    return block_gather_pallas(pool, indices, interpret=(mode == "interpret"))
