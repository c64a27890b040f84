"""Pallas kernels vs pure-jnp oracles: shape & dtype sweeps, interpret mode
(the kernel body executes in Python on CPU; TPU is the lowering target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.block_gather import block_gather_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    decode_attention_pallas,
    paged_attention_pallas,
    read_pages_pallas,
    write_pages_pallas,
    write_rows_pallas,
)
from repro.kernels.ssd_scan import ssd_chunk_scan_pallas


def rand(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.3).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,D,causal,window",
    [
        (1, 128, 128, 4, 4, 64, True, 0),
        (2, 256, 256, 4, 2, 64, True, 0),     # GQA
        (1, 128, 256, 2, 2, 32, False, 0),    # cross / bidirectional
        (2, 256, 256, 8, 2, 128, True, 128),  # sliding window
        (1, 384, 384, 2, 1, 64, True, 0),     # odd block count
    ],
)
def test_flash_attention(B, Sq, Skv, H, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Sq, H, D), dtype)
    k = rand(ks[1], (B, Skv, Hkv, D), dtype)
    v = rand(ks[2], (B, Skv, Hkv, D), dtype)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype],
    )


def test_flash_attention_q_offset():
    """Decode-style offset: queries start mid-sequence."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 128, 2, 64), jnp.float32)
    k = rand(ks[1], (1, 256, 2, 64), jnp.float32)
    v = rand(ks[2], (1, 256, 2, 64), jnp.float32)
    want = ref.flash_attention(q, k, v, causal=True, q_offset=128)
    got = flash_attention_pallas(q, k, v, causal=True, q_offset=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_traced_q_offset():
    """Chunked prefill passes a TRACED offset (positions[0]): the kernel
    takes it as a scalar-prefetch operand, so one compiled program
    serves every chunk position."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = rand(ks[0], (1, 128, 4, 64), jnp.float32)
    k = rand(ks[1], (1, 384, 2, 64), jnp.float32)
    v = rand(ks[2], (1, 384, 2, 64), jnp.float32)
    fn = jax.jit(lambda q, k, v, off: flash_attention_pallas(
        q, k, v, causal=True, q_offset=off, interpret=True))
    for off in (0, 128, 256):
        want = ref.flash_attention(q, k, v, causal=True, q_offset=off)
        got = fn(q, k, v, jnp.int32(off))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    assert fn._cache_size() == 1


def test_flash_attention_kernel_path_is_differentiable():
    """ops routes the Pallas forward through a custom VJP whose backward
    differentiates the oracle: gradients equal the ref path's."""
    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(ks[0], (1, 128, 4, 32), jnp.float32)
    k = rand(ks[1], (1, 128, 2, 32), jnp.float32)
    v = rand(ks[2], (1, 128, 2, 32), jnp.float32)

    def loss(impl):
        return lambda q, k, v: (ops.flash_attention(
            q, k, v, causal=True, impl=impl) ** 2).sum()

    want = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss("interpret"), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel selection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,tpu_kernel,want", [
    ("tpu", True, "pallas"),
    ("cpu", True, "ref"),
    ("gpu", True, "ref"),
    ("tpu", False, "ref"),  # ops whose kernel does not lower for the TPU
])
def test_impl_follows_backend(monkeypatch, backend, tpu_kernel, want):
    from repro.kernels import ops

    monkeypatch.delenv("REPRO_KERNEL_IMPL", raising=False)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops._impl(None, tpu_kernel=tpu_kernel) == want
    # a name always wins; interpret runs only when named
    assert ops._impl("interpret", tpu_kernel=tpu_kernel) == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    assert ops._impl(None, tpu_kernel=tpu_kernel) == "ref"


@pytest.mark.parametrize("name", ["cuda", "Pallas", "mosaic"])
def test_impl_rejects_unknown_names(monkeypatch, name):
    from repro.kernels import ops

    with pytest.raises(ValueError):
        ops._impl(name)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", name)
    with pytest.raises(ValueError):
        ops._impl(None)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,D",
    [(2, 256, 4, 4, 64), (4, 512, 8, 2, 64), (1, 128, 2, 1, 128)],
)
def test_decode_attention(B, S, H, Hkv, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (B, H, D), dtype)
    kc = rand(ks[1], (B, S, Hkv, D), dtype)
    vc = rand(ks[2], (B, S, Hkv, D), dtype)
    lengths = jnp.asarray(
        np.random.RandomState(0).randint(1, S, (B,)), jnp.int32
    )
    want = ref.decode_attention(q, kc, vc, lengths)
    got = decode_attention_pallas(q, kc, vc, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype],
    )


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,n_pool,block,mb,H,Hkv,D",
    [(2, 8, 64, 4, 4, 2, 64), (3, 16, 128, 8, 8, 8, 64)],
)
def test_paged_attention(B, n_pool, block, mb, H, Hkv, D, dtype):
    rs = np.random.RandomState(1)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, H, D), dtype)
    k_pool = rand(ks[1], (B, n_pool, Hkv, block, D), dtype)
    v_pool = rand(ks[2], (B, n_pool, Hkv, block, D), dtype)
    # scattered (reclaimed & reused) pages: random permutation per sequence
    table = np.stack([rs.permutation(n_pool)[:mb] for _ in range(B)])
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(rs.randint(1, mb * block, (B,)), jnp.int32)
    want = ref.paged_attention(q, k_pool, v_pool, table, lengths)
    got = paged_attention_pallas(q, k_pool, v_pool, table, lengths,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **TOL[dtype],
    )


def test_page_layout_is_head_major():
    """Pool pages are (Hkv, block, D): head h of page p holds tokens
    p*block .. p*block+block-1 contiguously (the kernel's KV tile)."""
    kv = jnp.arange(2 * 4 * 3 * 5, dtype=jnp.float32).reshape(1, 8, 3, 5)
    pages = ref.to_pages(kv, 4)
    assert pages.shape == (1, 2, 3, 4, 5)
    np.testing.assert_array_equal(np.asarray(pages[0, 1, 2]),
                                  np.asarray(kv[0, 4:8, 2]))
    np.testing.assert_array_equal(np.asarray(ref.from_pages(pages)),
                                  np.asarray(kv))


def test_paged_attention_global_pages_cross_slot():
    """Global page ids (CoW forks): a row streams pages another slot
    owns; kernel == oracle in the head-major pool."""
    rs = np.random.RandomState(4)
    B, n_pool, block, mb, H, Hkv, D = 3, 6, 64, 4, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = rand(ks[0], (B, H, D), jnp.float32)
    k_pool = rand(ks[1], (B, n_pool, Hkv, block, D), jnp.float32)
    v_pool = rand(ks[2], (B, n_pool, Hkv, block, D), jnp.float32)
    table = rs.permutation(B * n_pool)[: B * mb].reshape(B, mb)
    table[1, :2] = table[0, :2]  # slot 1 shares slot 0's prefix pages
    table = jnp.asarray(table, jnp.int32)
    lengths = jnp.asarray(rs.randint(1, mb * block, (B,)), jnp.int32)
    want = ref.paged_attention(q, k_pool, v_pool, table, lengths,
                               global_pages=True)
    got = paged_attention_pallas(q, k_pool, v_pool, table, lengths,
                                 n_kv=mb, global_pages=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def stacked_pools(L, B, n_pool, Hkv, block, D, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (rand(ks[0], (L, B, n_pool, Hkv, block, D), dtype),
            rand(ks[1], (L, B, n_pool, Hkv, block, D), dtype))


def page_table(rs, B, n_pool, mb, global_pages):
    """Scattered pages: global ids (any slot's page) or each slot's own."""
    if global_pages:
        return jnp.asarray(
            rs.permutation(B * n_pool)[:B * mb].reshape(B, mb), jnp.int32)
    return jnp.asarray(
        np.stack([rs.permutation(n_pool)[:mb] for _ in range(B)]), jnp.int32)


# (block, D): 128-token pages with a head off the 128 lanes are read in
# the order HBM holds them, (D, block); 64-token pages as (block, D)
@pytest.mark.parametrize("block,D", [(128, 64), (64, 64)])
@pytest.mark.parametrize("global_pages", [True, False])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_stacked_layer(layer, global_pages, block, D):
    """The kernel reads layer ``layer`` of stacked pools by its index:
    bit for bit what it reads from ``pool[layer]`` alone, and the oracle
    with ``layer`` is bit for bit the oracle on ``pool[layer]``."""
    L, B, n_pool, mb, H, Hkv = 3, 2, 5, 3, 4, 2
    rs = np.random.RandomState(10 + layer)
    k_pool, v_pool = stacked_pools(L, B, n_pool, Hkv, block, D,
                                   jnp.bfloat16, layer)
    q = rand(jax.random.PRNGKey(20 + layer), (B, H, D), jnp.bfloat16)
    table = page_table(rs, B, n_pool, mb, global_pages)
    lengths = jnp.asarray(rs.randint(1, mb * block, (B,)), jnp.int32)
    kw = dict(global_pages=global_pages)
    got = paged_attention_pallas(q, k_pool, v_pool, table, lengths,
                                 layer=jnp.int32(layer), interpret=True, **kw)
    alone = paged_attention_pallas(q, k_pool[layer], v_pool[layer], table,
                                   lengths, interpret=True, **kw)
    want = ref.paged_attention(q, k_pool[layer], v_pool[layer], table,
                               lengths, **kw)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(alone, np.float32))
    np.testing.assert_array_equal(
        np.asarray(ref.paged_attention(q, k_pool, v_pool, table, lengths,
                                       layer=layer, **kw), np.float32),
        np.asarray(want, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[jnp.bfloat16])


@pytest.mark.parametrize("block,D", [(128, 64), (64, 64)])
@pytest.mark.parametrize("global_pages", [True, False])
def test_pool_writers_and_reader_match_oracle(global_pages, block, D):
    """The in-place row and page writers and the page reader move the
    same values to the same places as their oracles: bit for bit, in
    layer 1 of 3 and nowhere else."""
    L, B, n_pool, Hkv = 3, 3, 4, 2
    rs = np.random.RandomState(5)
    k_pool, v_pool = stacked_pools(L, B, n_pool, Hkv, block, D,
                                   jnp.bfloat16, 7)
    kw = dict(layer=jnp.int32(1), global_pages=global_pages)
    k1 = rand(jax.random.PRNGKey(8), (B, Hkv, D), jnp.bfloat16)
    v1 = rand(jax.random.PRNGKey(9), (B, Hkv, D), jnp.bfloat16)
    if global_pages:
        pages = jnp.asarray(rs.permutation(B * n_pool)[:B], jnp.int32)
    else:
        pages = jnp.asarray(rs.randint(0, n_pool, (B,)), jnp.int32)
    offs = jnp.asarray(rs.randint(0, block, (B,)), jnp.int32)
    got = write_rows_pallas(k_pool, v_pool, k1, v1, pages, offs,
                            interpret=True, **kw)
    want = ref.write_rows(k_pool, v_pool, k1, v1, pages, offs, **kw)
    for g, w, before in zip(got, want, (k_pool, v_pool)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(before[0]))

    slot = jnp.int32(2)
    ids = page_table(rs, 1, n_pool, 2, global_pages)[0]
    kp = rand(jax.random.PRNGKey(11), (2, Hkv, block, D), jnp.bfloat16)
    vp = rand(jax.random.PRNGKey(12), (2, Hkv, block, D), jnp.bfloat16)
    got = write_pages_pallas(k_pool, v_pool, kp, vp, ids, slot=slot,
                             interpret=True, **kw)
    want = ref.write_pages(k_pool, v_pool, kp, vp, ids, slot=slot, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    read = read_pages_pallas(*got, ids, slot=slot, interpret=True, **kw)
    for r, pages_in in zip(read, (kp, vp)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(pages_in))


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [(1, 128, 4, 32, 16, 64), (2, 256, 8, 64, 32, 128),
     (1, 256, 16, 32, 64, 64)],
)
def test_ssd_chunk_scan(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = rand(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(
        jax.random.normal(ks[1], (B, S, H), jnp.float32) - 1.0
    )
    a = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    b = rand(ks[3], (B, S, 1, N), dtype)
    c = rand(ks[4], (B, S, 1, N), dtype)
    d = jnp.ones((H,), jnp.float32) * 0.5
    want_y, want_s = ref.ssd_chunk_scan(x, dt, a, b, c, chunk=chunk,
                                        d_skip=d)
    got_y, got_s = ssd_chunk_scan_pallas(x, dt, a, b, c, chunk=chunk,
                                         d_skip=d, interpret=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got_y, np.float32), np.asarray(want_y, np.float32),
        **tol,
    )
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want_s), rtol=1e-4, atol=1e-4
    )


def test_ssd_matches_sequential_recurrence():
    """The chunked dual form must equal the naive token recurrence."""
    B, S, H, P, N = 1, 64, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = rand(ks[3], (B, S, 1, N), jnp.float32)
    c = rand(ks[4], (B, S, 1, N), jnp.float32)

    y_chunk, s_chunk = ref.ssd_chunk_scan(x, dt, a, b, c, chunk=16)

    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, state = ref.ssd_decode_step(
            x[:, t], dt[:, t], a, b[:, t], c[:, t], state
        )
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(state),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# block gather
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_block_gather(dtype):
    rs = np.random.RandomState(2)
    pool = jnp.asarray(
        rs.randn(16, 4, 32, 64) * 10, dtype
    )
    idx = jnp.asarray(rs.permutation(16)[:7], jnp.int32)
    want = ref.block_gather(pool, idx)
    got = block_gather_pallas(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
