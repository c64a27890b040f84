"""The decoder's serving steps carry the WHOLE stacked KV pool through
their layer scan and update it in place by layer index.  Against a
per-layer Python loop over ``pool[i]`` that uses the per-layer write and
oracle arithmetic, ``decode_step`` and ``prefill_chunk`` give the same
logits and the same whole pool, bit for bit, with global and with local
page ids.  Families that keep the pool in their scan's xs/ys (hybrid,
encoder-decoder) still do."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ShapeConfig, smoke_config
from repro.kernels import ref
from repro.models import Model
from repro.models import layers as L
from repro.models import transformer as T

B, MAX_SEQ, N_KV = 3, 512, 4


@pytest.fixture(scope="module")
def model():
    return Model(smoke_config(ARCHS["qwen2-0.5b"]).scaled(num_layers=3))


@pytest.fixture(scope="module")
def state(model):
    """Parameters and a cache whose pools already hold random KV."""
    params = model.init_params(0)
    cache = model.init_cache(ShapeConfig("t", "decode", MAX_SEQ, B))
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    layers = {n: (jax.random.normal(k, a.shape, jnp.float32) * 0.5
                  ).astype(a.dtype)
              for (n, a), k in zip(sorted(cache["layers"].items()), ks)}
    return params, dict(cache, layers=layers)


def tables(n_pool, global_pages, seed):
    """Scattered block-table rows: each slot's pages, or any pages of
    the slot-flattened pool (as copy-on-write forks reference them)."""
    rs = np.random.RandomState(seed)
    mb = -(-MAX_SEQ // T.BLOCK_SIZE) + 1
    if global_pages:
        ids = rs.permutation(B * n_pool)[:B * mb].reshape(B, mb)
    else:
        ids = np.stack([rs.permutation(n_pool)[:mb] for _ in range(B)])
    return jnp.asarray(ids, jnp.int32)


def layer_params(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def per_layer_decode(params, cache, batch, cfg, global_pages):
    """decode_step as a loop over layers, each writing its own pool[i]
    and reading it through the oracle."""
    lengths, table = batch["lengths"], batch["block_table"]
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
    pools = {"k_pool": [], "v_pool": []}
    for i in range(cfg.num_layers):
        lp = layer_params(params, i)
        a_in = L.apply_norm(lp["norm1"], x, cfg)
        q, k, v = L._project_qkv(lp["attn"], a_in, cfg)
        q = L.apply_rope(q, lengths[:, None], cfg.rope_theta)
        k = L.apply_rope(k, lengths[:, None], cfg.rope_theta)
        kp = cache["layers"]["k_pool"][i]
        vp = cache["layers"]["v_pool"][i]
        block = kp.shape[3]
        page = table[jnp.arange(B), lengths // block]
        offs = lengths % block
        if global_pages:
            flat = (-1,) + kp.shape[2:]
            kp = kp.reshape(flat).at[page, :, offs].set(k[:, 0]).reshape(
                kp.shape)
            vp = vp.reshape(flat).at[page, :, offs].set(v[:, 0]).reshape(
                vp.shape)
        else:
            kp = kp.at[jnp.arange(B), page, :, offs].set(k[:, 0])
            vp = vp.at[jnp.arange(B), page, :, offs].set(v[:, 0])
        out = ref.paged_attention(q[:, 0], kp, vp, table, lengths + 1,
                                  n_kv=N_KV, global_pages=global_pages)
        x = x + jnp.einsum("bhd,hdm->bm", out,
                           lp["attn"]["wo"].astype(x.dtype))[:, None]
        x = x + T._ffn(lp, x, cfg)
        pools["k_pool"].append(kp)
        pools["v_pool"].append(vp)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(params["embed"], x[:, 0], cfg)
    return logits, {n: jnp.stack(p) for n, p in pools.items()}


def per_layer_chunk(params, cache, batch, cfg, global_pages):
    """prefill_chunk as a loop over layers, each scattering the chunk's
    pages into its own pool[i] and gathering the row's pages back."""
    slot, row, pages = batch["slot"], batch["row"], batch["pages"]
    C = batch["tokens"].shape[1]
    positions = batch["start"] + jnp.arange(C, dtype=jnp.int32)
    x = L.embed_tokens(params["embed"], batch["tokens"], cfg)
    pools = {"k_pool": [], "v_pool": []}
    for i in range(cfg.num_layers):
        lp = layer_params(params, i)
        a_in = L.apply_norm(lp["norm1"], x, cfg)
        q, k, v = L._project_qkv(lp["attn"], a_in, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kp = cache["layers"]["k_pool"][i]
        vp = cache["layers"]["v_pool"][i]
        block = kp.shape[3]
        kc = ref.to_pages(k[0], block).astype(kp.dtype)
        vc = ref.to_pages(v[0], block).astype(vp.dtype)
        if global_pages:
            flat = (-1,) + kp.shape[2:]
            kfl = kp.reshape(flat).at[pages].set(kc)
            vfl = vp.reshape(flat).at[pages].set(vc)
            kp, vp = kfl.reshape(kp.shape), vfl.reshape(vp.shape)
            gk, gv = kfl[row[:N_KV]], vfl[row[:N_KV]]
        else:
            kp = kp.at[slot, pages].set(kc)
            vp = vp.at[slot, pages].set(vc)
            gk, gv = kp[slot][row[:N_KV]], vp[slot][row[:N_KV]]
        out = ref.flash_attention(q, ref.from_pages(gk)[None],
                                  ref.from_pages(gv)[None], causal=True,
                                  q_offset=positions[0])
        x = x + jnp.einsum("bshd,hdm->bsm", out,
                           lp["attn"]["wo"].astype(x.dtype))
        x = x + T._ffn(lp, x, cfg)
        pools["k_pool"].append(kp)
        pools["v_pool"].append(vp)
    x = L.apply_norm(params["final_norm"], x, cfg)
    x_last = x[:, batch["last_index"]]
    logits = L.unembed(params["embed"], x_last, cfg)
    return logits, {n: jnp.stack(p) for n, p in pools.items()}


def assert_same(got, want):
    (g_logits, g_cache), (w_logits, w_pools) = got, want
    np.testing.assert_array_equal(np.asarray(g_logits, np.float32),
                                  np.asarray(w_logits, np.float32))
    for name, pool in w_pools.items():
        np.testing.assert_array_equal(
            np.asarray(g_cache["layers"][name], np.float32),
            np.asarray(pool, np.float32))


@pytest.mark.parametrize("global_pages", [True, False])
def test_decode_step_in_place_matches_per_layer_loop(model, state,
                                                     global_pages):
    params, cache = state
    table = tables(cache["layers"]["k_pool"].shape[2], global_pages, 1)
    batch = {"tokens": jnp.asarray([[5], [17], [301]], jnp.int32),
             "lengths": jnp.asarray([0, 130, 383], jnp.int32),
             "block_table": table}
    got = jax.jit(lambda p, c, b: model.decode_step(
        p, c, b, n_kv=N_KV, global_pages=global_pages))(params, cache, batch)
    want = jax.jit(lambda p, c, b: per_layer_decode(
        p, c, b, model.cfg, global_pages))(params, cache, batch)
    assert_same(got, want)


@pytest.mark.parametrize("global_pages", [True, False])
def test_prefill_chunk_in_place_matches_per_layer_loop(model, state,
                                                       global_pages):
    params, cache = state
    n_pool = cache["layers"]["k_pool"].shape[2]
    row = tables(n_pool, global_pages, 2)[1]
    C, start = T.BLOCK_SIZE, T.BLOCK_SIZE  # the prompt's second chunk
    tokens = np.random.RandomState(4).randint(1, 500, (1, C))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32),
             "start": jnp.int32(start), "slot": jnp.int32(1), "row": row,
             "pages": row[start // T.BLOCK_SIZE:][:1],
             "last_index": jnp.int32(77)}
    got = jax.jit(lambda p, c, b: model.prefill_chunk(
        p, c, b, n_kv=N_KV, global_pages=global_pages))(params, cache, batch)
    want = jax.jit(lambda p, c, b: per_layer_chunk(
        p, c, b, model.cfg, global_pages))(params, cache, batch)
    assert_same(got, want)


def scan_operands(jaxpr):
    """(carry shapes, xs shapes) of every scan in ``jaxpr``, nested ones
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            shapes = [tuple(v.aval.shape) for v in eqn.invars]
            found.append((shapes[nc:nc + nk], shapes[nc + nk:]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's body
                if hasattr(sub, "eqns"):
                    found += scan_operands(sub)
    return found


def decode_scans(m, global_pages=True):
    params = m.abstract_params()
    shape = ShapeConfig("t", "decode", MAX_SEQ, B)
    cache = jax.eval_shape(lambda: m.init_cache(shape))
    batch = jax.eval_shape(lambda: m.synthetic_batch(shape))
    jaxpr = jax.make_jaxpr(lambda p, c, b: m.decode_step(
        p, c, b, global_pages=global_pages))(params, cache, batch)
    return cache, scan_operands(jaxpr.jaxpr)


def test_decoder_scan_carries_the_whole_pool(model):
    cache, scans = decode_scans(model)
    pool = tuple(cache["layers"]["k_pool"].shape)
    flat = (pool[0], pool[1] * pool[2]) + pool[3:]
    (carry, xs), = scans
    assert carry.count(flat) == 2  # k and v, whole and slot-flattened
    assert all(s[1:] not in (pool[1:], flat[1:]) for s in xs)  # no slab
    assert xs[-1] == (pool[0],)    # the layer index


@pytest.mark.parametrize("arch", ["zamba2-7b", "seamless-m4t-medium"])
def test_hybrid_and_encdec_keep_their_own_scan(arch):
    """Hybrid and encoder-decoder stacks still slice their attention
    pool per layer as a scan input, and decode through it."""
    m = Model(smoke_config(ARCHS[arch]))
    pools = (lambda c: c["attn"]) if arch == "zamba2-7b" else (
        lambda c: c["self"])
    cache, scans = decode_scans(m)
    shape = tuple(pools(cache)["k_pool"].shape)
    assert any(shape in xs for _, xs in scans)
    assert not any(shape in carry for carry, _ in scans)

    shape_cfg = ShapeConfig("t", "decode", MAX_SEQ, B)
    c0 = m.init_cache(shape_cfg)
    logits, c1 = jax.jit(lambda p, c, b: m.decode_step(
        p, c, b, global_pages=True))(m.init_params(0), c0,
                                     m.synthetic_batch(shape_cfg))
    assert bool(jnp.isfinite(logits).all())
    assert not np.array_equal(np.asarray(pools(c1)["k_pool"]),
                              np.asarray(pools(c0)["k_pool"]))
