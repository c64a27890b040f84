"""Serving-engine end-to-end tests: output correctness against a model-
level reference decode, invariance across ALL reclamation policies (the
paper's seven schemes via the ReclamationPolicy plane plus the native
analogues), the fused single-dispatch step, prefix cache reuse, and pool
reclamation behaviour under async dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ShapeConfig, smoke_config
from repro.kernels.ref import to_pages
from repro.memory import POLICIES
from repro.models import Model
from repro.models.transformer import BLOCK_SIZE
from repro.serving import ServingEngine

MAX_SEQ = 512

#: every serving-selectable policy: the paper's seven schemes (stamp-it,
#: epoch, new-epoch, hazard, interval, qsr, debra, lfrc) + native analogues
ALL_POLICIES = sorted(POLICIES)


@pytest.fixture(scope="module")
def model():
    return Model(smoke_config(ARCHS["qwen2-0.5b"]))


def reference_generate(model, prompt, max_new):
    """Model-level greedy decode (contiguous positions, paged cache)."""
    shape = ShapeConfig("ref", "decode", MAX_SEQ, 1)
    params = model.init_params(0)
    cache = model.init_cache(shape)
    mb = cache["layers"]["k_pool"].shape[2]
    logits, kv = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}
    )
    # place prefill kv into pages 0..nb-1 (identity table)
    S = len(prompt)
    nb = -(-S // BLOCK_SIZE)
    pad = nb * BLOCK_SIZE - S
    k = jnp.pad(kv["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    v = jnp.pad(kv["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kr = to_pages(k, BLOCK_SIZE)  # (L, 1, nb, Hkv, block, D)
    cache["layers"]["k_pool"] = (
        cache["layers"]["k_pool"].at[:, :, :nb].set(
            kr.astype(cache["layers"]["k_pool"].dtype))
    )
    vr = to_pages(v, BLOCK_SIZE)
    cache["layers"]["v_pool"] = (
        cache["layers"]["v_pool"].at[:, :, :nb].set(
            vr.astype(cache["layers"]["v_pool"].dtype))
    )
    table = jnp.tile(jnp.arange(mb, dtype=jnp.int32), (1, 1)).reshape(1, mb)
    out = [int(jnp.argmax(logits[0]))]
    tok = out[0]
    length = S
    for _ in range(max_new - 1):
        logits, cache = model.decode_step(params, cache, {
            "tokens": jnp.asarray([[tok]], jnp.int32),
            "lengths": jnp.asarray([length], jnp.int32),
            "block_table": table,
        })
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
        length += 1
    return out


def make_prompts(n, lo=8, hi=200, seed=3):
    rs = np.random.RandomState(seed)
    return [
        list(rs.randint(1, 500, rs.randint(lo, hi)).astype(int))
        for _ in range(n)
    ]


def test_engine_matches_reference(model):
    prompts = make_prompts(3)
    want = [reference_generate(model, p, 6) for p in prompts]
    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ,
                        pipeline_depth=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    done = eng.run_until_done()
    eng.drain()
    got = {r.rid: r.generated for r in done}
    assert len(done) == 3
    for i in range(3):
        assert got[i] == want[i], f"request {i}: {got[i]} != {want[i]}"
    # the fused hot path: admission chunks, growth, teacher-forcing,
    # decode and sampling fold into exactly ONE device dispatch per
    # engine step — even on the steps that carry prefill chunks
    assert eng.stats()["dispatches_per_step"] == 1
    # ... and the admission plane is fully inside the fused step now:
    # chunked prefill needs ZERO extra dispatches, and the prefill jit
    # cache holds exactly one chunk shape (no power-of-two buckets)
    assert eng.stats()["admissions"] == 3
    assert eng.stats()["admission_dispatches"] == 0
    assert eng.stats()["chunk_shapes"] == [BLOCK_SIZE]
    assert eng.stats()["prefill_jit_shapes"] == []


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_policy_invariance(model, policy):
    """Reclamation policy may change pool pressure, never outputs —
    across every scheme selectable through the ReclamationPolicy plane."""
    prompts = make_prompts(4, seed=7)
    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ, policy=policy,
                        pipeline_depth=2, extra_pages_per_slot=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    done = sorted(eng.run_until_done(), key=lambda r: r.rid)
    eng.drain()
    tokens = [r.generated for r in done]
    # compare against the first parametrization's run
    key = tuple(map(tuple, tokens))
    ref = _POLICY_REFERENCE.setdefault("tokens", key)
    assert key == ref
    assert eng.stats()["dispatches_per_step"] == 1
    assert eng.stats()["admission_dispatches"] == 0  # chunked admissions
    # after drain, every policy but native-epoch fully reclaims (epoch
    # needs two more grace periods by design)
    if policy != "epoch":
        assert eng.pool.unreclaimed() == 0, eng.stats()


_POLICY_REFERENCE = {}


def test_slot_reuse_under_pressure(model):
    """More requests than slots; pages must cycle through reclamation."""
    prompts = make_prompts(8, lo=100, hi=300, seed=11)
    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ,
                        pipeline_depth=3)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    done = eng.run_until_done()
    assert len(done) == 8
    eng.drain()
    assert eng.pool.unreclaimed() == 0
    assert eng.pool.freed_total > 0


def test_prefix_cache_reuse(model):
    """A repeated long prompt must hit the cache and give identical
    output."""
    rs = np.random.RandomState(5)
    prompt = list(rs.randint(1, 500, 2 * BLOCK_SIZE + 7).astype(int))
    want = reference_generate(model, prompt, 5)

    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ,
                        prefix_cache_entries=8, extra_pages_per_slot=6)
    r1 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_done()
    assert eng.prefix_cache.hits == 0
    r2 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_done()
    eng.drain()
    assert r1.generated == want
    assert r2.generated == want, (r2.generated, want)
    assert eng.prefix_cache.hits >= 2  # both full blocks hit


def test_prefix_cache_reuse_slot0(model):
    """Teacher-forced suffix replay in slot 0 must not be clobbered by
    the batched token scatter's padding entries (regression: pads used
    in-bounds slot 0 and scatter-order made the stale pad win)."""
    rs = np.random.RandomState(9)
    prompt = list(rs.randint(1, 500, 2 * BLOCK_SIZE + 5).astype(int))
    want = reference_generate(model, prompt, 5)
    # max_slots=1: every admission (incl. the cache-hit replay) is slot 0
    eng = ServingEngine(model, max_slots=1, max_seq=MAX_SEQ,
                        prefix_cache_entries=8, extra_pages_per_slot=6)
    r1 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_done()
    r2 = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_done()
    eng.drain()
    assert eng.prefix_cache.hits >= 2
    assert r1.generated == want
    assert r2.generated == want, (r2.generated, want)


def test_prefix_hit_long_suffix_classic_path(model):
    """A cached-prefix prompt whose suffix is too long for replay takes
    the chunked full prefill WITHOUT a wasted hit-page copy: no extra
    dispatch, and the output matches the no-cache reference."""
    rs = np.random.RandomState(31)
    prefix = list(rs.randint(1, 500, BLOCK_SIZE).astype(int))
    p1 = prefix + list(rs.randint(1, 500, 5).astype(int))
    p2 = prefix + list(rs.randint(1, 500, 2 * BLOCK_SIZE + 9).astype(int))
    want = reference_generate(model, p2, 4)
    eng = ServingEngine(model, max_slots=1, max_seq=MAX_SEQ,
                        prefix_cache_entries=8, extra_pages_per_slot=6)
    eng.submit(p1, max_new_tokens=3)
    eng.run_until_done()
    r2 = eng.submit(p2, max_new_tokens=4)
    eng.run_until_done()
    eng.drain()
    assert eng.prefix_cache.hits >= 1  # p2's first block hit the cache
    assert r2.generated == want
    assert eng.stats()["admission_dispatches"] == 0  # no replay copy ran


def test_sampled_mode_on_device(model):
    """temperature/top-p sampling runs inside the single fused dispatch:
    deterministic under a fixed sample_seed, still one dispatch/step, and
    greedy (temperature=0) remains the statically-compiled fast path."""
    prompts = make_prompts(3, seed=17)

    def run(seed):
        eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ,
                            pipeline_depth=2, temperature=0.8, top_p=0.9,
                            sample_seed=seed)
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        done = sorted(eng.run_until_done(), key=lambda r: r.rid)
        eng.drain()
        assert eng.stats()["dispatches_per_step"] == 1
        return [r.generated for r in done]

    a, b = run(7), run(7)
    assert a == b  # device RNG chain is deterministic
    vocab = model.cfg.vocab_size
    assert all(0 <= t < vocab for toks in a for t in toks)


def test_backpressure_force_sync_and_retry(model):
    """Page growth hitting PoolExhausted must force-sync the pipeline,
    reclaim, and retry — not crash.  Setup: a tight pool where a finished
    request's pages are still awaiting reclamation (stale in-flight steps
    hold the ledger) exactly when the next request needs to grow."""
    eng = ServingEngine(model, max_slots=1, max_seq=MAX_SEQ,
                        pipeline_depth=4, extra_pages_per_slot=1)
    # pool: 6 pages; page 0 is scratch -> 5 usable
    assert eng.pool.pages_per_slot == 6
    a = eng.submit(make_prompts(1, lo=300, hi=301, seed=21)[0],
                   max_new_tokens=2)   # 3 pages, finishes fast
    b = eng.submit(make_prompts(1, lo=255, hi=256, seed=22)[0],
                   max_new_tokens=4)   # 2 pages, grows at length 256
    done = eng.run_until_done()
    eng.drain()
    assert len(done) == 2
    assert len(a.generated) == 2 and len(b.generated) == 4
    assert eng.backpressure_syncs >= 1, eng.stats()
    assert eng.pool.unreclaimed() == 0


def test_ledger_blocks_reuse_while_inflight(model):
    """Pages freed while steps are in flight must not be reclaimed until
    those steps complete (the async-dispatch hazard)."""
    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ,
                        pipeline_depth=3)
    eng.submit(make_prompts(1, lo=150, hi=151, seed=13)[0],
               max_new_tokens=3)
    eng.submit(make_prompts(1, lo=150, hi=151, seed=14)[0],
               max_new_tokens=12)
    saw_deferred = False
    while eng.waiting or eng.active or eng._inflight:
        eng.step()
        if eng.pool.unreclaimed() > 0 and eng._inflight:
            saw_deferred = True
    eng.drain()
    assert saw_deferred, "expected retired-but-not-reclaimed pages"
    assert eng.pool.unreclaimed() == 0


def _chip_smoke():
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    import chip_smoke

    return chip_smoke


def test_chip_smoke_refuses_without_tpu():
    """The chip check never falls back to the CPU: no result, non-zero
    exit."""
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main([])
    assert exc.value.code not in (0, None)


def test_chip_smoke_phases_rehearse_on_cpu(model, monkeypatch):
    """chip_smoke's one-chip phases at smoke size with the Pallas bodies
    interpreted: kernel selection by name, Pallas-vs-ref logits, and
    stamp-it == epoch tokens."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    _chip_smoke().one_chip(model, 0, expected_impl="interpret",
                           check_step_text=False, max_seq=1024)
