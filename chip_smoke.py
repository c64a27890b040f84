#!/usr/bin/env python3
"""Bring-up smoke test: serve qwen2-0.5b at its published widths on a TPU.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # four chips, cluster only

One chip: builds ``Model(ARCHS["qwen2-0.5b"])`` with weights drawn from
``--seed``, serves a handful of requests through ``ServingEngine``
(stamp-it, 8 slots, max_seq 4096, chunked prefill) and checks that

  * the paged-decode and flash (chunked-prefill) ops resolve to the
    Pallas kernels and the compiled fused step contains them;
  * the same requests under ``epoch`` give exactly the same tokens;
  * decode and chunk logits of the Pallas path agree with the ``ref``
    path within a bf16 tolerance, on the same cache.

Four chips: runs only the cluster phase — a 4-replica ``ReplicaGroup``
(round-robin), replica ``i`` on ``jax.devices()[i]`` with its own params,
cache and operands — and checks its tokens against a 1-replica run.

Every check that fails raises, so the exit code is non-zero and no result
is printed.  The last line of a passing run is one JSON object naming the
device.  Without a TPU the script exits non-zero; it never falls back to
the CPU.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: qwen2-0.5b as published (arXiv:2407.10671, hf config.json)
PUBLISHED = dict(num_layers=24, d_model=896, num_heads=14, num_kv_heads=2,
                 d_ff=4864, vocab_size=151936, qkv_bias=True,
                 tie_embeddings=True, dtype="bfloat16")
SLOTS, MAX_SEQ, CHUNK, MAX_NEW = 8, 4096, 128, 16
SHARED_PREFIX = 128
#: prompt lengths: three share one 128-token prefix, three stand alone;
#: 300 and 520 span three and five prefill chunks
PROMPT_LENS = (SHARED_PREFIX + 40, SHARED_PREFIX + 77, SHARED_PREFIX + 5,
               300, 520, 60)
#: logits tolerance, max |pallas - ref| / max |ref|: bf16 keeps 8 mantissa
#: bits (2^-8 = 0.4%); 5% leaves room for 24 layers of rounding in a
#: different summation order, and no room for a wrong mask or page
LOGIT_TOL = 5e-2


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (JAX backend is {devs[0].platform!r}); "
            "this check runs on the chip only")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def make_prompts(vocab: int, seed: int, lens=PROMPT_LENS):
    """Token prompts of ``lens``; the first three longer than
    ``SHARED_PREFIX`` start with one shared prefix."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(1, vocab, SHARED_PREFIX).tolist()
    out = []
    for n in lens:
        if n > SHARED_PREFIX and len(out) < 3:
            out.append(prefix + rs.randint(1, vocab,
                                           n - SHARED_PREFIX).tolist())
        else:
            out.append(rs.randint(1, vocab, n).tolist())
    return out


class CompileClock:
    """Sums XLA backend-compile seconds reported by jax.monitoring."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def check_config(cfg) -> None:
    got = {k: getattr(cfg, k) for k in PUBLISHED}
    check(got == PUBLISHED, f"config is not the published one: {got}")
    log("config", f"{cfg.name}: " + ", ".join(
        f"{k}={v}" for k, v in got.items()))


def check_kernel_selection(expected: str) -> None:
    from repro.kernels import ops

    chosen = {"paged_attention": ops._impl(None),
              "flash_attention": ops._impl(None),
              "decode_attention": ops._impl(None),
              "ssd_chunk_scan": ops._impl(None, tpu_kernel=False)}
    log("kernels", " ".join(f"{k}={v}" for k, v in chosen.items()))
    check(chosen["paged_attention"] == expected
          and chosen["flash_attention"] == expected,
          f"paged/flash attention resolved to {chosen}, not {expected}")


def logits_vs_ref(model, dev) -> dict:
    """Decode and chunked-prefill logits through the selected kernels and
    through ``ref``, on the engine's live cache (nothing is donated: the
    next engine step still owns these buffers)."""
    import jax
    import jax.numpy as jnp

    batch = {"tokens": dev.tokens, "lengths": dev.lengths,
             "block_table": dev.table}
    lengths = np.asarray(dev.lengths)
    slot = int(lengths.argmax())
    check(lengths[slot] > dev.block, "no slot spans two pages yet")
    row = dev.table[slot]
    toks = np.random.RandomState(7).randint(
        1, model.cfg.vocab_size, (1, dev.block)).astype(np.int32)
    chunk = {"tokens": jnp.asarray(toks), "start": jnp.int32(dev.block),
             "slot": jnp.int32(slot), "row": row, "pages": row[1:2],
             "last_index": jnp.int32(dev.block - 1)}

    def run():
        dec = jax.jit(lambda p, c, b: model.decode_step(
            p, c, b, n_kv=dev.mb, global_pages=True)[0])
        pre = jax.jit(lambda p, c, b: model.prefill_chunk(
            p, c, b, n_kv=4, global_pages=True)[0])
        return (np.asarray(dec(dev.params, dev.cache, batch), np.float32),
                np.asarray(pre(dev.params, dev.cache, chunk), np.float32))

    got = run()
    asked = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = "ref"
    try:
        want = run()
    finally:
        if asked is None:
            del os.environ["REPRO_KERNEL_IMPL"]
        else:
            os.environ["REPRO_KERNEL_IMPL"] = asked
    err = {}
    for name, g, w in zip(("decode", "chunk"), got, want):
        check(np.isfinite(g).all(), f"{name} logits are not finite")
        err[name] = float(np.abs(g - w).max() / np.abs(w).max())
        err[name + "_argmax_agree"] = float(
            (g.argmax(-1) == w.argmax(-1)).mean())
    return err


def serve(engine, prompts, *, probe=None):
    """Submit, run to completion, drain; returns generated tokens.
    ``probe(engine)`` runs once every prompt is prefilled."""
    reqs = [engine.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    probed = None
    while engine.sched.has_work():
        engine.step()
        if (probe is not None and probed is None
                and not engine.sched.waiting
                and not engine.sched.admitting
                and engine.decode_steps >= len(prompts)):
            probed = probe(engine)
    engine.drain()
    check(all(r.done and len(r.generated) == MAX_NEW for r in reqs),
          "not every request generated its tokens")
    return [list(map(int, r.generated)) for r in reqs], probed


def one_chip(model, seed: int, *, expected_impl: str = "pallas",
             check_step_text: bool = True, max_seq: int = MAX_SEQ) -> None:
    """The one-chip phases.  The keywords let the CPU tests rehearse them
    with interpret-mode kernels and a smaller cache."""
    import jax

    from repro.serving import ServingEngine

    check_kernel_selection(expected_impl)
    prompts = make_prompts(model.cfg.vocab_size, seed)
    log("requests", f"{len(prompts)} prompts, lengths "
        f"{[len(p) for p in prompts]}, {MAX_NEW} new tokens each; first "
        f"three share a {SHARED_PREFIX}-token prefix")
    clock = CompileClock()
    t0 = time.perf_counter()
    kw = dict(max_slots=SLOTS, max_seq=max_seq, chunk_tokens=CHUNK,
              params=model.init_params(seed))
    eng = ServingEngine(model, policy="stamp-it", **kw)
    probe = lambda e: logits_vs_ref(model, e.dev)  # noqa: E731
    tokens, err = serve(eng, prompts, probe=probe)
    wall = time.perf_counter() - t0
    st = eng.stats()
    check(st["dispatches_per_step"] == 1.0, "fused step took >1 dispatch")
    check(err is not None, "the logits probe never ran")
    log("serve", f"policy=stamp-it steps={st['steps']} "
        f"tokens={st['tokens_emitted']} prefill_chunks="
        f"{st['prefill_chunks']} wall_s={wall:.3f} (weights, compiles "
        f"and the logits probe included) compile_s={clock.seconds:.3f} "
        f"compiles={clock.count}")
    log("logits", f"pallas vs ref: {err} (tolerance {LOGIT_TOL})")
    check(err["decode"] <= LOGIT_TOL and err["chunk"] <= LOGIT_TOL,
          f"Pallas logits differ from ref by more than {LOGIT_TOL}")

    if check_step_text:
        text = eng.dev.compiled_step_text(n_kv=4, has_chunk=True)
        n = text.count("tpu_custom_call")
        log("step", f"compiled fused step (n_kv=4, chunk lane) holds "
            f"{n} tpu_custom_call sites")
        check(n >= 2, "fused step lacks the paged and flash kernels")

    eng2 = ServingEngine(model, policy="epoch", **kw)
    tokens2, _ = serve(eng2, prompts)
    same = tokens2 == tokens
    log("policy", f"epoch tokens == stamp-it tokens: {same}")
    check(same, "epoch and stamp-it served different tokens")
    for i, t in enumerate(tokens[:3]):
        log("tokens", f"req {i}: {t}")

    stats = jax.devices()[0].memory_stats() or {}
    log("memory", f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def four_chips(model, seed: int, devices, *, slots: int = SLOTS,
               max_seq: int = MAX_SEQ) -> None:
    """The cluster phase, one replica per device in ``devices``."""
    import jax

    from repro.cluster import ReplicaGroup

    prompts = make_prompts(model.cfg.vocab_size, seed,
                           PROMPT_LENS + (200, 90))
    kw = dict(policy="stamp-it", router="round-robin", max_slots=slots,
              max_seq=max_seq, chunk_tokens=CHUNK, seed=seed)

    def run(n):
        group = ReplicaGroup(model, n, devices=devices[:n], **kw)
        reqs = [group.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        t0 = time.perf_counter()
        group.run_until_done()
        group.drain()
        wall = time.perf_counter() - t0
        check(all(r.done and len(r.generated) == MAX_NEW for r in reqs),
              f"{n}-replica run left requests unfinished")
        return group, [list(map(int, r.generated)) for r in reqs], wall

    group, tokens, wall = run(len(devices))
    for i, eng in enumerate(group.engines):
        leaves = jax.tree_util.tree_leaves((eng.dev.params, eng.dev.cache))
        placed = {d for x in leaves for d in x.devices()}
        check(all(x.committed for x in leaves) and placed == {devices[i]},
              f"replica {i} state is not committed to {devices[i]}")
        log("replica", f"{i}: device={devices[i]} requests="
            f"{[r for r, e in group.route_trace if e == i]} steps="
            f"{eng.steps}")
    check(len({e.device for e in group.engines}) == len(devices),
          "replicas share a device")
    log("cluster", f"{len(devices)} replicas served {len(prompts)} "
        f"requests, wall_s={wall:.3f} (compile included)")
    del group
    _, ref_tokens, _ = run(1)
    same = tokens == ref_tokens
    log("cluster", f"tokens == 1-replica run: {same}")
    check(same, "4-replica tokens differ from the 1-replica run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache
    from repro.configs import ARCHS
    from repro.models import Model

    log("setup", f"devices={devs} compile_cache={enable_compile_cache()}")
    cfg = ARCHS["qwen2-0.5b"]
    check_config(cfg)
    model = Model(cfg)
    log("setup", f"params={model.n_params()} seed={args.seed}")
    if args.chips == 4:
        four_chips(model, args.seed, devs[:4])
    else:
        one_chip(model, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
