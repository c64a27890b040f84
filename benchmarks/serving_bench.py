"""Serving-layer reclamation + hot-path benchmark (beyond-paper, device
plane): the paper's seven-scheme comparison at serving scale.

Drives the ServingEngine with a stream of requests under every
ReclamationPolicy — stamp-it, epoch, new-epoch, hazard, interval, qsr,
debra, lfrc (the paper's §4 set, the adapter-backed ones running the
actual ``core.schemes`` implementations) plus the native scan/refcount
analogues — and measures (a) decode throughput (steps/sec), (b) host
bookkeeping overhead per step, (c) policy bookkeeping work (scan steps),
and (d) page-reclamation latency pressure (peak unreclaimed pages).
Every row also records ``dispatches_per_step`` (== 1.0 on the fused hot
path).

``python -m benchmarks.serving_bench`` writes ``BENCH_serving.json`` at
the repo root — schema ``{"policies": [...], "sweep": [...],
"long_prompt": [...], "cow": [...], "reclaim_latency": [...],
"obs_overhead": [...]}`` — the serving-perf trajectory
baseline that
``benchmarks/check_serving_regression.py`` gates CI against (>10%
stamp-it steps/sec drop fails the workflow; long-prompt p99 TTFT must
stay flat in prompt length).  ``--sweep pipeline_depth,slots``
additionally emits the paper-style scaling rows (pipeline depth is the
serving analogue of the paper's thread count: in-flight steps =
concurrent critical regions); ``--long-prompt`` emits the chunked-vs-
unchunked TTFT workload (one long prompt injected into continuous short
traffic); ``--best-of N --speculate k`` emits the CoW fork +
speculative-lane rows (pages saved vs independent submits, draft
acceptance rate, tokens per dispatch).  Sections are merge-written
ROW-wise with stale-row pruning:
a policy or bench that no longer exists cannot leave ghost rows for
``benchmarks/make_report.py`` to render.
"""

from __future__ import annotations

import argparse
import json
import time
from collections import deque
from pathlib import Path

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, smoke_config
from repro.memory import PAPER_POLICIES, POLICIES
from repro.models import Model
from repro.obs import Registry
from repro.serving import ServingEngine

BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_serving.json"

#: benchmarked by default: the paper's seven-scheme set + native analogues
BENCH_POLICIES = tuple(PAPER_POLICIES) + ("scan", "refcount")

#: sweep axes (the paper's x-axis analogues at serving scale)
SWEEP_DEPTHS = (1, 2, 4)
SWEEP_SLOTS = (2, 4)

#: long-prompt TTFT workload (chunked-prefill tentpole)
LONG_PROMPT_LENS = (512, 1024)
LONG_PROMPT_POLICIES = ("stamp-it", "hazard", "debra")

#: CoW fork + speculative-lane workload: stamp-it plus one adapter-backed
#: scheme (lfrc exercises the NATIVE per-fork reference-count path)
COW_POLICIES = ("stamp-it", "lfrc")

#: bench names this tool can produce — merge-written sections prune rows
#: whose bench/policy no longer exists (no ghost rows in the report)
KNOWN_BENCHES = {"serving_pool", "serving_sweep", "serving_long_prompt",
                 "serving_cow", "serving_disagg", "serving_disagg_fault",
                 "serving_disagg_ttft", "serving_reclaim_latency",
                 "serving_obs_overhead"}

#: observability-overhead budget (percent of stamp-it steps/sec the
#: enabled registry+tracer+spans may cost vs disabled) — asserted at
#: generation AND gated on the committed row by check_serving_regression
OBS_OVERHEAD_GATE_PCT = 5.0


def _pct(sorted_ms, q):
    if not sorted_ms:
        return 0.0
    return round(float(np.percentile(sorted_ms, q)), 2)


def _drive(model, prompts, *, policy, max_new, warmup_prompts,
           max_seq, repeats=3, max_slots=4, pipeline_depth=3,
           chunk_tokens=None, registry=None):
    kw = {} if chunk_tokens is None else {"chunk_tokens": chunk_tokens}
    if registry is not None:
        kw["registry"] = registry
    eng = ServingEngine(model, max_slots=max_slots, max_seq=max_seq,
                        policy=policy, pipeline_depth=pipeline_depth,
                        extra_pages_per_slot=2, **kw)
    # warm the prefill/decode compile caches so the timed section measures
    # the steady-state hot path, not XLA compilation
    for p in warmup_prompts:
        eng.submit(p, max_new_tokens=max_new)
    eng.run_until_done()
    eng.drain()

    # best-of-N timed passes: OS scheduling noise swamps a single short
    # pass; the minimum wall time is the standard microbenchmark
    # estimator.  Every reported metric is a per-pass delta from the
    # SAME (best) pass — mixing lifetime counters with best-pass steps
    # would skew scans-per-step ratios whenever repeats/warmup change.
    best = None
    for _ in range(repeats):
        st0 = eng.stats()
        fin0 = len(eng.finished)
        peak = 0
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        while eng.sched.has_work():
            eng.step()
            peak = max(peak, eng.pool.unreclaimed())
        dt = time.perf_counter() - t0
        eng.drain()
        st1 = eng.stats()
        d = {k: st1[k] - st0[k] for k in
             ("steps", "pool_scan_steps", "ledger_scan_steps",
              "pool_freed", "backpressure_syncs")}
        host_us = (
            (st1["host_us_per_step"] * st1["steps"]
             - st0["host_us_per_step"] * st0["steps"])
            / max(d["steps"], 1)
        )
        ttfts = sorted(
            (r.first_token_at - r.submitted_at) * 1e3
            for r in eng.finished[fin0:]
        )
        if best is None or dt < best[0]:
            best = (dt, d, host_us, peak, ttfts)
    dt, d, host_us, peak, ttfts = best
    scans = d["pool_scan_steps"] + d["ledger_scan_steps"]
    return {
        "bench": "serving_pool",
        "policy": policy,
        "steps": d["steps"],
        "time_s": round(dt, 3),
        "steps_per_s": round(d["steps"] / dt, 2),
        "host_us_per_step": round(host_us, 2),
        "dispatches_per_step": eng.stats()["dispatches_per_step"],
        "chunk_tokens": eng.chunk_tokens,
        "ttft_p50_ms": _pct(ttfts, 50),
        "ttft_p99_ms": _pct(ttfts, 99),
        "peak_unreclaimed_pages": peak,
        "final_unreclaimed": eng.pool.unreclaimed(),
        "ledger_scan_steps": d["ledger_scan_steps"],
        "bookkeeping_scans": scans,
        "scan_steps_per_step": round(scans / max(d["steps"], 1), 3),
        "pages_recycled": d["pool_freed"],
        "backpressure_syncs": d["backpressure_syncs"],
    }


def _workload(seed, n_requests, lo=40, hi=200):
    rs = np.random.RandomState(seed)
    prompts = [
        list(rs.randint(1, 500, rs.randint(lo, hi)).astype(int))
        for _ in range(n_requests)
    ]
    # warmup covers every prefill bucket (1, 2 blocks) and every decode
    # n_kv bucket the timed prompts can reach, so the timed section is
    # pure steady-state (no XLA compiles)
    warmup = [
        list(rs.randint(1, 500, n).astype(int))
        for n in (50, 120, 160, hi - 1)
    ]
    return prompts, warmup


def run(policies=BENCH_POLICIES, n_requests: int = 16, max_new: int = 32,
        seed: int = 0, max_seq: int = 2048, write_json: bool = False):
    """Decode-heavy chat-shaped workload on the production-shaped cell:
    ``max_seq=2048`` makes the block table 17 pages wide; the bucketed
    ``n_kv`` bound keeps the KV sweep at the 1-2 pages these 40-200-token
    prompts actually touch."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    prompts, warmup = _workload(seed, n_requests)
    rows = []
    for policy in policies:
        rows.append(_drive(model, prompts, policy=policy,
                           max_new=max_new, warmup_prompts=warmup,
                           max_seq=max_seq))
    if write_json:
        _update_json(policies=rows)
    return rows


def run_sweep(policies=PAPER_POLICIES, depths=SWEEP_DEPTHS,
              slot_counts=SWEEP_SLOTS, n_requests: int = 8,
              max_new: int = 16, seed: int = 0, max_seq: int = 2048,
              write_json: bool = False):
    """Paper-style scaling sweep: per policy, vary pipeline depth (the
    thread-count analogue — concurrent in-flight critical regions) and
    slot count (concurrent sequences -> page-reference set size).  One
    timed pass per cell (the sweep reads trends, not absolutes)."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    prompts, warmup = _workload(seed, n_requests)
    rows = []
    for policy in policies:
        for slots in slot_counts:
            for depth in depths:
                r = _drive(model, prompts, policy=policy, max_new=max_new,
                           warmup_prompts=warmup, max_seq=max_seq,
                           repeats=1, max_slots=slots,
                           pipeline_depth=depth)
                r["bench"] = "serving_sweep"
                r["pipeline_depth"] = depth
                r["slots"] = slots
                rows.append(r)
    if write_json:
        _update_json(sweep=rows)
    return rows


def _drive_long(model, *, policy, chunk_tokens, long_len, n_short,
                max_new, seed, max_seq, repeats=3):
    """Continuous short traffic with ONE long prompt injected mid-stream:
    the TTFT of the short requests arriving at/after the injection is the
    head-of-line-blocking signal the chunked tentpole bounds.  Best-of-N
    passes on the SAME engine: the first pass doubles as the compile
    warmup (every n_kv bucket x chunk-lane variant the scenario reaches),
    and the minimum-wall-time pass supplies every reported metric."""
    eng = ServingEngine(model, max_slots=4, max_seq=max_seq, policy=policy,
                        pipeline_depth=3, chunk_tokens=chunk_tokens,
                        extra_pages_per_slot=2)
    rs = np.random.RandomState(seed)
    shorts = [
        list(rs.randint(1, 500, rs.randint(40, 120)).astype(int))
        for _ in range(n_short)
    ]
    long_prompt = list(rs.randint(1, 500, long_len).astype(int))

    best = None
    for rep in range(repeats + 1):  # pass 0 = warmup, discarded
        fin0 = len(eng.finished)
        st0 = eng.stats()
        pending = deque(shorts)
        # clamp so the long prompt is always injected even for tiny
        # n_short (submitted can never exceed len(shorts))
        inject_at, submitted = min(3, n_short), 0
        long_req = None
        t0 = time.perf_counter()
        while True:
            if long_req is None and submitted >= inject_at:
                long_req = eng.submit(long_prompt, max_new_tokens=max_new)
            elif pending:
                eng.submit(pending.popleft(), max_new_tokens=max_new)
                submitted += 1
            if not (pending or long_req is None or eng.sched.has_work()):
                break
            eng.step()
        dt = time.perf_counter() - t0
        eng.drain()
        st1 = eng.stats()
        if rep == 0:
            continue
        d = {k: st1[k] - st0[k] for k in
             ("steps", "pool_scan_steps", "ledger_scan_steps",
              "prefill_chunks", "chunk_backpressure")}
        scans = d["pool_scan_steps"] + d["ledger_scan_steps"]
        blocked = [
            r for r in eng.finished[fin0:]
            if r is not long_req
            and r.submitted_at >= long_req.submitted_at
        ]
        ttfts = sorted((r.first_token_at - r.submitted_at) * 1e3
                       for r in blocked)
        long_ttft = (long_req.first_token_at - long_req.submitted_at) * 1e3
        if best is None or dt < best[0]:
            best = (dt, d, scans, ttfts, long_ttft, len(blocked))
    dt, d, scans, ttfts, long_ttft, n_blocked = best
    return {
        "bench": "serving_long_prompt",
        "policy": policy,
        "mode": "chunked" if chunk_tokens else "unchunked",
        "chunk_tokens": chunk_tokens,
        "long_prompt_tokens": long_len,
        "short_requests": n_blocked,
        "short_ttft_p50_ms": _pct(ttfts, 50),
        "short_ttft_p99_ms": _pct(ttfts, 99),
        "long_ttft_ms": round(long_ttft, 2),
        "steps_per_s": round(d["steps"] / max(dt, 1e-9), 2),
        "scan_steps_per_step": round(scans / max(d["steps"], 1), 3),
        "dispatches_per_step": eng.stats()["dispatches_per_step"],
        "prefill_chunks": d["prefill_chunks"],
        "chunk_backpressure": d["chunk_backpressure"],
    }


def run_long_prompt(policies=LONG_PROMPT_POLICIES,
                    long_lens=LONG_PROMPT_LENS, n_short: int = 12,
                    max_new: int = 8, seed: int = 0, max_seq: int = 2048,
                    write_json: bool = False):
    """Chunked-vs-unchunked TTFT under a long-prompt injection, per
    policy: chunked mode must keep short-request p99 TTFT flat in the
    long prompt's length (it only ever waits for ONE chunk), and
    stamp-it's scan-steps/step flat in the chunk count, while hazard/
    debra pay per-chunk guard/record bookkeeping — the paper's
    amortization argument at admission granularity."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    rows = []
    for policy in policies:
        for long_len in long_lens:
            for chunk_tokens in (128, 0):
                rows.append(_drive_long(
                    model, policy=policy, chunk_tokens=chunk_tokens,
                    long_len=long_len, n_short=n_short, max_new=max_new,
                    seed=seed, max_seq=max_seq))
    if write_json:
        _update_json(long_prompt=rows)
    return rows


def _drive_cow(model, *, policy, best_of, speculate_k, prompt_len,
               n_groups, max_new, seed, max_seq, repeats=2):
    """Best-of-N fork workload, CoW+speculative engine vs the
    independent-submit baseline (cow=False, no speculation): same
    prompts, greedy outputs asserted token-identical, page allocation
    measured as per-pass ``reused_total`` deltas so the scratch rows and
    prefix-cache donations cancel out.  One engine pass serves every
    metric in the row — the page accounting, the acceptance rate and the
    tokens/dispatch all come from the same (best) pass."""
    rs = np.random.RandomState(seed)
    prompts = [list(rs.randint(1, 500, prompt_len).astype(int))
               for _ in range(n_groups)]

    def _pass(eng):
        a0 = eng.pool.reused_total
        dd0 = eng.dev.decode_dispatches
        st0 = eng.stats()
        t0 = time.perf_counter()
        groups = [eng.fork_submit(p, best_of, max_new_tokens=max_new)
                  for p in prompts]
        eng.run_until_done()
        dt = time.perf_counter() - t0
        eng.drain()
        st1 = eng.stats()
        d = {k: st1[k] - st0[k] for k in
             ("steps", "cow_copies", "spec_drafted", "spec_accepted",
              "tokens_emitted")}
        d["decode_dispatches"] = eng.dev.decode_dispatches - dd0
        outs = [[list(r.generated) for r in g.branches] for g in groups]
        return dt, eng.pool.reused_total - a0, outs, d, st1

    base = ServingEngine(model, max_slots=best_of, max_seq=max_seq,
                         policy=policy, pipeline_depth=3,
                         extra_pages_per_slot=2, cow=False)
    _pass(base)  # pass 0: compile warmup + scratch allocation
    _, base_pages, base_outs, _, _ = _pass(base)

    eng = ServingEngine(model, max_slots=best_of, max_seq=max_seq,
                        policy=policy, pipeline_depth=3,
                        extra_pages_per_slot=2, cow=True,
                        speculate_k=speculate_k)
    best = None
    for rep in range(repeats + 1):  # pass 0 = warmup, discarded
        res = _pass(eng)
        if rep and (best is None or res[0] < best[0]):
            best = res
    dt, cow_pages, outs, d, st = best
    assert outs == base_outs, \
        f"CoW/spec outputs diverged from baseline under {policy}"

    return {
        "bench": "serving_cow",
        "policy": policy,
        "best_of": best_of,
        "speculate_k": speculate_k,
        "prompt_tokens": prompt_len,
        "groups": n_groups,
        "prompt_pages": -(-prompt_len // eng.block),
        "pages_baseline": base_pages,
        "pages_cow": cow_pages,
        # THE tentpole number: total pages the baseline allocates per
        # page the CoW engine allocates (>= 0.5 * best_of gates CI)
        "pages_saved_ratio": round(base_pages / max(cow_pages, 1), 3),
        "cow_copies": d["cow_copies"],
        "tokens_equal": True,  # asserted above
        "spec_drafted": d["spec_drafted"],
        "spec_acceptance": round(
            d["spec_accepted"] / max(d["spec_drafted"], 1), 4),
        "tokens_per_dispatch": round(
            d["tokens_emitted"] / max(d["decode_dispatches"], 1), 3),
        "dispatches_per_step": st["dispatches_per_step"],
        "forks_balanced": st["forks_taken"] == st["forks_released"],
        "steps": d["steps"],
        "time_s": round(dt, 3),
        "steps_per_s": round(d["steps"] / max(dt, 1e-9), 2),
    }


def run_cow(policies=COW_POLICIES, best_of: int = 4, speculate_k: int = 4,
            prompt_len: int = 520, n_groups: int = 2, max_new: int = 8,
            seed: int = 0, max_seq: int = 2048, write_json: bool = False):
    """CoW fork + speculative-lane workload: N-way best-of groups over a
    4-full-blocks-plus-partial prompt (exercises both the shared-ref and
    the partial-page-copy paths), speculative greedy decode in the same
    fused step.  The row's pages_saved_ratio / tokens_per_dispatch are
    the regression-gated numbers."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    rows = []
    for policy in policies:
        rows.append(_drive_cow(
            model, policy=policy, best_of=best_of,
            speculate_k=speculate_k, prompt_len=prompt_len,
            n_groups=n_groups, max_new=max_new, seed=seed,
            max_seq=max_seq))
    if write_json:
        _update_json(cow=rows)
    return rows


def _drive_reclaim(model, prompts, *, policy, max_new, warmup_prompts,
                   max_seq, max_slots=4, pipeline_depth=3):
    """One serving pass per policy against a FRESH registry; the row is
    the pool tracer's retire->reclaim / hold-lifetime / fork-park
    percentile summary — the paper's 'reclaims earlier' distributions
    (docs/observability.md)."""
    reg = Registry()
    eng = ServingEngine(model, max_slots=max_slots, max_seq=max_seq,
                        policy=policy, pipeline_depth=pipeline_depth,
                        extra_pages_per_slot=2, registry=reg)
    for p in warmup_prompts:
        eng.submit(p, max_new_tokens=max_new)
    eng.run_until_done()
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    eng.run_until_done()
    eng.drain()
    s = eng.pool.trace.summary()
    rl, hl, fp = s["reclaim_latency"], s["hold_lifetime"], s["fork_park"]
    return {
        "bench": "serving_reclaim_latency",
        "policy": policy,
        "steps": eng.steps,
        "retires": rl["count"],
        "p50_steps": rl["p50"],
        "p90_steps": rl["p90"],
        "p99_steps": rl["p99"],
        "mean_steps": round(rl["mean"], 3) if rl["mean"] is not None
        else None,
        "max_steps": rl["max"],
        "holds": hl["count"],
        "hold_p50_steps": hl["p50"],
        "hold_p99_steps": hl["p99"],
        "fork_parks": fp["count"],
        "pending_retired": s["pending_retired"],
        "final_unreclaimed": eng.pool.unreclaimed(),
    }


def run_reclaim_latency(policies=BENCH_POLICIES, n_requests: int = 16,
                        max_new: int = 32, seed: int = 0,
                        max_seq: int = 2048, write_json: bool = False):
    """Per-policy retire->reclaim step-latency distributions under the
    default serving workload.  The gated claim: stamp-it's p50 is no
    worse than the epoch family's (a retired page waits only for the
    steps in flight at retire time, not for two global epoch
    advances)."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    prompts, warmup = _workload(seed, n_requests)
    rows = [
        _drive_reclaim(model, prompts, policy=policy, max_new=max_new,
                       warmup_prompts=warmup, max_seq=max_seq)
        for policy in policies
    ]
    if write_json:
        _update_json(reclaim_latency=rows)
    return rows


def run_obs_overhead(n_requests: int = 16, max_new: int = 32,
                     seed: int = 0, max_seq: int = 2048,
                     repeats: int = 5, write_json: bool = False):
    """The observability tax on the stamp-it hot path: identical
    workload, registry+tracer+spans enabled vs disabled (null
    instruments).  Timed passes ALTERNATE between the two pre-warmed
    engines (best-of-N each) so slow machine drift — thermal throttle,
    background load — hits both sides equally instead of whichever ran
    second; sequential best-of-N runs drift by more than the real
    overhead on a noisy host.  Asserts the <= OBS_OVERHEAD_GATE_PCT
    budget at generation; the committed row is re-gated by
    check_serving_regression."""
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    prompts, warmup = _workload(seed, n_requests)

    def _mk(enabled):
        eng = ServingEngine(model, max_slots=4, max_seq=max_seq,
                            policy="stamp-it", pipeline_depth=3,
                            extra_pages_per_slot=2,
                            registry=Registry(enabled=enabled))
        for p in warmup:
            eng.submit(p, max_new_tokens=max_new)
        eng.run_until_done()
        eng.drain()
        return eng

    def _pass(eng):
        steps0 = eng.steps
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        while eng.sched.has_work():
            eng.step()
        dt = time.perf_counter() - t0
        eng.drain()
        return dt, eng.steps - steps0

    eng_off, eng_on = _mk(False), _mk(True)
    best = {}
    for _ in range(repeats):
        for key, eng in (("off", eng_off), ("on", eng_on)):
            dt, steps = _pass(eng)
            if key not in best or dt < best[key][0]:
                best[key] = (dt, steps)
    off_sps = round(best["off"][1] / best["off"][0], 2)
    on_sps = round(best["on"][1] / best["on"][0], 2)
    overhead_pct = round(
        (off_sps - on_sps) / max(off_sps, 1e-9) * 100, 2)
    row = {
        "bench": "serving_obs_overhead",
        "policy": "stamp-it",
        "steps": best["on"][1],
        "steps_per_s_enabled": on_sps,
        "steps_per_s_disabled": off_sps,
        "overhead_pct": overhead_pct,
        "gate_pct": OBS_OVERHEAD_GATE_PCT,
        "host_us_per_step_enabled": eng_on.stats()["host_us_per_step"],
        "host_us_per_step_disabled": eng_off.stats()["host_us_per_step"],
    }
    assert overhead_pct <= OBS_OVERHEAD_GATE_PCT, (
        f"observability overhead {overhead_pct}% exceeds the "
        f"{OBS_OVERHEAD_GATE_PCT}% budget"
    )
    if write_json:
        _update_json(obs_overhead=[row])
    return [row]


def _row_key(row):
    """Identity of a bench row inside a section (merge/prune unit)."""
    return (row.get("bench"), row.get("policy"),
            row.get("pipeline_depth"), row.get("slots"),
            row.get("mode"), row.get("long_prompt_tokens"),
            row.get("best_of"), row.get("speculate_k"),
            row.get("topology"))


def _merge_section(old_rows, new_rows):
    """Row-level merge: rows re-produced by this run replace their old
    versions; surviving old rows are PRUNED unless their policy still
    exists in the registry and their bench is still produced by this
    tool — a renamed/removed policy or bench can no longer leave ghost
    rows behind for the report to render forever."""
    new_keys = {_row_key(r) for r in new_rows}
    kept = [
        r for r in (old_rows or [])
        if _row_key(r) not in new_keys
        and r.get("policy") in POLICIES
        and r.get("bench") in KNOWN_BENCHES
    ]
    return kept + list(new_rows)


def _update_json(policies=None, sweep=None, long_prompt=None,
                 cow=None, disagg=None, reclaim_latency=None,
                 obs_overhead=None) -> None:
    """Merge-write BENCH_serving.json ({"policies", "sweep",
    "long_prompt", "cow", "disagg", "reclaim_latency",
    "obs_overhead"}), preserving sections this run did
    not produce and merging rows (by bench/policy/axis key) within the
    sections it did — with stale rows pruned (see _merge_section).
    Migrates the PR 2 era bare-list schema.  The "disagg" section is
    produced by benchmarks/disagg_bench.py, which imports this writer so
    both tools share one merge/prune discipline."""
    data = {}
    if BENCH_JSON.exists():
        old = json.loads(BENCH_JSON.read_text())
        data = {"policies": old} if isinstance(old, list) else old
    for name, rows in (("policies", policies), ("sweep", sweep),
                       ("long_prompt", long_prompt), ("cow", cow),
                       ("disagg", disagg),
                       ("reclaim_latency", reclaim_latency),
                       ("obs_overhead", obs_overhead)):
        if rows is not None:
            data[name] = _merge_section(data.get(name), rows)
    BENCH_JSON.write_text(json.dumps(data, indent=1))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", default="",
                    help='scaling axes, e.g. "pipeline_depth,slots" '
                         "(runs the sweep INSTEAD of the default "
                         "per-policy pass)")
    ap.add_argument("--long-prompt", action="store_true",
                    help="run the long-prompt TTFT workload (chunked vs "
                         "unchunked head-of-line blocking) INSTEAD of "
                         "the default per-policy pass")
    ap.add_argument("--best-of", type=int, default=0, metavar="N",
                    help="run the CoW fork + speculative-lane workload "
                         "with N-way best-of groups INSTEAD of the "
                         "default per-policy pass")
    ap.add_argument("--speculate", type=int, default=4, metavar="K",
                    help="draft K tokens per fused dispatch in the "
                         "--best-of workload (0 disables the lane)")
    ap.add_argument("--reclaim-latency", action="store_true",
                    help="run the per-policy retire->reclaim step-"
                         "latency tracing workload INSTEAD of the "
                         "default per-policy pass (obs plane)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="measure the enabled-vs-disabled registry/"
                         "tracer/spans cost on stamp-it and assert the "
                         f"<= {OBS_OVERHEAD_GATE_PCT}%% budget")
    ap.add_argument("--smoke", action="store_true",
                    help="small long-prompt run for CI (stamp-it only, "
                         "shorter prompts); never writes the baseline — "
                         "smoke-config rows measured under different "
                         "load must not merge next to full-run rows")
    ap.add_argument("--policies", default="",
                    help="comma-separated subset (default: all)")
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args()
    write = not args.no_write
    if args.sweep:
        axes = {a.strip() for a in args.sweep.split(",") if a.strip()}
        unknown = axes - {"pipeline_depth", "slots"}
        if unknown:
            ap.error(f"unknown sweep axes {sorted(unknown)}")
        policies = (tuple(args.policies.split(","))
                    if args.policies else PAPER_POLICIES)
        rows = run_sweep(
            policies=policies,
            depths=SWEEP_DEPTHS if "pipeline_depth" in axes else (3,),
            slot_counts=SWEEP_SLOTS if "slots" in axes else (4,),
            write_json=write,
        )
    elif args.best_of:
        policies = (tuple(args.policies.split(","))
                    if args.policies else COW_POLICIES)
        if args.smoke:
            write = False  # see --smoke help: never pollute the baseline
            rows = run_cow(policies=("stamp-it",), best_of=args.best_of,
                           speculate_k=args.speculate, prompt_len=200,
                           n_groups=1, max_new=4, max_seq=1024,
                           write_json=False)
        else:
            rows = run_cow(policies=policies, best_of=args.best_of,
                           speculate_k=args.speculate, write_json=write)
    elif args.reclaim_latency:
        policies = (tuple(args.policies.split(","))
                    if args.policies else BENCH_POLICIES)
        if args.smoke:
            write = False  # see --smoke help: never pollute the baseline
            rows = run_reclaim_latency(policies=policies, n_requests=4,
                                       max_new=8, max_seq=1024,
                                       write_json=False)
        else:
            rows = run_reclaim_latency(policies=policies,
                                       write_json=write)
    elif args.obs_overhead:
        if args.smoke:
            write = False  # see --smoke help: never pollute the baseline
            # best-of-6: the smoke workload is short enough that OS
            # scheduling noise exceeds the 5% budget at low repeats
            rows = run_obs_overhead(n_requests=4, max_new=8,
                                    max_seq=1024, repeats=6,
                                    write_json=False)
        else:
            rows = run_obs_overhead(write_json=write)
    elif args.long_prompt:
        policies = (tuple(args.policies.split(","))
                    if args.policies else LONG_PROMPT_POLICIES)
        if args.smoke:
            write = False  # see --smoke help: never pollute the baseline
            rows = run_long_prompt(policies=("stamp-it",),
                                   long_lens=(256, 512), n_short=6,
                                   max_new=4, max_seq=1024,
                                   write_json=False)
        else:
            rows = run_long_prompt(policies=policies, write_json=write)
    else:
        policies = (tuple(args.policies.split(","))
                    if args.policies else BENCH_POLICIES)
        rows = run(policies=policies, write_json=write)
    for row in rows:
        print(json.dumps(row))
    if write:
        print(f"# wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()
