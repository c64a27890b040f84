"""Disaggregated prefill/decode benchmark: the tier plane's three claims.

1. **ITL flatness** (``mode="itl"``) — continuous short-request traffic
   with long-prompt prefills injected mid-stream, measured twice per
   topology (calm, injected).  ITL is the steady-state decode cadence:
   inter-token deltas from token 2 onward.  The token1->token2 gap
   spans the handoff/admission wait (scheduling delay, not cadence) and
   is reported separately as ``first_gap_p99_*``.  In a unified cluster
   a long prompt's chunk rides share fused dispatches with co-located
   short-request decodes, so their inter-token latency degrades; with
   tiers the decode replicas never carry a chunk and short-request ITL
   p99 stays flat (the gate: injected/calm p99 ratio <= 1.5 for the
   tiered topology).
2. **Token equality** (``mode="equality"``) — the same request stream
   served by a tiered and a unified group must produce bit-identical
   token streams, greedy AND sampled (group-level sample keys are
   derived from submission order, not routing; the u for sequence index
   ``pos`` is ``counter_uniform(key, pos)`` on any replica).
3. **Handoff pinning** (``mode="handoff_pin"``, all eight paper
   policies) — during the export->import window the source's freed
   pages are retire-but-held under the kv-handoff ClusterHold
   (``pinned_during_handoff`` > 0 proves the window is real); after the
   hold releases, ``reclaim_rounds_after_commit`` counts scan rounds
   until the source domain is clean — stamp-it frees within ONE scan,
   deferred schemes lag by their batch amortization (the paper's
   asymmetry at handoff granularity).
3b. **TTFT decomposition** (``bench="serving_disagg_ttft"``) — each
   request's queue/prefill/handoff/decode wall time read back from the
   group's lifecycle spans (``repro.obs.SpanRecorder``), per topology:
   the observability plane's answer to "where did the TTFT go", and the
   rows ``benchmarks/make_report.py`` renders as the decomposition
   table.
4. **Mid-handoff faults** (``bench="serving_disagg_fault"``, all eight
   policies) — the prefill replica is killed while a packet is in the
   export window (``import_delay`` > heartbeat timeout forces the
   death-before-import interleaving): the hold force-expires, the pages
   reclaim within timeout + slack, the request replays on a survivor,
   and the stitched streams equal a no-fault run of the same traffic at
   temperature 0.8 (journaled sample keys resume mid-stream).

``python -m benchmarks.disagg_bench`` writes the ``disagg`` section of
``BENCH_serving.json`` (via serving_bench's merge/prune writer), which
``benchmarks/check_serving_regression.py`` gates.  ``--smoke`` shrinks
to stamp-it-only and never writes.
"""

from __future__ import annotations

import argparse
import json
import time
from collections import deque

import numpy as np

from repro.cluster import LifecycleManager, ReplicaGroup
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, smoke_config
from repro.memory import PAPER_POLICIES
from repro.models import Model

from .fault_bench import DEFAULT_HEARTBEAT_TIMEOUT, UNBLOCK_SLACK_STEPS
from .serving_bench import _pct, _update_json

MAX_SEQ = 1536
SHORT_MAX_NEW = 8


def _make_group(model, *, tiered, policy="stamp-it", temperature=0.0,
                import_delay=0, prefill_chunk=None, max_seq=MAX_SEQ,
                replicas=3, prefill=1):
    kw = dict(policy=policy, router="least-loaded", max_slots=2,
              max_seq=max_seq, pipeline_depth=2, extra_pages_per_slot=4,
              temperature=temperature)
    if tiered:
        return ReplicaGroup(model, prefill_replicas=prefill,
                            decode_replicas=replicas - prefill,
                            prefill_chunk_tokens=prefill_chunk,
                            handoff_import_delay=import_delay, **kw)
    return ReplicaGroup(model, replicas, **kw)


def _short_prompts(n, seed=3, lo=12, hi=40):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(1, 500, rs.randint(lo, hi)).astype(int))
            for _ in range(n)]


def _long_prompt(tokens, seed=11):
    rs = np.random.RandomState(seed)
    return list(rs.randint(1, 500, tokens).astype(int))


# ---------------------------------------------------------------------------
# workload 1: short-request ITL under long-prompt injection
# ---------------------------------------------------------------------------
def _drive_itl(model, *, tiered, inject, n_short, long_tokens,
               max_cluster_steps=4000):
    """Continuous short traffic (one submission every other cluster
    step); with ``inject``, two long prompts join mid-stream.  Returns
    the pooled inter-token deltas (ms) of the SHORT requests only."""
    group = _make_group(model, tiered=tiered)

    def one_pass():
        shorts = deque(_short_prompts(n_short))
        longs = deque([_long_prompt(long_tokens, 11),
                       _long_prompt(long_tokens, 12)] if inject else [])
        inject_at = {6, 12}
        tracked, tick = [], 0
        while shorts or longs or group.has_work():
            if shorts and tick % 2 == 0:
                tracked.append(
                    group.submit(shorts.popleft(),
                                 max_new_tokens=SHORT_MAX_NEW))
            if longs and tick in inject_at:
                group.submit(longs.popleft(), max_new_tokens=2)
            group.step()
            tick += 1
            if tick > max_cluster_steps:  # pragma: no cover
                raise RuntimeError("ITL workload did not converge")
        return tracked

    # Warmup: the IDENTICAL workload once, off-clock.  Deterministic
    # routing means the second pass replays the same shapes and
    # fused-step operand combos, so no jit compile (admit/chunk/decode
    # lanes, pow2 page-move buckets, chunk+export+reset dispatch
    # combos) lands inside a measured inter-token gap.
    one_pass()
    h0 = (group.stats().get("tiers") or {}).get("handoffs_completed", 0)
    tracked = one_pass()
    h1 = (group.stats().get("tiers") or {}).get("handoffs_completed", 0)
    group.drain()
    # ITL == steady-state decode cadence from token 2 onward, measured
    # on the EMITTING replica's busy clock (token_busy): the in-process
    # cluster ticks replicas serially, so a wall-clock delta would
    # charge the prefill tier's chunk dispatches to decode-tier tokens
    # in BOTH topologies; per-replica busy time is what independently
    # looping replicas would serve.  The token1->token2 gap spans the
    # handoff/admission wait (export -> ready queue -> import on tiered,
    # decode-slot queueing on unified) AND two replicas' clocks --
    # scheduling delay, not cadence -- so it is pooled separately, on
    # the wall clock.
    deltas, first_gaps = [], []
    for r in tracked:
        ts = r.token_times
        if len(ts) >= 2:
            first_gaps.append((ts[1] - ts[0]) * 1e3)
        bs = r.token_busy
        deltas.extend((b - a) * 1e3 for a, b in zip(bs[1:], bs[2:]))
    assert group.stats()["unreclaimed"] == 0
    return sorted(deltas), sorted(first_gaps), h1 - h0


def bench_itl(model, *, n_short, long_tokens, write_json):
    rows = []
    for topology in ("tiered", "unified"):
        tiered = topology == "tiered"
        calm, calm_gap, _ = _drive_itl(model, tiered=tiered, inject=False,
                                       n_short=n_short,
                                       long_tokens=long_tokens)
        loaded, load_gap, handoffs = _drive_itl(
            model, tiered=tiered, inject=True,
            n_short=n_short, long_tokens=long_tokens)
        row = {
            "bench": "serving_disagg",
            "mode": "itl",
            "policy": "stamp-it",
            "topology": topology,
            "short_requests": n_short,
            "long_prompt_tokens": long_tokens,
            "itl_p50_calm_ms": round(_pct(calm, 50), 3),
            "itl_p99_calm_ms": round(_pct(calm, 99), 3),
            "itl_p50_injected_ms": round(_pct(loaded, 50), 3),
            "itl_p99_injected_ms": round(_pct(loaded, 99), 3),
            "itl_p99_ratio": round(
                _pct(loaded, 99) / max(_pct(calm, 99), 1e-9), 3),
            "first_gap_p99_calm_ms": round(_pct(calm_gap, 99), 3),
            "first_gap_p99_injected_ms": round(_pct(load_gap, 99), 3),
            "handoffs": handoffs if tiered else 0,
        }
        rows.append(row)
        print(f"[itl] {topology:8s} p99 calm {row['itl_p99_calm_ms']:8.1f}ms"
              f"  injected {row['itl_p99_injected_ms']:8.1f}ms"
              f"  ratio {row['itl_p99_ratio']:.2f}"
              f"  handoffs {row['handoffs']}")
    if write_json:
        _update_json(disagg=rows)
    return rows


# ---------------------------------------------------------------------------
# workload 2: tiered == unified token equality (greedy + sampled)
# ---------------------------------------------------------------------------
def _streams(model, *, tiered, temperature, prompts):
    group = _make_group(model, tiered=tiered, temperature=temperature)
    for p in prompts:
        group.submit(p, max_new_tokens=6)
    group.run_until_done()
    group.drain()
    s = group.stats()
    assert s["unreclaimed"] == 0
    return [tuple(r.generated) for r in group.requests], s


def bench_equality(model, *, write_json):
    prompts = _short_prompts(6, seed=5, lo=20, hi=160)
    row = {"bench": "serving_disagg", "mode": "equality",
           "policy": "stamp-it", "topology": "tiered"}
    for label, temp in (("greedy", 0.0), ("sampled", 0.8)):
        uni, _ = _streams(model, tiered=False, temperature=temp,
                          prompts=prompts)
        tie, s = _streams(model, tiered=True, temperature=temp,
                          prompts=prompts)
        row[f"{label}_equal"] = bool(uni == tie)
        row[f"{label}_handoffs"] = s["tiers"]["handoffs_completed"]
        print(f"[equality] {label:8s} equal={row[f'{label}_equal']}  "
              f"handoffs={row[f'{label}_handoffs']}")
    if write_json:
        _update_json(disagg=[row])
    return [row]


# ---------------------------------------------------------------------------
# workload 3: retire-but-held window + scan rounds to reclaim, per policy
# ---------------------------------------------------------------------------
def _drive_handoff_pin(model, policy, *, import_delay=3,
                       max_cluster_steps=600):
    group = _make_group(model, tiered=True, policy=policy,
                        import_delay=import_delay)
    src = group.tiers.prefill_ids[0]
    for p in _short_prompts(2, seed=21, lo=140, hi=200):
        group.submit(p, max_new_tokens=4)
    pinned_max = 0
    tick = 0
    while group.has_work():
        group.step()
        if group.tiers.pending():
            # the export freed the source pages under the kv-handoff
            # hold: retired everywhere, reclaimable nowhere
            group.engines[src].pool.reclaim()
            pinned_max = max(pinned_max,
                             group.engines[src].pool.unreclaimed())
        tick += 1
        if tick > max_cluster_steps:  # pragma: no cover
            raise RuntimeError("handoff-pin workload did not converge")
    # every handoff committed (hold released): count scan rounds until
    # the source domain is clean — stamp-it needs ONE
    rounds = 0
    while group.engines[src].pool.unreclaimed() and rounds < 12:
        group.engines[src].pool.reclaim()
        rounds += 1
    stats = group.stats()
    group.drain()
    return {
        "bench": "serving_disagg",
        "mode": "handoff_pin",
        "policy": policy,
        "topology": "tiered",
        "import_delay": import_delay,
        "handoffs": stats["tiers"]["handoffs_completed"],
        "pages_handed_off": stats["tiers"]["pages_handed_off"],
        "pinned_during_handoff": pinned_max,
        "reclaim_rounds_after_commit": rounds,
    }


def bench_handoff_pin(model, policies, *, write_json):
    rows = []
    for policy in policies:
        row = _drive_handoff_pin(model, policy)
        rows.append(row)
        print(f"[pin] {policy:10s} pinned {row['pinned_during_handoff']:3d}"
              f" pages over {row['handoffs']} handoffs; "
              f"{row['reclaim_rounds_after_commit']} scan round(s) to "
              f"reclaim after commit")
    if write_json:
        _update_json(disagg=rows)
    return rows


# ---------------------------------------------------------------------------
# workload 3b: span-derived TTFT decomposition (obs plane)
# ---------------------------------------------------------------------------
def _drive_ttft_spans(model, *, tiered, n_requests):
    """Serve a prompt stream and decompose each request's lifecycle from
    the group's :class:`~repro.obs.SpanRecorder` — queue (submit->admit),
    prefill (admit->first token), handoff (export->commit, tiered only)
    and decode wall time per request, the observability tentpole's
    answer to 'where did the TTFT go'.  Spans are on by default on every
    ReplicaGroup; this reads them back rather than re-deriving phase
    boundaries from request timestamps."""
    group = _make_group(model, tiered=tiered)
    prompts = _short_prompts(n_requests, seed=17, lo=100, hi=200)
    tracked = [group.submit(p, max_new_tokens=SHORT_MAX_NEW)
               for p in prompts]
    # warmup pass already folded in: first requests pay compile, so run
    # the stream twice and only read spans of the second batch
    group.run_until_done()
    tracked = [group.submit(p, max_new_tokens=SHORT_MAX_NEW)
               for p in prompts]
    group.run_until_done()
    group.drain()
    phases = {ph: [] for ph in ("queue", "prefill", "handoff", "decode")}
    ttfts = []
    for r in tracked:
        bd = group.spans.ttft_breakdown(r._span_rid)
        for ph in phases:
            phases[ph].append(bd.get(ph, 0.0) * 1e3)
        ttfts.append((r.first_token_at - r.submitted_at) * 1e3)
    return phases, sorted(ttfts)


def bench_ttft(model, *, n_requests, write_json):
    rows = []
    for topology in ("tiered", "unified"):
        phases, ttfts = _drive_ttft_spans(
            model, tiered=topology == "tiered", n_requests=n_requests)
        row = {
            "bench": "serving_disagg_ttft",
            "mode": "ttft",
            "policy": "stamp-it",
            "topology": topology,
            "requests": n_requests,
            "ttft_p50_ms": round(_pct(ttfts, 50), 3),
            "ttft_p99_ms": round(_pct(ttfts, 99), 3),
        }
        for ph, vals in phases.items():
            row[f"{ph}_ms_p50"] = round(_pct(sorted(vals), 50), 3)
            row[f"{ph}_ms_mean"] = round(
                sum(vals) / max(len(vals), 1), 3)
        rows.append(row)
        print(f"[ttft] {topology:8s} p50 {row['ttft_p50_ms']:8.1f}ms = "
              f"queue {row['queue_ms_p50']}ms + prefill "
              f"{row['prefill_ms_p50']}ms (+ handoff "
              f"{row['handoff_ms_p50']}ms into token 2)")
    if write_json:
        _update_json(disagg=rows)
    return rows


# ---------------------------------------------------------------------------
# workload 4: kill the prefill replica mid-handoff, per policy
# ---------------------------------------------------------------------------
def _drive_kill(model, policy, *, heartbeat_timeout, temperature=0.8,
                max_cluster_steps=4000):
    prompts = _short_prompts(4, seed=31, lo=130, hi=170)

    def run(kill):
        # import_delay > timeout: the kill always lands BEFORE import
        group = _make_group(model, tiered=True, policy=policy,
                            temperature=temperature,
                            import_delay=heartbeat_timeout + 3)
        mgr = LifecycleManager(group, heartbeat_timeout=heartbeat_timeout)
        src = group.tiers.prefill_ids[0]
        for p in prompts:
            group.submit(p, max_new_tokens=4)
        killed_at = None
        unblocked_in = None
        baseline = 0  # unreclaimed level just before the export pinned
        tick = 0
        while group.has_work():
            if not group.tiers.pending():
                baseline = group.shards.unreclaimed()
            group.step()
            tick += 1
            if (kill and killed_at is None
                    and group.tiers.pending()):
                group.kill_replica(src)
                killed_at = tick
            if (killed_at is not None and unblocked_in is None
                    and src in mgr.dead):
                group.reclaim()
                if group.shards.unreclaimed() <= baseline:
                    unblocked_in = tick - killed_at
            if tick > max_cluster_steps:  # pragma: no cover
                raise RuntimeError("kill workload did not converge")
        if killed_at is not None and unblocked_in is None:
            group.reclaim()
            if group.shards.unreclaimed() <= baseline:
                unblocked_in = group.steps - killed_at
        group.drain()
        streams = [tuple(r.generated) for r in group.requests]
        return streams, group.stats(), mgr.stats(), unblocked_in

    ref, _, _, _ = run(kill=False)
    got, gs, ls, unblocked_in = run(kill=True)
    return {
        "bench": "serving_disagg_fault",
        "mode": "kill",
        "policy": policy,
        "topology": "tiered",
        "temperature": temperature,
        "heartbeat_timeout": heartbeat_timeout,
        "holds_force_expired": ls["holds_force_expired"],
        "handoffs_aborted": gs["tiers"]["handoffs_aborted"],
        "replays_submitted": ls["replays_submitted"],
        "replays_finished": ls["replays_finished"],
        "unblocked_in": unblocked_in,
        "streams_equal": bool(got == ref),
        "unreclaimed_after": gs["unreclaimed"],
    }


def bench_kill(model, policies, *, heartbeat_timeout, write_json):
    rows = []
    for policy in policies:
        row = _drive_kill(model, policy,
                          heartbeat_timeout=heartbeat_timeout)
        rows.append(row)
        print(f"[kill] {policy:10s} unblocked in {row['unblocked_in']} "
              f"steps  aborted {row['handoffs_aborted']}  replays "
              f"{row['replays_finished']}/{row['replays_submitted']}  "
              f"equal={row['streams_equal']}")
    if write_json:
        _update_json(disagg=rows)
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="stamp-it-only quick pass for CI; never writes "
                         "the baseline")
    ap.add_argument("--short-requests", type=int, default=10)
    ap.add_argument("--long-tokens", type=int, default=768)
    ap.add_argument("--heartbeat-timeout", type=int,
                    default=DEFAULT_HEARTBEAT_TIMEOUT)
    ap.add_argument("--skip-itl", action="store_true")
    args = ap.parse_args()

    write = not args.smoke
    policies = (("stamp-it",) if args.smoke else tuple(PAPER_POLICIES))
    n_short = 4 if args.smoke else args.short_requests
    long_tokens = 384 if args.smoke else args.long_tokens

    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    t0 = time.time()
    rows = []
    rows += bench_equality(model, write_json=write)
    if not args.skip_itl:
        rows += bench_itl(model, n_short=n_short,
                          long_tokens=long_tokens, write_json=write)
    rows += bench_ttft(model, n_requests=4 if args.smoke else 6,
                       write_json=write)
    rows += bench_handoff_pin(model, policies, write_json=write)
    rows += bench_kill(model, policies,
                       heartbeat_timeout=args.heartbeat_timeout,
                       write_json=write)
    print(f"\n{len(rows)} rows in {time.time() - t0:.0f}s"
          + ("" if write else "  (smoke: baseline not written)"))
    if args.smoke:
        # CI smoke gates: equality + a completed handoff + a clean kill
        eq = rows[0]
        assert eq["greedy_equal"] and eq["sampled_equal"]
        tt = next(r for r in rows if r["bench"] == "serving_disagg_ttft"
                  and r["topology"] == "tiered")
        assert tt["prefill_ms_mean"] > 0, "no prefill spans recorded"
        assert tt["handoff_ms_mean"] > 0, "no handoff spans recorded"
        pin = next(r for r in rows if r["mode"] == "handoff_pin")
        assert pin["handoffs"] >= 1 and pin["pinned_during_handoff"] >= 1
        assert pin["reclaim_rounds_after_commit"] <= 1  # stamp-it
        kill = next(r for r in rows if r["mode"] == "kill")
        assert kill["streams_equal"] and kill["holds_force_expired"] >= 1
        gate = args.heartbeat_timeout + UNBLOCK_SLACK_STEPS
        assert kill["unblocked_in"] is not None
        assert kill["unblocked_in"] <= gate, (kill["unblocked_in"], gate)
        print("smoke gates passed")


if __name__ == "__main__":
    main()
