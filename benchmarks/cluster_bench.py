"""Replica-scaling benchmark: the paper's thread-scaling story at the
cluster layer.

Drives a ReplicaGroup at 1..N replicas per policy — each replica its own
BlockPool shard and stamp domain — with a **periodic checkpoint writer**
keeping a cross-replica hold open for stretches of the run (the paper's
long-lived critical region).  Measures, per (policy, replica count):

  * steps/sec (aggregate engine steps / wall time),
  * scan-steps/step — the reclamation-bookkeeping cost the paper proves
    thread-count independent for Stamp-it.  The acceptance claim is that
    stamp-it stays FLAT (within 2x) from 1 to 4 replicas *while holds
    are active*, because domains are per-replica and a cluster hold is
    O(1) per replica;
  * peak/final unreclaimed pages (hold-induced pressure + recovery).

``python -m benchmarks.cluster_bench`` writes ``BENCH_cluster.json``
({"cluster": rows, "flatness": {policy: max/min scan ratio}}), which
``benchmarks/check_serving_regression.py`` gates (stamp-it flatness <=
2x).  ``--smoke`` shrinks the sweep for CI.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.cluster import ReplicaGroup
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, smoke_config
from repro.memory import PAPER_POLICIES
from repro.models import Model

BENCH_CLUSTER_JSON = (
    Path(__file__).resolve().parent.parent / "BENCH_cluster.json"
)

#: replica-scaling acceptance: stamp-it scan-steps/step flat within 2x
FLATNESS_GATE = 2.0


def _drive_cluster(model, *, policy, n_replicas, requests_per_replica,
                   max_new, checkpoint_every, hold_steps, seed=0,
                   max_seq=512):
    group = ReplicaGroup(
        model, n_replicas, policy=policy, router="least-loaded",
        max_slots=2, max_seq=max_seq, pipeline_depth=2,
        prefix_cache_entries=4, extra_pages_per_slot=2, seed=seed,
    )
    rs = np.random.RandomState(seed)
    # per-replica work constant: total requests scale with replicas
    prompts = [
        list(rs.randint(1, 500, rs.randint(40, 120)).astype(int))
        for _ in range(requests_per_replica * n_replicas)
    ]
    # warmup pass: compile every replica's prefill/decode buckets outside
    # the timed section
    for p in prompts[:2 * n_replicas]:
        group.submit(p, max_new_tokens=max_new)
    group.run_until_done()
    group.drain()

    st0 = group.stats()
    for p in prompts:
        group.submit(p, max_new_tokens=max_new)
    hold = None
    hold_opened_at = 0
    peak = 0
    t0 = time.perf_counter()
    while group.has_work():
        # periodic checkpoint writer: a cross-replica hold stays open
        # for ``hold_steps`` cluster steps out of every ``checkpoint_every``
        if hold is None and group.steps % checkpoint_every == 0:
            hold = group.hold("checkpoint")
            hold_opened_at = group.steps
        group.step()
        peak = max(peak, group.shards.unreclaimed())
        if hold is not None and group.steps - hold_opened_at >= hold_steps:
            hold.release()
            hold = None
    dt = time.perf_counter() - t0
    if hold is not None:
        hold.release()
    group.drain()
    group.reclaim()
    st1 = group.stats()
    d_steps = st1["engine_steps"] - st0["engine_steps"]
    d_scans = st1["scan_steps"] - st0["scan_steps"]
    return {
        "bench": "cluster",
        "policy": policy,
        "replicas": n_replicas,
        "requests": len(prompts),
        "engine_steps": d_steps,
        "time_s": round(dt, 3),
        "steps_per_s": round(d_steps / dt, 2),
        "scan_steps_per_step": round(d_scans / max(d_steps, 1), 3),
        "peak_unreclaimed_pages": peak,
        "final_unreclaimed": st1["unreclaimed"],
        "holds_issued": st1["holds_issued"] - st0["holds_issued"],
        "finished": st1["finished"] - st0["finished"],
    }


def run(policies=PAPER_POLICIES, replica_counts=(1, 2, 4),
        requests_per_replica=6, max_new=8, checkpoint_every=8,
        hold_steps=4, seed=0, write_json=False):
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    rows = []
    for policy in policies:
        for n in replica_counts:
            rows.append(_drive_cluster(
                model, policy=policy, n_replicas=n,
                requests_per_replica=requests_per_replica,
                max_new=max_new, checkpoint_every=checkpoint_every,
                hold_steps=hold_steps, seed=seed,
            ))
    flatness = {}
    for policy in policies:
        vals = [r["scan_steps_per_step"] for r in rows
                if r["policy"] == policy]
        lo = max(min(vals), 1e-9)
        flatness[policy] = round(max(vals) / lo, 3)
    out = {"cluster": rows, "flatness": flatness,
           "flatness_gate": FLATNESS_GATE}
    if write_json:
        BENCH_CLUSTER_JSON.write_text(json.dumps(out, indent=1))
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", default="",
                    help="comma-separated policy names (default: the "
                         "full paper set, hyaline/crystalline included)")
    ap.add_argument("--replicas", default="",
                    help="comma-separated replica counts (default 1,2,4; "
                         "--smoke default 1,2)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: fewer replicas/requests, no JSON")
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args()
    policies = (tuple(p for p in args.policies.split(",") if p)
                or PAPER_POLICIES)
    if args.replicas:
        counts = tuple(int(x) for x in args.replicas.split(","))
    else:
        counts = (1, 2) if args.smoke else (1, 2, 4)
    rpr = 3 if args.smoke else 6
    out = run(policies=policies, replica_counts=counts,
              requests_per_replica=rpr,
              write_json=not (args.smoke or args.no_write))
    for row in out["cluster"]:
        print(json.dumps(row))
    print(f"# flatness (max/min scan-steps/step): {out['flatness']}")
    if not (args.smoke or args.no_write):
        print(f"# wrote {BENCH_CLUSTER_JSON}")


if __name__ == "__main__":
    main()
