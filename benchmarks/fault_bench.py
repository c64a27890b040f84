"""Fault-tolerance benchmark: the paper's thread-failure argument at
cluster scale.

Kills one replica mid-traffic while a checkpoint writer RUNNING ON THAT
REPLICA has a cross-replica hold open — the cluster-scale reproduction
of the paper's known weakness (one stalled/crashed thread blocks
reclamation for everyone) and of its mitigation (forced stamp expiry
after a deadline).  Per policy, measures:

  * ``steps_to_detect``   — cluster steps from the kill to the missed
    heartbeat deadline (== the configured timeout, by construction);
  * ``steps_to_unblock``  — cluster steps from the kill until the
    surviving replicas' aggregate ``unreclaimed`` returns to the
    pre-hold baseline (the hold's pages were pinned in EVERY domain
    until the lifecycle plane force-expired it);
  * ``reclamation_blocked_steps`` — the manager's own observable: ticks
    in which a silent replica's holds pinned pages actually awaiting
    reclamation;
  * **goodput dip** — tokens/step before the kill, during the blocked
    window, and after recovery (replays landing on survivors);
  * replay accounting (submitted / finished).

``python -m benchmarks.fault_bench`` sweeps all eight paper policies at
4 replicas and writes ``BENCH_fault.json`` (``{"fault": rows,
"unblock_gate_steps": N}``), which
``benchmarks/check_serving_regression.py`` gates (every policy's
``steps_to_unblock`` bounded).  ``--smoke`` shrinks to stamp-it + one
adapter scheme at 2 replicas for CI.
"""

from __future__ import annotations

import argparse
import json
import time
from collections import deque
from pathlib import Path

import numpy as np

from repro.cluster import LifecycleManager, ReplicaGroup
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, smoke_config
from repro.memory import PAPER_POLICIES
from repro.models import Model

BENCH_FAULT_JSON = (
    Path(__file__).resolve().parent.parent / "BENCH_fault.json"
)

#: bounded recovery: unreclaimed must return to baseline within the
#: heartbeat timeout plus this many cluster steps (detection latency is
#: the timeout itself; the slack covers post-expiry reclaim rounds and
#: in-flight pins on the survivors)
UNBLOCK_SLACK_STEPS = 8

#: the bench's default missed-beat deadline; the regression checker's
#: fallback gate derives from this, so the two cannot drift
DEFAULT_HEARTBEAT_TIMEOUT = 3


def _tokens_total(group) -> int:
    return sum(len(r.generated or []) for r in group.requests)


def _drive_fault(model, *, policy, n_replicas, requests, max_new,
                 heartbeat_timeout, kill_after, hold_steps, seed=0,
                 max_seq=512, max_cluster_steps=4000):
    group = ReplicaGroup(
        model, n_replicas, policy=policy, router="least-loaded",
        max_slots=2, max_seq=max_seq, pipeline_depth=2,
        prefix_cache_entries=4, extra_pages_per_slot=4, seed=seed,
    )
    mgr = LifecycleManager(group, heartbeat_timeout=heartbeat_timeout)
    victim = 0
    rs = np.random.RandomState(seed)
    prompts = deque(
        list(rs.randint(1, 500, rs.randint(40, 120)).astype(int))
        for _ in range(requests)
    )
    # warmup: compile every replica's fused step outside the timed run
    w = group.submit(list(rs.randint(1, 500, 48).astype(int)),
                     max_new_tokens=2)
    group.run_until_done()
    group.drain()
    assert w.done

    baseline = group.shards.unreclaimed()  # pre-hold baseline
    hold = None
    hold_opened = 0
    killed_at = None
    unblocked_at = None
    tokens_at_kill = 0
    tokens_at_unblock = 0
    window = 5  # trailing-rate window for the pre-kill goodput
    history = deque(maxlen=window + 1)  # cumulative tokens per step
    t0 = time.perf_counter()
    while prompts or group.has_work():
        # two submissions per cluster step: enough offered load that the
        # survivors are saturated and losing a replica actually costs
        for _ in range(min(2, len(prompts))):
            group.submit(prompts.popleft(), max_new_tokens=max_new)
        # checkpoint writer RUNNING ON THE VICTIM: periodic cluster
        # holds owned by replica 0.  While the victim lives, it releases
        # them cooperatively after ``hold_steps``; the one open when the
        # victim dies can only go away via forced expiry.  The kill is
        # processed FIRST so the release/reopen logic below can never
        # cooperatively close (or post-mortem reopen) the dying writer's
        # hold on the kill step itself.
        if killed_at is None and group.steps >= kill_after:
            if hold is None or hold.released:
                # the writer crashes between checkpoints: model it as
                # crashing mid-write (hold open, never to be released)
                hold = group.hold("checkpoint", owner=victim)
            group.kill_replica(victim)
            killed_at = group.steps
            tokens_at_kill = _tokens_total(group)
        if (hold is None or hold.released) and killed_at is None:
            hold = group.hold("checkpoint", owner=victim)
            hold_opened = group.steps
        if (hold is not None and not hold.released
                and killed_at is None
                and group.steps - hold_opened >= hold_steps):
            hold.release()
        group.step()
        if killed_at is None:
            history.append(_tokens_total(group))
        if killed_at is not None and unblocked_at is None:
            # probe: local maintenance on survivors, then check whether
            # the hold-pinned pages actually freed.  "Unblocked" needs
            # the death to have been DECLARED (holds force-expired) AND
            # unreclaimed back at the pre-hold baseline — before the
            # deadline fires, the dead owner's hold pins every retire.
            group.reclaim()
            if (victim in mgr.dead
                    and group.shards.unreclaimed() <= baseline):
                unblocked_at = group.steps
                tokens_at_unblock = _tokens_total(group)
        if group.steps > max_cluster_steps:  # pragma: no cover
            raise RuntimeError("fault run did not converge")
    dt = time.perf_counter() - t0
    if killed_at is None:
        raise RuntimeError(
            f"workload drained in {group.steps} cluster steps, before "
            f"kill_after={kill_after} — raise requests/max_new so the "
            f"kill lands mid-traffic"
        )
    group.drain()
    if unblocked_at is None:
        # traffic may end on the death tick itself, before the in-loop
        # probe ran again — check once more post-drain before declaring
        # the recovery broken (never persist a corrupted row)
        if (victim in mgr.dead
                and group.shards.unreclaimed() <= baseline):
            unblocked_at = group.steps
            tokens_at_unblock = _tokens_total(group)
        else:
            raise RuntimeError(
                f"{policy}: reclamation never returned to the pre-hold "
                f"baseline after the kill — forced expiry is broken"
            )
    s = group.stats()
    ls = mgr.stats()
    death_tick = ls["deaths"][0][0] if ls["deaths"] else None
    tokens_final = _tokens_total(group)
    end = group.steps
    # goodput (tokens per cluster step) in the three phases; "before"
    # is a TRAILING-window rate so prefill ramp-up doesn't dilute it
    if len(history) >= 2:
        g_before = (history[-1] - history[0]) / (len(history) - 1)
    else:
        g_before = tokens_at_kill / max(killed_at, 1)
    blocked_span = max((unblocked_at or end) - killed_at, 1)
    g_during = (tokens_at_unblock - tokens_at_kill) / blocked_span
    after_span = max(end - (unblocked_at or end), 1)
    g_after = (tokens_final - tokens_at_unblock) / after_span
    return {
        "bench": "fault",
        "policy": policy,
        "replicas": n_replicas,
        "heartbeat_timeout": heartbeat_timeout,
        "requests": requests,
        "kill_step": killed_at,
        "steps_to_detect": (death_tick - killed_at
                            if death_tick is not None else None),
        "steps_to_unblock": (unblocked_at - killed_at
                             if unblocked_at is not None else None),
        "reclamation_blocked_steps": ls["reclamation_blocked_steps"],
        "holds_force_expired": ls["holds_force_expired"],
        "replays_submitted": ls["replays_submitted"],
        "replays_finished": ls["replays_finished"],
        "goodput_before": round(g_before, 3),
        "goodput_during_blocked": round(g_during, 3),
        "goodput_after": round(g_after, 3),
        "goodput_dip_pct": round(
            100 * (1 - g_during / max(g_before, 1e-9)), 1),
        # client-visible completions (internal replay admissions finish
        # on engines too, but surface on the original requests)
        "finished": sum(1 for r in group.requests if r.done),
        "unreclaimed_final": s["unreclaimed"],
        "time_s": round(dt, 3),
    }


def run(policies=PAPER_POLICIES, n_replicas=4, requests=24, max_new=10,
        heartbeat_timeout=DEFAULT_HEARTBEAT_TIMEOUT, kill_after=14,
        hold_steps=4, seed=0, write_json=False):
    model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
    rows = [
        _drive_fault(
            model, policy=p, n_replicas=n_replicas, requests=requests,
            max_new=max_new, heartbeat_timeout=heartbeat_timeout,
            kill_after=kill_after, hold_steps=hold_steps, seed=seed,
        )
        for p in policies
    ]
    out = {
        "fault": rows,
        "unblock_gate_steps": heartbeat_timeout + UNBLOCK_SLACK_STEPS,
    }
    if write_json:
        BENCH_FAULT_JSON.write_text(json.dumps(out, indent=1))
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", default="",
                    help="comma-separated policy names "
                         "(default: all eight paper policies)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="replica count (default 4; --smoke default 2)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: stamp-it + one adapter scheme, "
                         "2 replicas, no JSON")
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args()
    if args.policies:
        policies = tuple(p for p in args.policies.split(",") if p)
    else:
        policies = (("stamp-it", "debra") if args.smoke
                    else PAPER_POLICIES)
    n = args.replicas or (2 if args.smoke else 4)
    requests = 8 if args.smoke else 24
    out = run(policies=policies, n_replicas=n, requests=requests,
              write_json=not (args.smoke or args.no_write))
    for row in out["fault"]:
        print(json.dumps(row))
        assert row["steps_to_unblock"] is not None, (
            f"{row['policy']}: reclamation never unblocked")
        assert row["steps_to_unblock"] <= out["unblock_gate_steps"], (
            f"{row['policy']}: unblock took {row['steps_to_unblock']} "
            f"steps (> {out['unblock_gate_steps']} gate)")
    print(f"# unblock gate: <= {out['unblock_gate_steps']} steps "
          f"after the kill (all policies within)")
    if not (args.smoke or args.no_write):
        print(f"# wrote {BENCH_FAULT_JSON}")


if __name__ == "__main__":
    main()
