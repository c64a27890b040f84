"""Cluster-plane tests: cross-replica holds across every paper policy,
router determinism (incl. prefix affinity), hold-protected prefix
migration, and replica-scaling invariants of the ReplicaGroup."""

import numpy as np
import pytest

from repro.cluster import ReplicaGroup, migrate_prefix
from repro.cluster.ledger import ClusterLedger
from repro.configs import ARCHS, smoke_config
from repro.memory import PAPER_POLICIES, BlockPool, ShardedPoolSet
from repro.models import Model
from repro.models.transformer import BLOCK_SIZE
from repro.serving import ServingEngine

MAX_SEQ = 512


@pytest.fixture(scope="module")
def model():
    return Model(smoke_config(ARCHS["qwen2-0.5b"]))


def _reclaim(pool, rounds=4):
    # grace-period policies (native epoch) need a few advances
    for _ in range(rounds):
        pool.reclaim()


# ---------------------------------------------------------------------------
# cross-replica holds (pool level: no engines needed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", PAPER_POLICIES)
def test_cluster_hold_blocks_reclaim_across_replicas(policy):
    """A page retired on replica A while a cluster hold is open must not
    be reclaimed until the hold releases — for every paper scheme."""
    shards = ShardedPoolSet(2)
    pools = [
        BlockPool(1, 8, policy=policy, shard_id=i, shard_set=shards)
        for i in range(2)
    ]
    ledger = ClusterLedger([p.policy for p in pools])
    pages = pools[0].alloc(0, 3)

    hold = ledger.hold("checkpoint")
    pools[0].free(0, pages)  # retired on replica A, hold open
    _reclaim(pools[0])
    assert pools[0].unreclaimed() == 3, policy
    assert shards.unreclaimed() == 3

    hold.release()
    _reclaim(pools[0])
    assert pools[0].unreclaimed() == 0, policy
    assert pools[0].free_pages_total() == 8
    # the hold entered BOTH replicas' domains
    assert pools[1].policy.holds_issued == 1
    assert pools[1].policy.holds_open == 0


@pytest.mark.parametrize("policy", PAPER_POLICIES)
def test_overlapping_cluster_holds(policy):
    """Pages stay pinned until the LAST open hold releases."""
    pools = [BlockPool(1, 8, policy=policy)]
    ledger = ClusterLedger([p.policy for p in pools])
    h1 = ledger.hold("ckpt")
    h2 = ledger.hold("migration")
    pages = pools[0].alloc(0, 2)
    pools[0].free(0, pages)
    h1.release()
    _reclaim(pools[0])
    assert pools[0].unreclaimed() == 2, policy
    h2.release()
    _reclaim(pools[0])
    assert pools[0].unreclaimed() == 0, policy
    assert ledger.holds_issued == 2 and ledger.open_holds == 0


def test_cluster_hold_is_o1_for_stamp_it():
    """Stamp-it's headline at cluster scale: opening/closing a hold adds
    no scan work proportional to retired pages or replicas."""
    shards = ShardedPoolSet(4)
    pools = [
        BlockPool(1, 64, policy="stamp-it", shard_id=i, shard_set=shards)
        for i in range(4)
    ]
    ledger = ClusterLedger([p.policy for p in pools])
    pages = [p.alloc(0, 30) for p in pools]
    base = shards.ledger_scan_steps()
    with ledger.hold("checkpoint"):
        for p, pg in zip(pools, pages):
            p.free(0, pg)
        held_scans = shards.ledger_scan_steps() - base
        # while held: each shard's reclaim probe is O(1), regardless of
        # the 120 retired pages
        for p in pools:
            p.reclaim()
    for p in pools:
        _reclaim(p)
    assert shards.unreclaimed() == 0
    # bounded bookkeeping: no O(#retired) scans while the hold was open
    assert held_scans <= 4 * 4, held_scans


# ---------------------------------------------------------------------------
# ReplicaGroup end-to-end
# ---------------------------------------------------------------------------
def make_prompts(n, lo=8, hi=120, seed=3):
    rs = np.random.RandomState(seed)
    return [
        list(rs.randint(1, 500, rs.randint(lo, hi)).astype(int))
        for _ in range(n)
    ]


def test_group_matches_single_engine(model):
    """Replica count is an infrastructure knob: outputs must match a
    single engine serving the same requests (greedy, same params)."""
    prompts = make_prompts(4, seed=11)
    eng = ServingEngine(model, max_slots=2, max_seq=MAX_SEQ)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    eng.run_until_done()
    eng.drain()
    want = {tuple(r.prompt): r.generated for r in eng.finished}

    group = ReplicaGroup(model, 2, max_slots=2, max_seq=MAX_SEQ,
                         router="round-robin")
    reqs = [group.submit(p, max_new_tokens=4) for p in prompts]
    group.run_until_done()
    group.drain()
    for p, r in zip(prompts, reqs):
        assert r.done and r.generated == want[tuple(p)]
    # round-robin spread the work across both replicas
    assert {r for _, r in group.route_trace} == {0, 1}


PLACEMENT_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.cluster import ReplicaGroup
from repro.configs import ARCHS, smoke_config
from repro.models import Model
from repro.serving import ServingEngine

devs = jax.devices()
assert len(devs) == 4, devs
model = Model(smoke_config(ARCHS["qwen2-0.5b"]))
rs = np.random.RandomState(5)
prompts = [list(rs.randint(1, 500, n)) for n in (40, 150, 9, 200, 64, 130)]

eng = ServingEngine(model, max_slots=2, max_seq=512)
ref = [eng.submit(p, max_new_tokens=5) for p in prompts]
eng.run_until_done()

group = ReplicaGroup(model, 4, max_slots=2, max_seq=512,
                     router="round-robin", devices=devs)
reqs = [group.submit(p, max_new_tokens=5) for p in prompts]
group.run_until_done()
group.drain()
for i, e in enumerate(group.engines):
    leaves = jax.tree_util.tree_leaves(
        (e.dev.params, e.dev.cache, e.dev.tokens, e.dev.table))
    assert all(x.devices() == {devs[i]} for x in leaves), i
    assert all(x.committed for x in leaves[:-2]), i  # params + cache
assert {r for _, r in group.route_trace} == {0, 1, 2, 3}
assert [r.generated for r in reqs] == [r.generated for r in ref]
print("PLACEMENT_OK")

# host-side page moves between replicas on different devices
from repro.cluster.migration import migrate_prefix
pair = ReplicaGroup(model, 2, max_slots=2, max_seq=512,
                    router="prefix-affinity", prefix_cache_entries=8,
                    extra_pages_per_slot=6, devices=devs[2:])
prompt = prompts[3]  # 200 tokens: one full cached page
r1 = pair.submit(prompt, max_new_tokens=5)
pair.run_until_done()
src = pair.route_trace[0][1]
report = migrate_prefix(pair, prompt, src, 1 - src)
assert report["imported"] == 1, report
cache = pair.engines[1 - src].dev.cache
assert all(x.devices() == {devs[2 + 1 - src]}
           for x in jax.tree_util.tree_leaves(cache))
r2 = pair.submit(prompt, max_new_tokens=5)
pair.run_until_done()
pair.drain()
assert pair.route_trace[-1][1] == 1 - src
assert r2.generated == r1.generated == ref[3].generated
print("MIGRATION_OK")

import sys
sys.path.insert(0, ROOT)
import chip_smoke  # the script's four-chip phase, rehearsed on host devices
chip_smoke.four_chips(model, 0, devs, slots=2, max_seq=1024)
print("CHIP_SMOKE_FOUR_OK")
'''


def test_replicas_on_their_own_devices():
    """devices=: replica i commits its params and cache to device i (four
    forced host devices, in a subprocess because the device count is
    fixed at JAX start-up); tokens equal a single engine's; a prefix
    migrates between replicas on different devices and replays the same
    tokens.  Then runs chip_smoke.py's four-chip phase on the same
    devices."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).parents[1]
    r = subprocess.run(
        [sys.executable, "-c",
         PLACEMENT_SCRIPT.replace("ROOT", repr(str(root)))],
        capture_output=True, text=True, timeout=480,
        env={"PYTHONPATH": str(root / "src"),
             "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    for marker in ("PLACEMENT_OK", "MIGRATION_OK", "CHIP_SMOKE_FOUR_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr[-1500:])


def test_group_checkpoint_hold_defers_then_recovers(model):
    """A checkpoint hold spanning finishes pins their retired pages on
    every replica; teardown (`drain`) releases leaked holds FIRST, so a
    forgotten hold can no longer leave `unreclaimed > 0` forever."""
    group = ReplicaGroup(model, 2, max_slots=1, max_seq=MAX_SEQ,
                         pipeline_depth=2, extra_pages_per_slot=4)
    for p in make_prompts(4, lo=60, hi=100, seed=23):
        group.submit(p, max_new_tokens=3)
    hold = group.hold("checkpoint")
    group.run_until_done()
    # requests finished and retired pages under the open hold; local
    # maintenance cannot free them while it is open
    assert group.stats()["finished"] == 4
    group.reclaim()
    assert group.shards.unreclaimed() > 0
    # the hold is never cooperatively released — drain() releases it
    # (the teardown-leak fix), and teardown is clean
    group.drain()
    assert hold.released
    assert group.ledger.open_holds == 0
    assert group.shards.unreclaimed() == 0


def test_least_loaded_router_balances_free_pages(model):
    group = ReplicaGroup(model, 2, max_slots=2, max_seq=MAX_SEQ,
                         router="least-loaded")
    prompts = make_prompts(4, lo=60, hi=61, seed=5)
    for p in prompts:
        group.submit(p, max_new_tokens=3)
    # equal free pages tie-breaks on queue depth: submissions alternate
    assert [r for _, r in group.route_trace[:2]] == [0, 1]
    group.run_until_done()
    group.drain()


def test_router_prefix_affinity_deterministic(model):
    """Prefix-affinity routing is a deterministic function of the
    request stream: two identical runs route identically, and repeats of
    a cached prompt go to the replica holding the prefix."""

    def run_once():
        group = ReplicaGroup(model, 2, max_slots=2, max_seq=MAX_SEQ,
                             router="prefix-affinity",
                             prefix_cache_entries=8,
                             extra_pages_per_slot=6)
        long = make_prompts(1, lo=2 * BLOCK_SIZE + 4,
                            hi=2 * BLOCK_SIZE + 5, seed=7)[0]
        group.submit(long, max_new_tokens=3)      # cold: least-loaded
        group.run_until_done()                    # prefix now cached
        for p in make_prompts(2, seed=9):         # unrelated traffic
            group.submit(p, max_new_tokens=3)
        group.submit(long, max_new_tokens=3)      # must follow the cache
        group.run_until_done()
        group.drain()
        return group.route_trace, [r.generated for r in group.requests]

    (trace_a, gen_a), (trace_b, gen_b) = run_once(), run_once()
    assert trace_a == trace_b
    assert gen_a == gen_b
    first_replica = trace_a[0][1]
    assert trace_a[-1][1] == first_replica  # affinity followed the cache
    # and the repeat actually hit
    assert gen_a[-1] == gen_a[0]


def test_migration_moves_prefix_without_midflight_reclaim(model):
    """Acceptance: a migration moves a cached prefix between replicas
    and its pages are never reclaimed mid-flight (they retire on the
    source under the migration's cluster hold)."""
    group = ReplicaGroup(model, 2, max_slots=2, max_seq=MAX_SEQ,
                         router="prefix-affinity",
                         prefix_cache_entries=8, extra_pages_per_slot=6)
    prompt = make_prompts(1, lo=2 * BLOCK_SIZE + 5,
                          hi=2 * BLOCK_SIZE + 6, seed=13)[0]
    r1 = group.submit(prompt, max_new_tokens=5)
    group.run_until_done()
    src = group.route_trace[0][1]
    assert len(group.engines[src].prefix_cache) == 2

    dst = 1 - src
    report = migrate_prefix(group, prompt, src, dst)
    assert report["exported"] == report["imported"] == 2
    assert report["evicted"] == 2
    # mid-flight safety: source pages retired under the hold, NOT freed
    assert report["src_unreclaimed_during_hold"] >= 2
    # post-hold: fully reclaimed, cache ownership moved
    assert group.shards.unreclaimed() == 0
    assert len(group.engines[src].prefix_cache) == 0
    assert len(group.engines[dst].prefix_cache) == 2

    # the router follows the pages and the replay is bit-identical
    r2 = group.submit(prompt, max_new_tokens=5)
    group.run_until_done()
    group.drain()
    assert group.route_trace[-1][1] == dst
    assert group.engines[dst].prefix_cache.hits >= 2
    assert r2.generated == r1.generated


@pytest.mark.parametrize("policy", ("hazard", "debra"))
def test_migration_under_adapter_policies(model, policy):
    """Migration's hold protocol works through the CoreSchemeAdapter
    paths too (buffered hold for hazard, region hold for debra)."""
    group = ReplicaGroup(model, 2, policy=policy, max_slots=2,
                         max_seq=MAX_SEQ, router="round-robin",
                         prefix_cache_entries=8, extra_pages_per_slot=6)
    prompt = make_prompts(1, lo=BLOCK_SIZE + 3, hi=BLOCK_SIZE + 4,
                          seed=17)[0]
    group.submit(prompt, max_new_tokens=4)
    group.run_until_done()
    src = group.route_trace[0][1]
    report = migrate_prefix(group, prompt, src, 1 - src)
    assert report["imported"] == 1
    assert report["src_unreclaimed_during_hold"] >= 1
    group.reclaim()
    assert group.shards.unreclaimed() == 0
    group.drain()
