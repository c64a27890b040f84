"""Stamp Pool (paper §3.1-3.2) unit + stress tests."""

import random
import threading
import time

import pytest

from repro.core.atomics import MarkedValue
from repro.core.stamp_pool import (
    NOT_IN_LIST,
    PENDING_PUSH,
    STAMP_INC,
    Block,
    StampPool,
)


def test_initial_state():
    pool = StampPool()
    assert pool.lowest_stamp() == 0
    assert pool.highest_stamp() == 0
    pool.check_quiescent_invariants()


def test_single_push_remove():
    pool = StampPool()
    b = Block("t0")
    stamp = pool.push(b)
    assert stamp == STAMP_INC
    assert pool.highest_stamp() == stamp
    assert b.stamp.load() == stamp  # PendingPush cleared
    pool.check_quiescent_invariants()
    was_last = pool.remove(b)
    assert was_last
    assert b.stamp.load() & NOT_IN_LIST
    assert pool.lowest_stamp() >= stamp + STAMP_INC
    pool.check_quiescent_invariants()


def test_stamps_strictly_increasing():
    pool = StampPool()
    blocks = [Block(f"t{i}") for i in range(8)]
    stamps = [pool.push(b) for b in blocks]
    assert stamps == sorted(stamps)
    assert len(set(stamps)) == len(stamps)
    pool.check_quiescent_invariants()
    # prev direction: head -> newest ... oldest -> tail
    chain = pool.prev_chain()
    assert chain[1:-1] == list(reversed(blocks))


def test_fifo_removal_updates_tail_stamp():
    pool = StampPool()
    blocks = [Block(f"t{i}") for i in range(4)]
    stamps = [pool.push(b) for b in blocks]
    # remove in entry order: each leaver was the lowest
    for i, b in enumerate(blocks):
        was_last = pool.remove(b)
        assert was_last, f"block {i} should have been the last (lowest)"
        if i + 1 < len(blocks):
            # lowest active stamp must now be blocks[i+1]'s stamp
            assert pool.lowest_stamp() <= stamps[i + 1]
            assert pool.lowest_stamp() > stamps[i]
        pool.check_quiescent_invariants()


def test_lifo_removal():
    pool = StampPool()
    blocks = [Block(f"t{i}") for i in range(4)]
    for b in blocks:
        pool.push(b)
    # remove newest-first: never the last until the very end
    for b in reversed(blocks[1:]):
        assert not pool.remove(b)
        pool.check_quiescent_invariants()
    assert pool.remove(blocks[0])
    pool.check_quiescent_invariants()


def test_middle_removal():
    pool = StampPool()
    a, b, c = Block("a"), Block("b"), Block("c")
    sa = pool.push(a)
    pool.push(b)
    pool.push(c)
    assert not pool.remove(b)
    pool.check_quiescent_invariants()
    chain = pool.prev_chain()
    assert chain == [pool.head, c, a, pool.tail]
    assert pool.lowest_stamp() <= sa
    assert pool.remove(a)
    assert pool.remove(c)
    pool.check_quiescent_invariants()


def test_block_reuse():
    pool = StampPool()
    b = Block("reused")
    prev_stamp = 0
    for _ in range(50):
        s = pool.push(b)
        assert s > prev_stamp
        prev_stamp = s
        pool.remove(b)
    pool.check_quiescent_invariants()


def test_reentry_interleaved():
    pool = StampPool()
    b1, b2 = Block("b1"), Block("b2")
    for i in range(30):
        pool.push(b1)
        pool.push(b2)
        if i % 2:
            pool.remove(b1)
            pool.remove(b2)
        else:
            pool.remove(b2)
            pool.remove(b1)
        pool.check_quiescent_invariants()
    assert pool.lowest_stamp() <= pool.head.stamp.load()


def test_unlink_helper_rejects_a_recycled_successor():
    """A remover that stepped from ``head`` to ``x`` and saw ``x``
    marked must not unlink ``x`` after ``x`` left and re-entered the
    pool: ``head.prev`` names ``x`` again, but with a new tag.  Unlinking
    on the stale view would point ``head`` at a block that left the pool
    (and the next push of that block would link it to itself)."""
    pool = StampPool()
    x, z = Block("x"), Block("z")
    pool.push(z)
    x_stamp = pool.push(x)                 # head -> x -> z -> tail
    seen = pool.head.prev.load()           # the remover's step to x
    stale = x.prev.load().with_mark()      # ... and x.prev, marked
    pool.remove(x)
    pool.remove(z)
    pool.push(x)                           # x is back: head -> x -> tail
    pool._remove_or_skip_marked_block(seen, MarkedValue(pool.head), stale,
                                      x_stamp)
    assert pool.head.prev.load().obj is x
    pool.remove(x)
    pool.check_quiescent_invariants()
    assert pool.prev_chain() == [pool.head, pool.tail]


#: a stress run takes seconds; a thread still inside push/remove after
#: this long is live-locked, which fails the test instead of hanging it
JOIN_TIMEOUT_S = 60.0


def _join_all(threads):
    deadline = time.monotonic() + JOIN_TIMEOUT_S
    for t in threads:
        t.join(max(deadline - time.monotonic(), 0.0))
    stuck = [t.name for t in threads if t.is_alive()]
    assert not stuck, f"live-locked in the stamp pool: {stuck}"


@pytest.mark.parametrize("n_threads,iters", [(4, 400), (8, 250)])
def test_stress_concurrent_push_remove(n_threads, iters):
    """Concurrent enter/leave cycles; validate the tail-stamp safety
    invariant (tail.stamp never exceeds the stamp of an in-pool block) via
    per-thread observations, and structural invariants at quiescence."""
    pool = StampPool()
    errors = []
    barrier = threading.Barrier(n_threads)

    def worker(idx):
        rng = random.Random(idx)
        block = Block(f"w{idx}")
        try:
            barrier.wait()
            for _ in range(iters):
                my_stamp = pool.push(block)
                # While we are in the pool, lowest_stamp must stay <= ours.
                for _ in range(rng.randrange(4)):
                    lo = pool.lowest_stamp()
                    if lo > my_stamp:
                        errors.append(
                            f"tail stamp {lo} overtook in-pool stamp {my_stamp}"
                        )
                pool.remove(block)
        except Exception as e:  # pragma: no cover
            import traceback

            errors.append(traceback.format_exc())

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    _join_all(threads)
    assert not errors, errors[:3]
    pool.check_quiescent_invariants()
    # pool must be empty again
    assert pool.prev_chain() == [pool.head, pool.tail]


def test_stress_staggered_lifetimes():
    """Threads hold overlapping critical regions of random length."""
    pool = StampPool()
    n_threads = 6
    errors = []
    stop = threading.Event()

    def worker(idx):
        rng = random.Random(1000 + idx)
        block = Block(f"s{idx}")
        try:
            while not stop.is_set():
                s = pool.push(block)
                if pool.highest_stamp() < s:
                    errors.append("highest_stamp below an assigned stamp")
                if pool.lowest_stamp() > s:
                    errors.append("lowest_stamp above an in-pool stamp")
                pool.remove(block)
        except Exception:  # pragma: no cover
            import traceback

            errors.append(traceback.format_exc())

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    time.sleep(1.5)
    stop.set()
    _join_all(threads)
    assert not errors, errors[:3]
    pool.check_quiescent_invariants()
