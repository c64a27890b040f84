"""The traffic generator: deterministic by seed, and its lengths and
arrivals follow the mix's parameters."""

import statistics

import numpy as np
import pytest

import _paths  # noqa: F401
import traffic

SEED = 2**33 + 17  # wider than 32 bits: seeds may be


def _sig(items):
    return [(it.due, tuple(it.prompt), it.max_new, it.sample_key)
            for it in items]


@pytest.mark.parametrize("mix,rate", [("chat", 10.0), ("batch", None),
                                      ("sessions", 2.0)])
def test_same_seed_same_schedule_other_seed_other_tokens(mix, rate):
    m = traffic.load_mix(mix)
    a = traffic.generate(m, SEED, 20, 32000, rate)
    b = traffic.generate(m, SEED, 20, 32000, rate)
    c = traffic.generate(m, SEED + 1, 20, 32000, rate)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)
    # the seed permutes the work; it does not change how much there is
    assert len(a) == len(c)
    assert (sorted(len(i.prompt) for i in a)
            == sorted(len(i.prompt) for i in c))


def test_open_arrivals_count_rate_and_burstiness():
    m = traffic.load_mix("chat")
    items = traffic.generate(m, SEED, 30, 151936, 12.0)
    due = np.array([i.due for i in items])
    assert len(items) == 360 and due[0] == 0.0 and due[-1] < 30
    assert np.all(np.diff(due) >= 0)
    gaps = np.diff(due)
    cv = gaps.std() / gaps.mean()
    assert 1.5 < cv < 2.5, cv  # Gamma gaps with CV 2


def test_open_lengths_follow_the_mix():
    m = traffic.load_mix("chat")
    items = traffic.generate(m, SEED, 30, 151936, 12.0)
    p = [len(i.prompt) for i in items]
    o = [i.max_new for i in items]
    assert min(p) >= 16 and max(p) <= 3072
    assert abs(statistics.median(p) - 512) <= 16
    assert min(o) >= 8 and max(o) <= 1024
    assert abs(statistics.median(o) - 128) <= 4
    assert max(a + b for a, b in zip(p, o)) <= 4096
    # stratified: every seed draws the same multiset of prompt lengths
    q = [len(i.prompt) for i in traffic.generate(m, SEED + 5, 30, 151936,
                                                 12.0)]
    assert sorted(p) == sorted(q)


def test_backlog_blocks_hold_the_same_lengths():
    m = traffic.load_mix("batch")
    items = traffic.generate(m, SEED, 10, 32064)
    assert all(i.due is None for i in items)
    blk = m["block"]
    first = sorted(len(i.prompt) for i in items[:blk])
    second = sorted(len(i.prompt) for i in items[blk:2 * blk])
    assert first == second
    assert min(first) >= 256 and max(first) <= 1791
    assert all(len(i.prompt) + i.max_new <= 2047 for i in items)


def test_sessions_share_system_prompts_and_resend_history():
    m = traffic.load_mix("sessions")
    items = traffic.generate(m, SEED, 30, 151936, 2.0)
    by_session = {}
    for it in items:
        by_session.setdefault(it.session, []).append(it)
        assert len(it.prompt) + it.max_new <= 4096
    heads = {tuple(it.prompt[:1024]) for it in items}
    assert len(heads) <= 8
    multi = [v for v in by_session.values() if len(v) > 1]
    assert multi
    for turns in multi:
        turns.sort(key=lambda it: it.turn)
        for a, b in zip(turns, turns[1:]):
            # the next turn resends the whole previous prompt
            assert b.prompt[: len(a.prompt)] == a.prompt
            assert b.due > a.due


def test_quantiles_are_stratified():
    q = traffic.quantiles({"dist": "lognormal", "median": 100, "sigma": 1.0,
                           "min": 1, "max": 10**6}, 1001)
    assert q[500] == 100 and np.all(np.diff(q) >= 0)
    g = traffic.quantiles({"dist": "geometric", "mean": 2, "offset": 1,
                           "min": 2, "max": 6}, 1000)
    assert g.min() == 2 and g.max() == 6
