"""The benchmark's one traffic generator.

A traffic mix is a data file, ``chipbench/traffic/<mix>.json``; this
module reads it and turns ``(seed, seconds, rate)`` into a schedule of
requests.  Three kinds of mix exist:

* ``open``: independent users.  Arrivals are Gamma inter-arrival gaps
  with a stated coefficient of variation (CV 1 is Poisson); exactly
  ``rate * seconds`` requests are due inside the window.
* ``backlog``: an offline batch.  ``outstanding_per_slot`` requests per
  engine slot are queued at the start, and the harness submits the next
  one each time one finishes; ``due`` is ``None``.
* ``sessions``: multi-turn conversations.  Sessions start as a Poisson
  process; turn 1 is a shared system prompt (Zipf-chosen) plus a user
  message, and every later turn resends the whole history (earlier user
  messages and recorded assistant replies) plus a new message.

Steadiness: what a run measures must not swing with the seed, so the
structure of a mix (arrival times, session shapes and their lengths)
comes from the mix's own ``structure_seed``, and lengths are drawn as
stratified quantiles of their distribution (the same multiset for every
seed).  The order of the lengths comes from the structure seed too:
which request is long decides how many slots decode at a time and
which request of a burst waits, and a seed that moved it would change
the work measured (on the chip, runs of one seed repeated within 1 %
while six seeds spread by 11 %).  ``--seed`` draws every token id,
every sampling key and the weights.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Item:
    """One request of a schedule.  ``due`` is seconds after the window
    opens (``None``: backlog, submitted when a slot frees)."""

    due: Optional[float]
    prompt: List[int]
    max_new: int
    session: int = -1
    turn: int = 0
    sample_key: int = 0


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise ValueError(f"no traffic mix {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def rng_for(seed: int, salt: int) -> np.random.Generator:
    """A generator keyed on the whole seed (any size) and a salt."""
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws, ``F^-1((i + 0.5) / n)``, in ascending
    order and clipped to ``[min, max]``: integers for lengths."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "geometric":  # support 1, 2, ...; mean 1 / p
        p = 1.0 / dist["mean"]
        v = np.ceil(np.log1p(-q) / np.log1p(-p)) + dist.get("offset", 0)
    elif kind == "fixed":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist.get("min", 1),
                   dist.get("max", np.inf)).astype(np.int64)


def gamma_gaps(rng: np.random.Generator, n: int, rate: float,
               cv: float) -> np.ndarray:
    """``n`` inter-arrival gaps with mean ``1/rate`` and the given CV."""
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (rate * shape), n)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, n).tolist()


def _keys(rng: np.random.Generator, n: int) -> List[int]:
    return rng.integers(0, 2**31 - 1, n).tolist()


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[Item]:
    kind = mix["kind"]
    if kind == "open":
        return _open(mix, seed, seconds, vocab, rate)
    if kind == "backlog":
        return _backlog(mix, seed, seconds, vocab)
    if kind == "sessions":
        return _sessions(mix, seed, seconds, vocab, rate)
    raise ValueError(f"unknown traffic kind {kind!r}")


def _lengths(mix: dict, n: int, rng: np.random.Generator):
    """Stratified prompt and output lengths, shuffled by ``rng``,
    each pair cut so that prompt + output fits ``max_total``."""
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outs = rng.permutation(quantiles(mix["output"], n))
    outs = np.minimum(outs, mix["max_total"] - prompts)
    return prompts.tolist(), outs.tolist()


def _open(mix, seed, seconds, vocab, rate) -> List[Item]:
    n = max(int(round(rate * seconds)), 1)
    gaps = gamma_gaps(rng_for(mix["structure_seed"], 1), n, rate,
                      mix["arrival_cv"])
    # exactly n arrivals inside [0, seconds): the first at 0
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / gaps.sum()
    prompts, outs = _lengths(mix, n, rng_for(mix["structure_seed"], 2))
    rng = rng_for(seed, 2)
    keys = _keys(rng, n)
    return [Item(float(d), _tokens(rng, p, vocab), int(o), sample_key=k)
            for d, p, o, k in zip(due, prompts, outs, keys)]


def _backlog(mix, seed, seconds, vocab) -> List[Item]:
    """Enough requests for the longest window: blocks of ``block``
    stratified lengths, each block shuffled, so every run of ``block``
    consecutive requests holds the same multiset."""
    block = mix["block"]
    n = int(mix["requests_per_s_max"] * seconds) + block
    order = rng_for(mix["structure_seed"], 3)
    rng = rng_for(seed, 3)
    items: List[Item] = []
    while len(items) < n:
        prompts, outs = _lengths(mix, block, order)
        keys = _keys(rng, block)
        items += [Item(None, _tokens(rng, p, vocab), int(o), sample_key=k)
                  for p, o, k in zip(prompts, outs, keys)]
    return items


def _sessions(mix, seed, seconds, vocab, rate) -> List[Item]:
    srng = rng_for(mix["structure_seed"], 4)
    # session starts: Poisson at `rate` sessions/s, over the window
    starts, t = [], 0.0
    while True:
        t += srng.exponential(1.0 / rate)
        if t >= seconds:
            break
        starts.append(t)
    starts = [0.0] + starts
    ns = len(starts)
    turns = srng.permutation(quantiles(mix["turns"], ns))
    sys_cfg = mix["system_prompts"]
    ranks = np.arange(1, sys_cfg["count"] + 1, dtype=np.float64)
    zipf = ranks ** -sys_cfg["zipf_s"]
    system_of = srng.choice(sys_cfg["count"], ns, p=zipf / zipf.sum())
    n_turns = int(turns.sum())
    thinks = srng.exponential(mix["think_s"], n_turns)

    users = srng.permutation(quantiles(mix["user"], n_turns)).tolist()
    outs = srng.permutation(quantiles(mix["output"], n_turns)).tolist()
    rng = rng_for(seed, 5)
    systems = [_tokens(rng, sys_cfg["tokens"], vocab)
               for _ in range(sys_cfg["count"])]
    items: List[Item] = []
    k = 0
    for s in range(ns):
        history = list(systems[system_of[s]])
        due = starts[s]
        for turn in range(int(turns[s])):
            u, o = users[k], outs[k]
            think = thinks[k]
            k += 1
            prompt = history + _tokens(rng, u, vocab)
            if due >= seconds or len(prompt) + o > mix["max_total"]:
                break
            items.append(Item(float(due), prompt, int(o), session=s,
                              turn=turn,
                              sample_key=int(rng.integers(0, 2**31 - 1))))
            # the next turn resends this prompt and a recorded reply of
            # the served length (the client replays a conversation; it
            # does not wait on the live answer)
            history = prompt + _tokens(rng, o, vocab)
            due += think + o * mix["reply_s_per_token"]
    items.sort(key=lambda it: it.due)
    return items
