"""Traffic generator: one general reader of the mixes in ``bench/traffic``.

A mix is served in *groups*: each group holds as many requests as the
engine takes into one super-bucket (``max_batch + stage_depth``, the
lane reservation).  Every group carries the same multiset of sizes --
the stratified quantiles of the mix's length distributions, a *deck*
-- and an open loop's arrival gaps are the stratified quantiles of an
exponential.  The order in which a group's deck is dealt, and the
order of the gaps, are drawn once per group index from streams that do
not depend on the seed: the order decides how long a super-bucket
runs (its longest request may start first or last), so a seed that
changed it would change the work.  The seed draws the prompt tokens
(and, in ``bench/weights.py``, the weights): every seed offers the same
work, the same shapes and the same schedule with other numbers.  In a
backlog every ``run`` takes one whole group, so the engine meets the
one set of shapes that set-up warms; an open loop's calls take what is
due, so their shapes follow the arrivals.

Mix keys:
  loop       the file in ``bench/loops`` that drives the window:
             "backlog" (the next group as soon as the engine is free)
             or "open" (arrivals at ``rate_rps``, whatever is due
             submitted before each ``run``)
  rate_rps   open loop: mean arrival rate, requests per second
  prompt     {"dist": "lognormal", "median", "sigma", "min", "max"}
             or {"dist": "uniform", "min", "max"}; tokens
  output     the same, for the number of tokens generated
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Req:
    index: int              # position in the run's request sequence
    due_s: float            # arrival, seconds after the window opens
    prompt: np.ndarray      # (len,) int32 token ids
    max_new: int            # tokens to generate (no EOS: all of them)


def quantile(dist: dict, p: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(p))
    elif kind == "uniform":
        v = dist["min"] + p * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(v, dist["min"]), dist["max"])


def deck(dist: dict, n: int, div: int = 1, floor: int = 2) -> np.ndarray:
    """The ``n`` stratified quantiles of ``dist`` (at (i + 1/2) / n),
    rounded, each divided by ``div`` (the CPU rehearsal's cut)."""
    vals = [round(quantile(dist, (i + 0.5) / n)) for i in range(n)]
    return np.maximum(np.asarray(vals, np.int64) // div, floor)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**63 - 1), *stream])


def open_groups(traffic: dict, group: int, seconds: float) -> int:
    """Groups an open loop offers in ``seconds`` (at least one)."""
    return max(1, round(traffic["rate_rps"] * seconds / group))


def arrivals(traffic: dict, n: int) -> np.ndarray:
    """Due times (s) of ``n`` open-loop requests: the first at 0, the
    gaps the stratified quantiles of an exponential at ``rate_rps`` in
    a fixed shuffled order."""
    rate = traffic["rate_rps"]
    gaps = np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                       for i in range(n)])
    gaps = np.random.default_rng([1, n]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def make_group(traffic: dict, k: int, group: int, seed: int, vocab: int,
               due: np.ndarray | None = None, div: int = 1) -> list[Req]:
    """Group ``k`` of a run: the decks dealt in group ``k``'s fixed
    order, prompt tokens drawn from the seed, uniform over ``[1, vocab)``."""
    order = np.random.default_rng([2, k])
    plens = order.permutation(deck(traffic["prompt"], group, div))
    outs = order.permutation(deck(traffic["output"], group, div))
    rng = _rng(seed, 2, k)
    reqs = []
    for j in range(group):
        i = k * group + j
        toks = rng.integers(1, vocab, int(plens[j]), dtype=np.int64)
        reqs.append(Req(i, float(due[i]) if due is not None else 0.0,
                        toks.astype(np.int32), int(outs[j])))
    return reqs
