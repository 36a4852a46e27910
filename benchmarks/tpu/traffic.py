"""Seeded traffic from a cell's parameters: one generator for every mix.

Every seed gets the same multiset of sizes and gaps; the seed only sets
their order and the token ids.  So each run of a cell carries the same
amount of work, and seeds differ in order alone.  With ``block`` the order
is stratified: every run of ``block`` consecutive requests holds one
value of each of ``block`` quantile bands of each list (prompt lengths,
answer lengths, gaps), so that no seed draws a stretch of only long
prompts or a burst of short gaps that another seed does not.

A mix is the ``traffic`` object of a workload file:

* ``prompt``, ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length distribution, clipped; the set of lengths is its
  quantiles at ``(i + 0.5) / n``;
* ``rate_per_s``: Poisson arrivals at that mean rate, an open loop; the
  gaps are exponential quantiles, scaled so that ``n = round(rate *
  seconds)`` requests fall due inside the window;
* ``block`` (optional, default 1: a plain permutation): the stratum size
  of the order, above.

Training cells take ``batch`` and ``seq``: seeded token rows per step.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def lengths(n: int, spec: dict) -> np.ndarray:
    """The ``n`` clipped lognormal quantile lengths of ``spec``, sorted."""
    nd = NormalDist()
    q = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * q)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def stratified(values: np.ndarray, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """The sorted ``values`` in a seeded order in which each run of
    ``block`` consecutive entries takes one value from each of ``block``
    bands of neighbouring values (the last run may hold fewer)."""
    n = len(values)
    runs = -(-n // block)
    rows = np.full((runs, block), -1)
    for j in range(block):
        band = np.arange(j * runs, min((j + 1) * runs, n))
        rows[rng.permutation(runs)[:len(band)], j] = band
    order = [i for row in rows for i in rng.permutation(row) if i >= 0]
    return values[np.array(order, dtype=np.int64)]


def due_times(n: int, seconds: float, rng: np.random.Generator,
              block: int = 1) -> np.ndarray:
    """Poisson due times of ``n`` requests inside ``[0, seconds)``."""
    gaps = stratified(-np.log1p(-(np.arange(n) + 0.5) / n), block, rng)
    due = np.cumsum(gaps) - gaps[0]
    return due * (seconds / gaps.sum())


def requests(traffic: dict, vocab: int, seed: int,
             seconds: float) -> list[Request]:
    """The cell's requests, in due order."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    block = int(traffic.get("block", 1))
    due = due_times(n, seconds, rng, block)
    plen = stratified(lengths(n, traffic["prompt"]), block, rng)
    olen = stratified(lengths(n, traffic["output"]), block, rng)
    return [Request(float(t), rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o)) for t, p, o in zip(due, plen, olen)]


def token_rows(batch: int, seq: int, vocab: int, seed: int, step: int):
    """One training step's rows: ``batch`` sequences of ``seq + 1`` seeded
    token ids, distinct for every (seed, step, row)."""
    rng = np.random.default_rng([int(seed) % 2 ** 63, int(step)])
    rows = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return rows[:, :-1], rows[:, 1:]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) of ``values`` by the nearest rank:
    the smallest value with at least q% of the samples at or below it."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])
