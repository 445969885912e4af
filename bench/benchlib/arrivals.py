"""Open-loop request schedules made from ``--seed``.

Every seed gets the same work in another order: the same multiset of
inter-arrival gaps, of rows per request and of key popularities,
shuffled by the seed.  Runs with different seeds then differ only as
two runs of one seed do, and the spread that the bounds are set from
is the system's, not the sampler's.

- gaps: the n stratified quantiles of an exponential at ``rate``
  (a Poisson process's gaps), scaled so that the n arrivals fill
  ``seconds`` exactly, then shuffled;
- rows per request: a Zipf law over ``rows_min..rows_max`` with
  exponent ``rows_zipf``, the counts of each size fixed by largest
  remainder, then shuffled;
- keys ((node, task) pairs): a Zipf law over the V*T pairs with
  exponent ``keys_zipf``; which pair holds which popularity rank is
  drawn from the seed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchlib.datagen import host_rng


def zipf_counts(n: int, support: int, exponent: float) -> np.ndarray:
    """How many of ``n`` draws fall on each of ranks 1..support under a
    Zipf law, fixed by largest remainder (sums to n exactly)."""
    w = np.arange(1, support + 1, dtype=np.float64) ** (-float(exponent))
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    rest = n - int(counts.sum())
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def exponential_gaps(n: int, seconds: float) -> np.ndarray:
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    g = -np.log1p(-u)
    return g * (float(seconds) / g.sum())


def schedule(mix: dict, V: int, T: int, p: int, seed: int,
             seconds: float) -> Dict[str, np.ndarray]:
    """The window's requests: ``due`` (n,) seconds from the window's
    start, ``rows`` (n,) rows each, ``node``/``task`` (n,), ``start``
    (n,) offset of each request's rows in ``X`` (R, p) float32."""
    rng = host_rng(seed, salt=3)
    n = max(int(round(float(mix["rate"]) * float(seconds))), 1)
    gaps = rng.permutation(exponential_gaps(n, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    lo, hi = int(mix["rows_min"]), int(mix["rows_max"])
    sizes = np.repeat(np.arange(lo, hi + 1),
                      zipf_counts(n, hi - lo + 1, mix["rows_zipf"]))
    rows = rng.permutation(sizes).astype(np.int64)

    ranks = np.repeat(np.arange(V * T),
                      zipf_counts(n, V * T, mix["keys_zipf"]))
    pair_of_rank = rng.permutation(V * T)
    keys = pair_of_rank[rng.permutation(ranks)]

    start = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)
    X = rng.standard_normal((int(rows.sum()), p), dtype=np.float32)
    return {"due": due, "rows": rows, "node": keys // T, "task": keys % T,
            "start": start, "X": X}
