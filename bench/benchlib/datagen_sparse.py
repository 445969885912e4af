"""Sparse text inputs made from ``--seed``: RCV1-shaped topic tasks.

A configuration with ``nnz_per_row`` describes a corpus in the shape of
RCV1-v2 (Lewis et al., JMLR 5, 2004): ``n_total`` documents a task over
a ``p``-term vocabulary, split evenly over the V agents, each agent's
documents shared by all T one-vs-rest topic tasks (the topics label the
same documents).  The parts the source does not fix are drawn
here, and the configuration lists them under ``assumed``:

- terms a document draws: lognormal with mean ``draws_mean`` (solved on
  the host so that the distinct terms a row holds average
  ``nnz_per_row``) and log-sd ``sigma``, at most ``max_row``;
- which terms: Zipf document frequencies, term of rank f drawn with
  weight (f+1)^-``zipf``; a draw that repeats a term of its row is
  dropped (stored as an explicit zero); a random permutation from the
  seed maps rank to feature index, as a vocabulary's ids are not in
  frequency order;
- values: ltc, (1 + log tf) * log(1/df), tf geometric (1 + a run of
  repeats, each with probability ``tf_repeat``), df the term's expected
  share of documents (1 - exp(-draws * weight)); every row
  cosine-normalised;
- labels: planted sparse task vectors, each the unit mix of a shared
  vector and its own at ``relatedness`` (as ``large_n``'s tasks), on
  ``support`` terms drawn among the ``support_terms`` most frequent;
  a row's score is its dot product with its task's vector plus
  Gaussian noise at ``noise`` times the scores' spread, and exactly
  round(rate * n) of a block's rows with the highest scores are
  positive, the rate of each topic from ``label_rates``.

Each block stores ``capacity(cfg)`` entries, a fixed number from the
configuration (``nnz_per_row`` a row plus ``slack``), so every seed and
dataset gives the same shapes; a corpus that would overrun it loses its
last rows' entries.  Padded entries have value 0.  Everything is drawn
on the device in one jitted call; nothing here imports the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import numpy as np

from benchlib import datagen


def capacity(cfg: dict) -> int:
    """Entries stored per (node, task) block: N rows at ``nnz_per_row``,
    plus ``slack``, rounded up to a multiple of 1024."""
    d = cfg["data"]
    need = cfg["N"] * cfg["nnz_per_row"] * (1.0 + d["slack"])
    return int(math.ceil(need / 1024.0)) * 1024


def term_weights(p: int, zipf: float) -> np.ndarray:
    """Draw weight of the term of each frequency rank, summing to 1."""
    w = np.arange(1, p + 1, dtype=np.float64) ** -zipf
    return w / w.sum()


def draws_mean(cfg: dict) -> float:
    """Mean terms drawn a row so that the distinct ones average
    ``nnz_per_row``: a draw repeats an earlier one of its row with
    probability about (c - 1) sum w^2 / 2 on average over c draws."""
    d = cfg["data"]
    s2 = float(np.sum(term_weights(cfg["p"], d["zipf"]) ** 2))
    second = math.exp(d["sigma"] ** 2)                 # E[c^2] / E[c]^2
    target = float(cfg["nnz_per_row"])
    m = target
    for _ in range(50):
        m = target + 0.5 * s2 * (second * m * m - m)
    return m


def _gen(key, n_doc, cdf, idf, perm, sup_pool, *, V, T, N, p, E, mean,
         sigma, max_row, tf_repeat, rates, relatedness, support, noise):
    import jax
    import jax.numpy as jnp

    (k_len, k_col, k_tf, k_base, k_own, k_sb, k_so, k_noise
     ) = jax.random.split(key, 8)
    real = jnp.arange(N)[None] < n_doc[:, None]                     # (V, N)
    mu = jnp.log(mean) - 0.5 * sigma * sigma
    c = jnp.round(jnp.exp(mu + sigma * jax.random.normal(k_len, (V, N))))
    c = (jnp.clip(c, 1, max_row) * real).astype(jnp.int32)
    ends = jnp.cumsum(c, axis=-1)
    slot = jnp.arange(E, dtype=jnp.int32)
    row = jax.vmap(lambda e: jnp.searchsorted(e, slot, side="right"))(ends)
    rank = jnp.clip(jnp.searchsorted(cdf, jax.random.uniform(k_col, (V, E))),
                    0, p - 1)
    col = perm[rank]
    # each row's terms in column order, repeats and unused slots marked
    past = N * p
    kv = jnp.sort(jnp.where(row < N, row * p + col, past), axis=-1)
    used = kv < past
    row = jnp.where(used, kv // p, 0).astype(jnp.int32)
    col = jnp.where(used, kv % p, 0).astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((V, 1), bool),
                             kv[:, 1:] != kv[:, :-1]], -1)
    u = jax.random.uniform(k_tf, (V, E), minval=1e-7, maxval=1.0)
    tf = 1.0 + jnp.floor(jnp.log(u) / jnp.log(tf_repeat))
    val = jnp.where(used & first, (1.0 + jnp.log(tf)) * idf[col], 0.0)
    seg = jax.vmap(lambda v, r: jax.ops.segment_sum(v, r, num_segments=N))
    norm = jnp.sqrt(seg(val * val, row))
    val = val / jnp.where(norm > 0, norm, 1.0)[jnp.arange(V)[:, None], row]

    def unit(v):
        return v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    def planted(k_sup, k_val, shape):
        pick = jax.vmap(lambda k: jax.random.choice(
            k, sup_pool, (support,), replace=False))(
            jax.random.split(k_sup, shape))
        w = jax.random.normal(k_val, (shape, support))
        out = jnp.zeros((shape, p), jnp.float32)
        return unit(out.at[jnp.arange(shape)[:, None], perm[pick]].set(w))

    base = planted(k_sb, k_base, 1)
    own = planted(k_so, k_own, T)
    dirs = unit(relatedness * base + (1.0 - relatedness) * own)      # (T, p)
    score = jax.vmap(lambda v, r, cl: seg(v[None] * dirs[:, cl],
                                          jnp.broadcast_to(r, (T, E))))(
        val, row, col)                                               # (V,T,N)
    realt = jnp.broadcast_to(real[:, None], (V, T, N))
    spread = jnp.std(jnp.where(realt, score, 0.0)) + 1e-12
    score = score + noise * spread * jax.random.normal(k_noise, score.shape)
    order = jnp.argsort(jnp.argsort(jnp.where(realt, -score, jnp.inf),
                                    axis=-1), axis=-1)
    pos = jnp.round(rates[None, :, None] * n_doc[:, None, None])
    y = jnp.where(order < pos, 1.0, -1.0) * realt
    b = lambda x: jnp.broadcast_to(x[:, None], (V, T, E))
    return {"rows": b(row), "cols": b(col), "vals": b(val),
            "y": y.astype(jnp.float32), "mask": realt.astype(jnp.float32)}


@functools.lru_cache(maxsize=None)
def _gen_jit(statics: tuple):
    import jax

    return jax.jit(functools.partial(_gen, **dict(statics)))


@functools.lru_cache(maxsize=8)
def _vocab(p: int, zipf: float, draws: float, seed: int):
    """Rank-to-feature permutation and per-feature idf of a run."""
    w = term_weights(p, zipf)
    df = -np.expm1(-draws * w)                     # expected document share
    perm = datagen.host_rng(seed, salt=7).permutation(p).astype(np.int32)
    idf = np.empty(p, np.float32)
    idf[perm] = np.log(1.0 / df)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return cdf.astype(np.float32), idf, perm


def fit_data(cfg: dict, seed: int, index: int = 0) -> Dict[str, object]:
    """Dataset ``index`` of a run: ``rows``/``cols``/``vals`` (V, T, E)
    (int32, int32, float32; each agent's documents repeated over its T
    tasks), ``y``/``mask`` (V, T, N), on the default device."""
    import jax.numpy as jnp

    d = cfg["data"]
    n = datagen.counts(cfg)
    if np.any(n != n[:, :1]):
        raise ValueError("the topic tasks label the same documents: "
                         "n_total must be equal over the tasks")
    cdf, idf, perm = _vocab(cfg["p"], float(d["zipf"]), draws_mean(cfg),
                            int(seed))
    sup_pool = jnp.arange(int(d["support_terms"]), dtype=jnp.int32)
    statics = (("V", cfg["V"]), ("T", cfg["T"]), ("N", cfg["N"]),
               ("p", cfg["p"]), ("E", capacity(cfg)),
               ("mean", draws_mean(cfg)), ("sigma", float(d["sigma"])),
               ("max_row", int(d["max_row"])),
               ("tf_repeat", float(d["tf_repeat"])),
               ("relatedness", float(d["relatedness"])),
               ("support", int(d["support"])), ("noise", float(d["noise"])))
    rates = jnp.asarray(d["label_rates"], jnp.float32)
    return _gen_jit(statics)(datagen.device_key(seed, salt=100 + index),
                             jnp.asarray(n[:, 0], jnp.int32), cdf, idf, perm,
                             sup_pool, rates=rates)
