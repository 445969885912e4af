"""Inputs made from ``--seed``: multi-task SVM data, the network graph,
served weights.

The data follows the logic of the program's MNIST-proxy generator
(``data.synthetic.make_multitask_data``), copied here so that no later
change to the program moves the yardstick: class-conditional Gaussians
in R^p whose class-mean directions are shared up to a per-task
perturbation (``relatedness``), labels balanced per (node, task).  A
configuration with ``n_total`` gives each task that many training
samples in all, split evenly over the nodes as the paper does; the
arrays are padded to ``N`` (the largest per-node count) and ``mask``
marks the real rows.  It
is drawn on the device in one jitted call (``jax.random``), so set-up
pays no host-side sampling or copy of the large-n tensors.

The graph follows ``core.graph.random_graph``: a ring for connectivity
plus random extra edges up to the paper's network degree.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np


def seed_words(seed: int, salt: int = 0) -> np.ndarray:
    """Two uint32 words from any whole-number seed (negative or past 64
    bits included), via numpy's SeedSequence."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0),
                                   int(salt)]).generate_state(2, np.uint32)


def device_key(seed: int, salt: int = 0):
    import jax

    return jax.random.wrap_key_data(
        np.asarray(seed_words(seed, salt), np.uint32), impl="threefry2x32")


def host_rng(seed: int, salt: int = 0) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, salt))


def random_graph(V: int, degree: float, seed: int) -> np.ndarray:
    """Connected (V, V) bool adjacency with network degree ~ ``degree``
    (the paper's |B_v| / (V-1) averaged over nodes)."""
    A = np.zeros((V, V), bool)
    for v in range(V):
        A[v, (v + 1) % V] = A[v, (v - 1) % V] = True
    np.fill_diagonal(A, False)
    rng = host_rng(seed, salt=1)
    target = int(round(degree * V * (V - 1) / 2))
    cand = [(i, j) for i in range(V) for j in range(i + 1, V) if not A[i, j]]
    rng.shuffle(cand)
    need = max(target - int(A.sum()) // 2, 0)
    for i, j in cand[:need]:
        A[i, j] = A[j, i] = True
    return A


def make_graph(cfg: dict, seed: int) -> np.ndarray:
    g = cfg["graph"]
    V = cfg["V"]
    if g["kind"] == "random":
        return random_graph(V, g["degree"], seed)
    if g["kind"] == "full":
        A = np.ones((V, V), bool)
        np.fill_diagonal(A, False)
        return A
    raise ValueError(f"unknown graph kind {g['kind']!r}")


def counts(cfg: dict) -> np.ndarray:
    """Training samples per (node, task): ``n_total[t]`` split evenly
    over the V nodes (the first ``n_total[t] % V`` nodes one more), or
    ``N`` everywhere."""
    V, T, N = cfg["V"], cfg["T"], cfg["N"]
    if "n_total" not in cfg:
        return np.full((V, T), N, np.int64)
    out = np.zeros((V, T), np.int64)
    for t, n in enumerate(cfg["n_total"]):
        out[:, t] = n // V
        out[: n - (n // V) * V, t] += 1
    if out.max() != N or len(cfg["n_total"]) != T:
        raise ValueError(f"n_total {cfg['n_total']} over V={V} nodes does "
                         f"not give T={T} tasks of at most N={N}")
    return out


def _gen(key, n, *, V, T, N, p, n_test, relatedness, noise, margin):
    import jax
    import jax.numpy as jnp

    kb, ki, kl, kx, kt, ktx = jax.random.split(key, 6)
    unit = lambda v: v / jnp.linalg.norm(v, axis=-1, keepdims=True)
    base = unit(jax.random.normal(kb, (p,), jnp.float32))
    indep = unit(jax.random.normal(ki, (T, p), jnp.float32))
    dirs = unit(relatedness * base + (1.0 - relatedness) * indep)   # (T, p)

    def labels(k, n, width):
        # exactly round(n/2) positives among the first n of each row, in
        # a random order; 0 past n (padding)
        real = jnp.arange(width) < n[..., None]
        u = jnp.where(real, jax.random.uniform(k, n.shape + (width,)), 2.0)
        rank = jnp.argsort(jnp.argsort(u, axis=-1), axis=-1)
        pos = jnp.round(0.5 * n)[..., None]
        return (jnp.where(rank < pos, 1.0, -1.0) * real).astype(jnp.float32)

    y = labels(kl, n, N)
    mask = (y != 0).astype(jnp.float32)
    X = (y[..., None] * margin * dirs[None, :, None, :]
         + noise * jax.random.normal(kx, (V, T, N, p), jnp.float32)
         ) * mask[..., None]
    y_test = labels(kt, jnp.full((T,), n_test), n_test)
    X_test = (y_test[..., None] * margin * dirs[:, None, :]
              + noise * jax.random.normal(ktx, (T, n_test, p), jnp.float32))
    return {"X": X, "y": y, "mask": mask, "X_test": X_test, "y_test": y_test}


@functools.lru_cache(maxsize=None)
def _gen_jit(shapes: tuple):
    import jax

    return jax.jit(functools.partial(_gen, **dict(shapes)))


def fit_data(cfg: dict, seed: int, index: int = 0) -> Dict[str, object]:
    """Dataset ``index`` of a run: X (V,T,N,p), y/mask (V,T,N), X_test
    (T,n,p), y_test (T,n), float32 on the default device; padded rows are
    0 in X and y and masked out."""
    d = cfg["data"]
    shapes = (("V", cfg["V"]), ("T", cfg["T"]), ("N", cfg["N"]),
              ("p", cfg["p"]), ("n_test", d["n_test"]),
              ("relatedness", float(d["relatedness"])),
              ("noise", float(d["noise"])), ("margin", float(d["margin"])))
    return _gen_jit(shapes)(device_key(seed, salt=100 + index),
                            counts(cfg).astype(np.int32))


def serve_weights(cfg: dict, seed: int):
    """Hyperplanes (V, T, p) and biases (V, T) of a served network, as a
    fitted model's would be scaled: w ~ N(0, 1/p) per coordinate."""
    rng = host_rng(seed, salt=2)
    V, T, p = cfg["V"], cfg["T"], cfg["p"]
    W = (rng.standard_normal((V, T, p)) / np.sqrt(p)).astype(np.float32)
    b = (0.1 * rng.standard_normal((V, T))).astype(np.float32)
    return W, b
