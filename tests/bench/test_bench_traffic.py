"""The generators repeat exactly for a seed, and every seed gets the same
work in another order."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import arrivals, datagen  # noqa: E402

BIG_SEED = 2 ** 40 + 12345          # past 32 signed bits


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as fh:
        return json.load(fh)


def _small(name):
    cfg = {k: v for k, v in _load("configs", name).items() if k != "n_total"}
    return dict(cfg, V=3, T=2, N=48, p=5, data=dict(cfg["data"], n_test=40))


@pytest.mark.parametrize("name", ["paper", "large_n"])
def test_fit_inputs_repeat_for_a_seed(name):
    cfg = _small(name)
    a = datagen.fit_data(cfg, BIG_SEED, 0)
    b = datagen.fit_data(cfg, BIG_SEED, 0)
    other = datagen.fit_data(cfg, BIG_SEED, 1)
    for k in ("X", "y", "mask", "X_test", "y_test"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    assert not np.array_equal(np.asarray(a["X"]), np.asarray(other["X"]))
    assert np.array_equal(datagen.make_graph(cfg, BIG_SEED),
                          datagen.make_graph(cfg, BIG_SEED))


def test_fit_inputs_have_the_stated_shapes_and_balance():
    cfg = _small("paper")
    d = datagen.fit_data(cfg, 3, 0)
    assert d["X"].shape == (3, 2, 48, 5) and d["X"].dtype == np.float32
    y = np.asarray(d["y"])
    assert set(np.unique(y)) == {-1.0, 1.0}
    assert (y.sum(-1) == 0).all()                 # balanced per (v, t)
    assert np.asarray(d["mask"]).min() == 1.0
    A = datagen.make_graph(dict(cfg, V=30, graph={"kind": "random",
                                                  "degree": 0.8}), 5)
    assert (A == A.T).all() and not A.diagonal().any()
    # a ring is in every graph, so each node has two neighbours at least
    assert A.sum(1).min() >= 2
    assert abs(A.sum() / (30 * 29) - 0.8) < 0.01


def test_paper_inputs_split_each_task_total_over_the_nodes():
    """The paper's Fig. 2 network: 200 target and 800 source samples split
    evenly over 20 nodes, padded to N=40, balanced and masked."""
    cfg = _load("configs", "paper")
    d = datagen.fit_data(cfg, BIG_SEED, 0)
    mask = np.asarray(d["mask"])
    y = np.asarray(d["y"])
    X = np.asarray(d["X"])
    assert X.shape == (20, 2, 40, 10)
    assert (mask.sum(-1) == [[10, 40]] * 20).all()
    assert mask.sum(axis=(0, 2)).tolist() == [200, 800]
    assert (np.abs(y) == mask).all() and (y.sum(-1) == 0).all()
    assert (X[mask == 0] == 0).all()
    A = datagen.make_graph(cfg, BIG_SEED)
    assert A.sum() // 2 == round(0.6368 * 20 * 19 / 2)
    with pytest.raises(ValueError):
        datagen.counts(dict(cfg, N=30))


def test_seeds_of_any_size_are_accepted():
    words = {tuple(datagen.seed_words(s)) for s in
             (0, 1, -1, 2 ** 31, 2 ** 63 + 5, BIG_SEED)}
    assert len(words) == 6


def test_arrivals_repeat_for_a_seed_and_keep_their_work_across_seeds():
    mix = _load("traffic", "poisson_zipf")
    a = arrivals.schedule(mix, 20, 2, 10, BIG_SEED, 3.0)
    b = arrivals.schedule(mix, 20, 2, 10, BIG_SEED, 3.0)
    c = arrivals.schedule(mix, 20, 2, 10, BIG_SEED + 1, 3.0)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    n = int(round(mix["rate"] * 3.0))
    assert len(a["due"]) == n and (np.diff(a["due"]) > 0).all()
    assert a["due"][0] == 0.0 and a["due"][-1] < 3.0
    # another seed: the same sizes, gaps and popularities, another order
    assert not np.array_equal(a["rows"], c["rows"])
    assert np.array_equal(np.sort(a["rows"]), np.sort(c["rows"]))
    assert np.allclose(np.sort(np.diff(np.r_[a["due"], 3.0])),
                       np.sort(np.diff(np.r_[c["due"], 3.0])))
    pops = lambda s: np.sort(np.bincount(s["node"] * 2 + s["task"],
                                         minlength=40))
    assert np.array_equal(pops(a), pops(c))
    assert a["rows"].min() >= mix["rows_min"]
    assert a["rows"].max() <= mix["rows_max"]
    assert a["X"].shape == (int(a["rows"].sum()), 10)
    assert np.array_equal(a["start"][1:], np.cumsum(a["rows"])[:-1])


def test_zipf_counts_follow_the_law():
    c = arrivals.zipf_counts(10000, 16, 1.5)
    assert c.sum() == 10000 and (np.diff(c) <= 0).all()
    assert abs(c[0] / c[3] - 4 ** 1.5) < 0.05 * 4 ** 1.5
