"""The sparse-text cell ``rcv1_topics.fit``: its data, its work counts,
its reference and the comparison that decides ``correct``, run on the
CPU at small sizes (the harness's look for a chip is skipped)."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import compare, datagen, datagen_sparse  # noqa: E402
from benchlib import reference, reference_sparse, runner  # noqa: E402
from benchlib import spec as spec_lib, workcount_sparse  # noqa: E402

CELL = "rcv1_topics.fit"
SEED = 2 ** 40 + 161


def _cfg(**over):
    with open(os.path.join(BENCH, "configs", "rcv1_topics.json")) as fh:
        cfg = json.load(fh)
    data = dict(cfg["data"], **over.pop("data", {}))
    return dict(cfg, data=data, **over)


#: the cell cut to fit a CPU: 3 agents, 2 topics, 40 rows, 300 terms
SMALL = dict(V=3, T=2, n_total=[118, 118], N=40, p=300, nnz_per_row=6,
             iters=3, qp_iters=30,
             data=dict(support=30, support_terms=150,
                       label_rates=[0.47, 0.15]))


def _small():
    over = dict(SMALL)
    over["data"] = dict(_cfg()["data"], **SMALL["data"])
    return over


def _kind():
    return spec_lib.load_all(spec_lib.Spec(ROOT, BENCH), CELL)["kind"]


def _limit():
    return spec_lib.Spec(ROOT, BENCH).cell(CELL)["limits"]["state_rel_l2"]


def _run(**kw):
    spec = spec_lib.Spec(ROOT, BENCH)
    return runner.run_cell(spec, CELL, seed=SEED, seconds=0.5, trace=False,
                           t_start=time.perf_counter(), overrides=_small(),
                           **kw)


def test_cell_finds_its_parts_and_matches_its_listing():
    spec = spec_lib.Spec(ROOT, BENCH)
    parts = spec_lib.load_all(spec, CELL)
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(parts["kind"], fn))
    assert set(parts["workload"]["limits"]) == {"state_rel_l2"}
    assert parts["workload"]["limits"]["state_rel_l2"] > 0
    assert set(parts["readers"]) == {m["name"] for m in spec.per_layer(CELL)}
    assert [m["name"] for m in spec.end_to_end(CELL)] == ["fit_s.large",
                                                          "setup_s"]
    own = spec.cell(CELL)
    listed = [w for w in spec.benchmark["workloads"] if w["name"] == CELL]
    assert listed and listed[0] == {k: own[k] for k in listed[0]}


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s.large", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.engine import plan
    monkeypatch.setattr(plan, "plan_step", lambda prob, inv, state, **k:
                        state)


def _products_dropped(monkeypatch):
    """The sparse Z^T lam left out: the primal update sees no data."""
    from repro.core import sparse
    real = sparse.zt_dot
    monkeypatch.setattr(sparse, "zt_dot",
                        lambda Z, lam: 0.0 * real(Z, lam))


def _one_panel(monkeypatch):
    """The K build stops after its first feature panel."""
    from repro.core import sparse
    monkeypatch.setattr(sparse, "PANEL", 128)
    real = sparse._gram

    def first(Z, a, *, panel):
        x = Z.x
        keep = x.cols < panel
        cut = sparse.SparseRows(x.rows, x.cols, x.vals * keep, x.N, x.p)
        return real(sparse.SparseZ(cut, Z.s), a, panel=panel)
    monkeypatch.setattr(sparse, "_gram", first)
    sparse._gram_jit.clear_cache()


def _half_rows(monkeypatch):
    """Half of each block's rows left out of Z: the fit sees half its
    documents."""
    import jax.numpy as jnp

    from repro.core import sparse
    real = sparse.compute_z

    def half(X, y, mask):
        Z = real(X, y, mask)
        keep = Z.x.rows < X.N // 2
        x = sparse.SparseRows(Z.x.rows, Z.x.cols, Z.x.vals * keep, X.N, X.p)
        return sparse.SparseZ(x, Z.s * (jnp.arange(X.N) < X.N // 2))
    monkeypatch.setattr(sparse, "compute_z", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _products_dropped,
                                   _one_panel, _half_rows])
def test_broken_fit_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]


def test_control_run_is_not_correct():
    out = _run(control=True)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]


def test_traced_run_reads_the_program_counters(tmp_path):
    spec = spec_lib.Spec(ROOT, BENCH)
    out = runner.run_cell(spec, CELL, seed=SEED, seconds=600, trace=True,
                          t_start=time.perf_counter(), overrides=_small(),
                          trace_dir=str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["fit.lowerings_per_fit.rcv1"] == 0.0
    # one 384-term panel of 3 x 2 blocks of 40 rows, float32, a fit
    assert m["build.densified_mb_per_fit"] == 4 * 6 * 40 * 384 / 1e6
    assert out["notes"]["split_fits"] == 2 and out["notes"]["fits"] == 4
    # no TPU plane on the CPU: the device readers find nothing
    assert "sparse_build_roofline" not in m
    assert "fit.device_idle_pct.rcv1" not in m


def test_reference_agrees_with_the_program_and_not_its_control():
    """The plain reference against the program's fit of the same data,
    and against the dense reference of the densified data; the control
    (three bfloat16 passes) lands outside the cell's limit."""
    from repro.api import DTSVM, SolverConfig
    from repro.core.sparse import SparseRows

    cfg = dict(_cfg(), **_small())
    d = datagen_sparse.fit_data(cfg, SEED, 0)
    A = datagen.make_graph(cfg, SEED)
    X = SparseRows(d["rows"], d["cols"], d["vals"], cfg["N"], cfg["p"])
    sc = SolverConfig(C=cfg["solver"]["C"], iters=cfg["iters"],
                      qp_iters=cfg["qp_iters"])
    st = DTSVM(sc).fit(X, d["y"], mask=d["mask"], adj=A).state_
    prog = {k: np.asarray(getattr(st, k)) for k in compare.STATE_LEAVES}
    args = (d["rows"], d["cols"], d["vals"], d["y"], d["mask"], A)
    ref = reference_sparse.dtsvm_fit(cfg, *args)
    ctl = reference_sparse.dtsvm_fit(cfg, *args, precision="high")
    dense = reference.dtsvm_fit(cfg, X.todense(), d["y"], d["mask"], A)
    gap = _kind().state_rel_l2
    assert gap(prog, ref) < _limit() / 2
    assert gap(dense, ref) < _limit() / 2
    assert gap(ctl, ref) > _limit()


def test_control_fails_the_limit_at_the_published_width():
    """Every term of the vocabulary, all four agents and topics, 256
    documents an agent: the control reads above the limit."""
    cfg = _cfg(n_total=[1024] * 4, N=256)
    d = datagen_sparse.fit_data(cfg, SEED, 1)
    A = datagen.make_graph(cfg, SEED)
    args = (d["rows"], d["cols"], d["vals"], d["y"], d["mask"], A)
    ref = reference_sparse.dtsvm_fit(cfg, *args)
    ctl = reference_sparse.dtsvm_fit(cfg, *args, precision="high")
    assert _kind().state_rel_l2(ctl, ref) > _limit()


def test_state_rel_l2_by_hand():
    """The worst leaf's l2 gap over the reference's l2 norm; a leaf of
    another shape or not finite reads infinite."""
    ref = {"r": np.array([3.0, 4.0]), "alpha": np.array([1.0]),
           "beta": np.array([0.0, 2.0]), "lam": np.array([1.0, 0.0])}
    got = {"r": np.array([3.0, 4.5]), "alpha": np.array([1.0]),
           "beta": np.array([0.0, 2.0]), "lam": np.array([1.0, 0.0])}
    gap = _kind().state_rel_l2
    assert gap(ref, ref) == 0.0
    assert gap(got, ref) == pytest.approx(0.1)          # 0.5 / 5
    got["lam"] = np.array([1.0, 0.2])                   # 0.2 / 1 is worst
    assert gap(got, ref) == pytest.approx(0.2)
    assert gap(dict(got, beta=np.array([0.0, np.nan])), ref) == np.inf
    assert gap(dict(got, alpha=np.zeros(2)), ref) == np.inf


def test_datagen_is_seeded_and_hits_its_shape():
    """At the published vocabulary: the same seed gives the same corpus,
    another seed another; rows average 74 distinct terms and are unit
    vectors; each topic's positive share is its rate."""
    cfg = _cfg(V=2, T=4, n_total=[4000] * 4, N=2000)
    a = datagen_sparse.fit_data(cfg, SEED, 0)
    b = datagen_sparse.fit_data(cfg, SEED, 0)
    c = datagen_sparse.fit_data(cfg, SEED + 1, 0)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["vals"]), np.asarray(c["vals"]))
    rows, cols = np.asarray(a["rows"]), np.asarray(a["cols"])
    vals, y = np.asarray(a["vals"]), np.asarray(a["y"])
    mask = np.asarray(a["mask"])
    E = datagen_sparse.capacity(cfg)
    assert vals.shape == (2, 4, E) and E % 1024 == 0
    assert E >= cfg["N"] * cfg["nnz_per_row"]
    # the topics label the same documents
    for t in range(1, 4):
        np.testing.assert_array_equal(vals[:, t], vals[:, 0])
    nz = vals != 0
    per_row = nz[:, 0].sum() / mask[:, 0].sum()
    assert abs(per_row - 74) < 0.01 * 74
    for v in range(2):
        norms = np.bincount(rows[v, 0], weights=vals[v, 0].astype(
            np.float64) ** 2, minlength=cfg["N"])
        np.testing.assert_allclose(norms, 1.0, atol=1e-5)
        pairs = rows[v, 0][nz[v, 0]] * cfg["p"] + cols[v, 0][nz[v, 0]]
        assert len(np.unique(pairs)) == len(pairs)      # no repeats
    assert cols.min() >= 0 and cols.max() < cfg["p"]
    for t, rate in enumerate(cfg["data"]["label_rates"]):
        pos = (y[:, t] > 0).sum(-1)
        np.testing.assert_array_equal(pos, np.round(rate * 2000))
    assert np.all((y == 0) == (mask == 0))


def test_workcount_by_hand():
    """Two blocks of a 3-row, 4-column X: block 0 holds rows {0: cols
    0, 2}, {1: cols 0, 3}, {2: col 0} and a repeated (1, 3) entry and a
    stored zero; block 1 one entry.  Columns' row counts: block 0
    (3, 0, 1, 1), block 1 (0, 1, 0, 0); the bias column counts the
    real rows, 3 and 2."""
    rows = np.array([[[0, 0, 1, 1, 2, 1, 2]], [[0, 0, 0, 0, 0, 0, 0]]])
    cols = np.array([[[0, 2, 0, 3, 0, 3, 1]], [[1, 0, 0, 0, 0, 0, 0]]])
    vals = np.array([[[1., 2., 3., 4., 5., 6., 0.]],
                     [[7., 0., 0., 0., 0., 0., 0.]]], np.float32)
    mask = np.array([[[1, 1, 1]], [[1, 1, 0]]], np.float32)
    rows, cols, vals = (x.reshape(2, 1, 7) for x in (rows, cols, vals))
    c = workcount_sparse.column_counts(rows, cols, vals, 4)
    np.testing.assert_array_equal(c[:, 0], [[3, 0, 1, 1], [0, 1, 0, 0]])
    flops = workcount_sparse.build_flops(rows, cols, vals, mask, 4)
    assert flops == 2 * ((9 + 1 + 1) + 1 + (9 + 4))
    # 7 nonzero values, 8 bytes each, and 4 bytes for each of 6 rows
    assert workcount_sparse.admm_bytes(vals, mask, 80) == 80 * (7 * 8 + 6 * 4)
