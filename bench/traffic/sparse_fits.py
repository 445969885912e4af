"""Traffic kind ``sparse_fits``: whole DTSVM fits on sparse text
features, back to back, closed loop.

This is the ``fits`` kind (``traffic/fits.py``: its mix, window and
traced alternation of whole and split fits), run on inputs
made by ``benchlib.datagen_sparse``: X reaches the program as its
nonzeros (``repro.core.sparse.SparseRows``), and the plain reference
and its control are ``benchlib.reference_sparse``.  Over the window's
whole fits it also adds up the bytes of Z the program's build writes
dense (its ``build.densified_bytes`` counter, ``repro.obs.counters``;
nothing where the program has no such counter), and it names the
datasets the split fits ran on, for the work counts of the rooflines
(``benchlib.workcount_sparse``).

Every fit of the window is compared with the plain reference fit of its
dataset by ``state_rel_l2``: the worst leaf of (r, alpha, beta, lam) as
the l2 norm of the gap over the reference's.  Not ``fits``'s max abs
gap: at 40 FISTA steps float32 rounding is amplified into a few state
entries, and the largest of those grows with N until it reaches the
control's (``PERF.md`` section 4).
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

from benchlib import compare, datagen, datagen_sparse, reference_sparse


def _load_fits():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fits.py")
    spec = importlib.util.spec_from_file_location("bench_traffic_fits_base",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: a module of its own: the changes below reach no other kind's ``fits``
fits = _load_fits()
release = fits.release
_whole_or_split = fits._fit


def _densified():
    """The program's ``build.densified_bytes`` total, or None."""
    try:
        from repro.obs import counters
    except ImportError:
        return None
    return counters.read_counters().get("build.densified_bytes", 0)


def _fit(run, m: int, split: bool):
    """``fits._fit``, adding a whole fit's densified bytes to the tally
    the window reads."""
    before = _densified()
    st = _whole_or_split(run, m, split)
    tally = run.state.get("densified")
    if not split and tally is not None:
        run.state["densified"] = (None if before is None
                                  else tally + _densified() - before)
    return st


def _reference(run, m: int, precision: str):
    import jax

    d = run.state["data"][m]
    dev = run.devices[0]
    args = [jax.device_put(d[k], dev)
            for k in ("rows", "cols", "vals", "y", "mask")]
    adj = jax.device_put(run.state["adj"], dev)
    return reference_sparse.dtsvm_fit(run.config, *args, adj,
                                      precision=precision)


fits._fit = _fit
fits._reference = _reference


def setup(run) -> None:
    from repro.core.sparse import SparseRows

    cfg, mix = run.config, run.mix
    run.state["solver_config"] = fits._solver_config(run)
    run.state["adj"] = datagen.make_graph(cfg, run.seed)
    data = []
    for m in range(int(mix["datasets"])):
        d = datagen_sparse.fit_data(cfg, run.seed, m)
        d["X"] = SparseRows(d["rows"], d["cols"], d["vals"], cfg["N"],
                            cfg["p"])
        data.append(d)
    run.state["data"] = data
    for i in range(int(mix["warm_fits"])):
        _fit(run, i % len(data), False)
    if fits._splits(run):
        _fit(run, 0, True)


def window(run, seconds: float) -> dict:
    run.state["densified"] = None if _densified() is None else 0
    res = fits.window(run, seconds)
    res["whole"]["densified_bytes"] = run.state["densified"]
    n_data = len(run.state["data"])
    # fits.window runs fit i on dataset i mod n_data, split where i is odd
    res["split_datasets"] = ([i % n_data for i in range(1, res["fits"], 2)]
                             if fits._splits(run) else [])
    return res


def state_rel_l2(got, ref) -> float:
    """Worst leaf of a fit's state (r, alpha, beta, lam) against the
    reference's, each as ||got - ref||_2 / ||ref||_2."""
    worst = 0.0
    for k in compare.STATE_LEAVES:
        g = np.asarray(got[k], np.float64)
        r = np.asarray(ref[k], np.float64)
        if g.shape != r.shape or not np.isfinite(g).all():
            return float("inf")
        scale = float(np.linalg.norm(r))
        gap = float(np.linalg.norm(g - r))
        if scale == 0.0:
            worst = max(worst, 0.0 if gap == 0.0 else float("inf"))
        else:
            worst = max(worst, gap / scale)
    return worst


def _worst(pairs) -> dict:
    return {"state_rel_l2": max((state_rel_l2(st, ref) for st, ref in pairs),
                                default=float("inf"))}


def _refs(run, datasets) -> dict:
    refs = run.state.get("refs") or {}
    for m in sorted(set(datasets) - set(refs)):
        refs[m] = _reference(run, m, "highest")
    run.state["refs"] = refs
    return refs


def check(run) -> dict:
    done = run.state["fits"]
    refs = _refs(run, [m for m, _ in done])
    return _worst((st, refs[m]) for m, st in done)


def control(run) -> dict:
    refs = run.state.get("refs") or _refs(run,
                                          range(len(run.state["data"])))
    return _worst((_reference(run, m, "high"), ref)
                  for m, ref in refs.items())
