"""Find a cell's parts by name.

Every part of a cell is found by name under ``bench/``:

- ``workloads/<cell>.json``    the cell: its configuration, traffic mix,
                               chips and ``why``, the limits that decide
                               ``correct``, the traced fits;
- ``configs/<config>.json``    the deployment's sizes and settings;
- ``traffic/<traffic>.json``   the traffic mix: parameters, and ``kind``;
- ``traffic/<kind>.py``        the general generator of that kind;
- ``metrics/<metric>.py``      one per-layer metric's reader, ``read(r)``.

``BENCHMARK.json`` at the checkout root lists the cells that are
measured, and defines the metrics: which cells report each.  Adding a
configuration, a cell, a traffic kind or a metric is adding files (and
its entries in ``BENCHMARK.json``): nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class SpecError(ValueError):
    """A name that has no file, or a file that does not fit the layout."""


def _check_name(name: str) -> str:
    if not NAME_RE.match(name or ""):
        raise SpecError(f"not a valid name: {name!r}")
    return name


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, modname: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """The benchmark as laid out under one checkout root."""

    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.benchmark = _load_json(os.path.join(root, "BENCHMARK.json"))

    # -- cells -------------------------------------------------------------
    def cell(self, name: str) -> dict:
        """A cell's own file: its configuration, traffic mix, chips and
        ``why`` (what ``BENCHMARK.json`` lists for it), the limits that
        decide ``correct``, and the traced fits."""
        path = os.path.join(self.bench_dir, "workloads",
                            _check_name(name) + ".json")
        if not os.path.isfile(path):
            raise SpecError(f"no workload named {name!r} (no file "
                            f"{os.path.relpath(path, self.root)})")
        return dict(_load_json(path), name=name)

    def cells(self) -> List[str]:
        """The cells ``BENCHMARK.json`` lists: those measured."""
        return [w["name"] for w in self.benchmark["workloads"]]

    def cell_files(self) -> List[str]:
        """Every cell that has a file, listed or not."""
        d = os.path.join(self.bench_dir, "workloads")
        return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))

    # -- metrics (BENCHMARK.json) ---------------------------------------------

    def _metrics_for(self, key: str, cell: str) -> List[dict]:
        return [m for m in self.benchmark[key]
                if "workloads" not in m or cell in m["workloads"]]

    def end_to_end(self, cell: str) -> List[dict]:
        return self._metrics_for("end_to_end", cell)

    def per_layer(self, cell: str) -> List[dict]:
        return self._metrics_for("per_layer", cell)

    # -- files under bench/ ----------------------------------------------
    def config(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "configs",
                                       _check_name(name) + ".json"))

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "traffic",
                                       _check_name(name) + ".json"))

    def traffic_kind(self, kind: str):
        """The generator module of a traffic kind."""
        path = os.path.join(self.bench_dir, "traffic",
                            _check_name(kind) + ".py")
        return _load_module(path, "bench_traffic_" + kind.replace(".", "_")
                            .replace("-", "_"))

    def reader(self, metric: str) -> Callable:
        """``read(r)`` of a per-layer metric: a number, or None where
        the run holds nothing to read."""
        return reader_of(metric, self.bench_dir)


def reader_of(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(r)`` of the metric whose reader is ``metrics/<metric>.py``
    (a reader that reads what another one does, for other cells, takes
    it from here)."""
    path = os.path.join(bench_dir, "metrics", _check_name(metric) + ".py")
    mod = _load_module(path, "bench_metric_" + metric.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def load_all(spec: Spec, cell: str) -> Dict[str, object]:
    """Every part of one cell, found by name: fails on the first that
    is missing."""
    entry = spec.cell(cell)
    mix = spec.traffic(entry["traffic"])
    return {
        "workload": entry,
        "config": spec.config(entry["config"]),
        "mix": mix,
        "kind": spec.traffic_kind(mix["kind"]),
        "readers": {m["name"]: spec.reader(m["name"])
                    for m in spec.per_layer(cell)},
    }
