"""BENCHMARK.json keeps to its contract, and the harness finds every part
of a cell by name, so that a new one is added with files alone."""
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import runner, spec as spec_lib  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return spec_lib.Spec(ROOT, BENCH).benchmark


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_paths(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bm["paths"]) <= 16
    for p in bm["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(bm["command"]) <= 32 and all(map(_line, bm["command"]))
    for word in bm["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in bm["paths"])
            assert os.path.isfile(os.path.join(ROOT, word))
    s = bm["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    # a full check of 24 cells fits its 12 hours
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_cells_and_metrics(bm):
    names = [c["name"] for c in bm["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and \
            _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bm["paths"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and k in cfg
            assert not re.search(r"(_dim|_rank)$|^p$|hidden|width", k)
    cells = bm["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
    used = {w["config"] for w in cells}
    assert used == set(names)

    metrics = bm["end_to_end"] + bm["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        reporting = [w["name"] for w in cells
                     if "workloads" not in e2e[m["moves"]]
                     or w["name"] in e2e[m["moves"]]["workloads"]]
        assert set(m.get("workloads", reporting)) <= set(reporting)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in cells}
    for w in cells:    # every cell: setup_s, another e2e, a per-layer
        rep = [m for m in metrics if "workloads" not in m
               or w["name"] in m["workloads"]]
        assert any(m["name"] != "setup_s" for m in rep
                   if m in bm["end_to_end"])
        assert any(m in bm["per_layer"] for m in rep)
    assert len(json.dumps(bm)) <= 64 * 1024


def test_every_file_under_paths_is_named_from_name_characters(bm):
    for p in bm["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel


CELLS = ["paper.fit", "large_n.fit", "paper.serve", "large_n_x4.fit"]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_finds_its_parts(cell):
    spec = spec_lib.Spec(ROOT, BENCH)
    parts = spec_lib.load_all(spec, cell)
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(parts["kind"], fn))
    assert parts["workload"]["limits"]
    assert set(parts["readers"]) == {m["name"] for m in spec.per_layer(cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_file_matches_its_listing(cell):
    """A cell's own file holds what BENCHMARK.json lists for it; a cell
    that is not listed (not yet measured) still runs by its file."""
    spec = spec_lib.Spec(ROOT, BENCH)
    assert cell in spec.cell_files()
    own = spec.cell(cell)
    assert own["chips"] in (1, 4) and _line(own["why"])
    spec.config(own["config"])
    spec.traffic(own["traffic"])
    for w in spec.benchmark["workloads"]:
        if w["name"] == cell:
            assert w == {k: own[k] for k in w}


def test_new_parts_are_found_by_adding_files(tmp_path):
    """A new configuration, traffic kind, traffic mix, cell and per-layer
    metric, added as files plus entries in BENCHMARK.json: the harness
    finds all of them with no other edit."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (root / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "V": 2}))
    (root / "bench/traffic/echo.py").write_text(
        "def setup(run): pass\ndef window(run, s): return {}\n"
        "def release(run): pass\ndef check(run): return {}\n"
        "def control(run): return {}\nKIND = 'echo'\n")
    (root / "bench/traffic/echo_mix.json").write_text(
        json.dumps({"kind": "echo", "rate": 1}))
    (root / "bench/workloads/tiny.echo.json").write_text(json.dumps(
        {"config": "tiny", "traffic": "echo_mix", "chips": 1, "why": "w",
         "limits": {"x": 0}}))
    (root / "bench/metrics/echo.count.py").write_text(
        "def read(r):\n    return 7\n")
    bm["configs"].append({"name": "tiny", "source": "s",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "w"})
    bm["workloads"].append({"name": "tiny.echo", "config": "tiny",
                            "traffic": "echo_mix", "chips": 1, "why": "w"})
    bm["end_to_end"].append({"name": "echo_s", "unit": "s",
                             "better": "lower", "bound": 0.05,
                             "source": "host_clock",
                             "workloads": ["tiny.echo"]})
    bm["per_layer"].append({"name": "echo.count", "unit": "count",
                            "better": "lower", "source": "program_counter",
                            "layer": "echo", "moves": "echo_s",
                            "workloads": ["tiny.echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    spec = spec_lib.Spec(str(root), str(root / "bench"))
    parts = spec_lib.load_all(spec, "tiny.echo")
    assert parts["config"]["V"] == 2
    assert parts["kind"].KIND == "echo"
    assert parts["workload"]["limits"] == {"x": 0}
    assert "tiny.echo" in spec.cell_files()
    assert [m["name"] for m in spec.end_to_end("tiny.echo")] == \
        ["setup_s", "echo_s"]
    assert parts["readers"]["echo.count"](None) == 7
    # the cells already there still find theirs, and do not see the new one
    assert "echo.count" not in spec_lib.load_all(spec,
                                                 "paper.fit")["readers"]


def test_missing_parts_are_errors(tmp_path):
    spec = spec_lib.Spec(ROOT, BENCH)
    with pytest.raises(spec_lib.SpecError):
        spec.cell("no.such.cell")
    with pytest.raises(spec_lib.SpecError):
        spec.config("no_such_config")
    with pytest.raises(spec_lib.SpecError):
        spec.reader("no.such.metric")
    with pytest.raises(spec_lib.SpecError):
        spec.config("../escape")


@pytest.mark.parametrize("name,want", [
    ("fit_s", 1.5),             # the kind's own quantity
    ("fit_s.large", 1.5),       # the same quantity under a group's name
    ("request_p95_ms", None),   # not in this window
])
def test_end_to_end_value_by_name(name, want):
    """A metric ``<quantity>.<group>`` is the traffic kind's ``<quantity>``
    under a name, and a bound, of its own."""
    assert runner.e2e_value(name, {"fit_s": 1.5}) == want
