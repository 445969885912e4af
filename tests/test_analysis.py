"""repro.analysis: rule fixtures (exact ids + line numbers),
suppression mechanics, the jaxpr/pallas/substrate audits, and the CLI
gate.  The paired good/bad fixture files live under
``tests/analysis_fixtures/`` and are parsed only — never imported."""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import linter, rules
from repro.analysis.jaxpr_audit import audit_fn, trace_counter
from repro.analysis.linter import lint_paths, lint_source

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC_DIR = os.path.abspath(os.path.join(HERE, os.pardir, "src"))
SRC_PKG = os.path.join(SRC_DIR, "repro")


def lint_fixture(name, rule_id=None):
    only = [rules.get_rule(rule_id)] if rule_id else None
    return lint_paths([os.path.join(FIXTURES, name)], rules=only,
                      all_paths=True)


#: rule id -> (bad fixture, exact lines the rule must flag)
BAD_EXPECT = {
    "scalar-closure-in-scan": ("scalar_closure_bad.py", [7, 17]),
    "silent-downcast": ("silent_downcast_bad.py", [7, 11]),
    "host-sync-in-hot-path": ("host_sync_bad.py", [8, 9, 13, 14]),
    "raw-einsum-in-plan": ("raw_einsum_bad.py", [7]),
    "untiled-gram-call": ("untiled_gram_bad.py", [7]),
    "env-dependent-dtype": ("env_dtype_bad.py", [7, 11]),
    "telemetry-read-in-kernel": ("telemetry_kernel_bad.py", [4, 9]),
}

GOOD_FIXTURES = [
    "scalar_closure_good.py", "silent_downcast_good.py",
    "host_sync_good.py", "raw_einsum_good.py",
    "untiled_gram_good.py", "env_dtype_good.py",
    "telemetry_kernel_good.py",
]


# ----------------------------------------------------------------------
# lint rules on the paired fixtures
# ----------------------------------------------------------------------


def test_every_registered_rule_has_a_true_positive_fixture():
    assert set(BAD_EXPECT) == {r.id for r in rules.all_rules()}


@pytest.mark.parametrize("rule_id", sorted(BAD_EXPECT))
def test_bad_fixture_exact_ids_and_lines(rule_id):
    name, lines = BAD_EXPECT[rule_id]
    findings = lint_fixture(name, rule_id)
    assert [f.line for f in findings] == lines
    assert all(f.rule == rule_id for f in findings)
    assert not any(f.suppressed for f in findings)


@pytest.mark.parametrize("name", GOOD_FIXTURES)
def test_good_fixture_is_clean_under_all_rules(name):
    assert lint_fixture(name) == []


def test_pr3_regression_pattern_is_caught():
    """The exact PR-3 bug shape: hyper-parameter floats closed over by
    the ADMM scan body."""
    findings = lint_fixture("pr3_regression.py",
                            "scalar-closure-in-scan")
    assert [f.line for f in findings] == [10, 11]
    assert all("HLO literal" in f.message for f in findings)


def test_pr6_regression_pattern_is_caught():
    """The exact PR-6 bug shape: checkpoint _decode rebuilding leaves
    with a bare jnp.asarray."""
    findings = lint_fixture("pr6_regression.py", "silent-downcast")
    assert [(f.rule, f.line) for f in findings] == [
        ("silent-downcast", 12)]
    assert "downcast" in findings[0].message


# ----------------------------------------------------------------------
# suppression mechanics
# ----------------------------------------------------------------------


def test_suppression_mechanics():
    findings = lint_fixture("suppression.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)

    ein = {f.line: f for f in by_rule["raw-einsum-in-plan"]}
    assert sorted(ein) == [9, 10, 11, 12, 17]
    # line-above directive with a reason suppresses (and keeps it)
    assert ein[9].suppressed
    assert ein[9].reason.startswith("fixture attestation")
    # bare / unknown / malformed directives do NOT suppress ...
    assert not ein[10].suppressed
    assert not ein[11].suppressed
    assert not ein[12].suppressed
    # ... and are findings themselves, at the directive's line
    assert [f.line for f in by_rule["bare-noqa"]] == [10]
    assert [f.line for f in by_rule["unknown-noqa"]] == [11]
    assert [f.line for f in by_rule["malformed-noqa"]] == [12]
    # the wildcard form suppresses every rule on its line
    assert ein[17].suppressed


def test_same_line_suppression():
    src = ("import jax.numpy as jnp\n"
           "def plan_step(z, g):\n"
           "    return jnp.einsum('nd,d->n', z, g)"
           "  # repro: noqa[raw-einsum-in-plan] - test: same-line\n")
    (f,) = [f for f in lint_source(src)
            if f.rule == "raw-einsum-in-plan"]
    assert f.suppressed and f.reason == "test: same-line"


def test_directives_inside_docstrings_are_ignored():
    src = ('"""Example::\n\n'
           '    x = 1  # repro: noqa[not-a-rule]\n"""\n')
    assert lint_source(src) == []


def test_syntax_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = lint_paths([str(bad)])
    assert [f.rule for f in findings] == ["syntax-error"]


# ----------------------------------------------------------------------
# path scoping
# ----------------------------------------------------------------------


def test_rule_path_scoping():
    scan = rules.get_rule("scalar-closure-in-scan")
    assert scan.applies("engine/plan.py")
    assert not scan.applies("models/transformer.py")   # substrate
    assert not scan.applies("analysis/rules.py")       # tooling
    env = rules.get_rule("env-dependent-dtype")
    assert env.applies("serve/model.py")
    assert not env.applies("analysis/rules.py")        # tooling
    down = rules.get_rule("silent-downcast")
    assert down.applies("store/session_store.py")
    tel = rules.get_rule("telemetry-read-in-kernel")
    assert tel.applies("kernels/fused.py")
    assert not tel.applies("engine/plan.py")     # the step MAY collect


def test_src_tree_has_no_unsuppressed_findings():
    """The acceptance gate: the linter runs clean over src/repro, and
    every suppression carries an attested reason."""
    findings = lint_paths([SRC_PKG])
    assert [f for f in findings if not f.suppressed] == []
    suppressed = [f for f in findings if f.suppressed]
    assert suppressed, "the attested noqa sites should be reported"
    assert all(f.reason for f in suppressed)


# ----------------------------------------------------------------------
# jaxpr audit
# ----------------------------------------------------------------------


def test_audit_fn_flags_denied_dtype_and_prim():
    import jax.numpy as jnp

    def to_bf16(x):
        return (x.astype(jnp.bfloat16) * 2).astype(jnp.float32)

    fs = audit_fn(to_bf16, jnp.ones((4,), jnp.float32))
    assert any(f.rule == "jaxpr-denied-dtype"
               and "bfloat16" in f.message for f in fs)

    def scatter_add(x):
        return x.at[0].add(1.0)

    fs = audit_fn(scatter_add, jnp.ones((4,), jnp.float32))
    assert any(f.rule == "jaxpr-denied-prim"
               and "scatter-add" in f.message for f in fs)


@pytest.mark.parametrize("precision,flagged", [
    (None, True), ("high", True), ("highest", False)])
def test_audit_fn_flags_default_precision_f32_dot(precision, flagged):
    """An f32 contraction below 'highest' runs as bf16 passes on a TPU:
    the audit flags it, through a scan body too."""
    import jax
    import jax.numpy as jnp

    def matvec_scan(K, x):
        body = lambda c, _: (jnp.matmul(K, c, precision=precision), None)
        return jax.lax.scan(body, x, None, length=2)[0]

    fs = audit_fn(matvec_scan, jnp.eye(4), jnp.ones((4,)))
    assert any(f.rule == "jaxpr-default-precision" for f in fs) == flagged


def test_entry_points_are_clean():
    from repro.analysis.jaxpr_audit import audit_entry_points
    assert audit_entry_points() == []


def test_trace_counter_counts_and_restores():
    import jax.numpy as jnp

    from repro.kernels import ops

    orig = ops.weighted_gram
    Z = jnp.ones((2, 2, 4, 3), jnp.float32)
    a = jnp.ones((2, 2, 3), jnp.float32)
    with trace_counter("repro.kernels.ops:weighted_gram") as c:
        ops.weighted_gram(Z, a)
        ops.weighted_gram(Z, a)
        assert c["weighted_gram"] == 2
        snap = c.snapshot()
    assert ops.weighted_gram is orig       # restored on exit
    assert snap == {"repro.kernels.ops:weighted_gram": 2}


# ----------------------------------------------------------------------
# pallas audit
# ----------------------------------------------------------------------


def test_pallas_audit_runs_clean():
    from repro.analysis import pallas_audit
    assert pallas_audit.audit_kernels() == []


def test_pallas_audit_flags_bad_geometry():
    from repro.analysis import pallas_audit
    from repro.kernels.launch import LaunchSpec

    misaligned = LaunchSpec(grid=(2, 2), in_blocks=((8, 100),),
                            padded_in=((16, 200),), out_block=(8, 100),
                            out_shape=(16, 200))
    hit = {f.rule for f in pallas_audit.check_spec(misaligned, "bad")}
    assert "pallas-misaligned-block" in hit

    ragged = LaunchSpec(grid=(3,), in_blocks=((8, 128),),
                        padded_in=((20, 128),), out_block=(8, 128),
                        out_shape=(20, 128))
    hit = {f.rule for f in pallas_audit.check_spec(ragged, "ragged")}
    assert "pallas-grid-mismatch" in hit

    big = LaunchSpec(grid=(1,), in_blocks=((1024, 2048),),
                     padded_in=((1024, 2048),), out_block=(1024, 2048),
                     out_shape=(1024, 2048))
    hit = {f.rule for f in pallas_audit.check_spec(big, "big", 1 << 20)}
    assert "pallas-vmem-budget" in hit


# ----------------------------------------------------------------------
# substrate reachability
# ----------------------------------------------------------------------


def test_substrate_report_quarantines_seed_packages():
    from repro.analysis.substrate import substrate_report
    rep = substrate_report()
    tops = {m.split(".")[1] for m in rep["substrate"] if "." in m}
    assert tops == {"configs", "launch", "models", "optim", "train"}
    for live in ("repro.engine.plan", "repro.core.dtsvm",
                 "repro.net.fabric", "repro.kernels.gram"):
        assert live in rep["reachable"]
    assert not set(rep["reachable"]) & set(rep["substrate"])
    assert rep["tooling"]
    assert all(m.startswith("repro.analysis") for m in rep["tooling"])


# ----------------------------------------------------------------------
# the CLI gate
# ----------------------------------------------------------------------


def _run_cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC_DIR + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        capture_output=True, text=True, env=env)


def test_cli_json_gate_is_clean(tmp_path):
    out = tmp_path / "report.json"
    proc = _run_cli(SRC_PKG, "--format=json", "--no-jaxpr",
                    "--no-retrace", "--no-pallas", "--out", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["summary"]["unsuppressed"] == 0
    assert report["summary"]["suppressed"] >= 1
    assert report["substrate"]["substrate"]
    assert json.loads(proc.stdout)["summary"] == report["summary"]


def test_cli_fails_on_unsuppressed_finding(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\n\n\n"
                   "def _decode(obj):\n"
                   "    return jnp.asarray(obj)\n")
    proc = _run_cli(str(bad), "--no-jaxpr", "--no-retrace",
                    "--no-pallas")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "silent-downcast" in proc.stdout


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule in rules.all_rules():
        assert rule.id in proc.stdout
