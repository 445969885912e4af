"""The repo-specific lint rules.

Every rule here encodes a *real* past bug or a standing contract of
this codebase (each class docstring names it; ``docs/analysis.md`` has
the full catalog with the history).  Rules are pure-AST — no jax
import, no execution — and scoped to the package paths where the bug
class can actually occur.

Adding a rule: subclass :class:`Rule`, set ``id``/``summary``/
``history``/``paths``, implement ``check(mod) -> Iterator[Finding]``,
and append an instance to ``_REGISTRY`` at the bottom.  Add a paired
good/bad fixture under ``tests/analysis_fixtures/`` and a catalog
entry in ``docs/analysis.md`` — ``tests/test_analysis.py`` enforces
that every registered rule has a true-positive fixture.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from repro.analysis.linter import Finding, SourceModule

# ----------------------------------------------------------------------
# shared AST helpers
# ----------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _numpy_aliases(tree: ast.AST) -> Set[str]:
    """Local names bound to the ``numpy`` module (``np`` usually)."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _functions_by_name(mod: SourceModule
                       ) -> Dict[str, List[ast.AST]]:
    cache = getattr(mod, "_fn_index", None)
    if cache is None:
        cache = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cache.setdefault(node.name, []).append(node)
        mod._fn_index = cache
    return cache


#: traced-hot roots, matched by bare function name: the per-iteration
#: step bodies, the fabric's per-round traced methods, the QP engines,
#: the kernel entry ops, and the server's jitted GEMM.  Host-side
#: orchestration (compile_problem, PredictServer._run_batch,
#: PredictModel.decide_rows) is deliberately NOT here — numpy and
#: host syncs are its job.
HOT_ROOTS = frozenset({
    "plan_step", "consensus_update", "dtsvm_step", "_fabric_step",
    "gemm_rows", "reduce", "exchange", "_per_edge_quant",
    "apply_membership",
    "solve_fista", "solve_fista_factored", "solve_pg", "solve_pallas_fused",
    "solve_pallas_fused_multi", "solve_factored_multi",
    "solve_box_qp_pg", "solve_box_qp_fista",
    "weighted_gram", "weighted_gram_rows", "qp_pg_step", "qp_pg_multi",
    "collect_diagnostics", "collect_shard_diagnostics",
})


def _hot_functions(mod: SourceModule) -> List[ast.AST]:
    """Function nodes reachable (same-module static call graph) from
    the :data:`HOT_ROOTS` — cached on the module."""
    cache = getattr(mod, "_hot_cache", None)
    if cache is not None:
        return cache
    idx = _functions_by_name(mod)
    work = [fn for name in HOT_ROOTS for fn in idx.get(name, [])]
    seen = {id(fn) for fn in work}
    order = list(work)
    while work:
        fn = work.pop()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name):
                callee = node.func.id
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id in ("self", "cls")):
                callee = node.func.attr
            if callee is None:
                continue
            for target in idx.get(callee, []):
                if id(target) not in seen:
                    seen.add(id(target))
                    work.append(target)
                    order.append(target)
    mod._hot_cache = order
    return order


def _hot_calls(mod: SourceModule) -> Iterator[ast.Call]:
    """Every Call node inside the hot-reachable set, deduplicated."""
    seen: Set[int] = set()
    for fn in _hot_functions(mod):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and id(node) not in seen:
                seen.add(id(node))
                yield node


# ----------------------------------------------------------------------
# rule base + registry
# ----------------------------------------------------------------------


#: seed-substrate packages (see docs/substrates.md and
#: ``repro.analysis.substrate``): quarantined, not policed — the
#: substrate report marks them; lint rules skip them.
SUBSTRATE_PATHS = ("models/", "configs/", "optim/", "train/",
                   "launch/")


class Rule:
    """One lint rule: id, docs metadata, path scope, and ``check``."""
    id: str = ""
    summary: str = ""
    #: the real past bug / standing contract this rule encodes
    history: str = ""
    #: package-relative path prefixes the rule runs on (None = all)
    paths: Optional[Tuple[str, ...]] = None
    #: package-relative prefixes the rule never runs on
    exclude: Tuple[str, ...] = ("analysis/",) + SUBSTRATE_PATHS

    def applies(self, relpath: str) -> bool:
        """Whether the rule runs on a package-relative path."""
        if relpath.startswith(self.exclude):
            return False
        return self.paths is None or relpath.startswith(self.paths)

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Yield findings for one parsed module."""
        raise NotImplementedError

    def finding(self, mod: SourceModule, line: int, message: str
                ) -> Finding:
        """A Finding carrying this rule's id at ``mod.path:line``."""
        return Finding(self.id, mod.path, line, message)


# ----------------------------------------------------------------------
# scalar-closure-in-scan (the PR-3 bug)
# ----------------------------------------------------------------------

_CTRL_FN_ARG = {"scan": 0, "fori_loop": 2, "while_loop": 1, "jit": 0}
_CTRL_FN_KW = {"scan": ("f",), "fori_loop": ("body_fun",),
               "while_loop": ("body_fun", "cond_fun"), "jit": ("fun",)}


def _is_py_scalar(node: ast.AST) -> bool:
    """A binding value that is a *python* int/float at trace time."""
    if isinstance(node, ast.Constant):
        return (isinstance(node.value, (int, float))
                and not isinstance(node.value, bool))
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int")):
        return True
    if isinstance(node, ast.UnaryOp):
        return _is_py_scalar(node.operand)
    if isinstance(node, ast.BinOp):
        return _is_py_scalar(node.left) and _is_py_scalar(node.right)
    return False


def _scoped_nodes(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk ``fn``'s body without descending into nested function /
    lambda / class bodies (their bindings are their own scope)."""
    stack = list(getattr(fn, "body", []))
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                yield child          # visible in this scope, opaque body
                continue
            stack.append(child)


def _free_names(fn: ast.AST) -> Set[str]:
    """Names a function/lambda loads but neither binds nor receives."""
    bound: Set[str] = set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs
              + ([args.vararg] if args.vararg else [])
              + ([args.kwarg] if args.kwarg else [])):
        bound.add(a.arg)
    loads: Set[str] = set()
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Load):
                    loads.add(node.id)
                else:
                    bound.add(node.id)
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
                bound.add(node.name)
    return loads - bound


class ScalarCloseInScan(Rule):
    """Python int/float captured by a function handed to
    ``lax.scan`` / ``fori_loop`` / ``while_loop`` / ``jit``.

    The scalar embeds as an HLO *literal* inside the loop body, so the
    same math compiles to a different program than the operand-passing
    path — PR 3 spent a bitwise-equivalence bisect on exactly this
    before converting ``DTSVMProblem`` scalars to 0-d f32 arrays.
    """
    id = "scalar-closure-in-scan"
    summary = ("python scalar captured by a scan/jit body embeds as a "
               "divergent HLO literal")
    history = ("PR 3: hyper-parameters closed over by the ADMM scan "
               "body compiled differently from the sweep loop; fixed "
               "by storing problem scalars as 0-d jnp arrays")
    paths = ("engine/", "net/", "core/", "kernels/", "api/", "obs/")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Scan each function scope for control-flow calls whose
        bodies capture locally-bound python scalars."""
        for scope in _functions_by_name(mod).values():
            for fn in scope:
                yield from self._check_scope(mod, fn)

    def _check_scope(self, mod, fn) -> Iterator[Finding]:
        assigns: Dict[str, List[Tuple[ast.AST, int]]] = {}
        local_defs: Dict[str, ast.AST] = {}
        calls: List[ast.Call] = []
        for node in _scoped_nodes(fn):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        assigns.setdefault(tgt.id, []).append(
                            (node.value, node.lineno))
                        if isinstance(node.value, ast.Lambda):
                            local_defs[tgt.id] = node.value
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                local_defs[node.name] = node
            elif isinstance(node, ast.Call):
                calls.append(node)
        for call in calls:
            d = _dotted(call.func)
            if d is None:
                continue
            ctrl = d.rsplit(".", 1)[-1]
            if ctrl not in _CTRL_FN_ARG:
                continue
            for body in self._body_args(call, ctrl, local_defs):
                yield from self._check_capture(
                    mod, fn, call, ctrl, body, assigns)

    @staticmethod
    def _body_args(call, ctrl, local_defs):
        cands = []
        i = _CTRL_FN_ARG[ctrl]
        if len(call.args) > i:
            cands.append(call.args[i])
        for kw in call.keywords:
            if kw.arg in _CTRL_FN_KW[ctrl]:
                cands.append(kw.value)
        for c in cands:
            if isinstance(c, ast.Lambda):
                yield c
            elif isinstance(c, ast.Name) and c.id in local_defs:
                yield local_defs[c.id]

    def _check_capture(self, mod, fn, call, ctrl, body, assigns
                       ) -> Iterator[Finding]:
        for name in sorted(_free_names(body)):
            history = assigns.get(name)
            if not history:
                continue
            before = [h for h in history if h[1] <= call.lineno]
            value, line = (before or history)[-1]
            if _is_py_scalar(value):
                yield self.finding(
                    mod, line,
                    f"python scalar {name!r} is captured by the body "
                    f"passed to {ctrl} (line {call.lineno}); it embeds "
                    "as an HLO literal and breaks bitwise equivalence "
                    "with operand-passing paths — store it as a 0-d "
                    "jnp.float32/int32 array instead")


# ----------------------------------------------------------------------
# silent-downcast (the PR-6 bug)
# ----------------------------------------------------------------------

_RESTORE_NAME = ("restore", "_restore", "load", "_load", "decode",
                 "_decode", "from_")


class SilentDowncast(Rule):
    """``jnp.asarray`` / ``jnp.array`` without an explicit dtype on a
    checkpoint / restore path.

    Under the default x32 config those calls silently downcast 64-bit
    leaves, breaking the byte-identical save→restore→continue promise
    (PR 6's ``msgpack_ckpt._decode`` bug).  Restores must either stay
    in numpy or pass the dtype explicitly.
    """
    id = "silent-downcast"
    summary = ("jnp.asarray/jnp.array without dtype silently downcasts "
               "64-bit leaves under x32")
    history = ("PR 6: checkpoint decode used jnp.asarray and returned "
               "f32 for saved f64 leaves; fixed by decoding to numpy")
    paths = None  # everywhere, gated on path OR function name below

    _FUNCS = ("jnp.asarray", "jnp.array",
              "jax.numpy.asarray", "jax.numpy.array")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag dtype-less jnp.asarray/array in restore-path code."""
        in_store = mod.relpath.startswith(("checkpoint/", "store/"))
        seen: Set[int] = set()   # nested defs are walked once only
        for fn_name, fns in _functions_by_name(mod).items():
            named = fn_name.startswith(_RESTORE_NAME)
            if not (in_store or named):
                continue
            for fn in fns:
                for node in ast.walk(fn):
                    if (not isinstance(node, ast.Call)
                            or id(node) in seen):
                        continue
                    seen.add(id(node))
                    if _dotted(node.func) not in self._FUNCS:
                        continue
                    if len(node.args) >= 2 or any(
                            kw.arg in ("dtype", None)
                            for kw in node.keywords):
                        continue
                    yield self.finding(
                        mod, node.lineno,
                        f"{_dotted(node.func)} without an explicit "
                        "dtype on a restore path — silently downcasts "
                        "64-bit leaves under x32; pass the dtype or "
                        "keep the leaf in numpy")


# ----------------------------------------------------------------------
# host-sync-in-hot-path
# ----------------------------------------------------------------------


class HostSyncInHotPath(Rule):
    """Host round-trips inside functions reachable from the traced hot
    roots (``plan_step``, the fabric step, the QP engines, the serve
    GEMM — see :data:`HOT_ROOTS`).

    ``.item()`` / ``float()`` / ``np.*`` / ``print`` inside traced code
    either fails at trace time, forces a device→host sync per call, or
    bakes a trace-time value in as a literal — all three have bitten
    JAX hot loops; the engine's contract is that the hot path is pure
    jnp.  (``jax.debug.print`` is the sanctioned escape hatch.)
    """
    id = "host-sync-in-hot-path"
    summary = ("host sync (.item()/float()/np.*/print) inside code "
               "reachable from a traced hot root")
    history = ("standing contract since PR 2: the per-iteration step "
               "is pure jnp so every backend lowers it identically")
    paths = ("engine/", "net/", "core/", "kernels/", "api/", "serve/",
             "obs/")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag host round-trips in the hot-reachable call set."""
        np_aliases = _numpy_aliases(mod.tree)
        for call in _hot_calls(mod):
            msg = self._violation(call, np_aliases)
            if msg:
                yield self.finding(mod, call.lineno, msg)

    @staticmethod
    def _violation(call: ast.Call, np_aliases: Set[str]
                   ) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name):
            if f.id == "print":
                return ("print() in traced code — use jax.debug.print "
                        "or move it to the host driver")
            if (f.id in ("float", "int") and call.args
                    and not isinstance(call.args[0], ast.Constant)):
                return (f"{f.id}() on a non-literal in traced code — "
                        "fails on tracers or bakes a trace-time value "
                        "in as a literal; keep the value as an array")
            return None
        d = _dotted(f)
        if d is None:
            return None
        if d.split(".", 1)[0] in np_aliases:
            return (f"numpy call {d}() in traced code — runs on host, "
                    "forces a transfer; use jnp")
        if d.endswith(".item"):
            return ".item() forces a device→host sync per call"
        if d.endswith(".block_until_ready"):
            return (".block_until_ready() in traced code — a "
                    "benchmarking construct, not a hot-path one")
        if d == "jax.device_get":
            return "jax.device_get in traced code forces a host sync"
        return None


# ----------------------------------------------------------------------
# raw-einsum-in-plan
# ----------------------------------------------------------------------


class RawEinsumInPlan(Rule):
    """``einsum`` inside the traced hot set.

    The plan's linear term deliberately uses the mul+reduce form
    (``jnp.sum(Z * g[..., None, :], axis=-1)``) because einsum's
    contraction order is an XLA implementation choice that has differed
    across batching transforms — the exact class of silent divergence
    the bitwise contract forbids.  A *deliberate* einsum on the hot
    path (e.g. the plan's rank-3 ``zl`` contraction, where mul+reduce
    would materialize a (V,T,N,d) temporary) is allowed only with a
    ``noqa`` attestation stating why it is batching-stable.
    """
    id = "raw-einsum-in-plan"
    summary = ("einsum on the traced hot path must carry a "
               "batching-stability attestation (or use mul+reduce)")
    history = ("PR 3: the q linear term was converted to mul+reduce "
               "after einsum lowered differently under vmap vs the "
               "sweep's stacked trace")
    paths = ("engine/", "net/", "core/", "kernels/", "api/", "serve/",
             "obs/")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag einsum calls in the hot-reachable call set."""
        for call in _hot_calls(mod):
            d = _dotted(call.func)
            if d == "einsum" or (d and d.endswith(".einsum")):
                yield self.finding(
                    mod, call.lineno,
                    "einsum on the traced hot path: prefer the "
                    "mul+reduce form; if einsum is required (memory), "
                    "attest batching stability with a noqa reason")


# ----------------------------------------------------------------------
# untiled-gram-call
# ----------------------------------------------------------------------


class UntiledGramCall(Rule):
    """Direct ``weighted_gram`` call without ``tile=`` outside the
    kernel package and the legacy oracle.

    The scale path (PR 5) made the Gram build budget-aware: callers go
    through ``PlanBudget`` / pass ``tile=`` so large-n problems stream
    panels instead of materializing the (N, N) Gram at once.  A bare
    call silently reverts to the dense build.
    """
    id = "untiled-gram-call"
    summary = ("weighted_gram without tile= bypasses the PlanBudget "
               "streaming path")
    history = ("PR 5: dense Gram builds OOM'd the large-n path; the "
               "budgeted/tiled build is the supported route")
    paths = ("engine/", "api/", "net/", "serve/", "store/")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag tile-less weighted_gram calls anywhere in the file."""
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d or d.rsplit(".", 1)[-1] != "weighted_gram":
                continue
            if any(kw.arg in ("tile", None) for kw in node.keywords):
                continue
            yield self.finding(
                mod, node.lineno,
                "weighted_gram(...) without tile= — route through "
                "PlanBudget (gram_and_lipschitz) or pass tile= so the "
                "build can stream under a memory budget")


# ----------------------------------------------------------------------
# env-dependent-dtype
# ----------------------------------------------------------------------


class EnvDependentDtype(Rule):
    """Behavior keyed on the x64 switch.

    A ``jax_enable_x64`` read/write anywhere in the package makes
    numeric results depend on ambient process config — the opposite of
    the pinned-dtype policy (everything f32 unless a leaf says
    otherwise).
    """
    id = "env-dependent-dtype"
    summary = "jax_enable_x64 touched in package code"
    history = ("standing policy: dtypes are pinned per-leaf; PR 6's "
               "downcast bug was only possible because behavior "
               "differed with ambient x64 config")
    paths = None
    exclude = ("analysis/",) + SUBSTRATE_PATHS

    _KEYS = ("jax_enable_x64", "JAX_ENABLE_X64")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag any constant or attribute touching the x64 switch."""
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Constant)
                    and node.value in self._KEYS):
                yield self.finding(
                    mod, node.lineno,
                    f"{node.value!r} referenced — pin the dtype on the "
                    "leaf instead of keying behavior on the x64 switch")
            elif (isinstance(node, ast.Attribute)
                    and node.attr == "jax_enable_x64"):
                yield self.finding(
                    mod, node.lineno,
                    "jax_enable_x64 attribute touched — pin the dtype "
                    "on the leaf instead")


# ----------------------------------------------------------------------
# telemetry-read-in-kernel
# ----------------------------------------------------------------------


class TelemetryReadInKernel(Rule):
    """``repro.obs`` imported or telemetry collected inside the kernel
    package.

    The telemetry contract (PR 9) is that diagnostics are extra *scan
    outputs* computed by the engine's step body — the Pallas kernels
    stay observation-free so their lowering (and the compile-once /
    bitwise guarantees built on it) never depends on whether telemetry
    is enabled.  A ``collect_diagnostics`` call (or any ``repro.obs``
    import) under ``kernels/`` threads observation into the lowered
    program itself, where a telemetry toggle would change the kernel.
    """
    id = "telemetry-read-in-kernel"
    summary = ("repro.obs imported / telemetry collected inside the "
               "kernel package — kernels must stay observation-free")
    history = ("PR 9 contract: telemetry is collected by the engine "
               "step as extra scan outputs only, so telemetry-on is "
               "bitwise telemetry-off and kernels compile once")
    paths = ("kernels/",)

    _COLLECTORS = ("collect_diagnostics", "collect_shard_diagnostics")

    def check(self, mod: SourceModule) -> Iterator[Finding]:
        """Flag obs imports and collector calls anywhere in the file."""
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "repro.obs" or a.name.startswith(
                            "repro.obs."):
                        yield self.finding(
                            mod, node.lineno,
                            f"import {a.name} inside kernels/ — the "
                            "kernel package is observation-free; "
                            "collect telemetry in the engine step")
            elif isinstance(node, ast.ImportFrom):
                names = {a.name for a in node.names}
                from_obs = node.module is not None and (
                    node.module == "repro.obs"
                    or node.module.startswith("repro.obs."))
                if from_obs or (node.module == "repro"
                                and "obs" in names):
                    yield self.finding(
                        mod, node.lineno,
                        "repro.obs imported inside kernels/ — the "
                        "kernel package is observation-free; collect "
                        "telemetry in the engine step")
            elif isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d and d.rsplit(".", 1)[-1] in self._COLLECTORS:
                    yield self.finding(
                        mod, node.lineno,
                        f"{d}() inside kernels/ — telemetry is an "
                        "engine-step scan output, never part of the "
                        "lowered kernel (a toggle would change the "
                        "compiled program)")


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------

_REGISTRY: Dict[str, Rule] = {r.id: r for r in [
    ScalarCloseInScan(),
    SilentDowncast(),
    HostSyncInHotPath(),
    RawEinsumInPlan(),
    UntiledGramCall(),
    EnvDependentDtype(),
    TelemetryReadInKernel(),
]}

#: meta rule ids raised by the linter itself (not suppressible targets)
META_RULES = ("bare-noqa", "unknown-noqa", "malformed-noqa",
              "syntax-error")


def all_rules() -> List[Rule]:
    """Every registered rule, in registration order."""
    return list(_REGISTRY.values())


def get_rule(rule_id: str) -> Rule:
    """Look up one rule by id (KeyError on unknown)."""
    return _REGISTRY[rule_id]


def is_known(rule_id: str) -> bool:
    """Whether ``rule_id`` is a registered (suppressible) rule."""
    return rule_id in _REGISTRY
