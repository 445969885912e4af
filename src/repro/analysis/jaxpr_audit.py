"""Jaxpr-level audits of the real entry points + the retrace guard.

Where the lint rules reason about *source*, this module reasons about
what JAX actually *traces*:

- :func:`audit_fn` walks the (closed) jaxpr of a callable, recursively
  through scan/cond/jit sub-jaxprs, and reports any **denied
  primitive** (unordered-reduction scatters, stateful RNG — the
  nondeterministic-order class the bitwise contract forbids; the
  round-*keyed* threefry stream the lossy fabric uses is deterministic
  and allowed), any **denied dtype** (f64 — only possible when
  ambient x64 config leaks in; bf16/f16 — never intentional here) and
  any f32 ``dot_general`` left at **default precision** (one bf16 pass
  on a TPU, so a fit there drifts far from the f32 result).
- :func:`audit_entry_points` applies that to the paths the contract
  actually covers: ``compile_problem(...).step``, ``compile_sweep``'s
  batched step, the async fabric round (``_fabric_step``), and the
  serve GEMM at its bucket shapes.
- :func:`trace_counter` + :func:`jit_cache_size` turn "weighted_gram
  entered exactly once per fit" and "one GEMM compile per serve
  bucket" from commit-message claims into enforced invariants: a
  python function's body runs once per trace, so counting entries of a
  module attribute under jit counts traces; ``_cache_size`` counts a
  jitted function's compiled variants.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
from typing import Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.linter import Finding

#: primitives whose *unordered* accumulation / stateful randomness can
#: differ run-to-run or backend-to-backend — forbidden on contract
#: paths.  NOT here: ``threefry2x32`` (keyed, deterministic — the
#: fabric's round-keyed drop stream depends on it).
DENY_PRIMS = frozenset({
    "scatter-add", "scatter-mul", "rng_uniform", "rng_bit_generator",
})

#: dtypes that must never appear in a contract-path jaxpr: f64 means
#: ambient x64 config leaked past the pinned-f32 policy; bf16/f16 are
#: never intentional in this repo.
DENY_DTYPES = frozenset({"float64", "bfloat16", "float16"})

#: the precision every f32 contraction on a contract path pins
_F32_PRECISION = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            if hasattr(item, "eqns"):              # a Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):           # a ClosedJaxpr
                yield item.jaxpr


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def audit_fn(fn: Callable, *args, name: Optional[str] = None,
             deny_prims: Iterable[str] = DENY_PRIMS,
             deny_dtypes: Iterable[str] = DENY_DTYPES,
             **kwargs) -> List[Finding]:
    """Trace ``fn(*args, **kwargs)`` and audit the full jaxpr.

    Returns :class:`~repro.analysis.linter.Finding` objects with rule
    ids ``jaxpr-denied-prim`` / ``jaxpr-denied-dtype`` /
    ``jaxpr-default-precision``; the ``path``
    field carries the entry-point name (there is no source line for a
    jaxpr equation).  An empty list means the traced program is clean.
    """
    name = name or getattr(fn, "__name__", "<fn>")
    deny_prims, deny_dtypes = set(deny_prims), set(deny_dtypes)
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    findings: List[Finding] = []
    seen = set()
    for eqn in _walk_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim in deny_prims and ("prim", prim) not in seen:
            seen.add(("prim", prim))
            findings.append(Finding(
                "jaxpr-denied-prim", name, 0,
                f"primitive {prim!r} on a bitwise-contract path — its "
                "accumulation/ordering is not deterministic across "
                "backends"))
        for var in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in deny_dtypes and ("dtype", dt, prim) not in seen:
                seen.add(("dtype", dt, prim))
                findings.append(Finding(
                    "jaxpr-denied-dtype", name, 0,
                    f"dtype {dt} appears at primitive {prim!r} — the "
                    "pinned-f32 policy forbids it on contract paths "
                    "(ambient x64 leak or an unintended low-precision "
                    "cast)"))
        if (prim == "dot_general"
                and all(str(v.aval.dtype) == "float32" for v in eqn.invars)
                and eqn.params.get("precision") != (_F32_PRECISION,) * 2
                and ("precision", prim) not in seen):
            seen.add(("precision", prim))
            findings.append(Finding(
                "jaxpr-default-precision", name, 0,
                "f32 dot_general without precision='highest' — a TPU "
                "runs it as one bf16 pass"))
    return findings


# ----------------------------------------------------------------------
# retrace / compile counting
# ----------------------------------------------------------------------


class TraceCounts:
    """Entry counts per wrapped target, filled while the
    :func:`trace_counter` context is active.

    Index with the full ``"module:attr"`` target or just the attribute
    name (``counts["weighted_gram"]``).
    """

    def __init__(self):
        self._counts: Dict[str, int] = {}

    def __getitem__(self, key: str) -> int:
        if key in self._counts:
            return self._counts[key]
        hits = [v for k, v in self._counts.items()
                if k.rsplit(":", 1)[-1] == key]
        if len(hits) > 1:
            raise KeyError(f"{key!r} is ambiguous; use 'module:attr'")
        return hits[0] if hits else 0

    def snapshot(self) -> Dict[str, int]:
        """A plain dict copy of all counters."""
        return dict(self._counts)


@contextlib.contextmanager
def trace_counter(*targets: str):
    """Count python-body entries of module attributes.

    ``targets`` are ``"module.path:attr"`` strings, e.g.
    ``"repro.kernels.ops:weighted_gram"``.  Each named attribute is
    replaced (for the duration of the context) with a counting wrapper.
    Because jit/scan run a function's *python* body exactly once per
    trace, the count of a function only ever called from traced code
    equals its number of traces — "entered exactly once per fit" is
    ``counts["weighted_gram"] == 1``.

    Target the attribute in the *consuming* module: a ``from x import
    f`` binding in module ``m`` must be patched as ``"m:f"``, not
    ``"x:f"``.
    """
    counts = TraceCounts()
    saved = []
    try:
        for target in targets:
            modname, attr = target.split(":")
            mod = importlib.import_module(modname)
            fn = getattr(mod, attr)
            counts._counts[target] = 0

            def wrapper(*a, __fn=fn, __t=target, **kw):
                counts._counts[__t] += 1
                return __fn(*a, **kw)

            functools.update_wrapper(wrapper, fn)
            setattr(mod, attr, wrapper)
            saved.append((mod, attr, fn))
        yield counts
    finally:
        for mod, attr, fn in reversed(saved):
            setattr(mod, attr, fn)


def jit_cache_size(fn: Callable) -> int:
    """Number of compiled variants a ``jax.jit`` function holds — one
    per distinct input signature (the serve layer's "one GEMM compile
    per bucket" is a delta of this across requests)."""
    sizer = getattr(fn, "_cache_size", None)
    if sizer is None:
        raise TypeError(
            f"{fn!r} exposes no _cache_size — not a jitted function "
            "(or an unsupported jax version; pin per ci.yml)")
    return int(sizer())


# ----------------------------------------------------------------------
# entry-point audit (the CLI's --jaxpr section)
# ----------------------------------------------------------------------


def _tiny_problem():
    """The smallest representative problem (V=2, T=2, N=8, p=4)."""
    from repro.core import dtsvm as core
    from repro.core import graph
    from repro.data import synthetic

    V, T, N, p = 2, 2, 8, 4
    data = synthetic.make_multitask_data(
        V=V, T=T, p=p, n_train=np.full((V, T), N, int), n_test=4,
        relatedness=0.9, seed=0)
    adj = graph.make_graph("ring", V, seed=0)
    return core.make_problem(data["X"], data["y"], data["mask"], adj)


def audit_entry_points(deny_prims: Iterable[str] = DENY_PRIMS,
                       deny_dtypes: Iterable[str] = DENY_DTYPES
                       ) -> List[Finding]:
    """Audit the jaxprs of every bitwise-contract entry point.

    Covers the compiled plan step (``compile_problem``), the batched
    sweep step (``compile_sweep``), one async fabric round over a lossy
    net (``net.async_admm._fabric_step``), and the serve GEMM
    (``PredictModel.decide_rows``'s kernel) at two bucket shapes.
    Returns the concatenated findings (empty = all clean).
    """
    from repro.engine.plan import compile_problem
    from repro.engine.sweep import compile_sweep
    from repro.net import async_admm, fabric as fabric_lib
    from repro.net.policies import LinkPolicy, NetConfig
    from repro.serve.model import gemm_rows, row_bucket

    prob = _tiny_problem()
    findings: List[Finding] = []

    plan = compile_problem(prob, qp_iters=3)
    findings += audit_fn(plan.step, plan.init_state(),
                         name="compile_problem(...).step",
                         deny_prims=deny_prims, deny_dtypes=deny_dtypes)

    sweep = compile_sweep(prob, [{"C": 0.01}, {"C": 0.1}], qp_iters=3)
    findings += audit_fn(sweep.step, sweep.init_state(),
                         name="compile_sweep(...).step",
                         deny_prims=deny_prims, deny_dtypes=deny_dtypes)

    # a lossy, delayed f32 wire: exercises the keyed drop stream and
    # the mailbox rings (quantized links are deliberately outside the
    # bitwise contract and not audited here)
    net = NetConfig(policy=LinkPolicy(drop=0.3, delay=1), seed=7)
    fab = fabric_lib.build_fabric(prob, net)
    state = plan.init_state()
    fst = fab.init_state(jnp.zeros((fab.V, prob.X.shape[1], fab.D),
                                   jnp.float32))
    V = fab.V
    act = jnp.ones((V,), jnp.float32)
    links = jnp.ones((V, V), bool)
    findings += audit_fn(
        lambda s, f: async_admm._fabric_step(plan, fab, s, f, act,
                                             links, None),
        state, fst, name="async_admm._fabric_step",
        deny_prims=deny_prims, deny_dtypes=deny_dtypes)

    p = prob.X.shape[-1]
    Wf = jnp.zeros((V * prob.X.shape[1], p), jnp.float32)
    bf = jnp.zeros((V * prob.X.shape[1],), jnp.float32)
    for n in (1, 100):
        b = row_bucket(n)
        findings += audit_fn(
            gemm_rows, Wf, bf, jnp.zeros((b, p), jnp.float32),
            name=f"serve.gemm_rows[bucket={b}]",
            deny_prims=deny_prims, deny_dtypes=deny_dtypes)
    return findings
