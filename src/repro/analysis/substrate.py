"""Import-graph reachability: which packages are live, which are
seed substrate.

The repo grew from a generic multi-arch JAX training scaffold; several
seed packages (``models``, ``configs``, ``optim``, ``train``,
``launch``) are not reachable from any public entry point and are
QUARANTINED, not deleted, per ``docs/substrates.md`` (they may be
revived the way ``checkpoint`` was in the durable-session work).  This
module mechanizes that judgment: it builds the static import graph of
``src/repro`` and walks it from the public roots (``repro.api``,
``repro.store``, ``repro.serve``, ``repro.data``,
``repro.compile_cache``).  Whatever the walk
cannot reach is reported as substrate — an *informational* section of
the analysis report, never a CI failure.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Set

#: subpackages whose modules seed the reachability walk — the public
#: surface (API, durable store, serving), the dataset generators and the
#: compile-cache helper the scripts call at start-up.
ROOT_PACKAGES = ("repro.api", "repro.store", "repro.serve",
                 "repro.data", "repro.compile_cache")

#: the analyzer itself: excluded from both live and substrate sets.
TOOLING_PACKAGES = ("repro.analysis",)


def _module_name(pkg_dir: str, path: str) -> str:
    rel = os.path.relpath(path, os.path.dirname(pkg_dir))
    parts = rel[:-3].split(os.sep)          # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def import_graph(pkg_dir: str = None) -> Dict[str, Set[str]]:
    """``module -> imported repro modules`` over the package tree.

    Edges include every prefix package of a dotted import (importing
    ``repro.a.b`` executes ``repro.a``'s ``__init__`` too) and, for
    ``from repro.a import b`` forms, ``repro.a.b`` when it is a module.
    """
    if pkg_dir is None:
        import repro
        pkg_dir = os.path.dirname(os.path.abspath(repro.__file__))
    files = {}
    for root, dirs, names in os.walk(pkg_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in names:
            if n.endswith(".py"):
                path = os.path.join(root, n)
                files[_module_name(pkg_dir, path)] = path
    known = set(files)

    def expand(dotted: str) -> Set[str]:
        out = set()
        parts = dotted.split(".")
        for i in range(1, len(parts) + 1):
            prefix = ".".join(parts[:i])
            if prefix in known:
                out.add(prefix)
        return out

    graph: Dict[str, Set[str]] = {}
    for mod, path in files.items():
        with open(path, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
        edges: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    edges |= expand(a.name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.level:
                    continue      # the repo bans relative imports
                base = node.module
                edges |= expand(base)
                for a in node.names:
                    edges |= expand(f"{base}.{a.name}")
        graph[mod] = edges - {mod}
    return graph


def substrate_report(pkg_dir: str = None) -> Dict[str, List[str]]:
    """Reachability classification of every module in the package.

    Returns ``{"roots", "reachable", "substrate", "tooling"}`` —
    sorted module-name lists.  ``substrate`` is everything the walk
    from :data:`ROOT_PACKAGES` cannot reach (quarantined per
    docs/substrates.md, not an error); ``reachable`` includes the
    roots themselves.
    """
    graph = import_graph(pkg_dir)
    tooling = sorted(m for m in graph
                     if m.startswith(TOOLING_PACKAGES))
    roots = sorted(
        m for m in graph
        if m in ROOT_PACKAGES or m.startswith(
            tuple(p + "." for p in ROOT_PACKAGES)))
    seen: Set[str] = set(roots)
    work = list(roots)
    while work:
        for dep in graph.get(work.pop(), ()):
            if dep not in seen:
                seen.add(dep)
                work.append(dep)
    # a ``python -m`` entry point of a live package is itself live —
    # nothing imports a __main__, so the plain walk cannot see it
    mains = [m for m in graph
             if m.endswith(".__main__")
             and m.rsplit(".", 1)[0] in seen and m not in seen]
    seen.update(mains)
    work = list(mains)
    while work:
        for dep in graph.get(work.pop(), ()):
            if dep not in seen:
                seen.add(dep)
                work.append(dep)
    reachable = sorted(m for m in seen if m not in tooling)
    substrate = sorted(m for m in graph
                       if m not in seen and m not in tooling)
    return {"roots": roots, "reachable": reachable,
            "substrate": substrate, "tooling": tooling}
