"""Named host-side counters: how much work of a kind the program did.

A counter is a name and a running total, added to on the host from
static shapes, so counting never touches a device value or blocks.
``engine.invariants.compute_invariants`` adds, once per sparse build:

- ``build.densified_bytes``: float32 bytes of Z the K build writes
  dense (every feature panel of every block's (N, panel) tile);
- ``build.stored_nonzeros``: entries the sparse X stores, padding
  included (V * T * E).

Read the totals with :func:`read_counters` before and after the work of
interest; :func:`clear_counters` resets them.
"""
from __future__ import annotations

import threading
from typing import Dict

_LOCK = threading.Lock()
_TOTALS: Dict[str, int] = {}


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name``."""
    with _LOCK:
        _TOTALS[name] = _TOTALS.get(name, 0) + int(n)


def read_counters() -> Dict[str, int]:
    """A copy of every counter's total."""
    with _LOCK:
        return dict(_TOTALS)


def clear_counters() -> None:
    """Reset every counter."""
    with _LOCK:
        _TOTALS.clear()
