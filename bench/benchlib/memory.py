"""The device memory a run holds at its peak, on its fullest chip.

Two readings, each a floor of the true peak, and the larger is taken:

- the allocator's ``peak_bytes_in_use`` (``Device.memory_stats``): every
  buffer that lives across programs (inputs, outputs, what set-up
  keeps); the scratch that a compiled program takes while it runs is
  not among them, so a program that makes and drops a large array
  inside itself does not show here;
- the footprint of the largest program the run compiled or loaded:
  its arguments, outputs and temporaries on one device, less what it
  aliases (``get_compiled_memory_stats`` of the executable, as
  ``Compiled.memory_analysis`` gives it), all of it held at once while
  that program runs.

The programs are seen by wrapping JAX's ``compile_or_get_cached``,
which every compile and every persistent-cache load goes through.  The
wrap is in place only while a ``ProgramMemory`` is recording; where
JAX has no such function the second reading is 0.
"""
from __future__ import annotations

import threading
from typing import Dict, List


def allocator_peak_bytes(devices) -> int:
    """The allocator's peak over ``devices``: its fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks, default=0)


def footprint_bytes(stats) -> int:
    """Bytes one device holds while a program with these compiled
    memory stats runs."""
    return int(stats.argument_size_in_bytes + stats.output_size_in_bytes
               + stats.temp_size_in_bytes - stats.alias_size_in_bytes)


class ProgramMemory:
    """Records the footprint of every program compiled or loaded while
    recording (between ``start`` and ``stop``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._orig = None
        self._mod = None
        self.programs: List[Dict[str, object]] = []

    def start(self) -> "ProgramMemory":
        try:
            from jax._src import compiler
        except ImportError:
            return self
        orig = getattr(compiler, "compile_or_get_cached", None)
        if orig is None:
            return self

        def wrapped(backend, computation, *args, **kwargs):
            exe = orig(backend, computation, *args, **kwargs)
            self._record(computation, exe)
            return exe

        self._mod, self._orig = compiler, orig
        compiler.compile_or_get_cached = wrapped
        return self

    def stop(self) -> None:
        if self._orig is not None:
            self._mod.compile_or_get_cached = self._orig
            self._orig = None

    def _record(self, computation, exe) -> None:
        try:
            stats = exe.get_compiled_memory_stats()
            name = str(computation.operation.attributes["sym_name"]).strip('"')
        except Exception:                # no stats from this backend
            return
        with self._lock:
            self.programs.append({"name": name,
                                  "bytes": footprint_bytes(stats),
                                  "temp_bytes": int(stats.temp_size_in_bytes)})

    def largest(self) -> Dict[str, object]:
        with self._lock:
            return max(self.programs, key=lambda p: p["bytes"],
                       default={"name": None, "bytes": 0, "temp_bytes": 0})


def peak_bytes(devices, programs: ProgramMemory) -> Dict[str, object]:
    """``memory_peak_bytes`` (the larger floor) and both readings."""
    alloc = allocator_peak_bytes(devices)
    big = programs.largest()
    return {"memory_peak_bytes": max(alloc, int(big["bytes"])),
            "allocator_peak_bytes": alloc,
            "largest_program": big["name"],
            "largest_program_bytes": int(big["bytes"]),
            "largest_program_temp_bytes": int(big["temp_bytes"])}
