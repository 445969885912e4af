import gc
import os
import sys

import pytest

# tests import repro from src/ and helpers from tests/
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "src"))


#: the fit's cached programs: module-level jits that live for the process
_FIT_PROGRAMS = (("repro.engine.plan", "_plan_scan"),
                 ("repro.kernels.ops", "_gram_map"))


@pytest.fixture(autouse=True)
def _cold_fit_programs():
    """Start every test with the fit's cached programs cold.

    A test that counts traces, or that monkeypatches a function these
    programs call (the benchmark's broken-fit tests patch ``plan_step``
    and ``consensus_update``), must trace them anew, not dispatch what
    an earlier test of the same worker compiled.  A module not yet
    imported has cached nothing."""
    for module, program in _FIT_PROGRAMS:
        if module in sys.modules:
            getattr(sys.modules[module], program).clear_cache()
    yield


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Drop jit caches after each test module.

    The full tier-1 run compiles 500+ XLA:CPU executables in one
    process; keeping them all loaded can exhaust the JIT's executable
    memory and segfault a LATER large compile (observed on the sweep
    suite's interpret-mode Pallas scan, which passes in isolation).
    Caches are per-module anyway — the compile-once contracts count
    traces within a module (tests/test_analysis_retrace.py), never
    across modules."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device
# (the 512-device override belongs to repro.launch.dryrun ONLY).
# Distributed tests spawn subprocesses via helpers.run_with_devices.
