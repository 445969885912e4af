"""The device memory a run reports: the larger of the allocator's peak
and the footprint of the largest program compiled while recording, so
that a program's temporaries count."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import memory  # noqa: E402


def test_a_programs_temporaries_count():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gram_sum(x):            # a (2048, 2048) float32 temporary, 16 MiB
        return jnp.sum(x @ x.T)

    x = jnp.ones((2048, 64), jnp.float32)
    rec = memory.ProgramMemory().start()
    try:
        gram_sum(x).block_until_ready()
    finally:
        rec.stop()
    big = rec.largest()
    assert big["name"] == "jit_gram_sum"
    assert big["temp_bytes"] >= 4 * 2048 * 2048
    assert big["bytes"] >= big["temp_bytes"] + 4 * 2048 * 64
    out = memory.peak_bytes(jax.devices()[:1], rec)
    assert out["memory_peak_bytes"] == max(out["allocator_peak_bytes"],
                                           big["bytes"])


def test_recording_stops_and_restores_jax():
    from jax._src import compiler

    orig = compiler.compile_or_get_cached
    rec = memory.ProgramMemory().start()
    assert compiler.compile_or_get_cached is not orig
    rec.stop()
    assert compiler.compile_or_get_cached is orig
    # nothing recorded: the program reading is 0 and the allocator's rules
    assert memory.ProgramMemory().largest()["bytes"] == 0

