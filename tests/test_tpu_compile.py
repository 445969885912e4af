"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described (not
attached) ``v5e:2x2`` topology, with ``interpret=False`` so the kernel is
lowered to Mosaic instead of run by the interpreter.  Each test asserts
the compiled program holds the kernel (``tpu_custom_call``) and fits one
chip's 16 GB of HBM (``memory_analysis()``).  The widths are the large-n
regime's (N=20000 samples per node-task, p=256 features, D=p+1) and the
paper regime's (N=256, p=10).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gram as gram_kernel
from repro.kernels import qp_step as qp_kernel

HBM_BYTES = 16e9            # one TPU v5e chip
N_LARGE, D_LARGE = 20000, 257
QP_ITERS = 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return text.count("tpu_custom_call"), total


@pytest.mark.parametrize("rows,n,d", [
    (256, 256, 11),                    # paper regime, square
    (N_LARGE, N_LARGE, D_LARGE),       # large-n square build
    (512, N_LARGE, D_LARGE),           # one streamed row panel
])
def test_weighted_gram_tiled_compiles(one_chip, rows, n, d):
    fn = functools.partial(gram_kernel.weighted_gram_tiled, interpret=False)
    calls, total = _compile(fn, one_chip, (rows, d), (d,), (n, d))
    assert calls >= 1
    assert 4 * rows * n <= total < HBM_BYTES


def test_weighted_gram_2d_compiles(one_chip):
    fn = functools.partial(gram_kernel.weighted_gram_2d, interpret=False)
    calls, total = _compile(fn, one_chip, (256, 11), (11,))
    assert calls >= 1
    assert total < HBM_BYTES


def test_qp_pg_step_compiles(one_chip):
    fn = functools.partial(qp_kernel.qp_pg_step_1d, interpret=False)
    N = N_LARGE
    calls, total = _compile(fn, one_chip, (N,), (N, N), (N,), (N,), ())
    assert calls >= 1
    assert 4 * N * N <= total < HBM_BYTES


@pytest.mark.parametrize("mode", ["f32", "bf16", "fold"])
def test_qp_pg_multi_compiles(one_chip, mode):
    N = N_LARGE
    shapes = [(N,), (N, N), (N,), (N,), ()]
    if mode == "fold":
        fn = lambda l0, K, q, hi, g, Z: qp_kernel.qp_pg_multi_1d(
            l0, K, q, hi, g, iters=QP_ITERS, Z=Z, interpret=False)
        shapes.append((N, D_LARGE))
    else:
        fn = functools.partial(qp_kernel.qp_pg_multi_1d, iters=QP_ITERS,
                               precision=mode, interpret=False)
    calls, total = _compile(fn, one_chip, *shapes)
    assert calls >= 1
    assert 4 * N * N <= total < HBM_BYTES
