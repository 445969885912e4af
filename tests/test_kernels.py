"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps per the deliverable-(c) requirement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gram as gram_kernel
from repro.kernels import qp_step as qp_kernel
from repro.kernels import ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,d", [(1, 1), (5, 3), (37, 11), (128, 11),
                                 (130, 20), (300, 64), (513, 7)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_gram_kernel_matches_ref(n, d, dtype):
    Z = RNG.normal(size=(n, d)).astype(dtype)
    a = RNG.uniform(0.1, 2.0, size=(d,)).astype(dtype)
    out = gram_kernel.weighted_gram_2d(jnp.asarray(Z, jnp.float32),
                                       jnp.asarray(a, jnp.float32),
                                       interpret=True)
    want = ref.weighted_gram(jnp.asarray(Z, jnp.float32),
                             jnp.asarray(a, jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert out.shape == (n, n)


@pytest.mark.parametrize("block", [8, 64, 256])
def test_gram_kernel_block_sizes(block):
    Z = RNG.normal(size=(100, 11)).astype(np.float32)
    a = RNG.uniform(0.1, 2.0, size=(11,)).astype(np.float32)
    out = gram_kernel.weighted_gram_2d(jnp.asarray(Z), jnp.asarray(a),
                                       block=block, interpret=True)
    want = ref.weighted_gram(jnp.asarray(Z), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("m,n,d,tile", [
    (24, 24, 11, (8, 128)),          # degenerate: blocks = array
    (100, 300, 20, (64, 128)),       # rectangular streamed panel
    (300, 300, 64, (256, 256)),      # square via the tiled path
])
def test_gram_tiled_matches_ref(m, n, d, tile):
    """interpret-vs-oracle fixture for weighted_gram_tiled (the gap
    analysis.pallas_audit flagged: the kernel was only exercised
    indirectly via tests/test_scale.py)."""
    Zm = RNG.normal(size=(m, d)).astype(np.float32)
    Zn = RNG.normal(size=(n, d)).astype(np.float32)
    a = RNG.uniform(0.1, 2.0, size=(d,)).astype(np.float32)
    out = gram_kernel.weighted_gram_tiled(
        jnp.asarray(Zm), jnp.asarray(a), jnp.asarray(Zn), tile=tile,
        interpret=True)
    want = ref.weighted_gram_rows(jnp.asarray(Zm), jnp.asarray(a),
                                  jnp.asarray(Zn))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert out.shape == (m, n)


def test_gram_psd():
    Z = RNG.normal(size=(60, 11)).astype(np.float32)
    a = RNG.uniform(0.1, 2.0, size=(11,)).astype(np.float32)
    K = np.asarray(gram_kernel.weighted_gram_2d(
        jnp.asarray(Z), jnp.asarray(a), interpret=True))
    ev = np.linalg.eigvalsh(K.astype(np.float64))
    assert ev.min() > -1e-4


@pytest.mark.parametrize("n", [1, 7, 64, 200, 400, 513])
@pytest.mark.parametrize("gamma", [0.01, 0.5])
def test_qp_step_kernel_matches_ref(n, gamma):
    A = RNG.normal(size=(n, n)).astype(np.float32)
    K = (A @ A.T / max(n, 1)).astype(np.float32)
    q = RNG.normal(size=n).astype(np.float32)
    hi = RNG.uniform(0.0, 1.0, size=n).astype(np.float32)
    lam = (RNG.uniform(0, 1, size=n) * hi).astype(np.float32)
    out = qp_kernel.qp_pg_step_1d(jnp.asarray(lam), jnp.asarray(K),
                                  jnp.asarray(q), jnp.asarray(hi), gamma,
                                  interpret=True)
    want = ref.qp_pg_step(jnp.asarray(lam), jnp.asarray(K), jnp.asarray(q),
                          jnp.asarray(hi), gamma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_qp_step_kernel_projects_into_box():
    n = 96
    K = np.eye(n, dtype=np.float32)
    q = np.full(n, 100.0, np.float32)          # pushes far above the box
    hi = RNG.uniform(0.1, 0.5, size=n).astype(np.float32)
    lam = np.zeros(n, np.float32)
    out = np.asarray(qp_kernel.qp_pg_step_1d(
        jnp.asarray(lam), jnp.asarray(K), jnp.asarray(q), jnp.asarray(hi),
        1.0, interpret=True))
    np.testing.assert_allclose(out, hi, rtol=1e-6)


def test_qp_iterated_kernel_solves_qp():
    """Iterating the fused kernel step must converge to the QP optimum."""
    from helpers import brute_force_box_qp
    n = 50
    A = RNG.normal(size=(n, n)).astype(np.float32)
    K = (A @ A.T / n).astype(np.float32)
    q = RNG.normal(size=n).astype(np.float32)
    hi = np.full(n, 1.0, np.float32)
    gamma = 1.0 / max(np.abs(K).sum(1).max(), 1e-9)
    lam = jnp.zeros(n, jnp.float32)
    for _ in range(600):
        lam = qp_kernel.qp_pg_step_1d(lam, jnp.asarray(K), jnp.asarray(q),
                                      jnp.asarray(hi), gamma, interpret=True)
    want = brute_force_box_qp(K, q, hi)
    np.testing.assert_allclose(np.asarray(lam), want, atol=5e-4)


def test_ops_dispatch_batched(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    from repro.kernels import ops
    Z = RNG.normal(size=(2, 3, 40, 11)).astype(np.float32)
    a = RNG.uniform(0.1, 2, size=(2, 3, 11)).astype(np.float32)
    out = ops.weighted_gram(jnp.asarray(Z), jnp.asarray(a))
    want = ref.weighted_gram(jnp.asarray(Z), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# -- fused multi-iteration QP solve (qp_pg_multi_1d) ------------------------

def _qp_problem(n, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, n)).astype(np.float32)
    K = (A @ A.T / max(n, 1)).astype(np.float32)
    q = rng.normal(size=n).astype(np.float32)
    hi = rng.uniform(0.0, 1.0, size=n).astype(np.float32)
    lam0 = (rng.uniform(-0.5, 1.5, size=n) * hi).astype(np.float32)
    gamma = 1.0 / max(np.abs(K).sum(1).max(), 1e-9)
    return map(jnp.asarray, (lam0, K, q, hi)), float(gamma)


@pytest.mark.parametrize("n", [1, 7, 64, 200, 513])
@pytest.mark.parametrize("iters", [1, 3, 10])
def test_qp_multi_kernel_matches_ref(n, iters):
    (lam0, K, q, hi), gamma = _qp_problem(n, seed=n)
    out = qp_kernel.qp_pg_multi_1d(lam0, K, q, hi, gamma, iters=iters,
                                   interpret=True)
    want = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=iters)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("n,d", [(7, 5), (64, 12), (200, 20), (513, 7)])
def test_qp_multi_fold_matches_ref(n, d):
    """The folded w-update contraction zl = Z^T lam rides in the same
    launch; both outputs must track the oracle."""
    (lam0, K, q, hi), gamma = _qp_problem(n, seed=n + 1)
    Z = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    lam, zl = qp_kernel.qp_pg_multi_1d(lam0, K, q, hi, gamma, iters=5,
                                       Z=Z, interpret=True)
    lam_w, zl_w = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=5, Z=Z)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(lam_w),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(zl), np.asarray(zl_w),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("block", [64, 256])
def test_qp_multi_block_sizes(block):
    """Multi-block grids must carry the VMEM-resident iterate correctly
    across (iteration, row, col) grid steps."""
    (lam0, K, q, hi), gamma = _qp_problem(300, seed=3)
    out = qp_kernel.qp_pg_multi_1d(lam0, K, q, hi, gamma, iters=4,
                                   block=block, interpret=True)
    want = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_qp_multi_warm_start_clipped_in_kernel():
    """Out-of-box warm starts must be projected before the first step
    (the satellite-1 bug class, locked at the kernel layer too)."""
    n = 64
    (_, K, q, hi), gamma = _qp_problem(n, seed=9)
    lam0 = jnp.asarray(np.full(n, 50.0, np.float32))   # far above the box
    out = qp_kernel.qp_pg_multi_1d(lam0, K, q, hi, gamma, iters=1,
                                   interpret=True)
    want = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert float(jnp.max(out - hi)) <= 3e-5 and float(jnp.min(out)) >= -0.0


def test_qp_multi_bf16_mixed_precision():
    """bf16 K tiles against f32 iterates: tracks the bf16 oracle tightly
    and the f32 answer loosely (bf16 has ~8 mantissa bits)."""
    (lam0, K, q, hi), gamma = _qp_problem(128, seed=5)
    out16 = qp_kernel.qp_pg_multi_1d(lam0, K, q, hi, gamma, iters=5,
                                     precision="bf16", interpret=True)
    want16 = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=5,
                             precision="bf16")
    want32 = ref.qp_pg_multi(lam0, K, q, hi, gamma, iters=5)
    np.testing.assert_allclose(np.asarray(out16), np.asarray(want16),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out16), np.asarray(want32),
                               rtol=5e-2, atol=5e-2)


def test_ops_qp_pg_multi_batched(monkeypatch):
    """Batched dispatch: the pallas path (lax.map over the flattened
    batch) and the oracle path agree for plain and folded calls."""
    from repro.kernels import ops
    rng = np.random.default_rng(11)
    B, n, d = (2, 3), 24, 6
    A = rng.normal(size=B + (n, n)).astype(np.float32)
    K = jnp.asarray(A @ np.swapaxes(A, -1, -2) / n)
    q = jnp.asarray(rng.normal(size=B + (n,)).astype(np.float32))
    hi = jnp.asarray(rng.uniform(0, 1, size=B + (n,)).astype(np.float32))
    lam0 = jnp.zeros_like(q)
    Z = jnp.asarray(rng.normal(size=B + (n, d)).astype(np.float32))
    gamma = 1.0 / jnp.maximum(jnp.abs(K).sum(-1).max(-1), 1e-9)

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    lam_o, zl_o = ops.qp_pg_multi(lam0, K, q, hi, gamma, iters=4, Z=Z)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    lam_p, zl_p = ops.qp_pg_multi(lam0, K, q, hi, gamma, iters=4, Z=Z)
    np.testing.assert_allclose(np.asarray(lam_p), np.asarray(lam_o),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(zl_p), np.asarray(zl_o),
                               rtol=3e-5, atol=3e-5)


def test_ops_qp_multi_gamma_unbatched():
    """Satellite: gamma arriving as shape-(1,) against unbatched operands
    must normalize to a scalar, not broadcast a phantom batch dim."""
    from repro.kernels import ops
    (lam0, K, q, hi), gamma = _qp_problem(24, seed=7)
    out_scalar = ops.qp_pg_multi(lam0, K, q, hi, jnp.float32(gamma),
                                 iters=3)
    out_vec = ops.qp_pg_multi(lam0, K, q, hi,
                              jnp.asarray([gamma], jnp.float32), iters=3)
    assert out_vec.shape == out_scalar.shape == lam0.shape
    np.testing.assert_array_equal(np.asarray(out_vec),
                                  np.asarray(out_scalar))


# ---------------------------------------------------------------------------
# dispatch switches: no hidden oracle or interpreter on a TPU
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
])
def test_default_interpret_per_platform(monkeypatch, backend, interpret,
                                        want):
    from repro.kernels.launch import default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            default_interpret(interpret)
    else:
        assert default_interpret(interpret) is want


@pytest.mark.parametrize("backend,flag,want", [
    ("cpu", None, False), ("cpu", "0", False), ("cpu", "1", True),
    ("tpu", None, True), ("tpu", "1", True), ("tpu", "0", RuntimeError),
])
def test_use_pallas_switch_per_platform(monkeypatch, backend, flag, want):
    """REPRO_USE_PALLAS steers the CPU tests only; on a TPU the kernels
    run unless the explicit reference scope is open."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if flag is None:
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("REPRO_USE_PALLAS", flag)
    with ops.reference_kernels():
        assert ops._use_pallas() is False
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="REPRO_USE_PALLAS=0"):
            ops._use_pallas()
    else:
        assert ops._use_pallas() is want
