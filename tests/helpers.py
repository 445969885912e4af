"""Shared test utilities."""
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 420) -> str:
    """Run a python snippet in a CPU subprocess with N forced host devices.

    Needed because the main pytest process must keep the default single
    device (per the dry-run isolation rule) while distributed tests need a
    multi-device mesh.  The child is pinned to the CPU: a chip belongs to
    one process, and the parent may hold it.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def brute_force_box_qp(K, q, hi, iters=20000, tol=1e-10):
    """Very slow but reliable projected gradient with tiny steps (oracle)."""
    K = np.asarray(K, np.float64)
    q = np.asarray(q, np.float64)
    hi = np.asarray(hi, np.float64)
    L = max(np.abs(K).sum(1).max(), 1e-12)
    lam = np.zeros_like(q)
    for _ in range(iters):
        g = q - K @ lam
        new = np.clip(lam + g / L, 0.0, hi)
        if np.max(np.abs(new - lam)) < tol:
            lam = new
            break
        lam = new
    return lam
