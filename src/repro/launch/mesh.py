"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry run must set XLA_FLAGS before any jax
initialization.

Targets (TPU v5e): single pod = 16x16 = 256 chips ("data", "model");
multi-pod = 2 x 16 x 16 = 512 chips ("pod", "data", "model").
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s per link


def _mk(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def num_chips(mesh) -> int:
    return mesh.devices.size
