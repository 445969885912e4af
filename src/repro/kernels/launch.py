"""Launch geometry for the Pallas kernels, as inspectable data.

Every ``pl.pallas_call`` in this package derives its grid, block
shapes, padded operand shapes and scratch buffers from a
:class:`LaunchSpec` built by a pure function of the logical shapes
(``gram.gram_launch_spec`` / ``qp_step.qp_launch_spec``).  That split
exists so the static analyzer (``repro.analysis.pallas_audit``) can
validate the exact geometry a kernel will launch with — (8, 128) f32
tile alignment, VMEM footprint vs. budget — *without* running or even
tracing the kernel, and so the kernels and the auditor can never
disagree about what is launched.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

#: f32 TPU layout: second-minor (sublane) x minor (lane) minimum tile.
SUBLANE = 8
LANE = 128


class LaunchSpec(NamedTuple):
    """The complete static geometry of one ``pl.pallas_call``.

    ``in_blocks`` / ``out_block`` / ``scratch`` are 2-d block shapes;
    ``padded_in`` the padded operand shapes the blocks index into;
    ``out_shape`` the padded output.  ``grid`` is the iteration space.
    """
    grid: Tuple[int, ...]
    in_blocks: Tuple[Tuple[int, int], ...]
    padded_in: Tuple[Tuple[int, int], ...]
    out_block: Tuple[int, int]
    out_shape: Tuple[int, int]
    scratch: Tuple[Tuple[int, int], ...] = ()

    def vmem_bytes(self, itemsize: int = 4) -> int:
        """Static per-grid-step VMEM footprint: every in/out block plus
        scratch, resident at once (double-buffering pipelines add a
        constant factor the budget check absorbs in its margin)."""
        blocks = list(self.in_blocks) + [self.out_block] \
            + list(self.scratch)
        return sum(b[0] * b[1] for b in blocks) * itemsize


def next_multiple(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return -(-x // m) * m


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """The kernels' ``interpret`` flag, resolved from the platform:
    ``None`` means compiled on a TPU and interpret mode everywhere else.
    Interpret mode on a TPU is refused, so no kernel on the chip can run
    the Python interpreter while it claims to run compiled.  ``False``
    off a TPU is allowed: it lowers the compiled kernel, which is how
    the kernels are compiled for a described (not attached) TPU.
    Resolved at trace time (``interpret`` is a static jit argument)."""
    import jax  # local: keep this module importable as pure geometry data

    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU: the Pallas kernels run "
                         "compiled there (interpret mode is for the CPU)")
    return bool(interpret)
