"""Operations and bytes each piece of work needs, from its shapes alone.

A floor counts what the algorithm must do, not what an implementation
happens to do: the field passes it cannot avoid (each field read once,
each result written once) and two flops per stencil tap.  The arithmetic
follows ``expected_stencil`` / ``expected_penta`` / ``expected_ch_step``
in the library's ``analysis/cost.py``, copied here so that no change to
the program can change the yardstick; the penta floors keep the two
passes a sweep cannot avoid (read the right-hand side, write the
solution), not that module's six.

Every function returns ``(bytes, flops)`` for one call on ``shape``.
"""

from __future__ import annotations

import math

# the paper's CH right-hand side: a 25-tap biharmonic plus the 9-tap
# Laplacian of (c^3 - c)
CH_RHS_TAPS = 34
# a factored cyclic penta sweep: forward and backward substitution
# (2 FMAs each per unknown) plus the Woodbury closure (4 FMAs)
PENTA_FLOPS_PER_POINT = 2 * (2 + 2) + 2 * 4
# the CH step's pointwise update c_{n+1} = 2 c_n - c_{n-1} + v and the
# RHS's axpys
CH_AXPY_FLOPS_PER_POINT = 6


def points(shape) -> int:
    return math.prod(int(d) for d in shape)


def stencil(shape, taps: int, itemsize: int = 4):
    """A direct stencil apply: read one field, write one field."""
    n = points(shape)
    return 2.0 * n * itemsize, 2.0 * taps * n


def penta(shape, itemsize: int = 4, sweeps: int = 1):
    """``sweeps`` factored cyclic penta sweeps over every line of
    ``shape``: each reads its right-hand side and writes its solution."""
    n = points(shape)
    return 2.0 * sweeps * n * itemsize, float(sweeps * PENTA_FLOPS_PER_POINT * n)


def ch_xsweep(shape, itemsize: int = 4):
    """The fused CH RHS + x-sweep: read ``c_n`` and ``c_{n-1}``, write the
    x-solved field; the RHS taps and one penta sweep of flops."""
    n = points(shape)
    return 3.0 * n * itemsize, float((2 * CH_RHS_TAPS + PENTA_FLOPS_PER_POINT) * n)


def ch_step(shape, itemsize: int = 4):
    """One CH ADI step: read ``c_n`` and ``c_{n-1}``, write ``c_{n+1}``;
    the RHS, two penta sweeps and the update of flops."""
    n = points(shape)
    flops = 2 * CH_RHS_TAPS + 2 * PENTA_FLOPS_PER_POINT + CH_AXPY_FLOPS_PER_POINT
    return 3.0 * n * itemsize, float(flops * n)


def floor_s(bytes_: float, peak: dict) -> float:
    """The least time the chip's memory takes to move ``bytes_``: the
    bandwidth side of the roofline.  There is no compute side: the work
    here is float32 vector arithmetic, and the chip publishes no peak for
    it (its FLOP/s peak is the bfloat16 matrix unit's).  Operations per
    byte are reported beside each share instead."""
    return bytes_ / peak["hbm_bytes_per_s"]
