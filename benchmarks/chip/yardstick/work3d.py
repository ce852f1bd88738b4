"""Floors of the 3D steps, from their shapes alone, beside :mod:`work`.

Each returns ``(bytes, flops)`` for one step on ``shape`` and counts as
:mod:`work` does: the field passes the step cannot avoid, two flops a
stencil tap, and :data:`work.PENTA_FLOPS_PER_POINT` a penta sweep.
"""

from __future__ import annotations

from yardstick import work

# the 3D CH right-hand side: the 25-tap biharmonic plus the 7-tap
# Laplacian of (c^3 - c)
CH3D_RHS_TAPS = 25 + 7


def ch3d_step(shape, itemsize: int = 4):
    """One 3D CH ADI step: read ``c_n`` and ``c_{n-1}``, write
    ``c_{n+1}``; the RHS, three penta sweeps and the update of flops."""
    n = work.points(shape)
    flops = (2 * CH3D_RHS_TAPS + 3 * work.PENTA_FLOPS_PER_POINT
             + work.CH_AXPY_FLOPS_PER_POINT)
    return 3.0 * n * itemsize, float(flops * n)


def ch3d_rhs(shape, itemsize: int = 4):
    """The 3D CH right-hand side's two stencil applies a step, the
    biharmonic of ``Cbar`` and the Laplacian of ``c^3 - c``: each reads
    one field and writes one."""
    n = work.points(shape)
    return 4.0 * n * itemsize, float(2 * CH3D_RHS_TAPS * n)


def heat_lod_step(shape, itemsize: int = 4):
    """One LOD heat step: read and write one field; three sweeps of flops."""
    return 2.0 * work.points(shape) * itemsize, work.penta(shape, itemsize, sweeps=3)[1]
