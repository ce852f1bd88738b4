"""``ch_rhs3d_roofline`` (%): the 3D stencil kernel
(``kernels/stencil3d.py``) as the 3D CH right-hand side runs it: the
bandwidth floor of two applies a step (the 5x5x5 biharmonic of ``Cbar``
and the function-pointer Laplacian of ``C^3 - C``), each reading one
field and writing one, over the device time of its events; two flops a
non-zero tap give the operations per byte logged beside it.  Silent
where ``auto`` runs no Pallas stencil."""

from yardstick import work3d

KERNEL = r"jit\(stencil3d_pallas\)"


def read(ctx):
    w = ctx.work
    return ctx.roofline("ch_rhs3d_roofline", *work3d.ch3d_rhs(w["grid"], w["itemsize"]),
                        ctx.kernel_s(KERNEL))
