"""``stencil3d_roofline`` (%): the 3D stencil kernel
(``kernels/stencil3d.py``): the bandwidth floor of reading one field and
writing one field over the device time of its events, one call per step;
two flops a non-zero tap give the operations per byte logged beside it."""

from yardstick import work

KERNEL = r"jit\(stencil3d_pallas\)"


def read(ctx):
    w = ctx.work
    return ctx.roofline("stencil3d_roofline",
                        *work.stencil(w["grid"], w["taps"], w["itemsize"]),
                        ctx.kernel_s(KERNEL))
