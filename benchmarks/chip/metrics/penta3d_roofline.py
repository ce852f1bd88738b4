"""``penta3d_roofline`` (%): the penta sweep kernels of a 3D ADI step
(``kernels/penta.py``, every layout's ``_substitute_*_pallas``: rows for
x, planes for y, columns for z): the bandwidth floor of three sweeps over
the grid per step, each reading its right-hand side and writing its
solution, over the device time of their events; 16 flops a point a sweep
give the operations per byte logged beside it.  Silent where ``auto``
runs no Pallas sweep."""

from yardstick import work

KERNEL = r"jit\(_substitute_\w*_pallas\)"


def read(ctx):
    w = ctx.work
    return ctx.roofline("penta3d_roofline",
                        *work.penta(w["grid"], w["itemsize"], sweeps=3),
                        ctx.kernel_s(KERNEL))
