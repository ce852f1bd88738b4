"""``penta_roofline`` (%): the penta sweep kernels (``kernels/penta.py``,
every layout's ``_substitute_*_pallas``): the bandwidth floor of one
sweep over the grid per step (read the right-hand side, write the
solution) over the device time of their events; 16 flops a point give
the operations per byte logged beside it.  In the CH cell this is the
y-sweep, once a step."""

from yardstick import work

KERNEL = r"jit\(_substitute_\w*_pallas\)"


def read(ctx):
    w = ctx.work
    return ctx.roofline("penta_roofline", *work.penta(w["grid"], w["itemsize"]),
                        ctx.kernel_s(KERNEL))
