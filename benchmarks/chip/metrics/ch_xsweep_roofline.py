"""``ch_xsweep_roofline`` (%): the fused CH RHS + x-sweep kernel
(``kernels/fused_ch.py``): its bandwidth floor (read ``c_n`` and
``c_{n-1}``, write the x-solved field) over the device time of its
events, one call per step.  The RHS taps and one penta sweep of flops
give the operations per byte logged beside it."""

from yardstick import work

KERNEL = r"jit\(ch_rhs_xsweep_pallas\)"


def read(ctx):
    w = ctx.work
    return ctx.roofline("ch_xsweep_roofline", *work.ch_xsweep(w["grid"], w["itemsize"]),
                        ctx.kernel_s(KERNEL))
