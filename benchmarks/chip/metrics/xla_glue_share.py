"""``xla_glue_share`` (%): the device's busy time spent outside Pallas
kernels (XLA's own fusions and copies: in CH the Woodbury correction and
the step update of ``core/adi.py`` and ``core/cahn_hilliard.py``), over
its busy time."""


def read(ctx):
    if ctx.red.busy_s <= 0:
        return None
    kernels = sum(ctx.red.op_s.get(name, 0.0) for name in ctx.kernels)
    return 100.0 * (ctx.red.busy_s - kernels) / ctx.red.busy_s
