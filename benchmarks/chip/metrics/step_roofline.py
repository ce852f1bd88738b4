"""``step_roofline`` (%): the bandwidth floor of the whole step's own work
over the traced time per step.  The work is what the step must do whatever
implements it (the stepper's ``work["step"]``: for CH read ``c_n`` and
``c_{n-1}`` and write ``c_{n+1}``; for the explicit heat step read and
write one field), so it bounds every kernel's gain from above."""


def read(ctx):
    bytes_, flops = ctx.work["step"]
    return ctx.roofline("step_roofline", bytes_, flops, ctx.red.window_s)
