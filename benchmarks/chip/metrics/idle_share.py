"""``idle_share`` (%): the part of the traced window in which no
operation ran on the device, averaged over the chips."""


def read(ctx):
    if ctx.red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.red.busy_s / ctx.red.window_s)
