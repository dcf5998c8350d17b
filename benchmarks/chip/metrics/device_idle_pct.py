"""Share of the traced window in which no op ran on the device: 100 x (1 -
union of the op intervals / window), averaged over the chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
