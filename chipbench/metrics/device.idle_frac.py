"""Device layer: the share of the traced window in which no op ran on
the chip, averaged over the chips.  In percent."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
