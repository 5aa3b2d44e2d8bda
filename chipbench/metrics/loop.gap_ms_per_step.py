"""Loop layer (src/repro/training/train_loop.py): device idle time per
step, between dispatches — the traced window minus the union of device
busy intervals, per MGD step, averaged over the chips."""


def read(ctx):
    return 1e3 * (ctx.window_s - ctx.busy_s) / ctx.steps
