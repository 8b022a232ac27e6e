"""Device: share of the traced window in which no operation ran, 1 minus
the union of device-op intervals over the window, the mean over the chips
used, %."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
