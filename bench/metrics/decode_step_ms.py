"""Engine decode step: mean host time of one engine decode step (dispatch
plus harvest of the step before) over the window, from the engines'
``decode_step_s`` histogram (exact count and sum), ms."""


def read(ctx):
    n, s = ctx.host["decode_step_s"]
    return s / n * 1e3 if n else None
