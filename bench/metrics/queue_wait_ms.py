"""Engine admission: mean wait from submit to admission into a slot over
the window, from the engines' ``queue_wait_s`` histogram (its exact count
and sum, window end minus window start), ms."""


def read(ctx):
    n, s = ctx.host["queue_wait_s"]
    return s / n * 1e3 if n else None
