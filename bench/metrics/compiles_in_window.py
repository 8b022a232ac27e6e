"""AOT cache: programs built inside the window, the larger of the fabric
``ExecutableCache``'s build count and the engines' own, window end minus
window start.  Set-up builds every shape the traffic can use, so this
reads 0 unless a program is built on the serving path."""


def read(ctx):
    return float(ctx.host["builds"])
