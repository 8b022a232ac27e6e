"""Load generator: how late the client submitted, p95 over the requests due
in the window (submit minus due time, exact samples, ms).  A starved client
shows here before it is read as a slow server."""
import numpy as np


def read(ctx):
    lags = [r.submit - r.due for r in ctx.due_in_window()]
    return float(np.percentile(lags, 95) * 1e3) if lags else None
