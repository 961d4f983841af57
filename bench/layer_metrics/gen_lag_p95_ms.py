"""Load generator: how late requests were submitted, p95 over the
requests due in the window (submit time minus due time), in ms."""
from bench.stats import p95


def read(ctx):
    lags = [r.submit_t - r.due for r in ctx.window_reqs
            if r.submit_t is not None]
    return p95(lags) * 1e3 if lags else None
