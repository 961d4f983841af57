"""Load generator: time to first token, p95 over every request due in
the window (first token minus due time, censored at the close), in ms.
The same tail as the harness's ``ttft_p95_ms``, read per layer where
its runs spread too widely to bound it end to end."""
from bench.stats import p95, ttft_s


def read(ctx):
    ttft = ttft_s(ctx.window_reqs, ctx.seconds)
    return p95(ttft) * 1e3 if ttft else None
