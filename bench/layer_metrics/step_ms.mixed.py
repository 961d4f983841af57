"""Model step: mean time of the window's prefill-chunk-width (mixed)
steps, in ms, by the host clock around ``Engine.step()``."""
from bench.stats import mean_step_ms


def read(ctx):
    return mean_step_ms([s for s in ctx.steps if s.width > 1])
