"""Model step: mean time of the window's width-1 (all-decode) steps, in
ms, by the host clock around ``Engine.step()`` (which ends in a
device-to-host copy of the sampled tokens)."""
from bench.stats import mean_step_ms


def read(ctx):
    return mean_step_ms([s for s in ctx.steps if s.width == 1])
