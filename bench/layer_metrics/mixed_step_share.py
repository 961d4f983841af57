"""Scheduler: share of the window's steps that ran at the prefill-chunk
width because some slot was mid-prompt, in %."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * sum(s.width > 1 for s in ctx.steps) / len(ctx.steps)
