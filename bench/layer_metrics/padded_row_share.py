"""Scheduler: share of the rows the window's steps computed that fed no
token, 1 - sum(valid) / (n_slots * step width), in %."""


def read(ctx):
    total = sum(ctx.n_slots * s.width for s in ctx.steps)
    valid = sum(v for s in ctx.steps for _, v in s.rows)
    return 100.0 * (1.0 - valid / total) if total else None
