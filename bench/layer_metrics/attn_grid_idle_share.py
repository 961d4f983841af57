"""Attention kernel: share of the paged kernel's (slot, block) grid cells
dispatched in the window that held no running slot's live context,
1 - live / dispatched over the engine's own counters (``Engine.stats``),
in %."""


def read(ctx):
    if not ctx.stats or not ctx.stats["attn_cells"]:
        return None
    return 100.0 * (1.0 - ctx.stats["attn_live_cells"]
                    / ctx.stats["attn_cells"])
