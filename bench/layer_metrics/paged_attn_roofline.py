"""Attention kernel: the least time the chip needs for the paged
attention kernel's useful work in the traced stretch (bench/work.py:
live K/V and scales at stored width, q and output rows; causal FLOPs),
over the kernel's summed device time in the trace, in %."""
from bench import work

#: the Pallas kernel's name as the device trace shows it
KERNEL = "paged_kvattn"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.trace_steps:
        return None
    kernel_s = ctx.trace.matching_s(KERNEL)
    if kernel_s <= 0:
        return None
    m = vars(ctx.model)
    least = sum(work.least_time(work.attn_flops(m, s.rows),
                                work.paged_attn_bytes(m, s.rows, ctx.kv),
                                ctx.peaks.bf16_flops,
                                ctx.peaks.hbm_bytes_per_s)[0]
                for s in ctx.trace_steps)
    return 100.0 * least / kernel_s
