"""Model step: useful FLOPs of the steps in the traced stretch over its
seconds times the chip's bf16 peak, in % (bench/work.py counts the
GEMMs of valid rows, causal attention over each valid row's context,
and the LM head of each emitted token)."""
from bench import work


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.trace_steps:
        return None
    m = vars(ctx.model)
    flops = sum(work.step_flops(m, s.rows, s.emitted)
                for s in ctx.trace_steps)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks.bf16_flops)
