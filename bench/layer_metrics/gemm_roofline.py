"""GEMM: the least time the chip needs for a traced step's GEMM work
(bench/work.py: every packed weight once at its stored width with its
scales, the bf16 LM head, the valid rows' activations; the projections,
MLP and head FLOPs), mean over the traced steps, over the device time
per step under the program's GEMM stages (``qkv``, ``attn_out``,
``ffn``, ``lm_head``; bench/stages.py), in %.

The compute bound is the bf16 peak: a w4a16 GEMM unpacks its int4 codes
to bf16 and multiplies them with bf16 activations on the MXU; the int8
peak holds only where both operands are 8-bit."""
from bench import work


def read(ctx):
    if ctx.stages is None or ctx.peaks is None or not ctx.trace_steps:
        return None
    dev_ms = ctx.stages.dev_ms("gemm")
    if not dev_ms:
        return None
    m = vars(ctx.model)
    least = sum(work.least_time(
        work.gemm_flops(m, s.rows, s.emitted),
        work.gemm_bytes(m, s.rows, s.emitted, ctx.policy),
        ctx.peaks.bf16_flops, ctx.peaks.hbm_bytes_per_s)[0]
        for s in ctx.trace_steps) / len(ctx.trace_steps)
    return 100.0 * least * 1e3 / dev_ms
