"""GEMM: share of the GEMM stages' device time (``qkv``, ``attn_out``,
``ffn``, ``lm_head``; bench/stages.py) spent in ops whose name stack
holds the program's ``dequant`` scope (``core/gemm.mp_matmul``'s weight
unpack), in %.

An unpack that XLA fuses into its dot carries the dot's name and counts
as the matmul; what this reads is the unpack, and the relayout of its
output, that run as ops of their own.  A program without the scope has
no such op, and then nothing is read."""
from bench import stages as ST

SCOPE = "dequant"


def read(ctx):
    if ctx.stages is None:
        return None
    gemm = ST.METRIC_GROUPS["gemm"]
    total = sum(ctx.stages.group_s.get(g, 0.0) for g in gemm)
    paths = [path.split("/") for _, path in ctx.stages.op_s.values()]
    if total <= 0 or not any(SCOPE in p[:-1] for p in paths):
        return None
    dequant = sum(s for s, path in ctx.stages.op_s.values()
                  if SCOPE in path.split("/")[:-1]
                  and ST.group_of(path + ":") in gemm)
    return 100.0 * dequant / total
