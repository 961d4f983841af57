"""``gemm_dequant_share`` reads the share of the GEMM stages' device time
spent in ops under the program's ``dequant`` scope, from the stage
summary of a trace; without a trace, or in a program without the scope,
it reads nothing."""
from pathlib import Path

import pytest

from bench import spec as SP
from bench import stages as ST
from bench.tests.test_bench_metrics import ctx

STAGED_TRACE = Path(__file__).with_name("data") / "two_steps_staged.xplane.pb"
STEP = "jit(_step_fn)/layers/while/body/closed_call"


def summary(op_s):
    """A stage summary of two steps whose leaf ops are ``op_s``: op ->
    (seconds, name stack), grouped as bench/stages.py groups them."""
    group_s = {}
    for s, path in op_s.values():
        g = ST.group_of(path + ":")
        group_s[g] = group_s.get(g, 0.0) + s
    return ST.StageSummary(
        window_s=1.0, n_steps=2, group_s=group_s, op_s=op_s,
        leaf_s=sum(group_s.values()), step_host_s=[], idle_by_phase={},
        idle_gaps=[], same_clock=None, clock_offset=None)


def read(c):
    return SP.reader("gemm_dequant_share")(c)


def test_share_of_the_gemm_stages_under_dequant():
    op_s = {
        "fusion.1": (0.030, f"{STEP}/ffn/dequant/convert_element_type"),
        "copy.2": (0.010, f"{STEP}/qkv/dequant/mul"),
        "fusion.3": (0.040, f"{STEP}/ffn/dot_general"),
        "fusion.4": (0.020, f"{STEP}/qkv/dot_general"),
        "fusion.5": (0.100, f"{STEP}/attn/pallas_call"),
        "fusion.6": (0.005, "jit(_step_fn)/lm_head/dot_general"),
    }
    got = read(ctx(stages=summary(op_s)))
    assert got == pytest.approx(100 * 0.040 / 0.105)
    # the denominator is the GEMM stages' device time per step x steps
    staged = summary(op_s)
    assert staged.dev_ms("gemm") * staged.n_steps == pytest.approx(105.0)


def test_all_of_the_gemm_time_fused_into_the_dots_reads_zero():
    op_s = {"fusion.3": (0.040, f"{STEP}/ffn/dot_general"),
            "fusion.7": (0.001, f"{STEP}/attn/dequant/mul")}
    assert read(ctx(stages=summary(op_s))) == 0.0


def test_without_a_trace_or_the_scope_nothing_is_read():
    assert read(ctx(stages=None)) is None
    op_s = {"fusion.3": (0.040, f"{STEP}/ffn/dot_general"),
            "fusion.5": (0.100, f"{STEP}/attn/pallas_call")}
    assert read(ctx(stages=summary(op_s))) is None
    # the recorded trace of a program that had no dequant scope
    assert read(ctx(stages=ST.reduce(str(STAGED_TRACE)))) is None
