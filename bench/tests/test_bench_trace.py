"""The reduction from a profiler trace to busy time, idle share, per-op
time and idle gaps: its pieces on hand-made intervals, and the whole of
it on a small trace of two engine steps recorded on a TPU v5e."""
from pathlib import Path

from bench import trace_reduce as TRD

DATA = Path(__file__).with_name("data") / "two_steps.xplane.pb"


def test_union_clip_and_gaps():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)]
    merged = TRD.union(ivs)
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert TRD.clip(merged, 1, 12.5) == [(1, 3), (5, 9), (12, 12.5)]
    assert TRD.gaps_of(TRD.clip(merged, 1, 14), 1, 14) == \
        [(3, 5), (9, 12), (13, 14)]


def test_enclosing_ops_are_left_out_of_the_sums():
    evs = [(0, 10, "while"), (1, 3, "a"), (3, 9, "b"), (4, 5, "c"),
           (11, 12, "d")]
    assert [e[2] for e in TRD.leaves(evs)] == ["a", "c", "d"]


def test_gaps_are_named_by_the_host_span_they_overlap_most():
    spans = [("bench.engine_step", 0, 10), ("bench.client", 10, 12)]
    assert TRD.label_gap((9, 12), spans) == "bench.client"
    assert TRD.label_gap((20, 21), spans) == "no benchmark span"


def test_op_names():
    assert TRD.op_name("%fusion.12 = f32[8] fusion(x)") == "fusion.12"


def test_recorded_trace():
    s = TRD.reduce(str(DATA))
    assert s is not None and s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert sum(s.op_s.values()) <= s.busy_s * 1.0001
    assert s.matching_s("paged_kvattn") > 0
    assert s.idle_gaps and s.idle_gaps[0][1] >= s.idle_gaps[-1][1]
    assert {n for n, _ in s.idle_gaps} <= {"bench.engine_step",
                                           "bench.client",
                                           "no benchmark span"}
