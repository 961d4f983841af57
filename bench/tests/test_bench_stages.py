"""The reduction of a trace to the program's stages and phases: the wire
reader against the recorded two-step trace of a TPU v5e step without
stages, its pieces on hand-made spans, and the engine's counters against
what the benchmark's ``Recorder`` reads on the same run."""
from pathlib import Path

import pytest

from bench import harness as H
from bench import stages as ST
from bench import trace_reduce as TRD

DATA = Path(__file__).with_name("data")
UNSCOPED_TRACE = DATA / "two_steps.xplane.pb"


@pytest.fixture(scope="module")
def unscoped():
    spans = ST.host_spans(str(UNSCOPED_TRACE))
    lo, hi = ST.window_of(spans)
    (plane,) = ST.device_planes(str(UNSCOPED_TRACE))
    return plane, ST.leaf_ops(plane, lo, hi)


def test_wire_reader_reads_each_ops_tf_op_at_the_profilers_times(unscoped):
    plane, leaves = unscoped
    from jax.profiler import ProfileData
    (pd,) = [p for p in ProfileData.from_file(str(UNSCOPED_TRACE)).planes
             if p.name == plane.name]
    (line,) = [ln for ln in pd.lines if ln.name == TRD.OPS_LINE]
    ops = plane.lines[TRD.OPS_LINE]
    evs = list(line.events)
    assert [e.name for e in evs] == [e.name for e in ops]
    assert max(abs(a.start_ns - b.start_ns) for a, b in zip(evs, ops)) < 1
    assert max(abs(a.duration_ns - (b.end_ns - b.start_ns))
               for a, b in zip(evs, ops)) < 1
    paths = {ST.op_path(e.tf_op) for _, _, e in leaves
             if TRD.op_name(e.name) == "copy.293"}
    assert paths == {"jit(_step_fn)/while/body/closed_call/reshape"}
    assert [e.name.split("(")[0] for e in plane.lines[ST.MODULES_LINE]] == \
        [ST.STEP_MODULE] * 2


@pytest.mark.parametrize("prefix, ms", [
    ("jit(_step_fn)/while/body/dynamic_slice", 60.0),
    ("jit(_step_fn)/while/body/dynamic_update_slice", 31.0),
    ("jit(_step_fn)/while/body/closed_call/", 111.5),
    ("jit(_step_fn)/jit(sort)/sort", 3.2),
])
def test_leaf_time_of_the_two_steps_by_name_stack(unscoped, prefix, ms):
    _, leaves = unscoped
    by_path = ST.seconds_by(leaves, lambda e: ST.op_path(e.tf_op))
    got = sum(s for p, s in by_path.items() if p.startswith(prefix)) * 1e3
    assert got == pytest.approx(ms, abs=0.1)


def test_a_trace_without_stages_reads_all_unscoped():
    s = ST.reduce(str(UNSCOPED_TRACE))
    assert s.n_steps == 2
    assert set(s.group_s) == {ST.UNSCOPED}
    assert s.unscoped_share() == pytest.approx(100.0)
    assert s.step_host_ms() is None
    # ProfileData truncates times to whole ns, so trace_reduce takes a
    # few ops that end within 1 ns of the next one's start for enclosing
    # ops and leaves them out (14 of 11312 here, 0.15% of the time)
    t = TRD.reduce(str(UNSCOPED_TRACE))
    assert s.leaf_s == pytest.approx(sum(t.op_s.values()), rel=0.01)
    assert sum(s.idle_by_phase.values()) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-3)


@pytest.mark.parametrize("tf_op, group", [
    ("jit(_step_fn)/layers/while/body/closed_call/qkv/dot_general:", "qkv"),
    ("jit(_step_fn)/layers/while/body/closed_call/attn/"
     "jit(paged_kvattn_decode_grouped)/while/body/add:add", "attn"),
    ("jit(_step_fn)/layers/while/body/dynamic_slice:", ST.LAYER_IO),
    ("qkv/reduce_sum:", "qkv"),
    ("jit(_step_fn)/sample/jit(sort)/sort:sort", "sample"),
    ("jit(_step_fn)/while/body/closed_call/reshape:", ST.UNSCOPED),
    ("", ST.UNSCOPED),
])
def test_group_is_the_innermost_stage(tf_op, group):
    assert ST.group_of(tf_op) == group


def test_idle_time_goes_to_the_innermost_engine_span():
    spans = [("bench.engine_step", 0, 100), ("engine.step", 10, 90),
             ("engine.feed", 20, 30), ("engine.wait", 40, 80),
             ("bench.client", 100, 120)]
    gaps = [(0, 25), (35, 45), (85, 110), (125, 130)]
    got = ST.idle_by_phase(gaps, spans)
    assert got == pytest.approx({
        "bench.engine_step": 10e-9 + 10e-9, "engine.step": 10e-9 + 5e-9
        + 5e-9, "engine.feed": 5e-9, "engine.wait": 5e-9,
        "bench.client": 10e-9, ST.NO_SPAN: 5e-9})


def test_steps_with_their_phases_and_the_clock_check():
    spans = []
    for k, t in enumerate((0, 100)):
        spans.append(("engine.step", t, t + 90))
        for i, name in enumerate(ST.PHASES):
            spans.append((name, t + 10 * i, t + 10 * i + 10))
    spans.sort(key=lambda s: (s[1], -s[2]))
    got = ST.step_phases(spans)
    assert [tuple(n for n, _, _ in kids) for _, kids in got] == \
        [ST.PHASES] * 2
    # dispatch is [30, 40) and wait [40, 50) in the first step
    ev = lambda a, b: ST.DeviceEvent(a, b, ST.STEP_MODULE, "")  # noqa: E731
    assert ST.same_clock_share([ev(32, 48), ev(133, 149)], spans) == 1.0
    assert ST.same_clock_share([ev(32, 55), ev(25, 45)], spans) == 0.0
    # the latter two read right with the device clock 5 to 5 late
    assert ST.clock_offset_bounds([ev(32, 48), ev(133, 149)], spans) == \
        (-2, 1)
    assert ST.clock_offset_bounds([ev(32, 45), ev(125, 145)], spans) == \
        (5, 5)


def test_engine_counters_equal_what_the_recorder_reads():
    from repro.configs import get_reduced
    from repro.serving import Engine, EngineConfig, SamplingParams
    eng = Engine(EngineConfig(
        model=get_reduced("smollm-360m"), policy="w4a16kv8", n_slots=3,
        max_seq=32, max_prompt=16, seed=0, cache_kind="paged", block_size=4,
        prefill_chunk=4))
    for p, n in (([5, 6, 7, 8, 9, 1, 2], 3), ([3, 4], 4), ([1], 2),
                 ([9, 9, 9, 9, 9], 2)):
        eng.submit(p, SamplingParams(max_new_tokens=n))
    rec = H.Recorder(eng)
    widths, rows, valid = {}, 0, 0
    while not eng.scheduler.idle:
        snap = rec.before()
        eng.step()
        fed = rec.rows(snap)
        width = eng.prefill_chunk if max(v for _, v in fed) > 1 else 1
        widths[width] = widths.get(width, 0) + 1
        rows += eng.n_slots * width
        valid += sum(v for _, v in fed)
    assert eng.stats.steps_by_width == widths
    assert (eng.stats.rows, eng.stats.valid_rows) == (rows, valid)


STAGED_TRACE = DATA / "two_steps_staged.xplane.pb"


@pytest.fixture(scope="module")
def staged():
    return ST.reduce(str(STAGED_TRACE)), ST.host_spans(str(STAGED_TRACE))


def test_every_leaf_op_of_the_staged_steps_lands_in_one_group(staged):
    s, _ = staged
    assert s.n_steps == 2
    assert set(s.group_s) <= set(ST.STAGES) - {"layers"} | {
        ST.LAYER_IO, ST.UNSCOPED}
    assert {"qkv", "kv_append", "attn", "attn_out", "ffn", "lm_head",
            "sample", ST.LAYER_IO} <= set(s.group_s)
    assert sum(s.group_s.values()) == pytest.approx(s.leaf_s)
    assert s.unscoped_share() < 5.0
    for metric in ST.METRIC_GROUPS:
        assert s.dev_ms(metric) > 0
    # the trace's op names are the HLO instructions' as before: the
    # benchmark's kernel match still finds the paged kernel
    assert TRD.reduce(str(STAGED_TRACE)).matching_s("paged_kvattn") > 0


def test_engine_phases_nest_in_order_in_each_step(staged):
    _, spans = staged
    steps = ST.step_phases(spans)
    assert len(steps) == 2
    outer = [s for s in spans if s[0] == "bench.engine_step"]
    for (name, a, b), kids in steps:
        assert tuple(n for n, _, _ in kids) == ST.PHASES
        assert all(a <= ka and kb <= b for _, ka, kb in kids)
        assert all(k1[2] <= k2[1] for k1, k2 in zip(kids, kids[1:]))
        assert any(oa <= a and b <= ob for _, oa, ob in outer)


def test_step_programs_run_on_the_host_phases_clock(staged):
    s, _ = staged
    assert s.same_clock == 1.0
    lo, hi = s.clock_offset
    assert lo <= 0 <= hi
    assert s.step_host_ms() > 0
    assert sum(s.idle_by_phase.values()) <= s.window_s
