"""What the engine tells an operator about itself, at no option's cost:

* the jitted step names its device stages (``jax.named_scope``), so a
  profiler trace can split device time by stage;
* ``Engine.step`` records its host phases as ``TraceAnnotation`` spans
  under ``engine.step``, on the profiler's clock;
* ``Engine.stats`` counts steps, rows and attention grid cells, and every
  output of an admitted request carries its queue time.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.serving import Engine, EngineConfig, SamplingParams

STAGES = ("embed", "layers", "qkv", "kv_append", "attn", "attn_out", "ffn",
          "lm_head", "sample")
PHASES = ("engine.admit", "engine.plan", "engine.feed", "engine.dispatch",
          "engine.wait", "engine.emit")


def _mk(**kw):
    args = dict(n_slots=2, max_seq=32, max_prompt=16, seed=0,
                cache_kind="paged", block_size=4, prefill_chunk=4)
    args.update(kw)
    return Engine(EngineConfig(model=get_reduced("smollm-360m"),
                               policy="w4a16kv8", **args))


@pytest.mark.parametrize("cache_kind", ["paged", "dense"])
def test_step_names_every_stage(cache_kind):
    kw = {} if cache_kind == "paged" else dict(block_size=8)
    eng = _mk(cache_kind=cache_kind, **kw)
    B, T = eng.n_slots, eng.prefill_chunk
    z = lambda dt: np.zeros((B,), dt)  # noqa: E731
    lowered = eng._step.lower(
        eng.params, jnp.zeros((B, T), jnp.int32), eng.cache, z(np.int32),
        z(np.int32), z(np.uint32), z(np.int32), z(np.float32), z(np.int32),
        max_live=8 if cache_kind == "paged" else None)
    hlo = lowered.as_text(dialect="hlo", debug_info=True)
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
              for part in name.split("/")[:-1]}
    assert set(STAGES) <= scopes


def test_served_run_records_each_step_and_its_phases_in_order(tmp_path):
    eng = _mk()
    with jax.profiler.trace(str(tmp_path)):
        eng.generate([[5, 6, 7, 8, 9, 1], [3, 4]],
                     SamplingParams(max_new_tokens=3))
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    spans = sorted((ev.start_ns, -ev.duration_ns, ev.name)
                   for plane in pd.planes for line in plane.lines
                   for ev in line.events if ev.name.startswith("engine."))
    steps = [(a, a - d) for a, d, n in spans if n == "engine.step"]
    assert len(steps) == sum(eng.stats.steps_by_width.values())
    for a, b in steps:
        inner = [(s, s - d, n) for s, d, n in spans
                 if n != "engine.step" and a <= s and s - d <= b]
        assert tuple(n for _, _, n in inner) == PHASES
        ends = [e for _, e, _ in inner]
        starts = [s for s, _, _ in inner]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_stats_count_rows_widths_and_attention_cells():
    eng = _mk()
    eng.generate([[5, 6, 7, 8, 9, 1], [3, 4]],
                 SamplingParams(max_new_tokens=3))
    st = eng.stats
    # step 1 (width 4): the 6-token prompt feeds 4 rows, the 2-token one
    # 2 and emits; step 2 (width 4): 2 rows and 1 decode row; steps 3-4
    # decode, the second request retiring after step 3
    assert st.steps_by_width == {4: 2, 1: 2}
    assert st.rows == eng.n_slots * (4 * 2 + 1 * 2)
    assert st.valid_rows == (4 + 2) + (2 + 1) + (1 + 1) + 1
    # each admission and each retirement uploads its slot's table row
    assert st.table_uploads == 4
    # 4-token blocks; grid blocks = ceil((live bucket + width - 1) / 4):
    # 2, 3, 2, 2 over both slots; live blocks per running slot
    # ceil((pos + valid) / 4): 1+1, 2+1, 2+1, 2
    assert st.attn_cells == 2 * (2 + 3 + 2 + 2)
    assert st.attn_live_cells == 2 + 3 + 3 + 2


def test_queue_time_is_fixed_at_first_admission_across_a_preemption():
    eng = _mk(enable_block_growth=True, n_blocks=4)
    sp = SamplingParams(max_new_tokens=12)
    rids = [eng.submit([5, 6, 7], sp), eng.submit([9, 8, 7, 6, 5], sp),
            eng.submit([1, 2], sp)]
    seen = {rid: [] for rid in rids}
    while not eng.scheduler.idle:
        for out in eng.step():
            seen[out.rid].append((out.num_preemptions, out.queue_time))
    # the younger of the first two is evicted between two emissions
    assert {n for n, _ in seen[rids[1]]} == {0, 1}
    for rid in rids:
        times = {q for _, q in seen[rid]}
        assert len(times) == 1 and None not in times and min(times) >= 0
    # the third waited for a slot
    assert seen[rids[2]][0][1] > seen[rids[0]][0][1]


def test_waiting_request_has_no_queue_time():
    eng = _mk(n_slots=1)
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=4))
    rid = eng.submit([1, 2], SamplingParams(max_new_tokens=2))
    eng.step()
    out = eng.abort(rid)
    assert out.queue_time is None
