"""The metric arithmetic: tails over all requests, censored TTFT, rates
over the whole window, and the per-layer readers and work functions."""
import dataclasses

import numpy as np
import pytest

from bench import harness as H
from bench import spec as SP
from bench import work
from bench.loadgen import Req
from bench.trace_reduce import TraceSummary
from bench.weights import Model


def req(due, tokens, submit=None):
    return Req(idx=0, prompt=[1], max_new=len(tokens), due=due,
               submit_t=due if submit is None else submit,
               token_t=list(tokens))


def test_ttft_counts_every_request_due_and_censors_stalls():
    reqs = [req(0.0 + i, [0.1 + i, 0.2 + i]) for i in range(19)]
    reqs.append(req(8.0, []))                   # never served: 10 - 8 = 2 s
    reqs.append(req(-1.0, [5.0]))               # due before the window
    out = H.end_to_end(reqs, 10.0)
    ttft = [0.1] * 10 + [10.0 - 8.0]           # the ones due in [0, 10)
    assert out["ttft_p95_ms"] == pytest.approx(
        np.percentile(ttft, 95) * 1e3)


def test_first_token_reader_reads_the_harness_ttft_tail():
    reqs = [req(0.0 + i, [0.1 + i * 1.01]) for i in range(19)]
    reqs.append(req(8.0, []))                   # never served: censored
    reqs.append(req(-1.0, [5.0]))               # due before the window
    c = ctx(seconds=10.0, window_reqs=[r for r in reqs if 0 <= r.due < 10])
    assert SP.reader("first_token_p95_ms")(c) == pytest.approx(
        H.end_to_end(reqs, 10.0)["ttft_p95_ms"])
    assert SP.reader("first_token_p95_ms")(ctx(window_reqs=[])) is None


def test_itl_pools_every_gap_ending_in_the_window():
    reqs = [req(0.0, [1.0, 1.5, 3.5]), req(0.0, [9.0, 10.5])]
    out = H.end_to_end(reqs, 10.0)
    assert out["itl_p95_ms"] == pytest.approx(
        np.percentile([0.5, 2.0], 95) * 1e3)


def test_rate_is_all_tokens_over_all_window_time():
    reqs = [req(-5.0, [-1.0, 0.5, 2.0]), req(1.0, [4.0, 10.0, 11.0])]
    out = H.end_to_end(reqs, 10.0)
    assert out["output_tok_s"] == pytest.approx(4 / 10.0)


MODEL = Model(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
              d_ff=16, vocab=32, tie_embeddings=True, rope_theta=1e4,
              rotary_pct=1.0, norm_eps=1e-5)


def test_work_counts_by_hand():
    m = vars(MODEL)
    per_layer = 8 * 4 * 2 + 2 * 8 * 2 * 2 + 4 * 2 * 8 + 3 * 8 * 16
    assert work.gemm_params_per_layer(m) == per_layer
    rows = [(3, 1), (0, 2)]          # keys: 4; 1 + 2
    assert work.attn_flops(m, rows) == 4 * 2 * 4 * 2 * (4 + 1 + 2)
    assert work.step_flops(m, rows, emitted=1) == \
        2 * 2 * per_layer * 3 + work.attn_flops(m, rows) + 2 * 8 * 32
    per_token = 2 * 2 * (2 * 1.0 + 4)
    assert work.paged_attn_bytes(m, rows, "kv8") == \
        2 * ((4 + 2) * per_token + 3 * (2 * 4 * 2 * 2))
    assert work.least_time(10.0, 1.0, 10.0, 10.0) == (1.0, "compute")
    assert work.least_time(1.0, 10.0, 10.0, 10.0) == (1.0, "memory")


def ctx(**kw):
    steps = [H.Step(0.0, 0.1, 1, [(5, 1), (9, 1)], 2),
             H.Step(0.1, 0.4, 16, [(0, 16), (7, 1)], 1),
             H.Step(0.4, 0.5, 1, [(6, 1)], 1)]
    base = dict(model=MODEL, kv="kv8", n_slots=4, seconds=1.0,
                window_reqs=[req(0.1, [0.3], submit=0.15)], steps=steps,
                trace_steps=steps, compiles_in_window=0,
                trace=TraceSummary(window_s=0.5, busy_s=0.4, n_devices=1,
                                   op_s={"fusion.1": 0.1,
                                         "paged_kvattn.3": 0.2},
                                   op_detail={}, idle_gaps=[]),
                peaks=H.PK.peaks_for("TPU v5 lite"))
    base.update(kw)
    return H.Context(**base)


def read(name, c):
    return SP.reader(name)(c)


def test_scheduler_readers():
    c = ctx()
    total = 4 * 1 + 4 * 16 + 4 * 1
    assert read("padded_row_share", c) == pytest.approx(
        100 * (1 - (2 + 17 + 1) / total))
    assert read("mixed_step_share", c) == pytest.approx(100 / 3)


def test_step_time_readers_need_a_quarter_second():
    c = ctx()
    assert read("step_ms.mixed", c) == pytest.approx(300.0)
    assert read("step_ms.decode", c) is None          # 0.2 s in all
    long = dataclasses.replace(c, steps=c.steps + [
        H.Step(0.5, 0.6, 1, [(7, 1)], 1)])
    assert read("step_ms.decode", long) == pytest.approx(100.0)


def test_device_readers():
    c = ctx()
    assert read("device_idle_share", c) == pytest.approx(20.0)
    least = sum(work.least_time(
        work.attn_flops(vars(MODEL), s.rows),
        work.paged_attn_bytes(vars(MODEL), s.rows, "kv8"),
        197e12, 819e9)[0] for s in c.steps)
    assert read("paged_attn_roofline", c) == pytest.approx(
        100 * least / 0.2)
    flops = sum(work.step_flops(vars(MODEL), s.rows, s.emitted)
                for s in c.steps)
    assert read("step_mfu", c) == pytest.approx(100 * flops / (0.5 * 197e12))
    assert read("gen_lag_p95_ms", c) == pytest.approx(50.0)
    assert read("compiles_in_window", c) == 0


def test_readers_without_a_trace_read_nothing():
    c = ctx(trace=None, window_reqs=[])
    for name in ("device_idle_share", "paged_attn_roofline", "step_mfu",
                 "gen_lag_p95_ms"):
        assert read(name, c) is None
