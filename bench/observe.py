#!/usr/bin/env python3
"""Serve one cell as the benchmark's traced run does, and print what the
program's own spans, named stages and counters read there.

    python3 bench/observe.py --workload <cell> --seed <n> --seconds <s>

from the checkout root, on the chip.  The window, its traffic and its
traced stretch (the last 10 s) are ``bench/run.py --trace 1``'s; the
engine is wrapped only to keep each output's ``queue_time`` and the
engine's counters after each step.  The last line of standard output is
one JSON object:

* ``metrics``: ``step_host_ms`` (mean ``engine.step`` minus its
  ``engine.wait``), ``queue_wait_p95_ms`` (p95 of ``queue_time`` over the
  window's requests), ``attn_grid_idle_share`` (1 - live / dispatched
  cells of the paged kernel's grid in the window, in %), ``dev_ms.kv_io``,
  ``dev_ms.attn``, ``dev_ms.gemm``, ``dev_ms.sample`` (device ms per step
  program under those stages) and ``dev_unscoped_share`` (% of leaf-op
  time under no stage);
* ``stage_ms``: device ms per step under each stage;
* ``idle_by_phase``: device 0's idle seconds under each innermost span,
  and ``idle_gaps``, its longest idle gaps by the span holding most of
  each;
* ``same_clock``: the share of step programs that run between their
  step's ``engine.dispatch`` start and ``engine.wait`` end, and
  ``clock_offset_ms``, the range of constant shifts of the device clock
  under which all of them would;
* ``stats``: the engine's counters over the window;
* ``step_ms``: host-clock step time by width in the window before the
  traced stretch (spans not recording) and inside it.
Exits non-zero, printing no result, where JAX finds no TPU.
"""
import time

T_START = time.perf_counter()

import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness as H  # noqa: E402
from bench import spec as SP  # noqa: E402
from bench import stages as ST  # noqa: E402
from bench import trace_reduce as TRD  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.loadgen import Traffic  # noqa: E402
from bench.stats import mean_step_ms, p95  # noqa: E402


class Observed:
    """The engine as ``harness.serve`` drives it, keeping each request's
    queue time and the engine's counters after each step."""

    def __init__(self, engine):
        self.engine = engine
        self.queue_s = {}
        self.marks = []          # (perf_counter, EngineStats) per step

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def step(self):
        outs = self.engine.step()
        self.marks.append((time.perf_counter(),
                           copy.deepcopy(self.engine.stats)))
        for o in outs:
            self.queue_s.setdefault(o.rid, o.queue_time)
        return outs


def stats_between(marks, lo: float, hi: float) -> dict:
    """The counters' growth over the steps that ended in (lo, hi]."""
    before = [s for t, s in marks if t <= lo]
    after = [s for t, s in marks if t <= hi]
    a = dataclasses.asdict(before[-1]) if before else None
    b = dataclasses.asdict(after[-1])
    out = {}
    for k, v in b.items():
        if isinstance(v, dict):
            old = a[k] if a else {}
            out[k] = {w: n - old.get(w, 0) for w, n in v.items()}
        else:
            out[k] = v - (a[k] if a else 0)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = SP.load_cell(args.workload)
    H.place_compile_cache()
    try:
        H.accelerator(cell.chips)
    except H.NoAccelerator as e:
        print(f"observe: {e}", file=sys.stderr)
        return 1
    clock = H.CompileClock()
    model = W.Model.from_config(cell.config)
    engine = H.build_engine(cell, args.seed)
    traffic = Traffic(cell.traffic, model.vocab, args.seed, args.seconds)
    H.warm_up(engine, np.random.default_rng([args.seed, 2]), model.vocab)
    obs = Observed(engine)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        t_open, steps, n_compiles, traced = H.serve(
            obs, traffic, args.seconds, trace_dir, clock, record=True)
        xp = TRD.find_xplane(trace_dir)
        t0 = time.perf_counter()
        summary = ST.reduce(xp) if xp else None
        reduce_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if summary is None or traced is None:
        print("observe: the trace holds no device plane or window",
              file=sys.stderr)
        return 1

    window = [r for r in traffic.reqs if 0 <= r.due < args.seconds]
    queued = [obs.queue_s[r.rid] for r in window
              if r.rid in obs.queue_s and obs.queue_s[r.rid] is not None]
    stats = stats_between(obs.marks, t_open, t_open + args.seconds)
    metrics = {
        "step_host_ms": summary.step_host_ms(),
        "queue_wait_p95_ms": p95(queued) * 1e3 if queued else None,
        "attn_grid_idle_share": 100.0 * (
            1 - stats["attn_live_cells"] / stats["attn_cells"])
        if stats["attn_cells"] else None,
        "dev_unscoped_share": summary.unscoped_share()}
    for g in ST.METRIC_GROUPS:
        metrics[f"dev_ms.{g}"] = summary.dev_ms(g)

    def by_width(sel):
        return {"decode": mean_step_ms([s for s in sel if s.width == 1]),
                "mixed": mean_step_ms([s for s in sel if s.width > 1]),
                "steps": len(sel)}

    in_window = [s for s in steps if 0 <= s.t0 and s.t1 <= args.seconds]
    result = {
        "metrics": metrics,
        "stage_ms": {g: s / max(summary.n_steps, 1) * 1e3
                     for g, s in sorted(summary.group_s.items())},
        "steps_traced": summary.n_steps,
        "leaf_s": summary.leaf_s, "window_s": summary.window_s,
        "idle_by_phase": dict(sorted(summary.idle_by_phase.items(),
                                     key=lambda kv: -kv[1])),
        "idle_gaps": summary.idle_gaps,
        "same_clock": summary.same_clock,
        "clock_offset_ms": [x * 1e-6 for x in summary.clock_offset]
        if summary.clock_offset else None,
        "top_ops": summary.top_ops(15),
        "stats": stats,
        "step_ms": {
            "untraced": by_width([s for s in in_window
                                  if s.t1 < traced[0]]),
            "traced": by_width([s for s in in_window
                                if traced[0] <= s.t0
                                and s.t1 <= traced[1]])},
        "compiles_in_window": n_compiles,
        "reduce_s": reduce_s,
        "setup_s": t_open - T_START}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
