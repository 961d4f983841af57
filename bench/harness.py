"""One run of one cell: build the served model from the seed, warm up,
serve the cell's traffic for the window, report, and check the served
tokens against the plain reference.

The program under test is the serving engine (``repro.serving.Engine``),
driven only through ``submit`` and ``step``.  Its scheduler's request
states are read, never changed, in the traced run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import peaks as PK
from bench import reference as REF
from bench import spec as SP
from bench import trace_reduce as TRD
from bench import weights as W
from bench.stats import p95, ttft_s
from bench.loadgen import Req, Traffic

#: the traced run traces the window's last seconds, at most this many
TRACE_S = 10.0
#: requests whose served tokens the reference checks
CHECK_REQUESTS = 8


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Counts backend compiles and their seconds (``jax.monitoring``)."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.secs += duration


def place_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    the fixed ``<checkout>/.jax_cache``; every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(SP.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def accelerator(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs


@dataclasses.dataclass
class Step:
    t0: float                      # seconds from window open
    t1: float
    width: int                     # tokens fed per slot this step
    rows: List[Tuple[int, int]]    # (first position, valid) per slot fed
    emitted: int


@dataclasses.dataclass
class Context:
    """What the per-layer readers read (see bench/layer_metrics/)."""

    model: W.Model
    kv: str                        # KV format of the policy, e.g. "kv8"
    n_slots: int
    seconds: float
    window_reqs: List[Req]
    steps: List[Step]              # steps that ran inside the window
    trace_steps: List[Step]        # steps inside the traced stretch
    compiles_in_window: int
    trace: Optional[TRD.TraceSummary]
    peaks: Optional[PK.Peaks]


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    m = config["model"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], rope_theta=m["rope_theta"],
        rotary_pct=m["rotary_pct"], norm_eps=m["norm_eps"],
        tie_embeddings=m["tie_embeddings"])


def build_engine(cell: SP.Cell, seed: int):
    from repro.serving import Engine, EngineConfig
    params = W.program_params(W.Model.from_config(cell.config), seed,
                              cell.config["policy"])
    return Engine(EngineConfig(model=model_config(cell.config),
                               policy=cell.config["policy"],
                               **cell.traffic["engine"]), params=params)


# -- warm-up ------------------------------------------------------------------


def live_bucket(hw: int, block: int, bps: int) -> int:
    """The engine's static live-context bound (in blocks) for a batch
    whose highest fed position is ``hw - 1``."""
    nb = -(-hw // block)
    return min(1 << (nb - 1).bit_length(), bps)


def warmup_prompts(max_seq: int, block: int, chunk: int,
                   max_prompt: int) -> List[int]:
    """Prompt lengths whose decode steps land in every live bucket: each
    is the shortest whose first decode step lands in its bucket, and the
    last also prefills a chunk in the top bucket."""
    bps = max_seq // block
    top = live_bucket(max_seq, block, bps)
    lens: Dict[int, int] = {}
    for p in range(2, min(max_prompt, max_seq - 1) + 1):
        lens.setdefault(live_bucket(p + 1, block, bps), p)
    out = sorted(lens.values())
    for p in range(out[-1], min(max_prompt, max_seq - 1) + 1):
        if live_bucket(chunk * ((p - 2) // chunk) + 1, block, bps) == top:
            out[-1] = p
            break
    return out


def warm_up(engine, rng: np.random.Generator, vocab: int) -> None:
    """Compile, or load from the persistent cache, every program the
    window can run: the step at both widths and every live bucket, and
    the engine's eager block-table update at every block count.

    Each warm-up prompt is served with three output tokens: its prefill
    chunks, one decode step alone in its bucket, and one step beside a
    newly admitted two-token prompt, which widens the step to the chunk
    in the same bucket."""
    import jax.numpy as jnp
    from repro.serving import SamplingParams
    bps, nb = engine.blocks_per_slot, engine.n_blocks
    for n in range(1, bps + 1):
        row = jnp.full((bps,), nb, jnp.int32)
        row.at[:n].set(jnp.asarray(list(range(n)), jnp.int32)) \
            .block_until_ready()
    for p in warmup_prompts(engine.max_seq, engine.block_size,
                            engine.prefill_chunk, engine.max_prompt):
        rid = engine.submit(rng.integers(1, vocab, p).tolist(),
                            SamplingParams(temperature=0.0, max_new_tokens=3))
        emitted = 0
        while emitted < 2:
            emitted += sum(o.rid == rid for o in engine.step())
        engine.generate([rng.integers(1, vocab, 2).tolist()],
                        SamplingParams(temperature=0.0, max_new_tokens=1))


# -- the served loop ----------------------------------------------------------


class Recorder:
    """Reads which rows each step fed, from the scheduler's request
    states before and after the step (traced runs only)."""

    def __init__(self, engine):
        self.sched = engine.scheduler

    def before(self):
        free = len(self.sched.free_slots())
        reqs = self.sched.running() + list(self.sched.waiting)[:free]
        return [(r, r.pos) for r in reqs]

    @staticmethod
    def rows(snapshot) -> List[Tuple[int, int]]:
        return [(p0, r.pos - p0) for r, p0 in snapshot if r.pos > p0]


def serve(engine, traffic: Traffic, seconds: float, trace_dir: Optional[str],
          clock: CompileClock, record: bool):
    """Serve the traffic from its warm-up through the window's close.

    Returns (window open in perf_counter seconds, steps, compiles in the
    window, traced stretch (start, stop) from window open or None)."""
    import jax
    from repro.serving import SamplingParams
    from repro.serving.config import EngineError

    ann = (jax.profiler.TraceAnnotation if trace_dir
           else (lambda _name: contextlib.nullcontext()))
    rec = Recorder(engine) if record else None
    live: Dict[int, Req] = {}
    steps: List[Step] = []
    t_open = time.perf_counter() + traffic.warmup_s
    trace_at = max(0.0, seconds - TRACE_S)
    compiles0 = None
    tracing = None
    traced = None
    while True:
        now = time.perf_counter() - t_open
        if compiles0 is None and now >= 0:
            compiles0 = clock.n
        if trace_dir and traced is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            tracing = jax.profiler.TraceAnnotation(TRD.WINDOW_SPAN)
            tracing.__enter__()
            traced = [time.perf_counter() - t_open, None]
        if now >= seconds:
            break
        with ann("bench.generator"):
            for r in traffic.pop_due(now):
                try:
                    r.rid = engine.submit(r.prompt, SamplingParams(
                        temperature=0.0, max_new_tokens=r.max_new))
                    live[r.rid] = r
                except EngineError:
                    r.failed = True
                r.submit_t = time.perf_counter() - t_open
        if not live:
            nxt = traffic.next_due()
            with ann("bench.wait_arrival"):
                wait = (seconds if nxt is None else min(nxt, seconds)) - now
                time.sleep(max(0.0, min(wait, 0.05)))
            continue
        snap = rec.before() if rec else None
        t0 = time.perf_counter() - t_open
        with ann("bench.engine_step"):
            outs = engine.step()
        t1 = time.perf_counter() - t_open
        with ann("bench.client"):
            if rec:
                rows = rec.rows(snap)
                width = max((v for _, v in rows), default=1)
                steps.append(Step(t0, t1, engine.prefill_chunk
                                  if width > 1 else 1, rows, len(outs)))
            for o in outs:
                r = live[o.rid]
                r.token_t.append(t1)
                if o.finished:
                    r.output = list(o.output_token_ids)
                    r.done_t = t1
                    del live[o.rid]
    n_window = clock.n - (compiles0 if compiles0 is not None else clock.n)
    if tracing is not None:
        traced[1] = time.perf_counter() - t_open
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return t_open, steps, n_window, traced


# -- metrics ------------------------------------------------------------------


def end_to_end(reqs: List[Req], seconds: float) -> Dict[str, float]:
    """Client-side metrics of the window [0, seconds)."""
    ttft = ttft_s(reqs, seconds)
    itl = [b - a for r in reqs for a, b in zip(r.token_t, r.token_t[1:])
           if 0 <= b <= seconds]
    tokens = sum(1 for r in reqs for t in r.token_t if 0 <= t <= seconds)
    out = {"output_tok_s": tokens / seconds}
    if ttft:
        out["ttft_p95_ms"] = p95(ttft) * 1e3
    if itl:
        out["itl_p95_ms"] = p95(itl) * 1e3
    return out


# -- correctness --------------------------------------------------------------


def check_sample(reqs: List[Req], seconds: float, seed: int,
                 k: int = CHECK_REQUESTS) -> List[Req]:
    """Requests finished in the window: the longest, and ``k - 1`` more
    drawn from the seed."""
    done = [r for r in reqs if r.done_t is not None and 0 <= r.done_t
            <= seconds and r.output]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.output), r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_inputs(sample: List[Req], max_seq: int):
    """Padded token rows, the positions whose logits chose each served
    token, the served tokens, and the mask of real entries."""
    n_out = max(len(r.output) for r in sample)
    tokens = np.zeros((len(sample), max_seq), np.int32)
    rows = np.zeros((len(sample), n_out), np.int32)
    served = np.zeros((len(sample), n_out), np.int32)
    mask = np.zeros((len(sample), n_out), bool)
    for i, r in enumerate(sample):
        seq = r.prompt + r.output[:-1]
        tokens[i, :len(seq)] = seq
        n = len(r.output)
        rows[i, :n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
        served[i, :n] = r.output
        mask[i, :n] = True
    return tokens, rows, served, mask


def logit_gaps(ref, chosen, mask) -> np.ndarray:
    """How far below the reference's best logit each chosen token's
    reference logit lies, over the real entries."""
    import jax.numpy as jnp
    best = jnp.max(ref, -1)
    at = jnp.take_along_axis(ref, jnp.asarray(chosen)[..., None], -1)[..., 0]
    return np.asarray(best - at)[mask]


def check(model: W.Model, seed: int, sample: List[Req], max_seq: int,
          limit: float, control_kv_bits: Optional[int] = None):
    """``correct``, and each number it compares beside its limit: the
    widest gap by which a served token's reference logit lies below the
    reference's best at its position, and how many tokens were compared.

    With ``control_kv_bits`` the control is judged in the program's
    place: the tokens that the reference with a KV cache of that many
    bits puts first, at the same positions of the same streams."""
    if sample:
        tokens, rows, chosen, mask = reference_inputs(sample, max_seq)
        ref = REF.logits_at(model, seed, tokens, rows)
        if control_kv_bits is not None:
            import jax.numpy as jnp
            ctrl = REF.logits_at(model, seed, tokens, rows,
                                 kv_bits=control_kv_bits)
            chosen = np.asarray(jnp.argmax(ctrl, -1))
        gap = float(np.max(logit_gaps(ref, chosen, mask)))
        n = int(mask.sum())
    else:
        gap, n = math.inf, 0
    numbers = {"max_logit_gap": {"value": gap, "limit": limit},
               "served_tokens": {"value": n, "limit": 1}}
    return bool(gap <= limit and n >= 1), numbers


# -- one run ------------------------------------------------------------------


def run_cell(cell: SP.Cell, seed: int, seconds: float, trace: bool,
             t_start: float):
    """One run.  Returns (result line as a dict, check lines)."""
    devs = accelerator(cell.chips)
    dev = devs[0]
    clock = CompileClock()
    model = W.Model.from_config(cell.config)
    phases = [("device", time.perf_counter())]
    engine = build_engine(cell, seed)
    phases.append(("weights and engine", time.perf_counter()))
    traffic = Traffic(cell.traffic, model.vocab, seed, seconds)
    warm_up(engine, np.random.default_rng([seed, 2]), model.vocab)
    phases.append(("warm-up", time.perf_counter()))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        t_open, steps, n_compiles, traced = serve(
            engine, traffic, seconds, trace_dir, clock, record=trace)
        setup_s = t_open - t_start
        phases.append(("traffic warm-up", t_open))
        print("setup: " + ", ".join(
            f"{name} {b - a:.1f} s" for (_, a), (name, b)
            in zip([("start", t_start)] + phases[:-1], phases))
            + f"; {clock.n} compiles ({clock.secs:.1f} s) before the window",
            file=sys.stderr)
        summary = None
        if trace_dir:
            xp = TRD.find_xplane(trace_dir)
            summary = TRD.reduce(xp) if xp else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    max_seq, n_slots = engine.max_seq, engine.n_slots
    del engine
    gc.collect()

    reqs = traffic.reqs
    window = [r for r in reqs if 0 <= r.due < seconds]
    e2e = end_to_end(reqs, seconds)
    e2e["setup_s"] = setup_s
    print("client: " + ", ".join(f"{k} {v}" for k, v in sorted(e2e.items())),
          file=sys.stderr)
    result = {"correct": False, "attempted": len(window),
              "failed": sum(r.failed for r in window)}
    if trace:
        peaks = PK.peaks_for(dev.device_kind)
        in_trace = [s for s in steps if traced and traced[0] <= s.t0
                    and s.t1 <= traced[1]]
        ctx = Context(model=model,
                      kv="kv" + cell.config["policy"].split("kv", 1)[1],
                      n_slots=n_slots, seconds=seconds,
                      window_reqs=window,
                      steps=[s for s in steps if 0 <= s.t0
                             and s.t1 <= seconds],
                      trace_steps=in_trace, compiles_in_window=n_compiles,
                      trace=summary, peaks=peaks)
        result["metrics"] = SP.read_layer_metrics(cell.per_layer, ctx)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": summary.top_ops(10),
                "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        names = [m["name"] for m in cell.end_to_end]
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in e2e}
        missing = set(names) - set(result["metrics"])
        if missing:
            raise RuntimeError(f"no reading of {sorted(missing)}")
    result["device"] = device

    result["correct"], result["check"] = check(
        model, seed, check_sample(reqs, seconds, seed), max_seq,
        float(cell.limits["max_logit_gap"]))
    lines = [f"check {name} {v['value']} limit {v['limit']}"
             for name, v in result["check"].items()]
    return result, lines


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    cell = SP.load_cell(args.workload)
    place_compile_cache()
    try:
        result, lines = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    import json
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
