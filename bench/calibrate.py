#!/usr/bin/env python3
"""Readings that a cell's limit is set from: the served tokens' widest
logit gap on many seeds, and the control's on some of them.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 51 --control-seeds 3

One process holds the chip for every seed.  The engine is built and
warmed once; each further seed gets its own weights in place of the last
seed's (same shapes, so no program compiles again), serves the cell's
traffic for the window, and is checked as a run checks it.  The control
is the reference with a 4-bit KV cache (the next precision below the
configuration's 8-bit one) put in the program's place: the tokens it
puts first, at the same positions of the same prompts and served tokens,
go through the same ``check`` against the cell's limit.  Prints one JSON
line per seed.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness as H  # noqa: E402
from bench import spec as SP  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.loadgen import Traffic  # noqa: E402

CONTROL_KV_BITS = 4


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = SP.load_cell(args.workload)
    limit = float(cell.limits["max_logit_gap"])
    H.place_compile_cache()
    try:
        H.accelerator(cell.chips)
    except H.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    model = W.Model.from_config(cell.config)
    clock = H.CompileClock()
    engine = H.build_engine(cell, seeds[0])
    H.warm_up(engine, np.random.default_rng(0), model.vocab)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if i:
            engine.params = W.program_params(model, seed,
                                             cell.config["policy"])
        traffic = Traffic(cell.traffic, model.vocab, seed, args.seconds)
        _, _, n_compiles, _, _ = H.serve(engine, traffic, args.seconds, None,
                                      clock, record=False)
        for r in traffic.reqs:          # unfinished at the close: dropped
            if r.rid is not None and r.done_t is None:
                engine.abort(r.rid)
        sample = H.check_sample(traffic.reqs, args.seconds, seed)
        got = {"seed": seed, "limit": limit}
        runs = [("", None)]
        if i < args.control_seeds:
            runs.append(("control_", CONTROL_KV_BITS))
        for prefix, kv_bits in runs:
            correct, numbers = H.check(model, seed, sample, engine.max_seq,
                                       limit, kv_bits)
            got[prefix + "correct"] = correct
            got.update({prefix + name: v["value"]
                        for name, v in numbers.items()})
        got.update(compiles_in_window=n_compiles,
                   sample_requests=len(sample),
                   seconds=round(time.perf_counter() - t0, 1))
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
