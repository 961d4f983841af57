"""Serving driver: the continuous-batching engine with Poisson arrivals.

Runs the real engine on the default JAX backend and reports throughput
/ TTFT / latency percentiles, the paper's §5 metrics.  Engine knobs come
from :meth:`EngineConfig.add_cli_args` — the same flags the serving
benchmarks use.  ``--reduced`` (the default) serves the 2-layer variant,
which the CPU runs in seconds; ``--full`` serves the published widths.

Usage:
    python -m repro.launch.serve --arch smollm-360m --reduced \
        --requests 32 --rate 4.0 --policy w4a16kv8
    python -m repro.launch.serve --full --cache-kind paged --slots 8 \
        --max-seq 1024 --requests 8 --prompt-len 256 --max-new 32
"""
import argparse
import sys
import time


def main(argv=None) -> int:
    from repro.serving import (Engine, EngineConfig, EngineError,
                               SamplingParams, percentile_stats)

    ap = argparse.ArgumentParser()
    EngineConfig.add_cli_args(ap, max_seq=128)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0, help="req/s (Poisson)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    args = ap.parse_args(argv)

    import numpy as np

    try:
        config = EngineConfig.from_cli(args)
    except EngineError as e:
        print(f"invalid engine configuration: {e}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    devs = jax.devices()
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"devices={len(devs)}", flush=True)
    eng = Engine(config)
    vocab = config.model.vocab
    rng = np.random.default_rng(config.seed)
    # Poisson arrival schedule (paper §5.1: workload from a Poisson process)
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    arrivals = np.cumsum(gaps)
    finished = []
    t_start = eng.now()
    submitted = 0
    while submitted < args.requests or not eng.scheduler.idle:
        now = eng.now() - t_start
        while submitted < args.requests and arrivals[submitted] <= now:
            prompt = rng.integers(1, vocab, size=args.prompt_len).tolist()
            try:
                eng.submit(prompt, SamplingParams(
                    temperature=0.7, top_k=40, max_new_tokens=args.max_new))
            except EngineError as e:
                print(f"rejected request: {e}", file=sys.stderr)
            submitted += 1
        if eng.scheduler.idle:
            time.sleep(0.001)
            continue
        finished.extend(o for o in eng.step() if o.finished)

    total_tokens = sum(len(o.output_token_ids) for o in finished)
    wall = eng.now() - t_start
    print(f"served {len(finished)} requests, {total_tokens} tokens "
          f"in {wall:.2f}s → {total_tokens / wall:.1f} tok/s")
    if config.enable_block_growth:
        print(f"preemptions: {sum(o.num_preemptions for o in finished)} "
              f"(peak live blocks {eng.allocator.peak_live}"
              f"/{eng.n_blocks})")
    print("TTFT percentiles (s):",
          {k: round(v, 3) for k, v in percentile_stats(
              [o.ttft for o in finished]).items()})
    print("latency percentiles (s):",
          {k: round(v, 3) for k, v in percentile_stats(
              [o.latency for o in finished]).items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
