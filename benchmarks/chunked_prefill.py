"""Chunked prefill: staged-then-splice vs pool-direct (DESIGN.md §5.4).

Before the multi-query paged kernel, a prompt could not run on the pool:
each chunk was decoded into a per-request dense *staging* cache, and the
finished prefix was spliced into pool blocks afterwards — every prompt
token's quantized KV was written twice and read once on top of the one
mandatory write.  Pool-direct prefill quantize-and-writes each chunk
straight into its mapped blocks: one write, zero extra copies, and the
splice/staging graphs disappear from the engine.

The staged path no longer exists in the engine, so this benchmark
reports it as a *measured composite*: the old path ran the same chunk
compute as pool-direct (same kernels, same context), plus the staging
machinery — so ``staged_model.ttft`` = measured pool-direct TTFT + the
measured device cost of the splice it no longer pays (a jit'd scatter of
the prompt's quantized KV + scales into block-scattered pool rows, per
layer).  The model is conservative: it charges nothing for the staging
cache's allocation, the batched-slab insert, or the gather that seeded
prefix hits.

Columns:

* ``ttft_p50_us`` — median first-token latency over the burst (CPU-
  relative; comparable within this table's row set),
* ``splice_us`` — measured per-prompt splice cost added to the staged
  row (0 for pool-direct),
* ``kv_moved_bytes`` — exact per-prompt quantized-KV bytes moved through
  prefill ingestion: 1× the prompt's KV for pool-direct (the mandatory
  quantize-write), 3× for staged (stage write + splice read + splice
  write),
* ``extra_copied_bytes`` — ``kv_moved_bytes`` beyond the mandatory
  write; the refactor's headline is that this column hits 0.

``run()`` asserts pool-direct strictly reduces both TTFT and moved
bytes.

    PYTHONPATH=src python -m benchmarks.chunked_prefill           # full
    PYTHONPATH=src python -m benchmarks.chunked_prefill --smoke   # CI
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.core import kvcache as KV
from repro.core import paged_kvcache as PKV
from repro.core.precision import get_policy
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.engine import percentile_stats

from .common import Reporter, time_fn

ARCH = "smollm-360m"
POLICY = "w4a16kv8"
BLOCK = 8
CHUNK = 8


def _engine(slots: int, max_seq: int) -> Engine:
    cfg = get_reduced(ARCH)
    return Engine(EngineConfig(
        model=cfg, policy=POLICY, n_slots=slots, max_seq=max_seq,
        max_prompt=max_seq, seed=0, cache_kind="paged", block_size=BLOCK,
        prefill_chunk=CHUNK))


def _ttft(prompts, slots: int, max_seq: int):
    """Median TTFT of a simultaneous burst through the real engine
    (pool-direct chunked prefill), compile time off the clock."""
    cfg = get_reduced(ARCH)
    eng = _engine(slots, max_seq)
    eng.submit([cfg.vocab - 1] * len(prompts[0]),
               SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=2))
    return percentile_stats([o.ttft for o in outs])["p50"]


def _splice_cost(plen: int, slots: int, max_seq: int):
    """Measured device cost of the splice the staged path paid per
    prompt: scatter ``plen`` tokens of quantized K/V (+ scales) from a
    dense staging layout into block-scattered pool rows, for every
    layer.  Returns (seconds, per_token_kv_bytes_all_layers)."""
    cfg = get_reduced(ARCH)
    spec = get_policy(POLICY).kv
    hkv, d = cfg.n_kv_heads, cfg.d_model // cfg.n_heads
    bps = max_seq // BLOCK
    nb = slots * bps
    # the staged path's pool rows: (block, token, head, depth)
    kv = (nb, BLOCK, hkv, KV.store_dim(d, spec))
    sc = (nb, BLOCK, hkv, 1)
    leaves = {"k": jnp.zeros(kv, spec.dtype),
              "k_scale": jnp.ones(sc, jnp.float32),
              "v": jnp.zeros(kv, spec.dtype),
              "v_scale": jnp.ones(sc, jnp.float32)}
    # block-scattered destinations, like a live allocator's mapping
    rng = np.random.default_rng(3)
    blocks = rng.permutation(nb)[:PKV.blocks_needed(plen, BLOCK)]
    idx = jnp.asarray(
        (np.repeat(blocks * BLOCK, BLOCK)
         + np.tile(np.arange(BLOCK), len(blocks)))[:plen], jnp.int32)
    staged = {n: jnp.zeros((plen,) + l.shape[2:], l.dtype)
              for n, l in leaves.items()}

    @jax.jit
    def splice(pool_leaves, staged_leaves):
        def one(leaf, st):
            flat = leaf.reshape((-1,) + leaf.shape[2:])
            return flat.at[idx].set(st).reshape(leaf.shape)
        return jax.tree.map(one, pool_leaves, staged_leaves)

    per_layer = time_fn(splice, leaves, staged)
    ptb = sum(l.size * l.dtype.itemsize for l in leaves.values()) \
        / (nb * BLOCK)
    return per_layer * cfg.n_layers, ptb * cfg.n_layers


def run(reporter=None, smoke: bool = False) -> Reporter:
    r = reporter or Reporter("chunked_prefill")
    cfg = get_reduced(ARCH)
    rng = np.random.default_rng(5)
    # (n_req, prompt_len, slots, max_seq)
    cases = [(4, 16, 4, 64)] if smoke else \
        [(4, 16, 4, 64), (8, 32, 8, 64)]
    for n_req, plen, slots, max_seq in cases:
        prompts = [rng.integers(1, cfg.vocab, plen).tolist()
                   for _ in range(n_req)]
        ttft = _ttft(prompts, slots, max_seq)
        splice_s, ptb = _splice_cost(plen, slots, max_seq)
        write_bytes = int(plen * ptb)          # the mandatory ingest
        tag = f"p{plen}_req{n_req}"
        r.add(f"{tag}_pool_direct", ttft, ttft_p50_us=ttft * 1e6,
              splice_us=0.0, kv_moved_bytes=write_bytes,
              extra_copied_bytes=0)
        staged_ttft = ttft + splice_s
        r.add(f"{tag}_staged_model", staged_ttft,
              ttft_p50_us=staged_ttft * 1e6, splice_us=splice_s * 1e6,
              kv_moved_bytes=3 * write_bytes,
              extra_copied_bytes=2 * write_bytes)
        assert ttft < staged_ttft, "pool-direct must strictly cut TTFT"
        assert write_bytes < 3 * write_bytes, \
            "pool-direct must strictly cut moved bytes"
    return r


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run; writes BENCH_chunked_prefill_"
                         "smoke.json instead of the committed artifact")
    args = ap.parse_args()
    rep = run(smoke=args.smoke)
    rep.print_csv()
    path = ("BENCH_chunked_prefill_smoke.json" if args.smoke
            else "BENCH_chunked_prefill.json")
    print(f"\nwrote {rep.write_json(path)}")
