#!/usr/bin/env python3
"""Chip smoke: the serving engine's main path once on one TPU, at
SmolLM-360M's published widths (32 layers, d_model 960, 15 heads / 5 KV
heads, d_ff 2560, vocab 49152), with random weights from a fixed seed.

This is a smoke, not a benchmark: the times it prints are there to show
where a run spent itself, and are not metrics.

    python chip_smoke.py

Phases, in one process that holds the chip from start to end:

1. place JAX's persistent compilation cache (repro.launch.compile_cache);
2. print ``jax.devices()``; exit non-zero unless the platform is ``tpu``,
   and check that the Pallas kernels run compiled, not interpreted;
3. kernels: the dense and paged attention kernels against
   ``kernels/ref.py`` at full width, for kv16, kv8, kv4 and fp8 KV, at
   T = 1 and T = 32 query tokens per slot;
4. serving: the paged ``w4a16kv8`` engine serves 8 seeded prompts of
   200-600 tokens, 32 greedy tokens each; its compiled step must hold the
   Pallas kernels (``tpu_custom_call``), its logits must be finite, and a
   dense engine with the same seed must give byte-identical streams.

Any failed phase raises and the script exits non-zero.  The last line of
standard output is one JSON object naming the device, printed only when
every phase passed.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
SEED = 0
#: max |kernel - reference| allowed per KV format.  The reference attends
#: in float32 over the same dequantized KV; the kernel rounds dequantized
#: K/V and the softmax weights to bf16 for the MXU, and int4/fp8 tiles
#: carry coarser values into that rounding.
ATTN_TOL = {"kv16": 0.03, "kv8": 0.03, "kv4": 0.1, "kvfp8": 0.1}
SERVE = dict(n_slots=8, max_seq=1024, block_size=16, prefill_chunk=32)
N_PROMPTS, PROMPT_LEN, MAX_NEW = 8, (200, 600), 32


class CompileClock:
    """Counts backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.secs += duration


def _paged_twin(k, v, spec, bs: int, seed: int):
    """A paged pool holding what ``KV.append(..., k, v, 0, spec)`` holds,
    written through ``append_paged`` into shuffled pool blocks."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import paged_kvcache as PKV
    B, S, H, D = k.shape
    bps = S // bs
    perm = np.random.default_rng(seed).permutation(B * bps)
    paged = PKV.init_paged(B, B * bps, bs, H, D, spec, blocks_per_slot=bps)
    paged = dataclasses.replace(
        paged, block_table=jnp.asarray(perm.reshape(B, bps), jnp.int32))
    return PKV.append_paged(paged, k, v, jnp.zeros((B,), jnp.int32), spec)


def kernel_phase(cfg, *, B=4, S=512, bs=16) -> None:
    """Dense and paged attention kernels vs the float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kvcache as KV
    from repro.core.precision import get_policy
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    key = jax.random.PRNGKey(SEED)
    for fmt, tol in ATTN_TOL.items():
        spec = get_policy(f"w4a16{fmt}").kv
        kv = jax.random.normal(key, (2, B, S, Hkv, D), jnp.float32)
        k, v = kv[0].astype(jnp.bfloat16), kv[1].astype(jnp.bfloat16)
        dense = KV.append(KV.init_cache(B, S, Hkv, D, spec), k, v, 0, spec)
        paged = _paged_twin(k, v, spec, bs, SEED)
        for T in (1, SERVE["prefill_chunk"]):
            q = jax.random.normal(jax.random.fold_in(key, T), (B, T, H, D),
                                  jnp.float32).astype(jnp.bfloat16)
            pos = jnp.asarray(
                np.random.default_rng(T).integers(0, S - T, B), jnp.int32)
            out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=bs)
            out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
            with jax.default_matmul_precision("highest"):
                ref = jnp.concatenate([
                    kref.kvattn_ref(q[b:b + 1],
                                    jax.tree.map(lambda a: a[b:b + 1], dense),
                                    spec, int(pos[b]))
                    for b in range(B)])
            f32 = lambda a: np.asarray(a, np.float32)
            err_d = float(np.max(np.abs(f32(out_d) - f32(ref))))
            err_p = float(np.max(np.abs(f32(out_p) - f32(ref))))
            same = bool(np.array_equal(f32(out_d), f32(out_p)))
            print(f"kernel {fmt:5s} T={T:2d}: max|dense-ref|={err_d} "
                  f"max|paged-ref|={err_p} tol={tol} "
                  f"dense==paged bitwise: {same}", flush=True)
            if not (np.isfinite(err_d) and np.isfinite(err_p)):
                raise AssertionError(f"{fmt} T={T}: non-finite output")
            if max(err_d, err_p) > tol:
                raise AssertionError(f"{fmt} T={T}: error above {tol}")
            if not same:
                raise AssertionError(f"{fmt} T={T}: dense != paged")


def _prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_PROMPTS)
    return [rng.integers(1, vocab, n).tolist() for n in lens]


def _serve(engine, prompts, clock, label):
    from repro.serving import SamplingParams
    c0, s0, t0 = clock.n, clock.secs, time.perf_counter()
    outs = engine.generate(
        prompts, SamplingParams(temperature=0.0, max_new_tokens=MAX_NEW))
    wall = time.perf_counter() - t0
    print(f"serve {label}: {len(outs)} requests, {engine.iteration} steps, "
          f"{clock.n - c0} compiles ({clock.secs - s0:.1f} s), "
          f"wall {wall:.1f} s (smoke, not a benchmark)", flush=True)
    return outs


def _step_hlo(engine, t_step: int) -> str:
    """Optimized HLO of the engine's jitted step at width ``t_step``."""
    import jax.numpy as jnp
    import numpy as np
    B = engine.n_slots
    z = lambda dt: jnp.zeros((B,), dt)
    return engine._step.lower(
        engine.params, jnp.zeros((B, t_step), jnp.int32), engine.cache,
        z(jnp.int32), z(jnp.int32), np.zeros((B,), np.uint32), z(jnp.int32),
        z(jnp.float32), z(jnp.int32),
        max_live=engine.max_seq if engine.cache_kind == "paged" else None,
    ).compile().as_text()


def _first_chunk_logits(engine, prompts):
    """Logits of each prompt's first chunk through ``decode_step`` over a
    fresh paged cache (slot b mapped to pool blocks b*bps...)."""
    import jax
    import jax.numpy as jnp
    B, T = engine.n_slots, engine.prefill_chunk
    cache = engine.model.init_paged_cache(
        engine.policy, B, engine.n_blocks, engine.block_size,
        engine.blocks_per_slot)
    tbl = jnp.arange(B * engine.blocks_per_slot, dtype=jnp.int32).reshape(
        B, engine.blocks_per_slot)
    cache = dataclasses.replace(cache, block_table=tbl)
    tokens = jnp.asarray([p[:T] for p in prompts[:B]], jnp.int32)
    fn = jax.jit(lambda p, tok, c: engine.model.decode_step(
        p, engine.policy, tok, c, jnp.zeros((B,), jnp.int32),
        attn_impl="pallas", max_live=T)[0])
    return fn(engine.params, tokens, cache)


def serve_phase(model_cfg, clock) -> None:
    """Paged w4a16kv8 engine at full width; dense twin byte-identical."""
    import numpy as np

    from repro.serving import Engine, EngineConfig
    vocab = model_cfg.vocab
    prompts = _prompts(vocab)
    print(f"prompts: {[len(p) for p in prompts]} tokens", flush=True)
    streams = {}
    for kind in ("paged", "dense"):
        t0 = time.perf_counter()
        engine = Engine(EngineConfig(
            model=model_cfg, policy="w4a16kv8", cache_kind=kind,
            attn_impl="kernel", seed=SEED, **SERVE))
        print(f"engine {kind}: built in {time.perf_counter() - t0:.1f} s, "
              f"KV store {engine.kv_resident_bytes()} bytes", flush=True)
        outs = _serve(engine, prompts, clock, kind)
        for o in outs:
            if not o.finished or len(o.output_token_ids) != MAX_NEW:
                raise AssertionError(f"{kind}: request {o.rid} unfinished "
                                     f"({o.finish_reason}, "
                                     f"{len(o.output_token_ids)} tokens)")
            if not all(0 <= t < vocab for t in o.output_token_ids):
                raise AssertionError(f"{kind}: token out of vocabulary")
        streams[kind] = [o.output_token_ids for o in outs]
        for t_step in (1, engine.prefill_chunk):
            n = _step_hlo(engine, t_step).count("tpu_custom_call")
            print(f"{kind} step T={t_step}: {n} tpu_custom_call in the "
                  "compiled HLO", flush=True)
            if not n:
                raise AssertionError(f"{kind} step holds no Pallas kernel")
        if kind == "paged":
            logits = np.asarray(_first_chunk_logits(engine, prompts),
                                np.float32)
            print(f"paged first-chunk logits: shape {logits.shape}, "
                  f"finite {bool(np.isfinite(logits).all())}", flush=True)
            if logits.shape != (engine.n_slots, vocab) or \
                    not np.isfinite(logits).all():
                raise AssertionError("paged logits are not finite")
        del engine
    same = streams["paged"] == streams["dense"]
    print(f"dense == paged greedy streams: {same}", flush=True)
    if not same:
        raise AssertionError("dense and paged streams differ")


def main() -> int:
    from repro.launch.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    t_start = time.perf_counter()

    import jax
    devs = jax.devices()
    print(f"devices: {devs}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)
    if devs[0].platform != "tpu":
        print(f"chip smoke needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    from repro.kernels import ops
    if ops.INTERPRET is not False:
        raise AssertionError("Pallas kernels would run interpreted")

    from repro.configs import get_config
    cfg = get_config(ARCH)
    clock = CompileClock()
    t0 = time.perf_counter()
    kernel_phase(cfg)
    print(f"kernel phase: {time.perf_counter() - t0:.1f} s", flush=True)
    t0 = time.perf_counter()
    serve_phase(cfg, clock)
    print(f"serve phase: {time.perf_counter() - t0:.1f} s; total "
          f"{time.perf_counter() - t_start:.1f} s, {clock.n} compiles "
          f"({clock.secs:.1f} s) (smoke, not a benchmark)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
