"""Paged Pallas decode kernel: in-kernel block-table indirection.

Three-way equivalence, interpret mode on CPU:

* **paged kernel == dense kernel, bitwise** — both run the shared
  ``flash_block_update`` over bit-identical KV tiles at equal block
  granularity, so outputs must match to the bit (this is what keeps the
  serving engine's dense and paged backends byte-identical).
* **paged kernel ≈ fused XLA / oracle** — float tolerance, every
  FormatSpec.
* Edge cases: ragged per-slot lengths, sentinel (unmapped) table
  entries, sliding windows (including the traced NO_WINDOW sentinel),
  one-block tables, partial last blocks, and live-context-bounded grids.
* **No dense gather**: the whole paged decode path — kernel wrapper and
  a full paged engine run — works with ``gather_view`` poisoned.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kvcache as KV
from repro.core import paged_kvcache as PKV
from repro.core.precision import get_policy
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.kvattn import NO_WINDOW

FMTS = ["kv16", "kv8", "kv4", "kvfp8"]


def _spec(fmt):
    return get_policy(f"w4a16{fmt}").kv


def _paired(key, fmt, B=2, S=64, Hkv=2, D=32, bs=8, lengths=None,
            shuffle=True):
    """Dense cache + paged twin holding identical logical KV.

    ``lengths[b]`` tokens are written to slot ``b`` (default: full S) and
    only the blocks needed for them are mapped — the tail of each table
    row keeps the sentinel, like a live engine slot mid-decode.  Pool
    block order is shuffled so logical and physical orders differ.
    """
    spec = _spec(fmt)
    lengths = [S] * B if lengths is None else lengths
    bps = S // bs
    n_blocks = B * bps + 3
    dense = KV.init_cache(B, S, Hkv, D, spec)
    paged = PKV.init_paged(B, n_blocks, bs, Hkv, D, spec,
                           blocks_per_slot=bps)
    order = list(range(n_blocks))
    if shuffle:
        rng = np.random.default_rng(7)
        rng.shuffle(order)
    tbl = paged.block_table
    nxt = 0
    for b in range(B):
        need = PKV.blocks_needed(lengths[b], bs)
        tbl = tbl.at[b, :need].set(
            jnp.asarray(order[nxt:nxt + need], jnp.int32))
        nxt += need
    paged = dataclasses.replace(paged, block_table=tbl)
    for b in range(B):
        t = lengths[b]
        k = jax.random.normal(jax.random.fold_in(key, 2 * b),
                              (1, t, Hkv, D), jnp.float32) \
            .astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2 * b + 1),
                              (1, t, Hkv, D), jnp.float32) \
            .astype(jnp.bfloat16)
        d1 = KV.append(jax.tree.map(lambda a: a[b:b + 1], dense),
                       k, v, 0, spec)
        dense = jax.tree.map(lambda full, one: full.at[b:b + 1].set(one),
                             dense, d1)
        prow = dataclasses.replace(
            paged, block_table=paged.block_table[b:b + 1])
        prow = PKV.append_paged(prow, k, v, jnp.zeros((1,), jnp.int32),
                                spec)
        paged = dataclasses.replace(
            prow, block_table=paged.block_table,
            length=paged.length.at[b].add(t))
    return spec, dense, paged


def _q(key, B, H, D):
    return jax.random.normal(jax.random.fold_in(key, 99), (B, 1, H, D),
                             jnp.float32).astype(jnp.bfloat16)


def _ref_per_slot(q, dense, spec, pos, window=None):
    outs = []
    win = None if window is None else int(window)
    if win is not None and win >= NO_WINDOW:
        win = None
    for b in range(q.shape[0]):
        outs.append(kref.kvattn_ref(
            q[b:b + 1], jax.tree.map(lambda a: a[b:b + 1], dense), spec,
            int(pos[b]), window=win))
    return jnp.concatenate(outs, axis=0)


class TestPagedKernelEquivalence:
    @pytest.mark.parametrize("fmt", FMTS)
    def test_formats_bitwise_vs_dense_kernel(self, key, fmt):
        spec, dense, paged = _paired(key, fmt)
        q = _q(key, 2, 4, 32)
        pos = jnp.array([51, 13], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))
        ref = _ref_per_slot(q, dense, spec, pos)
        np.testing.assert_allclose(
            np.asarray(out_p, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.1 if fmt in ("kv4", "kvfp8") else 0.03)

    @pytest.mark.parametrize("fmt", FMTS)
    @pytest.mark.parametrize("T", [1, 3])
    def test_stacked_layer_equals_layer_alone(self, key, fmt, T):
        """The kernel on a stacked pool at layer l is, bitwise, the
        kernel on layer l's pool alone — the layers hold different
        contents under one block table, so reading the wrong layer
        shows."""
        layers = [_paired(jax.random.fold_in(key, layer), fmt)
                  for layer in range(3)]
        spec, paged = layers[0][0], layers[0][2]
        stacked = dataclasses.replace(paged, **{
            n: jnp.stack([getattr(p, n) for _, _, p in layers])
            for n in ("k", "v", "k_scale", "v_scale")})
        q = jax.random.normal(jax.random.fold_in(key, 5), (2, T, 4, 32),
                              jnp.float32).astype(jnp.bfloat16)
        pos = jnp.array([51, 13], jnp.int32)
        for layer, (_, _, alone) in enumerate(layers):
            got = kops.kvattn_decode_paged(
                q, dataclasses.replace(stacked, layer=jnp.int32(layer)),
                spec, pos)
            want = kops.kvattn_decode_paged(q, alone, spec, pos)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=f"{fmt} layer {layer}")

    @pytest.mark.parametrize("fmt", ["kv8", "kv4"])
    def test_fused_xla_equivalence(self, key, fmt):
        """Paged kernel ≈ fused XLA on the gathered dense view — the
        pre-existing fallback contract, now across ragged lengths."""
        from repro.core import attention as A
        spec, dense, paged = _paired(key, fmt, lengths=[40, 9])
        q = _q(key, 2, 4, 32)
        pos = jnp.array([39, 8], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_f = A.decode_attention(q, PKV.gather_view(paged), spec, pos,
                                   impl="fused")
        np.testing.assert_allclose(
            np.asarray(out_p, np.float32), np.asarray(out_f, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_ragged_lengths_and_sentinels(self, key):
        """Slots at very different frontiers; each table row maps only the
        blocks its length needs — the rest are unmapped sentinels that the
        kernel must zero exactly."""
        spec, dense, paged = _paired(key, "kv8", B=3, S=64,
                                     lengths=[64, 17, 3])
        assert int(jnp.max(paged.block_table)) >= paged.n_blocks - 1
        q = _q(key, 3, 4, 32)
        pos = jnp.array([63, 16, 2], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))

    @pytest.mark.parametrize("window", [8, 24])
    def test_sliding_window(self, key, window):
        spec, dense, paged = _paired(key, "kv8", lengths=[64, 30])
        q = _q(key, 2, 4, 32)
        pos = jnp.array([63, 29], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos,
                                         window=window)
        out_d = kops.kvattn_decode(q, dense, spec, pos, window=window,
                                   block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))
        ref = _ref_per_slot(q, dense, spec, pos, window=window)
        np.testing.assert_allclose(
            np.asarray(out_p, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.03)

    def test_traced_window_sentinel(self, key):
        """Per-layer window arrives as a traced int32 scalar (gemma3's
        local/global mix); NO_WINDOW must mean 'global', exactly."""
        spec, dense, paged = _paired(key, "kv8")
        q = _q(key, 2, 4, 32)
        pos = jnp.array([50, 20], jnp.int32)
        out_none = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_sent = kops.kvattn_decode_paged(q, paged, spec, pos,
                                            window=jnp.int32(NO_WINDOW))
        np.testing.assert_array_equal(np.asarray(out_none),
                                      np.asarray(out_sent))

    def test_gqa_groups(self, key):
        spec, dense, paged = _paired(key, "kv8", Hkv=3, lengths=[33, 64])
        q = _q(key, 2, 12, 32)                       # rep = 4
        pos = jnp.array([32, 63], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))


class TestBlockEdgeCases:
    def test_single_block_table(self, key):
        spec, dense, paged = _paired(key, "kv8", S=8, bs=8, lengths=[8, 5])
        q = _q(key, 2, 4, 32)
        pos = jnp.array([7, 4], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))

    @pytest.mark.parametrize("pos0", [0, 7, 8, 12, 63])
    def test_partial_last_block_positions(self, key, pos0):
        """Frontier at block starts/ends/middles: the last live block is
        partially masked, never read past its logical extent."""
        spec, dense, paged = _paired(key, "kv8")
        q = _q(key, 2, 4, 32)
        pos = jnp.array([pos0, 1], jnp.int32)
        out_p = kops.kvattn_decode_paged(q, paged, spec, pos)
        out_d = kops.kvattn_decode(q, dense, spec, pos, block_s=8)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_d))

    @pytest.mark.parametrize("max_live", [1, 8, 21, 64, 200])
    def test_live_bounded_grid_matches_full(self, key, max_live):
        """Shrinking the grid to the live high-water mark changes nothing
        as long as it covers every live position (trailing blocks are
        exact no-ops)."""
        hw = 21                                     # newest pos + 1
        spec, dense, paged = _paired(key, "kv8", lengths=[21, 13])
        q = _q(key, 2, 4, 32)
        pos = jnp.array([20, 12], jnp.int32)
        full = kops.kvattn_decode_paged(q, paged, spec, pos)
        bounded = kops.kvattn_decode_paged(q, paged, spec, pos,
                                           max_live=max_live)
        if max_live >= hw:
            np.testing.assert_array_equal(np.asarray(full),
                                          np.asarray(bounded))
        else:     # under-covering bound must NOT silently equal full
            assert not np.array_equal(np.asarray(full),
                                      np.asarray(bounded))

    def test_live_ctx_helper(self, key):
        spec = _spec("kv8")
        paged = PKV.init_paged(2, 8, 8, 2, 16, spec, blocks_per_slot=4)
        assert PKV.live_ctx(paged, max_live=1) == 8        # one block floor
        assert PKV.live_ctx(paged, max_live=9) == 16       # round up
        assert PKV.live_ctx(paged, max_live=1000) == 32    # clip to table
        assert PKV.live_ctx(paged) == 8                    # length all-zero
        paged = dataclasses.replace(
            paged, length=jnp.array([11, 3], jnp.int32))
        assert PKV.live_ctx(paged) == 16                   # concrete hwm
        # under a trace the bound is unknowable: full context (and the
        # capped gather still jit-compiles)
        out = jax.jit(lambda c: PKV.gather_view(
            c, n_ctx=PKV.live_ctx(c)))(paged)
        assert out.k.shape[1] == paged.max_context


class TestAttnImplKnob:
    def test_dense_xla_opt_out_runs(self):
        """attn_impl="xla" keeps a dense engine on fused XLA decode (the
        off-TPU escape hatch); invalid values are typed rejections."""
        from repro.configs import get_reduced
        from repro.serving import (Engine, EngineConfig, EngineError,
                                   SamplingParams)
        with pytest.raises(EngineError, match="attn_impl"):
            EngineConfig(model=get_reduced("smollm-360m"),
                         attn_impl="triton")
        eng = Engine(EngineConfig(model=get_reduced("smollm-360m"),
                                  policy="w4a16kv8", n_slots=2, max_seq=32,
                                  max_prompt=8, seed=0, attn_impl="xla",
                                  prefill_chunk=4))
        assert not eng._attn_kernels
        out = eng.generate([[3, 1, 4]], SamplingParams(max_new_tokens=4))
        assert len(out[0].output_token_ids) == 4

    def test_paged_xla_opt_out_matches_kernel(self):
        """attn_impl="xla" on a paged engine takes the capped gather_view
        fallback (its one remaining consumer) and must stream the exact
        same bytes as the in-kernel default."""
        from repro.configs import get_reduced
        from repro.serving import EngineConfig, SamplingParams
        from repro.serving.engine import Engine

        def run(impl):
            eng = Engine(EngineConfig(model=get_reduced("smollm-360m"),
                                      policy="w4a16kv8", n_slots=2,
                                      max_seq=32, max_prompt=8, seed=0,
                                      cache_kind="paged", block_size=8,
                                      attn_impl=impl, prefill_chunk=4))
            assert eng._attn_kernels == (impl == "kernel")
            return eng.generate([[3, 1, 4, 1, 5], [9, 2, 6]],
                                SamplingParams(max_new_tokens=6))

        got = {impl: [o.output_token_ids for o in run(impl)]
               for impl in ("kernel", "xla")}
        assert got["kernel"] == got["xla"]


class TestMultiTokenFallback:
    @pytest.mark.parametrize("impl", ["fused", "xla"])
    def test_chunked_paged_keeps_own_keys(self, key, impl):
        """T>1 paged attention with a tight ``max_live`` must still see
        the chunk's own just-appended keys on both the in-kernel path
        and the capped-gather opt-out (which widens the cap by T-1
        before gathering)."""
        from repro.models import common as C
        spec, dense, paged = _paired(key, "kv8", lengths=[18, 18])
        q4 = jax.random.normal(jax.random.fold_in(key, 5), (2, 4, 4, 32),
                               jnp.float32).astype(jnp.bfloat16)
        pos = jnp.array([14, 14], jnp.int32)   # chunk covers 14..17
        out_capped = C.attend_decode(q4, paged, spec, pos, impl=impl,
                                     max_live=15)
        out_full = C.attend_decode(q4, paged, spec, pos, impl=impl)
        np.testing.assert_array_equal(np.asarray(out_capped),
                                      np.asarray(out_full))


class TestNoGather:
    def test_kernel_path_never_gathers(self, key, monkeypatch):
        """ops.kvattn_decode_paged must not materialize a dense view."""
        spec, dense, paged = _paired(key, "kv8", lengths=[10, 30])

        def boom(*a, **k):
            raise AssertionError("gather_view called on the kernel path")

        monkeypatch.setattr(PKV, "gather_view", boom)
        q = _q(key, 2, 4, 32)
        out = kops.kvattn_decode_paged(q, paged, spec,
                                       jnp.array([9, 29], jnp.int32))
        assert np.isfinite(np.asarray(out, np.float32)).all()

    def test_paged_engine_never_gathers(self, monkeypatch):
        """A full paged engine run — ragged prefill, decode, retire —
        completes with gather_view poisoned: block-table indirection
        happens in-kernel end to end."""
        from repro.configs import get_reduced
        from repro.serving import Engine, EngineConfig, SamplingParams

        def boom(*a, **k):
            raise AssertionError("paged engine touched gather_view")

        monkeypatch.setattr(PKV, "gather_view", boom)
        eng = Engine(EngineConfig(model=get_reduced("smollm-360m"),
                                  policy="w4a16kv8", n_slots=2, max_seq=32,
                                  max_prompt=16, seed=0, cache_kind="paged",
                                  block_size=8, prefill_chunk=4))
        rid = eng.submit([5, 6, 7, 8, 9], SamplingParams(max_new_tokens=5))
        final = {o.rid: o for o in eng.run_until_idle()}
        assert len(final[rid].output_token_ids) == 5
