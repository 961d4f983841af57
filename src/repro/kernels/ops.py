"""Public jit'd wrappers around the Pallas kernels.

``INTERPRET`` is True on CPU hosts (kernel bodies execute in Python via the
Pallas interpreter — bit-exact semantics, no TPU required) and False on
real TPU backends.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.kvcache import KVCache
from repro.core.packing import PackedWeight
from repro.core.paged_kvcache import PagedKVCache, blocks_needed
from repro.core.precision import FormatSpec, PrecisionPolicy

from . import kvattn as _kvattn
from . import mpgemm as _mpgemm
from . import paged_kvattn as _pkvattn

INTERPRET = jax.default_backend() != "tpu"


def mpgemm(x: jax.Array, w: PackedWeight, policy: PrecisionPolicy,
           block_m: int = 128) -> jax.Array:
    """y = x @ W with in-kernel dequant.  x: (..., K) → (..., N).

    A16 → bf16 mainloop with I2F dequant; A8 → the MXU s8×s8→s32 mainloop
    (per-token activation quantization happens here, outside the kernel).
    """
    K, N = w.shape
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    bm = block_m
    while M % bm and bm > 8:
        bm //= 2
    if M % bm:
        bm = 1
    if policy.int8_matmul:
        from repro.core import quantize as Q
        xq, xs = Q.quantize_act_per_token(
            x.reshape(M, K).astype(jnp.float32), bits=8)
        y = _mpgemm.mpgemm_int8_2d(
            xq, xs.astype(jnp.float32), w.data,
            w.scales.astype(jnp.float32), bits=w.bits, group=w.group,
            block_m=bm, interpret=INTERPRET,
            out_dtype=policy.compute_dtype)
        return y.reshape(*lead, N)
    x2 = x.reshape(M, K).astype(policy.compute_dtype)
    y = _mpgemm.mpgemm_2d(x2, w.data, w.scales.astype(jnp.float32),
                          bits=w.bits, group=w.group, block_m=bm,
                          interpret=INTERPRET,
                          out_dtype=policy.compute_dtype)
    return y.reshape(*lead, N)


def flash_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True, window=None,
                            block_q: int = 512,
                            block_k: int = 512) -> jax.Array:
    """Fused flash prefill.  q: (B, S, H, D); k/v: (B, S, Hkv, D).

    Pads S to a block multiple; the kernel masks padded keys."""
    from . import flashprefill as _fp
    B, S, H, D = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    blk = max(bq, bk)
    Sp = -(-S // blk) * blk                    # pad to a block multiple
    if Sp != S:
        padw = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, padw), jnp.pad(k, padw), jnp.pad(v, padw)
    qp, kp, vp = q, k, v
    block_q, block_k = bq, bk
    out = _fp.flash_prefill(
        qp.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        kp.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        vp.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        causal=causal, window=window if isinstance(window, int) else None,
        block_q=block_q, block_k=block_k, seq=S, interpret=INTERPRET)
    return out.transpose(0, 2, 1, 3)[:, :S].astype(q.dtype)


def _norm_pos(pos, B: int) -> jax.Array:
    pos_arr = jnp.asarray(pos, jnp.int32)
    if pos_arr.ndim == 0:
        pos_arr = jnp.broadcast_to(pos_arr, (B,))
    return pos_arr


def _norm_window(window) -> jax.Array:
    """None / int / traced scalar → (1,) int32 operand for the kernels
    (``kvattn.NO_WINDOW`` disables the sliding-window mask exactly)."""
    if window is None:
        window = _kvattn.NO_WINDOW
    return jnp.asarray(window, jnp.int32).reshape(1)


def _group_rows(q: jax.Array, Hkv: int, rep: int):
    """(B, T, H, D) → ((B, Hkv, T*rk, D), rk) token-major q tile.

    Rows come out as ``r = t*rk + g``: the ``rk`` grouped-query heads of
    one token are consecutive, so the kernels' per-row causal frontier is
    ``first_pos + r // rk``.  ``rep == 1`` is zero-padded to ``rk == 2``
    (the pad rows are sliced off by :func:`_ungroup_rows`): a one-row
    q tile would hit XLA:CPU's GEMV path, whose summation order differs
    bitwise from the ≥2-row GEMM path, breaking the engine's cross-chunk
    byte-identity contract."""
    B, T, H, D = q.shape
    qg = q.reshape(B, T, Hkv, rep, D)
    rk = rep
    if rep == 1:
        qg = jnp.concatenate([qg, jnp.zeros_like(qg)], axis=3)
        rk = 2
    return qg.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, T * rk, D), rk


def _ungroup_rows(out: jax.Array, B: int, T: int, Hkv: int, rep: int,
                  rk: int, D: int) -> jax.Array:
    """Inverse of :func:`_group_rows` (drops any rep-1 pad rows)."""
    o = out.reshape(B, Hkv, T, rk, D).transpose(0, 2, 1, 3, 4)
    return o[:, :, :, :rep, :].reshape(B, T, Hkv * rep, D)


def kvattn_decode(q: jax.Array, cache: KVCache, spec: FormatSpec,
                  pos, window=None, block_s: int = 256) -> jax.Array:
    """Decode/chunked-prefill attention.  q: (B, T, H, D); ``pos`` is a
    scalar or a per-slot (B,) vector of *first*-query-token positions
    (the continuous-batching engine's ragged slots) — token t of the
    chunk attends causally through position ``pos + t``.  ``window`` may
    be None, an int, or a traced int32 scalar (per-layer local/global
    mixes)."""
    B, T, H, D = q.shape
    Hkv = cache.k.shape[2]
    rep = H // Hkv
    qg, rk = _group_rows(q, Hkv, rep)       # adaptive head alignment (§4.2)
    out = _kvattn.kvattn_decode_grouped(
        qg.astype(jnp.bfloat16),
        cache.k, cache.k_scale, cache.v, cache.v_scale,
        _norm_pos(pos, B), _norm_window(window),
        packed=spec.packed, kv_is_float=spec.is_float,
        block_s=block_s, rep=rk, interpret=INTERPRET)
    return _ungroup_rows(out, B, T, Hkv, rep, rk, D).astype(q.dtype)


def grid_blocks(max_live: int, t: int, block_size: int) -> int:
    """Extent of the paged kernel's block axis for a ``t``-row step whose
    first query rows reach ``max_live`` tokens: widened by ``t - 1`` so
    the chunk's last token's frontier stays in the grid (the kernel
    clips it to the table's width)."""
    return blocks_needed(max_live + t - 1, block_size)


def kvattn_decode_paged(q: jax.Array, cache: PagedKVCache, spec: FormatSpec,
                        pos, window=None,
                        max_live: Optional[int] = None) -> jax.Array:
    """Paged decode/chunked-prefill attention with **in-kernel**
    block-table indirection.

    q: (B, T, H, D); ``cache`` is a per-layer (unstacked) PagedKVCache
    whose block table maps each of the B slots' logical contexts; ``pos``
    is the per-slot *first*-query-token position (token t attends through
    ``pos + t``).  No dense view is ever materialized: the kernel
    scalar-prefetches the table and DMAs K/V/scale tiles block-by-block
    straight out of the pool (kernels/paged_kvattn.py).  ``max_live``
    (static, tokens) bounds the grid's block axis at the batch's
    live-context high-water mark for the *first* query row — widened by
    T-1 so the chunk's last token's frontier stays in-grid — so per-step
    traffic scales with live context, not ``max_context``.  Unmapped
    (sentinel) table entries are clamped to a real pool block and zeroed
    exactly by the kernel's ``kpos <= pos`` mask."""
    B, T, H, D = q.shape
    Hkv = cache.k.shape[2]
    rep = H // Hkv
    qg, rk = _group_rows(q, Hkv, rep)       # adaptive head alignment (§4.2)
    n_live = None
    if max_live is not None:
        n_live = grid_blocks(max_live, T, cache.block_size)
    out = _pkvattn.paged_kvattn_decode_grouped(
        qg.astype(jnp.bfloat16),
        cache.k, cache.k_scale, cache.v, cache.v_scale,
        cache.block_table, _norm_pos(pos, B), _norm_window(window),
        packed=spec.packed, kv_is_float=spec.is_float,
        n_live_blocks=n_live, rep=rk, interpret=INTERPRET)
    return _ungroup_rows(out, B, T, Hkv, rep, rk, D).astype(q.dtype)
