"""Pallas TPU kernel: decode attention over a quantized KV cache.

The paper's attention pipeline (§3.4) with adaptive head alignment (§4.2)
and the KV memory loading pipeline (§4.4), TPU-native:

* **Adaptive head alignment**: Q is the tensor that adapts — the wrapper
  reshapes it once per step into (B, Hkv, R, D) so the ``rep``
  grouped-query heads that share one quantized K/V head sit in one q
  tile.  K/V are never materialized in bf16 in HBM.
* **KV memory loading pipeline**: the grid is (batch, KV block), and each
  step loads one block of *every* KV head — a (bs, Hkv, Dstore) tile and
  its (bs, Hkv, 1) scales, whose two minor dimensions are the array's
  own, as the TPU's (8, 128) tiling rule requires for any Hkv.  The
  kernel loops over the heads statically.  ``pallas_call`` pipelines the
  next block's HBM→VMEM DMA under the current block's dequant (VPU) +
  QKᵀ/PV (MXU) — the triple overlap of Fig. 10.  Online-softmax state
  (m, l, acc), one slice per head, lives in VMEM scratch across grid
  steps, flash-decoding style.
* Dequantization is int32 nibble-unpack (kv4) + I2F + per-(token, head)
  scale, rounded to bf16 for the MXU.

VMEM per step at bs=64, Hkv=5, D=64, R=96 q rows, with the two minor
dims padded to the dtype's native tile ((32, 128) int8, (8, 128) f32):
int8 K/V + f32 scale tiles ≈ 1 MiB, q/out ≈ 0.25 MiB, scratch ≈ 0.7 MiB
— ≈ 2 MiB, double-buffered well within VMEM.

The per-block online-softmax update (:func:`flash_block_update`) is shared
with the *paged* decode kernel (kernels/paged_kvattn.py), which walks pool
blocks through a scalar-prefetched block table instead of a dense slab.
Because both kernels run the identical update over bit-identical KV tiles,
a paged cache and a dense cache of the same logical contents produce
bit-identical attention outputs when traversed at the same block
granularity — the serving engine's dense/paged equivalence guarantee.

``window`` is carried as a traced int32 operand (not a static Python
value) so per-layer sliding windows — gemma3's local/global mix arrives
as a traced scalar from inside the layer scan — need no retrace;
``NO_WINDOW`` (2^30) is the "global attention" sentinel, and the single
source models/transformer.BIG_WINDOW re-exports.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quantize as Q

NEG_INF = -1e30
#: "no sliding window" sentinel — any int32 ``pos - NO_WINDOW`` stays
#: negative for every reachable position, so the window mask is a no-op.
NO_WINDOW = 1 << 30


def _dequant_tile(q_ints: jax.Array, scale: jax.Array,
                  packed: bool) -> jax.Array:
    """(bs, Dstore) ints + (bs, 1) scales → (bs, d) bf16.

    int4 KV is packed half-split (low nibbles hold the first half of
    head_dim), so unpacking is a lane concat."""
    if packed:
        q_ints = Q.unpack_int4_halves(q_ints)
    return (q_ints.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def flash_block_update(q, kt, ks, vt, vs, pos, window, base,
                       m_ref, l_ref, acc_ref, *, d, packed, kv_is_float):
    """One flash-decoding step of one KV head over a (bs, Dstore) tile.

    ``ks``/``vs`` are the tile's (bs, 1) per-token scales.  ``base`` is
    the *logical* position of the tile's first token — the
    only place the dense and paged kernels differ (dense: ``s_blk *
    block_s`` over the slab; paged: ``logical_block * block_size``, while
    the tile itself was DMA'd from wherever the block table pointed).
    ``pos`` is a scalar (one causal frontier for every q row) or an
    (R, 1) per-row frontier — the multi-query grid passes ``first_pos +
    row // rep`` so each query token in the tile masks at its own
    position; the mask arithmetic broadcasts over either shape.
    Updates the online-softmax scratch (m, l, acc) in place.  A fully
    masked tile is an exact no-op (alpha = e^0 = 1, p = 0), which is what
    lets a shorter grid (live context) match a longer one bitwise.
    """
    if kv_is_float:
        kd = (kt.astype(jnp.float32) * ks).astype(jnp.bfloat16)
    else:
        kd = _dequant_tile(kt, ks, packed)              # (bs, D) bf16

    s = jax.lax.dot_general(q, kd, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s *= jax.lax.rsqrt(jnp.float32(d))                  # (rep, bs)

    idx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (idx <= pos) & (idx > (pos - window))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (rep, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                         # kill fully-masked rows
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)

    if kv_is_float:
        vd = (vt.astype(jnp.float32) * vs).astype(jnp.bfloat16)
    else:
        vd = _dequant_tile(vt, vs, packed)              # (bs, D)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(jnp.bfloat16), vd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def flash_init(m_ref, l_ref, acc_ref):
    """Reset the online-softmax scratch of every KV head."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def flash_heads_update(q_ref, head_tiles, pos, window, base, m_ref, l_ref,
                       acc_ref, *, rep, d, packed, kv_is_float):
    """:func:`flash_block_update` for every KV head of one block.

    One grid step holds all ``Hkv`` heads of a block; ``head_tiles(h)``
    returns head ``h``'s ``(bs, Dstore)`` K tile, ``(bs, 1)`` K scales,
    and the same for V, sliced from whatever tiles the kernel's layout
    DMA'd (the dense kernel's ``(1, bs, Hkv, Dstore)`` slab tile, the
    paged kernel's ``(bs, KVL)`` pool block).
    q rows are (token, group) pairs in token-major order (r = t*rep + g):
    row r's causal frontier is ``pos + r // rep``.  T == 1 keeps every
    row at ``pos`` — bitwise the single-token decode."""
    R = m_ref.shape[1]
    qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // rep
    for h in range(m_ref.shape[0]):
        kt, ks, vt, vs = head_tiles(h)
        flash_block_update(
            q_ref[0, h], kt, ks, vt, vs, qpos, window, base,
            m_ref.at[h], l_ref.at[h], acc_ref.at[h],
            d=d, packed=packed, kv_is_float=kv_is_float)


def flash_store(o_ref, m_ref, l_ref, acc_ref):
    """Final normalized store of every head's online-softmax accumulator."""
    l = jnp.maximum(l_ref[...], 1e-20)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_scratch(Hkv: int, R: int, D: int):
    """VMEM scratch (m, l, acc) for one grid cell's ``Hkv`` heads."""
    return [pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, D), jnp.float32)]


def _kvattn_kernel(pos_ref, win_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, block_s, n_s, d, rep,
                   packed, kv_is_float):
    b = pl.program_id(0)
    s_blk = pl.program_id(1)

    @pl.when(s_blk == 0)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)

    flash_heads_update(
        q_ref, lambda h: (k_ref[0, :, h], ks_ref[0, :, h], v_ref[0, :, h],
                          vs_ref[0, :, h]),
        pos_ref[b], win_ref[0], s_blk * block_s, m_ref, l_ref, acc_ref,
        rep=rep, d=d, packed=packed, kv_is_float=kv_is_float)

    @pl.when(s_blk == n_s - 1)
    def _store():
        flash_store(o_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "kv_is_float", "block_s", "rep", "interpret"))
def kvattn_decode_grouped(
    q: jax.Array,          # (B, Hkv, R, D) bf16 — adaptive head alignment
    k: jax.Array,          # (B, S, Hkv, Dstore) int8 / fp8 / bf16
    k_scale: jax.Array,    # (B, S, Hkv, 1) f32
    v: jax.Array,
    v_scale: jax.Array,
    pos: jax.Array,        # (B,) int32: per-slot *first* query position
    window: jax.Array,     # (1,) int32: sliding window (NO_WINDOW = off)
    *,
    packed: bool,
    kv_is_float: bool = False,
    block_s: int = 256,
    rep: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Multi-query grouped decode attention.

    The q tile carries ``R = T * rep`` rows per (batch, kv-head) in
    token-major order — ``rep`` consecutive rows share one causal
    frontier, and frontiers step by one every ``rep`` rows.  ``rep=None``
    (back-compat) treats the whole tile as a single token (T == 1).  The
    grid is (batch, block); each step covers every KV head.
    """
    B, Hkv, R, D = q.shape
    if rep is None:
        rep = R
    assert R % rep == 0, (R, rep)
    S = k.shape[1]
    Ds = k.shape[3]
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    n_s = S // bs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,         # positions, window
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, Hkv, R, D), lambda b, s, pos, win: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Ds), lambda b, s, pos, win: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, 1), lambda b, s, pos, win: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Ds), lambda b, s, pos, win: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, 1), lambda b, s, pos, win: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, R, D),
                               lambda b, s, pos, win: (b, 0, 0, 0)),
        scratch_shapes=flash_scratch(Hkv, R, D),
    )
    kernel = functools.partial(
        _kvattn_kernel, block_s=bs, n_s=n_s, d=D, rep=rep, packed=packed,
        kv_is_float=kv_is_float)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), window.astype(jnp.int32),
      q, k, k_scale, v, v_scale)


# Paged decode lives in kernels/paged_kvattn.py: the block-table
# indirection happens *inside* that kernel (scalar-prefetched tables drive
# each grid step's BlockSpec index_map straight into the block pool), so no
# dense gather ever materializes — see ops.kvattn_decode_paged.
