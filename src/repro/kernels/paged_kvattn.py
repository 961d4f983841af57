"""Pallas TPU kernel: paged decode attention — flash-decoding straight
over the block pool, block-table indirection *inside* the kernel.

This is the paper's KV memory loading pipeline (§4.4) applied to paged
storage: instead of first materializing a dense ``(B, max_context, Hkv,
Dstore)`` per-slot view with an HBM→HBM gather (the pre-kernel fallback —
transient traffic proportional to worst-case context), the per-slot block
tables are **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``) so
each grid step's ``BlockSpec`` index_map resolves ``(slot, logical_block)
→ pool_block`` and DMAs the K/V/scale tiles of exactly that pool block
HBM→VMEM.  ``pallas_call`` still pipelines the *next* block's DMA under
the current block's dequant (VPU) + QKᵀ/PV (MXU) — the Fig. 10 triple
overlap — because the prefetched table makes every upcoming block address
known ahead of the compute.

Traffic per decode step is therefore proportional to **live** context
(the grid's block axis is ``n_live_blocks = ceil(max_live / block_size)``
when the caller knows the batch's high-water mark, ``blocks_per_slot``
otherwise), and there is no transient dense copy at all.

Ragged slots and sentinel table entries: a slot whose context ends before
the grid does (or whose trailing table entries are unmapped sentinels,
clamped to a real pool block by the wrapper) is handled by the logical
``kpos <= pos`` mask — a fully masked block is an *exact* no-op of the
online-softmax state (see kvattn.flash_block_update), so garbage blocks
contribute nothing, bitwise.

Per-block compute is :func:`kvattn.flash_heads_update`, shared with the
dense decode kernel — the two kernels are bit-identical over equal logical
contents at equal block granularity, which is what keeps the serving
engine's dense and paged backends byte-identical under greedy decoding.
The grid is (slot, logical block); one step DMAs the pool block's tiles
for every KV head at once — a ``(bs, KVL)`` K/V tile whose heads the
kernel takes as static lane slices, and ``(1, SL)`` scales — straight
out of the stacked pool at the layer the model's scan names (the layer
index is scalar-prefetched with the table), in the row-major layout that
the in-place writer (kernels/pool_write.py) also addresses, so the pool
is never relaid out or copied (core/paged_kvcache.py).  The layer, table
and positions live in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kvattn import (flash_heads_update, flash_init, flash_scratch,
                     flash_store)


def _paged_kvattn_kernel(layer_ref, tbl_ref, pos_ref, win_ref,
                         q_ref, k_ref, ks_ref, v_ref, vs_ref,
                         o_ref, m_ref, l_ref, acc_ref, *,
                         block_size, n_s, d, ds, rep, packed, kv_is_float):
    del layer_ref                         # used by the index maps only
    b = pl.program_id(0)
    s_blk = pl.program_id(1)   # logical block index within the slot

    @pl.when(s_blk == 0)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)

    # the tiles were DMA'd from pool block tbl[b, s_blk] of the addressed
    # layer: K/V (bs, KVL) with head h on lanes [h*ds, (h+1)*ds); scales
    # (1, SL) with head h's tokens on lanes [h*bs, (h+1)*bs), turned once
    # into columns (SL, 8) so each head's scales are a (bs, 1) slice
    ks_t = jnp.broadcast_to(ks_ref[...], (8, ks_ref.shape[1])).T
    vs_t = jnp.broadcast_to(vs_ref[...], (8, vs_ref.shape[1])).T

    def head_tiles(h):
        lanes, toks = slice(h * ds, (h + 1) * ds), \
            slice(h * block_size, (h + 1) * block_size)
        return (k_ref[:, lanes], ks_t[toks, 0:1], v_ref[:, lanes],
                vs_t[toks, 0:1])

    # their *logical* positions start at s_blk * block_size
    flash_heads_update(
        q_ref, head_tiles, pos_ref[b], win_ref[0], s_blk * block_size,
        m_ref, l_ref, acc_ref, rep=rep, d=d, packed=packed,
        kv_is_float=kv_is_float)

    @pl.when(s_blk == n_s - 1)
    def _store():
        flash_store(o_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "kv_is_float", "n_live_blocks", "rep",
                     "interpret"))
def paged_kvattn_decode_grouped(
    q: jax.Array,            # (B, Hkv, R, D) bf16 — adaptive head alignment
    k: jax.Array,            # ([L,] n_blocks, block_size, KVL) pool
    k_scale: jax.Array,      # ([L,] n_blocks, 1, SL) f32
    v: jax.Array,
    v_scale: jax.Array,
    block_table: jax.Array,  # (B, blocks_per_slot) int32; n_blocks=unmapped
    pos: jax.Array,          # (B,) int32: per-slot *first* query position
    window: jax.Array,       # (1,) int32 window (kvattn.NO_WINDOW = off)
    layer: jax.Array | None = None,  # () int32: layer of a stacked pool
    *,
    packed: bool,
    kv_is_float: bool = False,
    n_live_blocks=None,      # static: grid extent ≤ blocks_per_slot
    rep: int | None = None,  # static: rows per query token (None → R, T=1)
    interpret: bool = False,
) -> jax.Array:
    """Multi-query paged decode: the q tile carries ``R = T * rep`` rows
    per (slot, kv-head) in token-major order — ``rep`` consecutive rows
    share one causal frontier, frontiers step by one every ``rep`` rows.
    ``rep=None`` (back-compat) treats the whole tile as one token.  The
    grid is (slot, logical block); each step covers every KV head of the
    pool block.  The pool is read in place: a stacked ``(L, ...)`` pool
    at ``layer`` (scalar-prefetched with the table), a per-layer pool as
    ``L = 1``.  This is the single kernel behind chunked prefill,
    preemption replay, and decode."""
    B, Hkv, R, D = q.shape
    if rep is None:
        rep = R
    assert R % rep == 0, (R, rep)
    if k.ndim == 3:                   # per-layer pool: L = 1, layer 0
        k, k_scale, v, v_scale = (a[None] for a in (k, k_scale, v, v_scale))
    if layer is None:
        layer = jnp.zeros((), jnp.int32)
    nb, bs, C = k.shape[1:]
    S = k_scale.shape[-1]
    nbp = block_table.shape[1]
    n_s = nbp if n_live_blocks is None else max(1, min(n_live_blocks, nbp))

    # Sentinel entries (>= n_blocks) clamp to the last real pool block so
    # the index_map always names a mapped tile; its contents are masked to
    # an exact no-op by kpos <= pos.  int32 keeps the SMEM table compact.
    tbl = jnp.minimum(block_table.astype(jnp.int32), nb - 1)

    pool_map = lambda b, s, lay, tbl, pos, win: (lay[0], tbl[b, s], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,         # layer, block table, positions, window
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, Hkv, R, D),
                         lambda b, s, lay, tbl, pos, win: (b, 0, 0, 0)),
            pl.BlockSpec((None, None, bs, C), pool_map),
            pl.BlockSpec((None, None, 1, S), pool_map),
            pl.BlockSpec((None, None, bs, C), pool_map),
            pl.BlockSpec((None, None, 1, S), pool_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, R, D),
                               lambda b, s, lay, tbl, pos, win: (b, 0, 0, 0)),
        scratch_shapes=flash_scratch(Hkv, R, D),
    )
    kernel = functools.partial(
        _paged_kvattn_kernel, block_size=bs, n_s=n_s, d=D,
        ds=D // 2 if packed else D,
        rep=rep, packed=packed, kv_is_float=kv_is_float)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tbl,
      pos.astype(jnp.int32), window.astype(jnp.int32),
      q, k, k_scale, v, v_scale)
