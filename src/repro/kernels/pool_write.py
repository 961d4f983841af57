"""Pallas TPU kernel: write new KV rows into the paged pool in place.

The pool (core/paged_kvcache.py) is stored row-major, the layout the
paged attention kernel reads, and is aliased from this kernel's input to
its output (``input_output_aliases``): a step reads and writes back only
the pool blocks its new rows touch, and no other byte of the pool moves.
An XLA scatter would do the same write, but the TPU compiler wants a
block-minor layout for it, and the pool would then be relaid out around
every layer's kernel.

The grid is one step per (slot, pool block the slot's rows can touch);
the caller (``paged_kvcache._write_plan``) scalar-prefetches each step's
pool block and the block rows ``[lo, hi)`` it overwrites, and hands the
new rows already laid out as the block (row ``r`` of a step's tile is
what lands on block row ``r``), so a step is one masked select per leaf.

The pipeline hazard: ``pallas_call`` fetches a step's input block ahead
of time and writes an output block back only when the next step names
another block.  So a step that names the same block as the step before
it must build on that step's output buffer — its input is the block as
it was before the previous step's write — and a block named by two runs
of steps could be fetched before its first run's write-back lands.  The
plan guarantees one run per block (steps that write nothing repeat a
neighbour's block); the kernel checks ``blk[g] == blk[g - 1]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kv_pool_write_kernel(layer_ref, blk_ref, lo_ref, hi_ref, row_ref,
                          kn_ref, ksn_ref, vn_ref, vsn_ref,
                          k_in, ks_in, v_in, vs_in,
                          k_out, ks_out, v_out, vs_out, *, kv_lanes):
    del layer_ref                         # used by the index maps only
    g = pl.program_id(0)
    again = (g > 0) & (blk_ref[g] == blk_ref[jnp.maximum(g - 1, 0)])
    lo, hi = lo_ref[g], hi_ref[g]
    # K/V tiles are (bs, KVL): block rows on axis 0, heads' values on
    # lanes below kv_lanes; scale tiles are (1, SL), and row_ref maps
    # each lane to its block row (-1 on pad lanes)
    rows = jax.lax.broadcasted_iota(jnp.int32, kn_ref.shape, 0)
    kv_put = ((rows >= lo) & (rows < hi) &
              (jax.lax.broadcasted_iota(jnp.int32, kn_ref.shape, 1)
               < kv_lanes))
    rows = row_ref[...]
    sc_put = (rows >= lo) & (rows < hi)
    for new, old, out, put in ((kn_ref, k_in, k_out, kv_put),
                               (ksn_ref, ks_in, ks_out, sc_put),
                               (vn_ref, v_in, v_out, kv_put),
                               (vsn_ref, vs_in, vs_out, sc_put)):
        @pl.when(again)
        def _():
            out[...] = jnp.where(put, new[...], out[...])

        @pl.when(jnp.logical_not(again))
        def _():
            out[...] = jnp.where(put, new[...], old[...])


@functools.partial(jax.jit, static_argnames=("kv_lanes", "heads",
                                             "interpret"))
def kv_pool_write(pool, new, layer, blk, lo, hi, *, kv_lanes, heads,
                  interpret=False):
    """Overwrite rows of pool blocks in place.

    pool: ``(k, k_scale, v, v_scale)`` — K/V ``(L, n_blocks, bs, KVL)``
    and scales ``(L, n_blocks, 1, SL)`` f32 (token ``t`` of head ``h``
    on lane ``h*bs + t``); new: the same four as per-step tiles, ``(G,
    bs, KVL)`` and ``(G, 1, SL)``; layer: () int32, the pool layer
    written; blk/lo/hi: ``(G,)`` int32 — step g overwrites block rows
    ``[lo[g], hi[g])`` of block ``blk[g]`` with the same rows of its
    tiles, on the first ``kv_lanes`` K/V lanes and the first ``heads *
    bs`` scale lanes.  Pad lanes are left as they are.  Returns the four
    pool leaves."""
    G, bs, C = new[0].shape
    S = new[1].shape[-1]
    lane = jnp.arange(S, dtype=jnp.int32)
    row_of_lane = jnp.where(lane < heads * bs, lane % bs, -1)[None]
    pool_map = lambda g, lay, blk, lo, hi: (lay[0], blk[g], 0, 0)
    step_map = lambda g, *_: (g, 0, 0)
    kv_spec = pl.BlockSpec((None, None, bs, C), pool_map)
    sc_spec = pl.BlockSpec((None, None, 1, S), pool_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # layer, block, first row, end row
        grid=(G,),
        in_specs=[pl.BlockSpec((1, S), lambda g, *_: (0, 0)),
                  pl.BlockSpec((None, bs, C), step_map),
                  pl.BlockSpec((None, 1, S), step_map),
                  pl.BlockSpec((None, bs, C), step_map),
                  pl.BlockSpec((None, 1, S), step_map),
                  kv_spec, sc_spec, kv_spec, sc_spec],
        out_specs=[kv_spec, sc_spec, kv_spec, sc_spec],
    )
    return tuple(pl.pallas_call(
        functools.partial(_kv_pool_write_kernel, kv_lanes=kv_lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pool],
        # operands: 4 scalar-prefetch, the lane map, 4 tiles, then the
        # pool leaves
        input_output_aliases={9: 0, 10: 1, 11: 2, 12: 3},
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), blk.astype(jnp.int32),
      lo.astype(jnp.int32), hi.astype(jnp.int32), row_of_lane, *new,
      *pool))
