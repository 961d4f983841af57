"""Pallas TPU kernel: mixed-precision GEMM over offline-packed weights.

The online half of the paper's GEMM pipeline (§3.4/§4.3), TPU-native:

* Weights arrive in the tile-major layout produced by
  ``core.packing.pack_weight`` — each grid step's BlockSpec reads ONE
  contiguous (bk_store × bn) int8 tile from HBM (the coalesced-load
  guarantee of hardware-aware packing).
* In-kernel dequantization = nibble unpack (VPU shifts) + I2F cast +
  per-group scale broadcast — the weight needs no permutation, because
  the offline packer already stored sub-words in MXU feed order (paper
  Fig. 6); the activations' K order adapts instead (see Tiling).
* Parallel MMA–dequantization (§4.3): ``pl.pallas_call`` software-pipelines
  the grid — while the MXU contracts block k, the next block's HBM→VMEM DMA
  is in flight, and the VPU dequant of block k overlaps the MXU issue
  stream.  This is the TPU's structural equivalent of the paper's
  three-way (tensor core ∥ ALU ∥ cp.async) overlap.

Tiling: the packer picks (bk, bn) per matrix (models/common.pick_blocks;
bk = 128 = quant group where K allows, 64 at K = 960).  The wrapper lays
x out K-tile-major, (K/bk, M, bk), and takes the output N-tile-major,
(N/bn, M, bn): every block then spans its array's full minor dimension,
which satisfies the TPU's (8, 128) tiling rule at any bk/bn the packer
emits.  int4 nibbles are unpacked in int32 (no 8-bit vector shifts) and
stacked even-k rows over odd-k rows; the wrapper orders each x tile's
columns to match, so the unpack needs no interleave.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_nibbles_tile(wp: jax.Array) -> jax.Array:
    """(bk//2, bn) int8 containers → (bk, bn) int32 values, even-k rows
    first then odd-k rows (the wrapper permutes x's K tile to match).

    Container row i holds k = 2i (low nibble) and k = 2i+1 (high nibble)
    (core.quantize.pack_int4).  The nibbles are sign-extended in int32,
    since the TPU's vector unit has no 8-bit shifts, and stacked along
    sublanes, which needs no relayout (an interleave would)."""
    w = wp.astype(jnp.int32)
    return jnp.concatenate([(w << 28) >> 28, (w << 24) >> 28], axis=0)


def _mpgemm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits, n_k,
                   out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wp = w_ref[0, 0]                                   # (bk_store, bn) int8
    wv = _unpack_nibbles_tile(wp) if bits == 4 else wp
    # I2F + scale: the dequantized fragment feeds the MXU directly.
    scale = s_ref[0].astype(jnp.float32)               # (1, bn)
    wd = (wv.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[0], wd,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(out_dtype)


def _mpgemm_int8_kernel(x_ref, xs_ref, w_ref, s_ref, o_ref, acc_ref, *,
                        bits, n_k, out_dtype):
    """W4A8/W8A8 mainloop: MXU s8×s8→s32 dot, per-group weight scale
    applied to each K-block's s32 partial product (block_k == group), the
    per-token activation scale at the final store — QServe's W4A8 compute
    mapped to the TPU's native int8 matmul mode."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wp = w_ref[0, 0]
    wv = _unpack_nibbles_tile(wp).astype(jnp.int8) if bits == 4 else wp
    part = jax.lax.dot_general(x_ref[0], wv, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    acc_ref[...] += part.astype(jnp.float32) * s_ref[0].astype(jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[0] = (acc_ref[...] *
                    xs_ref[...].astype(jnp.float32)).astype(out_dtype)


def _k_tiles(x: jax.Array, Kt: int, bk: int, bits: int) -> jax.Array:
    """(M, K) activations → (Kt, M, bk) K-tile-major.

    Every block of the result spans the array's full minor dimension, so
    the TPU tiling rule holds for any ``bk`` the packer picks (K = 960
    gives ``bk = 64``).  For int4 weights each tile's columns are
    reordered even-k first, matching :func:`_unpack_nibbles_tile`."""
    M = x.shape[0]
    if bits == 4:
        return x.reshape(M, Kt, bk // 2, 2).transpose(1, 0, 3, 2) \
            .reshape(Kt, M, bk)
    return x.reshape(M, Kt, bk).transpose(1, 0, 2)


def _gemm_call(kernel, x, extra, w_tiles, scales, *, bits, group, block_m,
               out_dtype, interpret):
    """Shared pallas_call: grid (M/bm, Nt, Kt) over K-tile-major x, the
    tile-major packed weight and per-(K-tile, N-tile) scale rows; the
    output comes back N-tile-major and is transposed to (M, N)."""
    M, K = x.shape
    Kt, Nt, bk_store, bn = w_tiles.shape
    bk = bk_store * 2 if bits == 4 else bk_store
    N = Nt * bn
    assert Kt * bk == K, (K, Kt, bk)
    assert group == bk, "kernel requires quant group == block_k (packer default)"
    bm = min(block_m, M)
    assert M % bm == 0, (M, bm)
    xt = _k_tiles(x, Kt, bk, bits)
    st = scales.reshape(Kt * Nt, 1, bn)
    extra_specs = [pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0))] \
        if extra is not None else []
    out = pl.pallas_call(
        functools.partial(kernel, bits=bits, n_k=Kt, out_dtype=out_dtype),
        grid=(M // bm, Nt, Kt),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, k: (k, i, 0)),
            *extra_specs,
            pl.BlockSpec((1, 1, bk_store, bn), lambda i, j, k: (k, j, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (k * Nt + j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, k: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Nt, M, bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xt, *([extra] if extra is not None else []), w_tiles, st)
    return out.transpose(1, 0, 2).reshape(M, N)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group", "block_m", "interpret", "out_dtype"))
def mpgemm_int8_2d(
    xq: jax.Array,           # (M, K) int8 — per-token quantized activations
    xscale: jax.Array,       # (M, 1) f32
    w_tiles: jax.Array,      # (Kt, Nt, bk_store, bn) int8 tile-major
    scales: jax.Array,       # (K // group, N) f32
    *,
    bits: int,
    group: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    return _gemm_call(_mpgemm_int8_kernel, xq, xscale, w_tiles, scales,
                      bits=bits, group=group, block_m=block_m,
                      out_dtype=out_dtype, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group", "block_m", "interpret", "out_dtype"))
def mpgemm_2d(
    x: jax.Array,            # (M, K) bf16
    w_tiles: jax.Array,      # (Kt, Nt, bk_store, bn) int8 (tile-major packed)
    scales: jax.Array,       # (K // group, N) f32
    *,
    bits: int,
    group: int = 128,
    block_m: int = 128,
    interpret: bool = False,
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    return _gemm_call(_mpgemm_kernel, x, None, w_tiles, scales, bits=bits,
                      group=group, block_m=block_m, out_dtype=out_dtype,
                      interpret=interpret)
