"""Quantization primitives: per-group / per-channel / per-token symmetric
quantization, INT4 nibble packing, and fp8 casting.

Conventions
-----------
* Weights are quantized along the **contraction (K) axis** in groups of
  ``group`` (default 128, the paper's AWQ/GPTQ-compatible granularity):
  ``w_q[k, n] = round(w[k, n] / scale[k // group, n])``.
* INT4 values live in int8 containers.  *Packed* weights store two
  nibbles per container along the quantized axis: packed[k] holds values
  (2k) in the low nibble and (2k+1) in the high nibble — the same
  sub-word ordering the offline packer (packing.py) preserves.  Packed
  int4 *KV* is half-split instead (:func:`pack_int4_halves`): packed[j]
  holds head_dim index j low and j + head_dim/2 high, so the attention
  kernels unpack with a lane concat rather than an interleave.
* KV cache quantization is per-(token, head) absmax — each (t, h) row of
  head_dim values shares one scale.  This matches per-head dynamic KV
  quantization (KIVI/QServe-style) and keeps scale application lane-aligned
  on TPU (scale broadcasts over the 128-lane head_dim axis).

Everything here is pure jnp and jit-safe; these functions double as the
oracle pieces used by kernels/ref.py.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .precision import FormatSpec

# ---------------------------------------------------------------------------
# Symmetric integer quantization
# ---------------------------------------------------------------------------


def absmax_scale(x: jax.Array, axis, qmax: float, keepdims=True) -> jax.Array:
    """Symmetric absmax scale; safe for all-zero slices."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=keepdims)
    return jnp.maximum(amax, 1e-8) / qmax


def quantize_int(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Round-to-nearest symmetric quantization to signed ``bits``-bit ints."""
    qmax = 2 ** (bits - 1) - 1
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


# -- weights (per-group along K) --------------------------------------------


def quantize_weight_grouped(
    w: jax.Array, bits: int, group: int = 128
) -> Tuple[jax.Array, jax.Array]:
    """Quantize (K, N) weights per-(group, column).

    Returns (q [K, N] int8 holding b-bit values, scales [K//group, N] f32).
    """
    K, N = w.shape
    assert K % group == 0, f"K={K} not divisible by group={group}"
    wg = w.reshape(K // group, group, N)
    scale = absmax_scale(wg, axis=1, qmax=2 ** (bits - 1) - 1)   # (G,1,N)
    q = quantize_int(wg, scale, bits).reshape(K, N)
    return q, scale[:, 0, :]


def dequantize_weight_grouped(
    q: jax.Array, scale: jax.Array, group: int = 128,
    dtype=jnp.bfloat16,
) -> jax.Array:
    K, N = q.shape
    G = K // group
    deq = q.reshape(G, group, N).astype(jnp.float32) * scale[:, None, :]
    return deq.reshape(K, N).astype(dtype)


# -- INT4 nibble packing -----------------------------------------------------


def pack_int4(q: jax.Array, axis: int = 0) -> jax.Array:
    """Pack int8-held int4 values two-per-byte along ``axis``.

    Low nibble = even index, high nibble = odd index.  Values must be in
    [-8, 7].
    """
    assert q.shape[axis] % 2 == 0
    lo = jax.lax.slice_in_dim(q, 0, q.shape[axis], stride=2, axis=axis)
    hi = jax.lax.slice_in_dim(q, 1, q.shape[axis], stride=2, axis=axis)
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def unpack_int4(p: jax.Array, axis: int = 0) -> jax.Array:
    """Inverse of pack_int4: int8 containers -> int8-held int4 values."""
    # sign-extend the low nibble: shift up then arithmetic shift down.
    lo = ((p << 4).astype(jnp.int8) >> 4).astype(jnp.int8)
    hi = (p >> 4).astype(jnp.int8)          # arithmetic shift keeps sign
    stacked = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(p.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def pack_int4_halves(q: jax.Array) -> jax.Array:
    """Pack int8-held int4 values two-per-byte along the last axis in
    half-split order: low nibble = index j, high nibble = index j + n/2."""
    n = q.shape[-1]
    assert n % 2 == 0
    lo, hi = q[..., :n // 2], q[..., n // 2:]
    return ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)


def unpack_int4_halves(p: jax.Array) -> jax.Array:
    """Inverse of pack_int4_halves, as int32 values.

    Each nibble is sign-extended with int32 shifts and the halves are
    concatenated along the last axis: the TPU's vector unit has no 8-bit
    shifts, and this form lowers inside a Pallas kernel too (the
    attention kernels call it on their KV tiles)."""
    w = p.astype(jnp.int32)
    return jnp.concatenate([(w << 28) >> 28, (w << 24) >> 28], axis=-1)


# -- activations (dynamic per-token) ----------------------------------------


def quantize_act_per_token(x: jax.Array, bits: int = 8):
    """Dynamic per-token symmetric quantization (last axis = features)."""
    scale = absmax_scale(x, axis=-1, qmax=2 ** (bits - 1) - 1)
    return quantize_int(x, scale, bits), scale


# -- KV cache (per-token, per-head) ------------------------------------------


def quantize_kv(kv: jax.Array, spec: FormatSpec):
    """Quantize KV states of shape (..., heads, head_dim).

    Returns (q, scale) where scale has shape (..., heads, 1).  For float
    formats (fp8/bf16) q is a cast and scale is per-tensor-ish (ones /
    absmax-normalizing for fp8).
    """
    if spec.is_float:
        if spec.bits == 16:
            return kv.astype(spec.dtype), jnp.ones(kv.shape[:-1] + (1,), jnp.float32)
        # fp8: per-(token, head) normalization into representable range.
        scale = absmax_scale(kv, axis=-1, qmax=spec.qmax)
        return (kv.astype(jnp.float32) / scale).astype(spec.dtype), scale
    scale = absmax_scale(kv, axis=-1, qmax=spec.qmax)
    q = quantize_int(kv, scale, spec.bits)
    if spec.packed:  # int4: pack head_dim two-per-byte, half-split
        q = pack_int4_halves(q)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, spec: FormatSpec,
                  dtype=jnp.bfloat16) -> jax.Array:
    if spec.is_float:
        if spec.bits == 16:
            return q.astype(dtype)
        return (q.astype(jnp.float32) * scale).astype(dtype)
    if spec.packed:
        q = unpack_int4_halves(q)
    return (q.astype(jnp.float32) * scale).astype(dtype)


# -- fp8 ----------------------------------------------------------------------


def quantize_fp8(x: jax.Array, dtype=jnp.float8_e4m3fn):
    """Per-tensor scaled fp8 cast."""
    import ml_dtypes
    qmax = float(ml_dtypes.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-8) / qmax
    return (x.astype(jnp.float32) / scale).astype(dtype), scale


def dequantize_fp8(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale).astype(dtype)
