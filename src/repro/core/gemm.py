"""Mixed-precision GEMM — the online stage of the paper's GEMM pipeline.

Three compute paths over the same packed weights:

* ``impl="xla"``    — pure-jnp math, written so XLA fuses the dequant into
  the dot (weights are read from HBM at their low-bit width).  This is the
  path the distributed model code uses (pjit-friendly, identical math to
  the Pallas kernel; kernels/ref.py reuses it as the oracle).
* ``impl="pallas"`` — the Pallas TPU kernel (kernels/mpgemm.py): in-kernel
  nibble unpack + I2F + MXU matmul with grid pipelining (paper §4.3's
  parallel MMA-dequantization).
* ``impl="naive"``  — the baseline the paper criticizes (TensorRT-LLM-style
  runtime dequantization): weights are dequantized to a **materialized**
  bf16 buffer first (enforced with an optimization barrier), then a dense
  matmul runs.  Costs full 16-bit weight traffic + a separate dequant pass.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import quantize as Q
from .packing import PackedWeight, dequantize_packed, unpack_weight
from .precision import PrecisionPolicy


#: the name-stack scope of the XLA paths' weight unpack: ops XLA fuses
#: into the dot keep the dot's name, the rest carry this one under the
#: caller's stage
DEQUANT_SCOPE = "dequant"


def _dequant_fused(p: PackedWeight, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize for the fused XLA path (convert feeds straight into dot)."""
    with jax.named_scope(DEQUANT_SCOPE):
        return dequantize_packed(p, dtype=dtype)


def mp_matmul(
    x: jax.Array,
    w: PackedWeight,
    policy: PrecisionPolicy,
    impl: str = "xla",
) -> jax.Array:
    """y = x @ W for quantized, offline-packed W.

    x : (..., K) activation in policy.compute_dtype (or to-be-quantized for A8)
    w : PackedWeight of logical shape (K, N)
    """
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.mpgemm(x, w, policy=policy)
    if impl == "naive":
        wd = _dequant_fused(w, policy.compute_dtype)
        # Force materialization of the dequantized weights in HBM — this is
        # the "dequantize first, matmul second" baseline (paper §2, the
        # TensorRT-LLM runtime-dequant overhead it cites).
        wd = jax.lax.optimization_barrier(wd)
        return jnp.dot(x.astype(policy.compute_dtype), wd)
    assert impl == "xla", impl

    if policy.int8_matmul:
        # W8A8 / W4A8: native MXU s8×s8→s32 with per-token × per-group
        # rescale (unpack_weight yields s8-held values for both widths).
        xq, xscale = Q.quantize_act_per_token(x, bits=8)
        with jax.named_scope(DEQUANT_SCOPE):
            qw = unpack_weight(w)                 # (K, N) int8
        acc = jax.lax.dot_general(
            xq, qw, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        # per-group weight scales → effective per-column scale (group
        # structure folded; exact when one group spans K, else mean-field —
        # the exact path multiplies per-group partial sums, which XLA can't
        # express in one s8 dot; we use K-grouped dots when G > 1).
        G = w.scales.shape[0]
        if G == 1:
            y = acc.astype(jnp.float32) * (xscale * w.scales[0][None])
        else:
            K, N = w.shape
            gsz = K // G
            xg = xq.reshape(*xq.shape[:-1], G, gsz)
            wg = qw.reshape(G, gsz, N)
            accg = jnp.einsum("...gk,gkn->...gn", xg, wg,
                              preferred_element_type=jnp.int32)
            y = jnp.einsum("...gn,gn->...n", accg.astype(jnp.float32),
                           w.scales) * xscale
        return y.astype(policy.compute_dtype)

    # W4A16 / W8A16 / fp8: dequant fused into the dot by XLA.
    wd = _dequant_fused(w, policy.compute_dtype)
    return jnp.dot(x.astype(policy.compute_dtype), wd)


def dense_matmul(x: jax.Array, w: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Reference full-precision GEMM (the FP16×FP16 baseline of Fig. 13)."""
    return jnp.dot(x.astype(dtype), w.astype(dtype))
