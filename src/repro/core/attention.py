"""Mixed-precision attention — the paper's attention pipeline (§3.4).

Q stays in compute precision (bf16); K/V live in the quantized cache and are
dequantized **inside the attention contraction** (never materialized as a
full bf16 tensor in HBM).  Scale application is algebraically hoisted out of
the dot products:

    S = (Q · K_q) * k_scale        (per-token,per-head scalar)
    O = (P * v_scale) · V_q

so the MXU consumes the low-bit operands' casts directly — the XLA analogue
of the paper's adaptive-head-alignment + on-the-fly I2F.  The Pallas decode
kernel (kernels/kvattn.py) does the same math blockwise with online softmax.

The *baseline* the paper criticizes (vLLM/TensorRT: dequantize the whole KV
cache to 16-bit first, then run standard attention) is ``impl="dequant_first"``
— an optimization barrier forces the full bf16 KV materialization.

Supports GQA, causal + sliding-window masks, and per-batch valid lengths.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import kvcache as KV
from . import quantize as Q
from .precision import FormatSpec


def _unpack_if_needed(x: jax.Array, spec: FormatSpec) -> jax.Array:
    if spec.packed:
        return Q.unpack_int4_halves(x)
    return x


# ---------------------------------------------------------------------------
# Prefill (full-sequence) attention — bf16 Q/K/V, causal (+ window) mask.
# ---------------------------------------------------------------------------


def prefill_attention(
    q: jax.Array,              # (B, S, H, D)
    k: jax.Array,              # (B, S, Hkv, D)
    v: jax.Array,              # (B, S, Hkv, D)
    window: Optional[int] = None,
    causal: bool = True,
) -> jax.Array:
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, D)
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / jnp.sqrt(D).astype(jnp.float32)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool) if not causal else (kpos <= qpos)
    if window is not None:
        mask &= kpos > (qpos - window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(B, S, H, D)


# §Perf hillclimb #1 (beyond-paper): causal-triangle block iteration.
# The baseline iterates all nq×nk score blocks and masks; with BLOCK_SKIP
# the scan walks only the nq(nq+1)/2 blocks on/below the diagonal —
# ~2× less attention compute AND ~2× less materialized-score HBM traffic
# at long sequence.  Toggled globally so the dry-run can record both.
BLOCK_SKIP = False

# §Perf hillclimb #2: sequence-parallel prefill attention (shard_map) —
# installed by launch code for meshes where head counts don't divide the
# model axis.  Callable(q, k, v, causal, window) -> out or None.
SP_PREFILL = None


def set_block_skip(on: bool) -> None:
    global BLOCK_SKIP
    BLOCK_SKIP = bool(on)


def set_sp_prefill(fn) -> None:
    global SP_PREFILL
    SP_PREFILL = fn


def flash_attention(
    q: jax.Array,              # (B, Sq, H, D)
    k: jax.Array,              # (B, Sk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,               # int / traced scalar / None
    pos_offset=0,              # absolute position of q[0] (for chunked prefill)
    q_chunk: int = 512,
    kv_chunk: int = 512,
) -> jax.Array:
    """Memory-bounded attention: online softmax over (q_chunk × kv_chunk)
    tiles.  Pure XLA (scan over blocks) — the compile-friendly prefill path
    for 4k–32k sequences; peak intermediate is O(q_chunk·kv_chunk) not O(S²).
    """
    if (SP_PREFILL is not None and causal
            and isinstance(pos_offset, int) and pos_offset == 0
            and q.shape[1] > q_chunk):
        out = SP_PREFILL(q, k, v, causal=causal, window=window)
        if out is not None:
            return out
    if (BLOCK_SKIP and causal and q.shape[1] == k.shape[1]
            and isinstance(pos_offset, int) and pos_offset == 0
            and q.shape[1] > q_chunk):
        return _flash_triangle(q, k, v, window=window, q_chunk=q_chunk,
                               kv_chunk=kv_chunk)
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    Sk = k.shape[1]
    rep = H // Hkv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    # pad to multiples
    nq = -(-Sq // qc)
    nk = -(-Sk // kc)
    pad_q = nq * qc - Sq
    pad_k = nk * kc - Sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qg = qp.reshape(B, nq, qc, Hkv, rep, D).transpose(1, 0, 3, 4, 2, 5)
    # (nq, B, Hkv, rep, qc, D)
    kb = kp.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    def q_block(args):
        qi, qblk = args                                    # (B,Hkv,rep,qc,D)
        qpos = pos_offset + qi * qc + jnp.arange(qc)

        def kv_step(carry, blk):
            m, l, acc = carry
            kj, kblk, vblk = blk
            s = jnp.einsum("bhrqd,bhkd->bhrqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            kpos = kj * kc + jnp.arange(kc)
            mask = (kpos[None, :] < Sk)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > (qpos[:, None] - window)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            p = jnp.where(mask[None, None, None], p, 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhrqk,bhkd->bhrqd", p.astype(qblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, rep, qc, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, qc, 1), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, qc, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        return (acc / jnp.maximum(l, 1e-20)).astype(q.dtype)

    out = jax.lax.map(q_block, (jnp.arange(nq), qg))       # (nq,B,Hkv,rep,qc,D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * qc, H, D)
    return out[:, :Sq]


def _flash_triangle(q, k, v, *, window, q_chunk, kv_chunk):
    """Causal flash over ONLY the lower-triangle block pairs.

    One scan over T = nq(nq+1)/2 (qi, kj) pairs in row-major order; the
    online-softmax state (m, l, acc) resets at each row start (kj == 0)
    and the running normalized output is written into out_buf[qi] every
    step — the row's final pair leaves the finished value, later pairs
    write other rows.  No conditionals, uniform trip count, SPMD-friendly.
    """
    import numpy as np

    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    bc = min(q_chunk, S)
    assert q_chunk == kv_chunk, "triangle path uses square blocks"
    n = -(-S // bc)
    pad = n * bc - S
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qg = qp.reshape(B, n, bc, Hkv, rep, D).transpose(1, 0, 3, 4, 2, 5)
    kb = kp.reshape(B, n, bc, Hkv, D).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(B, n, bc, Hkv, D).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)

    qi_list, kj_list = [], []
    for i in range(n):
        for j in range(i + 1):
            qi_list.append(i)
            kj_list.append(j)
    qi_arr = jnp.asarray(np.array(qi_list, np.int32))
    kj_arr = jnp.asarray(np.array(kj_list, np.int32))

    def pair_step(carry, idx):
        m, l, acc, out_buf = carry
        qi, kj = idx
        fresh = (kj == 0)
        m = jnp.where(fresh, jnp.full_like(m, -1e30), m)
        l = jnp.where(fresh, jnp.zeros_like(l), l)
        acc = jnp.where(fresh, jnp.zeros_like(acc), acc)

        qblk = jax.lax.dynamic_index_in_dim(qg, qi, 0, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kb, kj, 0, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vb, kj, 0, keepdims=False)
        s = jnp.einsum("bhrqd,bhkd->bhrqk", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        qpos = qi * bc + jnp.arange(bc)
        kpos = kj * bc + jnp.arange(bc)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < S)
        if window is not None:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask[None, None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhrqk,bhkd->bhrqd", p.astype(qblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        out = acc_new / jnp.maximum(l_new, 1e-20)
        # in-place slice write into an f32 carry buffer — keeping the
        # buffer in the compute dtype (f32) is what lets XLA update it in
        # place; a bf16 buffer makes the loop round-trip a full-buffer
        # dtype conversion every step (measured in §Perf iteration 2).
        out_buf = jax.lax.dynamic_update_slice(
            out_buf, out[None], (qi,) + (0,) * (out_buf.ndim - 1))
        return (m_new, l_new, acc_new, out_buf), None

    m0 = jnp.full((B, Hkv, rep, bc, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, bc, 1), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, bc, D), jnp.float32)
    buf0 = jnp.zeros((n, B, Hkv, rep, bc, D), jnp.float32)
    (_, _, _, out_buf), _ = jax.lax.scan(
        pair_step, (m0, l0, a0, buf0), (qi_arr, kj_arr))
    out = out_buf.astype(q.dtype).transpose(1, 0, 4, 2, 3, 5) \
        .reshape(B, n * bc, H, D)
    return out[:, :S]


# ---------------------------------------------------------------------------
# Decode attention over the quantized cache.
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,              # (B, T, H, D) — T new queries (typically 1)
    cache: KV.KVCache,
    spec: FormatSpec,
    pos: jax.Array,            # scalar: index of the first new token
    window: Optional[int] = None,
    impl: str = "fused",
    block_s: Optional[int] = None,   # pallas impl: KV tile height
) -> jax.Array:
    """Attend T new queries against `pos + t` cached tokens (causal)."""
    B, T, H, D = q.shape
    Hkv = cache.k.shape[2]
    S = cache.max_seq
    rep = H // Hkv

    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.kvattn_decode(q, cache, spec, pos, window=window,
                                  block_s=block_s or 256)

    if impl == "dequant_first":
        # Baseline: materialize the whole cache in bf16 (what §4.2 says
        # PyTorch/TensorRT/vLLM do), then plain attention.
        kd = jax.lax.optimization_barrier(KV.dequant_k(cache, spec, q.dtype))
        vd = jax.lax.optimization_barrier(KV.dequant_v(cache, spec, q.dtype))
        scores = jnp.einsum("bthrd,bshd->bhrts",
                            q.reshape(B, T, Hkv, rep, D), kd,
                            preferred_element_type=jnp.float32)
    else:
        assert impl == "fused", impl
        # Fused path: dot against the low-bit ints' cast; scales applied to
        # the (tiny) score matrix afterwards.
        kq = _unpack_if_needed(cache.k, spec).astype(q.dtype)   # fused by XLA
        scores = jnp.einsum("bthrd,bshd->bhrts",
                            q.reshape(B, T, Hkv, rep, D), kq,
                            preferred_element_type=jnp.float32)
        # k_scale: (B, S, Hkv, 1) → (B, Hkv, 1, 1, S)
        scores = scores * cache.k_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]

    scores *= 1.0 / jnp.sqrt(D).astype(jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    qpos = pos[:, None] + jnp.arange(T)[None, :]                # (B, T)
    kpos = jnp.arange(S)                                        # (S,)
    mask = kpos[None, None, :] <= qpos[..., None]               # (B, T, S)
    if window is not None:
        mask &= kpos[None, None, :] > (qpos[..., None] - window)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)

    if impl == "dequant_first":
        out = jnp.einsum("bhrts,bshd->bthrd", probs.astype(q.dtype), vd)
    else:
        # fold v_scale into probs (per (B, S, Hkv) scalar): P' = P * vs
        vs = cache.v_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
        pv = (probs * vs).astype(q.dtype)
        vq = _unpack_if_needed(cache.v, spec).astype(q.dtype)
        out = jnp.einsum("bhrts,bshd->bthrd", pv, vq)
    return out.reshape(B, T, H, D)


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder): static encoder KV, no causal mask.
# ---------------------------------------------------------------------------


def cross_attention(q: jax.Array, cache: KV.KVCache, spec: FormatSpec,
                    enc_len: Optional[int] = None) -> jax.Array:
    B, T, H, D = q.shape
    Hkv = cache.k.shape[2]
    rep = H // Hkv
    kq = _unpack_if_needed(cache.k, spec).astype(q.dtype)
    scores = jnp.einsum("bthrd,bshd->bhrts", q.reshape(B, T, Hkv, rep, D), kq,
                        preferred_element_type=jnp.float32)
    scores = scores * cache.k_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    scores *= 1.0 / jnp.sqrt(D).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    vs = cache.v_scale[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    vq = _unpack_if_needed(cache.v, spec).astype(q.dtype)
    out = jnp.einsum("bhrts,bshd->bthrd", (probs * vs).astype(q.dtype), vq)
    return out.reshape(B, T, H, D)
