"""Plain reference of the served model: a float32 forward pass in
``jax.numpy``, one layer at a time, with no kernel, cache or batching of
the program's.  It imports nothing of the program and draws its own
weights from the seed (:mod:`bench.weights`).

The block: pre-norm RMSNorm with gain ``1 + g``; q/k/v projections with
grouped-query attention (query head ``h`` reads KV head ``h // (H /
H_kv)``); rotary embedding on the leading ``rotary_pct`` of each head in
interleaved pairs; causal softmax attention scaled by ``head_dim**-0.5``;
the output projection; a SwiGLU MLP ``(silu(x W1) * (x W3)) W2``; a final
RMSNorm; and the LM head (the embedding's transpose when tied).

``kv_bits`` rounds K (after the rotary embedding) and V to symmetric
integers with one absmax scale per (token, head) before attention: the
form of a low-bit KV cache, used for the control.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def rope(x, pos, rotary_pct, theta):
    """x: (B, S, H, D); rotate the leading ``rotary_pct`` of D in
    interleaved pairs (2i, 2i + 1)."""
    b, s, h, d = x.shape
    rot = int(d * rotary_pct) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (S, rot/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xr = x[..., :rot].reshape(b, s, h, rot // 2, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], -1)
    return jnp.concatenate([out.reshape(b, s, h, rot), x[..., rot:]], -1)


def kv_round(x, bits: int):
    """Symmetric ``bits``-bit rounding with one scale per (token, head)."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer_forward(x, w, m: W.Model, kv_bits: Optional[int]):
    """One block over x: (B, S, d) float32."""
    b, s, _ = x.shape
    h_, hkv, hd = m.n_heads, m.n_kv_heads, m.head_dim
    pos = jnp.arange(s)
    h = rms_norm(x, w["ln1"], m.norm_eps)
    q = (h @ w["wq"]).reshape(b, s, h_, hd)
    k = (h @ w["wk"]).reshape(b, s, hkv, hd)
    v = (h @ w["wv"]).reshape(b, s, hkv, hd)
    q = rope(q, pos, m.rotary_pct, m.rope_theta)
    k = rope(k, pos, m.rotary_pct, m.rope_theta)
    if kv_bits is not None:
        k, v = kv_round(k, kv_bits), kv_round(v, kv_bits)
    rep = h_ // hkv
    causal = pos[:, None] >= pos[None, :]

    def attend(qkv):                       # one sequence at a time
        qb, kb, vb = qkv
        kb, vb = jnp.repeat(kb, rep, 1), jnp.repeat(vb, rep, 1)
        sc = jnp.einsum("qhd,khd->hqk", qb, kb) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vb)

    attn = jax.lax.map(attend, (q, k, v)).reshape(b, s, h_ * hd)
    x = x + attn @ w["wo"]
    h2 = rms_norm(x, w["ln2"], m.norm_eps)
    return x + (jax.nn.silu(h2 @ w["w1"]) * (h2 @ w["w3"])) @ w["w2"]


@functools.partial(jax.jit, static_argnums=(1,))
def _embed(key, m: W.Model, tokens):
    return jnp.take(W.embed_bf16(key, m).astype(jnp.float32), tokens, 0)


@functools.partial(jax.jit, static_argnums=(1,))
def _logits(key, m: W.Model, x, rows):
    h = jnp.take_along_axis(x, rows[..., None], 1)              # (B, P, d)
    h = rms_norm(h, W.final_norm_bf16(key, m).astype(jnp.float32),
                 m.norm_eps)
    return h @ W.head_bf16(key, m).astype(jnp.float32)


def logits_at(m: W.Model, seed: int, tokens: np.ndarray, rows: np.ndarray,
              kv_bits: Optional[int] = None) -> jax.Array:
    """Logits (B, P, vocab) at positions ``rows`` (B, P) of ``tokens``
    (B, S); every row of ``tokens`` attends causally, so padding after a
    sequence's end changes nothing before it."""
    key = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(key, m, jnp.asarray(tokens, jnp.int32))
        for layer in range(m.n_layers):
            x = layer_forward(x, W.layer_f32(key, m, layer), m, kv_bits)
        return _logits(key, m, x, jnp.asarray(rows, jnp.int32))
