"""Dense decoder-only transformer family (llama-style), covering:
smollm-360m, chatglm3-6b (partial/2d RoPE, GQA kv=2), gemma3-1b (5:1
local:global sliding window), mistral-large-123b, the internvl2 language
decoder, and the attention/FFN backbone reused by the MoE family.

Functional, scan-over-layers, quantization-transparent (weights may be
bf16 arrays or PackedWeight), KV cache quantized per PrecisionPolicy.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as A
from repro.core import kvcache as KV
from repro.core import paged_kvcache as PKV
from repro.core.precision import PrecisionPolicy
from repro.configs.base import ModelConfig

from . import common as C
from . import moe as MOE

# "no window" sentinel usable as a traced scalar — one constant shared
# with the decode kernels' window operand (kernels/kvattn.NO_WINDOW), so
# the mask arithmetic can never desynchronize from the model layer.
from repro.kernels.kvattn import NO_WINDOW as BIG_WINDOW  # noqa: E402


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer_stack(cfg: ModelConfig, key) -> Dict[str, Any]:
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = C.split_keys(key, ["wq", "wk", "wv", "wo", "w1", "w2", "w3",
                            "moe", "router", "shared"])
    p = {
        "ln1": jnp.zeros((L, d), jnp.bfloat16),
        "ln2": jnp.zeros((L, d), jnp.bfloat16),
        "wq": C.dense_init(ks["wq"], (L, d, H * hd)),
        "wk": C.dense_init(ks["wk"], (L, d, Hkv * hd)),
        "wv": C.dense_init(ks["wv"], (L, d, Hkv * hd)),
        "wo": C.dense_init(ks["wo"], (L, H * hd, d)),
    }
    if cfg.n_experts:
        E = cfg.n_experts
        p["router"] = C.dense_init(ks["router"], (L, d, E), scale=0.02)
        p["we1"] = C.dense_init(ks["moe"], (L, E, d, f))
        p["we3"] = C.dense_init(jax.random.fold_in(ks["moe"], 1), (L, E, d, f))
        p["we2"] = C.dense_init(jax.random.fold_in(ks["moe"], 2), (L, E, f, d))
        if cfg.moe_dense_residual or cfg.shared_expert:
            p["ws1"] = C.dense_init(ks["shared"], (L, d, f))
            p["ws3"] = C.dense_init(jax.random.fold_in(ks["shared"], 1), (L, d, f))
            p["ws2"] = C.dense_init(jax.random.fold_in(ks["shared"], 2), (L, f, d))
    else:
        p["w1"] = C.dense_init(ks["w1"], (L, d, f))
        p["w3"] = C.dense_init(ks["w3"], (L, d, f))
        p["w2"] = C.dense_init(ks["w2"], (L, f, d))
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = C.split_keys(key, ["embed", "layers", "head", "proj"])
    params = {
        "embed": C.dense_init(ks["embed"], (cfg.vocab, cfg.d_model), scale=0.02),
        "layers": init_layer_stack(cfg, ks["layers"]),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.bfloat16),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = C.dense_init(ks["head"], (cfg.d_model, cfg.vocab),
                                         scale=0.02)
    if cfg.n_img_tokens:   # VLM projector stub: ViT width 1024 → d_model
        params["img_proj"] = C.dense_init(ks["proj"], (1024, cfg.d_model))
    return params


# ---------------------------------------------------------------------------
# Per-layer pieces
# ---------------------------------------------------------------------------


def layer_window(cfg: ModelConfig, layer_idx) -> jax.Array:
    """Per-layer effective window as a traced scalar (BIG_WINDOW = global).

    gemma3: every ``local_global_period``-th layer is global, rest local.
    """
    if cfg.window is None:
        return jnp.int32(BIG_WINDOW)
    if cfg.local_global_period:
        is_global = (layer_idx % cfg.local_global_period) == (
            cfg.local_global_period - 1)
        return jnp.where(is_global, jnp.int32(BIG_WINDOW),
                         jnp.int32(cfg.window))
    return jnp.int32(cfg.window)


def qkv(h, lp, cfg: ModelConfig, policy, impl):
    B, T, _ = h.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = C.linear(h, lp["wq"], policy, impl).reshape(B, T, H, hd)
    k = C.linear(h, lp["wk"], policy, impl).reshape(B, T, Hkv, hd)
    v = C.linear(h, lp["wv"], policy, impl).reshape(B, T, Hkv, hd)
    return q, k, v


def ffn(h, lp, cfg: ModelConfig, policy, impl):
    if cfg.n_experts:
        y = MOE.moe_ffn(h, lp, cfg, policy, impl)
        if cfg.moe_dense_residual or cfg.shared_expert:
            y = y + C.swiglu(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                 "w2": lp["ws2"]}, policy, impl)
        return y
    return C.swiglu(h, {"w1": lp["w1"], "w3": lp["w3"], "w2": lp["w2"]},
                    policy, impl)


# ---------------------------------------------------------------------------
# Full-sequence forward (training / logit-consistency checks) — bf16 path
# ---------------------------------------------------------------------------


def hidden_states(params, cfg: ModelConfig, tokens,
                  img_embeds: Optional[jax.Array] = None,
                  policy: Optional[PrecisionPolicy] = None,
                  impl: str = "xla", remat: bool = False) -> jax.Array:
    """tokens: (B, S_text) int32 → final normed hidden (B, S, d).

    VLM: img_embeds (B, n_img, 1024) are projected and prepended; S =
    n_img + S_text.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    if img_embeds is not None:
        proj = C.linear(img_embeds.astype(x.dtype), params["img_proj"],
                        policy, impl)
        x = jnp.concatenate([proj, x], axis=1)
    B, S, d = x.shape
    pos = jnp.arange(S)
    if not cfg.use_rope:
        x = x + C.sinusoidal_pos(S, d)[None]

    def body(xc, sl):
        lp, idx = sl
        h = C.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv(h, lp, cfg, policy, impl)
        if cfg.use_rope:
            q = C.apply_rope(q, pos, rotary_pct=cfg.rotary_pct,
                             theta=cfg.rope_theta)
            k = C.apply_rope(k, pos, rotary_pct=cfg.rotary_pct,
                             theta=cfg.rope_theta)
        win = layer_window(cfg, idx)
        attn = A.flash_attention(q, k, v, causal=True, window=win)
        xc = xc + C.linear(attn.reshape(B, S, -1), lp["wo"], policy, impl)
        h2 = C.rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + ffn(h2, lp, cfg, policy, impl)
        return xc, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x,
                        (params["layers"], jnp.arange(cfg.n_layers)))
    return C.rms_norm(x, params["final_norm"], cfg.norm_eps)


def lm_logits(params, h: jax.Array) -> jax.Array:
    w = params["embed"].T if "lm_head" not in params else params["lm_head"]
    return jnp.dot(h, w.astype(h.dtype))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, policy: PrecisionPolicy, batch: int,
               max_seq: int) -> KV.KVCache:
    f = jax.vmap(lambda _: KV.init_cache(batch, max_seq, cfg.n_kv_heads,
                                         cfg.hd, policy.kv))
    return f(jnp.arange(cfg.n_layers))           # leaves: (L, B, S, H, Ds)


def cache_spec(cfg: ModelConfig, policy: PrecisionPolicy, batch: int,
               max_seq: int) -> KV.KVCache:
    base = KV.cache_spec(batch, max_seq, cfg.n_kv_heads, cfg.hd, policy.kv)
    L = cfg.n_layers
    f = lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype)
    return jax.tree.map(f, base)


def init_paged_cache(cfg: ModelConfig, policy: PrecisionPolicy, n_slots: int,
                     n_blocks: int, block_size: int,
                     blocks_per_slot: int) -> PKV.PagedKVCache:
    """Per-layer block pools stacked (L, n_blocks, block_size, H, Ds).

    The block table is replicated across layers (a logical block occupies
    the same pool index in every layer's pool) so the stacked cache scans
    over layers exactly like the dense cache; the replication is int32 and
    negligible next to the pools."""
    f = jax.vmap(lambda _: PKV.init_paged(
        n_slots, n_blocks, block_size, cfg.n_kv_heads, cfg.hd, policy.kv,
        blocks_per_slot=blocks_per_slot))
    return f(jnp.arange(cfg.n_layers))


# ---------------------------------------------------------------------------
# Prefill: full prompt → last-token logits + populated quantized cache
# ---------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, policy: PrecisionPolicy, tokens,
            cache: KV.KVCache, img_embeds: Optional[jax.Array] = None,
            impl: str = "xla") -> Tuple[jax.Array, KV.KVCache]:
    x = jnp.take(params["embed"], tokens, axis=0).astype(policy.compute_dtype)
    if img_embeds is not None:
        proj = C.linear(img_embeds.astype(x.dtype), params["img_proj"],
                        policy, impl)
        x = jnp.concatenate([proj, x], axis=1)
    B, S, d = x.shape
    pos = jnp.arange(S)
    if not cfg.use_rope:
        x = x + C.sinusoidal_pos(S, d)[None]

    def body(xc, sl):
        lp, cache_l, idx = sl
        h = C.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv(h, lp, cfg, policy, impl)
        if cfg.use_rope:
            q = C.apply_rope(q, pos, rotary_pct=cfg.rotary_pct,
                             theta=cfg.rope_theta)
            k = C.apply_rope(k, pos, rotary_pct=cfg.rotary_pct,
                             theta=cfg.rope_theta)
        win = layer_window(cfg, idx)
        attn = A.flash_attention(q, k, v, causal=True, window=win)
        # write the quantized KV for subsequent decoding (attention pipeline:
        # KV is stored low-bit, Q adapts at read time)
        cache_l = KV.append(cache_l, k, v, jnp.int32(0), policy.kv)
        xc = xc + C.linear(attn.reshape(B, S, -1), lp["wo"], policy, impl)
        h2 = C.rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + ffn(h2, lp, cfg, policy, impl)
        return xc, cache_l

    x, new_cache = jax.lax.scan(
        body, x, (params["layers"], cache, jnp.arange(cfg.n_layers)))
    h_last = C.rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return lm_logits(params, h_last), new_cache


# ---------------------------------------------------------------------------
# Decode: one token per call against the quantized cache
# ---------------------------------------------------------------------------


def decode_step(params, cfg: ModelConfig, policy: PrecisionPolicy,
                tokens, cache, pos,
                impl: str = "xla", attn_impl: Optional[str] = None,
                attn_block_s: Optional[int] = None,
                max_live: Optional[int] = None,
                valid: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, KV.KVCache]:
    """tokens: (B, T); pos: scalar or (B,) position of the first new token.

    T > 1 is the engine's chunked ragged prefill / preemption replay /
    mixed prefill+decode step: the T queries attend causally to
    ``pos + t`` cached tokens each.  ``cache`` may be the dense
    :class:`KV.KVCache` slab or a :class:`PKV.PagedKVCache` block pool —
    paged appends go through the block table and decode/prefill alike run
    the paged multi-query Pallas kernel, which resolves the block table
    *inside* the kernel (no dense per-slot view; see
    models/common.attend_decode).

    ``valid`` (optional, (B,) int32) is the mixed-step ragged mask: slot
    b's first ``valid[b]`` chunk rows are real, the rest padding.  KV
    appends drop padded rows (they must not dirty cells past a slot's
    frontier — shared prefix blocks are refcounted), and the returned
    logits are taken from each slot's last *valid* row instead of row
    T-1.  Attention over padded rows is computed and discarded.

    ``attn_impl`` picks the decode-attention path independently of the
    GEMM ``impl`` (default: ``fused`` XLA, or the flash-decode kernels
    when ``impl == "pallas"``); ``attn_block_s`` is the dense kernel's
    tile height and ``max_live`` (static) the batch's live-context
    high-water mark bounding paged traffic — the serving engine sets all
    three.

    Every device op of the step lies under one ``jax.named_scope`` stage
    (``embed``, ``layers`` around the scan, and in its body ``qkv``,
    ``kv_append``, ``attn``, ``attn_out`` and ``ffn``; then ``lm_head``),
    which the profiler's trace carries in each op's name stack.
    """
    paged = isinstance(cache, PKV.PagedKVCache)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens,
                     axis=0).astype(policy.compute_dtype)
        B, T, d = x.shape
        pos = jnp.asarray(pos, jnp.int32)
        per_slot = pos.ndim == 1
        # stacked cache leaves carry (L, ...): dense k is (L, B, S, H, Ds),
        # paged tables are (L, n_slots, blocks_per_slot) mapping bs-token
        # blocks
        if paged:
            n_ctx = cache.block_table.shape[2] * cache.k.shape[2]
        else:
            n_ctx = cache.k.shape[2]
        if not cfg.use_rope:
            sp = C.sinusoidal_pos(n_ctx, d)
            if per_slot:
                idx = pos[:, None] + jnp.arange(T)[None]
                x = x + jnp.take(sp, idx, axis=0)
            else:
                x = x + jax.lax.dynamic_slice_in_dim(sp, pos, T)[None]
        if per_slot:
            rope_pos = pos[:, None] + jnp.arange(T)[None]
        else:
            rope_pos = jnp.broadcast_to(pos + jnp.arange(T), (B, T))

    def body(xc, sl):
        lp, cache_l, idx = sl
        with jax.named_scope("qkv"):
            h = C.rms_norm(xc, lp["ln1"], cfg.norm_eps)
            q, k, v = qkv(h, lp, cfg, policy, impl)
            if cfg.use_rope:
                q = C.apply_rope(q, rope_pos, rotary_pct=cfg.rotary_pct,
                                 theta=cfg.rope_theta)
                k = C.apply_rope(k, rope_pos, rotary_pct=cfg.rotary_pct,
                                 theta=cfg.rope_theta)
        with jax.named_scope("kv_append"):
            if paged:
                cache_l = PKV.append_paged(cache_l, k, v, pos, policy.kv,
                                           valid=valid)
            elif per_slot:
                cache_l = KV.append_per_slot(cache_l, k, v, pos, policy.kv,
                                             valid=valid)
            else:
                cache_l = KV.append(cache_l, k, v, pos, policy.kv)
        with jax.named_scope("attn"):
            win = layer_window(cfg, idx)
            attn = C.attend_decode(q, cache_l, policy.kv, pos, window=win,
                                   impl=attn_impl
                                   or ("fused" if impl != "pallas" else impl),
                                   block_s=attn_block_s, max_live=max_live)
        with jax.named_scope("attn_out"):
            xc = xc + C.linear(attn.reshape(B, T, -1), lp["wo"], policy,
                               impl)
        with jax.named_scope("ffn"):
            h2 = C.rms_norm(xc, lp["ln2"], cfg.norm_eps)
            xc = xc + ffn(h2, lp, cfg, policy, impl)
        return xc, cache_l

    with jax.named_scope("layers"):
        x, new_cache = jax.lax.scan(
            body, x, (params["layers"], cache, jnp.arange(cfg.n_layers)))
    with jax.named_scope("lm_head"):
        if valid is None:
            h_sel = x[:, -1]
        else:
            # each slot samples from its last *valid* chunk row (idle
            # slots clamp to row 0 — their logits are discarded by the
            # engine)
            idx = jnp.clip(valid.astype(jnp.int32) - 1, 0, T - 1)
            h_sel = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        h_last = C.rms_norm(h_sel, params["final_norm"], cfg.norm_eps)
        return lm_logits(params, h_last), new_cache
