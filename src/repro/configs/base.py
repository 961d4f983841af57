"""ModelConfig — the single config dataclass every architecture file fills in.

One file per assigned architecture lives next to this module; each exports
``CONFIG`` (the exact assigned spec) and ``REDUCED`` (a ≤2-layer,
d_model ≤ 512, ≤4-expert member of the same family for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int                 # 0 for attention-free (rwkv)
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None      # default: d_model // n_heads

    # attention variants ------------------------------------------------
    rope_theta: float = 10_000.0
    rotary_pct: float = 1.0             # chatglm3 "RoPE 2d": 0.5
    window: Optional[int] = None        # sliding-window size (local layers)
    local_global_period: int = 0        # gemma3: 6 → every 6th layer global
    use_rope: bool = True               # whisper: sinusoidal instead
    qkv_bias: bool = False              # chatglm3: biased q/k/v projections

    # MoE ----------------------------------------------------------------
    n_experts: int = 0
    topk: int = 0
    moe_dense_residual: bool = False    # arctic: parallel dense FFN
    shared_expert: bool = False         # llama4-scout
    capacity_factor: float = 1.25

    # SSM / hybrid ---------------------------------------------------------
    rwkv_head_dim: int = 64
    rglru_period: int = 0               # recurrentgemma: (rg, rg, attn) → 3
    conv_width: int = 4
    lru_width: Optional[int] = None

    # encoder-decoder (whisper) -------------------------------------------
    enc_layers: int = 0
    enc_seq: int = 0                    # stub conv-frontend frames (1500)
    max_dec_pos: int = 448              # learned decoder position table size

    # VLM -------------------------------------------------------------------
    n_img_tokens: int = 0               # stub ViT patch embeddings

    # misc --------------------------------------------------------------
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    big_model: bool = False             # FSDP sharding + adafactor
    sub_quadratic: bool = False         # eligible for long_500k
    source: str = ""                    # citation for the assigned config

    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    def _attn_params(self) -> int:
        """q/k/v/o projections of one layer, with the q/k/v biases."""
        d, hd, H, Hkv = self.d_model, self.hd, self.n_heads, self.n_kv_heads
        bias = (H + 2 * Hkv) * hd if self.qkv_bias else 0
        return d * H * hd + 2 * d * Hkv * hd + H * hd * d + bias

    def param_count(self) -> int:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        hd, H, Hkv = self.hd, self.n_heads, self.n_kv_heads
        if self.family == "ssm":
            # rwkv: time-mix (r,k,v,g,o ≈ 5 d²) + channel-mix (d·f·2? rwkv
            # uses k: d→f, v: f→d, r: d→d)
            per = 5 * d * d + 2 * d * f + d * d
        else:
            attn = self._attn_params()
            mlp = 3 * d * f
            if self.n_experts:
                moe = self.n_experts * 3 * d * f + d * self.n_experts
                if self.moe_dense_residual or self.shared_expert:
                    moe += 3 * d * f
                mlp = moe
            per = attn + mlp
            if self.rglru_period:
                w = self.lru_width or d
                rec = d * w * 2 + w * d + w * self.conv_width + 2 * w * w
                att_layers = L // self.rglru_period
                per = mlp + rec  # mixed; refined below
                return int(att_layers * (attn + 3 * d * f)
                           + (L - att_layers) * (rec + 3 * d * f) + 2 * V * d)
        total = L * per + V * d * (1 if self.tie_embeddings else 2)
        if self.enc_layers:
            total += self.enc_layers * (4 * d * d + 2 * d * f)  # encoder
            total += L * (d * d + 2 * d * Hkv * hd)             # cross-attn
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts) — for
        MODEL_FLOPS = 6·N_active·D."""
        if not self.n_experts:
            return self.param_count()
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        attn = self._attn_params()
        act_mlp = self.topk * 3 * d * f + d * self.n_experts
        if self.moe_dense_residual or self.shared_expert:
            act_mlp += 3 * d * f
        return int(L * (attn + act_mlp) + 2 * V * d)
