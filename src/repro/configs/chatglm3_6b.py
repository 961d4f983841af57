"""ChatGLM3-6B — dense decoder with 2D/partial RoPE (rotation on half the
head dim), aggressive GQA (kv=2) and biased q/k/v projections
(``add_qkv_bias``).  [arXiv:2406.12793]

Published config (THUDM/chatglm3-6b ``config.json``): 28L d_model=4096
32H of 128 (multi-query groups 2) ffn 13696 padded vocab 65024, RMSNorm,
q/k/v bias and no other linear bias, untied head.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="chatglm3-6b",
    family="dense",
    n_layers=28,
    d_model=4096,
    n_heads=32,
    n_kv_heads=2,
    d_ff=13696,
    vocab=65024,
    rotary_pct=0.5,          # "RoPE 2d": rotate the leading half of head_dim
    qkv_bias=True,
    source="https://huggingface.co/THUDM/chatglm3-6b/blob/main/config.json",
)

REDUCED = ModelConfig(
    name="chatglm3-6b-reduced",
    family="dense",
    n_layers=2,
    d_model=256,
    n_heads=8,
    n_kv_heads=2,
    d_ff=512,
    vocab=1024,
    rotary_pct=0.5,
    qkv_bias=True,
    source="reduced variant of the published chatglm3-6b config",
)
