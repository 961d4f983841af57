"""Operations and bytes that a step's useful work needs, computed from the
request states the step served and the model's sizes.

A step serves ``rows``: for each occupied slot, ``(pos, valid)`` — the
first position it feeds and how many tokens.  Padding rows, idle slots
and the power-of-two live bucket are not counted: these functions give
the same work whatever implements it, so a share of a roofline or a peak
computed from them can only rise when the implementation wastes less.
"""
from __future__ import annotations

from typing import Iterable, Tuple

Rows = Iterable[Tuple[int, int]]

#: stored bytes per K or V value of each KV format, and per-(token, head)
#: scale bytes (float32)
KV_VALUE_BYTES = {"kv4": 0.5, "kv8": 1.0, "kvfp8": 1.0, "kv16": 2.0}
KV_SCALE_BYTES = 4


def gemm_params_per_layer(m: dict) -> int:
    """Weights a token multiplies by in one layer (attention projections
    and the gated MLP)."""
    d, h, hkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f


def attn_flops(m: dict, rows: Rows) -> float:
    """Causal attention of every valid row over its own context:
    QK^T and PV, 2 FLOPs per multiply-add, every layer and head."""
    per_key = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"]
    total = 0
    for pos, valid in rows:
        # sum over i < valid of (pos + i + 1) keys
        total += valid * (pos + 1) + valid * (valid - 1) // 2
    return float(per_key * total)


def step_flops(m: dict, rows: Rows, emitted: int) -> float:
    """Useful FLOPs of one engine step: the GEMMs of every valid row,
    attention over each valid row's context, and the LM head for each
    emitted token."""
    rows = list(rows)
    valid = sum(v for _, v in rows)
    dense = 2.0 * m["n_layers"] * gemm_params_per_layer(m) * valid
    head = 2.0 * m["d_model"] * m["vocab"] * emitted
    return dense + attn_flops(m, rows) + head


def paged_attn_bytes(m: dict, rows: Rows, kv: str) -> float:
    """Least HBM bytes of the paged attention kernel for one step: each
    slot's live K/V and scales at their stored width up to
    ``pos + valid``, and its q and output rows in bf16, every layer."""
    hkv, h, hd = m["n_kv_heads"], m["n_heads"], m["head_dim"]
    per_token = 2 * hkv * (hd * KV_VALUE_BYTES[kv] + KV_SCALE_BYTES)
    per_row = 2 * h * hd * 2
    total = 0.0
    for pos, valid in rows:
        total += (pos + valid) * per_token + valid * per_row
    return m["n_layers"] * total


def least_time(flops: float, nbytes: float, peak_flops: float,
               bytes_per_s: float) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
