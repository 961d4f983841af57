"""Operations and bytes that a step's useful work needs, computed from the
request states the step served and the model's sizes.

A step serves ``rows``: for each occupied slot, ``(pos, valid)`` — the
first position it feeds and how many tokens.  Padding rows, idle slots
and the power-of-two live bucket are not counted: these functions give
the same work whatever implements it, so a share of a roofline or a peak
computed from them can only rise when the implementation wastes less.
"""
from __future__ import annotations

import re
from typing import Iterable, Tuple

from bench import weights as W

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


def gemm_flops(m: dict, rows: Rows, emitted: int) -> float:
    """FLOPs of one step's GEMMs: every layer's projections and MLP for
    every valid row, and the LM head for each emitted token."""
    valid = sum(v for _, v in rows)
    dense = 2.0 * m["n_layers"] * gemm_params_per_layer(m) * valid
    return dense + 2.0 * m["d_model"] * m["vocab"] * emitted


def step_flops(m: dict, rows: Rows, emitted: int) -> float:
    """Useful FLOPs of one engine step: the GEMMs of every valid row,
    attention over each valid row's context, and the LM head for each
    emitted token."""
    rows = list(rows)
    return gemm_flops(m, rows, emitted) + attn_flops(m, rows)


def weight_bits(policy: str) -> int:
    """Stored bits per weight of a policy such as ``w4a16kv8``."""
    got = re.match(r"w(\d+)a", policy)
    if got is None:
        raise ValueError(f"no integer weight width in policy {policy!r}")
    return int(got.group(1))


def gemm_bytes(m: dict, rows: Rows, emitted: int, policy: str) -> float:
    """Least HBM bytes of one step's GEMMs: every GEMM leaf of every layer
    once at the policy's stored weight width, with its float32 scales at
    the model's own group (:func:`bench.weights.group_of`) where that
    width is below 16 bits; the qkv biases in bf16 where the model has
    them; the bf16 LM head once; and the bf16 activations of the valid
    rows, each GEMM's input (counted once where GEMMs share it) and
    output, and the head's for each emitted token."""
    bits = weight_bits(policy)
    d, h, hkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    layer = 0.0
    for k, n in W.leaf_shapes(W.Model(**m)).values():
        layer += k * n * bits / 8
        if bits < 16:
            layer += k // W.group_of(k) * n * 4
    if m.get("qkv_bias"):
        layer += (h + 2 * hkv) * hd * 2
    # q/k/v: x in, q, k, v out; wo: attention in, d out; w1/w3: x in,
    # two f out; w2: f in, d out
    act = 4 * d + 2 * h * hd + 2 * hkv * hd + 3 * f
    valid = sum(v for _, v in rows)
    return (m["n_layers"] * (layer + 2.0 * act * valid)
            + 2.0 * d * m["vocab"] + 2.0 * (d + m["vocab"]) * emitted)


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
