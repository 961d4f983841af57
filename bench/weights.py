"""Seeded weights of a configuration, made on the device.

The model a cell serves is defined here, from ``--seed``: every GEMM
weight is int4 codes times a float32 scale per (group of ``group`` rows,
column), the form a w4 checkpoint has; embeddings, the LM head and the
norm gains are bfloat16.  Two consumers draw the same numbers from the
seed, each by itself:

* :func:`program_params` packs the codes into the serving program's own
  weight format (with the program's ``pack_prequantized``), in one jitted
  call with a scan over layers, so a 6B model never holds more than one
  layer unpacked;
* :mod:`bench.reference` calls :func:`layer_f32` and friends one layer at
  a time and never sees an array the program made.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: std of the normal draw that is rounded to int4 codes in [-7, 7]
CODE_STD = 2.0
QMAX = 7
EMBED_STD = 0.02
NORM_STD = 0.1
GEMM_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a configuration's ``model`` entry (hashable, so it
    can be a static argument of a jitted function)."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie_embeddings: bool
    rope_theta: float
    rotary_pct: float
    norm_eps: float

    @classmethod
    def from_config(cls, config: dict) -> "Model":
        m = config["model"]
        return cls(**{f.name: m[f.name] for f in dataclasses.fields(cls)})


def leaf_shapes(m: Model) -> Dict[str, Tuple[int, int]]:
    d, hd = m.d_model, m.head_dim
    return {"wq": (d, m.n_heads * hd), "wk": (d, m.n_kv_heads * hd),
            "wv": (d, m.n_kv_heads * hd), "wo": (m.n_heads * hd, d),
            "w1": (d, m.d_ff), "w3": (d, m.d_ff), "w2": (m.d_ff, d)}


def group_of(k: int) -> int:
    """Rows sharing one scale: 128, or the largest of 64 and 32 that
    divides ``k`` when 128 does not."""
    for g in (128, 64, 32):
        if k % g == 0:
            return g
    raise ValueError(f"no weight group divides K={k}")


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed."""
    word = np.random.SeedSequence(seed).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def _bf16_normal(key, shape, std) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def _layer_key(key, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 3), layer)


def draw_leaf(key, k: int, n: int):
    """(codes int8 (k, n), scales float32 (k // group, n))."""
    kc, ks = jax.random.split(key)
    codes = jnp.clip(jnp.round(jax.random.normal(kc, (k, n)) * CODE_STD),
                     -QMAX, QMAX).astype(jnp.int8)
    scales = jax.random.uniform(ks, (k // group_of(k), n), jnp.float32,
                                0.75, 1.25) / (math.sqrt(k) * CODE_STD)
    return codes, scales


def draw_layer(key, m: Model, layer) -> dict:
    """Codes and scales of every GEMM leaf of one layer, and its norms."""
    lk = _layer_key(key, layer)
    out = {name: draw_leaf(jax.random.fold_in(lk, i), *shape)
           for i, (name, shape) in enumerate(leaf_shapes(m).items())}
    out["ln1"] = _bf16_normal(jax.random.fold_in(lk, 100), (m.d_model,),
                              NORM_STD)
    out["ln2"] = _bf16_normal(jax.random.fold_in(lk, 101), (m.d_model,),
                              NORM_STD)
    return out


def dequant(codes, scales) -> jax.Array:
    k, n = codes.shape
    g = k // scales.shape[0]
    return (codes.reshape(k // g, g, n).astype(jnp.float32)
            * scales[:, None, :]).reshape(k, n)


def embed_bf16(key, m: Model) -> jax.Array:
    return _bf16_normal(jax.random.fold_in(key, 0), (m.vocab, m.d_model),
                        EMBED_STD)


def head_bf16(key, m: Model) -> jax.Array:
    """(d_model, vocab); the tied embedding's transpose when tied."""
    if m.tie_embeddings:
        return embed_bf16(key, m).T
    return _bf16_normal(jax.random.fold_in(key, 1), (m.d_model, m.vocab),
                        EMBED_STD)


def final_norm_bf16(key, m: Model) -> jax.Array:
    return _bf16_normal(jax.random.fold_in(key, 2), (m.d_model,), NORM_STD)


@functools.partial(jax.jit, static_argnums=(1,))
def layer_f32(key, m: Model, layer) -> dict:
    """One layer's weights in float32, as the reference uses them."""
    d = draw_layer(key, m, layer)
    out = {name: dequant(*d[name]) for name in GEMM_LEAVES}
    out["ln1"] = d["ln1"].astype(jnp.float32)
    out["ln2"] = d["ln2"].astype(jnp.float32)
    return out


def program_params(m: Model, seed: int, policy: str):
    """The serving program's parameter tree for this model and seed: GEMM
    leaves packed in the format the program's own quantizer would choose
    for the policy (its tiles, and its scale groups where they are finer
    than the model's), the rest bfloat16."""
    return _program_maker(m, policy)(root_key(seed))


@functools.lru_cache(maxsize=None)
def _program_maker(m: Model, policy: str):
    """The jitted maker of :func:`program_params` for one model and
    policy (one compile per process)."""
    from repro.core.packing import PackedWeight, pack_prequantized
    from repro.core.precision import get_policy
    from repro.models.common import maybe_quantize

    pol = get_policy(policy)
    fmt = {}
    for name, (k, n) in leaf_shapes(m).items():
        pw = jax.eval_shape(lambda w: maybe_quantize(w, pol),
                            jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
        if isinstance(pw, PackedWeight):
            if group_of(k) % pw.group:
                raise ValueError(f"{name}: the program groups {pw.group} "
                                 f"rows per scale, which do not divide the "
                                 f"model's {group_of(k)}")
            fmt[name] = (pw.bits, pw.group, pw.block_k, pw.block_n)
        else:
            fmt[name] = None

    @jax.jit
    def make(key):
        def layer(_, i):
            d = draw_layer(key, m, i)
            out = {"ln1": d["ln1"], "ln2": d["ln2"]}
            for name in GEMM_LEAVES:
                codes, scales = d[name]
                if fmt[name] is None:
                    out[name] = dequant(codes, scales).astype(jnp.bfloat16)
                else:
                    # a scale shared by g rows is the same scale on each
                    # of its g / group sub-groups: the model is unchanged
                    bits, group, bk, bn = fmt[name]
                    reps = group_of(codes.shape[0]) // group
                    out[name] = pack_prequantized(
                        codes, jnp.repeat(scales, reps, axis=0), bits=bits,
                        group=group, block_k=bk, block_n=bn)
            return None, out

        _, layers = jax.lax.scan(layer, None, jnp.arange(m.n_layers))
        params = {"embed": embed_bf16(key, m), "layers": layers,
                  "final_norm": final_norm_bf16(key, m)}
        if not m.tie_embeddings:
            params["lm_head"] = head_bf16(key, m)
        return params

    return make
