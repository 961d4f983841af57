"""The served path's Pallas kernels compile for a TPU v5e at SmolLM-360M's
published widths (15 heads over 5 KV heads, head_dim 64, d_model 960,
d_ff 2560).

Nothing runs: each case compiles the kernel for one chip of a *described*
``v5e:2x2`` topology, which catches what interpret mode cannot — block
shapes off the (8, 128) tiling, vector layouts and ops Mosaic refuses,
too much VMEM.  The engine's whole paged step is compiled too, to check
that it leaves the KV pool where it is: no relayout or copy of it, and
the pool aliased from the step's input to its output, at SmolLM-360M's
widths and at ChatGLM3-6B's (d_model 4096, 32 heads over 2 KV heads of
128, d_ff 13696, q/k/v biases).  The topology is described inside a
module fixture, so only the worker that runs this file loads the TPU
compiler, and the file skips where no topology can be described.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.paged_kvcache import lanes
from repro.kernels import kvattn as KA
from repro.kernels import mpgemm as MG
from repro.kernels import paged_kvattn as PKA
from repro.kernels import pool_write as PW
from repro.models.common import pick_blocks

HKV, REP, D = 5, 3, 64                  # SmolLM-360M: 15 heads / 5 KV heads
GLM_HKV, GLM_REP, GLM_D = 2, 16, 128    # ChatGLM3-6B: 32 heads / 2 KV heads
D_MODEL, D_FF = 960, 2560
B, BS, CTX, CHUNK = 8, 16, 1024, 32     # engine: slots, block, max_seq, chunk
L = 2                                   # layers of a stacked pool
#: KV store dtype, int4-packed, float per format
FMTS = {"kv16": (jnp.bfloat16, False, True),
        "kv8": (jnp.int8, False, False),
        "kv4": (jnp.int8, True, False),
        "kvfp8": (jnp.float8_e4m3fn, False, True)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding onto one described chip; the persistent compilation cache
    is off meanwhile (an entry compiled for a described chip cannot be
    read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _compile_paged(sharding, fmt, T, hkv, rep, d):
    """The paged kernel over a stacked pool, ``(L, n_blocks, BS, KVL)``
    K/V and ``(L, n_blocks, 1, SL)`` scales, read at a layer given at
    run time."""
    dt, packed, is_float = FMTS[fmt]
    Ds = d // 2 if packed else d
    n_blocks = B * CTX // BS
    kv = ((L, n_blocks, BS, lanes(hkv * Ds)), dt)
    sc = ((L, n_blocks, 1, lanes(hkv * BS)), jnp.float32)
    _compile(
        lambda *a: PKA.paged_kvattn_decode_grouped(
            *a, packed=packed, kv_is_float=is_float, rep=rep),
        sharding,
        ((B, hkv, T * rep, d), jnp.bfloat16), kv, sc, kv, sc,
        ((B, CTX // BS), jnp.int32), ((B,), jnp.int32), ((1,), jnp.int32),
        ((), jnp.int32))


@pytest.mark.parametrize("T", [1, CHUNK])
@pytest.mark.parametrize("fmt", sorted(FMTS))
def test_paged_attention_compiles(one_chip, fmt, T):
    _compile_paged(one_chip, fmt, T, HKV, REP, D)


@pytest.mark.parametrize("T", [1, CHUNK])
@pytest.mark.parametrize("fmt", sorted(FMTS))
def test_paged_attention_compiles_at_chatglm3_heads(one_chip, fmt, T):
    """Two KV heads of 128: the pool's K/V rows are exactly two lane
    tiles wide."""
    _compile_paged(one_chip, fmt, T, GLM_HKV, GLM_REP, GLM_D)


@pytest.mark.parametrize("heads", [(HKV, D), (GLM_HKV, GLM_D)],
                         ids=["smollm", "chatglm3"])
@pytest.mark.parametrize("fmt", sorted(FMTS))
def test_pool_writer_compiles(one_chip, fmt, heads):
    """The in-place writer at the engine's chunk width: 3 block steps
    per slot, every step aliased onto the pool."""
    hkv, d = heads
    dt, packed, _ = FMTS[fmt]
    n_blocks, G = B * CTX // BS, B * 3
    C, S = lanes(hkv * (d // 2 if packed else d)), lanes(hkv * BS)
    compiled = _compile(
        lambda k, ks, v, vs, kn, ksn, vn, vsn, lay, blk, lo, hi:
        PW.kv_pool_write((k, ks, v, vs), (kn, ksn, vn, vsn), lay, blk, lo,
                         hi, kv_lanes=hkv * (d // 2 if packed else d),
                         heads=hkv),
        one_chip,
        ((L, n_blocks, BS, C), dt), ((L, n_blocks, 1, S), jnp.float32),
        ((L, n_blocks, BS, C), dt), ((L, n_blocks, 1, S), jnp.float32),
        ((G, BS, C), dt), ((G, 1, S), jnp.float32),
        ((G, BS, C), dt), ((G, 1, S), jnp.float32),
        ((), jnp.int32), ((G,), jnp.int32), ((G,), jnp.int32),
        ((G,), jnp.int32))
    assert "output_to_operand_aliasing" in compiled.as_text()


@pytest.mark.parametrize("T", [1, CHUNK])
@pytest.mark.parametrize("fmt", sorted(FMTS))
def test_dense_attention_compiles(one_chip, fmt, T):
    dt, packed, is_float = FMTS[fmt]
    Ds = D // 2 if packed else D
    _compile(
        lambda *a: KA.kvattn_decode_grouped(
            *a, packed=packed, kv_is_float=is_float, block_s=BS, rep=REP),
        one_chip,
        ((B, HKV, T * REP, D), jnp.bfloat16),
        ((B, CTX, HKV, Ds), dt), ((B, CTX, HKV, 1), jnp.float32),
        ((B, CTX, HKV, Ds), dt), ((B, CTX, HKV, 1), jnp.float32),
        ((B,), jnp.int32), ((1,), jnp.int32))


@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_mpgemm_compiles_at_packed_widths(one_chip, K, N):
    """W4A16 GEMM at a decode M, with the blocks the packer picks."""
    bk, bn = pick_blocks(K, N)
    _compile(
        lambda x, w, s: MG.mpgemm_2d(x, w, s, bits=4, group=bk),
        one_chip,
        ((B, K), jnp.bfloat16), ((K // bk, N // bn, bk // 2, bn), jnp.int8),
        ((K // bk, N), jnp.float32))


# ---------------------------------------------------------------------------
# The engine's whole paged step leaves the pool where it is
# ---------------------------------------------------------------------------

#: HLO element type names of the pool's dtypes
_HLO_DTYPE = {"int8": "s8", "float32": "f32", "bfloat16": "bf16",
              "float8_e4m3fn": "f8e4m3fn"}
_BYTES = {"s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "bf16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4}


@pytest.fixture(scope="module")
def smollm_engines():
    """The cell's paged engine (32 slots x 1024 tokens, block 16, chunk
    32) at SmolLM-360M widths with two layers, per KV format."""
    from repro.configs import get_config
    from repro.serving import Engine, EngineConfig
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=L)
    engines = {}

    def get(fmt):
        if fmt not in engines:
            engines[fmt] = Engine(EngineConfig(
                model=cfg, policy=f"w4a16{fmt}", cache_kind="paged",
                attn_impl="kernel", n_slots=32, max_seq=CTX, block_size=BS,
                prefill_chunk=CHUNK))
        return engines[fmt]
    return get


_ARRAY = r"(\w+)\[([\d,]*)\]\{([^}]*)\}"


def _relayouts(hlo: str):
    """(element type, dims, bytes) of the result of every transpose, and
    of every copy or async copy whose layout differs from its operand's:
    the copies that move data into another layout.  A copy that only
    moves an array between memory spaces (``S(n)``, the compiler's
    prefetches into on-chip memory) keeps its layout and is left out."""
    layouts, moves = {}, []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (.*?) ([\w-]+)\((.*)", line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        arrays = re.findall(_ARRAY, result)
        if len(arrays) == 1:
            layouts[name] = arrays[0]
        if op in ("copy", "copy-start", "transpose"):
            src = re.match(r"(%[^,)\s]+)", rest)
            moves.append((op, arrays[1 if op == "copy-start" else 0],
                          layouts.get(src.group(1)) if src else None))
    plain = lambda layout: re.sub(r"S\(\d+\)", "", layout)  # noqa: E731
    out = []
    for op, (dt, dims, layout), src in moves:
        if op != "transpose" and src is not None and \
                (src[1], plain(src[2])) == (dims, plain(layout)):
            continue
        dims = tuple(int(x) for x in dims.split(",") if x)
        out.append((dt, dims, int(np.prod(dims)) * _BYTES.get(dt, 4)))
    return out


@pytest.mark.parametrize("T", [1, CHUNK])
@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_paged_step_moves_no_pool(one_chip, smollm_engines, monkeypatch,
                                  fmt, T):
    """The engine's paged step, compiled for the chip in the layout the
    compiler picks for its arguments, neither relayouts nor copies the
    KV pool: no copy or transpose into another layout spans the pool's
    blocks with as many bytes as one layer's K pool, and every pool leaf
    is aliased from the step's input to its output (the step donates it
    and the kernels read and write it in place).  Weight relayouts are
    not the pool's: they are told apart by the block axis."""
    _step_moves_no_pool(one_chip, smollm_engines(fmt), monkeypatch, T, CTX)


GLM_CTX = 2048                          # the ChatGLM3-6B cell's max_seq


@pytest.fixture(scope="module")
def glm_engine():
    """The ChatGLM3-6B cell's paged engine (32 slots x 2048 tokens, block
    16, chunk 32, kv8) with two layers at its published widths, q/k/v
    biases included.  Its weights are shapes only: nothing is run."""
    from repro.configs import get_config
    from repro.core.precision import get_policy
    from repro.models.transformer import init_params
    from repro.serving import Engine, EngineConfig
    from repro.serving.engine import quantize_params
    cfg = dataclasses.replace(get_config("chatglm3-6b"), n_layers=L)
    assert cfg.qkv_bias and (cfg.hd, cfg.n_kv_heads) == (GLM_D, GLM_HKV)
    policy = "w4a16kv8"
    params = jax.eval_shape(lambda k: quantize_params(
        init_params(cfg, k), get_policy(policy)), jax.random.PRNGKey(0))
    return Engine(EngineConfig(
        model=cfg, policy=policy, cache_kind="paged", attn_impl="kernel",
        n_slots=32, max_seq=GLM_CTX, block_size=BS, prefill_chunk=CHUNK),
        params=params)


@pytest.mark.parametrize("T", [1, CHUNK])
def test_paged_step_moves_no_pool_at_chatglm3_widths(one_chip, glm_engine,
                                                     monkeypatch, T):
    """The same at ChatGLM3-6B's widths: 16 query heads per KV head of
    128, the biased q/k/v projections, the top live bucket."""
    _step_moves_no_pool(one_chip, glm_engine, monkeypatch, T, GLM_CTX)


def _step_moves_no_pool(one_chip, eng, monkeypatch, T, ctx):
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "INTERPRET", False)
    cache, n_slots = eng.cache, eng.n_slots
    sds = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        np.shape(a), a.dtype, sharding=one_chip)
    z = lambda dt: jax.ShapeDtypeStruct((n_slots,), dt,  # noqa: E731
                                        sharding=one_chip)
    hlo = eng._step.lower(
        jax.tree.map(sds, eng.params),
        jax.ShapeDtypeStruct((n_slots, T), jnp.int32, sharding=one_chip),
        jax.tree.map(sds, cache), z(jnp.int32), z(jnp.int32),
        z(jnp.uint32), z(jnp.int32), z(jnp.float32), z(jnp.int32),
        max_live=ctx).compile().as_text()
    assert "tpu_custom_call" in hlo

    layer_k = cache.k[0].size * cache.k.dtype.itemsize
    pool_dt = {_HLO_DTYPE[a.dtype.name]
               for a in (cache.k, cache.v, cache.k_scale, cache.v_scale)}
    # Where the block count equals d_model (ChatGLM3-6B's widths), the
    # weights' relayouts span it as well; there a relayout is the pool's
    # if it keeps the block size as an axis or a pool element type.
    weights_span = cache.n_blocks == eng.model_cfg.d_model
    moved = [(dt, dims, n) for dt, dims, n in _relayouts(hlo)
             if cache.n_blocks in dims and n >= layer_k
             and (not weights_span or cache.block_size in dims
                  or dt in pool_dt)]
    assert not moved, moved

    header = hlo.split("\n", 1)[0]
    aliased = {int(i) for i in
               re.findall(r"\((\d+), \{\}, may-alias\)", header)}
    params = header.split("entry_computation_layout={(", 1)[1]
    params = re.findall(r"(\w+\[[\d,]*\])\{", params.split(")->", 1)[0])
    leaves = {f"{_HLO_DTYPE[a.dtype.name]}[{','.join(map(str, a.shape))}]"
              for a in (cache.k, cache.v, cache.k_scale, cache.v_scale)}
    pool_params = [i for i, p in enumerate(params) if p in leaves]
    assert len(pool_params) == 4 and set(pool_params) <= aliased, \
        (pool_params, aliased)
