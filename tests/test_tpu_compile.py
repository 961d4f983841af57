"""The served path's Pallas kernels compile for a TPU v5e at SmolLM-360M's
published widths (15 heads over 5 KV heads, head_dim 64, d_model 960,
d_ff 2560).

Nothing runs: each case compiles the kernel for one chip of a *described*
``v5e:2x2`` topology, which catches what interpret mode cannot — block
shapes off the (8, 128) tiling, vector layouts and ops Mosaic refuses,
too much VMEM.  The topology is described inside a module fixture, so
only the worker that runs this file loads the TPU compiler, and the file
skips where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import kvattn as KA
from repro.kernels import mpgemm as MG
from repro.kernels import paged_kvattn as PKA
from repro.models.common import pick_blocks

HKV, REP, D = 5, 3, 64                  # SmolLM-360M: 15 heads / 5 KV heads
D_MODEL, D_FF = 960, 2560
B, BS, CTX, CHUNK = 8, 16, 1024, 32     # engine: slots, block, max_seq, chunk
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


@pytest.mark.parametrize("T", [1, CHUNK])
@pytest.mark.parametrize("fmt", sorted(FMTS))
def test_paged_attention_compiles(one_chip, fmt, T):
    dt, packed, is_float = FMTS[fmt]
    Ds = D // 2 if packed else D
    n_blocks = B * CTX // BS
    _compile(
        lambda *a: PKA.paged_kvattn_decode_grouped(
            *a, packed=packed, kv_is_float=is_float, rep=REP),
        one_chip,
        ((B, HKV, T * REP, D), jnp.bfloat16),
        ((n_blocks, BS, HKV, Ds), dt), ((n_blocks, BS, HKV, 1), jnp.float32),
        ((n_blocks, BS, HKV, Ds), dt), ((n_blocks, BS, HKV, 1), jnp.float32),
        ((B, CTX // BS), jnp.int32), ((B,), jnp.int32), ((1,), jnp.int32))


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
