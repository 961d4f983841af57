"""Quantization primitives: round-trips, packing inverses, error bounds.

Includes property-style tests on the system's core invariants — int4
pack/unpack is a bijection, symmetric quantization error is bounded by
scale/2 per element, and ``quantize_kv``/``dequantize_kv`` round-trip
within format-dependent bounds for *every* KV ``FormatSpec`` — driven by
seeded ``pytest.mark.parametrize`` sweeps (no ``hypothesis`` dependency;
the tier-1 environment is jax + pytest only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as Q
from repro.core.precision import _KV_FORMATS, get_policy


class TestIntQuant:
    def test_roundtrip_error_bound(self, key):
        w = jax.random.normal(key, (256, 64), jnp.float32)
        q, scale = Q.quantize_weight_grouped(w, bits=4, group=128)
        deq = Q.dequantize_weight_grouped(q, scale, group=128,
                                          dtype=jnp.float32)
        # |err| <= scale/2 per group-column (+ eps for clip at qmax)
        bound = np.repeat(np.asarray(scale), 128, axis=0) / 2 + 1e-6
        assert np.all(np.abs(np.asarray(w - deq)) <= bound)

    @pytest.mark.parametrize("bits", [4, 8])
    def test_qrange(self, key, bits):
        w = jax.random.normal(key, (128, 32), jnp.float32) * 100
        q, _ = Q.quantize_weight_grouped(w, bits=bits, group=64)
        qmax = 2 ** (bits - 1) - 1
        assert int(jnp.max(q)) <= qmax and int(jnp.min(q)) >= -qmax

    def test_all_zero_column_safe(self):
        w = jnp.zeros((128, 8), jnp.float32)
        q, scale = Q.quantize_weight_grouped(w, bits=4, group=128)
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.isfinite(np.asarray(scale)))


class TestInt4Packing:
    def test_pack_unpack_inverse(self, key):
        q = jax.random.randint(key, (64, 32), -8, 8, jnp.int8)
        for axis in (0, 1):
            p = Q.pack_int4(q, axis=axis)
            assert p.shape[axis] == q.shape[axis] // 2
            np.testing.assert_array_equal(np.asarray(Q.unpack_int4(p, axis)),
                                          np.asarray(q))

    def test_nibble_order(self):
        # low nibble = even index (matches the offline packer / kernels)
        q = jnp.array([[1], [-2]], jnp.int8)
        p = Q.pack_int4(q, axis=0)
        assert p.shape == (1, 1)
        raw = int(np.asarray(p)[0, 0]) & 0xFF
        assert raw & 0x0F == 1
        assert (raw >> 4) & 0x0F == 0xE      # -2 two's complement nibble


class TestActKV:
    def test_per_token_act(self, key):
        x = jax.random.normal(key, (4, 16, 64), jnp.float32)
        q, scale = Q.quantize_act_per_token(x)
        assert q.shape == x.shape and scale.shape == (4, 16, 1)
        err = np.abs(np.asarray(x) - np.asarray(q, np.float32) *
                     np.asarray(scale))
        assert err.max() <= np.asarray(scale).max() / 2 + 1e-6

    @pytest.mark.parametrize("fmt", ["kv4", "kv8", "kvfp8", "kv16"])
    def test_kv_roundtrip(self, key, fmt):
        spec = get_policy(f"w4a16{fmt}").kv
        kv = jax.random.normal(key, (2, 8, 4, 64), jnp.float32) \
            .astype(jnp.bfloat16)
        q, scale = Q.quantize_kv(kv, spec)
        if spec.packed:
            assert q.shape[-1] == 32
        deq = Q.dequantize_kv(q, scale, spec, jnp.float32)
        rel = np.abs(np.asarray(deq) - np.asarray(kv, np.float32))
        amax = np.abs(np.asarray(kv, np.float32)).max()
        tol = {"kv4": 0.1, "kv8": 0.01, "kvfp8": 0.1, "kv16": 0.005}[fmt]
        assert rel.max() <= tol * max(amax, 1.0)


# ---------------------------------------------------------------------------
# Property-style invariants (seeded sweeps)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,n", [(s, n) for s in range(10)
                                    for n in (2, 6, 32, 64)])
def test_prop_pack_bijection(seed, n):
    """Every even-length int4 vector survives pack → unpack exactly."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.randint(key, (n, 1), -8, 8, jnp.int8)
    p = Q.pack_int4(q, axis=0)
    np.testing.assert_array_equal(np.asarray(Q.unpack_int4(p, 0)),
                                  np.asarray(q))


def test_prop_pack_bijection_exhaustive_pairs():
    """All 256 (lo, hi) nibble pairs round-trip — the full value space."""
    lo, hi = jnp.meshgrid(jnp.arange(-8, 8), jnp.arange(-8, 8))
    q = jnp.stack([lo.ravel(), hi.ravel()], axis=0).astype(jnp.int8)
    p = Q.pack_int4(q, axis=0)
    np.testing.assert_array_equal(np.asarray(Q.unpack_int4(p, 0)),
                                  np.asarray(q))


@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("group", [32, 64, 128])
@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1])
def test_prop_quant_error_bound(seed, bits, group):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (group * 2, 8), jnp.float32) * \
        (10.0 ** jax.random.randint(jax.random.fold_in(key, 1), (), -2, 3))
    q, scale = Q.quantize_weight_grouped(w, bits=bits, group=group)
    deq = Q.dequantize_weight_grouped(q, scale, group=group,
                                      dtype=jnp.float32)
    bound = np.repeat(np.asarray(scale), group, axis=0) / 2 + 1e-6
    assert np.all(np.abs(np.asarray(w - deq)) <= bound)


# ---------------------------------------------------------------------------
# KV round-trip properties over every FormatSpec
# ---------------------------------------------------------------------------

#: seeded random (batch, seq, heads, head_dim) shapes; head_dim stays even
#: so kv4 nibble-packing applies.  Magnitudes sweep 1e-2 .. 1e2 to exercise
#: scale dynamics.
_KV_SHAPES = [(1, 1, 1, 2), (2, 3, 4, 8), (1, 16, 2, 64),
              (3, 5, 1, 128), (2, 2, 8, 32)]


@pytest.mark.parametrize("fmt", sorted(_KV_FORMATS))
@pytest.mark.parametrize("seed,shape",
                         [(i, s) for i, s in enumerate(_KV_SHAPES)])
def test_prop_kv_roundtrip_all_formats(fmt, seed, shape):
    """quantize_kv → dequantize_kv round-trips for every KV FormatSpec:
    scales are strictly positive and finite, quantized storage has the
    spec's dtype and (packed) head_dim, and the reconstruction error obeys
    the format's bound (exact for kv16, scale/2 per element for ints)."""
    spec = get_policy(f"w16a16{fmt}").kv
    key = jax.random.PRNGKey(100 + seed)
    mag = 10.0 ** jax.random.randint(jax.random.fold_in(key, 1), (), -2, 3)
    kv = (jax.random.normal(key, shape, jnp.float32) * mag) \
        .astype(jnp.bfloat16)
    q, scale = Q.quantize_kv(kv, spec)

    assert q.dtype == spec.dtype
    d_expect = shape[-1] // 2 if spec.packed else shape[-1]
    assert q.shape == shape[:-1] + (d_expect,)
    assert scale.shape == shape[:-1] + (1,)
    s = np.asarray(scale)
    assert np.all(np.isfinite(s)) and np.all(s > 0)       # scale positivity

    deq = np.asarray(Q.dequantize_kv(q, scale, spec, jnp.float32))
    ref = np.asarray(kv, np.float32)
    if fmt == "kv16":
        np.testing.assert_array_equal(deq, ref)           # pure bf16 cast
    elif spec.is_float:                                   # kvfp8
        amax = np.abs(ref).max(axis=-1, keepdims=True)
        assert np.all(np.abs(deq - ref) <= 0.15 * amax + 1e-6)
    else:                                                 # kv4 / kv8
        assert np.all(np.abs(deq - ref) <= s / 2 + 1e-6 * np.abs(ref).max())


@pytest.mark.parametrize("fmt", sorted(_KV_FORMATS))
def test_prop_kv_quantize_is_deterministic(fmt):
    """Same input → bit-identical quantized KV (the paged/dense cache
    equivalence in serving relies on this)."""
    spec = get_policy(f"w16a16{fmt}").kv
    kv = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 2, 16),
                           jnp.float32).astype(jnp.bfloat16)
    q1, s1 = Q.quantize_kv(kv, spec)
    q2, s2 = Q.quantize_kv(kv, spec)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_prop_kv4_pack_unpack_inverse_on_quantized():
    """The kv4 path's nibble packing is the exact inverse of unpacking on
    real quantized data (not just synthetic ints)."""
    spec = get_policy("w16a16kv4").kv
    kv = jax.random.normal(jax.random.PRNGKey(11), (2, 8, 2, 32),
                           jnp.float32).astype(jnp.bfloat16)
    q_packed, scale = Q.quantize_kv(kv, spec)
    q_vals = Q.unpack_int4_halves(q_packed)
    assert int(jnp.max(q_vals)) <= 7 and int(jnp.min(q_vals)) >= -7
    repacked = Q.pack_int4_halves(q_vals)
    np.testing.assert_array_equal(np.asarray(repacked),
                                  np.asarray(q_packed))


@pytest.mark.parametrize("D", [2, 8, 64, 128])
def test_kv4_half_split_order_roundtrip(D):
    """Packed int4 KV is half-split: byte j holds head_dim index j in its
    low nibble and j + D/2 in its high nibble (the attention kernels
    unpack with a lane concat); every value in [-8, 7] round-trips."""
    q = jax.random.randint(jax.random.PRNGKey(D), (3, 2, D), -8, 8,
                           jnp.int32).astype(jnp.int8)
    p = np.asarray(Q.pack_int4_halves(q)).astype(np.int32)
    assert p.shape == (3, 2, D // 2)
    lo = ((p & 0xF) ^ 8) - 8                 # sign-extend the nibbles
    hi = (((p >> 4) & 0xF) ^ 8) - 8
    qn = np.asarray(q, np.int32)
    np.testing.assert_array_equal(lo, qn[..., :D // 2])
    np.testing.assert_array_equal(hi, qn[..., D // 2:])
    np.testing.assert_array_equal(
        np.asarray(Q.unpack_int4_halves(Q.pack_int4_halves(q))), qn)
    # weights keep the interleaved order
    w = np.asarray(Q.pack_int4(q, axis=2)).astype(np.int32)
    np.testing.assert_array_equal(((w & 0xF) ^ 8) - 8, qn[..., 0::2])
