"""The served model as the benchmark defines it: seeded weights that stay
as the benchmark first drew them, q/k/v biases drawn beside them without
moving any other leaf, a configuration's ``model`` entry passed whole to
the program, and a plain reference that adds the biases as its
equations say."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import reference as REF
from bench import spec as SP
from bench import weights as W
from bench.tests import tiny

SEED = 2 ** 31 + 77
TINY = W.Model.from_config(tiny.CONFIG)
BIASED = dataclasses.replace(TINY, qkv_bias=True)


def draw(m: W.Model, layer: int, seed: int = SEED) -> dict:
    return jax.jit(W.draw_layer, static_argnums=(1,))(W.root_key(seed), m,
                                                      layer)


def test_smollm_weights_are_the_first_benchmarks():
    """One SmolLM-360M layer, bit for bit as the benchmark first drew it
    (a digest of that code's output)."""
    m = W.Model.from_config(SP.load_json(SP.BENCH / "configs"
                                         / "smollm-360m.json"))
    assert not m.qkv_bias
    d = draw(m, 3)
    h = hashlib.sha256()
    for k in sorted(d):
        for a in jax.tree.leaves(d[k]):
            h.update(k.encode())
            h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == \
        "8cad2a72655f2accd72be0b4e2e530a5aac6a8bd8bfb86652352e1e4d9b6454f"


def test_biases_leave_every_other_leaf_as_it_was():
    plain, biased = draw(TINY, 1), draw(BIASED, 1)
    assert set(biased) == set(plain) | set(W.BIAS_LEAVES)
    for k in plain:
        for a, b in zip(jax.tree.leaves(plain[k]),
                        jax.tree.leaves(biased[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hd = TINY.head_dim
    for k, n in (("bq", TINY.n_heads * hd), ("bk", TINY.n_kv_heads * hd),
                 ("bv", TINY.n_kv_heads * hd)):
        assert biased[k].shape == (n,) and biased[k].dtype == jnp.bfloat16
        assert 0.3 < float(jnp.std(biased[k].astype(jnp.float32))) < 0.7
    assert not np.array_equal(np.asarray(biased["bk"]),
                              np.asarray(biased["bv"]))


def test_program_params_carry_the_biases_stacked_and_unpacked():
    params = W.program_params(BIASED, SEED, tiny.CONFIG["policy"])
    for k, n in W.bias_shapes(BIASED).items():
        got = params["layers"][k]
        assert got.shape == (BIASED.n_layers, n) and got.dtype == jnp.bfloat16
        for layer in range(BIASED.n_layers):
            np.testing.assert_array_equal(np.asarray(got[layer]),
                                          np.asarray(draw(BIASED, layer)[k]))
    assert "bq" not in W.program_params(TINY, SEED,
                                        tiny.CONFIG["policy"])["layers"]


def test_a_model_key_the_program_lacks_fails_at_build_and_is_named():
    config = dict(tiny.CONFIG, model=dict(tiny.CONFIG["model"],
                                          n_widgets=3))
    with pytest.raises(ValueError, match="n_widgets"):
        H.model_config(config)
    with pytest.raises(ValueError, match="n_widgets"):
        W.Model.from_config(config)
    cfg = H.model_config(tiny.CONFIG)
    for k, v in tiny.CONFIG["model"].items():
        assert getattr(cfg, k) == v


def numpy_layer(x, w, m: W.Model):
    """One block in float64 numpy, written from the equations of
    ``bench/reference.py``'s docstring."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    b, s, _ = x.shape
    hd, rep = m.head_dim, m.n_heads // m.n_kv_heads

    def norm(v, g):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + m.norm_eps) \
            * (1 + g)

    def rope(v):
        rot = int(hd * m.rotary_pct) // 2 * 2
        inv = 1.0 / m.rope_theta ** (np.arange(0, rot, 2) / rot)
        ang = np.arange(s)[:, None] * inv[None]
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        out = v.copy()
        a, c = v[..., 0:rot:2], v[..., 1:rot:2]
        out[..., 0:rot:2] = a * cos - c * sin
        out[..., 1:rot:2] = a * sin + c * cos
        return out

    h = norm(x, w["ln1"])
    q = (h @ w["wq"] + w.get("bq", 0)).reshape(b, s, m.n_heads, hd)
    k = (h @ w["wk"] + w.get("bk", 0)).reshape(b, s, m.n_kv_heads, hd)
    v = (h @ w["wv"] + w.get("bv", 0)).reshape(b, s, m.n_kv_heads, hd)
    q, k = rope(q), rope(k)
    k, v = np.repeat(k, rep, 2), np.repeat(v, rep, 2)
    sc = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    attn = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, -1)
    x = x + attn @ w["wo"]
    h2 = norm(x, w["ln2"])
    g = h2 @ w["w1"]
    return x + (g / (1 + np.exp(-g)) * (h2 @ w["w3"])) @ w["w2"]


def layer_input(m: W.Model):
    return np.random.default_rng(0).standard_normal((2, 9, m.d_model))


def test_one_biased_layer_equals_its_numpy_forward():
    w = W.layer_f32(W.root_key(SEED), BIASED, 1)
    x = layer_input(BIASED)
    with jax.default_matmul_precision("highest"):
        got = REF.layer_forward(jnp.asarray(x, jnp.float32), w, BIASED, None)
    want = numpy_layer(x, w, BIASED)
    # float32 against float64 over sums of at most 256 terms
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    unbiased = numpy_layer(x, {k: v for k, v in w.items()
                               if k not in W.BIAS_LEAVES}, TINY)
    assert np.abs(want - unbiased).max() > 0.1


def test_zero_biases_give_the_unbiased_reference():
    w = W.layer_f32(W.root_key(SEED), BIASED, 0)
    zero = dict(w, **{k: jnp.zeros_like(w[k]) for k in W.BIAS_LEAVES})
    plain = {k: v for k, v in w.items() if k not in W.BIAS_LEAVES}
    x = jnp.asarray(layer_input(TINY), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(
            np.asarray(REF.layer_forward(x, zero, BIASED, None)),
            np.asarray(REF.layer_forward(x, plain, TINY, None)))


def test_leaving_the_biases_out_fails_the_tiny_limit_by_far():
    """The tokens a reference without the biases puts first, judged by the
    reference with them: the gap is far over the tiny cell's limit."""
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, TINY.vocab, (4, 24)).astype(np.int32)
    rows = np.tile(np.arange(24), (4, 1)).astype(np.int32)
    ref = REF.logits_at(BIASED, SEED, tokens, rows)
    chosen = jnp.argmax(REF.logits_at(TINY, SEED, tokens, rows), -1)
    gap = float(np.max(H.logit_gaps(ref, chosen, np.ones(rows.shape, bool))))
    assert gap > 10 * tiny.LIMIT
