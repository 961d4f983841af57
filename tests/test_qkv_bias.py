"""Biased q/k/v projections (``ModelConfig.qkv_bias``, ChatGLM3-6B's
``add_qkv_bias``): drawn only where the model has them and beside every
other leaf unchanged, kept in bf16 by the offline quantizer, added in
``transformer.qkv`` on every path that projects q/k/v, byte-identical
between the dense and paged engines, and sharded like their columns.
The XLA GEMM path's weight unpack lies under a ``dequant`` scope inside
the step's GEMM stages."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced
from repro.core.packing import PackedWeight
from repro.core.precision import get_policy
from repro.models import transformer as TF
from repro.models.registry import build
from repro.serving import Engine, EngineConfig, SamplingParams
from repro.serving.engine import quantize_params

GLM = get_reduced("chatglm3-6b")
PLAIN = dataclasses.replace(GLM, qkv_bias=False)


def test_default_config_draws_no_bias(key):
    cfg = get_reduced("smollm-360m")
    assert not cfg.qkv_bias
    assert not set(TF.QKV_BIAS) & set(TF.init_params(cfg, key)["layers"])


def test_biases_are_drawn_beside_unchanged_leaves(key):
    assert get_config("chatglm3-6b").qkv_bias and GLM.qkv_bias
    biased, plain = TF.init_params(GLM, key), TF.init_params(PLAIN, key)
    assert set(biased["layers"]) == set(plain["layers"]) | set(TF.QKV_BIAS)
    unbiased = dict(biased, layers={k: v for k, v in biased["layers"].items()
                                    if k not in TF.QKV_BIAS})
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(unbiased)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hd, L = GLM.hd, GLM.n_layers
    for name, n in zip(TF.QKV_BIAS, (GLM.n_heads * hd, GLM.n_kv_heads * hd,
                                     GLM.n_kv_heads * hd)):
        leaf = biased["layers"][name]
        assert leaf.shape == (L, n) and leaf.dtype == jnp.bfloat16
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0.25


def test_param_count_counts_the_biases():
    full = get_config("chatglm3-6b")
    plain = dataclasses.replace(full, qkv_bias=False)
    assert full.param_count() - plain.param_count() == 28 * (4096 + 2 * 256)


@pytest.mark.parametrize("policy", ["w4a16kv8", "w8a8kv8"])
def test_quantizer_keeps_the_biases_in_bf16(key, policy):
    q = quantize_params(TF.init_params(GLM, key), get_policy(policy))
    for name in TF.QKV_BIAS:
        leaf = q["layers"][name]
        assert isinstance(leaf, jax.Array) and leaf.dtype == jnp.bfloat16
    assert isinstance(q["layers"]["wq"], PackedWeight)


@pytest.mark.parametrize("policy", [None, "w4a16kv8"])
def test_qkv_adds_each_bias_in_the_output_dtype(key, policy):
    """qkv(biased) - qkv(unbiased) is each bias, broadcast over tokens
    and split into heads, exactly."""
    params = TF.init_params(GLM, key)
    if policy:
        params = quantize_params(params, get_policy(policy))
        policy = get_policy(policy)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(key, (2, 3, GLM.d_model)).astype(jnp.bfloat16)
    got = TF.qkv(h, lp, GLM, policy, "xla")
    base = TF.qkv(h, lp, PLAIN, policy, "xla")
    for g, b, name in zip(got, base, TF.QKV_BIAS):
        assert g.dtype == b.dtype
        want = (b.reshape(2, 3, -1) + lp[name].astype(b.dtype)).reshape(
            b.shape)
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(want, np.float32))


def test_zero_biases_give_the_unbiased_model(key):
    params = TF.init_params(GLM, key)
    zero = dict(params, layers=dict(params["layers"], **{
        k: jnp.zeros_like(params["layers"][k]) for k in TF.QKV_BIAS}))
    toks = jax.random.randint(key, (1, 6), 1, GLM.vocab)
    with_zero = TF.hidden_states(zero, GLM, toks)
    plain = TF.hidden_states(params, PLAIN, toks)
    np.testing.assert_array_equal(np.asarray(with_zero, np.float32),
                                  np.asarray(plain, np.float32))
    biased = TF.hidden_states(params, GLM, toks)
    assert np.abs(np.asarray(biased, np.float32)
                  - np.asarray(plain, np.float32)).max() > 0.1


PROMPTS = [[5, 6, 7], [1], [9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3], [42, 17]]


def test_dense_and_paged_engines_serve_the_biased_model_identically():
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    outs = {}
    for kind in ("dense", "paged"):
        eng = Engine(EngineConfig(model=GLM, policy="w4a16kv8", n_slots=3,
                                  max_seq=32, max_prompt=16, seed=0,
                                  cache_kind=kind, block_size=8,
                                  prefill_chunk=4))
        outs[kind] = [o.output_token_ids for o in eng.generate(PROMPTS, sp)]
    assert outs["dense"] == outs["paged"]
    assert all(len(o) == 6 for o in outs["paged"])


def test_bias_shardings_follow_their_columns(key):
    from repro.launch.sharding import ShardingRules
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = jax.eval_shape(build(GLM).init_params, key)
    specs = ShardingRules(mesh, GLM).params(params)
    assert jax.tree_util.tree_structure(specs) == \
        jax.tree_util.tree_structure(params)

    class FakeMesh:                      # a 16-way model axis
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    full = get_config("chatglm3-6b")
    rules = ShardingRules(FakeMesh(), full, fsdp=False)
    layers = jax.eval_shape(build(dataclasses.replace(full, n_layers=1))
                            .init_params, key)["layers"]
    path = lambda name: (jax.tree_util.DictKey("layers"),  # noqa: E731
                         jax.tree_util.DictKey(name))
    # 32 query heads split whole over 16; 2 KV heads do not
    assert rules.param_spec(path("bq"), layers["bq"]) == P(None, "model")
    assert rules.param_spec(path("bk"), layers["bk"]) == P(None, None)
    assert rules.param_spec(path("bv"), layers["bv"]) == P(None, None)


GEMM_STAGES = ("qkv", "attn_out", "ffn", "lm_head")


@pytest.mark.parametrize("policy", ["w4a16kv8", "w8a8kv8"])
def test_dequant_scope_lies_inside_the_gemm_stages(policy):
    """Every op of the served step whose name stack holds ``dequant``
    holds one of the GEMM stages too, and some op holds it."""
    eng = Engine(EngineConfig(model=GLM, policy=policy, n_slots=2,
                              max_seq=32, block_size=16, prefill_chunk=4,
                              cache_kind="paged", attn_impl="kernel"))
    n = eng.n_slots
    z = lambda dt: jnp.zeros((n,), dt)  # noqa: E731
    hlo = jax.jit(eng._step_fn, static_argnames=("max_live",)).lower(
        eng.params, jnp.zeros((n, 4), jnp.int32), eng.cache, z(jnp.int32),
        z(jnp.int32), z(jnp.uint32), z(jnp.int32), z(jnp.float32),
        z(jnp.int32), max_live=32).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    scoped = [s for s in names if "dequant" in s.split("/")]
    assert scoped
    for s in scoped:
        assert set(s.split("/")) & set(GEMM_STAGES), s
