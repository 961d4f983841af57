"""ChatGLM3-6B's block, served by the program at a tiny size (q/k/v
biases, half-width rotary embedding, 16 query heads per KV head), against
the plain reference: the paged prefill and decode give the reference's
logits, the tiny cell is correct end to end under a closed loop, and the
same served program with its biases set to zero is not."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import reference as REF
from bench import spec as SP
from bench import weights as W
from bench.tests import tiny

GLM = {
    "name": "tiny-glm", "policy": "w4a16kv8",
    "model": {"n_layers": 2, "d_model": 128, "n_heads": 32, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab": 512,
              "tie_embeddings": False, "rope_theta": 10000.0,
              "rotary_pct": 0.5, "norm_eps": 1e-5, "qkv_bias": True}}
MODEL = W.Model.from_config(GLM)
CELL = "chatglm3.decode_closed"
SECONDS = 1.5


def glm_cell() -> SP.Cell:
    spec = SP.benchmark()
    return SP.Cell(name=CELL, chips=1, config=GLM,
                   traffic=tiny.CLOSED_TRAFFIC,
                   limits={"max_logit_gap": tiny.LIMIT},
                   end_to_end=SP.metrics_for(spec, "end_to_end", CELL),
                   per_layer=SP.metrics_for(spec, "per_layer", CELL))


def served_logits(params, tokens, chunk):
    """The program's logits at positions ``chunk - 1`` to ``S - 1`` of
    ``tokens`` (B, S): one prompt chunk through the paged pool, then one
    decode step a token, on the paged kernel as the engine runs them."""
    from repro.core.precision import get_policy
    from repro.models.registry import build
    model = build(H.model_config(GLM))
    pol = get_policy(GLM["policy"])
    b, s = tokens.shape
    bs, bps = 16, 2
    cache = model.init_paged_cache(pol, b, b * bps, bs, bps)
    cache = dataclasses.replace(cache, block_table=jnp.arange(
        b * bps, dtype=jnp.int32).reshape(b, bps))
    kw = dict(attn_impl="pallas", attn_block_s=bs, max_live=bps * bs)
    step = jax.jit(lambda p, t, c, pos, valid: model.decode_step(
        p, pol, t, c, pos, valid=valid, **kw))
    full = jnp.full((b,), chunk, jnp.int32)
    out, cache = step(params, jnp.asarray(tokens[:, :chunk]), cache,
                      jnp.zeros((b,), jnp.int32), full)
    outs = [out]
    for p in range(chunk, s):
        out, cache = step(params, jnp.asarray(tokens[:, p:p + 1]), cache,
                          jnp.full((b,), p, jnp.int32),
                          jnp.ones((b,), jnp.int32))
        outs.append(out)
    return np.stack([np.asarray(o, np.float32) for o in outs], 1)


def test_paged_prefill_and_decode_give_the_reference_logits():
    chunk, s = 16, 24
    tokens = np.random.default_rng(3).integers(
        1, MODEL.vocab, (2, s)).astype(np.int32)
    got = served_logits(W.program_params(MODEL, tiny.SEED, GLM["policy"]),
                        tokens, chunk)
    rows = np.tile(np.arange(chunk - 1, s), (2, 1)).astype(np.int32)
    ref = np.asarray(REF.logits_at(MODEL, tiny.SEED, tokens, rows))
    scale = np.abs(ref).max()
    # bf16 activations and an 8-bit KV cache against float32
    assert np.abs(got - ref).max() < 0.05 * scale
    gap = H.logit_gaps(jnp.asarray(ref), got.argmax(-1),
                       np.ones(rows.shape, bool))
    assert gap.max() <= tiny.LIMIT
    plain = dataclasses.replace(MODEL, qkv_bias=False)
    unbiased = np.asarray(REF.logits_at(plain, tiny.SEED, tokens, rows))
    assert np.abs(unbiased - ref).max() > 0.2 * scale


@pytest.fixture
def cpu(monkeypatch):
    monkeypatch.setattr(H, "accelerator", lambda chips: jax.devices())
    return monkeypatch


def test_tiny_glm_cell_is_correct_under_a_closed_loop(cpu):
    result, _ = H.run_cell(glm_cell(), tiny.SEED, SECONDS, False,
                           time.perf_counter())
    assert result["correct"] is True
    assert result["attempted"] > 4 and result["failed"] == 0
    assert result["check"]["served_tokens"]["value"] > 0


def test_served_program_with_zero_biases_is_not_correct(cpu):
    """The same served program and weights, its ``bq``/``bk``/``bv``
    leaves set to zero: the adds are on the served path."""
    made = W.program_params

    def zero_biases(*a):
        params = made(*a)
        layers = dict(params["layers"], **{
            k: jnp.zeros_like(params["layers"][k]) for k in W.BIAS_LEAVES})
        return dict(params, layers=layers)

    cpu.setattr(W, "program_params", zero_biases)
    result, _ = H.run_cell(glm_cell(), tiny.SEED, SECONDS, False,
                           time.perf_counter())
    assert result["correct"] is False
    assert result["check"]["max_logit_gap"]["value"] > 3 * tiny.LIMIT
