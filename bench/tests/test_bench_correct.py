"""The comparison that decides ``correct``: a sound run of a tiny cell is
correct; its control (the reference with a 4-bit KV cache in the
program's place) and the faults the served path can have are not.

Each run skips the harness's look for a chip and drives the rest of a
run on the CPU (Pallas interpreted): the engine's window at its own
batch, the check of finished requests against the plain reference, and
the result line."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness as H
from bench.tests import tiny

SECONDS = 1.5


def on_cpu(chips):
    return jax.devices()


def run():
    return H.run_cell(tiny.cell(), tiny.SEED, SECONDS, False,
                      time.perf_counter())


@pytest.fixture(scope="module")
def sound():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "accelerator", on_cpu)
        return run()


@pytest.fixture
def cpu(monkeypatch):
    monkeypatch.setattr(H, "accelerator", on_cpu)
    return monkeypatch


def test_sound_run_is_correct_and_reports(sound):
    result, lines = sound
    assert result["correct"] is True
    assert result["check"]["served_tokens"]["value"] > 0
    assert result["check"]["max_logit_gap"]["value"] <= tiny.LIMIT
    assert list(result)[-1] == "check"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {m["name"]
                                      for m in tiny.cell().end_to_end}
    assert lines[0].startswith("check max_logit_gap")


def test_closed_loop_run_is_correct(cpu):
    """The tiny cell served end to end under a closed loop of four
    clients: every client's next request is sent as its last finishes."""
    result, _ = H.run_cell(tiny.cell(traffic=tiny.CLOSED_TRAFFIC), tiny.SEED,
                           SECONDS, False, time.perf_counter())
    assert result["correct"] is True
    assert result["attempted"] > 4 and result["failed"] == 0
    assert result["check"]["served_tokens"]["value"] > 0


def test_control_is_not_correct(cpu):
    """The control's tokens, judged by the run's own ``check``."""
    check = H.check
    cpu.setattr(H, "check", lambda *a: check(*a, control_kv_bits=4))
    result, _ = run()
    assert result["correct"] is False
    assert result["check"]["max_logit_gap"]["value"] > tiny.LIMIT


def test_token_altered_where_produced_is_not_correct(cpu):
    from repro.serving import sampler
    orig = sampler.sample

    def altered(keys, logits, temp, top_k):
        return (orig(keys, logits, temp, top_k) + 1) % logits.shape[-1]

    cpu.setattr(sampler, "sample", altered)
    result, _ = run()
    assert result["correct"] is False


def test_step_that_returns_its_state_unchanged_is_not_correct(cpu):
    from repro.core import paged_kvcache
    cpu.setattr(paged_kvcache, "append_paged",
                lambda cache, k, v, pos, spec, valid=None: cache)
    result, _ = run()
    assert result["correct"] is False


def test_half_of_the_batch_left_out_is_not_correct(cpu):
    """The lower half of the slots (where admission starts) is left out of
    the step: each of them samples from a row of the upper half."""
    from repro.serving import sampler
    orig = sampler.sample

    def half(keys, logits, temp, top_k):
        b = logits.shape[0] // 2
        return orig(keys, jnp.concatenate([logits[b:], logits[b:]]), temp,
                    top_k)

    cpu.setattr(sampler, "sample", half)
    result, _ = run()
    assert result["correct"] is False
