"""Roofline machinery: the trip-count-aware HLO analyzer against modules
with known costs, collective parsing, and MODEL_FLOPS."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.roofline import hlo_cost
from repro.roofline.analysis import (HW, PEAKS, RooflineTerms,
                                     collective_bytes_from_hlo, model_flops,
                                     peaks_for)


class TestHloCost:
    def test_scan_trip_count(self):
        def f(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), None
            return jax.lax.scan(body, x, w)[0]
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)).compile()
        cost = hlo_cost.analyze(c.as_text())
        expect = 8 * 2 * 128 ** 3
        assert expect <= cost.flops <= expect * 1.05

    def test_nested_scans_multiply(self):
        def g(x, ws):
            def outer(c, wi):
                def inner(ci, _):
                    return jnp.tanh(ci @ wi), None
                return jax.lax.scan(inner, c, None, length=4)[0], None
            return jax.lax.scan(outer, x, ws)[0]
        c = jax.jit(g).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32),
            jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)).compile()
        cost = hlo_cost.analyze(c.as_text())
        expect = 32 * 2 * 64 ** 3
        assert expect <= cost.flops <= expect * 1.1

    def test_dot_flops_unrolled(self):
        f = lambda a, b: a @ b
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 32), jnp.float32)).compile()
        cost = hlo_cost.analyze(c.as_text())
        assert cost.flops >= 2 * 64 * 128 * 32

    def test_bytes_positive(self):
        f = lambda a: a * 2.0
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
        cost = hlo_cost.analyze(c.as_text())
        assert cost.bytes >= 2 * 4096      # read + write


class TestCollectiveParse:
    def test_ring_factors(self):
        hlo = """
ENTRY %main (a: f32[256]) -> f32[256] {
  %a = f32[256]{0} parameter(0)
  ROOT %ar = f32[256]{0} all-reduce(%a), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
        out = collective_bytes_from_hlo(hlo)
        # all-reduce wire = 2 * (3/4) * 1024B
        assert abs(out["all-reduce"] - 2 * 0.75 * 1024) < 1e-6

    def test_iota_groups(self):
        hlo = """
ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  ROOT %ag = f32[64]{0} all-gather(%a), replica_groups=[2,8]<=[16], dimensions={0}
}
"""
        out = collective_bytes_from_hlo(hlo)
        assert abs(out["all-gather"] - (7 / 8) * 256) < 1e-6


class TestRooflineTerms:
    def test_terms_and_dominance(self):
        t = RooflineTerms(arch="a", shape="s", mesh="16x16", chips=256,
                          hlo_flops=1e18, hlo_bytes=1e12,
                          collective_bytes_per_device=1e9,
                          collective_counts={}, model_flops=5e17)
        assert t.compute_s == pytest.approx(1e18 / (256 * HW.peak_flops))
        assert t.memory_s == pytest.approx(1e12 / (256 * HW.hbm_bw))
        assert t.collective_s == pytest.approx(1e9 / HW.ici_bw)
        assert t.dominant == "compute"
        assert t.useful_flops_ratio == pytest.approx(0.5)

    def test_peaks_keyed_by_device_kind(self):
        v5e = peaks_for("TPU v5 lite")
        assert v5e is HW and v5e is PEAKS["TPU v5 lite"]
        assert (v5e.peak_flops, v5e.peak_int8_ops, v5e.hbm_bw,
                v5e.hbm_bytes) == (197e12, 393e12, 819e9, 16e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
    def test_unknown_device_kind_raises(self, kind):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks_for(kind)

    def test_model_flops_modes(self):
        cfg = get_config("smollm-360m")
        n = cfg.active_param_count()
        assert model_flops(cfg, 128, 4, "train") == 6.0 * n * 512
        assert model_flops(cfg, 128, 4, "prefill") == 2.0 * n * 512
        assert model_flops(cfg, 128, 4, "decode") == 2.0 * n * 4

    def test_moe_active_less_than_total(self):
        cfg = get_config("arctic-480b")
        assert cfg.active_param_count() < cfg.param_count() / 10
