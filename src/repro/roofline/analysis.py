"""Roofline analysis from the compiled dry-run artifact (no TPU runtime).

Three terms per (arch × shape × mesh), all in seconds:

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

Sources: ``compiled.cost_analysis()`` supplies HLO_FLOPs / HLO_bytes
per device (verified empirically); we scale by chip count to global so
every term divides by chips uniformly.  collective_bytes is parsed from the
post-SPMD optimized HLO (``compiled.as_text()``), whose shapes are
per-device: we sum ring-model wire bytes per device and multiply by chip
count to get the global figure, so the division by chips recovers the
per-device (per-link-serialized) time.

Ring-model wire factors (N = shard group size):
    all-reduce        2·(N−1)/N × full bytes   (reduce-scatter + all-gather)
    all-gather        (N−1)/N × full bytes
    reduce-scatter    (N−1)/N × full bytes
    all-to-all        (N−1)/N × full bytes
    collective-permute 1 × bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Published per-chip peaks of one accelerator."""
    name: str
    peak_flops: float                 # bf16 FLOP/s per chip
    peak_int8_ops: float              # int8 OP/s per chip
    hbm_bw: float                     # bytes/s per chip
    ici_bw: float                     # bytes/s per link
    hbm_bytes: float                  # capacity per chip


#: Peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
#: at 819 GB/s, 1,600 Gbit/s interconnect (4 links of 50 GB/s).
PEAKS: Dict[str, Hardware] = {
    "TPU v5 lite": Hardware(name="tpu-v5e", peak_flops=197e12,
                            peak_int8_ops=393e12, hbm_bw=819e9,
                            ici_bw=50e9, hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Hardware:
    """The peaks of ``device_kind``; a kind not in :data:`PEAKS` is an
    error, never a silent v5e default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None


#: the dry run's target chip, named explicitly
HW = peaks_for("TPU v5 lite")

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

# result-side shapes of a collective instruction, e.g.
#   %ag = bf16[16,256]{1,0} all-gather(...), replica_groups=...
_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_WIRE_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> Optional[int]:
    m = _GROUP_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUP_RE2.search(line)       # iota-style [num_groups, group_size]
    if m:
        return int(m.group(2))
    return None


def collective_bytes_from_hlo(hlo_text: str,
                              default_group: int = 2) -> Dict[str, float]:
    """Per-device ring-model wire bytes, by collective kind.

    Shapes in post-SPMD HLO are per-device.  ``-start`` variants are
    counted, ``-done`` skipped (same transfer).
    """
    out: Dict[str, float] = {k: 0.0 for k in _WIRE_FACTOR}
    out["count"] = 0.0
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue
        shape_str, kind = m.group(1), m.group(2)
        size = _shape_bytes(shape_str)
        n = _group_size(line) or default_group
        frac = (n - 1) / n if n > 1 else 0.0
        factor = _WIRE_FACTOR[kind] * (frac if kind != "collective-permute"
                                       else 1.0)
        out[kind] += size * factor
        out["count"] += 1
    out["total"] = sum(out[k] for k in _WIRE_FACTOR)
    return out


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes_per_device: float
    collective_counts: Dict[str, float]
    model_flops: float
    hw: Hardware = HW

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.hw.peak_flops)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * self.hw.hbm_bw)

    @property
    def collective_s(self) -> float:
        # per-device wire bytes serialized over one link
        return self.collective_bytes_per_device / self.hw.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops / max(self.hlo_flops, 1.0)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes_dev": self.collective_bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "collectives": self.collective_counts,
        }


def model_flops(cfg, seq: int, batch: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N_active·D (train: fwd+bwd) or 2·N_active·D
    (inference fwd), D = tokens processed this step."""
    n = cfg.active_param_count()
    if kind == "train":
        tokens = batch * seq
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = batch * seq
        return 2.0 * n * tokens
    tokens = batch * 1           # decode: one new token per sequence
    return 2.0 * n * tokens


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_desc: str,
                     chips: int, cfg, seq: int, batch: int,
                     kind: str) -> RooflineTerms:
    from . import hlo_cost
    # XLA's cost_analysis() counts while-loop (scan) bodies once and is
    # per-device; the trip-count-aware analyzer in hlo_cost re-derives
    # per-device flops / HBM bytes / collective wire bytes from the
    # optimized HLO text with loop multipliers (see hlo_cost docstring).
    c = hlo_cost.analyze(compiled.as_text())
    return RooflineTerms(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        hlo_flops=c.flops * chips, hlo_bytes=c.bytes * chips,
        collective_bytes_per_device=c.coll_bytes,
        collective_counts=dict(c.coll_counts),
        model_flops=model_flops(cfg, seq, batch, kind),
    )
