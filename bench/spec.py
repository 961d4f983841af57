"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

A later cell, configuration, traffic mix or per-layer metric is added as
files and entries only: nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

#: the checkout root: this file is ``<root>/bench/spec.py``
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    limits: dict          # bench/cells/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def metrics_for(spec: dict, kind: str, cell: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload name to its configuration, traffic and limits
    (KeyError for a name ``BENCHMARK.json`` does not hold)."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / configs[w["config"]]["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench / "cells" / f"{name}.json"),
        end_to_end=metrics_for(spec, "end_to_end", name),
        per_layer=metrics_for(spec, "per_layer", name))


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read(ctx)`` function of ``bench/layer_metrics/<metric>.py``."""
    path = root / "bench" / "layer_metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + re.sub(r"\W", "_", metric), path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(metrics: List[dict], ctx,
                       root: Path = ROOT) -> Dict[str, dict]:
    """Run each metric's reader; a reader that finds nothing to read
    returns None and its metric is left out."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
