"""The traffic generator is seeded, keeps its clips and medians, gives
every seed the same work at other times and in another order, and
arrives as a Poisson process does; a mix, a cell or a per-layer metric
added as files is found by name."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import spec as SP
from bench.loadgen import Traffic, lognormal_sizes

MIXES = {p.stem: SP.load_json(p)
         for p in sorted((SP.BENCH / "traffic").glob("*.json"))}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    a, b = (Traffic(MIXES[name], 1000, 2 ** 31 + 5, 51) for _ in range(2))
    assert [(r.prompt, r.max_new, r.due) for r in a.reqs] == \
        [(r.prompt, r.max_new, r.due) for r in b.reqs]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_seeds_permute_one_multiset(name):
    a = Traffic(MIXES[name], 1000, 1, 51)
    b = Traffic(MIXES[name], 1000, 2, 51)
    assert [r.prompt for r in a.reqs] != [r.prompt for r in b.reqs]
    assert [len(r.prompt) for r in a.reqs] != \
        [len(r.prompt) for r in b.reqs]
    assert [r.due for r in a.reqs] != [r.due for r in b.reqs]
    for lo, hi in ((-MIXES[name]["warmup_s"], 0), (0, 51), (51, 60)):
        def sizes(t):
            inside = [r for r in t.reqs if lo <= r.due < hi]
            return (sorted(len(r.prompt) for r in inside),
                    sorted(r.max_new for r in inside))
        assert sizes(a) == sizes(b)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_clips_and_medians(name):
    mix = MIXES[name]
    for key in ("prompt", "output"):
        p = mix[key]
        x = lognormal_sizes(p, 4001, np.random.default_rng(0))
        assert x.min() >= p["min"] and x.max() <= p["max"]
        assert abs(np.median(x) - p["median"]) <= 0.05 * p["median"] + 1


def test_open_loop_rate_and_window():
    mix = MIXES["alpaca_poisson"]
    t = Traffic(mix, 1000, 7, 51)
    dues = np.array([r.due for r in t.reqs])
    assert dues.min() >= -mix["warmup_s"]
    inside = ((dues >= 0) & (dues < 51)).sum()
    assert inside == round(51 * mix["arrivals"]["rate_per_s"])
    assert list(dues) == sorted(dues)


def test_arrivals_are_poisson_given_their_count():
    """Gaps have the exponential's spread, and counts per second vary as
    a Poisson count does: bursts and lulls, not an even beat."""
    mix = dict(MIXES["alpaca_poisson"],
               arrivals={"kind": "poisson", "rate_per_s": 4.0})
    t = Traffic(mix, 1000, 2 ** 31 + 9, 3000)
    dues = np.array([r.due for r in t.reqs if 0 <= r.due < 3000])
    gaps = np.diff(dues)
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05
    counts = np.bincount(dues.astype(int), minlength=3000)
    assert abs(counts.var() / counts.mean() - 1) < 0.1
    assert counts.max() >= 12 and (counts == 0).sum() > 0


def test_unknown_arrival_kind_is_refused():
    mix = dict(MIXES["alpaca_poisson"],
               arrivals={"kind": "gamma", "rate_per_s": 2.0})
    with pytest.raises(ValueError):
        Traffic(mix, 1000, 1, 51)


def test_new_files_are_found_by_name(tmp_path: Path):
    """A cell, a mix and a per-layer metric added as files only."""
    root = tmp_path
    shutil.copytree(SP.BENCH / "configs", root / "bench" / "configs")
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "cells").mkdir()
    (root / "bench" / "layer_metrics").mkdir()
    mix = dict(MIXES["alpaca_poisson"])
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 2.0}
    (root / "bench" / "traffic" / "slow.json").write_text(json.dumps(mix))
    (root / "bench" / "cells" / "smollm360m.slow.json").write_text(
        json.dumps({"max_logit_gap": 1.0}))
    (root / "bench" / "layer_metrics" / "queue_wait_p95_ms.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = SP.benchmark()
    spec["workloads"].append({"name": "smollm360m.slow",
                              "config": "smollm-360m", "traffic": "slow",
                              "chips": 1, "why": "slow arrivals"})
    spec["per_layer"].append({"name": "queue_wait_p95_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "scheduler", "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = SP.load_cell("smollm360m.slow", root)
    assert cell.traffic["arrivals"]["rate_per_s"] == 2.0
    assert "queue_wait_p95_ms" in {m["name"] for m in cell.per_layer}
    got = SP.read_layer_metrics(
        [m for m in cell.per_layer if m["name"] == "queue_wait_p95_ms"],
        None, root)
    assert got == {"queue_wait_p95_ms": {"value": 42.0, "unit": "ms"}}
    t = Traffic(cell.traffic, 1000, 1, 51)
    assert len(t.reqs) == sum(round(2.0 * s) for s in
                              (cell.traffic["warmup_s"], 51, 2))
