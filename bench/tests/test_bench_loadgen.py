"""The traffic generator is seeded, keeps its clips and medians, gives
every seed the same work at other times and in another order, arrives as
a Poisson process does in an open loop and as its clients free in a
closed one; a configuration, a mix, a cell or a per-layer metric added
as files is found by name."""
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import spec as SP
from bench import weights as W
from bench.loadgen import BLOCK, Traffic, lognormal_sizes

MIXES = {p.stem: SP.load_json(p)
         for p in sorted((SP.BENCH / "traffic").glob("*.json"))}
POISSON = sorted(k for k, v in MIXES.items()
                 if v["arrivals"]["kind"] == "poisson")
#: a closed loop the tests always have, beside the mixes in files
CLOSED_MIXES = dict(
    {k: v for k, v in MIXES.items() if v["arrivals"]["kind"] == "closed"},
    closed_test=dict(MIXES["alpaca_poisson"], arrivals={
        "kind": "closed", "clients": 8, "think_s": 0.25}))


@pytest.mark.parametrize("name", POISSON)
def test_same_seed_same_requests(name):
    a, b = (Traffic(MIXES[name], 1000, 2 ** 31 + 5, 51) for _ in range(2))
    assert [(r.prompt, r.max_new, r.due) for r in a.reqs] == \
        [(r.prompt, r.max_new, r.due) for r in b.reqs]


@pytest.mark.parametrize("name", POISSON)
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


def test_poisson_requests_are_the_first_benchmarks():
    """The open loop's requests for a seed, bit for bit as the benchmark
    first made them (a digest of that generator's output for the Alpaca
    mix at its first rate, 3.2 requests a second)."""
    mix = dict(MIXES["alpaca_poisson"],
               arrivals={"kind": "poisson", "rate_per_s": 3.2})
    t = Traffic(mix, 49152, 2 ** 31 + 77, 51)
    got = hashlib.sha256(json.dumps(
        [(r.prompt, r.max_new, r.due) for r in t.reqs]).encode()).hexdigest()
    assert len(t.reqs) == 201
    assert got == \
        "d14e86a9c149eee894826ebb03094d4b8871a5831fe7307e1a7518e5a65230eb"


def serve_closed(t: Traffic, clients: int, until: float):
    """Serve a closed loop of ``clients`` on a simulated clock: each
    request is in flight for a time set by its sizes.  Returns every
    (submit, finish, req)."""
    now, flight, done = -t.warmup_s, [], []
    while now < until:
        for r in t.pop_due(now):
            flight.append((now + 0.01 * r.max_new + 0.001 * len(r.prompt),
                           now, r))
            assert len(flight) <= clients
            assert len({f[2].client for f in flight}) == len(flight)
        flight.sort(key=lambda f: (f[0], f[2].idx))
        nxt = t.next_due()
        if flight and (nxt is None or flight[0][0] < nxt):
            end, start, r = flight.pop(0)
            now = end
            t.finish(r, end)
            done.append((start, end, r))
        else:
            assert nxt is not None, "a closed loop never runs dry"
            now = max(now, nxt)
    return done


@pytest.mark.parametrize("name", sorted(CLOSED_MIXES))
def test_closed_loop_keeps_its_clients_and_think_time(name):
    mix = CLOSED_MIXES[name]
    arr = mix["arrivals"]
    t = Traffic(mix, 1000, 2 ** 31 + 11, 51)
    assert [r.due for r in t.reqs] == [-mix["warmup_s"]] * arr["clients"]
    done = serve_closed(t, arr["clients"], 60.0)
    assert len(done) > 2 * BLOCK
    finish = {r.idx: end for _, end, r in done}
    for c in range(arr["clients"]):
        mine = [r for r in t.reqs if r.client == c]
        assert mine[0].due == -mix["warmup_s"]
        for a, b in zip(mine, mine[1:]):
            assert b.due == finish[a.idx] + arr["think_s"]
    # every request is due at submit: none waits on another client
    assert all(start == r.due for start, _, r in done)


@pytest.mark.parametrize("name", sorted(CLOSED_MIXES))
def test_closed_loop_same_seed_same_requests(name):
    runs = []
    for _ in range(2):
        mix = CLOSED_MIXES[name]
        t = Traffic(mix, 1000, 2 ** 31 + 5, 51)
        serve_closed(t, mix["arrivals"]["clients"], 30.0)
        runs.append([(r.prompt, r.max_new, r.due, r.client) for r in t.reqs])
    assert runs[0] == runs[1]


@pytest.mark.parametrize("name", sorted(CLOSED_MIXES))
def test_closed_loop_blocks_hold_one_multiset(name):
    """Each block of ``BLOCK`` requests, in order of issue, holds the same
    lengths whatever the seed, in another order."""
    mix = CLOSED_MIXES[name]
    a, b = (Traffic(mix, 1000, seed, 51) for seed in (1, 2 ** 31 + 3))
    for t in (a, b):
        serve_closed(t, mix["arrivals"]["clients"], 40.0)
    n = min(len(a.reqs), len(b.reqs)) // BLOCK * BLOCK
    assert n >= 2 * BLOCK
    for k in range(0, n, BLOCK):
        block = [(ra, rb) for ra, rb in zip(a.reqs[k:k + BLOCK],
                                             b.reqs[k:k + BLOCK])]
        assert sorted((len(x.prompt), x.max_new) for x, _ in block) == \
            sorted((len(y.prompt), y.max_new) for _, y in block)
        assert [len(x.prompt) for x, _ in block] != \
            [len(y.prompt) for _, y in block]
    assert [r.prompt for r in a.reqs[:n]] != [r.prompt for r in b.reqs[:n]]


def test_closed_loop_failed_submit_frees_its_client():
    mix = CLOSED_MIXES["closed_test"]
    t = Traffic(mix, 1000, 4, 51)
    first = t.pop_due(-mix["warmup_s"])
    assert len(first) == 8 and t.next_due() is None
    first[3].failed = True
    t.finish(first[3], -9.5)
    (nxt,) = t.pop_due(-9.25)
    assert nxt.client == first[3].client and nxt.due == -9.5 + 0.25


def test_unknown_arrival_kind_is_refused():
    mix = dict(MIXES["alpaca_poisson"],
               arrivals={"kind": "gamma", "rate_per_s": 2.0})
    with pytest.raises(ValueError):
        Traffic(mix, 1000, 1, 51)


def test_new_files_are_found_by_name(tmp_path: Path):
    """A configuration with qkv biases, an open and a closed mix, their
    cells and a per-layer metric, added to a copy of ``bench/`` as files
    and entries only: every file already there stays as it is."""
    root = tmp_path
    bench = root / "bench"
    shutil.copytree(SP.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    mix = dict(MIXES["alpaca_poisson"])
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 2.0}
    (bench / "traffic" / "slow.json").write_text(json.dumps(mix))
    closed = dict(MIXES["alpaca_poisson"])
    closed["arrivals"] = {"kind": "closed", "clients": 32, "think_s": 0.0}
    (bench / "traffic" / "decode_closed.json").write_text(json.dumps(closed))
    config = SP.load_json(bench / "configs" / "smollm-360m.json")
    config["name"] = "biased"
    config["model"] = dict(config["model"], qkv_bias=True)
    (bench / "configs" / "biased.json").write_text(json.dumps(config))
    (bench / "cells" / "smollm360m.slow.json").write_text(
        json.dumps({"max_logit_gap": 1.0}))
    (bench / "cells" / "biased.decode_closed.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (bench / "layer_metrics" / "queue_wait_p95_ms.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = SP.benchmark()
    spec["configs"].append({"name": "biased", "source": "a test",
                            "file": "bench/configs/biased.json",
                            "reduced": [], "why": "qkv biases"})
    spec["workloads"] += [
        {"name": "smollm360m.slow", "config": "smollm-360m",
         "traffic": "slow", "chips": 1, "why": "slow arrivals"},
        {"name": "biased.decode_closed", "config": "biased",
         "traffic": "decode_closed", "chips": 1, "why": "closed loop"}]
    spec["per_layer"].append({"name": "queue_wait_p95_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "scheduler", "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items())

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

    cell = SP.load_cell("biased.decode_closed", root)
    assert cell.limits == {"max_logit_gap": 0.5}
    model = W.Model.from_config(cell.config)
    assert model.qkv_bias and model.n_layers == 32
    t = Traffic(cell.traffic, model.vocab, 2 ** 31 + 1, 51)
    assert len(t.pop_due(-cell.traffic["warmup_s"])) == 32
    assert t.next_due() is None


class FakeEngine:
    """Serves one token per live request a step; refuses prompts whose
    length is a multiple of five, as a full engine refuses a request."""

    def __init__(self):
        from repro.serving.engine import EngineStats
        self.stats = EngineStats()
        self.live = {}
        self.most_live = 0
        self.n_submitted = 0

    def submit(self, prompt, params):
        from repro.serving.config import EngineError
        if len(prompt) % 5 == 0:
            raise EngineError("refused")
        rid = self.n_submitted
        self.n_submitted += 1
        self.live[rid] = [params.max_new_tokens, []]
        self.most_live = max(self.most_live, len(self.live))
        return rid

    def step(self):
        import time
        from types import SimpleNamespace
        time.sleep(0.001)
        self.stats.rows += 1
        outs = []
        for rid, (n, toks) in list(self.live.items()):
            toks.append(1)
            outs.append(SimpleNamespace(rid=rid, finished=len(toks) == n,
                                        output_token_ids=list(toks)))
            if len(toks) == n:
                del self.live[rid]
        return outs


def test_serve_tells_a_closed_loop_of_each_finish_and_failed_submit():
    from types import SimpleNamespace

    from bench import harness as H
    mix = dict(MIXES["alpaca_poisson"], warmup_s=0.05,
               prompt={"median": 10, "sigma": 0.5, "min": 4, "max": 22},
               output={"median": 4, "sigma": 0.5, "min": 2, "max": 8},
               arrivals={"kind": "closed", "clients": 3, "think_s": 0.01})
    t = Traffic(mix, 1000, 2 ** 31 + 21, 0.4)
    eng = FakeEngine()
    _, _, _, _, stats = H.serve(eng, t, 0.4, None, SimpleNamespace(n=0),
                                record=False)
    assert eng.most_live <= 3
    failed = [r for r in t.reqs if r.failed]
    done = [r for r in t.reqs if r.done_t is not None]
    assert failed and len(done) > 3 and stats["rows"] > 0
    for r in failed + done:
        end = r.submit_t if r.failed else r.done_t
        later = [n for n in t.reqs if n.client == r.client and n.idx > r.idx]
        if later:
            assert later[0].due == end + 0.01
