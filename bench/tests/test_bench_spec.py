"""BENCHMARK.json keeps to its contract, and every name it gives leads to
a file of its own."""
import json
import re

import pytest

from bench import spec as SP

B = SP.benchmark()
ROOT = SP.ROOT
TEXT_RE = re.compile(r"^[^\t\n]{1,200}$")
WIDTH_RE = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                      r"projection|head_size|expansion|experts_per_tok")


def test_top_level_keys_and_size():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= B["run_seconds"] <= 51


def test_command_and_paths_stay_inside():
    assert 1 <= len(B["command"]) <= 32
    for p in B["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    for word in B["command"]:
        assert TEXT_RE.match(word)
        if "/" in word:
            assert any(word.startswith(p + "/") for p in B["paths"])


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in B["configs"]] + \
        [w["name"] for w in B["workloads"]] + \
        [m["name"] for m in B["end_to_end"] + B["per_layer"]] + \
        [w["traffic"] for w in B["workloads"]] + \
        [k for c in B["configs"] for k in c["reduced"]]
    for n in names:
        assert SP.NAME_RE.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert SP.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    all_names = [c["name"] for c in B["configs"]] + \
        [w["name"] for w in B["workloads"]] + \
        [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(all_names) == len(set(all_names))


def test_entries_have_only_their_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert TEXT_RE.match(c["why"]) and TEXT_RE.match(c["source"])
        assert not any(WIDTH_RE.search(k) for k in c["reduced"])
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and TEXT_RE.match(w["why"])
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert TEXT_RE.match(m["layer"])
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}


def test_each_moves_is_reported_where_its_metric_is():
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            reported = {e["name"] for e in SP.metrics_for(B, "end_to_end",
                                                          cell)}
            assert m["moves"] in reported, (m["name"], cell)


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_every_name_leads_to_its_files(w):
    cell = SP.load_cell(w["name"])
    assert cell.config["name"] == w["config"]
    assert cell.config["reduced"] == next(
        c["reduced"] for c in B["configs"] if c["name"] == w["config"])
    assert "max_logit_gap" in cell.limits
    assert cell.traffic["engine"]["cache_kind"] == "paged"
    for m in cell.per_layer:
        assert callable(SP.reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_config_files_lie_under_paths_and_differ():
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in B["paths"])
        json.loads((ROOT / f).read_text())


def test_check_budget_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
