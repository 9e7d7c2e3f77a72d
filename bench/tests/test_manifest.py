"""BENCHMARK.json against the benchmark's own rules: every file it names
exists, names and units use the allowed characters, every metric is
reported where it says, and a full check fits its time."""
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.fixture(scope="module")
def bench():
    return BENCH


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_cells_name_files_that_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        c = configs[w["config"]]
        used.add(w["config"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["layout"]["chips"] == w["chips"]
        assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap",
                                      "update_norm_gap"}
    assert used == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)


def test_four_chip_cells_at_most_half(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        def here(m):
            return w["name"] in m.get("workloads", [w["name"]])
        mine = {m["name"] for m in bench["end_to_end"] if here(m)}
        assert "setup_s" in mine and len(mine) >= 2
        assert any(here(m) for m in bench["per_layer"])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_module(bench, m):
    mod = importlib.import_module(f"bench.metrics.{m['name']}")
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    e2e = {x["name"]: x for x in bench["end_to_end"]}
    assert m["moves"] in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for w in m["workloads"]:
        assert w in cells
        assert w in e2e[m["moves"]].get("workloads", [w])


def test_bounds_and_run_length(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
