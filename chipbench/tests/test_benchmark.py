"""BENCHMARK.json against the files it names: every cell's configuration and
traffic exist and load, every metric has a reader, names and units keep to
the benchmark's rules, and the route kernel's byte count is its derivation."""
import importlib
import json
import re
from pathlib import Path

import pytest

import source
from metrics import route_roofline

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_lines():
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key], (e["name"], key)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m["name"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_cells_name_files_that_load():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
        assert config["name"] == cell["config"] and config["chips"] == cell["chips"]
        traffic = source.Traffic.load(cell["traffic"])
        assert traffic.population <= config["job"]["state_capacity"] * cell["chips"]
        assert traffic.sweep_batches(cell["chips"]) >= 1


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_each_departure_is_a_key_with_a_reason(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in config and config["departures"][key]


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    reader = importlib.import_module(f"metrics.{metric['name']}")
    assert callable(reader.read)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "moves" in metric:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_route_bytes_derivation():
    # 2^20 records = 1024 tiles of (8, 128); 16 B a record; 128 heavy slots
    # x 3 int32 columns, 4096 host entries and 1 lane count, 4 B each
    assert route_roofline.route_bytes(1 << 20, 128, 4096, 1) == 16 * 2**20 + 4 * (384 + 4097)
    # a partial tile is padded to a whole one
    assert route_roofline.route_bytes(1, 0, 0, 0) == 16 * 1024
