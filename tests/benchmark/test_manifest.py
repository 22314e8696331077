"""BENCHMARK.json is well-formed, and every file it names is there."""

import json
import os
import re

import pytest

from tests.benchmark.helpers import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _bench(*parts):
    return os.path.join(REPO, "benchmark", *parts)


def test_top_level_keys(manifest):
    assert sorted(manifest) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "bound", "name", "source", "unit"]
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for entry in manifest["configs"] + manifest["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic = _bench("traffic", w["traffic"] + ".json")
        assert os.path.exists(traffic)
        with open(traffic) as f:
            doc = json.load(f)
        assert doc["chips"] == w["chips"]
        assert os.path.exists(_bench("drivers", doc["driver"] + ".py"))
        with open(_bench("limits", w["name"] + ".json")) as f:
            assert json.load(f)["limits"]
    assert used == set(configs)  # every configuration has a cell
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["reduced"] == c["reduced"] == []
        assert len(c["source"]) <= 200


def test_per_layer_metrics_move_what_their_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    reported = {c: 0 for c in cells}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]]
            reported[cell] += 1
        spec_path = _bench("layer_metrics", m["name"] + ".json")
        with open(spec_path) as f:
            spec = json.load(f)
        assert os.path.exists(_bench("readers", spec["reader"] + ".py"))
    assert all(reported.values())
    # a share of a peak or of a roofline carries the contract's names
    for m in manifest["per_layer"]:
        if m["unit"] == "%" and m["name"] != "device_idle_pct":
            assert "mfu" in m["name"] or "_roofline" in m["name"]


def test_a_full_check_fits_the_day(manifest):
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
