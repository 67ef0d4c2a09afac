"""``BENCHMARK.json`` against the contract it was written to, and every file
it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
        assert os.path.isdir(os.path.join(ROOT, path))
    # the full check of 24 cells fits the driver's budget
    cells = 24
    assert (2 + 14 * cells) * (bench["run_seconds"] + 60) + cells * 180 + 1200 <= 43_200


def test_names_units_and_entries(bench):
    seen = set()
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound", "source"}),
                        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for m in bench[group]:
            assert set(m) - {"workloads"} == keys, m
            assert NAME.match(m["name"]) and m["name"] not in seen
            seen.add(m["name"])
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_configs_and_cells_name_files_that_exist(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"])
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert body["guarantees"] and body["assumed"]
    cells = set()
    four = 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        traffic = os.path.join(ROOT, "benchmark", "traffic", f"{w['traffic']}.json")
        with open(traffic) as f:
            assert json.load(f)["kind"] in ("backlog", "arrivals")
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert {c for c, _ in cells} == set(configs), "a configuration no cell uses"


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_cell_reports_what_its_metrics_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells, (m["name"], cell)
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if _reports(m, cell)]
        assert any(m["name"] == "setup_s" for m in mine)
        assert len(mine) >= 2
        assert any(_reports(m, cell) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in cells:
            if _reports(m, cell):
                assert _reports(e2e[m["moves"]], cell), (m["name"], cell)
        reader = os.path.join(ROOT, "benchmark", "layer_metrics", f"{m['name']}.py")
        assert os.path.isfile(reader), f"no reader for {m['name']}"


def test_files_under_paths_are_named_from_name_characters(bench):
    for path in bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", name), os.path.join(base, name)
