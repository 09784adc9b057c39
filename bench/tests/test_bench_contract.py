"""BENCHMARK.json keeps to the form the harness and its checker read."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == KEYS["top"]
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") for w in BENCH["command"])
    files = [w for w in BENCH["command"] if "/" in w]
    assert all(any(f.startswith(p + "/") for p in BENCH["paths"])
               for f in files)
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = 24   # what the check must fit with all cells a benchmark may have
    budget = (2 + 14 * cells) * (BENCH["run_seconds"] + 60) + \
        cells * 2 * 90 + 1200
    assert budget <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_configs():
    configs = BENCH["configs"]
    assert 1 <= len(configs) <= 24
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in configs:
        assert set(c) == KEYS["config"]
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert c["file"] not in files
        files.add(c["file"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    assert len({c["name"] for c in configs}) == len(configs)


def test_workloads():
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in cells:
        assert set(w) == KEYS["workload"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (ROOT / "bench/mixes" / f"{w['traffic']}.json").is_file()
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    assert len({w["name"] for w in cells}) == len(cells)


def _reported(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def test_metrics():
    e2e, layer = BENCH["end_to_end"], BENCH["per_layer"]
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == KEYS["end_to_end"]
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in e2e)
    by_name = {m["name"]: m for m in e2e}
    for m in layer:
        assert set(m) - {"workloads"} == KEYS["per_layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in by_name
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in [x["name"] for x in _reported(e2e, cell)]
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough(cell):
    e2e = [m["name"] for m in _reported(BENCH["end_to_end"], cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert _reported(BENCH["per_layer"], cell)
