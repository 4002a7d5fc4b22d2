"""BENCHMARK.json against the rules a benchmark file must keep."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= spec["run_seconds"] <= 51
    assert spec["command"][1] == "bench/run.py"
    assert all(not w.startswith("/") and ".." not in w
               for w in spec["command"])
    for p in spec["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
        assert (ROOT / p).is_dir()


def test_names_and_units(spec):
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec[key]:
            assert NAME.match(e["name"]), e["name"]
            names.append((key, e["name"]))
    for key in ("configs", "workloads"):
        ns = [e["name"] for e in spec[key]]
        assert len(ns) == len(set(ns))
    ms = [e["name"] for e in spec["end_to_end"] + spec["per_layer"]]
    assert len(ms) == len(set(ms))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_files_and_cells(spec):
    used = {w["config"] for w in spec["workloads"]}
    files = set()
    for c in spec["configs"]:
        assert c["name"] in used, f"{c['name']} keeps no cell"
        assert c["file"].startswith("bench/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf["model"])
        assert "max_log_gap" in conf["limits"]
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text


def test_workloads(spec):
    pairs = set()
    four = 0
    for w in spec["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert NAME.match(w["traffic"])
        with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
            traffic = json.load(f)
        assert (ROOT / traffic["pool"]).is_file()
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_cell_reports_enough(spec):
    cells = [w["name"] for w in spec["workloads"]]

    def reports(metrics, cell):
        return [m["name"] for m in metrics
                if cell in m.get("workloads", cells)]
    for cell in cells:
        e2e = reports(spec["end_to_end"], cell)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reports(spec["per_layer"], cell)


def test_per_layer_moves_a_metric_its_cells_report(spec):
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 3


def test_bounds(spec):
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_metric_has_a_reader(spec):
    import run
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.load_reader(m["name"]))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
