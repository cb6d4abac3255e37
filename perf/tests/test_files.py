"""Every file the harness finds by name loads and points at things that
exist, and every name keeps to the manifest's alphabet."""

import glob
import json
import os
import re

import pytest

from perf import harness, readers, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def names(kind):
    return sorted(os.path.basename(p)[:-5]
                  for p in glob.glob(os.path.join(HERE, kind, "*.json")))


with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


@pytest.mark.parametrize("name", names("workloads"))
def test_workload_file(name):
    cell = harness.load_json("workloads", name)
    assert cell["name"] == name and NAME.match(name)
    assert cell["config"] in names("configs")
    assert cell["traffic"] in names("traffic")
    assert os.path.exists(os.path.join(HERE, f"{cell['runner']}_runner.py"))
    assert cell["chips"] in (1, 4)
    assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
    assert 0 < len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert set(cell["check"]["limits"]) and all(
        isinstance(v, (int, float)) for v in cell["check"]["limits"].values())
    weights.load_sizes(cell["config"])


@pytest.mark.parametrize("name", names("metrics"))
def test_metric_file(name):
    m = harness.load_json("metrics", name)
    assert m["name"] == name and NAME.match(name) and UNIT.match(m["unit"])
    assert callable(readers.find_reader(m["reader"]))
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
    if name.endswith("_roofline") or "mfu" in name:
        assert m["unit"] == "%"


@pytest.mark.parametrize("name", names("configs"))
def test_config_file(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    assert cfg["name"] == name and cfg["source"].startswith("https://")
    assert cfg["reduced"] == [] and cfg["assumed"]
    sizes = weights.load_sizes(name)
    assert sizes.d_model % sizes.num_heads == 0
    from perf import counts

    assert counts.num_params(sizes) == cfg["parameters"]


def test_manifest_keeps_to_the_contract():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["perf"] and 1 <= m["run_seconds"] <= 51
    cells = [w["name"] for w in m["workloads"]]
    assert len(set(cells)) == len(cells)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(cells) // 4)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert harness.load_cell(w["name"])["end_to_end"]
        assert len(w["why"]) <= 200
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and os.path.exists(
            os.path.join(harness.ROOT, c["file"]))
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1 and UNIT.match(e["unit"])
        assert e["source"] in ("host_clock", "device_trace")
        assert set(e.get("workloads", cells)) <= set(cells)
    layers = set()
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        on_file = harness.load_json("metrics", p["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert on_file[key] == p[key], (p["name"], key)
        reporting = set(e2e[p["moves"]].get("workloads", cells))
        assert set(p.get("workloads", cells)) <= reporting
        layers.add(p["layer"])
    for cell in cells:
        loaded = harness.load_cell(cell)
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
        assert any("mfu" in p["name"] for p in loaded["per_layer"])
    names_ = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names_)) == len(names_)
