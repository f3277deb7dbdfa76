"""``BENCHMARK.json`` against the contract's limits on names and files, and
against the files it names."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["perf"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        names.append(w["name"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_cells_files_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        wl = _load("perf", "workloads", w["name"] + ".json")
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert len(wl["why"]) > 0
        cfg = configs[w["config"]]
        assert cfg["file"].startswith("perf/")
        body = _load(cfg["file"])
        assert body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"]
        for kind in ("models", "refs"):
            assert os.path.isfile(os.path.join(
                ROOT, "perf", kind, w["config"] + ".py"))
        assert os.path.isfile(os.path.join(
            ROOT, "perf", "loops", wl["loop"] + ".py"))
    assert {c["name"] for c in manifest["configs"]} == \
        {w["config"] for w in manifest["workloads"]}


def test_every_metric_has_its_reader_and_its_cells(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in manifest["end_to_end"]}
    layers = {}
    for m in manifest["per_layer"]:
        spec = _load("perf", "metrics", m["name"] + ".json")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.isfile(os.path.join(
            ROOT, "perf", "reducers", spec["reducer"] + ".py"))
        assert m["moves"] in e2e
        mine = set(m.get("workloads", e2e[m["moves"]]))
        assert mine and mine <= cells
        # every cell that reports the metric reports what it moves
        assert mine <= e2e[m["moves"]], m["name"]
        layers.setdefault(m["layer"], []).append(m["name"])
    # every cell reports setup_s, another end-to-end and a per-layer metric
    for c in cells:
        assert sum(1 for v in e2e.values() if c in v) >= 2
        assert any(c in set(m.get("workloads", cells))
                   for m in manifest["per_layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf_md = f.read()
    for layer in layers:
        assert layer in perf_md, "PERF.md's list of layers lacks %r" % layer


def test_peaks_table():
    from perf import harness
    row = harness.peaks(ROOT, "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks(ROOT, "TPU v9 imaginary")
