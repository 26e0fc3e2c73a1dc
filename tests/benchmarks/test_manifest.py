"""BENCHMARK.json against the contract's form, and against the files it
names."""

import json
import os
import re

import pytest

from benchmarks.files import HERE, ROOT, Manifest, load_py

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def doc(manifest):
    return manifest.doc


def _metrics(doc):
    return doc["end_to_end"] + doc["per_layer"]


def test_keys_and_sizes(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    # 2 + 14 runs a cell at run_seconds + 60, 180 s a cell to compile and
    # 1200 s spare have to fit 43200 s with the full 24 cells
    assert 2 * (doc["run_seconds"] + 60) + 24 * (
        14 * (doc["run_seconds"] + 60) + 180) + 1200 <= 43200
    assert 1 <= len(doc["workloads"]) <= 24 and 1 <= len(doc["configs"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16
    assert 1 <= len(doc["per_layer"]) <= 128


def test_names_and_units_use_the_allowed_characters(doc):
    names = [m["name"] for m in _metrics(doc)] \
        + [w["name"] for w in doc["workloads"]] \
        + [c["name"] for c in doc["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in _metrics(doc):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for text in [w["why"] for w in doc["workloads"]] \
            + [c["why"] for c in doc["configs"]] \
            + [c["source"] for c in doc["configs"]] \
            + [m["layer"] for m in doc["per_layer"]] + doc["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_reports_setup_another_metric_and_a_layer(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in doc["workloads"]:
        own = [m for m in doc["end_to_end"] if m["name"] != "setup_s"
               and w["name"] in m.get("workloads", [w["name"]])]
        assert own, w["name"]
        assert any(w["name"] in m.get("workloads", [])
                   for m in doc["per_layer"]), w["name"]
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= max(
        1, len(doc["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in doc["workloads"])


def test_each_per_layer_metrics_cells_report_the_metric_it_moves(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["per_layer"]:
        moved = e2e[m["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting, m["name"]
        assert set(m.get("workloads", [])) <= cells
    # a kernel's roofline stands beside the whole step's share of the peak
    for m in doc["per_layer"]:
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in doc["per_layer"]), m["name"]


def test_every_file_a_cell_or_a_metric_names_exists(manifest, doc):
    paths = [p.rstrip("/") + "/" for p in doc["paths"]]
    for c in doc["configs"]:
        assert any(c["file"].startswith(p) for p in paths)
        cfg = manifest.config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        for kind in ("model", "reference", "counts"):
            assert os.path.isfile(os.path.join(HERE, {
                "model": "models"}.get(kind, kind), cfg[kind] + ".py"))
    files = [c["file"] for c in doc["configs"]]
    assert len(set(files)) == len(files)
    for w in doc["workloads"]:
        mix = manifest.traffic(w["traffic"])
        assert os.path.isfile(os.path.join(HERE, "drivers",
                                           mix["driver"] + ".py"))
        limits = manifest.limits(w["name"])
        assert limits and all(v is None or v >= 0 for v in limits.values())
    for m in doc["per_layer"]:
        spec = manifest.metric_file(m["name"])
        assert (spec["unit"], spec["source"], spec["layer"], spec["moves"]) \
            == (m["unit"], m["source"], m["layer"], m["moves"])
        assert hasattr(load_py("readers", spec["reader"]), "read")
        if "count" in spec.get("args", {}):
            assert hasattr(load_py("counts", spec["args"]["count"]), "work")


def test_no_width_is_reduced(doc):
    width = re.compile(r"(hidden_size|intermediate|latent|state_size|proj"
                       r"|head_|experts_per|_dim$|_rank$|expansion)")
    for c in doc["configs"]:
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if width.search(k)]


def test_peaks_name_their_source():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert "cloud.google.com" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
