"""BENCHMARK.json against the contract's limits and against the files the
harness looks up by name."""

import importlib
import json
import os
import re

import pytest

from benchmark.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


MANIFESTS = ["BENCHMARK.json", "tests/benchmark/manifest.json"]


@pytest.fixture(params=MANIFESTS)
def manifest(request):
    return _load(request.param)


def _cells_of(metric, manifest):
    return metric.get("workloads",
                      [w["name"] for w in manifest["workloads"]])


def test_top_level_keys_are_the_contracts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["command"][:2] == ["python3", "benchmark/run.py"]
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_and_units_use_the_allowed_characters(manifest):
    names = []
    for c in manifest["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        listed = [x["name"] for x in manifest[kind]]
        assert len(listed) == len(set(listed))
    metrics = [m["name"] for m in
               manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_end_to_end_metrics_have_bounds_and_setup_s(manifest):
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for w in manifest["workloads"]:
        mine = [m for m in manifest["end_to_end"]
                if w["name"] in _cells_of(m, manifest)]
        assert len(mine) >= 2, f"{w['name']} reports only setup_s"


def test_every_layer_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e, m
        moved = set(_cells_of(e2e[m["moves"]], manifest))
        assert set(_cells_of(m, manifest)) <= moved, m
    for w in manifest["workloads"]:
        assert any(w["name"] in _cells_of(m, manifest)
                   for m in manifest["per_layer"])


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        used.add(w["config"])
        config = _load(configs[w["config"]]["file"])
        assert config["reduced"] == configs[w["config"]]["reduced"]
        traffic = _load(f"benchmark/traffic/{w['traffic']}.json")
        runner = importlib.import_module(
            f"benchmark.runners.{traffic['runner']}")
        assert callable(runner.run)
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("benchmark/") for f in files)


def test_every_layer_metric_has_a_reader(manifest):
    for m in manifest["per_layer"]:
        spec = _load(f"benchmark/layer_metrics/{m['name']}.json")
        reducer = importlib.import_module(
            f"benchmark.reducers.{spec['reducer']}")
        assert callable(reducer.reduce)


def test_published_widths_are_not_cut():
    gpt = _load("benchmark/configs/gpt2-large.json")
    assert (gpt["n_layer"], gpt["n_embd"], gpt["n_head"],
            gpt["n_positions"], gpt["vocab_size"]) == (36, 1280, 20, 1024,
                                                       50257)
    assert gpt["reduced"] == []
    mis = _load("benchmark/configs/mistral-7b-l16.json")
    assert (mis["hidden_size"], mis["intermediate_size"],
            mis["num_attention_heads"], mis["num_key_value_heads"],
            mis["vocab_size"]) == (4096, 14336, 32, 8, 32768)
    assert mis["reduced"] == ["num_hidden_layers"]


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9")
    with pytest.raises(KeyError):
        harness.load_peaks("_source")
