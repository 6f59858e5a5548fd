"""``BENCHMARK.json`` held to the contract the driver checks, and to what
the harness needs to find each cell's files by name."""

import json
import os
import re

import pytest

import bench_paths
from harness import spec as spec_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return spec_lib.load()


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert spec["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    path = os.path.join(bench_paths.REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024


def test_names_are_within_the_allowed_characters_and_unique(spec):
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in spec[kind]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(NAME.match(n) for n in metrics), metrics
    for cell in spec["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    for config in spec["configs"]:
        assert all(NAME.match(k) for k in config["reduced"])
        assert len(config["reduced"]) <= 16


def test_entries_have_just_the_keys_of_the_contract(spec):
    for config in spec["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
    for cell in spec["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_texts_are_one_line_of_at_most_200_characters(spec):
    texts = [c["why"] for c in spec["configs"] + spec["workloads"]]
    texts += [c["source"] for c in spec["configs"]]
    texts += [m["layer"] for m in spec["per_layer"]] + spec["command"]
    for text in texts:
        assert 1 <= len(text) <= 200, text
        assert "\n" not in text and "\t" not in text


def test_units_sources_and_directions(spec):
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0.01 <= m["bound"] <= 0.1, m
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


def test_every_cell_has_its_files_and_its_metrics(spec):
    configs = {c["name"] for c in spec["configs"]}
    for cell in spec["workloads"]:
        assert cell["config"] in configs
        assert cell["chips"] in (1, 4)
        traffic = spec_lib.traffic(cell["traffic"])
        assert traffic["chips"] == cell["chips"]
        values, builder = spec_lib.config(spec, cell["config"])
        assert builder.exists(), builder
        assert values["source"] == next(
            c["source"] for c in spec["configs"]
            if c["name"] == cell["config"])
        end_to_end = {m["name"] for m in spec_lib.metrics(
            spec, "end_to_end", cell["name"])}
        assert "setup_s" in end_to_end and len(end_to_end) >= 2
        per_layer = spec_lib.metrics(spec, "per_layer", cell["name"])
        assert per_layer
        # a per-layer metric is reported only where the metric it moves is
        for m in per_layer:
            assert m["moves"] in end_to_end, (cell["name"], m["name"])
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {c["config"] for c in spec["workloads"]}
    assert used == configs  # every configuration keeps a cell


def test_metric_workloads_name_cells_and_moves_names_an_end_to_end(spec):
    cells = {c["name"] for c in spec["workloads"]}
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m
    for m in spec["per_layer"]:
        assert m["moves"] in end_to_end, m


def test_every_per_layer_metric_has_a_reader(spec):
    for m in spec["per_layer"]:
        assert callable(spec_lib.layer_reader(m["name"])), m["name"]
    layers = {m["layer"] for m in spec["per_layer"]}
    perf = open(os.path.join(bench_paths.REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layers do not name {layer!r}"


def test_at_most_a_quarter_of_the_cells_take_four_chips(spec):
    four = [c for c in spec["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)


def test_config_files_lie_under_paths_and_are_not_shared(spec):
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in spec["paths"]), f
        data = json.load(open(os.path.join(bench_paths.REPO, f)))
        for key in ("source", "reduced", "assumed", "dtype_policy",
                    "optimizer"):
            assert key in data, (f, key)


def test_reduced_names_no_width(spec):
    width = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                       r"head_size|n_embd|n_inner|expansion|experts_per")
    for config in spec["configs"]:
        for key in config["reduced"]:
            assert not width.search(key), (config["name"], key)
        data = json.load(open(os.path.join(bench_paths.REPO, config["file"])))
        assert data["reduced"] == config["reduced"]


def test_an_unknown_workload_is_an_error(spec):
    with pytest.raises(spec_lib.SpecError, match="no workload"):
        spec_lib.workload(spec, "no-such-cell")
    with pytest.raises(spec_lib.SpecError, match="no traffic mix"):
        spec_lib.traffic("no-such-mix")
