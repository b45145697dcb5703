"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix and per-layer metric is found by name, and what the JSON says of
each is what its file says."""
import json
import os
import re

import pytest

from benchmark import cells, device as D, kernel_costs

REPO = cells.REPO_DIR
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24            # a full check with all 24 cells
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry_matches_its_files(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(entry[k]) for k in ("name", "config", "traffic"))
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    cell = cells.load_cell(entry["name"])
    for key in ("config", "traffic", "chips", "why"):
        assert cell[key] == entry[key], key
    assert cell["platform"] == "tpu"
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    cells.job_module(cell)
    # the JSON's per-cell metric lists are the workload file's
    for kind, listed in (("end_to_end", cell["end_to_end"]),
                         ("per_layer", cell["layer_metrics"])):
        by_json = {m["name"] for m in BENCH[kind]
                   if entry["name"] in m.get(
                       "workloads", [w["name"] for w in BENCH["workloads"]])}
        assert by_json == set(listed), kind
    assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
    units = cells.job_module(cell).END_TO_END
    for m in BENCH["end_to_end"]:
        if m["name"] in cell["end_to_end"]:
            assert units[m["name"]] == m["unit"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_matches_its_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert isinstance(config["assumed"], dict) and config["deployment"]
    # no width may be cut
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_size|_dim|_rank|head|experts_per)", key)
    cells.family_module(config)
    cells.reference_module(config)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_is_one_module_that_says_the_same(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    module = cells.metric_module(metric["name"])
    assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
        metric["layer"], metric["unit"], metric["moves"], metric["source"])
    assert metric["source"] in SOURCES
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    everywhere = [w["name"] for w in BENCH["workloads"]]
    assert set(metric.get("workloads", everywhere)) <= set(
        moved.get("workloads", everywhere))
    # the layer is one of PERF.md's list of layers
    with open(os.path.join(REPO, "PERF.md")) as f:
        assert f"**{metric['layer']}**" in f.read()


def test_names_are_unique():
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_files_under_paths_are_named_from_name_characters():
    for root, dirs, files in os.walk(cells.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", n), os.path.join(root, n)


def test_peaks_table_and_unknown_device():
    peaks = D.peaks_for({"platform": "tpu", "kind": "TPU v5 lite"})
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["source"]
    with pytest.raises(D.DeviceError):
        D.peaks_for({"platform": "tpu", "kind": "TPU v9 imaginary"})
    assert D.peaks_for({"platform": "cpu", "kind": "cpu"}) is None


def test_memory_bytes_is_the_fullest_chip_of_those_the_cell_uses(monkeypatch):
    import jax
    from types import SimpleNamespace as NS
    devs = [NS(memory_stats=lambda b=b: {"bytes_in_use": b,
                                         "peak_bytes_in_use": 2 * b})
            for b in (5, 9, 7)] + [NS(memory_stats=lambda: None)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    assert D.memory_bytes(3, "bytes_in_use") == 9
    assert D.memory_bytes(1, "peak_bytes_in_use") == 10
    assert D.memory_bytes(4, "bytes_in_use") == 9   # one keeps no count


def test_kernel_costs_arithmetic():
    peaks = D.load_peaks()["TPU v5 lite"]
    flops, bytes_ = kernel_costs.flash_cost("flash_fwd", 64, 2048, 128)
    assert flops == 2 * 2 * 2048 * 2048 * 128 * 64 / 2
    assert bytes_ == 4 * 64 * 2048 * 128 * 2 + 2 * 64 * 2048 * 4
    # compute-bound at these shapes
    assert kernel_costs.min_seconds(flops, bytes_, peaks) == flops / 197e12
    flops, bytes_ = kernel_costs.paged_cost(800, 8, 8, 16, 32, 8, 128)
    assert bytes_ == 2 * (800 + 8 * 7.5) * 8 * 128 * 2 + 2 * 8 * 32 * 128 * 2
    # memory-bound
    assert kernel_costs.min_seconds(flops, bytes_, peaks) == bytes_ / 819e9
    assert kernel_costs.train_flops_per_token(10, 2, 4, 8) \
        == 60 + 6 * 2 * 8 * 4
