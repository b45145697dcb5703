"""`trace/request_spans.py` and the six "Request path" metrics: end to end
on the tiny CPU cell `tiny.serve-queued` (6 clients on 4 slots, added as
files only), where the four phase means add up to the mean of the engine's
own TTFT over the same requests; on recorded traces without the events (the
parent commit of PR 49: nothing to read, no metric); and the new cell's
files against `BENCHMARK.json`, found by name."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import cells
from benchmark.trace import host_spans as H
from benchmark.trace import request_spans as Q

REPO = cells.REPO_DIR
RECORDED = os.path.join(cells.BENCH_DIR, "trace", "recorded")
CELLS = os.path.join(REPO, "benchmark", "tests", "cells")
CELL = "mistral-7b.serve-decode-queued"
METRICS = {"ttft_queued_ms": "ms", "ttft_bound_ms": "ms",
           "ttft_prefill_ms": "ms", "ttft_first_fetch_ms": "ms",
           "ttft_steps": "steps", "slot_vacant_queued_pct": "%"}


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("queued")
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "tiny.serve-queued", "--seed", "2147483659",
         "--seconds", "2", "--trace", "1", "--cells-root", CELLS],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    assert not os.listdir(tmp)              # the window's trace is gone
    return json.loads(lines[-1]), lines


def test_tiny_queued_cell_reports_the_six_on_the_cpu(tiny_run):
    line, _ = tiny_run
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == set(METRICS) | {"host_admit_ms_per_step"}
    for name, unit in METRICS.items():
        assert line["metrics"][name]["unit"] == unit
        assert line["metrics"][name]["value"] >= 0
    value = {n: line["metrics"][n]["value"] for n in METRICS}
    # two of six clients always wait: the queue is where the time goes,
    # and a freed slot rides the step after its request's last one empty
    assert value["ttft_queued_ms"] > 0
    assert value["ttft_prefill_ms"] > 0 and value["ttft_first_fetch_ms"] > 0
    assert value["ttft_bound_ms"] < value["ttft_queued_ms"]
    assert 2 <= value["ttft_steps"] <= 8      # prompts of 2-4 chunks, + 1
    assert 0 < value["slot_vacant_queued_pct"] < 50


def test_the_four_means_add_up_to_the_engines_mean_ttft(tiny_run):
    line, lines = tiny_run
    said, = [x for x in lines if x.startswith("request spans:")]
    m = re.search(r"(\d+) first token\(s\) in the window \((\d+) with their "
                  r"submit in the trace too\).*add up to ([\d.]+), the "
                  r"engine's ttft_ms has the mean ([\d.]+)", said)
    n, submits, added, engine = (float(g) for g in m.groups())
    assert n > 20 and 0 < submits <= n
    assert added == pytest.approx(engine, abs=2e-3)   # printed to 3 places
    four = sum(line["metrics"][k]["value"] for k in (
        "ttft_queued_ms", "ttft_bound_ms", "ttft_prefill_ms",
        "ttft_first_fetch_ms"))
    assert four == pytest.approx(engine, abs=1e-3)
    # and the engine's mean stands by the client's median: same requests
    # but for the window's edges, a poll and a step apart
    client = re.search(r"ttft_p50_ms ([\d.]+)", [
        x for x in lines if x.startswith("end to end")][0])
    assert float(client.group(1)) == pytest.approx(engine, rel=0.5)


@pytest.mark.parametrize("recording", ["serve-prefill-cached-3steps",
                                       "serve-decode-3steps"])
def test_a_trace_without_the_events_reads_as_nothing(recording, monkeypatch):
    """PR 23's recording holds `pdtpu/serve/` spans and `dispatch` events
    without `slots_vacant_queued`, PR 22's no span at all: as the parent
    commit's traces do. Nothing to read, none of the six on the line."""
    path = os.path.join(RECORDED, recording + ".xplane.pb")
    assert Q.read_xplane(path) is None
    monkeypatch.setattr(H, "find_xplane", lambda trace=None: path)
    monkeypatch.setattr(Q, "_CACHE", {})
    for name in METRICS:
        module = cells.metric_module(name)
        assert module.read(None, {"slots": 128}, None) is None
        assert (module.LAYER, module.MOVES, module.SOURCE) == (
            "Request path", "ttft_p50_ms", "program_span")
    assert Q._CACHE == {"summary": None}


def test_summarize_means_medians_and_the_vacant_share(monkeypatch):
    reqs = [{"rid": str(i), "ttft_ms": 10.0 * i + 6, "queued_ms": 10.0 * i,
             "bound_ms": 1.0, "prefill_ms": 2.0, "first_fetch_ms": 3.0,
             "steps_to_first_token": 2 + i} for i in range(3)]
    s = Q.summarize({"requests": reqs, "submits_inside": 2,
                     "vacant": [0, 1, 0, 2]})
    assert s["queued_ms"] == {"mean": 10.0, "median": 10.0}
    assert s["sum_of_means_ms"] == pytest.approx(16.0) \
        == pytest.approx(s["ttft_ms"]["mean"])
    assert s[Q.STEPS]["mean"] == 3.0
    assert (s["dispatches"], s["vacant_slot_steps"]) == (4, 3)
    monkeypatch.setattr(Q, "_CACHE", {"summary": s})
    assert Q.vacant_queued_pct(None, 4) == pytest.approx(100 * 3 / 16)
    assert Q.mean_of(None, "bound_ms") == 1.0
    # launches with the stat and no first token in the window
    empty = Q.summarize({"requests": [], "submits_inside": 0, "vacant": [0]})
    monkeypatch.setattr(Q, "_CACHE", {"summary": empty})
    assert Q.mean_of(None, "queued_ms") is None
    assert Q.vacant_queued_pct(None, 4) == 0.0


def test_the_new_cell_is_serve_decode_with_more_clients_than_slots():
    """Found by name: the cell's file, its traffic file and the entries of
    `BENCHMARK.json` say the same, and the traffic is `serve-decode`'s but
    for the clients."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    cell = cells.load_cell(CELL)
    twin = cells.load_cell("mistral-7b.serve-decode")
    assert (cell["config"], cell["job"], cell["chips"]) == (
        twin["config"], "serve-closed-loop", 1)
    assert (entry["config"], entry["traffic"], entry["chips"],
            entry["why"]) == (cell["config"], "serve-decode-queued", 1,
                              cell["why"])
    mix, twin_mix = cell["traffic_data"], twin["traffic_data"]
    assert (mix["clients"], mix["slots"]) == (192, 128)
    assert twin_mix["clients"] == twin_mix["slots"] == 128
    drop = ("clients", "doc")
    assert {k: v for k, v in mix.items() if k not in drop} \
        == {k: v for k, v in twin_mix.items() if k not in drop}
    assert "engine" not in mix              # engine defaults, as its twin
    assert cell["end_to_end"] == twin["end_to_end"]
    assert (cell["kernels"], cell["trace_seconds"]) == (
        twin["kernels"], twin["trace_seconds"])
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(cell["layer_metrics"]) >= set(METRICS)
    assert not {"token_efficiency_pct", "step_gap_attributed_pct"} & listed
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            assert m == {"name": m["name"], "unit": METRICS[m["name"]],
                         "better": "lower", "source": "program_span",
                         "layer": "Request path", "moves": "ttft_p50_ms",
                         "workloads": [CELL]}
    for m in bench["end_to_end"]:
        if m["name"] in cell["end_to_end"] and "workloads" in m:
            assert CELL in m["workloads"]
