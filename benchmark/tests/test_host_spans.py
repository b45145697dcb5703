"""`trace/host_spans.py`: the program's `pdtpu/` spans read from a raw
profiler trace, on the recorded chip trace of the new cell (the numbers it
gave when recorded), on synthetic spans (the arithmetic of clipping, self
time and attribution), on a trace without spans (the parent commit: nothing
to read, no metric), and end to end on the tiny CPU cell `tiny.serve-spans`,
which is added as files only, like a later PR's cell."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import cells
from benchmark.trace import host_spans as H

RECORDED = os.path.join(cells.BENCH_DIR, "trace", "recorded")
REPO = cells.REPO_DIR
CELLS = os.path.join(REPO, "benchmark", "tests", "cells")
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    raw = H.read_xplane(os.path.join(
        RECORDED, "serve-prefill-cached-3steps.xplane.pb"), "jit_step")
    with open(os.path.join(
            RECORDED, "serve-prefill-cached-3steps.expected.json")) as f:
        return raw, json.load(f)


def test_recorded_trace_spans_runs_and_window(recorded):
    raw, want = recorded
    assert raw["window_ns"] == want["window_ns"]
    assert raw["runs"] == want["runs"] and len(raw["runs"]) == 3
    assert {n: len(v) for n, v in raw["spans"].items()} \
        == want["span_count"]
    assert set(raw["spans"]) >= {H.PUMP, H.ADMIT, H.EVICT, H.BUILD_ROWS,
                                 H.DISPATCH, H.FETCH, H.COMMIT, H.PUBLISH}
    t0, t1 = raw["window_ns"]
    assert all(t0 <= s < e <= t1 for ivs in raw["spans"].values()
               for s, e in ivs)


def test_recorded_trace_gap_by_span_adds_up(recorded):
    raw, want = recorded
    s = H.summarize(raw)
    assert s["steps"] == 3 and s["gaps"] == 2
    assert s["gap_s"] == pytest.approx(want["gap_s"], abs=1e-12)
    assert s["attributed_s"] == pytest.approx(want["attributed_s"],
                                              abs=1e-12)
    assert s["gap_by_span_s"] == pytest.approx(want["gap_by_span_s"],
                                               abs=1e-12)
    # self times partition the gap exactly
    assert sum(s["gap_by_span_s"].values()) == pytest.approx(s["gap_s"],
                                                             abs=1e-9)
    assert {n: pytest.approx(v, abs=1e-12)
            for n, v in want["span_s"].items()} == s["span_s"]
    # what is not attributed is the end of `fetch`, the bare `pump` and
    # the scheduler's loop between two pumps
    g = s["gap_by_span_s"]
    assert s["attributed_s"] + g[H.FETCH] + g[H.PUMP] + g["(no span)"] \
        == pytest.approx(s["gap_s"], abs=1e-9)
    assert 0.4 < s["attributed_s"] / s["gap_s"] < 0.6


def test_a_trace_without_spans_reads_as_nothing():
    """PR 22's recording predates the spans, as the parent commit does."""
    assert H.read_xplane(os.path.join(
        RECORDED, "serve-decode-3steps.xplane.pb"), "jit_step") is None


def _raw(spans, runs, window=(0, 100 * MS)):
    return {"window_ns": list(window), "runs": runs,
            "spans": {k: sorted(v) for k, v in spans.items()}}


def test_summarize_splits_a_gap_by_self_time():
    # runs [0,10] [30,40] [50,60] ms: gaps [10,30] and [40,50]
    runs = [[0, 10 * MS], [30 * MS, 40 * MS], [50 * MS, 60 * MS]]
    spans = {
        H.PUMP: [[5 * MS, 28 * MS], [29 * MS, 49 * MS]],
        H.FETCH: [[5 * MS, 12 * MS], [31 * MS, 41 * MS]],
        H.COMMIT: [[12 * MS, 16 * MS], [41 * MS, 44 * MS]],
        H.ADMIT: [[17 * MS, 25 * MS]],
        H.EVICT: [[18 * MS, 24 * MS]],
        H.DISPATCH: [[25 * MS, 28 * MS], [44 * MS, 49 * MS]],
    }
    s = H.summarize(_raw(spans, runs))
    assert s["steps"] == 3 and s["steps_from"] == "device runs"
    assert s["gap_s"] == pytest.approx(0.030)
    g = s["gap_by_span_s"]
    assert g[H.FETCH] == pytest.approx(0.003)        # 10-12, 40-41
    assert g[H.COMMIT] == pytest.approx(0.007)
    assert g[H.EVICT] == pytest.approx(0.006)
    assert g[H.ADMIT] == pytest.approx(0.002)        # 8 less its evict's 6
    assert g[H.DISPATCH] == pytest.approx(0.008)
    assert g[H.PUMP] == pytest.approx(0.002)         # 16-17 and 29-30
    assert g["(no span)"] == pytest.approx(0.002)    # 28-29, 49-50
    assert sum(g.values()) == pytest.approx(s["gap_s"])
    # fetch and the bare pump do not attribute
    assert s["attributed_s"] == pytest.approx(0.023)
    assert s["span_s"][H.ADMIT] == pytest.approx(0.008)


def test_summarize_without_a_device_plane_counts_dispatch_spans():
    spans = {H.PUMP: [[0, 9 * MS], [10 * MS, 19 * MS]],
             H.DISPATCH: [[1 * MS, 2 * MS], [11 * MS, 13 * MS]],
             H.COMMIT: [[5 * MS, 6 * MS]]}
    s = H.summarize(_raw(spans, None))
    assert s["steps"] == 2 and "gap_s" not in s
    assert s["span_s"][H.DISPATCH] == pytest.approx(0.003)


def test_readers_over_the_summary(monkeypatch):
    runs = [[0, 10 * MS], [20 * MS, 30 * MS]]
    spans = {H.PUMP: [[9 * MS, 21 * MS]], H.ADMIT: [[12 * MS, 14 * MS]],
             H.BUILD_ROWS: [[14 * MS, 15 * MS]],
             H.DISPATCH: [[15 * MS, 19 * MS]], H.COMMIT: [[10 * MS, 12 * MS]]}
    monkeypatch.setitem(H._CACHE, "summary",
                        H.summarize(_raw(spans, runs)))
    from benchmark.layer_metrics import (host_admit_ms_per_step,
                                         host_commit_ms_per_step,
                                         host_prepare_ms_per_step,
                                         step_gap_attributed_pct)
    c = {"main_module": "jit_step"}
    assert host_admit_ms_per_step.read(None, c, None) == pytest.approx(1.0)
    assert host_prepare_ms_per_step.read(None, c, None) \
        == pytest.approx(2.5)
    assert host_commit_ms_per_step.read(None, c, None) == pytest.approx(1.0)
    assert step_gap_attributed_pct.read(None, c, None) \
        == pytest.approx(90.0)
    every = (host_admit_ms_per_step, host_prepare_ms_per_step,
             host_commit_ms_per_step, step_gap_attributed_pct)
    # a train cell: spans, but none of the serve loop's; and the parent
    # commit: no span at all. Either way nothing to read, no metric.
    train = {"pdtpu/train/chunk_dispatch": [[1 * MS, 2 * MS]],
             "pdtpu/train/batch_wait": [[12 * MS, 13 * MS]]}
    for cached in (H.summarize(_raw(train, runs)), None):
        monkeypatch.setitem(H._CACHE, "summary", cached)
        for m in every:
            assert m.read(None, c, None) is None
            assert (m.LAYER, m.MOVES, m.SOURCE) == (
                "Serve host loop", "tpot_p50_ms", "program_span")


def _window_trace(path):
    """The reduced trace's two fields `find_xplane` matches a file by."""
    from jax.profiler import ProfileData
    from benchmark.trace.reduce import _window
    return {"annotated": True, "devices": [
        {"window_ns": list(_window(ProfileData.from_file(path)))}]}


def test_find_xplane_takes_this_runs_trace_or_nothing(tmp_path, monkeypatch,
                                                      capsys):
    """One `bench_trace_*` directory is this run's. Among several (a killed
    run's leftover) only the file whose `benchmark_window` is the reduced
    trace's window is read, and with nothing to match by, nothing is."""
    import shutil
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert H.find_xplane() is None
    paths = {}
    for d, src in (("bench_trace_aaa", "serve-prefill-cached-3steps"),
                   ("other", "serve-decode-3steps"),
                   ("bench_trace_bbb", "serve-decode-3steps")):
        run = tmp_path / d / "plugins" / "profile" / "run"
        run.mkdir(parents=True)
        paths[d] = str(run / "host.xplane.pb")
        shutil.copy(os.path.join(RECORDED, src + ".xplane.pb"), paths[d])
        if d == "other":        # not a Window's directory: never counted
            assert H.find_xplane() == paths["bench_trace_aaa"]
    for d in ("bench_trace_aaa", "bench_trace_bbb"):
        assert H.find_xplane(_window_trace(paths[d])) == paths[d]
    assert capsys.readouterr().out == ""
    assert H.find_xplane() is None                       # the CPU: no trace
    stranger = {"annotated": True, "devices": [{"window_ns": [1, 2]}]}
    assert H.find_xplane(stranger) is None
    assert capsys.readouterr().out.count("nothing read") == 2


def test_tiny_serve_spans_cell_reports_host_times_on_the_cpu(tmp_path):
    """End to end through run.py: the program's spans come back from the
    profiler's trace; three per-step times above zero; no device plane,
    so no gap and no attributed share."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "tiny.serve-spans", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--cells-root", CELLS],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {
        "token_efficiency_pct", "host_admit_ms_per_step",
        "host_prepare_ms_per_step", "host_commit_ms_per_step"}
    for name in ("host_admit_ms_per_step", "host_prepare_ms_per_step",
                 "host_commit_ms_per_step"):
        assert line["metrics"][name]["value"] > 0
        assert line["metrics"][name]["unit"] == "ms"
    said = [x for x in lines if x.startswith("host spans:")]
    assert said and "serve/evict" in said[0] and "serve/pump" in said[0]
    assert not os.listdir(tmp_path)          # the window's trace is gone
