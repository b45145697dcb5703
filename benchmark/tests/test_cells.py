"""Each job kind end to end on the tiny CPU cells of `tests/cells/` — which
are added the way a later PR adds a cell: as files under workloads/,
configs/ and traffic/, with no edit to a file that is there (they are not in
BENCHMARK.json). Control flow, the final line's keys, `correct` true, and
false when a reference weight is perturbed. A test cell reports counts
only. A real cell without its chip fails before the window.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = os.path.join(REPO, "benchmark", "tests", "cells")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}

# run.main() with one family's reference handed a perturbed output head
PERTURBED = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.{family} as ref
plain = ref.logits
ref.logits = lambda w, ids, cfg: plain(
    {{**w, "lm_head.weight": w["lm_head.weight"] * 1.02}}, ids, cfg)
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""


def _run(argv, devices=1, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices}")
    cmd = ([sys.executable, "-c", code] if code else
           [sys.executable, os.path.join(REPO, "benchmark", "run.py")] + argv)
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    lines = [x for x in proc.stdout.splitlines() if x.strip()]
    return proc, lines


def _cell_args(cell, trace=0, seconds=1.0):
    return ["--workload", cell, "--seed", "5", "--seconds", str(seconds),
            "--trace", str(trace), "--cells-root", CELLS]


def _result(proc, lines):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(lines[-1])
    assert KEYS <= set(line) <= KEYS | {"breakdown"}
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    return line


@pytest.mark.parametrize("cell,chips,wanted", [
    ("tiny.train", 1, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny.train-x4", 4, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("tiny.serve", 1, {"serve_out_tokens_per_s", "ttft_p50_ms",
                       "tpot_p50_ms", "setup_s"}),
    ("tiny.serve-nocache", 1, {"serve_out_tokens_per_s", "ttft_p50_ms",
                               "tpot_p50_ms", "setup_s"}),
])
def test_cell_end_to_end(cell, chips, wanted):
    proc, lines = _run(_cell_args(cell), devices=chips)
    line = _result(proc, lines)
    if cell == "tiny.serve-nocache":   # the mix's engine field was applied
        assert "'enable_prefix_cache': False" in proc.stdout.split(
            "program defaults")[0]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == wanted
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips


@pytest.mark.parametrize("cell,counts", [
    ("tiny.train", set()),
    ("tiny.serve", {"token_efficiency_pct"}),
])
def test_traced_run_on_the_cpu_reports_counts_only(cell, counts):
    """The CPU backend leaves no device plane: every reader of the trace
    finds nothing and its metric is left out; counts stay."""
    line = _result(*_run(_cell_args(cell, trace=1)))
    assert line["correct"] is True
    assert set(line["metrics"]) == counts
    assert "busy_s" not in line["device"]
    for name in counts:
        assert 0 < line["metrics"][name]["value"] <= 100


@pytest.mark.parametrize("cell,family", [("tiny.train", "gpt"),
                                         ("tiny.serve", "llama")])
def test_perturbed_reference_weight_is_caught(cell, family):
    code = PERTURBED.format(repo=REPO, family=family, argv=_cell_args(cell))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


@pytest.mark.parametrize("cell", [
    "gpt3-1.3b.train-seq2048", "gpt3-1.3b.train-zero2-x4",
    "mistral-7b.serve-decode", "mistral-7b.serve-prefill"])
def test_real_cell_without_its_chip_fails_before_the_window(cell):
    """No CPU fallback: non-zero exit, no result line."""
    proc, lines = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "refusing to measure" in proc.stderr
    assert not any(x.startswith("{") for x in lines)


def test_unknown_cell_is_an_error():
    proc, lines = _run(["--workload", "no-such-cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and not lines


@pytest.mark.parametrize("perturbed,code_wanted", [(False, 0), (True, 1)])
def test_check_grads_on_the_tiny_train_cell(perturbed, code_wanted):
    argv = _cell_args("tiny.train") + ["--check-grads"]
    code = PERTURBED.format(repo=REPO, family="gpt", argv=argv) \
        if perturbed else None
    proc, lines = _run(argv, code=code)
    assert proc.returncode == code_wanted, proc.stderr[-2000:]
    assert any("every leaf's gradient" in x for x in lines)
    assert not any(x.startswith("{") for x in lines)


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/."""
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b.serve-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert "not importable" in proc.stderr
