"""The five set-up metrics (PR 34): the tiny cell `tiny.serve-setup` lists
them, its traced run prints them from the program's own set-up ledger, the
parts do not exceed the whole that `setup_s` times from outside, and each
reader returns None against a program without the ledger (the parent
commit, on which the driver runs this PR's benchmark files too)."""
import re

import pytest

from benchmark import cells
from benchmark.tests.test_cells import _cell_args, _result, _run

SETUP = ["setup_trace_lower_s", "setup_compile_load_s",
         "setup_cache_misses", "setup_first_step_s", "setup_import_s"]


def test_traced_run_prints_the_five_and_the_parts_fit_in_the_whole():
    proc, lines = _run(_cell_args("tiny.serve-setup", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"token_efficiency_pct", *SETUP}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    units = {k: v["unit"] for k, v in line["metrics"].items()}
    assert units["setup_cache_misses"] == "programs"
    assert all(units[k] == "s" for k in SETUP if k != "setup_cache_misses")
    setup_s = float(re.search(r"setup_s (\d+\.\d+)", proc.stdout).group(1))
    assert m["setup_trace_lower_s"] > 0 and m["setup_compile_load_s"] > 0
    assert m["setup_import_s"] > 0
    assert m["setup_trace_lower_s"] + m["setup_compile_load_s"] \
        + m["setup_import_s"] <= setup_s
    # the step's first call holds its own trace, lower and build
    assert 0 < m["setup_first_step_s"] < setup_s
    # `_run` takes the compile cache's directory out of the environment,
    # but the checkout's own may be warm: hits or misses, some program was
    # built either way
    assert m["setup_cache_misses"] >= 0


@pytest.mark.parametrize("name", SETUP)
def test_reader_returns_none_without_the_ledger(name, monkeypatch):
    from paddle_tpu.obs import goodput
    module = cells.metric_module(name)
    assert (module.LAYER, module.MOVES, module.SOURCE) == (
        "Set-up", "setup_s", "program_counter")
    counters = {"main_module": "jit_step"}
    fresh = goodput.CompileLedger()
    monkeypatch.setattr(goodput, "_LEDGER", fresh)
    assert module.read(None, counters, None) is None     # never warm
    fresh.add_phase("import", 1.5)
    fresh.add_phase("first_step", 2.5, program="step")
    fresh.on_span(goodput.TRACE_EVENT, 9.0, 9.5, fun_name="step")
    fresh.on_duration(goodput.COMPILE_EVENT, 0.25, fun_name="jit(step)")
    fresh.on_span(goodput.COMPILE_EVENT, 10.0, 10.25, fun_name="jit(step)")
    fresh.freeze()
    want = {"setup_trace_lower_s": 0.5, "setup_compile_load_s": 0.25,
            "setup_cache_misses": 1, "setup_first_step_s": 2.5,
            "setup_import_s": 1.5}[name]
    assert module.read(None, counters, None) == pytest.approx(want)
    # the parent's program: the module is there, the ledger is not
    monkeypatch.delattr(goodput, "compile_ledger")
    assert module.read(None, counters, None) is None
