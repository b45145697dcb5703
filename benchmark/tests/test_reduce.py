"""`trace/reduce.py`: the same busy / idle / kernel numbers on the recorded
chip trace as when it was recorded, and the arithmetic of nesting, clipping
and collectives on a synthetic plane."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import cells
from benchmark.trace import reduce as R

RECORDED = os.path.join(cells.BENCH_DIR, "trace", "recorded")


@pytest.fixture(scope="module")
def recorded():
    trace = R.reduce_xplane(os.path.join(
        RECORDED, "serve-decode-3steps.xplane.pb"))
    with open(os.path.join(RECORDED,
                           "serve-decode-3steps.expected.json")) as f:
        return trace, json.load(f)


def test_recorded_trace_window_busy_and_idle(recorded):
    trace, want = recorded
    assert trace["annotated"] is True and len(trace["devices"]) == 1
    assert R.window_s(trace) == pytest.approx(want["window_s"], abs=1e-9)
    assert R.busy_s(trace) == pytest.approx(want["busy_s"], abs=1e-9)
    dev = trace["devices"][0]
    # self times partition the busy time exactly
    assert sum(r["self_ns"] for r in dev["ops"].values()) == dev["busy_ns"]
    assert dev["busy_ns"] == want["sum_self_ns"]
    idle = sum(g["ns"] for g in dev["idle_gaps"].values())
    assert idle + dev["busy_ns"] == dev["window_ns"][1] - dev["window_ns"][0]
    assert R.top_gaps(trace) == [[k, pytest.approx(v)]
                                 for k, v in want["top_gaps"]]


def test_recorded_trace_runs_of_the_unified_step(recorded):
    trace, want = recorded
    runs = R.module_runs(trace, "jit_step")
    assert runs == want["jit_step"]
    assert runs["count"] == 3 and len(runs["gaps_ns"]) == 2
    assert R.median(runs["durations_ns"]) == 118437997
    assert R.module_runs(trace, "jit_no_such") is None


def test_recorded_trace_kernel_time(recorded):
    trace, want = recorded
    seconds, calls = R.op_time_s(trace, "paged_attention",
                                 opcode="custom-call")
    assert calls == want["paged_attention"]["calls"] == 24   # 8 layers x 3
    assert seconds == pytest.approx(want["paged_attention"]["seconds"],
                                    abs=1e-12)
    assert R.op_time_s(trace, "flash_fwd", opcode="custom-call") == (0, 0)
    assert R.top_ops(trace, 5) == [[k, pytest.approx(v)]
                                   for k, v in want["top_ops"]]
    assert R.top_ops(trace, 1)[0][0] == "paged_attention"
    assert len(trace["devices"][0]["ops"]) == want["distinct_ops"]
    assert trace["devices"][0]["collective_exposed_ns"] == 0


@pytest.mark.parametrize("text,want", [
    ("%paged_attention.1 = bf16[8,32,16,128]{3,2,1,0:T(8,128)(2,1)} "
     "custom-call(s32[8,256]{1,0:T(8,128)S(1)} %x)",
     ("paged_attention", "custom-call")),
    ("%jvp_flash_fwd_.21 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)}, "
     "f32[64,1,2048]{2,1,0:T(1,128)}) custom-call(bf16[64,2048,128]{2,1,0} "
     "%a)", ("jvp_flash_fwd_", "custom-call")),
    ("%fusion.778 = bf16[50304,2048]{1,0:T(8,128)(2,1)} fusion(s32[8192]{0} "
     "%g), kind=kCustom, calls=%fused_computation.106",
     ("fusion kCustom bf16[50304,2048]", "fusion")),
    ("%while.99 = (s32[]{:T(128)}, bf16[2048]{0}) while((s32[]) %t), "
     "condition=%c, body=%b", ("while", "while")),
    ("%all-gather-done.3 = bf16[2048,8192]{1,0} all-gather-done(%s)",
     ("all-gather-done", "all-gather-done")),
    ("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add",
     ("all-reduce", "all-reduce")),
    ("not an instruction", ("not an instruction", "")),
])
def test_parse_op(text, want):
    assert R.parse_op(text) == want


def test_collectives_and_modules_by_name():
    assert R.is_collective("all-gather-done", "all-gather-done")
    assert R.is_collective("reduce-scatter", "fusion")
    assert not R.is_collective("fusion kLoop f32[8]", "fusion")
    assert R.module_name("jit_step(6892548630688767988)") == "jit_step"
    assert R.module_name("plain") == "plain"


def _plane(**lines):
    return NS(name="/device:TPU:0", lines=[
        NS(name=name.replace("_", " "), events=[
            NS(start_ns=s, duration_ns=d, name=n) for s, d, n in events])
        for name, events in lines.items()])


def test_nesting_clipping_gaps_and_exposed_collectives():
    """window [100, 1100): a while [100, 600) holding a matmul [150, 350)
    and an all-reduce [400, 500); then idle; a second run whose op
    [900, 1200) is clipped at the window's end."""
    mm = "%convolution_fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput"
    plane = _plane(
        XLA_Modules=[(100, 500, "jit_chunk_step(1)"),
                     (900, 150, "jit_chunk_step(1)")],
        XLA_Ops=[(100, 500, "%while.1 = (s32[]{:T(128)}) while((s32[]) %t)"),
                 (150, 200, mm),
                 (400, 100, "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %g)"),
                 (900, 300, mm)],
        Async_XLA_Ops=[(300, 150, "%all-gather-start.1 = (bf16[4]{0}, "
                                  "bf16[8]{0}) all-gather-start(%p)")])
    dev = R._reduce_device(plane, (100, 1100))
    assert dev["busy_ns"] == 500 + 200
    assert dev["ops"]["while"]["self_ns"] == 500 - 200 - 100
    assert dev["ops"]["convolution_fusion"] == {
        "self_ns": 200 + 200, "count": 2, "opcode": "fusion"}
    assert dev["collective_exposed_ns"] == 100
    assert dev["collective_in_flight_ns"] == 150
    assert dev["idle_gaps"] == {
        "between jit_chunk_step runs": {"ns": 300, "count": 1}}
    runs = dev["modules"]["jit_chunk_step"]
    assert runs["count"] == 2 and runs["gaps_ns"] == [300]
    trace = {"devices": [dev, dev], "annotated": True}
    assert R.busy_s(trace) == pytest.approx(700e-9)
    assert R.window_s(trace) == pytest.approx(1000e-9)
    assert R.op_time_s(trace, "convolution") == (pytest.approx(400e-9), 2)


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    """What a CPU run leaves: the readers then return nothing."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = R.find_xplane(str(tmp_path))
    assert path is not None and R.reduce_xplane(path) is None
    assert R.median([]) is None and R.median([3, 1, 2]) == 2
    assert R.median([1, 2, 3, 4]) == 2.5
