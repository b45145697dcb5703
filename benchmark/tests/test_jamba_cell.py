"""The Jamba family through the benchmark: the tiny CPU cell
`tiny.jamba-serve` end to end through `serve-closed-loop-long` (added as
files, like every cell), a perturbed `A_log` caught by the comparison that
decides `correct`, the family's arithmetic against the published model,
`_selective_scan.py`'s cost against operations and bytes counted by hand,
the two new readers on counts (a synthetic reduced trace and the job's
counters: no device time is involved), `BENCHMARK.json` against the cell's
files (its entries found by name), the parent's clean refusal, and the
cell's controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import jamba as family
from benchmark.layer_metrics import (_selective_scan as S,
                                     selective_scan_roofline,
                                     selective_scan_time_pct,
                                     ssm_update_roofline)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "jamba2-3b.serve-reasoning-2k"
CONFIG = "jamba2-3b"

# run.main() with the reference handed layer 0's decay rates moved by half a
# nat. The job scores through `hidden_and_head`, so that is what is wrapped.
PERTURBED = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.jamba as ref
plain = ref.hidden_and_head
key = "model.layers.0.mamba.A_log"
ref.hidden_and_head = lambda w, ids, cfg: plain(
    {{**w, key: w[key] + 0.5}}, ids, cfg)
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""


def test_jamba_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.jamba-serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the two counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "recurrent_state_gb"}
    held = line["metrics"]["recurrent_state_gb"]
    # 4 slots x 4 Mamba layers x (3 x 128 conv + 16 x 128 state) float32
    assert held["unit"] == "GB" and held["value"] == pytest.approx(
        4 * 4 * (3 * 128 + 16 * 128) * 4 / 1e9)
    assert "'selective_scan/scan': 4" in proc.stdout
    assert "ssm_update" not in proc.stdout
    assert "enable_prefix_cache is switched off" in proc.stdout + proc.stderr
    assert "program constructor 0.0s" in proc.stdout        # LazyGuard
    assert "12 leaves drawn again" in proc.stdout
    # prompts in chunks, then 12 tokens each decoded through the state
    assert "prompts [5, 9, 17, 30], outputs [12, 12, 12, 12]" in proc.stdout


def test_a_perturbed_decay_is_caught():
    code = PERTURBED.format(repo=REPO, argv=_cell_args("tiny.jamba-serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_jamba2_3bs():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 3_029_337_472
    assert sum(family._mamba(config)) == 41_241_792
    assert family._attention(config) == 13_762_560
    assert family._ffn(config) == 62_914_560
    assert family._per_kind(config) == (26, 2)
    assert family.attention_shape(config) == {
        "heads": 20, "kv_heads": 1, "head_dim": 128}
    # a slot: 26 layers of a 16 x 5,120 float32 state and 3 bfloat16 conv
    # columns; 1 KB of keys and values a token in the 2 attention layers
    traffic = cells.load_cell(CELL)["traffic_data"]
    slot = 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert slot == 26 * (327_680 + 30_720)
    assert round(traffic["slots"] * slot / 1e9, 2) == 2.39
    assert round(traffic["slots"] * traffic["context_tokens"] * 1024 / 1e9,
                 2) == 0.67


def test_the_program_builds_what_the_family_counts():
    """Under LazyGuard the real configuration constructs in a moment and
    holds nothing; its shapes sum to the family's count."""
    import numpy as np
    from paddle_tpu.core.tensor import Unassigned
    config = cells.load_cell(CELL)["config_data"]
    model = family.build(config)
    named = dict(model.named_parameters())
    assert all(isinstance(p.data, Unassigned) for p in named.values())
    assert sum(int(np.prod(p.shape)) for p in named.values()) \
        == family.total_params(config)
    assert named["model.layers.0.mamba.A_log"].shape == [5120, 16]
    assert named["model.layers.21.self_attn.v_proj.weight"].shape \
        == [2560, 128]
    assert {str(p.dtype) for p in named.values()} == {"bfloat16"}
    with pytest.raises(cells.CellError, match="serving only"):
        family.build(config, recompute=True)


def test_nothing_differs_from_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == set()
    assert set(config["reduced"]) == {"context_tokens", "slots"}
    assert {"equations", "layer order", "head_dim", "state dtype",
            "state layout", "initial values"} <= set(config["assumed"])
    assert "whole model" in config["deployment"]
    assert set(config["leaf_seeding"]) == {"A_log", "dt_proj.bias",
                                           "conv_weight"}


def test_the_traffic_is_the_issues_letter_for_letter():
    traffic = cells.load_cell(CELL)["traffic_data"]
    assert {k: v for k, v in traffic.items() if k != "doc"} == {
        "kind": "serve-closed-loop", "clients": 256, "slots": 256,
        "context_tokens": 2560, "ramp_seconds": 4.0,
        "prompt_tokens": {"dist": "loguniform", "lo": 64, "hi": 512,
                          "strata": 32},
        "output_tokens": {"dist": "uniform", "lo": 512, "hi": 2048,
                          "strata": 32},
        "prompt_ids": {"dist": "uniform"}, "check_output_tokens": 64}


def test_benchmark_json_agrees_with_the_cells_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_cell(CELL)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-reasoning-2k", "chips": 1,
                     "why": cell["why"]}
    config, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json" \
        and config["source"] == cell["config_data"]["source"] \
        and config["reduced"] == list(cell["config_data"]["reduced"])
    everywhere = [w["name"] for w in bench["workloads"]]
    for kind, listed in (("end_to_end", cell["end_to_end"]),
                         ("per_layer", cell["layer_metrics"])):
        by_json = [m["name"] for m in bench[kind]
                   if CELL in m.get("workloads", everywhere)]
        assert sorted(by_json) == sorted(listed), kind
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("selective_scan_time_pct", "selective_scan_roofline"):
        module = cells.metric_module(name)
        m = by_name[name]
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["workloads"] == [CELL]
        assert m["layer"] == by_name["ssm_update_roofline"]["layer"]
    assert cell["kernels"] == ["selective_scan", "conv_tokens",
                               "paged_attention"]
    assert set(cell["limits"]) == {"mean", "max", "margin"}
    assert cell["job"] == "serve-closed-loop-long" \
        and cell["trace_seconds"] >= 20


def test_cost_against_operations_and_bytes_counted_by_hand():
    # one row's state at Jamba2-3B's widths: 5,120 channels x 16 elements of
    # float32 = 320 KiB, read once and written once; A once a call
    flops, bytes_ = S.layer_cost(1, 0, 5120, 16)
    assert (flops, bytes_) == (0.0, 2 * 327_680 + 327_680)
    # one live position: c and dt in and y out (3 x 5,120 bf16), B and C
    # (2 x 16 bf16); 7 operations a state element
    flops, bytes_ = S.layer_cost(0, 1, 5120, 16)
    assert bytes_ - 327_680 == 3 * 5120 * 2 + 2 * 16 * 2 == 30_784
    assert flops == 7 * 5120 * 16
    # the cell's mean step: 256 active rows, 300 live positions
    flops, bytes_ = S.layer_cost(256, 300, 5120, 16)
    assert bytes_ == 256 * 655_360 + 327_680 + 300 * 30_784
    peaks = D.load_peaks()["TPU v5 lite"]
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12                # memory-bound
    # a bfloat16 state halves the state's bytes and nothing else
    assert S.layer_cost(1, 0, 5120, 16, state_itemsize=2)[1] \
        == 2 * 163_840 + 327_680
    config = cells.load_cell(CELL)["config_data"]
    assert S.mamba_shape(config) == (5120, 16, 3)
    assert S.mamba_layers(config) == 26
    granite = cells.load_cell("granite-4.0-h-small.serve-decode")
    assert S.mamba_shape(granite["config_data"]) is None


def _trace(scan_s, calls, window_s=2.0):
    ops = {"selective_scan": {"self_ns": int(scan_s * 1e9), "count": calls,
                              "opcode": "custom-call"},
           # a fusion that merely carries the name is not the kernel, and
           # the other recurrence is not this one
           "fusion_selective_scan": {"self_ns": 10 ** 9, "count": 1,
                                     "opcode": "fusion"},
           "ssm_update": {"self_ns": 10 ** 9, "count": 5,
                          "opcode": "custom-call"}}
    return {"devices": [{"window_ns": [0, int(window_s * 1e9)],
                         "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    from paddle_tpu.serving import metrics
    # what the cell's pool holds: 256 slots x 26 layers x (the float32
    # state + three bfloat16 conv columns)
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES",
                        256 * 26 * (327_680 + 30_720), raising=False)
    # 10 steps of 26 Mamba layers, one call a layer; 300 live positions and
    # 250 active rows a step
    counters = {"steps": 10, "output_tokens": 2500, "prefill_tokens": 500,
                "active_rows_per_step": 250.0, "slots": 256}
    trace = _trace(scan_s=0.5, calls=260)
    assert selective_scan_time_pct.read(trace, counters, ctx) == 25.0
    assert S.state_itemsize(config, 256) == 4.0
    _, bytes_ = S.layer_cost(250.0, 300.0, 5120, 16, state_itemsize=4.0)
    assert selective_scan_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 260 * bytes_ / 819e9 / 0.5)
    assert 0 < selective_scan_roofline.read(trace, counters, ctx) < 100
    # a pool that held the state in bfloat16 would be read at that width
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES",
                        256 * 26 * (163_840 + 30_720))
    assert S.state_itemsize(config, 256) == 2.0
    # Mamba-2's reader finds nothing to read in this configuration
    assert ssm_update_roofline.read(trace, counters, ctx) is None
    # nothing to read: no trace, no kernel in it, no steps, no rows, no
    # gauge (the parent), a model without Mamba-1 layers
    for reader in (selective_scan_time_pct, selective_scan_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    assert selective_scan_roofline.read(trace, {"steps": 0}, ctx) is None
    assert selective_scan_roofline.read(
        trace, {**counters, "active_rows_per_step": None}, ctx) is None
    granite = cells.load_cell("granite-4.0-h-small.serve-decode")
    assert selective_scan_roofline.read(
        trace, counters, NS(config=granite["config_data"], peaks=peaks)) \
        is None
    monkeypatch.delattr(metrics, "RECURRENT_STATE_BYTES")
    assert selective_scan_roofline.read(trace, counters, ctx) is None


def test_controls_come_out_as_they_should():
    """Under the tiny cell's limits the sound program is `correct` and
    every faulty reference is not: the state wiped at every position, the
    three inner norms left out, a decay a channel, the matrices in the
    precision below; the bfloat16 state is reported beside them."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.jamba_controls", "--workload",
         "tiny.jamba-serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    verdict = proc.stdout[proc.stdout.index("controls: {"):]
    assert verdict.count(": False") == 5 and "'sound': True" in verdict
    for fault in ("wiped after every position", "without dt_layernorm",
                  "a decay a channel", "rounded to bfloat16",
                  "matrices held in bfloat16"):
        assert fault in verdict, fault


def test_parent_program_cannot_build_the_family(monkeypatch):
    """On a program without `models/jamba.py` the family fails at once and
    by name (the driver tries a new cell on the parent)."""
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.jamba", None)
    config = cells.load_cell(CELL)["config_data"]
    with pytest.raises(cells.CellError, match="no models/jamba.py"):
        family.build(config)


def test_real_cell_without_its_chip_fails_before_the_window():
    proc, lines = _run(["--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "refusing to measure" in proc.stderr
    assert not any(x.startswith("{") for x in lines)
