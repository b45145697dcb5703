"""The Solar Open 2 family through the benchmark: the tiny CPU cell
`tiny.solar-open2.serve` end to end through `serve-closed-loop-long` (added
as files, like every cell), a perturbed decay rate caught by the comparison
that decides `correct`, the family's arithmetic against the published model,
`_kda.py`'s cost against operations and bytes counted by hand, the two new
readers on counts (a synthetic reduced trace and the job's counters: no
device time is involved), `BENCHMARK.json` against the cell's files (its
entries found by name), the parent's clean refusal, and the cell's
controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import solar_open2 as family
from benchmark.jobs import solar_open2_controls as controls
from benchmark.layer_metrics import (_kda as K, kda_time_pct,
                                     kda_update_roofline,
                                     selective_scan_roofline,
                                     ssm_update_roofline)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "solar-open2-250b.serve-reasoning-2k"
CONFIG = "solar-open2-250b-d4-e40"

# run.main() with the reference handed layer 1's decay rates moved by half a
# nat. The job scores through `hidden_and_head`, so that is what is wrapped.
PERTURBED = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.solar_open2 as ref
plain = ref.hidden_and_head
key = "model.layers.1.kda.A_log"
ref.hidden_and_head = lambda w, ids, cfg: plain(
    {{**w, key: w[key] + 0.5}}, ids, cfg)
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""


def test_solar_open2_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.solar-open2.serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "recurrent_state_gb",
                                    "moe_share_here_pct",
                                    "moe_rows_per_expert"}
    held = line["metrics"]["recurrent_state_gb"]
    # 4 slots x 3 KDA layers x (3 x 192 conv + 16 x 64 state) float32
    assert held["unit"] == "GB" and held["value"] == pytest.approx(
        4 * 3 * (3 * 192 + 16 * 64) * 4 / 1e9)
    # 8 of the router's 16 experts are held: about half the assignments
    assert 35 < line["metrics"]["moe_share_here_pct"]["value"] < 65
    assert "'kda_update/scan': 3" in proc.stdout
    assert "ssm_update" not in proc.stdout
    assert "selective_scan" not in proc.stdout
    assert "enable_prefix_cache is switched off" in proc.stdout + proc.stderr
    assert "program constructor 0.0s" in proc.stdout        # LazyGuard
    assert "74 leaves drawn again" in proc.stdout
    # prompts in chunks, then 12 tokens each decoded through the state
    assert "prompts [5, 9, 17, 30], outputs [12, 12, 12, 12]" in proc.stdout


def test_a_perturbed_decay_is_caught():
    code = PERTURBED.format(repo=REPO,
                            argv=_cell_args("tiny.solar-open2.serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_solar_open2s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 3_308_376_640
    assert sum(family._kda(config)) == 137_740_480
    assert family._attention(config) == 109_051_904
    assert family._ffn(config, 40) == 646_184_960
    assert family._per_kind(config) == (3, 1)
    whole = {**config, "num_hidden_layers": 48, "n_routed_experts": 320,
             "vocab_size": 196608}
    assert family.total_params(whole) == 250_288_089_856
    assert family.attention_shape(config) == {
        "heads": 64, "kv_heads": 8, "head_dim": 128}
    assert family.expert_shape(config) == {
        "hidden": 4096, "width": 1280, "held": 40, "published": 320,
        "per_token": 8, "layers": 4}
    # a slot: 3 layers of a 128 x 8,192 float32 state and 3 bfloat16 conv
    # columns of 24,576; 4 KB of keys and values a token in the GQA layer
    traffic = cells.load_cell(CELL)["traffic_data"]
    slot = 3 * (128 * 8192 * 4 + 3 * 24576 * 2)
    assert slot == 3 * (4_194_304 + 147_456)
    assert round(traffic["slots"] * slot / 1e9, 2) == 3.33
    assert round(traffic["slots"] * (traffic["context_tokens"] + 16)
                 * 4096 / 1e9, 2) == 2.70


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
    assert [layer.kind for layer in model.model.layers] \
        == ["attention", "kda", "kda", "kda"]
    assert named["model.layers.1.kda.A_log"].shape == [64]
    assert named["model.layers.3.kda.conv_weight"].shape == [24576, 4]
    assert named["model.layers.0.self_attn.g_proj.weight"].shape \
        == [4096, 8192]
    assert named["model.layers.2.experts.router_weight"].shape == [4096, 320]
    assert named["model.layers.2.experts.w_down"].shape == [40, 1280, 4096]
    assert named["lm_head.weight"].shape == [4096, 24576]
    assert {str(p.dtype) for p in named.values()} == {"bfloat16"}
    with pytest.raises(cells.CellError, match="serving only"):
        family.build(config, recompute=True)


def test_nothing_differs_from_the_catalog_row_but_what_is_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert list(config["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "context_tokens", "slots"]
    # the published value beside each cut, and the floors of the guide
    assert (config["num_hidden_layers_published"],
            config["n_routed_experts_published"],
            config["vocab_size_published"]) == (48, 320, 196608)
    assert config["num_hidden_layers"] == 4 \
        and config["n_routed_experts"] >= 8 \
        and config["vocab_size"] * 8 >= 196608
    assert {"equations", "kda_use_full_proj", "use_gqa_gate", "router",
            "conv", "shared expert", "state dtype", "storage",
            "initial values"} <= set(config["assumed"])
    assert "eight chips share each layer" in config["deployment"]
    assert set(config["leaf_seeding"]) == {"A_log", "dt_bias", "conv_weight"}


def test_the_traffic_file_is_jamba2s_unchanged():
    traffic = cells.load_cell(CELL)["traffic_data"]
    assert traffic == cells.load_cell(
        "jamba2-3b.serve-reasoning-2k")["traffic_data"]
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
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "serve-reasoning-2k",
        "chips": 1, "why": cell["why"]}
    config = bench["configs"][-1]
    assert config["name"] == CONFIG \
        and config["file"] == f"benchmark/configs/{CONFIG}.json" \
        and config["source"] == cell["config_data"]["source"] \
        and config["reduced"] == list(cell["config_data"]["reduced"])
    assert len(bench["workloads"]) == 16 and len(bench["configs"]) == 11 \
        and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    everywhere = [w["name"] for w in bench["workloads"]]
    for kind, listed in (("end_to_end", cell["end_to_end"]),
                         ("per_layer", cell["layer_metrics"])):
        by_json = [m["name"] for m in bench[kind]
                   if CELL in m.get("workloads", everywhere)]
        assert sorted(by_json) == sorted(listed), kind
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] \
        == ["kda_time_pct", "kda_update_roofline"]
    for name in ("kda_time_pct", "kda_update_roofline"):
        module = cells.metric_module(name)
        m = by_name[name]
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["workloads"] == [CELL]
        assert m["layer"] == "Linear-attention layer"
    assert cell["kernels"] == ["kda_update", "conv_tokens",
                               "paged_attention", "moe_gmm"]
    assert set(cell["limits"]) == {"mean", "max", "margin"}
    assert cell["job"] == "serve-closed-loop-long" \
        and cell["trace_seconds"] >= 20
    assert len(json.dumps(bench, indent=2)) < 64 * 1024


def test_cost_against_operations_and_bytes_counted_by_hand():
    # one row's state at the published widths: 64 heads x 128 x 128 of
    # float32 = 4 MiB, read once and written once
    flops, bytes_ = K.layer_cost(1, 0, 64, 128)
    assert (flops, bytes_) == (0.0, 2 * 4_194_304)
    # one live position: q, k, g and v in and o out (5 x 8,192 bf16), beta
    # (64 bf16); 7 operations a state element
    flops, bytes_ = K.layer_cost(0, 1, 64, 128)
    assert bytes_ == 5 * 8192 * 2 + 64 * 2 == 82_048
    assert flops == 7 * 64 * 128 * 128
    # the cell's mean step: 256 active rows, 300 live positions
    flops, bytes_ = K.layer_cost(256, 300, 64, 128)
    assert bytes_ == 256 * 8_388_608 + 300 * 82_048
    peaks = D.load_peaks()["TPU v5 lite"]
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12                # memory-bound
    # a bfloat16 state halves the state's bytes and nothing else
    assert K.layer_cost(1, 0, 64, 128, state_itemsize=2)[1] == 4_194_304
    config = cells.load_cell(CELL)["config_data"]
    assert K.kda_shape(config) == (64, 128, 24576, 3)
    assert K.kda_layers(config) == 3
    for other in ("granite-4.0-h-small.serve-decode",
                  "jamba2-3b.serve-reasoning-2k", "mistral-7b.serve-decode"):
        assert K.kda_shape(cells.load_cell(other)["config_data"]) is None


def _trace(kda_s, calls, window_s=2.0):
    ops = {"kda_update": {"self_ns": int(kda_s * 1e9), "count": calls,
                          "opcode": "custom-call"},
           # a fusion that merely carries the name is not the kernel, and
           # the other recurrences are not this one
           "fusion_kda_update": {"self_ns": 10 ** 9, "count": 1,
                                 "opcode": "fusion"},
           "ssm_update": {"self_ns": 10 ** 9, "count": 5,
                          "opcode": "custom-call"},
           "selective_scan": {"self_ns": 10 ** 9, "count": 5,
                              "opcode": "custom-call"}}
    return {"devices": [{"window_ns": [0, int(window_s * 1e9)],
                         "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    from paddle_tpu.serving import metrics
    # what the cell's pool holds: 256 slots x 3 layers x (the float32
    # state + three bfloat16 conv columns)
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES",
                        256 * 3 * (4_194_304 + 147_456), raising=False)
    # 10 steps of 3 KDA layers, one call a layer; 300 live positions and
    # 250 active rows a step
    counters = {"steps": 10, "output_tokens": 2500, "prefill_tokens": 500,
                "active_rows_per_step": 250.0, "slots": 256}
    trace = _trace(kda_s=0.5, calls=30)
    assert kda_time_pct.read(trace, counters, ctx) == 25.0
    assert K.state_itemsize(config, 256) == 4.0
    _, bytes_ = K.layer_cost(250.0, 300.0, 64, 128, state_itemsize=4.0)
    assert kda_update_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 30 * bytes_ / 819e9 / 0.5)
    assert 0 < kda_update_roofline.read(trace, counters, ctx) < 100
    # a pool that held the state in bfloat16 would be read at that width
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES",
                        256 * 3 * (2_097_152 + 147_456))
    assert K.state_itemsize(config, 256) == 2.0
    # the state-space layers' readers find nothing to read here
    assert ssm_update_roofline.read(trace, counters, ctx) is None
    assert selective_scan_roofline.read(trace, counters, ctx) is None
    # nothing to read: no trace, no kernel in it (the parent), no steps,
    # no rows, no gauge, a model without KDA layers
    for reader in (kda_time_pct, kda_update_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    assert kda_update_roofline.read(trace, {"steps": 0}, ctx) is None
    assert kda_update_roofline.read(
        trace, {**counters, "active_rows_per_step": None}, ctx) is None
    jamba = cells.load_cell("jamba2-3b.serve-reasoning-2k")
    assert kda_update_roofline.read(
        trace, counters, NS(config=jamba["config_data"], peaks=peaks)) \
        is None
    monkeypatch.delattr(metrics, "RECURRENT_STATE_BYTES")
    assert kda_update_roofline.read(trace, counters, ctx) is None


def test_controls_come_out_as_they_should():
    """Under the tiny cell's limits the sound program is `correct` and
    every faulty reference is not, the bfloat16 state (reported only on
    the chip) included."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.solar_open2_controls",
         "--workload", "tiny.solar-open2.serve", "--seed", "5",
         "--cells-root", "benchmark/tests/cells"], cwd=REPO,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "required readings as they should be: True" in proc.stdout
    verdict = proc.stdout[proc.stdout.index("controls: {"):]
    assert verdict.count(": False") >= 9 and "'sound': True" in verdict
    for fault in controls.faulty_references({}):
        assert f"{fault!r}: False" in verdict \
            or f'"{fault}": False' in verdict, fault
    assert "matrices held in bfloat16': False" in verdict
    assert f"{{{controls.BF16_STATE!r}: True}}" in proc.stdout


def test_parent_program_cannot_build_the_family(monkeypatch):
    """On a program without `models/solar_open2.py` the family fails at
    once and by name (the driver tries a new cell on the parent)."""
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.solar_open2", None)
    config = cells.load_cell(CELL)["config_data"]
    with pytest.raises(cells.CellError, match="no models/solar_open2.py"):
        family.build(config)


def test_real_cell_without_its_chip_fails_before_the_window():
    proc, lines = _run(["--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "refusing to measure" in proc.stderr
    assert not any(x.startswith("{") for x in lines)
