"""The Laguna family through the benchmark: the tiny CPU cell
`tiny.laguna.serve` end to end (added as files, like every cell), a
perturbed weight and each mechanism the model brought (the gate, the half
rotary, the head groups by layer type, the window, the shared expert, the
factor on the gates, the sigmoid) caught by the comparison that decides
`correct`, the real cell's `BENCHMARK.json` entries against its files, the
family's arithmetic against the published model and the catalog's row, the
two new readers on counts (no device time is involved), and the job's
controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark import cells
from benchmark.families import laguna as family
from benchmark.layer_metrics import (moe_load_max_over_mean,
                                     moe_load_max_over_mean_sparse,
                                     moe_rows_per_expert)
from benchmark.tests.test_cells import CELLS, REPO, _cell_args, _result, _run

CELL = "laguna-xs.2.serve-reasoning-2k-s128"

# run.main() with the reference handed a fault. The job scores through
# `hidden_and_head`, so that is what is wrapped.
FAULTY = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.laguna as ref
plain = ref.hidden_and_head
def faulty(w, ids, cfg):
    cfg = ref._whole(cfg)
    {fault}
    return plain(w, ids, cfg)
ref.hidden_and_head = faulty
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""
FAULTS = {
    "perturbed head": 'w = {**w, "lm_head.weight": w["lm_head.weight"] * 1.02}',
    "dropped gate": 'cfg = {**cfg, "gating": False}',
    "whole head turned": 'cfg = {**cfg, "rope_parameters": {'
                         '**cfg["rope_parameters"], "full_attention": {'
                         '**cfg["rope_parameters"]["full_attention"], '
                         '"partial_rotary_factor": 1.0}}}',
    "head groups swapped": 'cfg = {**cfg, "gqa_group": {'
                           '"full_attention": 8, "sliding_attention": 6}}',
    "dropped window": 'cfg = {**cfg, "sliding_window": None}',
    "dropped shared expert": 'cfg = {**cfg, '
                             '"shared_expert_intermediate_size": 0}',
    "dropped scaling factor": 'cfg = {**cfg, '
                              '"moe_routed_scaling_factor": 1.0}',
    "softmax scores": 'cfg = {**cfg, "router_scoring": "softmax"}',
}


def test_cell_end_to_end_and_the_new_readers():
    proc, lines = _run(_cell_args("tiny.laguna.serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the counts stay; `moe_load_max_over_mean` finds no row for
    # the dense layer and says nothing, its `_sparse` sibling reads the four
    assert set(line["metrics"]) == {
        "token_efficiency_pct", "kv_pool_window_gb",
        "moe_load_max_over_mean_sparse", "moe_rows_per_expert"}
    load = line["metrics"]["moe_load_max_over_mean_sparse"]
    assert load["unit"] == "ratio" and 1.0 < load["value"] < 4.0
    counters = next(json.loads(x[len("counters: "):])
                    for x in lines if x.startswith("counters: "))
    rows = line["metrics"]["moe_rows_per_expert"]
    live = (counters["prefill_tokens"] + counters["output_tokens"]) \
        / counters["steps"]
    assert rows["unit"] == "rows" and rows["value"] == pytest.approx(
        live * 4 / 16)
    # 4 slots x 3 window layers x (48 ring + 16 pad) columns x 2 KV heads
    # x 16 x float32, K and V: the heads by layer type change no slab
    assert counters["kv_pool_bytes"] == {
        "full": 4 * 2 * (176 + 16) * 2 * 16 * 8,
        "window": 4 * 3 * 64 * 2 * 16 * 8}
    assert "'paged_window/scan': 3" in proc.stdout
    assert "'paged_attention/scan': 2" in proc.stdout
    assert "enable_prefix_cache is switched off" in proc.stdout + proc.stderr
    assert "prompts [11, 24, 52, 110]" in proc.stdout   # round the ring


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_reference_is_caught(fault):
    code = FAULTY.format(repo=REPO, fault=FAULTS[fault],
                         argv=_cell_args("tiny.laguna.serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_benchmark_json_names_the_cell_and_agrees_with_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "laguna-xs.2-d5",
                     "traffic": "serve-reasoning-2k-s128", "chips": 1,
                     "why": cell["why"]}
    assert bench["workloads"][-1] == entry          # appended, not inserted
    config = next(c for c in bench["configs"]
                  if c["name"] == "laguna-xs.2-d5")
    assert bench["configs"][-1] == config
    assert config["source"] == cell["config_data"]["source"]
    assert config["reduced"] == list(cell["config_data"]["reduced"])
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [CELL])}
    assert listed == set(cell["end_to_end"]) | set(cell["layer_metrics"])
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in new] == ["moe_load_max_over_mean_sparse",
                                        "moe_rows_per_expert"]
    assert bench["per_layer"][-2:] == new
    assert cell["job"] == "serve-closed-loop-long"
    assert cell["kernels"] == ["paged_attention", "paged_window", "moe_gmm"]
    assert "ttft_p50_ms" not in cell["end_to_end"]
    traffic = cell["traffic_data"]
    assert (traffic["clients"], traffic["slots"],
            traffic["context_tokens"]) == (128, 128, 2560)
    assert "engine" not in traffic
    # the mix is serve-reasoning-2k with half the slots, and nothing else
    other = cells.load_cell("jamba2-3b.serve-reasoning-2k")["traffic_data"]
    differs = {k for k in set(traffic) | set(other)
               if traffic.get(k) != other.get(k)}
    assert differs == {"clients", "slots", "doc"}


def test_family_arithmetic_is_laguna_xs2s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 3_869_857_792       # this chip's
    # 205.5 M a side of the vocabulary; layer 0: attention 29.36 M + gate
    # 0.10 M + MLP 50.33 M; a sliding sparse layer: 37.75 M + 0.13 M, the
    # router 0.52 M, the shared expert 3.15 M, 256 experts of 3.146 M
    h = 2048
    assert family._attention(config, 0) == 2 * h * 48 * 128 \
        + 2 * h * 8 * 128 + h * 48 == 29_458_432
    assert family._attention(config, 1) == 2 * h * 64 * 128 \
        + 2 * h * 8 * 128 + h * 64 == 37_879_808
    assert family._ffn(config, 0, 256) == 3 * h * 8192
    assert family._ffn(config, 1, 256) == h * 256 + 257 * 3 * h * 512
    full = {**config, "num_hidden_layers": 40,
            "layer_types": (config["layer_types"][:4] * 10),
            "mlp_layer_types": ["dense"] + ["sparse"] * 39}
    assert round(family.total_params(full) / 1e9, 2) == 33.44
    # active: 2.81 B of matmuls a token + the embedding's row = "A3B"
    assert round(family.matmul_params(full) / 1e9, 2) == 2.81
    assert family.attention_shape(config) == {
        "heads": 48, "kv_heads": 8, "head_dim": 128}
    assert family.expert_shape(config) == {
        "hidden": 2048, "width": 512, "held": 256, "published": 256,
        "per_token": 8, "layers": 4}
    # a slot: 2 full layers of 2,560 + 16 columns, 3 rings of 512 + 16 + 16,
    # 4 KB a column and layer (K and V, 8 heads of 128, bf16)
    traffic = cells.load_cell(CELL)["traffic_data"]
    per_token = 2 * 8 * 128 * 2
    slot = 2 * (traffic["context_tokens"] + 16) * per_token \
        + 3 * (512 + 16 + 16) * per_token
    assert round(traffic["slots"] * slot / 1e9, 2) == 3.56


def test_no_width_differs_from_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert differs | {"context_tokens", "slots"} == set(config["reduced"])
    for key in ("layer_types", "mlp_layer_types"):
        assert config[key] == row["config"][key][:5]
    # the readings the config leaves open are keys of their own, listed
    assert {"gating", "router_scoring", "qk_norm"} <= set(config["assumed"])
    assert config["router_scoring"] == "sigmoid" and not config["qk_norm"]
    assert config["num_experts"] == 256 and config["vocab_size"] == 100352


def test_readers_on_counts(monkeypatch):
    from paddle_tpu.nn.layer import moe
    config = cells.load_cell(CELL)["config_data"]
    ctx = NS(config=config, peaks=None)
    counters = {"steps": 100, "prefill_tokens": 3_000,
                "output_tokens": 12_400}
    # 154 live positions a step x 8 over 256 experts
    assert moe_rows_per_expert.read(None, counters, ctx) \
        == pytest.approx(154 * 8 / 256)
    assert moe_rows_per_expert.read(None, {}, ctx) is None
    # the program's table: row i is the i-th layer that HAS experts
    table = {(layer, e): 10 for layer in range(4) for e in range(256)}
    table[(2, 7)] = 30          # one busy expert in one layer
    monkeypatch.setattr(moe, "EXPERT_TOKENS", table)
    want = (3 * 1.0 + 30 * 256 / (255 * 10 + 30)) / 4
    assert moe_load_max_over_mean_sparse.read(None, counters, ctx) \
        == pytest.approx(want)
    # the reader that asks for a row a layer finds none for the fifth
    assert moe_load_max_over_mean.read(None, counters, ctx) is None
    # a sparse layer that routed nothing, no table, a family without an
    # expert layer, a program without the table (the parent): nothing
    monkeypatch.setattr(moe, "EXPERT_TOKENS",
                        {k: v for k, v in table.items() if k[0] != 3})
    assert moe_load_max_over_mean_sparse.read(None, counters, ctx) is None
    monkeypatch.setattr(moe, "EXPERT_TOKENS", {})
    assert moe_load_max_over_mean_sparse.read(None, counters, ctx) is None
    dense = NS(config=cells.load_cell(
        "mistral-7b.serve-decode")["config_data"], peaks=None)
    for reader in (moe_load_max_over_mean_sparse, moe_rows_per_expert):
        assert reader.read(None, counters, dense) is None
    monkeypatch.delattr(moe, "EXPERT_TOKENS")
    assert moe_load_max_over_mean_sparse.read(None, counters, ctx) is None


def test_controls_come_out_as_they_should():
    """The sound program is `correct` under the tiny cell's limits; the
    eight faulty references and the one from matrices held in the next
    precision down are not."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.laguna_controls",
         "--workload", "tiny.laguna.serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    assert "'sound': True" in proc.stdout
    assert proc.stdout.count(": correct False") == 9
    assert "58 leaves drawn again" in proc.stdout
