"""The latent-attention family through the benchmark: the tiny CPU cell
`tiny.axk1-serve` end to end (added as files, like every cell), a perturbed
weight, a dropped YaRN and a softmax router each caught by the comparison
that decides `correct`, the family's arithmetic against the published
model, `_latent.py`'s cost against operations and bytes counted by hand,
the new readers on counts (a synthetic reduced trace and the job's
counters: no device time is involved), `BENCHMARK.json` against the cell's
files, the parent's clean refusal, and the cell's controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import axk1 as family
from benchmark.layer_metrics import (_latent, _moe, kv_pool_latent_gb,
                                     moe_gmm_share_roofline,
                                     moe_share_here_pct,
                                     paged_latent_roofline,
                                     paged_latent_time_pct)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "a.x-k1.serve-mixed-8k-nocache"
CONFIG = "a.x-k1-d6-e12"

# run.main() with the reference handed a fault. The job scores through
# `hidden_and_head`, so that is what is wrapped.
FAULTY = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.axk1 as ref
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
    "dropped yarn": 'cfg = {**cfg, "rope_scaling": None}',
    "softmax router": 'cfg = {**cfg, "scoring_func": "softmax"}',
}


def test_latent_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.axk1-serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "kv_pool_latent_gb",
                                    "moe_share_here_pct"}
    held = line["metrics"]["kv_pool_latent_gb"]
    # 4 slots x 3 layers x (176 + 16) columns x (32 latent + the 8-wide
    # rotary key in 128 lanes) x float32
    assert held["unit"] == "GB" and held["value"] == pytest.approx(
        4 * 3 * 192 * 160 * 4 / 1e9)
    # 4 of 16 experts held: one whole group of four, of which two are
    # eligible a token; an even router would send a quarter here
    assert 10 < line["metrics"]["moe_share_here_pct"]["value"] < 45
    assert "'paged_latent/scan'" in proc.stdout
    assert "program constructor 0.0s" in proc.stdout        # LazyGuard
    counters = next(json.loads(x[len("counters: "):])
                    for x in lines if x.startswith("counters: "))
    assert counters["full_kv_tokens_per_step"] > 0
    assert counters["window_kv_tokens_per_step"] == 0
    assert counters["kv_pool_bytes"] == {"full": 0, "window": 0,
                                         "latent": 4 * 3 * 192 * 160 * 4}
    assert "prompts [11, 24, 52, 110]" in proc.stdout


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_reference_is_caught(fault):
    code = FAULTY.format(repo=REPO, fault=FAULTS[fault],
                         argv=_cell_args("tiny.axk1-serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_a_x_k1s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 4_166_294_528        # this chip's
    assert family._attention(config) + 1536 + 512 == 101_124_096
    assert family._expert(config) == 44_040_192
    whole = {**config, "num_hidden_layers": 61, "n_routed_experts": 192,
             "vocab_size": 163_840}
    assert round(family.total_params(whole) / 1e9, 1) == 519.0  # "519B"
    assert round(family.matmul_params(whole) / 1e9, 1) == 31.6
    assert family.attention_shape(config) == {
        "heads": 64, "kv_heads": 1, "head_dim": 576, "latent": 512,
        "rope": 64}
    assert family.expert_shape(config) == {
        "hidden": 7168, "width": 2048, "held": 12, "published": 192,
        "per_token": 8, "layers": 5}
    # a slot: 6 layers of 8,288 + 16 columns, 512 + 128 columns of bf16
    traffic = cells.load_cell(CELL)["traffic_data"]
    slot = 6 * (traffic["context_tokens"] + 16) * (512 + 128) * 2
    assert round(traffic["slots"] * slot / 1e9, 2) == 2.04
    # the same heads as K and V: 35.6 times the latent and its rotary key
    assert 64 * (192 + 128) * 2 / ((512 + 64) * 2) == pytest.approx(35.6,
                                                                   abs=0.05)


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
    assert named["model.layers.0.mlp.gate_proj.weight"].shape \
        == [7168, 18432]
    assert named["model.layers.1.mlp.experts.w_gate"].shape \
        == [12, 7168, 2048]
    assert named["model.layers.1.mlp.experts.router_weight"].shape \
        == [7168, 192]
    assert {str(p.dtype) for p in named.values()} == {"bfloat16"}
    with pytest.raises(cells.CellError, match="serving only"):
        family.build(config, recompute=True)


def test_no_width_differs_from_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs | {"context_tokens", "slots"} == set(config["reduced"])
    assert {"topk_method", "group scores", "rotary layout"} \
        <= set(config["assumed"])
    assert "16 chips share each layer" in config["deployment"]
    assert "12 pipeline stages" in config["deployment"]


def test_traffic_is_serve_mixed_8k_with_the_cache_off():
    new = cells.load_cell(CELL)["traffic_data"]
    old = cells._load(cells.BENCH_DIR, "traffic", "serve-mixed-8k")
    assert new["engine"] == {"enable_prefix_cache": False}
    assert {k: v for k, v in new.items() if k not in ("doc", "engine")} \
        == {k: v for k, v in old.items() if k != "doc"}


def test_benchmark_json_agrees_with_the_cells_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_cell(CELL)
    entry = bench["workloads"][-1]
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-mixed-8k-nocache", "chips": 1,
                     "why": cell["why"]}
    config = bench["configs"][-1]
    assert config["name"] == CONFIG \
        and config["file"] == f"benchmark/configs/{CONFIG}.json" \
        and config["source"] == cell["config_data"]["source"] \
        and config["reduced"] == list(cell["config_data"]["reduced"])
    everywhere = [w["name"] for w in bench["workloads"]]
    for kind, listed in (("end_to_end", cell["end_to_end"]),
                         ("per_layer", cell["layer_metrics"])):
        by_json = [m["name"] for m in bench[kind]
                   if CELL in m.get("workloads", everywhere)]
        assert sorted(by_json) == sorted(listed), kind
    # appended, never inserted: the cell is the last name of every list it
    # is on, and the five new metrics are the last five
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    new = [m["name"] for m in bench["per_layer"][-5:]]
    assert new == ["paged_latent_time_pct", "paged_latent_roofline",
                   "kv_pool_latent_gb", "moe_gmm_share_roofline",
                   "moe_share_here_pct"]
    for m in bench["per_layer"][-5:]:
        module = cells.metric_module(m["name"])
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["workloads"] == [CELL]
    assert cell["kernels"] == ["paged_latent", "moe_gmm"]
    assert set(cell["limits"]) == {"mean", "max", "margin"}


def test_cost_against_operations_and_bytes_counted_by_hand():
    config = cells.load_cell(CELL)["config_data"]
    # one step of 32 rows holding 2,100 keys each: 67,200 resident keys,
    # half a page of 16 a row more; a key is 512 + 64 values of bf16, read
    # once; 364 live queries of 64 heads: 576 values in, 512 out, each
    counters = {"full_kv_tokens_per_step": 32 * 2100.0,
                "active_rows_per_step": 32.0, "steps": 100,
                "prefill_tokens": 35_400, "output_tokens": 1_000,
                "block_len": 16}
    flops, bytes_ = _latent.call_cost(counters, config)
    assert bytes_ == (32 * 2100 + 32 * 7.5) * 576 * 2 \
        + 364 * 64 * (576 + 512) * 2
    assert bytes_ == pytest.approx(128.4e6, rel=1e-2)
    assert flops == 2.0 * 364 * 2100 * 64 * (576 + 512)
    assert flops == pytest.approx(106.5e9, rel=1e-2)
    peaks = D.load_peaks()["TPU v5 lite"]
    # a step that is mostly 16-column chunks is bound by compute ...
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == flops / 197e12 > bytes_ / 819e9
    # ... and one of decode rows alone by reading the pages
    decode = {**counters, "prefill_tokens": 0, "output_tokens": 3_200}
    flops, bytes_ = _latent.call_cost(decode, config)
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12
    # a K/V cache of the same heads would read each key twice, 64 times over
    _, kv = kernel_costs.paged_cost(32 * 2100.0, 32.0, 32.0, 16, 64, 64, 160)
    assert kv > 30 * bytes_
    assert _latent.call_cost({**counters, "full_kv_tokens_per_step": None},
                             config) is None
    # a family without a latent cache has nothing to cost
    mellum = cells.load_cell("mellum2-12b.serve-mixed-8k")["config_data"]
    assert _latent.call_cost(counters, mellum) is None


def _trace(latent_s, calls, gmm_s=0.3, gmm_calls=150, span_s=2.0):
    ops = {"paged_latent.2": {"self_ns": int(latent_s * 1e9), "count": calls,
                              "opcode": "custom-call"},
           "moe_gmm.7": {"self_ns": int(gmm_s * 1e9), "count": gmm_calls,
                         "opcode": "custom-call"},
           # the other walks are other kernels, and a fusion that merely
           # carries the name is not the kernel
           "paged_attention": {"self_ns": 7 * 10 ** 8, "count": 40,
                               "opcode": "custom-call"},
           "fusion_paged_latent": {"self_ns": 10 ** 9, "count": 1,
                                   "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(span_s * 1e9)], "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    from benchmark.layer_metrics import paged_time_pct, paged_window_time_pct
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    counters = {"full_kv_tokens_per_step": 67_200.0,
                "active_rows_per_step": 32.0, "steps": 10,
                "prefill_tokens": 3_540, "output_tokens": 100,
                "block_len": 16}
    # 10 steps of 6 latent layers, one call a layer
    trace = _trace(latent_s=0.2, calls=60)
    assert paged_latent_time_pct.read(trace, counters, ctx) \
        == pytest.approx(10.0)
    # the other walks' readers do not see the latent walk, nor it them
    assert paged_time_pct.read(trace, counters, ctx) == pytest.approx(35.0)
    assert paged_window_time_pct.read(trace, counters, ctx) is None
    flops, _ = _latent.call_cost(counters, config)
    assert paged_latent_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 60 * flops / 197e12 / 0.2)
    assert paged_latent_roofline.read(trace, counters, ctx) < 100
    for reader in (paged_latent_time_pct, paged_latent_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    assert paged_latent_roofline.read(
        trace, {**counters, "full_kv_tokens_per_step": None}, ctx) is None

    from paddle_tpu.serving import metrics
    monkeypatch.setattr(metrics, "KV_POOL_BYTES",
                        {"full": 0, "window": 0, "latent": 2_040_791_040})
    assert kv_pool_latent_gb.read(None, counters, ctx) == 2.04079104
    monkeypatch.setattr(metrics, "KV_POOL_BYTES", {"full": 1, "window": 2})
    assert kv_pool_latent_gb.read(None, counters, ctx) is None
    monkeypatch.delattr(metrics, "KV_POOL_BYTES")        # no such value:
    assert kv_pool_latent_gb.read(None, counters, ctx) is None  # parent

    # the expert share: 5 sparse layers, 364 live positions a step, 8
    # assignments each, 7% of them on the 12 experts held here
    from paddle_tpu.nn.layer import moe
    held = {(layer, e): 700 for layer in range(5) for e in range(12)}
    monkeypatch.setattr(moe, "EXPERT_TOKENS", held)
    monkeypatch.setattr(moe, "ROUTED_TOKENS", {i: 75_000 for i in range(5)})
    assert moe_share_here_pct.read(None, counters, ctx) \
        == pytest.approx(100 * 8_400 / 600_000)
    flops, bytes_ = _moe.layer_cost(364 * 8 * 0.014, 12, 7168, 2048)
    assert bytes_ == pytest.approx(12 * 44_040_192 * 2, rel=2e-3)
    assert moe_gmm_share_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 50 * bytes_ / 819e9 / 0.3)
    assert moe_gmm_share_roofline.read(trace, counters, ctx) < 100
    # nothing to read: no tables (the parent), a family that states no
    # expert shape
    monkeypatch.setattr(moe, "EXPERT_TOKENS", {})
    assert moe_share_here_pct.read(None, counters, ctx) is None
    assert moe_gmm_share_roofline.read(trace, counters, ctx) is None
    mellum = cells.load_cell("mellum2-12b.serve-mixed-8k")["config_data"]
    assert moe_share_here_pct.read(
        None, counters, NS(config=mellum, peaks=peaks)) is None


def test_a_program_without_the_model_refuses_the_cell_cleanly(monkeypatch):
    """What the parent does with the new cell: `CellError` from the
    family's `build`, at once, before anything is built."""
    import paddle_tpu.models
    config = cells.load_cell(CELL)["config_data"]
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.deepseek", None)
    with pytest.raises(cells.CellError, match="cannot build a.x-k1-d6-e12"):
        family.build(config)
    del paddle_tpu


def test_controls_come_out_as_they_should():
    """The sound program is `correct` under the tiny cell's limits; the
    reference without YaRN, with a softmax router, without the group
    limit, without the shared expert, without the rotary key and from
    matrices held in the next precision down are not."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.axk1_controls",
         "--workload", "tiny.axk1-serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    assert "'sound': True" in proc.stdout
    assert proc.stdout.count(": False") >= 6
