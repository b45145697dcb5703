"""The hyper-connected family through the benchmark: the tiny CPU cell
`tiny.xing-serve` end to end (added as files, like every cell), a reference
with one Sinkhorn pass, with static mixing and without the selection bias
each caught by the comparison that decides `correct`, the family's
arithmetic against the published model, `_hyper.py`'s cost against
operations and bytes counted by hand, the two new readers on counts (a
synthetic reduced trace: no device time is involved), `BENCHMARK.json`
against the cell's files (entries found by name), the parent's clean
refusal, and the cell's controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import xing4_0 as family
from benchmark.layer_metrics import (_hyper, hc_roofline, hc_time_pct,
                                     moe_gmm_share_roofline,
                                     paged_latent_roofline)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "xing4.0-29b-a4b.serve-reasoning-2k-nocache"
CONFIG = "xing4.0-29b-a4b-d5"

# run.main() with the reference handed a fault. The job scores through
# `hidden_and_head`, so that is what is wrapped.
FAULTY = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.xing4_0 as ref
plain = ref.hidden_and_head
def faulty(w, ids, cfg):
    {fault}
    return plain(w, ids, cfg)
ref.hidden_and_head = faulty
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""
FAULTS = {
    "one Sinkhorn pass": 'cfg = {**cfg, "hc_sinkhorn_iters": 1}',
    "static mixing": 'cfg = {**cfg, "hc_dynamic": False}',
    "no selection bias": 'w = {k: v * 0 if k.endswith("select_bias") else v '
                         'for k, v in w.items()}',
}


def test_hyper_connected_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.xing-serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing (the two new ones included), the counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "kv_pool_latent_gb"}
    assert "'hc_pre/reference': 6" in proc.stdout       # 3 layers x 2
    assert "'hc_post/reference': 6" in proc.stdout
    assert "'paged_latent/scan'" in proc.stdout
    assert "program constructor 0.0s" in proc.stdout        # LazyGuard
    assert "'enable_prefix_cache': False" in proc.stdout.split(
        "program defaults")[0]
    # the connection's gains and bias and the selection bias are drawn again
    assert "'_hc.alpha': ['uniform', 0.5, 1.5]" in proc.stdout
    counters = next(json.loads(x[len("counters: "):])
                    for x in lines if x.startswith("counters: "))
    assert counters["full_kv_tokens_per_step"] > 0
    assert counters["kv_pool_bytes"]["latent"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_reference_is_caught(fault):
    code = FAULTY.format(repo=REPO, fault=FAULTS[fault],
                         argv=_cell_args("tiny.xing-serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_xing4_0s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 4_047_680_782       # this chip's
    assert family._attention(config) + 768 + 512 == 28_411_136
    assert family._expert(config) == 11_010_048
    assert family.connection_params(config) == 14_336 * 24 + 24 + 3
    whole = {**config, "num_hidden_layers": 40, "first_k_dense_replace": 2}
    assert round(family.total_params(whole) / 1e9, 1) == 29.5    # "29B"
    # a token's share: 4 of 64 experts (the published "A4B" counts the
    # next-token module and leaves the embedding out)
    assert round(family.matmul_params(whole) / 1e9, 1) == 3.9
    assert family.attention_shape(config) == {
        "heads": 32, "kv_heads": 1, "head_dim": 576, "latent": 512,
        "rope": 64}
    assert family.expert_shape(config) == {
        "hidden": 3584, "width": 1024, "held": 64, "published": 64,
        "per_token": 4, "layers": 4}
    # a slot: 5 layers of 2,560 + 16 columns, 512 + 128 columns of bf16
    traffic = cells.load_cell(CELL)["traffic_data"]
    slot = 5 * (traffic["context_tokens"] + 16) * (512 + 128) * 2
    assert round(traffic["slots"] * slot / 1e9, 2) == 4.22


def test_the_program_builds_what_the_family_counts():
    import numpy as np
    from paddle_tpu.core.tensor import Unassigned
    config = cells.load_cell(CELL)["config_data"]
    model = family.build(config)
    named = dict(model.named_parameters())
    assert all(isinstance(p.data, Unassigned) for p in named.values())
    assert sum(int(np.prod(p.shape)) for p in named.values()) \
        == family.total_params(config)
    assert named["model.layers.0.mlp.gate_proj.weight"].shape == [3584, 9216]
    assert named["model.layers.1.mlp.experts.w_gate"].shape \
        == [64, 3584, 1024]
    assert named["model.layers.1.mlp.experts.select_bias"].shape == [64]
    for sub in ("attn_hc", "mlp_hc"):
        assert named[f"model.layers.4.{sub}.phi"].shape == [14336, 24]
        assert named[f"model.layers.4.{sub}.bias"].shape == [24]
        assert named[f"model.layers.4.{sub}.alpha"].shape == [3]
    assert model.config.hc_mult == 4 and model.config.hc_sinkhorn_iters == 20
    assert model.config.select_bias is True
    with pytest.raises(cells.CellError, match="serving only"):
        family.build(config, recompute=True)
    with pytest.raises(cells.CellError, match="every expert is held"):
        family.build({**config, "n_routed_experts_published": 128})


def test_no_width_differs_from_the_catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "first_k_dense_replace",
                       "num_nextn_predict_layers"}
    assert differs | {"context_tokens", "slots"} == set(config["reduced"])
    assert config["n_routed_experts"] == 64 and config["vocab_size"] == 131072
    assert {"hc keys", "hc_eps", "clamp", "copy-in and sum-out",
            "flattened norm", "rotary layout", "topk_method",
            "initial values"} <= set(config["assumed"])
    assert set(config["leaf_seeding"]) == {"_hc.alpha", "_hc.bias",
                                           "select_bias", "w_down"}
    assert "ep_size 1" in config["deployment"]


def test_traffic_is_serve_reasoning_2k_with_the_cache_off():
    new = cells.load_cell(CELL)["traffic_data"]
    old = cells._load(cells.BENCH_DIR, "traffic", "serve-reasoning-2k")
    assert new["engine"] == {"enable_prefix_cache": False}
    assert {k: v for k, v in new.items() if k not in ("doc", "engine")} \
        == {k: v for k, v in old.items() if k != "doc"}
    assert (new["clients"], new["slots"], new["context_tokens"]) \
        == (256, 256, 2560)


def test_benchmark_json_agrees_with_the_cells_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_cell(CELL)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-reasoning-2k-nocache", "chips": 1,
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
    for name in ("hc_time_pct", "hc_roofline"):
        module = cells.metric_module(name)
        m = by_name[name]
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["workloads"] == [CELL] and m["layer"] == "Residual path"
    assert cell["kernels"] == ["paged_latent", "moe_gmm", "hc_pre",
                               "hc_post"]
    assert set(cell["limits"]) == {"mean", "max", "margin"}
    assert cell["trace_seconds"] == 30


def test_cost_against_operations_and_bytes_counted_by_hand():
    # a packed step's 512 positions of 4 streams x 3,584 in bf16: the
    # streams in twice and out once, u out and y in, phi once
    flops, bytes_ = _hyper.connection_cost(512, 4, 3584)
    assert bytes_ == 512 * (3 * 14336 + 2 * 3584) * 2 + 14336 * 24 * 2
    assert bytes_ == pytest.approx(52.07e6, rel=1e-3)
    assert flops == 512 * (2 * 14336 * 24 + 2 * 14336 + 2 * 20 * 3584)
    peaks = D.load_peaks()["TPU v5 lite"]
    # bound by the bytes, thirty times over: 63.6 us a connection, 0.64 ms
    # for a step's ten
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > 20 * flops / 197e12
    assert 10 * bytes_ / 819e9 == pytest.approx(0.636e-3, rel=1e-2)
    # the positions a step computes are the program's: packed to 512
    assert _hyper.computed_positions({"slots": 256, "prefill_chunk": 16}) \
        == 512
    assert _hyper.computed_positions({"slots": 4, "prefill_chunk": 16}) == 64
    assert _hyper.computed_positions({"slots": 256}) is None
    from paddle_tpu.serving.llm import llm_engine
    assert _hyper.MIN_STEP_TOKENS == llm_engine.MIN_STEP_TOKENS
    assert _hyper.streams({"hc_mult": 4, "hidden_size": 3584}) == (4, 3584)
    assert _hyper.streams({"hidden_size": 7168}) is None


def _trace(pre_s, post_s, calls, span_s=2.0):
    ops = {"hc_pre.3": {"self_ns": int(pre_s * 1e9), "count": calls,
                        "opcode": "custom-call"},
           "hc_post.4": {"self_ns": int(post_s * 1e9), "count": calls,
                         "opcode": "custom-call"},
           "paged_latent.2": {"self_ns": 4 * 10 ** 8, "count": 50,
                              "opcode": "custom-call"},
           # a fusion that merely carries the name is not the kernel
           "fusion_hc_pre": {"self_ns": 10 ** 9, "count": 1,
                             "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(span_s * 1e9)], "ops": ops}]}


def test_readers_on_counts():
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    counters = {"slots": 256, "prefill_chunk": 16, "steps": 10}
    # 10 steps of 5 layers, two connections a layer, a call of each kernel
    trace = _trace(pre_s=0.004, post_s=0.006, calls=100)
    assert hc_time_pct.read(trace, counters, ctx) == pytest.approx(0.5)
    _, bytes_ = _hyper.connection_cost(512, 4, 3584)
    assert hc_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 100 * bytes_ / 819e9 / 0.010)
    assert 60 < hc_roofline.read(trace, counters, ctx) < 100
    for reader in (hc_time_pct, hc_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0.0, 0), counters, ctx) is None
    assert hc_roofline.read(trace, {"steps": 10}, ctx) is None
    # a one-stream configuration (the parent on any cell) has nothing to
    # cost, and its trace holds no such kernel
    axk1 = cells.load_cell("a.x-k1.serve-mixed-8k-nocache")["config_data"]
    assert hc_roofline.read(trace, counters,
                            NS(config=axk1, peaks=peaks)) is None
    no_kernels = {"devices": [{"window_ns": [0, 10 ** 9], "ops": {
        "paged_latent": {"self_ns": 1, "count": 1,
                         "opcode": "custom-call"}}}]}
    assert hc_time_pct.read(no_kernels, counters, ctx) is None
    # the accepted readers the cell lists read this family's keys: the
    # latent walk's through `attention_shape`, the grouped matmuls' through
    # `expert_shape` (64 of 64 held)
    latent = {"full_kv_tokens_per_step": 256 * 1400.0,
              "active_rows_per_step": 256.0, "steps": 10,
              "prefill_tokens": 800, "output_tokens": 2480, "block_len": 16}
    assert 0 < paged_latent_roofline.read(trace, latent, ctx) < 100
    assert moe_gmm_share_roofline.expert_shape(config)["held"] == 64


def test_a_program_without_the_streams_refuses_the_cell_cleanly(monkeypatch):
    """What the parent does with the new cell: `CellError` from the
    family's `build`, at once, before anything is built."""
    import dataclasses
    from paddle_tpu.models import deepseek
    config = cells.load_cell(CELL)["config_data"]
    parent = dataclasses.make_dataclass("DeepseekConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(
            deepseek.DeepseekConfig) if not f.name.startswith("hc_")])
    monkeypatch.setattr(deepseek, "DeepseekConfig", parent)
    with pytest.raises(cells.CellError,
                       match="one residual stream.*cannot build " + CONFIG):
        family.build(config)
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.deepseek", None)
    with pytest.raises(cells.CellError, match="cannot build " + CONFIG):
        family.build(config)


def test_controls_come_out_as_they_should():
    """The sound program is `correct` under the tiny cell's limits; the
    reference with one Sinkhorn pass, with static mixing, without H_post's
    factor 2, with one stream, without the selection bias and from matrices
    held in the next precision down are not."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.xing4_0_controls",
         "--workload", "tiny.xing-serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    assert "'sound': True" in proc.stdout
    assert proc.stdout.count(": False") >= 6
