"""The learned-sparse-attention family through the benchmark: the tiny CPU
cell `tiny.glm-serve` end to end (added as files, like every cell), faults
in the reference caught by the comparison that decides `correct`, the
family's arithmetic against the published model, `_sparse.py`'s costs
against operations and bytes counted by hand, the new readers on counts (a
synthetic reduced trace and the job's counters: no device time is
involved), `BENCHMARK.json` against the cell's files, the parent's clean
refusal, and the cell's controls."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import glm_moe_dsa as family
from benchmark.jobs import serve_sessions_long as job
from benchmark.layer_metrics import (_sparse, index_score_roofline,
                                     index_score_time_pct,
                                     index_topk_time_pct, kv_pool_index_gb,
                                     paged_sparse_roofline,
                                     paged_sparse_time_pct,
                                     prefix_hit_tokens_pct,
                                     sparse_keys_read_pct)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "glm-5.2.serve-sessions-32k"
CONFIG = "glm-5.2-d5-e8"
NEW = ["paged_sparse_time_pct", "paged_sparse_roofline",
       "index_score_time_pct", "index_score_roofline", "index_topk_time_pct",
       "kv_pool_index_gb", "sparse_keys_read_pct", "prefix_hit_tokens_pct"]

FAULTY = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.glm_moe_dsa as ref
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
    "no selection": 'cfg = {**cfg, "index_topk": 1 << 30}',
    "no sharing": 'cfg = {**cfg, "index_share": False}',
    "no relu": 'cfg = {**cfg, "index_relu": False}',
}


def test_sessions_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.glm-serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the counts stay
    assert set(line["metrics"]) == {
        "token_efficiency_pct", "kv_pool_latent_gb", "kv_pool_index_gb",
        "sparse_keys_read_pct", "prefix_hit_tokens_pct"}
    # 3 slots x (256 + 16) columns x float32: 5 layers of 32 latent + the
    # 8-wide rotary key in 128 lanes, 2 "full" layers of a 16-wide index key
    assert line["metrics"]["kv_pool_latent_gb"]["value"] == pytest.approx(
        3 * 5 * 272 * 160 * 4 / 1e9)
    assert line["metrics"]["kv_pool_index_gb"]["value"] == pytest.approx(
        3 * 2 * 272 * 16 * 4 / 1e9)
    # contexts of 40-250 tokens under a selection of 16
    assert 5 < line["metrics"]["sparse_keys_read_pct"]["value"] < 40
    # a turn hits its session's cached context
    assert line["metrics"]["prefix_hit_tokens_pct"]["value"] > 40
    for kernel in ("paged_sparse", "index_score", "index_topk"):
        assert f"'{kernel}/scan'" in proc.stdout
    assert "program constructor 0.0s" in proc.stdout        # LazyGuard
    counters = next(json.loads(x[len("counters: "):])
                    for x in lines if x.startswith("counters: "))
    # two "full" and three "shared" layers a committed step; the window's
    # edge may fall between a step's two counters
    steps = counters["unified_steps_window"]
    full = counters["index_layers_full_window"]
    assert abs(full - 2 * steps) <= 2
    assert 2 * counters["index_layers_shared_window"] == 3 * full
    assert 0 < counters["sparse_keys_selected_window"] \
        < counters["sparse_keys_resident_window"]
    assert counters["kv_pool_bytes"]["index"] == 3 * 2 * 272 * 16 * 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_reference_is_caught(fault):
    code = FAULTY.format(repo=REPO, fault=FAULTS[fault],
                         argv=_cell_args("tiny.glm-serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_sessions_are_seeded_and_turns_grow():
    traffic = cells.load_cell(CELL)["traffic_data"]
    a = job.histories(traffic, 19_360, 7)
    b = job.histories(traffic, 19_360, 7)
    assert [len(h) for h in a] == [len(h) for h in b] and len(a) == 16
    assert all((x == y).all() for x, y in zip(a, b))
    lengths = sorted(len(h) for h in a)
    # one of each sixteenth of the log-uniform range
    edges = 16_384 * 2 ** (np.arange(17) / 16)
    assert all(lo <= n <= hi + 1 for n, lo, hi
               in zip(lengths, edges[:-1], edges[1:]))
    turns = job.check_turns(traffic, 19_360, 7, a)
    picked = [lengths[i] for i in job.CHECK_SESSIONS]
    new = [len(p) - h for (p, _), h in zip(turns, picked)]
    assert new == [90, 181, 362, 724] and {n for _, n in turns} == {24}
    widths = job.padded_lengths(traffic)
    assert widths == [17_408, 21_504, 27_648, 33_792]
    for seed in (7, 8, 2 ** 31 + 5):          # whatever the seed
        h = job.histories(traffic, 19_360, seed)
        assert all(len(p) + 24 <= w for (p, _), w in zip(
            job.check_turns(traffic, 19_360, seed, h), widths))
    assert job.histories(traffic, 19_360, 8)[0][:8].tolist() \
        != a[0][:8].tolist()


def test_family_arithmetic_is_glm_5_2s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 2_673_557_504        # this chip's
    assert family._attention(config) == 165_019_648
    assert family._indexer(config) == 9_371_648
    assert family._expert(config) == 37_748_736
    whole = {**config, "num_hidden_layers": 78, "first_k_dense_replace": 3,
             "n_routed_experts": 256, "vocab_size": 154_880,
             "indexer_types": ["full"] * 3
             + ["shared", "shared", "shared", "full"] * 18 + ["shared"] * 3}
    assert len(whole["indexer_types"]) == 78
    assert round(family.total_params(whole) / 1e9) == 743     # "~750B"
    assert round(family.matmul_params(whole) / 1e9) == 40     # "A40B"
    assert family.attention_shape(config) == {
        "heads": 64, "kv_heads": 1, "head_dim": 576, "latent": 512,
        "rope": 64, "index_heads": 32, "index_dim": 128, "index_topk": 2048,
        "index_layers": 2}
    assert family.expert_shape(config) == {
        "hidden": 6144, "width": 2048, "held": 8, "published": 256,
        "per_token": 8, "layers": 4}
    # a token: 5 layers of 512 + 128 columns, 2 of 128 more, bf16
    traffic = cells.load_cell(CELL)["traffic_data"]
    token = 5 * (512 + 128) * 2 + 2 * 128 * 2
    assert token == 6_912
    pool = traffic["slots"] * (traffic["context_tokens"] + 16) * token
    assert round(pool / 1e9, 2) == 4.08


def test_the_program_builds_what_the_family_counts():
    """Under LazyGuard the real configuration constructs in a moment and
    holds nothing; its shapes sum to the family's count."""
    from paddle_tpu.core.tensor import Unassigned
    config = cells.load_cell(CELL)["config_data"]
    model = family.build(config)
    named = dict(model.named_parameters())
    assert all(isinstance(p.data, Unassigned) for p in named.values())
    assert sum(int(np.prod(p.shape)) for p in named.values()) \
        == family.total_params(config)
    assert named["model.layers.0.mlp.gate_proj.weight"].shape \
        == [6144, 12288]
    assert named["model.layers.1.mlp.experts.w_gate"].shape \
        == [8, 6144, 2048]
    assert named["model.layers.1.mlp.experts.router_weight"].shape \
        == [6144, 256]
    assert named["model.layers.1.mlp.experts.select_bias"].shape == [256]
    indexed = sorted({k.split(".")[2] for k in named if "indexer" in k})
    assert indexed == ["0", "4"]                    # the "full" layers
    assert named["model.layers.4.self_attn.indexer.wq_b.weight"].shape \
        == [2048, 32 * 128]
    assert {str(p.dtype) for p in named.values()} == {"bfloat16"}
    kinds = [type(e).__name__ for e in model.init_cache(1, 16)]
    assert kinds == ["IndexedLatentKV", "LatentKV", "LatentKV", "LatentKV",
                     "IndexedLatentKV"]
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
    assert differs == {"num_hidden_layers", "first_k_dense_replace",
                       "indexer_types", "mlp_layer_types",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert differs | {"context_tokens", "slots"} == set(config["reduced"])
    assert config["indexer_types"] == row["config"]["indexer_types"][2:7]
    assert config["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7]
    assert {"hadamard rotation", "index key dtype", "index rotary columns",
            "index key norm", "ties"} <= set(config["assumed"])
    assert "32 chips share each layer" in config["deployment"]
    assert "published layers 2-6" in config["deployment"]
    # no key that ends in _dim or _rank, no size of a layer is reduced
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size", "_per_tok"))
                and k != "vocab_size"]


def test_benchmark_json_agrees_with_the_cells_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_cell(CELL)
    # by name, not by place: the next cell is appended behind this one
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "serve-sessions-32k", "chips": 1,
                     "why": cell["why"]}
    assert len(entry["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmark/configs/{CONFIG}.json" \
        and config["source"] == cell["config_data"]["source"] \
        and config["reduced"] == list(cell["config_data"]["reduced"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    everywhere = [w["name"] for w in bench["workloads"]]
    for kind, listed in (("end_to_end", cell["end_to_end"]),
                         ("per_layer", cell["layer_metrics"])):
        by_json = [m["name"] for m in bench[kind]
                   if CELL in m.get("workloads", everywhere)]
        assert sorted(by_json) == sorted(listed), kind
    # appended, never inserted: behind every cell the benchmark had
    older = everywhere[:everywhere.index(CELL)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            assert set(listed[:listed.index(CELL)]) <= set(older)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW and at >= 38
    for m in bench["per_layer"][at:at + len(NEW)]:
        module = cells.metric_module(m["name"])
        assert (module.LAYER, module.UNIT, module.MOVES, module.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["workloads"][0] == CELL
    # the accepted expert-share roofline is not listed here: its cost takes
    # every held expert to have a row, and at an average of ~20 assignments
    # a step over 8 experts (decode-only steps: 4) it reads over 100
    assert "moe_gmm_share_roofline" not in cell["layer_metrics"]
    assert cell["kernels"] == ["paged_sparse", "index_score", "index_topk",
                               "moe_gmm"]
    assert set(cell["limits"]) == {"mean", "max", "margin"}
    # a turn's prefill differs 16-fold, and over six seeds on the chip the
    # median time per output token spread by more than half its bound
    assert cell["end_to_end"] == ["serve_out_tokens_per_s", "setup_s"]


def test_costs_against_operations_and_bytes_counted_by_hand():
    peaks = D.load_peaks()["TPU v5 lite"]
    # a step of 12 decode rows and 4 chunks of 16 at a context of 24,000:
    # 76 live positions, each attending to 2,048 keys
    selected, live = 76 * 2048.0, 76.0
    flops, bytes_ = _sparse.sparse_cost(selected, live, 64, 512, 64)
    assert flops == 2.0 * 76 * 2048 * 64 * (2 * 512 + 64)
    assert bytes_ == 76 * 2048 * 1280 + 76 * 64 * (640 + 512) * 2
    assert bytes_ == pytest.approx(210.4e6, rel=1e-2)
    # a query of 64 heads against a key of 1,280 B: bound by reading it
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12
    # the indexer: every resident key of a live position, 32 heads x 128
    resident, rows = 76 * 24_000.0, 16 * 24_000.0
    flops, bytes_ = _sparse.index_cost(resident, rows, 32, 128)
    assert flops == 2.0 * 76 * 24_000 * 32 * 128
    assert bytes_ == 16 * 24_000 * 256 + 76 * 24_000 * 4
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12


def _trace(sparse_s=0.4, score_s=0.1, topk_s=0.05, steps=10, span_s=2.0):
    def op(seconds, count):
        return {"self_ns": int(seconds * 1e9), "count": count,
                "opcode": "custom-call"}
    ops = {"paged_sparse.2": op(sparse_s, steps * 10),
           "index_score.1": op(score_s, steps * 2),
           "index_topk": op(topk_s, steps * 2),
           # the other walks are other kernels, and a fusion that merely
           # carries a name is not the kernel
           "paged_latent": op(0.7, 40),
           "fusion_index_score": {"self_ns": 10 ** 9, "count": 1,
                                  "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(span_s * 1e9)], "ops": ops,
                         "modules": {"jit_step": {"count": steps}}}]}


def test_readers_on_counts(monkeypatch):
    from benchmark.layer_metrics import paged_latent_time_pct
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    counters = {"main_module": "jit_step", "steps": 10,
                "prefill_tokens": 640, "output_tokens": 120,
                "unified_steps_window": 10,
                "sparse_keys_selected_window": 10 * 76 * 2048,
                "sparse_keys_resident_window": 10 * 76 * 24_000,
                "full_kv_tokens_window": 10 * 16 * 24_000,
                "prefix_hit_tokens_window": 98_000,
                "prefix_lookup_tokens_window": 100_000}
    trace = _trace()
    assert paged_sparse_time_pct.read(trace, counters, ctx) \
        == pytest.approx(20.0)
    assert index_score_time_pct.read(trace, counters, ctx) \
        == pytest.approx(5.0)
    assert index_topk_time_pct.read(trace, counters, ctx) \
        == pytest.approx(2.5)
    # the latent walk's reader does not see the sparse one, nor it that
    assert paged_latent_time_pct.read(trace, counters, ctx) \
        == pytest.approx(35.0)
    _, bytes_ = _sparse.sparse_cost(76 * 2048.0, 76.0, 64, 512, 64)
    assert paged_sparse_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 10 * 5 * bytes_ / 819e9 / 0.4)
    _, bytes_ = _sparse.index_cost(76 * 24_000.0, 16 * 24_000.0, 32, 128)
    assert index_score_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 10 * 2 * bytes_ / 819e9 / 0.1)
    for reader in (paged_sparse_roofline, index_score_roofline):
        assert 0 < reader.read(trace, counters, ctx) < 100
    assert sparse_keys_read_pct.read(None, counters, ctx) \
        == pytest.approx(100 * 2048 / 24_000)
    assert prefix_hit_tokens_pct.read(None, counters, ctx) \
        == pytest.approx(98.0)
    # nothing to read: no trace, no such kernel in it, no counter (a
    # program without them: the parent), a family without an indexer
    for reader in (paged_sparse_time_pct, paged_sparse_roofline,
                   index_score_time_pct, index_score_roofline,
                   index_topk_time_pct):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0.0, 0.0, 0), counters, ctx) is None
    bare = {k: v for k, v in counters.items() if not k.endswith("_window")}
    for reader in (paged_sparse_roofline, index_score_roofline,
                   sparse_keys_read_pct, prefix_hit_tokens_pct):
        assert reader.read(trace, bare, ctx) is None
    axk1 = cells.load_cell("a.x-k1.serve-mixed-8k-nocache")["config_data"]
    assert paged_sparse_roofline.read(
        trace, counters, NS(config=axk1, peaks=peaks)) is None

    from paddle_tpu.serving import metrics
    monkeypatch.setattr(metrics, "KV_POOL_BYTES",
                        {"full": 0, "window": 0, "latent": 3_776_512_000,
                         "index": 302_120_960})
    assert kv_pool_index_gb.read(None, counters, ctx) == 0.30212096
    monkeypatch.setattr(metrics, "KV_POOL_BYTES", {"full": 1, "latent": 2})
    assert kv_pool_index_gb.read(None, counters, ctx) is None
    monkeypatch.delattr(metrics, "KV_POOL_BYTES")        # no such value:
    assert kv_pool_index_gb.read(None, counters, ctx) is None   # parent


def test_a_program_without_the_indexer_refuses_the_cell_cleanly(monkeypatch):
    """What the parent does with the new cell: `CellError` from the
    family's `build`, at once, before anything is built: its
    `DeepseekConfig` has no `indexer_types`."""
    import dataclasses
    from paddle_tpu.models import deepseek
    config = cells.load_cell(CELL)["config_data"]

    @dataclasses.dataclass
    class ParentsConfig:
        vocab_size: int = 1
        hidden_size: int = 1

    monkeypatch.setattr(deepseek, "DeepseekConfig", ParentsConfig)
    with pytest.raises(cells.CellError,
                       match="no .*indexer_types.*cannot build "
                             "glm-5.2-d5-e8"):
        family.build(config)
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.deepseek", None)
    with pytest.raises(cells.CellError, match="cannot build glm-5.2-d5-e8"):
        family.build(config)


def test_controls_come_out_as_they_should():
    """The sound program is `correct` under the tiny cell's limits; the
    reference that attends to every key, that selects in every layer, whose
    scores lack the ReLU or the head weights, without the selection bias
    and from matrices held in the next precision down are not."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.glm_dsa_controls",
         "--workload", "tiny.glm-serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    assert "'sound': True" in proc.stdout
    assert proc.stdout.count(": False") >= 6
