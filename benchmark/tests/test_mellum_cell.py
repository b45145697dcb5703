"""The window/full family through the benchmark: the tiny CPU cell
`tiny.mellum-serve` end to end (added as files, like every cell), a
perturbed weight, a dropped window and a dropped YaRN each caught by the
comparison that decides `correct`, the reference's scores in blocks equal
to `reference/common.py`'s, the family's arithmetic against the published
model, `_window.py`'s cost against bytes counted by hand, the three
window-layer readers on counts (a synthetic reduced trace and the job's
counters: no device time is involved), and the job's controls."""
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import cells, device as D, kernel_costs
from benchmark.families import mellum as family
from benchmark.jobs import serve_closed_loop_long as job
from benchmark.layer_metrics import (_window, kv_pool_window_gb,
                                     paged_window_roofline,
                                     paged_window_time_pct)
from benchmark.reference import common, mellum as ref
from benchmark.tests.test_cells import CELLS, REPO, _cell_args, _result, _run

CELL = "mellum2-12b.serve-mixed-8k"

# run.main() with the reference handed a fault: a perturbed output head; the
# configuration without its window; with the sliding layers' plain rotary
# parameters in the full layers too. The job scores through
# `hidden_and_head`, so that is what is wrapped.
FAULTY = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.mellum as ref
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
    "dropped window": 'cfg = {**cfg, "sliding_window": None}',
    "dropped yarn": 'cfg = {**cfg, "rope_parameters": {**cfg["rope_parameters"]'
                    ', "full_attention": cfg["rope_parameters"]'
                    '["sliding_attention"]}}',
}


def test_window_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.mellum-serve", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the two counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "kv_pool_window_gb"}
    held = line["metrics"]["kv_pool_window_gb"]
    # 4 slots x 6 window layers x (48 ring + 16 pad) columns x 2 KV heads
    # x 16 x float32, K and V
    assert held["unit"] == "GB" and held["value"] == pytest.approx(
        4 * 6 * 64 * 2 * 16 * 4 * 2 / 1e9)
    assert "'paged_window/scan'" in proc.stdout
    assert "'paged_attention/scan'" in proc.stdout
    assert "enable_prefix_cache is switched off" in proc.stdout + proc.stderr
    # the program's counters, a mean step of the window: the keys inside
    # the rows' windows are fewer than the keys resident
    counters = next(__import__("json").loads(x[len("counters: "):])
                    for x in lines if x.startswith("counters: "))
    assert 0 < counters["window_kv_tokens_per_step"] \
        < counters["full_kv_tokens_per_step"]
    assert counters["sliding_window"] == 32
    assert counters["kv_pool_bytes"]["window"] == 4 * 6 * 64 * 2 * 16 * 8
    # the check requests go below, across and round the ring of 48
    assert "prompts [11, 24, 52, 110]" in proc.stdout


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_reference_is_caught(fault):
    code = FAULTY.format(repo=REPO, fault=FAULTS[fault],
                         argv=_cell_args("tiny.mellum-serve"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_scores_in_blocks_are_commons_scores(monkeypatch):
    import jax
    cell = cells.load_cell("tiny.mellum-serve", CELLS)
    config = cell["config_data"]
    model = family.build(config)
    weights = {k: p.data for k, p in model.named_parameters()}
    ids = np.random.default_rng(0).integers(0, 512, (3, 70)).astype(np.int32)
    monkeypatch.setattr(job, "HEAD_BLOCK", 64)      # 207 rows: 4 blocks
    job._score_fn.cache_clear()
    lp, margin = job.blockwise_scores(ref.logits, weights, ids, config)
    lp0, margin0 = common.next_token_scores(ref.logits, weights, ids, config)
    assert lp.shape == lp0.shape == (3, 69)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lp0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(margin), np.asarray(margin0),
                               atol=1e-5)
    # a family whose reference gives no hidden states keeps common's
    from benchmark.reference import llama
    called = []
    monkeypatch.setattr(job, "_scores_whole",
                        lambda *a: called.append(a) or "whole")
    assert job.blockwise_scores(llama.logits, {}, ids, {}) == "whole"
    job._score_fn.cache_clear()
    del jax


def test_family_arithmetic_is_mellum2s():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 2_380_605_696        # this chip's
    full = {**config, "num_hidden_layers": 28, "num_experts": 64}
    assert round(family.total_params(full) / 1e9, 2) == 12.15
    # active: 2.21 B of matmuls a token + the embedding's row = "A2.5B"
    assert round(family.matmul_params(full) / 1e9, 2) == 2.21
    assert family.matmul_params(config) == 16 * (
        21_233_664 + 147_456 + 8 * 6_193_152) + 2304 * 98304
    assert family.attention_shape(config) == {
        "heads": 32, "kv_heads": 4, "head_dim": 128}
    assert config["layer_types"].count("sliding_attention") == 12
    assert config["layer_types"].count("full_attention") == 4
    assert config["num_experts_published"] == 64 \
        and config["num_experts_per_tok"] == 8
    # a slot: 4 full layers of 8,288 + 16 columns, 12 rings of 1,040 + 16,
    # 2 KB a column and layer (K and V, 4 heads of 128, bf16)
    traffic = cells.load_cell(CELL)["traffic_data"]
    per_token = 2 * 4 * 128 * 2
    slot = 4 * (traffic["context_tokens"] + 16) * per_token \
        + 12 * (1024 + 16 + 16) * per_token
    assert round(slot / 1e6, 1) == 94.0
    assert round(traffic["slots"] * slot / 1e9, 2) == 3.01


def test_no_width_differs_from_the_catalog_row():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = cells.load_cell(CELL)["config_data"]
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "layer_types",
                       "mlp_layer_types", "num_experts"}
    assert differs | {"context_tokens", "slots"} == set(config["reduced"])


def test_cost_against_bytes_counted_by_hand():
    config = cells.load_cell(CELL)["config_data"]
    # one step of 32 rows, every one past the window: 32 x 1,024 keys in
    # the windows; a page is 16 columns x 4 KV heads x 128 x bf16 = 16 KB
    # of K and as much of V; a row rounds up by half a page; 364 live
    # queries of 32 heads x 128 read and written once
    counters = {"window_kv_tokens_per_step": 32 * 1024.0,
                "active_rows_per_step": 32.0, "steps": 100,
                "prefill_tokens": 35_400, "output_tokens": 1_000,
                "block_len": 16}
    flops, bytes_ = _window.call_cost(counters, config)
    tokens = 32 * 1024 + 32 * 7.5
    assert bytes_ == 2 * tokens * 4 * 128 * 2 + 2 * 364 * 32 * 128 * 2
    assert bytes_ == pytest.approx(73.6e6, rel=1e-2)
    assert flops == 4.0 * 364 * 1024 * 32 * 128
    peaks = D.load_peaks()["TPU v5 lite"]
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12                  # memory-bound
    assert _window.call_cost({**counters,
                              "window_kv_tokens_per_step": None},
                             config) is None


def _trace(window_s, calls, span_s=2.0):
    ops = {"paged_window.3": {"self_ns": int(window_s * 1e9),
                              "count": calls, "opcode": "custom-call"},
           # the full walk is another kernel, and a fusion that merely
           # carries the name is not the kernel
           "paged_attention": {"self_ns": 7 * 10 ** 8, "count": 40,
                               "opcode": "custom-call"},
           "fusion_paged_window": {"self_ns": 10 ** 9, "count": 1,
                                   "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(span_s * 1e9)], "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    from benchmark.layer_metrics import paged_time_pct
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    counters = {"window_kv_tokens_per_step": 30_000.0,
                "active_rows_per_step": 32.0, "steps": 10,
                "prefill_tokens": 3_540, "output_tokens": 100,
                "block_len": 16}
    # 10 steps of 12 window layers, one call a layer
    trace = _trace(window_s=0.2, calls=120)
    assert paged_window_time_pct.read(trace, counters, ctx) \
        == pytest.approx(10.0)
    # the full walk's reader does not see the windowed walk, nor it the
    # full walk
    assert paged_time_pct.read(trace, counters, ctx) == pytest.approx(35.0)
    _, bytes_ = _window.call_cost(counters, config)
    assert paged_window_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 120 * bytes_ / 819e9 / 0.2)
    assert paged_window_roofline.read(trace, counters, ctx) < 100
    # nothing to read: no trace, no kernel in it, a program or a job that
    # left no count (the parent)
    for reader in (paged_window_time_pct, paged_window_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    counters.pop("window_kv_tokens_per_step")
    assert paged_window_roofline.read(trace, counters, ctx) is None

    from paddle_tpu.serving import metrics
    monkeypatch.setattr(metrics, "KV_POOL_BYTES",
                        {"full": 2_176_843_776, "window": 830_472_192})
    assert kv_pool_window_gb.read(None, counters, ctx) == 0.830472192
    monkeypatch.setattr(metrics, "KV_POOL_BYTES", {})    # no such engine
    assert kv_pool_window_gb.read(None, counters, ctx) is None
    monkeypatch.delattr(metrics, "KV_POOL_BYTES")        # no such value:
    assert kv_pool_window_gb.read(None, counters, ctx) is None  # parent


def test_controls_come_out_as_they_should():
    """The sound program is `correct` under the tiny cell's limits; the
    reference without the window, without YaRN, and from matrices held in
    the next precision down are not."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.serve_closed_loop_long",
         "--workload", "tiny.mellum-serve", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "as they should be: True" in proc.stdout
    assert "'sound': True" in proc.stdout
    assert proc.stdout.count(": False") >= 3
    assert "66 leaves drawn again" in proc.stdout
