"""The sparse-expert family through the benchmark: the tiny CPU cell
`tiny.serve-moe` end to end (added as files, like every cell), a perturbed
router weight caught by the comparison that decides `correct`, and the
three expert-layer readers on counts (a synthetic reduced trace and the
job's counters: no device time is involved)."""
import json
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D
from benchmark.families import olmoe
from benchmark.layer_metrics import (_moe, moe_gmm_roofline,
                                     moe_gmm_time_pct,
                                     moe_load_max_over_mean)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

# run.main() with the reference handed layer 0's router negated: it then
# chooses other experts than the program does
PERTURBED = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.olmoe as ref
plain = ref.logits
key = "llama.layers.0.mlp.router_weight"
ref.logits = lambda w, ids, cfg: plain({{**w, key: -w[key]}}, ids, cfg)
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""


def test_sparse_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.serve-moe", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the two readers of kernel time find
    # nothing, the two counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "moe_load_max_over_mean"}
    skew = line["metrics"]["moe_load_max_over_mean"]
    assert skew["unit"] == "ratio" and 1.0 <= skew["value"] < 8 / 2
    assert "'moe_gmm/ragged_dot'" in proc.stdout


def test_perturbed_router_weight_is_caught():
    code = PERTURBED.format(repo=REPO, argv=_cell_args("tiny.serve-moe"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_olmoes():
    config = cells.load_cell("olmoe-1b-7b.serve-decode")["config_data"]
    assert olmoe.total_params(config) == 3_562_604_544       # 8 layers
    full = {**config, "num_hidden_layers": 16}
    assert round(olmoe.total_params(full) / 1e9, 2) == 6.92
    # active: what a token multiplies against, plus its embedding row
    active = olmoe.matmul_params(full) + config["hidden_size"]
    assert 1.15e9 < active < 1.3e9
    assert olmoe.attention_shape(config) == {
        "heads": 16, "kv_heads": 16, "head_dim": 128}


def _trace(gmm_s, calls, window_s=2.0):
    ops = {"moe_gmm": {"self_ns": int(gmm_s * 1e9), "count": calls,
                       "opcode": "custom-call"},
           # a fusion that merely carries the name is not the kernel
           "fusion_moe_gmm": {"self_ns": 10 ** 9, "count": 1,
                              "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(window_s * 1e9)],
                         "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    config = cells.load_cell("olmoe-1b-7b.serve-decode")["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    # 10 steps of 8 layers, three calls a layer; 250 live positions a step
    counters = {"steps": 10, "output_tokens": 1000, "prefill_tokens": 1500}
    trace = _trace(gmm_s=0.5, calls=240)
    assert moe_gmm_time_pct.read(trace, counters, ctx) == 25.0
    assert _moe.assignments_per_step(counters, config) == 2000.0
    flops, bytes_ = _moe.layer_cost(2000.0, 64, 2048, 1024)
    assert flops == 6 * 2048 * 1024 * 2000
    assert bytes_ == 64 * 3 * 2048 * 1024 * 2 + 2 * 2000 * 2048 * 2
    least = 80 * bytes_ / peaks["hbm_bytes_per_s"]        # memory-bound
    assert flops / peaks["bf16_flops_per_s"] < bytes_ / 819e9
    assert moe_gmm_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * least / 0.5)
    # fewer assignments than experts: only the experts that have a row
    assert _moe.layer_cost(8.0, 64, 2048, 1024)[1] \
        == 8 * 3 * 2048 * 1024 * 2 + 2 * 8 * 2048 * 2
    # nothing to read: no trace, no kernel in it, no steps, a dense model
    dense = NS(config={"hidden_size": 8}, peaks=peaks)
    for reader in (moe_gmm_time_pct, moe_gmm_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    assert moe_gmm_roofline.read(trace, {"steps": 0}, ctx) is None
    assert moe_gmm_roofline.read(trace, counters, dense) is None
    assert moe_load_max_over_mean.read(None, counters, dense) is None

    from paddle_tpu.nn.layer import moe
    table = {(layer, e): 10 for layer in range(8) for e in range(64)}
    table[(0, 5)] = 10 + 63 * 10               # layer 0: busiest 640 of 1270
    monkeypatch.setattr(moe, "EXPERT_TOKENS", table)
    want = (640 * 64 / 1270 + 7 * 1.0) / 8
    assert moe_load_max_over_mean.read(None, counters, ctx) \
        == pytest.approx(want)
    del table[(3, 0)]                              # an expert with no row
    assert moe_load_max_over_mean.read(None, counters, ctx) \
        == pytest.approx((640 * 64 / 1270 + 6 + 64 / 63) / 8)
    monkeypatch.setattr(moe, "EXPERT_TOKENS", {})        # nothing counted
    assert moe_load_max_over_mean.read(None, counters, ctx) is None
    monkeypatch.delattr(moe, "EXPERT_TOKENS")            # no such table
    assert moe_load_max_over_mean.read(None, counters, ctx) is None


def test_parent_program_cannot_build_the_family(monkeypatch):
    """On a program whose LlamaConfig has no sparse fields the family
    fails at once and by name (the driver tries a new cell on the parent)."""
    import dataclasses
    from paddle_tpu.models import llama
    Old = dataclasses.make_dataclass(
        "LlamaConfig", [(f.name, f.type, f) for f in dataclasses.fields(
            llama.LlamaConfig) if f.name not in ("num_experts", "qk_norm")])
    monkeypatch.setattr(llama, "LlamaConfig", Old)
    config = cells.load_cell("olmoe-1b-7b.serve-decode")["config_data"]
    with pytest.raises(NotImplementedError, match="no sparse-expert FFN"):
        olmoe.build(config)
    json.dumps(config)


def test_real_sparse_cell_without_its_chip_fails_before_the_window():
    proc, lines = _run(["--workload", "olmoe-1b-7b.serve-decode", "--seed",
                        "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "refusing to measure" in proc.stderr
    assert not any(x.startswith("{") for x in lines)
