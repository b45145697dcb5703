"""The state-space family through the benchmark: the tiny CPU cell
`tiny.serve-ssm` end to end (added as files, like every cell), a perturbed
mixer weight caught by the comparison that decides `correct`, the
family's arithmetic against the published model, `_ssm.py`'s cost function
against bytes counted by hand, and the three state-space readers on counts
(a synthetic reduced trace and the job's counters: no device time is
involved)."""
from types import SimpleNamespace as NS

import pytest

from benchmark import cells, device as D
from benchmark.families import granitemoehybrid as family
from benchmark.layer_metrics import (_moe, _ssm, moe_gmm_held_roofline,
                                     moe_held_share_pct, recurrent_state_gb,
                                     ssm_time_pct, ssm_update_roofline)
from benchmark.tests.test_cells import REPO, _cell_args, _result, _run

CELL = "granite-4.0-h-small.serve-decode"

# run.main() with the reference handed layer 0's state-space mixer with its
# output projection times -40 (a scaled in-projection would not show: the
# gated norm takes the scale out again; and at this width the seeding
# rule's N(0, 0.02) matrices leave a mixer a hundredth of the embedding's
# size in the residual stream, so a mere sign moves the log-probabilities
# by 2e-5, under float32's tolerance)
PERTURBED = """
import sys
sys.path.insert(0, {repo!r})
import benchmark.reference.granitemoehybrid as ref
plain = ref.logits
key = "model.layers.0.mamba.out_proj.weight"
ref.logits = lambda w, ids, cfg: plain({{**w, key: -40 * w[key]}}, ids, cfg)
import benchmark.run as run
sys.exit(run.main({argv!r}))
"""


def test_state_space_cell_end_to_end_and_its_counts():
    proc, lines = _run(_cell_args("tiny.serve-ssm", trace=1))
    line = _result(proc, lines)
    assert line["correct"] is True and line["failed"] == 0
    # the CPU leaves no device plane: the readers of kernel time find
    # nothing, the two counts stay
    assert set(line["metrics"]) == {"token_efficiency_pct",
                                    "recurrent_state_gb"}
    held = line["metrics"]["recurrent_state_gb"]
    # 4 slots x 2 layers x (3 x 160 conv + 16 x 128 state) float32
    assert held["unit"] == "GB" and held["value"] == pytest.approx(
        4 * 2 * (3 * 160 + 16 * 128) * 4 / 1e9)
    assert "'ssm_update/scan'" in proc.stdout
    assert "enable_prefix_cache is switched off" in proc.stdout + proc.stderr


def test_perturbed_mixer_weight_is_caught():
    code = PERTURBED.format(repo=REPO, argv=_cell_args("tiny.serve-ssm"))
    line = _result(*_run(None, code=code))
    assert line["correct"] is False


def test_family_arithmetic_is_granites():
    config = cells.load_cell(CELL)["config_data"]
    assert family.total_params(config) == 3_264_039_552    # this chip's
    full = {**config, "num_hidden_layers": 40, "num_local_experts": 72,
            "layer_types": config["layer_types"] * 4}
    assert round(family.total_params(full) / 1e9, 1) == 32.2
    assert round(family.matmul_params(full) / 1e9, 1) == 8.8     # "A9B"
    assert family.attention_shape(config) == {
        "heads": 32, "kv_heads": 8, "head_dim": 128}
    assert config["layer_types"].count("mamba") == 9
    assert config["num_local_experts_published"] == 72 \
        and config["num_experts_per_tok"] == 10


def test_cost_function_against_bytes_counted_by_hand():
    # one row's state at granite's widths: 128 heads x 64 x 128 channels
    # of bfloat16 = 2 MiB; read once and written once
    flops, bytes_ = _ssm.layer_cost(1, 0, 128, 64, 128)
    assert (flops, bytes_) == (0.0, 2 * 2 * 1024 * 1024)
    # one live position: x in and y out (2 x 8192 bf16), B and C (2 x 128
    # bf16), dt and its decay (2 x 128 float32); 6 operations a state
    # element
    flops, bytes_ = _ssm.layer_cost(0, 1, 128, 64, 128)
    assert bytes_ == 2 * 8192 * 2 + 2 * 128 * 2 + 2 * 128 * 4 == 34_304
    assert flops == 6 * 128 * 64 * 128
    # the decode cell's mean step: 128 active rows, 223 live positions
    flops, bytes_ = _ssm.layer_cost(128, 223, 128, 64, 128)
    assert bytes_ == 128 * 4 * 1024 * 1024 + 223 * 34_304
    peaks = D.load_peaks()["TPU v5 lite"]
    from benchmark import kernel_costs
    assert kernel_costs.min_seconds(flops, bytes_, peaks) \
        == bytes_ / 819e9 > flops / 197e12                # memory-bound
    # a float32 state doubles the state's bytes and nothing else
    assert _ssm.layer_cost(1, 0, 128, 64, 128, state_itemsize=4)[1] \
        == 2 * 4 * 1024 * 1024


def _trace(ssm_s, calls, window_s=2.0):
    ops = {"ssm_update": {"self_ns": int(ssm_s * 1e9), "count": calls,
                          "opcode": "custom-call"},
           # a fusion that merely carries the name is not the kernel
           "fusion_ssm_update": {"self_ns": 10 ** 9, "count": 1,
                                 "opcode": "fusion"}}
    return {"devices": [{"window_ns": [0, int(window_s * 1e9)],
                         "ops": ops}]}


def test_readers_on_counts(monkeypatch):
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    # 10 steps of 9 state-space layers, one call a layer; 250 live
    # positions and 120 active rows a step
    counters = {"steps": 10, "output_tokens": 1000, "prefill_tokens": 1500,
                "active_rows_per_step": 120.0}
    trace = _trace(ssm_s=0.5, calls=90)
    assert ssm_time_pct.read(trace, counters, ctx) == 25.0
    _, bytes_ = _ssm.layer_cost(120.0, 250.0, 128, 64, 128)
    assert ssm_update_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 90 * bytes_ / 819e9 / 0.5)
    assert ssm_update_roofline.read(trace, counters, ctx) < 100
    # nothing to read: no trace, no kernel in it, no steps, no rows, a
    # model without state-space layers
    dense = NS(config={"hidden_size": 8}, peaks=peaks)
    for reader in (ssm_time_pct, ssm_update_roofline):
        assert reader.read(None, counters, ctx) is None
        assert reader.read(_trace(0.0, 0), counters, ctx) is None
    assert ssm_update_roofline.read(trace, {"steps": 0}, ctx) is None
    assert ssm_update_roofline.read(
        trace, {**counters, "active_rows_per_step": None}, ctx) is None
    assert ssm_update_roofline.read(trace, counters, dense) is None

    from paddle_tpu.serving import metrics
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES", 2_474_311_680)
    assert recurrent_state_gb.read(None, counters, ctx) == 2.47431168
    monkeypatch.setattr(metrics, "RECURRENT_STATE_BYTES", 0)   # no such engine
    assert recurrent_state_gb.read(None, counters, ctx) is None
    monkeypatch.delattr(metrics, "RECURRENT_STATE_BYTES")      # no such value:
    assert recurrent_state_gb.read(None, counters, ctx) is None  # parent


def test_share_aware_expert_readers_on_counts(monkeypatch):
    """18 of 72 experts held: the roofline counts the part of a step's
    assignments the program says fell on held experts, not live x top-k."""
    from paddle_tpu.nn.layer import moe
    config = cells.load_cell(CELL)["config_data"]
    peaks = D.load_peaks()["TPU v5 lite"]
    ctx = NS(config=config, peaks=peaks)
    counters = {"steps": 10, "output_tokens": 1000, "prefill_tokens": 1230}
    # 2 layers routed 400 live positions each (x 10 a token); 2,200 of the
    # 8,000 assignments fell on held experts
    monkeypatch.setattr(moe, "ROUTED_TOKENS", {0: 400, 1: 400})
    monkeypatch.setattr(moe, "EXPERT_TOKENS", {(0, 3): 1000, (1, 17): 1200})
    assert moe_held_share_pct.read(None, counters, ctx) \
        == pytest.approx(27.5)
    # 10 steps x 10 layers x 3 calls; 223 live positions a step
    trace = {"devices": [{"window_ns": [0, 2 * 10 ** 9], "ops": {
        "moe_gmm.1": {"self_ns": int(0.1 * 1e9), "count": 300,
                      "opcode": "custom-call"}}}]}
    held = 223 * 10 * 0.275
    assert held > 18                       # every held expert has a row
    bytes_ = 18 * 3 * 4096 * 768 * 2 + 2 * held * 4096 * 2
    assert _moe.layer_cost(held, 18, 4096, 768)[1] == bytes_
    assert moe_gmm_held_roofline.read(trace, counters, ctx) \
        == pytest.approx(100 * 100 * bytes_ / 819e9 / 0.1)
    assert moe_gmm_held_roofline.read(trace, counters, ctx) < 100
    # nothing to read: no trace; a program without the routed count (the
    # parent); a model whose layers hold every expert
    assert moe_gmm_held_roofline.read(None, counters, ctx) is None
    monkeypatch.delattr(moe, "ROUTED_TOKENS")
    assert moe_gmm_held_roofline.read(trace, counters, ctx) is None
    assert moe_held_share_pct.read(None, counters, ctx) is None
    whole = NS(config={"num_experts": 64, "num_experts_per_tok": 8},
               peaks=peaks)
    assert moe_gmm_held_roofline.read(trace, counters, whole) is None
    assert moe_held_share_pct.read(None, counters, whole) is None


def test_controls_come_out_as_they_should():
    """The calibrated job's two faults (the recurrent state wiped after
    every step; the reference from matrices held in the next precision
    down) are not `correct` under the tiny cell's limits, and the sound
    program is."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.jobs.serve_closed_loop_calibrated",
         "--workload", "tiny.serve-ssm", "--seed", "5", "--cells-root",
         "benchmark/tests/cells"], cwd=REPO, capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "'sound': True, 'recurrent state wiped after every step': " \
           "False, 'reference from matrices held in bfloat16': False" \
        in proc.stdout
    assert "6 leaves drawn again" in proc.stdout


def test_a_calibrated_cell_must_bring_its_limits():
    from benchmark import harness
    from benchmark.jobs import serve_closed_loop_calibrated as job
    cell = cells.load_cell("tiny.serve-ssm",
                           REPO + "/benchmark/tests/cells")
    del cell["limits"]
    ctx = harness.Context(cell=cell, seed=1, seconds=1, trace=False,
                          device={"platform": "cpu"}, peaks=None, t_start=0)
    with pytest.raises(cells.CellError, match="limits"):
        job.run(ctx)


def test_parent_program_cannot_build_the_family(monkeypatch):
    """On a program without `models/granitemoehybrid.py` the family fails
    at once and by name (the driver tries a new cell on the parent)."""
    import sys
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.granitemoehybrid",
                        None)
    config = cells.load_cell(CELL)["config_data"]
    with pytest.raises(cells.CellError, match="no models/granitemoehybrid"):
        family.build(config)


def test_real_state_space_cell_without_its_chip_fails_before_the_window():
    proc, lines = _run(["--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "refusing to measure" in proc.stderr
    assert not any(x.startswith("{") for x in lines)
