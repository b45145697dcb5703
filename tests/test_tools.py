"""Benchmark regression gate (tools/check_bench_result.py — the
check_op_benchmark_result.py analog, VERDICT r4 item 10): measured chip rows
gate against pinned per-preset MFU floors; regressions fail, CPU-fallback
rows never gate."""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import check_bench_result as gate  # noqa: E402


def _row(preset, mfu, backend="tpu", err=None):
    if err:
        return {"tag": preset, "error": err}
    return {"metric": f"tokens/sec/chip {preset} bs8 seq1024 bf16",
            "value": 1.0, "extra": {"mfu": mfu, "backend": backend}}


def _write(tmp_path, name, obj):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        json.dump(obj, f)
    return p


def test_gate_passes_within_tolerance(tmp_path, capsys):
    new = _write(tmp_path, "new.json", [_row("gpt3-125m", 0.31)])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    rc = gate.main(["--new", new, "--thresholds", th,
                    "--max-regress", "0.05"])
    assert rc == 0  # 0.31 >= 0.32 * 0.95


def test_gate_fails_on_regression(tmp_path, capsys):
    new = _write(tmp_path, "new.json", [_row("gpt3-125m", 0.25)])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    rc = gate.main(["--new", new, "--thresholds", th,
                    "--max-regress", "0.05"])
    assert rc == 2
    assert "REGRESSION" in capsys.readouterr().out


def test_cpu_fallback_and_error_rows_never_gate(tmp_path):
    new = _write(tmp_path, "new.json", [
        _row("gpt3-125m", 0.01, backend="cpu"),
        _row("gpt3-350m", None, err="hung>900s")])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    rc = gate.main(["--new", new, "--thresholds", th])
    assert rc == 0  # vacuous: no chip rows


def test_gate_takes_best_row_per_preset(tmp_path):
    new = _write(tmp_path, "new.json", [
        _row("gpt3-125m", 0.20), _row("gpt3-125m", 0.33)])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    assert gate.main(["--new", new, "--thresholds", th]) == 0


def test_update_raises_floors_only_upward(tmp_path):
    new = _write(tmp_path, "new.json", [_row("gpt3-125m", 0.30)])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    gate.main(["--new", new, "--thresholds", th, "--update"])
    assert json.load(open(th))["gpt3-125m"]["mfu"] == 0.32  # not lowered
    new2 = _write(tmp_path, "new2.json", [_row("gpt3-125m", 0.40)])
    gate.main(["--new", new2, "--thresholds", th, "--update"])
    assert json.load(open(th))["gpt3-125m"]["mfu"] == 0.40


def test_measured_json_dict_shape_parses(tmp_path):
    new = _write(tmp_path, "m.json", {"results": [
        {"metric": "tokens/sec/chip GPT(gpt3-125m) bs8 seq1024",
         "value": 1.0, "mfu_6nd": 0.3227}]})
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    assert gate.main(["--new", new, "--thresholds", th]) == 0


def test_unmapped_key_warns_loudly(tmp_path, capsys):
    """A measured row whose key matches no pinned floor must shout (the
    gate silently going vacuous was ADVICE r5): warning on stderr, and
    --strict turns it into a failure."""
    new = _write(tmp_path, "new.json", [_row("renamed-preset", 0.30)])
    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    rc = gate.main(["--new", new, "--thresholds", th])
    assert rc == 0
    assert "no pinned floor" in capsys.readouterr().err
    rc = gate.main(["--new", new, "--thresholds", th, "--strict"])
    assert rc == 3


def test_chunked_metric_keys_separately():
    """Scan-fused bench rows ('... chunked32') key as <preset>-chunked so a
    dedicated floor can be pinned for the fused path."""
    row = {"metric": "tokens/sec/chip gpt3-125m bs8 seq1024 bf16 fused "
                     "train step chunked32",
           "value": 1.0, "extra": {"mfu": 0.33, "backend": "tpu"}}
    assert gate._preset_of(row) == "gpt3-125m-chunked"


def test_chunked_row_gates_against_base_floor(tmp_path, capsys):
    """Without its own pinned floor a chunked row gates against the BASE
    preset's floor (scan fusion must never be slower than eager), keeping
    --strict green."""
    def chunked(mfu):
        return {"metric": "tokens/sec/chip gpt3-125m bs8 seq1024 bf16 "
                          "fused train step chunked32",
                "value": 1.0, "extra": {"mfu": mfu, "backend": "tpu"}}

    th = _write(tmp_path, "th.json", {"gpt3-125m": {"mfu": 0.32}})
    new = _write(tmp_path, "new.json", [chunked(0.33)])
    assert gate.main(["--new", new, "--thresholds", th, "--strict"]) == 0

    slow = _write(tmp_path, "slow.json", [chunked(0.10)])
    assert gate.main(["--new", slow, "--thresholds", th, "--strict"]) == 2

    # a dedicated chunked floor, when pinned, wins over the base fallback
    th2 = _write(tmp_path, "th2.json", {
        "gpt3-125m": {"mfu": 0.32}, "gpt3-125m-chunked": {"mfu": 0.05}})
    assert gate.main(["--new", slow, "--thresholds", th2, "--strict"]) == 0


# ---- serving rows (ISSUE 3): direction-aware keys ----

def _serve_row(qps, p99, backend="tpu"):
    return {"metric": "req/sec serve-mlp maxb16 wait2.0ms poisson3000",
            "value": qps, "extra": {"serve_qps": qps, "serve_p99_ms": p99,
                                    "backend": backend}}


def test_serve_qps_gates_as_floor(tmp_path, capsys):
    th = _write(tmp_path, "th.json",
                {"serve-mlp": {"serve_qps": 2000.0}})
    ok = _write(tmp_path, "ok.json", [_serve_row(1950.0, 3.0)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0  # within 5%
    bad = _write(tmp_path, "bad.json", [_serve_row(1500.0, 3.0)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "REGRESSION" in capsys.readouterr().out


def test_serve_p99_gates_as_ceiling(tmp_path, capsys):
    """serve_p99_ms pins a CEILING: tail latency growing past it fails even
    while throughput holds."""
    th = _write(tmp_path, "th.json",
                {"serve-mlp": {"serve_qps": 2000.0, "serve_p99_ms": 3.0}})
    ok = _write(tmp_path, "ok.json", [_serve_row(2100.0, 3.1)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0  # 3.1 <= 3.0 * 1.05
    bad = _write(tmp_path, "bad.json", [_serve_row(2100.0, 4.5)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "serve_p99_ms" in capsys.readouterr().out


def test_update_tightens_serving_keys_favorably_only(tmp_path):
    """--update raises the qps floor and LOWERS the p99 ceiling; it never
    loosens either direction."""
    th = _write(tmp_path, "th.json",
                {"serve-mlp": {"serve_qps": 2000.0, "serve_p99_ms": 3.0}})
    worse = _write(tmp_path, "worse.json", [_serve_row(1800.0, 4.0)])
    gate.main(["--new", worse, "--thresholds", th, "--update"])
    pinned = json.load(open(th))["serve-mlp"]
    assert pinned == {"serve_qps": 2000.0, "serve_p99_ms": 3.0}  # unchanged
    better = _write(tmp_path, "better.json", [_serve_row(2400.0, 2.2)])
    gate.main(["--new", better, "--thresholds", th, "--update"])
    pinned = json.load(open(th))["serve-mlp"]
    assert pinned == {"serve_qps": 2400.0, "serve_p99_ms": 2.2}


# ---- comm rows (ISSUE 4): bytes-on-wire and latency ceilings ----

def _comm_row(bytes_q, ms, backend="tpu"):
    return {"metric": "bytes/step comm-allreduce n4194304 w8 block256 "
                      "int8-rs-ag",
            "value": bytes_q, "tag": "comm-allreduce",
            "extra": {"comm_bytes_per_step": bytes_q,
                      "comm_bytes_fp32": 4 * bytes_q,
                      "allreduce_ms": ms, "backend": backend}}


def test_comm_row_keys_by_metric_tag():
    assert gate._preset_of(_comm_row(1000, 1.0)) == "comm-allreduce"


def test_comm_bytes_gates_as_ceiling(tmp_path, capsys):
    """comm_bytes_per_step pins a CEILING: bytes on the wire growing past
    the pinned value (someone fattening the quantized payload) fails."""
    th = _write(tmp_path, "th.json",
                {"comm-allreduce": {"comm_bytes_per_step": 15_000_000.0}})
    ok = _write(tmp_path, "ok.json", [_comm_row(14_800_000, 5.0)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0
    bad = _write(tmp_path, "bad.json", [_comm_row(60_000_000, 5.0)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "comm_bytes_per_step" in capsys.readouterr().out


def test_allreduce_ms_gates_as_ceiling(tmp_path, capsys):
    th = _write(tmp_path, "th.json",
                {"comm-allreduce": {"comm_bytes_per_step": 15_000_000.0,
                                    "allreduce_ms": 5.0}})
    ok = _write(tmp_path, "ok.json", [_comm_row(14_000_000, 5.2)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0  # 5.2 <= 5.0 * 1.05
    bad = _write(tmp_path, "bad.json", [_comm_row(14_000_000, 9.0)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "allreduce_ms" in capsys.readouterr().out


def test_update_tightens_comm_keys_favorably_only(tmp_path):
    """--update only ever LOWERS the comm ceilings (both keys are 'lower'
    direction); a worse measurement never loosens them."""
    th = _write(tmp_path, "th.json",
                {"comm-allreduce": {"comm_bytes_per_step": 15_000_000.0,
                                    "allreduce_ms": 5.0}})
    worse = _write(tmp_path, "worse.json", [_comm_row(20_000_000, 7.0)])
    gate.main(["--new", worse, "--thresholds", th, "--update"])
    pinned = json.load(open(th))["comm-allreduce"]
    assert pinned == {"comm_bytes_per_step": 15_000_000.0,
                      "allreduce_ms": 5.0}
    better = _write(tmp_path, "better.json", [_comm_row(12_000_000, 3.5)])
    gate.main(["--new", better, "--thresholds", th, "--update"])
    pinned = json.load(open(th))["comm-allreduce"]
    assert pinned == {"comm_bytes_per_step": 12_000_000.0,
                      "allreduce_ms": 3.5}


def test_comm_cpu_rows_never_gate(tmp_path):
    th = _write(tmp_path, "th.json",
                {"comm-allreduce": {"comm_bytes_per_step": 15_000_000.0}})
    new = _write(tmp_path, "new.json",
                 [_comm_row(60_000_000, 50.0, backend="cpu")])
    assert gate.main(["--new", new, "--thresholds", th]) == 0


def test_mixed_train_and_serve_rows_gate_independently(tmp_path):
    th = _write(tmp_path, "th.json", {
        "gpt3-125m": {"mfu": 0.32},
        "serve-mlp": {"serve_qps": 2000.0, "serve_p99_ms": 3.0}})
    new = _write(tmp_path, "new.json",
                 [_row("gpt3-125m", 0.33), _serve_row(2100.0, 2.8)])
    assert gate.main(["--new", new, "--thresholds", th, "--strict"]) == 0
    # the serving row regressing must fail even with training green
    new2 = _write(tmp_path, "new2.json",
                  [_row("gpt3-125m", 0.33), _serve_row(900.0, 2.8)])
    assert gate.main(["--new", new2, "--thresholds", th]) == 2


# ---- llm rows (ISSUE 5): decode throughput floor, TTFT ceiling ----

def _llm_row(tok_s, ttft_ms, backend="tpu"):
    return {"metric": "tok/sec llm-gpt2-tiny slots4 poisson50",
            "value": tok_s, "extra": {"llm_tok_s": tok_s,
                                      "llm_ttft_ms": ttft_ms,
                                      "backend": backend}}


def test_llm_row_keys_by_preset():
    assert gate._preset_of(_llm_row(200.0, 5.0)) == "llm-gpt2-tiny"


def test_llm_tok_s_gates_as_floor(tmp_path, capsys):
    """llm_tok_s pins a FLOOR: generated tokens/sec dropping beyond
    --max-regress fails the gate."""
    th = _write(tmp_path, "th.json", {"llm-gpt2-tiny": {"llm_tok_s": 200.0}})
    ok = _write(tmp_path, "ok.json", [_llm_row(195.0, 5.0)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0  # within 5%
    bad = _write(tmp_path, "bad.json", [_llm_row(150.0, 5.0)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "REGRESSION" in capsys.readouterr().out


def test_llm_ttft_gates_as_ceiling(tmp_path, capsys):
    """llm_ttft_ms pins a CEILING: p95 time-to-first-token growing past it
    fails even while decode throughput holds."""
    th = _write(tmp_path, "th.json",
                {"llm-gpt2-tiny": {"llm_tok_s": 200.0, "llm_ttft_ms": 5.0}})
    ok = _write(tmp_path, "ok.json", [_llm_row(210.0, 5.2)])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0  # 5.2 <= 5.0 * 1.05
    bad = _write(tmp_path, "bad.json", [_llm_row(210.0, 8.0)])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "llm_ttft_ms" in capsys.readouterr().out


def test_update_tightens_llm_keys_favorably_only(tmp_path):
    """--update raises the tok/s floor and LOWERS the TTFT ceiling; a worse
    measurement never loosens either."""
    th = _write(tmp_path, "th.json",
                {"llm-gpt2-tiny": {"llm_tok_s": 200.0, "llm_ttft_ms": 5.0}})
    worse = _write(tmp_path, "worse.json", [_llm_row(150.0, 9.0)])
    gate.main(["--new", worse, "--thresholds", th, "--update"])
    assert json.load(open(th))["llm-gpt2-tiny"] == \
        {"llm_tok_s": 200.0, "llm_ttft_ms": 5.0}      # unchanged
    better = _write(tmp_path, "better.json", [_llm_row(260.0, 3.1)])
    gate.main(["--new", better, "--thresholds", th, "--update"])
    assert json.load(open(th))["llm-gpt2-tiny"] == \
        {"llm_tok_s": 260.0, "llm_ttft_ms": 3.1}


def test_llm_cpu_rows_never_gate(tmp_path):
    """`bench.py --llm` on CPU emits backend="cpu" rows: the gate stays
    vacuous-green (chip floors only bind chip rows)."""
    th = _write(tmp_path, "th.json", {"llm-gpt2-tiny": {"llm_tok_s": 200.0}})
    cpu = _write(tmp_path, "cpu.json", [_llm_row(10.0, 50.0, backend="cpu")])
    assert gate.main(["--new", cpu, "--thresholds", th]) == 0

def test_llm_overload_keys_gate_as_ceilings(tmp_path, capsys):
    """ISSUE 6 overload gates: interactive p99 TTFT under the bench's 2x
    overload phase and the shed rate are both CEILINGS — the premium tail
    growing or shedding turning into panic fails the gate."""
    row = _llm_row(210.0, 5.0)
    row["extra"].update({"llm_interactive_ttft_p99_ms": 20.0,
                         "llm_shed_rate": 0.10})
    th = _write(tmp_path, "th.json",
                {"llm-gpt2-tiny": {"llm_interactive_ttft_p99_ms": 25.0,
                                   "llm_shed_rate": 0.20}})
    ok = _write(tmp_path, "ok.json", [row])
    assert gate.main(["--new", ok, "--thresholds", th,
                      "--max-regress", "0.05"]) == 0
    worse = dict(row, extra=dict(row["extra"],
                                 llm_interactive_ttft_p99_ms=40.0))
    bad = _write(tmp_path, "bad.json", [worse])
    assert gate.main(["--new", bad, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "llm_interactive_ttft_p99_ms" in capsys.readouterr().out
    panicking = dict(row, extra=dict(row["extra"], llm_shed_rate=0.50))
    bad2 = _write(tmp_path, "bad2.json", [panicking])
    assert gate.main(["--new", bad2, "--thresholds", th,
                      "--max-regress", "0.05"]) == 2
    assert "llm_shed_rate" in capsys.readouterr().out
