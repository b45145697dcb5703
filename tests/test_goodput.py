"""Training goodput ledger (ISSUE 10): phase attribution that tiles the
trainer's wall clock, live MFU from obs.flops' analytic-FLOPs
helpers, the recompile sentinel (jax.monitoring + jit-cache fallback),
HBM telemetry + OOM forensics, and the rollback-storm fault-matrix
scenario proving a faulted run books rollback_waste, drops goodput, and
leaves a black-box dump the postmortem CLI can filter to `train_*`.

Ledger unit tests run on an injected fake clock, so every attribution
number is exact, not approximate."""
import json
import logging
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.obs.goodput import (
    PHASES, GoodputLedger, HBMTelemetry, RecompileSentinel, oom_forensics)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "flight_recorder.py")


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def _tiles(snap, tol=1e-9):
    return abs(sum(snap["phase_seconds"].values())
               - snap["wall_seconds"]) <= tol


# ---- ledger attribution on a fake clock ----

def test_phase_order_matches_exclusive_set():
    assert PHASES == ("compute", "rollback_waste", "data_wait", "h2d",
                      "compile", "checkpoint", "idle")


def test_measure_books_self_time_and_idle_is_residual():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start()
    with led.measure("compute"):
        clk.tick(2.0)
    clk.tick(0.5)                       # unbooked -> idle
    with led.measure("data_wait"):
        clk.tick(0.25)
    snap = led.snapshot()
    assert snap["wall_seconds"] == pytest.approx(2.75)
    assert snap["phase_seconds"]["compute"] == pytest.approx(2.0)
    assert snap["phase_seconds"]["data_wait"] == pytest.approx(0.25)
    assert snap["phase_seconds"]["idle"] == pytest.approx(0.5)
    assert _tiles(snap)
    assert snap["goodput"] == pytest.approx(2.0 / 2.75)


def test_nested_measure_books_only_self_time():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.measure("compute"):        # first measure auto-starts
        clk.tick(1.0)
        with led.measure("h2d"):
            clk.tick(3.0)
        clk.tick(0.5)
    snap = led.snapshot()
    assert snap["phase_seconds"]["compute"] == pytest.approx(1.5)
    assert snap["phase_seconds"]["h2d"] == pytest.approx(3.0)
    assert snap["phase_seconds"]["idle"] == 0.0
    assert _tiles(snap)


def test_book_inside_measure_shrinks_enclosing_frame():
    # the sentinel's compile callback fires while the compute measure is
    # open: compile seconds must come OUT of compute, not double-count
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.measure("compute"):
        clk.tick(2.0)
        led.book("compile", 0.75)
    snap = led.snapshot()
    assert snap["phase_seconds"]["compute"] == pytest.approx(1.25)
    assert snap["phase_seconds"]["compile"] == pytest.approx(0.75)
    assert _tiles(snap)


def test_book_outside_any_measure_still_tiles():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start()
    clk.tick(1.0)
    led.book("checkpoint", 0.4)         # no open frame: plain attribution
    snap = led.snapshot()
    assert snap["phase_seconds"]["checkpoint"] == pytest.approx(0.4)
    assert snap["phase_seconds"]["idle"] == pytest.approx(0.6)
    assert _tiles(snap)


def test_overbooked_clock_clamps_never_negative():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start()
    with led.measure("compute"):
        clk.tick(0.1)
        led.book("compile", 5.0)        # callback over-reports
    snap = led.snapshot()
    assert snap["phase_seconds"]["compute"] == 0.0   # clamped, not -4.9
    assert snap["phase_seconds"]["idle"] == 0.0      # residual clamped too
    assert all(v >= 0.0 for v in snap["phase_seconds"].values())


def test_snapshot_before_start_is_zero():
    led = GoodputLedger(clock=FakeClock())
    snap = led.snapshot()
    assert snap["wall_seconds"] == 0.0 and snap["goodput"] == 0.0
    assert snap["mfu"] is None


def test_mfu_requires_flops_and_productive_steps():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start()
    with led.measure("compute"):
        clk.tick(2.0)
    assert led.snapshot()["mfu"] is None          # no flops registered
    led.set_flops(1e9, 1e12)
    assert led.snapshot()["mfu"] is None          # no productive steps
    led.add_steps(4, productive=False)
    assert led.snapshot()["mfu"] is None          # waste isn't MFU
    led.add_steps(10)
    snap = led.snapshot()
    assert snap["mfu"] == pytest.approx(1e9 * 10 / 2.0 / 1e12)
    assert snap["productive_steps"] == 10 and snap["wasted_steps"] == 4


# ---- recompile sentinel (unit, no jax needed) ----

def test_sentinel_warmup_then_recompiles_and_storm_warning(caplog):
    obs.flight_recorder().clear()
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.start()
    sen = RecompileSentinel(led, storm_threshold=2)
    sen.on_compile(1.5)                 # warmup: counted, not a recompile
    assert sen.compiles == 1 and sen.recompiles == 0
    sen.mark_warm()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.goodput"):
        sen.on_compile(0.5, fun_name="step", trace_s=0.1, lower_s=0.2)
        assert not any("recompile storm" in r.message
                       for r in caplog.records)
        # a load from the persistent cache after warm is a recompile too
        sen.on_compile(0.25, fun_name="step", loaded=True)  # -> warn once
        sen.on_compile(0.25, fun_name="other")
    storms = [r for r in caplog.records if "recompile storm" in r.message]
    assert len(storms) == 1
    assert "step (compiled), step (loaded)" in storms[0].getMessage()
    snap = sen.snapshot()
    assert snap["recompiled"] == sen.recompiled
    del snap["recompiled"]
    assert snap == {"compiles": 3, "loads": 1, "recompiles": 3,
                    "compile_seconds": pytest.approx(2.5)}
    # the names, newest last, for whoever prints the count
    assert [(r["fun_name"], r["how"], r["paid"])
            for r in sen.recompiled] == [
        ("step", "compiled", "trace+lower+backend"),
        ("step", "loaded", "backend"), ("other", "compiled", "backend")]
    # compile seconds booked to the ledger
    assert led.snapshot()["phase_seconds"]["compile"] == pytest.approx(2.5)
    # every post-warm build dropped a flight event that names the program,
    # what it paid and how it was had; the storm one is flagged
    ev = [e for e in obs.flight_recorder().snapshot()["events"]
          if e["kind"] == "train_recompile"]
    assert [e["recompiles"] for e in ev] == [1, 2, 3]
    assert [e["storm"] for e in ev] == [False, True, False]
    assert [e["fun_name"] for e in ev] == ["step", "step", "other"]
    assert [e["how"] for e in ev] == ["compiled", "loaded", "compiled"]
    assert ev[0]["paid"] == "trace+lower+backend"
    assert ev[0]["trace_seconds"] == pytest.approx(0.1)


def test_sentinel_rejects_bad_threshold():
    with pytest.raises(ValueError):
        RecompileSentinel(storm_threshold=0)


def test_sentinel_counts_monitoring_events_compiles_and_loads():
    """The one source (the `jit_cache` fallback went with ISSUE 34): the
    backend event under its own name, as jax fires it. A hit in the
    persistent cache fires it too, after `cache_hits` on the same thread:
    a load, not a compile."""
    import jax.monitoring as mon
    from paddle_tpu.obs.goodput import (CACHE_HIT_EVENT, COMPILE_EVENT,
                                        LOWER_EVENT, TRACE_EVENT)
    sen = RecompileSentinel().install()
    assert sen.installed
    try:
        mon.record_event_duration_secs(COMPILE_EVENT, 0.5,
                                       fun_name="jit(goodput_a)")
        mon.record_event_duration_secs(TRACE_EVENT, 0.1,
                                       fun_name="goodput_a")   # no build
        mon.record_event_duration_secs(COMPILE_EVENT, 0.5,
                                       fun_name="jit(goodput_b)")
        assert (sen.compiles, sen.loads) == (2, 0)
        mon.record_event(CACHE_HIT_EVENT)
        mon.record_event_duration_secs(COMPILE_EVENT, 0.01,
                                       fun_name="jit(goodput_a)")
        assert (sen.compiles, sen.loads) == (2, 1)
        sen.mark_warm()
        mon.record_event_duration_secs(TRACE_EVENT, 0.2,
                                       fun_name="goodput_c")
        mon.record_event_duration_secs(LOWER_EVENT, 0.3,
                                       fun_name="jit(goodput_c)")
        mon.record_event_duration_secs(COMPILE_EVENT, 0.4,
                                       fun_name="jit(goodput_c)")
        assert sen.recompiles == 1
        assert sen.recompiled[-1] == {
            "fun_name": "goodput_c", "how": "compiled",
            "paid": "trace+lower+backend",
            "seconds": pytest.approx(0.9)}
    finally:
        sen.uninstall()
    mon.record_event_duration_secs(COMPILE_EVENT, 0.5,
                                   fun_name="jit(goodput_d)")  # detached
    assert sen.compiles == 3 and not sen.installed


def test_sentinel_install_is_idempotent_and_uninstall_detaches():
    s1 = RecompileSentinel().install()
    assert s1.install() is s1                          # second no-op
    s1.uninstall()
    s1.uninstall()                                     # idempotent


# ---- recompile sentinel against real jax (acceptance criterion) ----

def test_stable_shapes_hold_recompile_count_but_churn_raises_it(caplog):
    import jax
    import jax.numpy as jnp

    obs.flight_recorder().clear()
    sen = RecompileSentinel(storm_threshold=2).install()

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    try:
        f(jnp.ones((4,))).block_until_ready()          # warmup compile
        assert sen.compiles >= 1
        sen.mark_warm()
        baseline = sen.recompiles
        for _ in range(5):                             # stable shapes:
            f(jnp.ones((4,))).block_until_ready()      # cache hits only
        assert sen.recompiles == baseline, \
            "stable-shape loop must stay at its post-warmup count"
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.goodput"):
            for n in (5, 6, 7):                        # shape churn
                f(jnp.ones((n,))).block_until_ready()
        assert sen.recompiles >= baseline + 3
        assert any("recompile storm" in r.message for r in caplog.records)
        kinds = [e["kind"] for e in
                 obs.flight_recorder().snapshot()["events"]]
        assert "train_recompile" in kinds
    finally:
        sen.uninstall()
    before = sen.compiles
    f(jnp.ones((9,))).block_until_ready()              # detached: ignored
    assert sen.compiles == before


# ---- HBM telemetry + OOM forensics ----

def test_hbm_sample_and_attribution_with_fake_stats():
    hbm = HBMTelemetry(stats_fn=lambda: {
        "bytes_in_use": 1 << 30, "peak_bytes_in_use": 2 << 30,
        "bytes_limit": 16 << 30, "num_allocs": 7})
    hbm.attribute("params", 4096)
    hbm.attribute("opt_state", 8192)
    snap = hbm.snapshot()
    assert snap["available"] is True
    assert snap["bytes_in_use"] == 1 << 30
    assert snap["peak_bytes_in_use"] == 2 << 30
    assert snap["bytes_limit"] == 16 << 30
    assert "num_allocs" not in snap                    # gauge allowlist
    assert snap["attributed"] == {"params": 4096, "opt_state": 8192}


def test_hbm_unavailable_backend_is_graceful():
    # CPU jax returns None from memory_stats(); a raising fn degrades the
    # same way
    assert HBMTelemetry(stats_fn=lambda: None).sample() == {
        "available": False}
    def boom():
        raise RuntimeError("no allocator stats")
    assert HBMTelemetry(stats_fn=boom).sample() == {"available": False}
    # the default stats_fn on the forced-CPU test backend must not raise
    assert HBMTelemetry().sample()["available"] is False


def test_tree_nbytes_walks_nests_and_tensor_wrappers():
    class Wrapped:                       # core.Tensor-style .data holder
        data = np.zeros((4, 4), np.float32)
    tree = {"a": np.zeros(8, np.float32),
            "b": [np.zeros(2, np.int64), (np.zeros(3, np.int8),)],
            "c": Wrapped(), "d": "not-an-array"}
    assert HBMTelemetry.tree_nbytes(tree) == 8 * 4 + 2 * 8 + 3 + 64


def test_oom_forensics_dumps_watermarks_and_attribution(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    obs.flight_recorder().clear()
    hbm = HBMTelemetry(stats_fn=lambda: {
        "bytes_in_use": 900, "peak_bytes_in_use": 1000,
        "bytes_limit": 1000})
    hbm.attribute("params", 600)
    # not an OOM: no event, no dump
    assert oom_forensics(ValueError("shape mismatch"), hbm) is None
    assert not list(tmp_path.iterdir())
    # XLA's RESOURCE_EXHAUSTED surfaces as a generic RuntimeError text
    exc = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "1073741824 bytes")
    path = oom_forensics(exc, hbm)
    assert path is not None and os.path.exists(path)
    doc = json.loads(open(path).read())
    assert doc["reason"] == "oom"
    oom = [e for e in doc["events"] if e["kind"] == "train_oom"]
    assert len(oom) == 1
    assert oom[0]["hbm_peak_bytes_in_use"] == 1000
    assert oom[0]["attr_params_bytes"] == 600
    assert "RESOURCE_EXHAUSTED" in oom[0]["error"]


# ---- ResilientTrainer integration ----

class _Toy:
    """Step fn with a fixed per-step cost so phase shares are predictable."""

    def __init__(self, step_cost=0.0, fail=None):
        self.w = 0.0
        self.trained = []
        self.step_cost = step_cost
        # step -> list of exceptions, one consumed per attempt
        self.fail = {k: list(v) for k, v in (fail or {}).items()}

    def train_fn(self, step):
        if self.step_cost:
            time.sleep(self.step_cost)
        if self.fail.get(step):
            raise self.fail[step].pop(0)
        self.w += 1.0
        self.trained.append(step)
        return 1.0 / (step + 1)

    def trainer(self, tmp_path, name="ckpt", plan=None, goodput=True, **cfg):
        from paddle_tpu.distributed.resilient import (ResilientConfig,
                                                      ResilientTrainer)
        from paddle_tpu.utils.fault_injection import FaultPlan
        return ResilientTrainer(
            self.train_fn, str(tmp_path / name),
            get_state=lambda: {"w": self.w},
            set_state=lambda s: setattr(self, "w", s["w"]),
            config=ResilientConfig(**cfg),
            fault_plan=plan if plan is not None else FaultPlan(),
            use_orbax=False, goodput=goodput)


def test_disabled_goodput_leaves_every_hook_at_none(tmp_path):
    toy = _Toy()
    t = toy.trainer(tmp_path, goodput=False)
    assert t.ledger is None and t.sentinel is None and t.hbm is None
    assert t.worker.ledger is None
    summary = t.run(lambda i: i, num_steps=2)
    assert "goodput" not in summary


def test_faulted_run_reconciles_phases_against_wall_clock(tmp_path):
    """Acceptance: on a deterministic run with injected rollback +
    checkpoint + data-stall faults, phase seconds tile measured wall
    clock within 1% and the waste phases are actually populated."""
    from paddle_tpu.utils.fault_injection import FaultPlan

    toy = _Toy(step_cost=0.02)
    # raise@2 twice with max_step_retries=1: one backoff retry (booked as
    # rollback_waste), then rollback to the step-2 checkpoint and a
    # below-watermark replay would occur had we rolled further back; the
    # nan at 5 escalates straight to rollback (policy) replaying 4..5
    plan = FaultPlan.from_spec("raise@2:OSError;raise@2:OSError;nan_loss@5")
    t = toy.trainer(tmp_path, plan=plan, nan_policy="rollback",
                    max_rollbacks=3, max_step_retries=1,
                    retry_backoff=0.03, save_interval=2)

    def batch_fn(i):
        time.sleep(0.005)               # a stalled input pipeline
        return i

    summary = t.run(batch_fn, num_steps=8)
    assert summary["completed_steps"] == 8
    assert summary["rollbacks"] >= 2 and summary["retries"] >= 1
    snap = summary["goodput"]
    booked = sum(snap["phase_seconds"].values())
    assert booked == pytest.approx(snap["wall_seconds"],
                                   rel=0.01, abs=1e-4)
    ph = snap["phase_seconds"]
    assert ph["compute"] > 0.0
    assert ph["data_wait"] >= 8 * 0.005 * 0.5   # batch_fn stalls booked
    assert ph["checkpoint"] > 0.0               # periodic saves + restores
    # rollback_waste: the backoff sleep plus the step-4 replay after the
    # nan rollback (below the watermark -> device time is waste)
    assert ph["rollback_waste"] >= 0.03 * 0.5
    assert snap["wasted_steps"] >= 1
    # 8 completed + the poisoned step-5 execution: it ran ABOVE the
    # watermark (the trainer can't know a loss is bad until it reads it),
    # so only the below-watermark step-4 replay is booked as waste
    assert snap["productive_steps"] == 9
    assert 0.0 < snap["goodput"] < 1.0


def test_live_mfu_matches_offline_formula_on_clean_run(tmp_path):
    """Acceptance: live MFU (ledger) and the same obs.flops arithmetic
    over a wall clock measured around the run agree within 5%."""
    from paddle_tpu.obs.flops import peak_flops, train_flops_per_step

    flops_per_step = train_flops_per_step(1e6, tokens_per_step=64)
    peak = peak_flops("cpu", backend="cpu")
    toy = _Toy(step_cost=0.03)
    t = toy.trainer(tmp_path, save_interval=100)
    t.ledger.set_flops(flops_per_step, peak)
    t0 = time.perf_counter()
    summary = t.run(lambda i: i, num_steps=10)
    wall = time.perf_counter() - t0
    live = summary["goodput"]["mfu"]
    offline = flops_per_step * 10 / wall / peak
    assert live is not None
    assert live == pytest.approx(offline, rel=0.05)
    # and the exporter scrapes it as a finite gauge (the scrape happens
    # a beat later, so its wall is a hair larger: compare loosely)
    flat = obs.parse_exposition(t.metrics.render())
    assert flat["pdtpu_train_mfu"] == pytest.approx(live, rel=0.05)
    assert flat["pdtpu_train_goodput"] == pytest.approx(
        summary["goodput"]["goodput"], rel=0.05)


# ---- the fault-matrix scenario (tools/check_fault_matrix.py) ----

@pytest.mark.fault_matrix
def test_rollback_storm_books_waste_and_dump_is_filterable(tmp_path,
                                                           monkeypatch):
    """Rollback storm: a run hit by an OOM step + shape churn books
    rollback_waste, its goodput drops below the clean run's, and the
    black-box dump (written at the OOM, before recovery even starts)
    already carries the train_recompile/train_oom vocabulary — which the
    postmortem CLI isolates with --kind 'train_*'."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    obs.flight_recorder().clear()
    obs.flight_recorder().record("unit_noise", n=1)    # non-train kind

    # clean reference run: stable shapes, no faults
    clean = _Toy(step_cost=0.02)
    sc = clean.trainer(tmp_path, name="ckpt_clean").run(
        lambda i: i, num_steps=6)
    clean_goodput = sc["goodput"]["goodput"]
    assert sc["goodput"]["phase_seconds"]["rollback_waste"] == 0.0

    # storm run: every step jits a NEW shape (churn), and step 2 dies
    # with an XLA OOM -> retries (backoff waste) -> rollback (replay
    # waste)
    @jax.jit
    def probe(x):
        return (x * 2.0).sum()

    oom = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "9663676416 bytes")
    # two OOMs at step 2: the first retry (backoff -> rollback_waste)
    # also fails, exhausting max_step_retries=1 -> rollback
    storm = _Toy(step_cost=0.02, fail={2: [oom, oom]})
    orig = storm.train_fn

    def churny(step):
        probe(jnp.ones((step + 1,))).block_until_ready()
        return orig(step)

    storm.train_fn = churny
    t = storm.trainer(tmp_path, name="ckpt_storm", max_step_retries=1,
                      retry_backoff=0.05, max_rollbacks=2, save_interval=2)
    summary = t.run(lambda i: i, num_steps=6)
    assert summary["completed_steps"] == 6
    assert summary["rollbacks"] >= 1

    snap = summary["goodput"]
    assert snap["phase_seconds"]["rollback_waste"] > 0.0
    assert snap["goodput"] < clean_goodput
    assert t.sentinel.recompiles >= 1                  # churn was seen
    assert any(e["kind"] == "step_error"
               and "RESOURCE_EXHAUSTED" in e["error"]
               for e in summary["events"])

    # the OOM dumped the ring atomically at failure time
    dump_path = tmp_path / f"pdtpu_flight_{os.getpid()}.json"
    assert dump_path.exists(), "OOM must dump the flight ring"
    assert not (tmp_path / (dump_path.name + ".tmp")).exists()
    doc = json.loads(dump_path.read_text())
    assert doc["reason"] == "oom"
    kinds = [e["kind"] for e in doc["events"]]
    assert "train_oom" in kinds
    assert "train_recompile" in kinds                  # churn preceded it
    assert "unit_noise" in kinds                       # ring is unfiltered

    # postmortem CLI: --kind 'train_*' isolates the trainer vocabulary
    r = subprocess.run(
        [sys.executable, CLI, str(dump_path), "--kind", "train_*"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "train_oom" in r.stdout and "train_recompile" in r.stdout
    assert "unit_noise" not in r.stdout
    assert "RESOURCE_EXHAUSTED" in r.stdout            # info survives
