"""End-to-end observability (ISSUE 9): per-request tracing (traceparent
ingestion, phase spans that tile the recorded latency, the bounded
timeline LRU behind /debug/requests), the process-global flight recorder
(ring bound, atomic dumps, postmortem CLI), the shared Prometheus
plumbing (`pdtpu_train_*` exporter + opt-in MetricsServer), and the
fault-matrix scenario proving a breaker-open cascade leaves a black-box
dump that names the quarantined request.

Engine integration tests run the PRODUCTION schedulers threadless under
a SimClock, so every timeline number is exact, not approximate."""
import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import obs, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "flight_recorder.py")


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read()


# ---- request-trace primitives ----

def test_ingest_traceparent_and_request_ids():
    tid = "0af7651916cd43dd8448eb211c80319c"
    hdr = f"00-{tid}-b7ad6b7169203331-01"
    assert obs.ingest_traceparent(hdr) == tid
    assert obs.ingest_traceparent(hdr.upper()) == tid       # case-folded
    assert obs.ingest_traceparent("  " + hdr + "  ") == tid
    assert obs.ingest_traceparent(None) is None
    assert obs.ingest_traceparent("") is None
    assert obs.ingest_traceparent("not-a-traceparent") is None
    assert obs.ingest_traceparent("00-xyz-b7ad6b7169203331-01") is None
    rid = obs.new_request_id()
    assert len(rid) == 32 and rid != obs.new_request_id()


def test_request_trace_phases_tile_latency():
    tr = obs.RequestTrace("ab" * 16, 10.0, slo="interactive", tenant="t0")
    tr.mark("admitted", 10.004)
    tr.mark("admitted", 99.0)           # marks record at most once
    tr.mark("first_launch", 10.005)
    tr.mark("final_launch", 10.007)
    tr.mark("first_token", 10.010)
    tr.event("decode_step", 10.011, tok=7)
    tr.finish(10.020, "completed")
    tr.finish(10.5, "failed")           # finish is idempotent too
    d = tr.to_dict()
    assert d["outcome"] == "completed"
    assert d["slo"] == "interactive" and d["tenant"] == "t0"
    assert [p["name"] for p in d["phases"]] == [
        "queued", "bound", "prefill", "first_fetch", "decode"]
    # the tiling contract: phase durations sum EXACTLY to the latency
    assert sum(p["dur_ms"] for p in d["phases"]) == \
        pytest.approx(d["latency_ms"])
    assert d["latency_ms"] == pytest.approx(20.0)
    assert d["ttft_ms"] == pytest.approx(10.0)
    assert d["marks_ms"]["admitted"] == pytest.approx(4.0)
    assert d["events"][0]["name"] == "decode_step"
    assert d["events"][0]["args"] == {"tok": 7}
    # chrome view: one X span per phase + an instant per event, one lane
    ev = tr.chrome_events()
    xs = [e for e in ev if e["ph"] == "X"]
    assert len(xs) == 5
    assert all(e["name"].startswith("req/abababab/") for e in ev)
    assert len({e["tid"] for e in ev}) == 1


def test_request_trace_unfinished_and_event_bound():
    tr = obs.RequestTrace("cd" * 16, 0.0)
    assert tr.phases() == []            # no finish mark yet -> no spans
    assert tr.to_dict()["latency_ms"] is None
    for i in range(obs.RequestTrace.MAX_EVENTS + 5):
        tr.event("e", float(i))
    assert len(tr.events) == obs.RequestTrace.MAX_EVENTS
    assert tr.to_dict()["events_dropped"] == 5


def test_timeline_store_lru():
    store = obs.TimelineStore(capacity=2)
    store.put("a", {"n": 1})
    store.put("b", {"n": 2})
    assert store.get("a") == {"n": 1}   # refreshes 'a'
    store.put("c", {"n": 3})            # evicts 'b' (LRU), not 'a'
    assert store.get("b") is None
    assert store.ids() == ["a", "c"]
    assert len(store) == 2
    with pytest.raises(ValueError):
        obs.TimelineStore(capacity=0)


# ---- flight recorder ----

def test_flight_recorder_ring_and_atomic_dump(tmp_path, monkeypatch):
    fr = obs.FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("tick", i=i)
    snap = fr.snapshot()
    assert snap["recorded"] == 6 and snap["dropped"] == 2
    assert [e["i"] for e in snap["events"]] == [2, 3, 4, 5]
    assert [e["seq"] for e in snap["events"]] == [2, 3, 4, 5]
    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    path = fr.dump(reason="unit")
    assert path == str(tmp_path / f"pdtpu_flight_{os.getpid()}.json")
    assert not os.path.exists(path + ".tmp")    # tmp renamed away
    doc = json.loads(open(path).read())
    assert doc["version"] == 1 and doc["reason"] == "unit"
    assert doc["pid"] == os.getpid()
    assert [e["i"] for e in doc["events"]] == [2, 3, 4, 5]
    # try_dump never raises, even at an unwritable path
    assert fr.try_dump(path=str(tmp_path / "no" / "dir" / "x.json")) is None
    fr.clear()
    assert fr.snapshot()["recorded"] == 0


# ---- prometheus plumbing ----

def test_prom_builder_parse_round_trip():
    b = obs.PromBuilder()
    b.family("m_total", "counter").sample("m_total", 3, labels={"k": "v"})
    b.family("g", "gauge").sample("g", 1.23456, round_to=2)
    b.sample("n", None)
    text = b.render()
    flat = obs.parse_exposition(text)
    assert flat['m_total{k="v"}'] == 3
    assert flat["g"] == 1.23
    assert np.isnan(flat["n"])


def test_training_metrics_counters_and_render():
    tm = obs.TrainingMetrics()
    tm.on_event("retry", step=3)
    tm.on_event("bad_loss", step=4)
    tm.on_event("checkpoint_save", step=4)
    tm.on_event("not_a_counter", step=9)   # unknown kinds only move step
    tm.set_step(7)
    flat = obs.parse_exposition(tm.render())
    assert flat["pdtpu_train_retries_total"] == 1
    assert flat["pdtpu_train_bad_losses_total"] == 1
    assert flat["pdtpu_train_checkpoint_saves_total"] == 1
    assert flat["pdtpu_train_rollbacks_total"] == 0
    assert flat["pdtpu_train_last_step"] == 9
    # throughput gauges ride along when a tracker is attached
    tracker = profiler.ThroughputTracker()
    tracker.update(steps=4, seconds=2.0, tokens=8)
    flat2 = obs.parse_exposition(
        obs.TrainingMetrics(tracker=tracker).render())
    assert flat2["pdtpu_train_steps_per_sec"] == 2.0
    assert flat2["pdtpu_train_total_tokens"] == 8


def test_throughput_tracker_zero_seconds_guard_and_mfu():
    # a zero-duration chunk (clock granularity) must not poison the rate
    # window; totals and last_chunk_seconds still advance
    tp = profiler.ThroughputTracker(window=4)
    tp.update(steps=2, seconds=0.0, tokens=100)
    assert tp.total_steps == 2 and tp.total_tokens == 100
    assert tp.last_chunk_seconds == 0.0
    assert tp.steps_per_sec == 0.0                 # empty window, no inf
    tp.update(steps=2, seconds=1.0, tokens=100)
    assert tp.steps_per_sec == pytest.approx(2.0)
    assert tp.last_chunk_seconds == 1.0
    s = tp.summary()
    assert s["last_chunk_seconds"] == 1.0
    assert "mfu" not in s                          # flops not registered
    assert tp.mfu is None
    # register_flops arms the windowed MFU: 2 steps/s x 1e10 / 1e12
    tp.register_flops(flops_per_step=1e10, peak_flops=1e12)
    assert tp.mfu == pytest.approx(0.02)
    assert tp.summary()["mfu"] == pytest.approx(0.02)


def test_throughput_tracker_window_aging():
    tp = profiler.ThroughputTracker(window=2)
    tp.update(steps=1, seconds=1.0)                # will age out
    tp.update(steps=4, seconds=1.0)
    tp.update(steps=4, seconds=1.0)
    assert tp.steps_per_sec == pytest.approx(4.0)  # only the last two
    assert tp.total_steps == 9                     # totals never age


def test_training_metrics_goodput_families_round_trip():
    from paddle_tpu.obs.goodput import (GoodputLedger, HBMTelemetry,
                                        RecompileSentinel)
    t = [0.0]
    led = GoodputLedger(clock=lambda: t[0])
    led.start()
    sen = RecompileSentinel(led)                   # not installed: unit feed
    with led.measure("compute"):
        t[0] += 3.0
        sen.on_compile(0.25)                       # comes out of compute
    sen.mark_warm()
    with led.measure("checkpoint"):
        t[0] += 1.0
        sen.on_compile(0.25)                       # a recompile
    led.add_steps(6)
    hbm = HBMTelemetry(stats_fn=lambda: {
        "bytes_in_use": 10, "peak_bytes_in_use": 20, "bytes_limit": 100})
    hbm.attribute("kv_slab", 7)
    tm = obs.TrainingMetrics(ledger=led, hbm=hbm, sentinel=sen)
    flat = obs.parse_exposition(tm.render())
    assert flat["pdtpu_train_goodput"] == pytest.approx(2.75 / 4.0)
    assert np.isnan(flat["pdtpu_train_mfu"])       # flops not registered
    assert flat["pdtpu_train_wall_seconds"] == pytest.approx(4.0)
    assert flat['pdtpu_train_phase_seconds_total{phase="compute"}'] == 2.75
    assert flat['pdtpu_train_phase_seconds_total{phase="checkpoint"}'] == 0.75
    assert flat['pdtpu_train_phase_seconds_total{phase="compile"}'] == 0.5
    assert flat['pdtpu_train_phase_seconds_total{phase="idle"}'] == 0.0
    assert flat["pdtpu_train_compiles_total"] == 2
    assert flat["pdtpu_train_recompiles_total"] == 1
    assert flat["pdtpu_train_compile_seconds_total"] == 0.5
    assert flat["pdtpu_train_hbm_bytes_in_use"] == 10
    assert flat["pdtpu_train_hbm_peak_bytes_in_use"] == 20
    assert flat["pdtpu_train_hbm_bytes_limit"] == 100
    assert flat['pdtpu_train_hbm_attributed_bytes{component="kv_slab"}'] == 7
    # registering flops flips the NaN to a finite gauge
    led.set_flops(1e11, 1e12)
    flat = obs.parse_exposition(tm.render())
    assert flat["pdtpu_train_mfu"] == pytest.approx(
        1e11 * 6 / 4.0 / 1e12, abs=1e-4)
    # an unavailable HBM backend just drops the hbm_* families
    tm2 = obs.TrainingMetrics(ledger=led,
                              hbm=HBMTelemetry(stats_fn=lambda: None))
    flat2 = obs.parse_exposition(tm2.render())
    assert "pdtpu_train_hbm_bytes_in_use" not in flat2
    assert "pdtpu_train_goodput" in flat2


def test_metrics_server_endpoints():
    tm = obs.TrainingMetrics()
    tm.on_event("rollback", step=2)
    srv = obs.MetricsServer([tm.render], port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, body = _get(base + "/metrics")
        assert code == 200
        flat = obs.parse_exposition(body.decode())
        assert flat["pdtpu_train_rollbacks_total"] == 1
        code, body = _get(base + "/healthz")
        assert code == 200 and body == b"ok\n"
        obs.flight_recorder().record("unit_marker", n=1)
        code, body = _get(base + "/debug/flightrecorder")
        snap = json.loads(body)
        assert any(e["kind"] == "unit_marker" for e in snap["events"])
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(base + "/nope")
        assert exc.value.code == 404
    finally:
        srv.stop()


# ---- postmortem CLI (tools/flight_recorder.py) ----

def _write_dump(tmp_path):
    fr = obs.FlightRecorder()
    fr.record("reject", engine="serving", reason="queue_full", rid="r1")
    fr.record("quarantine", engine="llm", rid="deadbeef", reason="poisoned")
    return fr.dump(path=str(tmp_path / "dump.json"), reason="unit")


def _cli(*args):
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, cwd=REPO)


def test_flight_recorder_cli_postmortem_and_filters(tmp_path):
    dump = _write_dump(tmp_path)
    r = _cli(dump)
    assert r.returncode == 0, r.stderr
    assert "reason=unit" in r.stdout
    assert "quarantine" in r.stdout and "rid=deadbeef" in r.stdout
    r = _cli(dump, "--kind", "quarantine")
    assert r.returncode == 0
    assert "quarantine" in r.stdout and "queue_full" not in r.stdout
    r = _cli(dump, "--json")
    doc = json.loads(r.stdout)
    assert doc["reason"] == "unit" and len(doc["events"]) == 2


def test_flight_recorder_cli_merge_and_bad_file(tmp_path):
    dump = _write_dump(tmp_path)
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [
        {"name": "step", "ph": "X", "ts": 0, "dur": 5, "pid": 0,
         "tid": 1}]}))
    out = tmp_path / "merged.json"
    r = _cli(dump, "--merge", str(trace), "-o", str(out))
    assert r.returncode == 0, r.stderr
    merged = json.loads(out.read_text())["traceEvents"]
    names = [e["name"] for e in merged]
    assert "step" in names          # original spans survive the overlay
    assert "flight/quarantine" in names and "flight/reject" in names
    inst = next(e for e in merged if e["name"] == "flight/quarantine")
    assert inst["ph"] == "i" and inst["args"]["rid"] == "deadbeef"

    bad = tmp_path / "bad.json"
    bad.write_text('{"not": "a dump"}')
    assert _cli(str(bad)).returncode == 2
    assert _cli(str(tmp_path / "missing.json")).returncode == 2


# ---- BatchingEngine tracing (threadless SimClock) ----

def test_serving_engine_traced_request_timeline():
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.BatchingEngine(
        lambda args: [np.asarray(args[0]) * 2.0],
        serving.EngineConfig(max_batch_size=4, max_wait_ms=5.0),
        clock=clock)
    rid = "f00dfeed" * 4
    fut = eng.submit([np.ones((1, 3), np.float32)], rid=rid, trace=True)
    clock.advance(0.010)
    eng.pump()
    np.testing.assert_allclose(np.asarray(fut.result(timeout=0)[0]), 2.0)
    tl = eng.timelines.get(rid)
    assert tl is not None and tl["rid"] == rid
    assert tl["outcome"] == "completed"
    assert [p["name"] for p in tl["phases"]] == ["queued", "dispatch"]
    assert sum(p["dur_ms"] for p in tl["phases"]) == \
        pytest.approx(tl["latency_ms"])
    assert tl["latency_ms"] == pytest.approx(10.0)
    names = [e["name"] for e in tl["events"]]
    assert "submitted" in names and "dispatched" in names
    # untraced requests leave no timeline (and pay only a predicate)
    fut2 = eng.submit([np.ones((1, 3), np.float32)])
    clock.advance(0.010)
    eng.pump()
    fut2.result(timeout=0)
    assert len(eng.timelines) == 1
    eng.stop()


# ---- LLMEngine tracing: the reconciliation proof ----

@pytest.mark.llm
def test_llm_traced_request_timeline_reconciles(gpt_tiny):
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4),
        clock=clock)
    h = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=4,
                   trace=True)
    assert h.rid and len(h.rid) == 32
    while eng.has_work():
        clock.advance(0.002)
        eng.pump()
    assert len(h.result(timeout=0)) == 4
    tl = h.timeline()
    assert tl["rid"] == h.rid and tl["outcome"] == "completed"
    assert [p["name"] for p in tl["phases"]] == [
        "queued", "bound", "prefill", "first_fetch", "decode"]
    # span-sum == latency, and the trace's TTFT boundary IS the handle's
    # ttft_ms (recorded at the same clock instant)
    assert sum(p["dur_ms"] for p in tl["phases"]) == \
        pytest.approx(tl["latency_ms"])
    assert tl["latency_ms"] > 0
    assert tl["ttft_ms"] == h.ttft_ms
    names = [e["name"] for e in tl["events"]]
    for expected in ("submitted", "admitted", "prefill_chunk",
                     "decode_step"):
        assert expected in names, names
    # the engine's LRU serves the same timeline (/debug/requests/<rid>)
    stored = eng.timelines.get(h.rid)
    assert stored["ttft_ms"] == tl["ttft_ms"]
    assert stored["outcome"] == "completed"
    eng.stop()


@pytest.mark.llm
def test_traced_request_spans_interleave_with_profiler(gpt_tiny, tmp_path):
    """The chrome export carries BOTH the pump thread's request spans
    (emitted via the process-global profiler sink) and host RecordEvent
    spans, on the same timeline."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4),
        clock=clock)
    profiler.start_profiler()
    try:
        h = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3,
                       trace=True)
        with profiler.RecordEvent("pump_loop"):
            while eng.has_work():
                clock.advance(0.001)
                eng.pump()
        h.result(timeout=0)
    finally:
        out = tmp_path / "trace.json"
        profiler.stop_profiler(profile_path=str(out))
    eng.stop()
    events = json.load(open(out))["traceEvents"]
    names = [e["name"] for e in events]
    assert "pump_loop" in names            # RecordEvent host span
    prefix = f"req/{h.rid[:8]}/"
    req_events = [e for e in events if e["name"].startswith(prefix)]
    assert {e["ph"] for e in req_events} == {"X", "i"}
    assert any(e["name"] == prefix + "decode" and e["ph"] == "X"
               for e in req_events)


# ---- HTTP layer: traceparent propagation + debug routes ----

@pytest.mark.serving
def test_server_debug_routes_and_traced_predict():
    from paddle_tpu import serving
    W = np.random.RandomState(0).randn(3, 2).astype(np.float32)
    eng = serving.BatchingEngine(
        lambda args: [np.asarray(args[0], np.float32) @ W],
        serving.EngineConfig(max_batch_size=4, max_wait_ms=2.0))
    server = serving.ServingServer(eng, port=0).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        tid = "0af7651916cd43dd8448eb211c80319c"
        x = np.random.RandomState(1).rand(1, 3).astype(np.float32)
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"inputs": [x.tolist()]}).encode(),
            headers={"traceparent": f"00-{tid}-b7ad6b7169203331-01",
                     "X-PDTPU-Trace": "1"},
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            body = json.loads(r.read())
        np.testing.assert_allclose(body["outputs"][0], (x @ W).tolist(),
                                   rtol=1e-5, atol=1e-5)
        assert body["rid"] == tid          # traceparent trace-id propagated
        trace = body["trace"]
        assert trace["rid"] == tid and trace["outcome"] == "completed"
        assert [p["name"] for p in trace["phases"]] == ["queued",
                                                        "dispatch"]
        assert sum(p["dur_ms"] for p in trace["phases"]) == \
            pytest.approx(trace["latency_ms"])

        _, ids_body = _get(base + "/debug/requests")
        assert tid in json.loads(ids_body)["ids"]
        _, tl_body = _get(base + f"/debug/requests/{tid}")
        assert json.loads(tl_body)["rid"] == tid
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(base + "/debug/requests/" + "0" * 32)
        assert exc.value.code == 404
        _, fr_body = _get(base + "/debug/flightrecorder")
        assert json.loads(fr_body)["version"] == 1

        # untraced request: rid still echoed, no timeline kept
        req2 = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"inputs": [x.tolist()]}).encode(),
            method="POST")
        with urllib.request.urlopen(req2, timeout=30) as r:
            b2 = json.loads(r.read())
        assert "trace" not in b2 and len(b2["rid"]) == 32
        assert eng.timelines.get(b2["rid"]) is None
    finally:
        server.stop()


# ---- training side: ResilientTrainer exporter ----

def test_resilient_trainer_metrics_exporter(tmp_path):
    from paddle_tpu.distributed.resilient import (ResilientConfig,
                                                  ResilientTrainer)
    from paddle_tpu.utils.fault_injection import FaultPlan

    state = {"w": 0.0}

    def train_fn(step):
        state["w"] += 1.0
        return 1.0 / (step + 1)

    t = ResilientTrainer(
        train_fn, str(tmp_path / "ckpt"),
        get_state=lambda: dict(state),
        set_state=lambda s: state.update(s),
        config=ResilientConfig(),
        fault_plan=FaultPlan.from_spec("nan_loss@2"),
        use_orbax=False, metrics_port=0)
    try:
        summary = t.run(lambda i: i, num_steps=4)
        assert summary["completed_steps"] == 4
        snap = t.metrics.snapshot()
        assert snap["bad_losses"] == 1 and snap["skips"] == 1
        assert snap["checkpoint_saves"] >= 1
        assert snap["last_step"] >= 3
        # the recovery events also landed in the black-box ring
        kinds = [e["kind"] for e in
                 obs.flight_recorder().snapshot()["events"]]
        assert "train_bad_loss" in kinds
        assert "train_checkpoint_save" in kinds
        # and the same counters are scraped over HTTP
        _, body = _get(
            f"http://127.0.0.1:{t.metrics_server.port}/metrics")
        flat = obs.parse_exposition(body.decode())
        assert flat["pdtpu_train_bad_losses_total"] == 1
        assert flat["pdtpu_train_skips_total"] == 1
        assert flat["pdtpu_train_checkpoint_saves_total"] == \
            snap["checkpoint_saves"]
        assert flat["pdtpu_train_steps_per_sec"] >= 0
    finally:
        if t.metrics_server is not None:
            t.metrics_server.stop()


# ---- the fault-matrix scenario (tools/check_fault_matrix.py) ----

@pytest.mark.llm
@pytest.mark.fault_matrix
def test_breaker_open_dump_names_quarantined_request(gpt_tiny, tmp_path,
                                                     monkeypatch):
    """Black-box contract: a breaker-open cascade leaves an atomic dump
    in PDTPU_FLIGHT_DIR that names the quarantined request id and carries
    the blame sequence — dispatch retry -> failing solo probe ->
    quarantine -> breaker open — in recorded (seq) order, readable by the
    postmortem CLI."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan

    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    obs.flight_recorder().clear()
    plan = FaultPlan.from_spec(
        "poison_request@0;poison_request@2;poison_request@3")
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4,
                                dispatch_retries=0, breaker_threshold=1),
        clock=serving.SimClock(), fault_plan=plan)
    # phase 1: A (idx 0) poisoned, B (idx 1) innocent -> whole-step
    # failure, solo probes blame exactly A, quarantine + absolve, B
    # completes (threshold 1 would trip on any *charged* failure, so this
    # also proves exact blame never charges the breaker)
    bad = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=3)
    good = eng.submit(np.arange(11, 15, dtype=np.int32), max_new_tokens=3)
    while eng.has_work():
        eng.pump()
    with pytest.raises(serving.DispatchFailedError, match="quarantined"):
        bad.result(timeout=0)
    assert len(good.result(timeout=0)) == 3
    assert not eng.broken
    # phase 2: C (idx 2) and D (idx 3) BOTH poisoned -> every probe fails
    # with 2 suspects -> non-attributable engine fault -> breaker opens
    c = eng.submit(np.arange(21, 25, dtype=np.int32), max_new_tokens=3)
    d = eng.submit(np.arange(31, 35, dtype=np.int32), max_new_tokens=3)
    while eng.has_work():
        eng.pump()
    for h in (c, d):
        with pytest.raises(serving.DispatchFailedError):
            h.result(timeout=0)
    assert eng.broken

    dump_path = tmp_path / f"pdtpu_flight_{os.getpid()}.json"
    assert dump_path.exists(), "breaker-open must dump the flight ring"
    assert not (tmp_path / (dump_path.name + ".tmp")).exists()
    doc = json.loads(dump_path.read_text())
    assert doc["reason"] == "breaker_open:llm"

    def seqs(kind, **match):
        return [e["seq"] for e in doc["events"] if e["kind"] == kind
                and all(e.get(k) == v for k, v in match.items())]

    # the dump NAMES the quarantined request
    q = [e for e in doc["events"] if e["kind"] == "quarantine"]
    assert len(q) == 1 and q[0]["rid"] == bad.rid
    assert q[0]["reason"] == "poisoned" and q[0]["submit_idx"] == 0
    # blame sequence in recorded order
    assert min(seqs("dispatch_retry")) < \
        min(seqs("solo_probe", rid=bad.rid, outcome="failed")) < \
        min(seqs("quarantine")) < min(seqs("breaker_open", engine="llm"))
    assert seqs("solo_probe", rid=good.rid, outcome="ok")
    assert seqs("breaker_absolved", engine="llm")   # phase 1 exonerated
    assert seqs("engine_failure", engine="llm")     # phase 2 charged
    # the postmortem CLI reads it and surfaces the rid
    r = _cli(str(dump_path))
    assert r.returncode == 0, r.stderr
    assert bad.rid in r.stdout and "breaker_open" in r.stdout
