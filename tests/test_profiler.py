"""Profiler spans (reference: platform/profiler.h RecordEvent)."""
import json

import numpy as np

from paddle_tpu import profiler


def test_record_event_spans_and_summary(tmp_path):
    profiler.start_profiler()
    with profiler.RecordEvent("fwd"):
        np.dot(np.ones((64, 64)), np.ones((64, 64)))
    with profiler.RecordEvent("fwd"):
        pass
    with profiler.RecordEvent("bwd"):
        pass
    profiler.stop_profiler(profile_path=str(tmp_path / "trace.json"))
    events = json.load(open(tmp_path / "trace.json"))["traceEvents"]
    names = [e["name"] for e in events]
    assert names.count("fwd") == 2 and names.count("bwd") == 1
    assert all(e["dur"] >= 0 for e in events)


def test_summary_survives_instant_events():
    """Regression (ISSUE 9): record_instant 'i' events share the buffer
    with 'X' spans; Profiler.summary() must skip them instead of
    KeyError'ing on the missing 'dur'."""
    p = profiler.Profiler(timer_only=True)
    p.start()
    with profiler.RecordEvent("fwd"):
        pass
    profiler.record_instant("fault", {"kind": "rollback"})
    summary = p.summary()
    p.stop()
    assert "fwd" in summary and "fault" not in summary


def test_multithread_spans_share_one_export(tmp_path):
    """The event sink is process-global: spans recorded on worker threads
    land in the same export as the caller's, on distinct tid lanes."""
    import threading
    profiler.start_profiler()

    def worker(i):
        with profiler.RecordEvent(f"worker{i}"):
            pass

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    with profiler.RecordEvent("main"):
        pass
    out = tmp_path / "mt.json"
    profiler.stop_profiler(profile_path=str(out))
    events = json.load(open(out))["traceEvents"]
    names = {e["name"] for e in events}
    assert {"worker0", "worker1", "worker2", "main"} <= names
    tids = {e["tid"] for e in events if e["name"].startswith("worker")}
    assert len(tids) >= 2       # distinct thread lanes


def test_stop_profiler_from_another_thread_sees_trace_dir(tmp_path,
                                                          monkeypatch):
    """Regression (ISSUE 9): start_profiler(trace_dir=...) arms the
    device tracer in MODULE-GLOBAL state, so stop_profiler from a
    different thread still stops it (trace_dir used to be thread-local,
    leaking the jax trace when another thread stopped the profiler)."""
    import threading
    calls = []
    monkeypatch.setattr(profiler.jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profiler.jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    profiler.start_profiler(trace_dir=str(tmp_path / "xprof"))
    t = threading.Thread(target=profiler.stop_profiler,
                         kwargs={"profile_path": str(tmp_path / "t.json")})
    t.start()
    t.join()
    assert calls == [("start", str(tmp_path / "xprof")), ("stop", None)]
    assert (tmp_path / "t.json").exists()
