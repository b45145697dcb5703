"""Compile observatory (ISSUE 12): stable signature fingerprints over
argument pytrees, culprit-named recompile diffs (`batch['x'].shape[0]:
32→48`), the process-global executable registry with AOT
cost/memory analyses, the 6ND-vs-XLA-cost-model cross-check, the
/debug/compiles + pdtpu_compile_* exposition on both HTTP servers, the
one-predicate-when-disabled contract, the one jax.monitoring dispatcher
behind sentinels and set-up ledger alike (no build counted twice), the
hardened jit-cache miss listeners, and the
shape-churn fault-matrix scenario proving every post-warmup recompile
event names the churned leaf — readable by
`tools/flight_recorder.py --kind 'compile_*'`."""
import json
import logging
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.obs.compile_observatory import (CompileObservatory,
                                                compile_observatory,
                                                diff_signatures,
                                                fingerprint_of,
                                                signature_of)

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "flight_recorder.py")


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture()
def global_observatory():
    """The process-global observatory, armed for one test and returned
    to its disabled/empty state after — the registry is process-global
    by design, so tests must not leak rows into each other."""
    o = compile_observatory()
    o.reset()
    o.enable()
    yield o
    o.disable()
    o.reset()


# ---- signatures, fingerprints, culprit diffs (pure units) ----

def test_signature_walk_is_stable_and_unwraps_tensors():
    import paddle_tpu as paddle
    a = {"x": np.zeros((32, 8), np.float32),
         "y": np.zeros((32,), np.int32)}
    b = {"y": np.zeros((32,), np.int32),
         "x": np.zeros((32, 8), np.float32)}   # same leaves, other order
    sig = signature_of((a, 3))
    assert signature_of((b, 3)) == sig          # dict order is irrelevant
    paths = [e[0] for e in sig]
    assert "args[0]['x']" in paths and "args[0]['y']" in paths
    # the non-array leaf rides as a static entry (a changed static arg
    # must diff like a changed shape)
    static = next(e for e in sig if e[0] == "args[1]")
    assert static[1] == "static" and static[2] == "3"
    # core.Tensor wrappers contribute their underlying abstract value
    t = paddle.to_tensor(np.zeros((32, 8), np.float32))
    sig_t = signature_of(({"x": t, "y": a["y"]}, 3))
    assert sig_t == sig


def test_fingerprint_separates_shape_dtype_and_static_args():
    base = signature_of((np.zeros((8, 4), np.float32),))
    assert fingerprint_of(base) == fingerprint_of(
        signature_of((np.zeros((8, 4), np.float32),)))
    assert fingerprint_of(base) != fingerprint_of(
        signature_of((np.zeros((16, 4), np.float32),)))
    assert fingerprint_of(base) != fingerprint_of(
        signature_of((np.zeros((8, 4), np.int32),)))
    assert fingerprint_of(base) != fingerprint_of(base, static_hash="k=1")
    assert len(fingerprint_of(base)) == 12


def test_diff_signatures_names_culprit_leaf():
    old = signature_of(({"x": np.zeros((32, 8), np.float32),
                         "y": np.zeros((32,), np.int32)},))
    new = signature_of(({"x": np.zeros((48, 8), np.float32),
                         "y": np.zeros((32,), np.int32)},))
    changes = diff_signatures(old, new)
    assert changes == ["args[0]['x'].shape: (32, 8)→(48, 8)"]
    # dtype-only change names the dtype field
    new_dt = signature_of(({"x": np.zeros((32, 8), np.float64),
                            "y": np.zeros((32,), np.int32)},))
    assert diff_signatures(old, new_dt) == \
        ["args[0]['x'].dtype: float32→float64"]
    # added / removed leaves are reported too
    fewer = signature_of(({"x": np.zeros((32, 8), np.float32)},))
    assert any("removed" in c for c in diff_signatures(old, fewer))
    assert any("added" in c for c in diff_signatures(fewer, old))


def test_recompile_event_names_culprit_and_groups_storm(tmp_path,
                                                       monkeypatch):
    """Post-warmup builds for a known call site drop compile_recompile
    events whose culprit names the leaf; the PER-CULPRIT storm latch
    fires once, logs the grouped warning, and dumps the black box."""
    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    obs.flight_recorder().clear()
    o = CompileObservatory(storm_threshold=2)

    def plain_fn(batch):               # no .lower: signature-only rows
        return batch

    o.observe_call("unit/step", plain_fn,
                   ({"x": np.zeros((32, 8), np.float32)},))
    o.mark_warm()
    for bsz in (48, 64, 80):
        o.observe_call("unit/step", plain_fn,
                       ({"x": np.zeros((bsz, 8), np.float32)},))
    assert o.recompiles == 3
    # all three churns share one culprit bucket (grouped by leaf path)
    assert o.recompiles_by_culprit == \
        {"unit/step: args[0]['x'].shape": 3}
    assert "args[0]['x'].shape x3" in o.culprit_summary()
    events = obs.flight_recorder().snapshot()["events"]
    recs = [e for e in events if e["kind"] == "compile_recompile"]
    assert [e["culprit"] for e in recs] == [
        "args[0]['x'].shape: (32, 8)→(48, 8)",
        "args[0]['x'].shape: (48, 8)→(64, 8)",
        "args[0]['x'].shape: (64, 8)→(80, 8)"]
    assert all(e["callsite"] == "unit/step" for e in recs)
    # the storm latched exactly once (at the 2nd same-culprit recompile)
    storms = [e for e in events if e["kind"] == "compile_storm"]
    assert len(storms) == 1 and storms[0]["count"] == 2
    assert [e["storm"] for e in recs] == [False, True, False]
    assert (tmp_path / f"pdtpu_flight_{os.getpid()}.json").exists()


def test_observe_call_counts_dispatches_and_device_seconds():
    o = CompileObservatory()
    fn = lambda x: x                  # noqa: E731 — no AOT path
    args = (np.zeros((4,), np.float32),)
    fp = o.observe_call("unit/disp", fn, args)
    assert o.observe_call("unit/disp", fn, args) == fp
    o.note_device_seconds("unit/disp", 0.25)
    o.note_device_seconds("unit/disp", 0.75)
    snap = o.snapshot()
    assert snap["executables"] == 1
    assert snap["dispatches_total"] == 2
    assert snap["device_seconds_total"] == pytest.approx(1.0)
    row = snap["rows"][0]
    assert row["fingerprint"] == fp and row["dispatches"] == 2
    # unknown call sites and negative seconds are ignored, never raise
    o.note_device_seconds("unit/ghost", 1.0)
    o.note_device_seconds("unit/disp", -5.0)
    assert o.snapshot()["device_seconds_total"] == pytest.approx(1.0)


def test_snapshot_reconciles_predicted_vs_measured_hbm():
    o = CompileObservatory()
    o.record_build("unit/hbm", signature_of((np.zeros((4,)),)),
                   seconds=0.1,
                   analyses={"temp_bytes": 600, "argument_bytes": 300,
                             "output_bytes": 100, "flops": 10.0})
    hbm = obs.HBMTelemetry(stats_fn=lambda: {
        "bytes_in_use": 500, "peak_bytes_in_use": 2000,
        "bytes_limit": 4096})
    row = o.snapshot(hbm=hbm)["hbm"]
    assert row["predicted_bytes"] == 1000
    assert row["measured_peak_bytes"] == 2000
    assert row["ratio"] == pytest.approx(0.5)
    # backends without memory_stats reconcile to None, never raise
    row = o.snapshot(hbm=obs.HBMTelemetry(stats_fn=lambda: None))["hbm"]
    assert row["measured_peak_bytes"] is None and row["ratio"] is None


def test_prom_families_render_and_parse():
    from paddle_tpu.obs.prom import parse_exposition
    o = CompileObservatory()
    assert o.render_prom() == ""      # empty registry: empty exposition
    o.record_build("unit/prom", signature_of((np.zeros((8, 2)),)),
                   seconds=1.5,
                   analyses={"flops": 123.0, "temp_bytes": 4096})
    o.mark_warm()
    o.record_build("unit/prom", signature_of((np.zeros((16, 2)),)),
                   seconds=0.5, analyses={"flops": 246.0})
    parsed = parse_exposition(o.render_prom())
    assert parsed["pdtpu_compile_executables"] == 2
    assert parsed["pdtpu_compile_recompiles_total"] == 1
    assert parsed['pdtpu_compile_seconds_total{callsite="unit/prom"}'] \
        == pytest.approx(2.0)
    assert parsed['pdtpu_compile_flops{callsite="unit/prom"}'] == 246.0
    assert parsed['pdtpu_compile_recompiles_by_culprit_total'
                  '{culprit="unit/prom: args[0].shape"}'] == 1


# ---- AOT analyses against real jax (the registry's payload) ----

def test_cost_analysis_flops_agree_with_6nd(gpt_tiny, global_observatory):
    """XLA's own cost model vs the analytic 6ND accounting live MFU
    uses (obs/flops.py), over a REAL sharded train step of the tiny
    gpt. On a model this small 6ND overcounts (embedding-table params
    do no matmul work), so agreement is order-of-magnitude — the point
    is that the two can only diverge by measurement, not by formula or
    by a broken analysis (zero/None flops would fail hard here)."""
    import jax
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.obs.flops import train_flops_per_step
    from paddle_tpu.parallel import ShardedTrainStep

    opt = optim.AdamW(learning_rate=1e-4,
                      parameters=gpt_tiny.parameters())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    step = ShardedTrainStep(gpt_tiny, opt, mesh, zero_stage=0,
                            donate=False)
    assert step.observatory is None   # disabled default (one predicate)
    step.observatory = global_observatory
    B, S = 8, 32
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(
        0, gpt_tiny.config.vocab_size, (B, S)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(
        0, gpt_tiny.config.vocab_size, (B, S)).astype(np.int32))
    step(ids, labels)
    rows = global_observatory.snapshot()["rows"]
    assert [r["callsite"] for r in rows] == ["train/sharded_step"]
    row = rows[0]
    params, _ = gpt_tiny.functional_state()
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    analytic = train_flops_per_step(n_params, B * S)
    assert row["flops"] is not None and row["flops"] > 0
    ratio = row["flops"] / analytic
    assert 0.02 < ratio < 5.0, (row["flops"], analytic, ratio)
    # the memory analysis came through too (donate=False: the outputs
    # carry the full updated params/opt state, so both sides are real)
    assert row["temp_bytes"] > 0
    assert row["argument_bytes"] > 0 and row["output_bytes"] > 0
    assert row["compile_seconds"] > 0
    # trace, lower and compile apart: they add up to the one figure
    phases = row["phase_seconds"]
    assert set(phases) == {"trace", "lower", "compile"}
    assert all(v > 0 for v in phases.values())
    assert sum(phases.values()) == pytest.approx(row["compile_seconds"])
    assert row["dispatches"] == 1


# ---- the SimClock serving acceptance (every executable, nonzero flops) ----

def test_llm_engine_registers_every_executable_with_flops(
        gpt_tiny, global_observatory):
    """The SimClock LLM engine with `observatory=True` registers every
    unified-step executable it dispatches, each with nonzero
    cost_analysis FLOPs, and the training MetricsServer serves the same
    process-global registry at /debug/compiles (acceptance)."""
    from paddle_tpu import serving
    from paddle_tpu.obs.prom import MetricsServer

    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4,
                                max_queue_depth=8, observatory=True),
        clock=clock)
    assert eng.observatory is compile_observatory()
    rng = np.random.RandomState(0)
    handles = [eng.submit(rng.randint(1, 400, size=(4,)).astype(np.int32),
                          max_new_tokens=3) for _ in range(2)]
    while eng.has_work():
        eng.pump()
    for h in handles:
        assert len(h.result(timeout=0)) == 3
    eng.stop()

    snap = global_observatory.snapshot()
    assert snap["executables"] >= 1
    assert snap["dispatches_total"] >= snap["executables"]
    for row in snap["rows"]:
        assert row["callsite"] == "llm/unified_step"
        assert row["flops"] is not None and row["flops"] > 0, row
        assert row["dispatches"] >= 1

    server = MetricsServer([]).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/compiles",
                timeout=30) as r:
            doc = json.loads(r.read())
        assert doc["executables"] == snap["executables"]
        assert {row["fingerprint"] for row in doc["rows"]} == \
            {row["fingerprint"] for row in snap["rows"]}
        assert all(row["flops"] > 0 for row in doc["rows"])
        # the always-on set-up ledger rides the same payload: programs by
        # name, slowest first, and the set-up as of the first mark_warm()
        # (null before it: an operator's "why was this start slow")
        step = next(p for p in doc["programs"] if p["program"] == "step")
        assert step["traces"] >= 1 and step["backend_s"] > 0
        cost = [p["trace_self_s"] + p["lower_self_s"] + p["backend_self_s"]
                for p in doc["programs"]]
        assert cost == sorted(cost, reverse=True)
        assert doc["program_totals"]["phases"]["engine_init"] > 0
        assert "setup" in doc
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=30) as r:
            text = r.read().decode()
        assert "pdtpu_compile_executables" in text
        flat = obs.parse_exposition(text)
        assert flat["pdtpu_compile_trace_seconds_total"] > 0
        assert flat["pdtpu_compile_lower_seconds_total"] > 0
        assert flat["pdtpu_compile_cache_hits_total"] \
            + flat["pdtpu_compile_cache_misses_total"] >= 1
    finally:
        server.stop()


@pytest.mark.serving
def test_batching_engine_debug_compiles_endpoint(global_observatory):
    """The stateless BatchingEngine's predict hook registers per-shape
    executables (signature-only for a plain callable) and ServingServer
    serves /debug/compiles + the pdtpu_compile_* scrape families."""
    from paddle_tpu import serving

    eng = serving.BatchingEngine(
        lambda args: [np.asarray(args[0], np.float32) * 2.0],
        serving.EngineConfig(max_batch_size=8, max_wait_ms=1.0,
                             observatory=True))
    assert eng.observatory is compile_observatory()
    server = serving.ServingServer(eng, port=0).start()
    try:
        x = np.ones((3, 2), np.float32)
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict",
            data=json.dumps({"inputs": [x.tolist()]}).encode(),
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            json.loads(r.read())
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/debug/compiles",
                timeout=30) as r:
            doc = json.loads(r.read())
        rows = [row for row in doc["rows"]
                if row["callsite"] == "serve/predict"]
        assert len(rows) == 1 and rows[0]["dispatches"] >= 1
        # pow2 bucketing: 3 real rows dispatched on the padded-4 shape
        assert "(4, 2)" in rows[0]["signature"][0]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=30) as r:
            text = r.read().decode()
        assert 'pdtpu_compile_dispatches_total{callsite="serve/predict"}' \
            in text
        # ... and the set-up ledger's, on this server too
        assert isinstance(doc["programs"], list) and "setup" in doc
        for family in ("trace_seconds", "lower_seconds", "cache_hits",
                       "cache_misses"):
            assert f"pdtpu_compile_{family}_total " in text
    finally:
        server.stop()


# ---- the one-predicate-when-disabled contract ----

def test_disabled_hooks_never_touch_the_observatory(monkeypatch):
    """Engines/workers built without the flag hold observatory=None, and
    their dispatch paths never call into CompileObservatory at all —
    pinned by making every observatory entry point raise."""
    from paddle_tpu import serving
    from paddle_tpu.distributed.trainer import DeviceWorker

    def boom(*a, **k):
        raise AssertionError("disabled hook touched the observatory")

    monkeypatch.setattr(CompileObservatory, "observe_call", boom)
    monkeypatch.setattr(CompileObservatory, "note_device_seconds", boom)

    eng = serving.BatchingEngine(
        lambda args: [np.asarray(args[0], np.float32) + 1.0],
        serving.EngineConfig(max_batch_size=4, max_wait_ms=1.0))
    assert eng.observatory is None
    clock = serving.SimClock()
    eng2 = serving.BatchingEngine(
        lambda args: [np.asarray(args[0], np.float32) + 1.0],
        serving.EngineConfig(max_batch_size=4, max_wait_ms=0.0),
        clock=clock)
    fut = eng2.submit([np.ones((2, 2), np.float32)])
    eng2.pump()
    assert fut.result(timeout=0)[0].shape == (2, 2)

    worker = DeviceWorker(lambda x: float(np.asarray(x).sum()),
                          print_period=0)
    assert worker.observatory is None
    assert worker.run_step(np.ones((3,), np.float32)) == 3.0


# ---- one dispatcher, one ledger (ISSUE 34; the jit_cache source went) ----

def test_one_build_reaches_every_sentinel_and_the_ledger_once():
    """One real backend compile reaches each installed sentinel exactly
    once and the ledger's row exactly once, whether or not the build
    happened inside a JitLRUCache (whose miss hooks no longer feed
    either: the ISSUE 12 double-counting cannot come back)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.obs.goodput import RecompileSentinel, compile_ledger
    from paddle_tpu.utils.jit_cache import JitLRUCache

    x = jnp.ones((7,))                 # materialized BEFORE installing:
    _ = float(x.sum())                 # its fill/reduce compiles are done
    a = RecompileSentinel().install()
    b = RecompileSentinel().install()
    assert a.installed and b.installed
    try:
        cache = JitLRUCache(4, name="iss34-one-source")

        def iss34_once(v):
            return v * 3.0 + 1.0

        def build():
            f = jax.jit(iss34_once)
            f(x).block_until_ready()   # the one backend compile
            return f

        cache.get_or_build(("k7",), build)
        assert a.compiles + a.loads == 1, a.snapshot()
        assert b.compiles + b.loads == 1, b.snapshot()
        row = compile_ledger().row("iss34_once")
        assert row["traces"] == 1
        assert row["cache_hits"] + row["cache_misses"] == 1
        # a cache HIT reaches nobody
        cache.get_or_build(("k7",), build)
        assert a.compiles + a.loads == 1 and b.compiles + b.loads == 1
        assert compile_ledger().row("iss34_once") == row
    finally:
        a.uninstall()
        b.uninstall()
    assert not a.installed and not b.installed


def test_listeners_are_registered_once_per_process():
    from jax._src import monitoring as mon   # the getters are not public
    from paddle_tpu.obs import goodput

    goodput.register_listeners()       # `import paddle_tpu` did already
    goodput.register_listeners()
    assert mon.get_event_duration_listeners().count(
        goodput._duration_dispatch) == 1
    assert mon.get_event_time_span_listeners().count(
        goodput._span_dispatch) == 1
    assert mon.get_event_listeners().count(goodput._event_dispatch) == 1
    assert not hasattr(goodput, "_PROCESS_SOURCE")


# ---- satellite: hardened jit-cache miss listeners ----

def test_jit_cache_raising_listener_is_isolated_and_logged_once(caplog):
    from paddle_tpu.utils import jit_cache

    seen = []

    def bad(name, key, dt):
        raise RuntimeError("boom")

    def good(name, key, dt):
        seen.append(key)

    jit_cache.add_miss_listener(bad)
    jit_cache.add_miss_listener(good)
    try:
        cache = jit_cache.JitLRUCache(2, name="iss12-hardening")
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu.jit_cache"):
            assert cache.get_or_build(("a",), lambda: "exe-a") == "exe-a"
            assert cache.get_or_build(("b",), lambda: "exe-b") == "exe-b"
        # the build was never poisoned: executables cached, hits served
        assert ("a",) in cache and ("b",) in cache
        assert cache.get_or_build(("a",), lambda: "rebuilt") == "exe-a"
        # listeners after the raising one still ran, for every miss
        assert seen == [("a",), ("b",)]
        # one WARNING for the broken listener, not one per miss
        warns = [r for r in caplog.records
                 if r.levelno >= logging.WARNING
                 and "miss listener" in r.getMessage()]
        assert len(warns) == 1
    finally:
        jit_cache.remove_miss_listener(bad)
        jit_cache.remove_miss_listener(good)


# ---- the fault-matrix scenario (tools/check_fault_matrix.py) ----

@pytest.mark.fault_matrix
def test_shape_churn_storm_names_culprit_and_cli_table(tmp_path,
                                                      monkeypatch):
    """Shape churn through the REAL DeviceWorker hook: every post-warmup
    recompile event carries a named culprit diff (leaf path +
    before→after shape), the per-culprit storm drops an atomic black-box
    dump, and `tools/flight_recorder.py --kind 'compile_*'` renders the
    recompiles-grouped-by-culprit table (acceptance)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.trainer import DeviceWorker

    monkeypatch.setenv(obs.DUMP_DIR_ENV, str(tmp_path))
    obs.flight_recorder().clear()
    o = CompileObservatory(storm_threshold=3)

    @jax.jit
    def train_fn(x, y):
        return ((x - y[:, None]) ** 2).mean()

    worker = DeviceWorker(train_fn, print_period=0)
    worker.observatory = o
    worker.run_step((jnp.ones((8, 4)), jnp.ones((8,))))   # warmup
    o.mark_warm()
    for b in (12, 16, 24):                                # batch churn
        worker.run_step((jnp.ones((b, 4)), jnp.ones((b,))))
    assert o.recompiles == 3

    events = obs.flight_recorder().snapshot()["events"]
    recs = [e for e in events if e["kind"] == "compile_recompile"]
    assert len(recs) == 3
    for e in recs:
        assert e["callsite"] == "train/device_worker"
        # EVERY recompile names its culprit: leaf path + before→after
        assert e["culprit"].startswith("args[0].shape: ")
        assert "→" in e["culprit"]
    assert recs[0]["culprit"] == "args[0].shape: (8, 4)→(12, 4)"
    assert recs[1]["culprit"] == "args[0].shape: (12, 4)→(16, 4)"
    assert recs[2]["culprit"] == "args[0].shape: (16, 24)→(24, 4)" \
        or recs[2]["culprit"] == "args[0].shape: (16, 4)→(24, 4)"
    # both churned leaves are named in the full change list
    assert "args[1].shape" in recs[0]["changes"]
    # the per-culprit storm latched at 3 and dumped the ring
    storm = next(e for e in events if e["kind"] == "compile_storm")
    assert storm["count"] == 3

    dump_path = tmp_path / f"pdtpu_flight_{os.getpid()}.json"
    assert dump_path.exists(), "a recompile storm must dump the ring"
    doc = json.loads(dump_path.read_text())
    assert doc["reason"] == "recompile_storm"
    dump_recs = [e for e in doc["events"]
                 if e["kind"] == "compile_recompile"]
    assert len(dump_recs) == 3
    assert all("shape" in e["culprit"] and "→" in e["culprit"]
               for e in dump_recs)

    # postmortem CLI: --kind 'compile_*' filters the events and appends
    # the recompiles-grouped-by-culprit table
    r = subprocess.run(
        [sys.executable, CLI, str(dump_path), "--kind", "compile_*"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "recompiles by culprit:" in r.stdout
    out_lines = r.stdout.splitlines()
    table = out_lines[out_lines.index("recompiles by culprit:") + 2:]
    assert table and table[0].strip().startswith("3"), r.stdout
    assert "train/device_worker" in table[0]
    assert "args[0].shape" in table[0]
    event_lines = [ln for ln in r.stdout.splitlines()
                   if ln.lstrip().startswith("[")]
    assert event_lines
    assert all("compile_recompile" in ln or "compile_storm" in ln
               for ln in event_lines)
