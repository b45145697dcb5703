"""The program's spans on the profiler's clock (ISSUE 23): `RecordEvent`
is a `jax.profiler.TraceAnnotation` as well as a sink event; the serve host
loop holds the table's spans, nested; `unified_steps` counts every committed
step; an economics / observatory engine runs the default engine's host
sequence (no extra `block_until_ready`) and books the launch-to-fetch span
as `device_seconds`; and the two executables keep the names the benchmark
finds them by."""
import glob
import os

import numpy as np
import pytest

import jax

from paddle_tpu import profiler
from paddle_tpu.profiler import (REQUEST_SPANS, SERVE_SPANS,
                                 SPAN_REQUEST_ADMIT,
                                 SPAN_REQUEST_FIRST_LAUNCH,
                                 SPAN_REQUEST_FIRST_TOKEN,
                                 SPAN_REQUEST_SUBMIT, SPAN_SERVE_ADMIT,
                                 SPAN_SERVE_BUILD_ROWS, SPAN_SERVE_COMMIT,
                                 SPAN_SERVE_DRAFT, SPAN_SERVE_EVICT,
                                 SPAN_SERVE_PUMP)


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


def _engine(model, clock, draft=None, **cfg_kw):
    from paddle_tpu import serving
    kw = dict(num_slots=2, block_len=8, n_blocks=4, max_queue_depth=64)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=clock, draft_model=draft)


def _drain(eng, clock, dt=0.01):
    steps = 0
    while eng.has_work():
        clock.advance(dt)
        eng.pump()
        steps += 1
        assert steps < 2000, "engine failed to converge"


def _prompts(n, length=17, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 500, size=(length,)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture
def sink():
    profiler.start_profiler()
    try:
        yield profiler
    finally:
        profiler._SINK.enabled = False


# ---- RecordEvent: one span type, two recorders ----

def test_record_event_is_a_trace_annotation_with_its_args(tmp_path):
    """Any jax.profiler session records the program's spans, with the ids
    they were given as stats, on the trace's /host:CPU plane."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # what the benchmark's Window uses
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with profiler.RecordEvent("pdtpu/test/outer", step=7):
            with profiler.RecordEvent("pdtpu/test/inner", rid="abc",
                                      decode_rows=3):
                pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pdtpu/test/"):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     dict(e.stats))
    assert set(found) == {"pdtpu/test/outer", "pdtpu/test/inner"}
    assert found["pdtpu/test/outer"][2] == {"step": 7}
    assert found["pdtpu/test/inner"][2] == {"rid": "abc", "decode_rows": 3}
    (os_, od, _), (is_, id_, _) = (found["pdtpu/test/outer"],
                                   found["pdtpu/test/inner"])
    assert os_ <= is_ and is_ + id_ <= os_ + od


def test_sink_event_records_parent_and_ids(sink):
    with sink.RecordEvent("outer", step=4):
        with sink.RecordEvent("inner", rid="r1"):
            pass
    with sink.RecordEvent("sibling"):
        pass
    by = {e["name"]: e for e in sink.get_events()}
    assert by["outer"]["args"]["step"] == 4
    assert by["outer"]["args"]["parent"] == 0
    assert by["inner"]["args"]["rid"] == "r1"
    assert by["inner"]["args"]["parent"] == by["outer"]["args"]["id"]
    assert by["sibling"]["args"]["parent"] == 0
    assert by["inner"]["ts"] >= by["outer"]["ts"]
    assert (by["inner"]["ts"] + by["inner"]["dur"]
            <= by["outer"]["ts"] + by["outer"]["dur"])
    assert len({e["args"]["id"] for e in by.values()}) == 3


def test_span_with_the_sink_off_keeps_no_state():
    """No session, sink off: nothing is appended, nothing is pushed on the
    thread's stack, and end() without enter is harmless."""
    assert not profiler.profiler_enabled()
    before = len(profiler.get_events())
    with profiler.RecordEvent("idle", step=1) as ev:
        assert profiler._T.stack == []
        assert ev.begin is None
    profiler.RecordEvent("never entered").end()
    assert len(profiler.get_events()) == before


# ---- the serve host loop's spans ----

def test_one_pump_yields_the_tables_spans_nested(gpt_tiny, sink):
    """Every pump pass holds the table's spans: children inside `pump`,
    `evict` inside one request's `admit` inside `admit`, a request's
    other events inside `build_rows` and `commit`, its `submit` on the
    caller's thread; `draft` only with a draft model attached."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    # three distinct two-page prompts through two slots: the third
    # admission finds every free row pinned by cached pages, and evicts
    for p in _prompts(3):
        eng.submit(p, max_new_tokens=3)
        _drain(eng, clock)
    eng.stop()
    events = [e for e in sink.get_events()
              if e["name"].startswith("pdtpu/serve/")]
    names = {e["name"] for e in events}
    assert names == (set(SERVE_SPANS) | set(REQUEST_SPANS)) \
        - {SPAN_SERVE_DRAFT}
    inside = {SPAN_SERVE_EVICT: SPAN_REQUEST_ADMIT,
              SPAN_REQUEST_ADMIT: SPAN_SERVE_ADMIT,
              SPAN_REQUEST_FIRST_LAUNCH: SPAN_SERVE_BUILD_ROWS,
              SPAN_REQUEST_FIRST_TOKEN: SPAN_SERVE_COMMIT}
    by_id = {e["args"]["id"]: e for e in events}
    pumps = [e for e in events if e["name"] == SPAN_SERVE_PUMP]
    assert [e["args"]["step"] for e in pumps] == sorted(
        e["args"]["step"] for e in pumps)
    assert pumps[-1]["args"]["step"] == eng.unified_steps - 1
    for e in events:
        if e["name"] in (SPAN_SERVE_PUMP, SPAN_REQUEST_SUBMIT):
            assert e["args"]["parent"] == 0
            continue
        parent = by_id[e["args"]["parent"]]
        want = inside.get(e["name"], SPAN_SERVE_PUMP)
        assert parent["name"] == want, (e["name"], parent["name"])
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    evicts = [e for e in events if e["name"] == SPAN_SERVE_EVICT]
    assert len(evicts) >= 1
    assert eng.prefix_cache.stats["evictions"] >= 1
    dispatches = [e for e in events if e["name"].endswith("/dispatch")]
    assert len(dispatches) == eng.unified_steps
    assert all(e["args"]["prefill_rows"] + e["args"]["decode_rows"] >= 1
               for e in dispatches)
    # the children tile the pump but for a few clock reads
    one = pumps[1]
    kids = sum(e["dur"] for e in events
               if e["args"]["parent"] == one["args"]["id"])
    assert kids <= one["dur"] + 1e-3


def test_draft_span_only_with_a_draft_model(gpt_tiny, sink):
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny, spec_k=2)
    eng.submit(_prompts(1, length=6)[0], max_new_tokens=6)
    _drain(eng, clock)
    eng.stop()
    names = {e["name"] for e in sink.get_events()}
    assert SPAN_SERVE_DRAFT in names


# ---- every committed step is counted ----

def test_unified_steps_counts_every_committed_step(gpt_tiny):
    """A mixed run: prefill-only steps and steps with a decode row."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, prefill_chunk=8)
    handles = []
    for i, p in enumerate(_prompts(4, length=20)):
        handles.append(eng.submit(p, max_new_tokens=4))
        clock.advance(0.01)
        eng.pump()
    _drain(eng, clock)
    assert eng.prefill_dispatches > 0 and eng.decode_iterations > 0
    assert eng.unified_steps == (eng.decode_iterations
                                 + eng.prefill_dispatches)
    snap = eng.metrics.snapshot()
    assert snap["unified_steps"] == eng.unified_steps
    assert snap["dispatches"] == eng.decode_iterations   # meaning kept
    assert f"pdtpu_llm_unified_steps_total {eng.unified_steps}" \
        in eng.metrics.render()
    eng.stop()


# ---- one measurement point, no extra synchronisation ----

@pytest.mark.parametrize("armed", [{"economics": True},
                                   {"observatory": True},
                                   {"economics": True, "draft": True}],
                         ids=["economics", "observatory",
                              "economics-with-draft"])
def test_armed_engine_runs_the_default_host_sequence(gpt_tiny, monkeypatch,
                                                     armed):
    """An economics / observatory engine emits the default engine's tokens
    and executes no block_until_ready: the dispatch's device span is read
    at the start of `dispatch` and the end of `fetch`, where the host has
    already waited."""
    from paddle_tpu import serving
    from paddle_tpu.serving.llm import llm_engine
    armed = dict(armed)
    draft = gpt_tiny if armed.pop("draft", False) else None
    if draft is not None:
        armed["spec_k"] = 2
    prompts = _prompts(3, length=9)

    def run(**kw):
        clock = serving.SimClock()
        eng = _engine(gpt_tiny, clock, draft=draft if kw else None, **kw)
        hs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        _drain(eng, clock)
        out = [h.result(timeout=0).tolist() for h in hs]
        return eng, out

    _, plain = run()
    calls = []
    monkeypatch.setattr(llm_engine.jax, "block_until_ready",
                        lambda x: calls.append(1) or x)
    eng, got = run(**armed)
    assert got == plain
    assert calls == []
    if eng.ledger is not None:
        snap = eng.ledger.snapshot()
        assert snap["dispatches"] >= eng.unified_steps > 0
        assert snap["useful_positions"] > 0
    if eng.observatory is not None:
        rows = eng.observatory.snapshot()
        assert "llm/unified_step" in str(rows)
    eng.stop()


@pytest.mark.parametrize("with_draft", [False, True],
                         ids=["plain", "with-draft"])
def test_device_seconds_is_the_span_from_launch_to_fetch(gpt_tiny,
                                                         with_draft):
    """What one dispatch books, on a SimClock that only the test moves:
    the time inside `_run_dispatch` (launch; the fetch that follows moves
    no SimClock) is `device_seconds`, the operand uploads before it stay
    in the host phase, and a draft dispatch books its own span into
    `draft_compute`. The amounts are powers of two, so the sums are exact."""
    from paddle_tpu import serving
    UPLOAD, STEP, DRAFT = 0.125, 0.5, 0.25
    clock = serving.SimClock()
    kw = dict(economics=True, observatory=True)
    if with_draft:
        kw["spec_k"] = 2
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny if with_draft else None,
                  **kw)
    def observed():         # the observatory is one per process
        return sum(r["device_seconds"]
                   for r in eng.observatory.snapshot()["rows"]
                   if r["callsite"] == "llm/unified_step")

    observed_before = observed()
    launches = {"step": 0, "draft": 0}
    inner_dispatch = eng._run_dispatch
    inner_table = eng.pool.device_block_table

    def dispatch(kinds, fn, args, exempt=False):
        is_draft = kinds[0][0] == "draft"
        launches["draft" if is_draft else "step"] += 1
        clock.advance(DRAFT if is_draft else STEP)
        return inner_dispatch(kinds, fn, args, exempt=exempt)

    def table():
        clock.advance(UPLOAD)           # an upload: before the launch
        return inner_table()

    eng._run_dispatch = dispatch
    eng.pool.device_block_table = table
    h = eng.submit(_prompts(1, length=6)[0], max_new_tokens=6,
                   tenant="acme")
    steps = 0
    while eng.has_work():
        eng.pump()
        steps += 1
        assert steps < 200
    assert len(h.tokens_so_far()) == 6
    assert launches["step"] == eng.unified_steps > 0
    assert (launches["draft"] > 0) == with_draft
    snap = eng.ledger.snapshot()
    phases = snap["phase_seconds"]
    assert phases["prefill_compute"] + phases["decode_compute"] \
        == STEP * launches["step"]
    assert phases["draft_compute"] == DRAFT * launches["draft"]
    assert snap["compute_seconds"] == snap["tenants"]["acme"][
        "device_seconds"] == snap["classes"]["batch"]["device_seconds"]
    assert snap["dispatches"] == launches["step"] + launches["draft"]
    assert phases["host"] == UPLOAD * launches["step"]
    assert observed() - observed_before == STEP * launches["step"]
    eng.stop()


# ---- the executables keep the names the benchmark reads ----

def test_unified_step_lowers_to_jit_step(gpt_tiny):
    """benchmark/jobs/serve_closed_loop.py finds the step in a trace as
    `jit_step`; step_gap_ms_p50, unified_step_ms_p50 and the ledger's
    breakdown are keyed on it."""
    from paddle_tpu import serving
    from paddle_tpu.serving.llm import llm_engine
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    seen = []
    inner = eng._run_dispatch

    def spy(kinds, fn, args, exempt=False):
        seen.append((fn, args))
        return inner(kinds, fn, args, exempt=exempt)

    eng._run_dispatch = spy
    eng.submit(_prompts(1, length=6)[0], max_new_tokens=2)
    _drain(eng, clock)
    eng.stop()
    fn, args = seen[0]
    assert llm_engine.UNIFIED_STEP_NAME == "step"
    assert "module @jit_step " in fn.lower(*args).as_text()[:200]


def test_scan_chunk_lowers_to_jit_chunk_step():
    """benchmark/jobs/train.py finds the train step as `jit_chunk_step`."""
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as optim
    from paddle_tpu.parallel import ScanTrainStep, api, stack_batches
    paddle.seed(0)
    model = nn.Linear(8, 4)
    opt = optim.AdamW(learning_rate=1e-2, parameters=model.parameters())
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    step = ScanTrainStep(model, opt, mesh, scan_steps=2,
                         loss_fn=lambda out, y:
                         nn.functional.mse_loss(out, y))
    seen = []
    inner = step._chunk_jitted

    class Spy:
        def __call__(self, *a):
            seen.append(inner.lower(*a).as_text()[:200])
            return inner(*a)

    step._chunk_jitted = Spy()
    rng = np.random.RandomState(0)
    batches = [(rng.randn(4, 8).astype(np.float32),
                rng.randn(4, 4).astype(np.float32)) for _ in range(2)]
    step(*stack_batches(batches))
    assert api.CHUNK_STEP_NAME == "chunk_step"
    assert "module @jit_chunk_step " in seen[0]


def test_train_spans_surround_the_prefetchers_get_and_the_chunk(sink):
    from paddle_tpu.io.prefetch import ChunkPrefetcher
    from paddle_tpu.profiler import TRAIN_SPANS
    batches = [(np.zeros((2, 3), np.float32),) for _ in range(4)]
    with ChunkPrefetcher(batches, scan_steps=2) as pf:
        chunks = list(pf)
    assert len(chunks) == 2
    names = [e["name"] for e in sink.get_events()]
    assert names.count(TRAIN_SPANS[0]) >= 2
