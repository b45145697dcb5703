"""A request's way to its first token (ISSUE 49): every request carries four
stamps on the engine's clock (`admitted`, `first_launch`, `final_launch`,
`first_token`; `arrival` is the first), which cut `handle.ttft_ms` into
queued + bound + prefill + first_fetch; `LLMMetrics` keeps the phases and
three counters; four `pdtpu/serve/request/*` events a request carry them
on the profiler's clock; `slots_vacant_queued` counts, a launch, the slots
that rode empty while somebody waited; and a traced request's
`RequestTrace` phases are those stamps. CPU, gpt2-tiny; `SimClock` wherever
time matters (a pump pass is one instant of it, so what shows is whole
passes: what a pass costs is the chip's to say).
"""
import glob
import os

import numpy as np
import pytest

import jax

from paddle_tpu import profiler, serving
from paddle_tpu.obs.prom import parse_exposition
from paddle_tpu.profiler import (REQUEST_SPANS, SPAN_REQUEST_ADMIT,
                                 SPAN_REQUEST_FIRST_LAUNCH,
                                 SPAN_REQUEST_FIRST_TOKEN,
                                 SPAN_REQUEST_SUBMIT, SPAN_SERVE_ADMIT,
                                 SPAN_SERVE_BUILD_ROWS, SPAN_SERVE_COMMIT,
                                 SPAN_SERVE_DISPATCH)
from paddle_tpu.serving.metrics import TTFT_PHASES

DT = 0.01           # a pump pass, on the SimClock
CHUNK = 16          # LLMEngineConfig.prefill_chunk's default
PHASE_KEYS = tuple(f"{p}_ms" for p in TTFT_PHASES)


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM.from_preset("gpt2-tiny")
    model.eval()
    return model


def _engine(model, clock=None, **cfg_kw):
    kw = dict(num_slots=2, block_len=8, n_blocks=16, max_queue_depth=64)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=clock or serving.SimClock())


def _pump(eng, passes=1):
    for _ in range(passes):
        eng.clock.advance(DT)
        eng.pump()


def _drain(eng):
    passes = 0
    while eng.has_work():
        _pump(eng)
        passes += 1
        assert passes < 2000, "engine failed to converge"


def _prompt(n, seed=3):
    return np.random.default_rng(seed).integers(1, 500, n).astype(np.int32)


def _phases(handle):
    return [handle.ttft_phases_ms[k] for k in PHASE_KEYS]


def _assert_tiles(handle):
    """The four phases are differences of five stamps of which the first is
    the arrival and the last the instant `ttft_ms` was taken: they add up to
    it, but for the rounding of four float additions."""
    assert tuple(handle.ttft_phases_ms) == PHASE_KEYS
    assert all(ms >= 0 for ms in _phases(handle))
    assert sum(_phases(handle)) == pytest.approx(handle.ttft_ms, abs=1e-9)


@pytest.fixture
def sink():
    profiler.start_profiler()
    try:
        yield profiler
    finally:
        profiler._SINK.enabled = False


def _sink_args(name):
    return [e["args"] for e in profiler.get_events() if e["name"] == name]


# ---- the stamps and the phases they cut the TTFT into ----

@pytest.mark.parametrize("tokens, chunks", [(5, 1), (70, 5)])
def test_phases_tile_the_ttft_of_a_cold_prompt(gpt_tiny, tokens, chunks):
    """An idle engine, passes 10 ms apart: a pass to be admitted, a pass a
    chunk but the last, one for the last chunk's step to come back (the
    first pass launches two steps, so the last chunk's fetch is the pass
    after its launch unless it is the first)."""
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    h = eng.submit(_prompt(tokens), max_new_tokens=2)
    reqs = list(eng._queues[h.slo])
    _drain(eng)
    _assert_tiles(h)
    req, = reqs
    # the boundaries are the engine's own: `ttft_ms` to the bit
    assert (req.first_token - req.arrival) * 1e3 == h.ttft_ms
    assert req.arrival <= req.admitted <= req.first_launch \
        <= req.final_launch <= req.first_token
    assert req.chunks == chunks
    queued, bound, prefill, first_fetch = _phases(h)
    assert queued == pytest.approx(DT * 1e3) and bound == 0
    if chunks == 1:
        assert prefill == 0 and first_fetch == 0
    else:
        # chunk 1 and 2 launched in the first pass, then a pass a chunk
        assert prefill == pytest.approx((chunks - 2) * DT * 1e3)
        assert first_fetch == pytest.approx(DT * 1e3)
    # nothing was in flight when it was admitted: a step a chunk
    assert h.steps_to_first_token == chunks


def test_phases_tile_the_ttft_of_a_prefix_cache_hit(gpt_tiny, sink):
    """A prompt the cache holds but for its last token is stamped by the
    same code: one chunk of one token, `cached_tokens` on its admission."""
    eng = _engine(gpt_tiny)
    prompt = _prompt(41)
    cold = eng.submit(prompt, max_new_tokens=2)
    _drain(eng)
    warm = eng.submit(prompt, max_new_tokens=2)
    _drain(eng)
    for h in (cold, warm):
        _assert_tiles(h)
    assert warm.ttft_phases_ms["prefill_ms"] == 0
    assert cold.ttft_phases_ms["prefill_ms"] > 0
    admits = {a["rid"]: a for a in _sink_args(SPAN_REQUEST_ADMIT)}
    assert admits[cold.rid]["cached_tokens"] == 0
    assert admits[warm.rid]["cached_tokens"] == 40
    firsts = {a["rid"]: a for a in _sink_args(SPAN_REQUEST_FIRST_TOKEN)}
    assert firsts[cold.rid]["chunks"] == 3
    assert firsts[warm.rid]["chunks"] == 1


def test_a_deferred_first_chunk_is_time_bound(gpt_tiny):
    """40 prompts of three chunks on 40 slots, 512 positions a step: 32
    rows' chunks fill a step, so the last eight admitted wait, bound to
    their slots, until the others' prefill is through: their `bound` is the
    deferred passes, everybody else's is 0."""
    eng = _engine(gpt_tiny, num_slots=40, n_blocks=8, max_queue_depth=128,
                  enable_prefix_cache=False)
    assert eng.step_tokens == 512
    hs = [eng.submit(_prompt(48, seed=i), max_new_tokens=2)
          for i in range(40)]
    _drain(eng)
    for h in hs:
        _assert_tiles(h)
    bound = [h.ttft_phases_ms["bound_ms"] for h in hs]
    assert bound[:32] == [0.0] * 32
    # steps 0 and 1 leave in the first pass, step 2 in the second, and the
    # third pass launches the first step with room for them
    assert bound[32:] == [pytest.approx(2 * DT * 1e3)] * 8
    assert eng.metrics.snapshot()["prefill_rows_deferred"] == 3 * 8
    # deferred rows are taken slots: none of them counts as vacant
    assert eng.metrics.snapshot()["slot_steps_vacant_queued"] == 0


def test_the_third_of_three_requests_queues_until_a_slot_is_freed(gpt_tiny):
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    a = eng.submit(_prompt(5, 1), max_new_tokens=3)
    b = eng.submit(_prompt(5, 2), max_new_tokens=12)
    c = eng.submit(_prompt(5, 3), max_new_tokens=2)
    c_req = eng._queues[c.slo][-1]
    freed_at = None
    while eng.has_work():
        _pump(eng)
        if freed_at is None and a.future.done():
            freed_at = eng.clock.now()
    for h in (a, b, c):
        _assert_tiles(h)
    assert a.ttft_phases_ms["queued_ms"] == pytest.approx(DT * 1e3)
    # admitted by the pass after the one that retired `a`'s last token
    assert c_req.admitted == pytest.approx(freed_at + DT)
    assert c.ttft_phases_ms["queued_ms"] == pytest.approx(
        (freed_at + DT - c_req.arrival) * 1e3)
    assert c.ttft_phases_ms["queued_ms"] > 3 * DT * 1e3
    assert c.ttft_phases_ms["bound_ms"] == 0


# ---- a step in flight: the period launch-ahead costs a first token ----

def test_admission_behind_a_step_in_flight_costs_a_step(gpt_tiny):
    """A request submitted while the pump holds a step in flight is
    admitted on the next pass, rides the step launched then, which runs
    after the one in flight: chunks + 1 steps to its first token."""
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    eng.submit(_prompt(5, 1), max_new_tokens=40)
    _pump(eng, 3)
    assert eng._inflight is not None
    for tokens, chunks in ((5, 1), (40, 3)):
        h = eng.submit(_prompt(tokens, 2), max_new_tokens=2)
        while h.ttft_ms is None:
            _pump(eng)
        _assert_tiles(h)
        assert h.steps_to_first_token == chunks + 1
        queued, bound, prefill, first_fetch = _phases(h)
        assert queued == pytest.approx(DT * 1e3) and bound == 0
        assert prefill == pytest.approx((chunks - 1) * DT * 1e3)
        assert first_fetch == pytest.approx(DT * 1e3)
        while not h.future.done():
            _pump(eng)
    eng.stop(drain=False)


def test_slots_vacant_queued_counts_the_launch_a_freed_slot_rode_empty(
        gpt_tiny, sink):
    """ROADMAP A4(b)'s period as a count, pinned before anyone shortens it:
    the launch that knew `a`'s last token was in flight carried no row in
    `a`'s slot while `c` waited; the pass after it admits `c`."""
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    for seed, new in ((1, 3), (2, 12), (3, 2)):
        eng.submit(_prompt(5, seed), max_new_tokens=new)
    _drain(eng)
    vacant = [a["slots_vacant_queued"]
              for a in _sink_args(SPAN_SERVE_DISPATCH)]
    assert len(vacant) == eng.unified_steps
    assert sum(vacant) == 1 and vacant.index(1) == 3
    assert eng.metrics.snapshot()["slot_steps_vacant_queued"] == 1


def test_slots_vacant_queued_is_zero_with_nobody_queued(gpt_tiny, sink):
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    for seed, new in ((1, 3), (2, 12)):
        eng.submit(_prompt(5, seed), max_new_tokens=new)
    _drain(eng)
    dispatches = _sink_args(SPAN_SERVE_DISPATCH)
    # `a`'s slot does ride empty, and nobody wanted it
    assert any(a["decode_rows"] + a["prefill_rows"] == 1 for a in dispatches)
    assert [a["slots_vacant_queued"] for a in dispatches] \
        == [0] * eng.unified_steps
    assert eng.metrics.snapshot()["slot_steps_vacant_queued"] == 0


# ---- what the operator reads ----

def test_snapshot_and_exposition_hold_the_phases_and_the_counters(gpt_tiny):
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    hs = [eng.submit(_prompt(n, n), max_new_tokens=3) for n in (5, 40, 20)]
    _drain(eng)
    s = eng.metrics.snapshot()
    assert s["first_tokens"] == 3 == s["prefills"]
    assert s["ttft_steps"] == sum(h.steps_to_first_token for h in hs)
    assert s["slot_steps_vacant_queued"] >= 1
    assert tuple(s["ttft_phase_ms"]) == TTFT_PHASES
    for phase, key in zip(TTFT_PHASES, PHASE_KEYS):
        values = sorted(h.ttft_phases_ms[key] for h in hs)
        assert s["ttft_phase_ms"][phase] == {"p50": values[1],
                                             "p99": values[2]}
    prom = parse_exposition(eng.metrics.render())
    for phase in TTFT_PHASES:
        for q, key in (("0.5", "p50"), ("0.99", "p99")):
            assert prom[f'pdtpu_llm_ttft_phase_ms{{phase="{phase}",'
                        f'quantile="{q}"}}'] == pytest.approx(
                s["ttft_phase_ms"][phase][key], abs=1e-3)
    for name in ("first_tokens", "ttft_steps", "slot_steps_vacant_queued"):
        assert prom[f"pdtpu_llm_{name}_total"] == s[name]


def test_a_traced_requests_phases_are_the_always_on_stamps(gpt_tiny):
    eng = _engine(gpt_tiny, enable_prefix_cache=False)
    eng.submit(_prompt(5, 1), max_new_tokens=30)
    _pump(eng, 2)
    h = eng.submit(_prompt(40, 2), max_new_tokens=3, trace=True)
    plain = eng.submit(_prompt(40, 3), max_new_tokens=3)
    _drain(eng)
    assert plain.timeline() is None and plain.ttft_phases_ms is not None
    tl = h.timeline()
    assert [p["name"] for p in tl["phases"]] == list(TTFT_PHASES) \
        + ["decode"]
    assert [p["dur_ms"] for p in tl["phases"][:4]] == pytest.approx(
        _phases(h), abs=1e-9)
    assert tl["ttft_ms"] == h.ttft_ms
    assert sum(p["dur_ms"] for p in tl["phases"]) == pytest.approx(
        tl["latency_ms"])
    admitted, = [e for e in tl["events"] if e["name"] == "admitted"]
    assert admitted["args"]["queue_wait_ms"] == pytest.approx(
        h.ttft_phases_ms["queued_ms"])


def test_a_request_leaves_no_event_behind_with_no_session(gpt_tiny):
    """No profiler session, sink off: the stamps are all a request costs.
    Nothing is appended, nothing stays on the thread's stack."""
    assert not profiler.profiler_enabled()
    before = len(profiler.get_events())
    eng = _engine(gpt_tiny)
    h = eng.submit(_prompt(20), max_new_tokens=2)
    _drain(eng)
    _assert_tiles(h)
    assert len(profiler.get_events()) == before
    assert profiler._T.stack == []
    with profiler.RecordEvent("idle") as ev:
        ev.set(slot=3)                  # as `admit` does, and as cheaply
        assert ev.begin is None
    assert len(profiler.get_events()) == before


# ---- the four events of a request, on the profiler's clock ----

@pytest.fixture(scope="module")
def traced(gpt_tiny, tmp_path_factory):
    """A `jax.profiler` session round a tiny engine on the wall clock, six
    requests through two slots. Returns (the trace file, the handles, every
    `pdtpu/serve/` event as (name, start_ns, end_ns, stats))."""
    from jax.profiler import ProfileData
    trace_dir = str(tmp_path_factory.mktemp("request_trace"))
    eng = _engine(gpt_tiny, clock=serving.MonotonicClock(),
                  enable_prefix_cache=False)
    warm = eng.submit(_prompt(20, 9), max_new_tokens=2)     # compile
    while eng.has_work():
        eng.pump()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # what the benchmark's Window uses
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        hs = [eng.submit(_prompt(n, n), max_new_tokens=4)
              for n in (5, 40, 20, 70, 33, 16)]
        while eng.has_work():
            eng.pump()
    finally:
        jax.profiler.stop_trace()
    assert warm.future.done() and all(h.future.done() for h in hs)
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("pdtpu/serve/")]
    return path, hs, events


def test_trace_holds_four_events_a_request_in_order_and_nested(traced):
    _, hs, events = traced
    inside = {SPAN_REQUEST_ADMIT: SPAN_SERVE_ADMIT,
              SPAN_REQUEST_FIRST_LAUNCH: SPAN_SERVE_BUILD_ROWS,
              SPAN_REQUEST_FIRST_TOKEN: SPAN_SERVE_COMMIT}
    for h in hs:
        mine = sorted((e for e in events if e[3].get("rid") == h.rid),
                      key=lambda e: e[1])
        assert [e[0] for e in mine] == list(REQUEST_SPANS)
        for name, start, end, _ in mine[1:]:
            assert any(n == inside[name] and s <= start and end <= e
                       for n, s, e, _ in events), name
        submit, admit, launch, token = mine
        assert submit[3]["prompt_tokens"] == h.prompt_len
        stats = token[3]
        assert [stats[k] for k in PHASE_KEYS] == _phases(h)
        assert stats["ttft_ms"] == h.ttft_ms
        assert stats["steps_to_first_token"] == h.steps_to_first_token
        assert stats["chunks"] == -(-h.prompt_len // CHUNK)
        assert stats["step"] - launch[3]["step"] == stats["chunks"] - 1
        assert admit[3]["queued_ms"] == stats["queued_ms"]
        assert launch[3]["bound_ms"] == stats["bound_ms"]
        # the events lie where the stamps were taken: the differences of
        # their timestamps are the phases, to 2 ms
        ms = 1e-6
        assert (admit[1] - submit[1]) * ms == pytest.approx(
            stats["queued_ms"], abs=2)
        assert (launch[1] - admit[1]) * ms == pytest.approx(
            stats["bound_ms"], abs=2)
        assert (token[1] - launch[1]) * ms == pytest.approx(
            stats["prefill_ms"] + stats["first_fetch_ms"], abs=2)
        assert (token[1] - submit[1]) * ms == pytest.approx(
            h.ttft_ms, abs=2)


def test_the_benchmarks_reader_gives_the_same_means(traced):
    """`benchmark/trace/request_spans.py` on that trace: every request's
    first token, the means the per-layer metrics report, and the launches'
    vacant slots."""
    from benchmark.trace import request_spans as Q
    path, hs, events = traced
    raw = Q.read_xplane(path)
    assert {r["rid"] for r in raw["requests"]} == {h.rid for h in hs}
    assert raw["submits_inside"] == len(hs)
    s = Q.summarize(raw)
    assert s["requests"] == len(hs)
    for key in PHASE_KEYS:
        assert s[key]["mean"] == pytest.approx(
            np.mean([h.ttft_phases_ms[key] for h in hs]))
    assert s["sum_of_means_ms"] == pytest.approx(
        np.mean([h.ttft_ms for h in hs]))
    assert s["sum_of_means_ms"] == pytest.approx(s["ttft_ms"]["mean"])
    assert s[Q.STEPS]["mean"] == pytest.approx(
        np.mean([h.steps_to_first_token for h in hs]))
    dispatches = [e for e in events if e[0] == SPAN_SERVE_DISPATCH]
    assert s["dispatches"] == len(dispatches)
    assert s["vacant_slot_steps"] == sum(
        e[3]["slots_vacant_queued"] for e in dispatches) >= 1
