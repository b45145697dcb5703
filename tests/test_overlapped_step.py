"""One unified step in flight: `LLMEngine` launches step k+1, from the rows
step k will commit and with step k's selected tokens fed back on the
device, before it fetches and commits step k (`_launch` / `_retire`,
`_step_pass`). Pinned here, on the CPU under a `SimClock`:

(i)   token streams and log-probabilities are bit-identical with the step
      launched ahead and in the synchronous order of the same two functions;
(ii)  a request that ends by EOS while its row rides the step in flight has
      that row discarded, and a request admitted into its slot before that
      step retires gets none of its tokens (and, on a model with recurrent
      layers, starts from a zero state);
(iii) a row at its `max_new_tokens` is not scheduled again;
(iv)  a draft model and a grammar row keep the synchronous order;
(v)   a dispatch that fails ahead of its predecessor commits that
      predecessor exactly once and is retried synchronously;
(vi)  `stop()` and `evacuate()` with a step in flight leave no future
      unresolved and no slot leaked;
(vii) a cached prompt's pages are intact after the freed row's stray write.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate
from paddle_tpu.profiler import (SPAN_SERVE_DISPATCH, SPAN_SERVE_FETCH,
                                 SPAN_SERVE_PUMP)
from paddle_tpu.serving.llm.sampling import SamplingParams
from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan


@pytest.fixture(scope="module")
def gpt_tiny():
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    set_global_plan(None)
    yield
    set_global_plan(None)


def _engine(model, draft=None, fault_plan=None, **kw):
    cfg = dict(num_slots=3, block_len=8, n_blocks=8, max_queue_depth=128,
               enable_prefix_cache=False)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**cfg),
                             clock=serving.SimClock(), draft_model=draft,
                             fault_plan=fault_plan)


def _prompts(lengths, vocab=500, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lengths]


def _reference(model, prompt, n, **kw):
    out = generate(model, prompt[None, :], max_new_tokens=n, **kw)
    return np.asarray(out.numpy())[0, len(prompt):]


def _drain(eng):
    passes = 0
    while eng.has_work():
        eng.pump()
        passes += 1
        assert passes < 2000, "engine failed to converge"


def _drain_synchronously(eng):
    """Today's order from the same two functions: admit, launch, retire."""
    passes = 0
    while eng.has_work():
        eng._admit()
        rec = eng._launch()
        if rec is not None:
            eng._retire(rec)
        passes += 1
        assert passes < 2000, "engine failed to converge"


def _counters(eng):
    return eng.metrics.snapshot()


# ---- (i) both orders give the same streams ----------------------------------

@pytest.mark.parametrize("slots", [3, 40], ids=["unpacked", "packed"])
def test_streams_are_bit_identical_launched_ahead_and_in_todays_order(
        gpt_tiny, slots):
    """A mixed prefill + decode load with slot turnover (more requests
    than slots, prompts of one to three chunks), greedy and seeded
    sampling rows side by side: tokens AND log-probabilities agree bit for
    bit between the pass that launches ahead and launch, retire."""
    lengths = [5, 20, 33, 9, 17, 3, 40, 12] * (1 if slots == 3 else 6)
    prompts = _prompts(lengths)
    sampling = [None if i % 3 else SamplingParams(
        temperature=0.9, top_k=16, top_p=0.95, seed=4242 + i)
        for i in range(len(prompts))]
    runs = []
    for drain in (_drain, _drain_synchronously):
        eng = _engine(gpt_tiny, num_slots=slots)
        assert (eng.step_tokens < slots * 16) == (slots == 40)
        handles = [eng.submit(p, max_new_tokens=3 + i % 5, logprobs=True,
                              sampling=sp)
                   for i, (p, sp) in enumerate(zip(prompts, sampling))]
        drain(eng)
        runs.append(([np.asarray(h.result(0)) for h in handles],
                     [h.logprobs_so_far() for h in handles],
                     _counters(eng)))
        assert eng._step()._cache_size() == 1      # one executable still
        eng.pool.check_balance()
        eng.stop()
    (toks_a, lps_a, ahead), (toks_s, lps_s, sync) = runs
    for i, (a, s) in enumerate(zip(toks_a, toks_s)):
        np.testing.assert_array_equal(a, s)
        assert len(a) == 3 + i % 5
    assert lps_a == lps_s                          # floats, bit for bit
    for i, p in enumerate(prompts[:8]):
        if sampling[i] is None:
            np.testing.assert_array_equal(
                toks_a[i], _reference(gpt_tiny, p, 3 + i % 5))
    # every step but the first was launched ahead; today's order never is
    assert ahead["steps_overlapped"] == ahead["unified_steps"] - 1
    assert sync["steps_overlapped"] == 0
    assert ahead["rows_discarded"] == sync["rows_discarded"] == 0
    assert ahead["step_tokens_live"] == sync["step_tokens_live"]
    text = eng.metrics.render()
    assert "pdtpu_llm_steps_overlapped_total 0" in text
    assert "pdtpu_llm_rows_discarded_total 0" in text


def test_a_steady_pass_dispatches_the_next_step_before_it_fetches(gpt_tiny):
    eng = _engine(gpt_tiny)
    eng.submit(_prompts([6])[0], max_new_tokens=6)
    profiler.start_profiler()
    try:
        _drain(eng)
        events = [e for e in profiler.get_events()
                  if e["name"].startswith("pdtpu/serve/")]
    finally:
        profiler._SINK.enabled = False
    pumps = [e for e in events if e["name"] == SPAN_SERVE_PUMP]
    first, steady = pumps[0], pumps[2]

    def kids(pump, name):
        return [e for e in events if e["name"] == name
                and e["args"]["parent"] == pump["args"]["id"]]

    # the first pass has nothing in flight: launch, launch ahead, retire
    assert [e["args"]["in_flight"]
            for e in kids(first, SPAN_SERVE_DISPATCH)] == [0, 1]
    (dispatch,), (fetch,) = (kids(steady, SPAN_SERVE_DISPATCH),
                             kids(steady, SPAN_SERVE_FETCH))
    assert dispatch["args"]["in_flight"] == 1
    assert dispatch["ts"] + dispatch["dur"] <= fetch["ts"] + 1e-3
    eng.stop()


# ---- (ii) a request that ends while its row is in flight --------------------

def _eos_of(model, prompt, n=12):
    """An eos taken from the greedy continuation, and where it ends it."""
    ref = _reference(model, prompt, n)
    eos = int(ref[min(2, len(ref) - 1)])
    return eos, int(np.argmax(ref == eos)), ref


def _run_until_done(eng, handle):
    passes = 0
    while not handle.future.done():
        eng.pump()
        passes += 1
        assert passes < 200


def test_eos_row_in_flight_is_discarded_and_its_slot_reused(gpt_tiny):
    prompt, other = _prompts([8, 11], seed=3)
    eos, j, ref = _eos_of(gpt_tiny, prompt)
    eng = _engine(gpt_tiny, num_slots=1)
    old = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    _run_until_done(eng, old)
    np.testing.assert_array_equal(old.result(0), ref[:j + 1])
    # the step launched before the host saw the eos still carries the row
    stray = eng._inflight
    assert stray is not None and list(stray.reqs) == [0]
    assert eng.pool.free_slots() == 1
    new = eng.submit(other, max_new_tokens=5)
    eng.pump()              # admits into slot 0, THEN retires the stray step
    assert _counters(eng)["rows_discarded"] == 1
    assert old.tokens_so_far() == list(ref[:j + 1])
    assert new.tokens_so_far() == []          # its first chunk is in flight
    assert eng._active[0].handle is new
    _drain(eng)
    np.testing.assert_array_equal(new.result(0),
                                  _reference(gpt_tiny, other, 5))
    assert _counters(eng)["rows_discarded"] == 1
    eng.pool.check_balance()
    eng.stop()


def test_reused_slot_starts_its_recurrent_state_from_zero():
    """granitemoehybrid, tiny: the stray step advanced the freed slot's
    conv and ssm state; the request admitted into the slot before that
    step retires must still equal `generate()`."""
    from test_granitemoehybrid import _model
    model = _model()
    prompt, other = _prompts([9, 14], vocab=128, seed=5)
    eos, j, ref = _eos_of(model, prompt, 10)
    eng = _engine(model, num_slots=1)
    assert eng.pool.recurrent
    old = eng.submit(prompt, max_new_tokens=10, eos_token_id=eos)
    _run_until_done(eng, old)
    assert eng._inflight is not None
    # the slot's state is what the ended row and the stray step left there
    # (so starting from zero is what saves the new row, not a clean slot)
    conv, ssm_state = eng.pool.slabs[0]
    assert eng.pool.layer_kinds[0] == "recurrent"
    assert float(np.abs(np.asarray(ssm_state[0])).max()) > 0
    new = eng.submit(other, max_new_tokens=6)
    _drain(eng)
    np.testing.assert_array_equal(old.result(0), ref[:j + 1])
    np.testing.assert_array_equal(new.result(0), _reference(model, other, 6))
    snap = _counters(eng)
    assert snap["rows_discarded"] == 1
    assert snap["recurrent_rows_started"] == 2
    eng.stop()


# ---- (iii) a row at its cap is not scheduled again --------------------------

def test_a_row_at_its_cap_rides_no_further_step(gpt_tiny):
    prompts = _prompts([5, 20, 9, 17])
    caps = [1, 2, 4, 7]
    eng = _engine(gpt_tiny, num_slots=4)
    handles = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, caps)]
    profiler.start_profiler()
    try:
        _drain(eng)
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    for p, n, h in zip(prompts, caps, handles):
        np.testing.assert_array_equal(h.result(0),
                                      _reference(gpt_tiny, p, n))
    snap = _counters(eng)
    # every position computed is one a stream needed: the prompt, and one
    # per emitted token but the last
    assert snap["step_tokens_live"] == sum(
        len(p) + n - 1 for p, n in zip(prompts, caps))
    assert sum(s["live_tokens"] for s in spans) == snap["step_tokens_live"]
    assert snap["rows_discarded"] == 0
    # no step was launched for nobody: each dispatch was committed
    assert eng._dispatch_idx == snap["unified_steps"] == len(spans)
    assert eng._inflight is None
    eng.stop()


# ---- (iv) what cannot be projected keeps today's order ----------------------

def test_a_draft_model_keeps_todays_order(gpt_tiny):
    eng = _engine(gpt_tiny, draft=gpt_tiny, spec_k=2)
    prompt = _prompts([6])[0]
    h = eng.submit(prompt, max_new_tokens=8)
    _drain(eng)
    np.testing.assert_array_equal(h.result(0),
                                  _reference(gpt_tiny, prompt, 8))
    snap = _counters(eng)
    assert snap["spec_windows"] > 0
    assert snap["steps_overlapped"] == 0 and snap["unified_steps"] > 1
    eng.stop()


def test_a_grammar_row_keeps_todays_order_while_it_rides(gpt_tiny):
    tokens = {1: "{", 2: "}", 3: '"a"', 4: ":", 5: "1", 6: "23", 7: ","}
    grammar = SamplingParams(
        temperature=1.0, seed=7,
        grammar={"schema": {"type": "object",
                            "properties": {"a": {"type": "integer"}},
                            "required": ["a"]},
                 "tokens": tokens})
    eng = _engine(gpt_tiny, num_slots=2)
    prompt = np.arange(1, 9, dtype=np.int32)
    constrained = eng.submit(prompt, max_new_tokens=40, sampling=grammar)
    _run_until_done(eng, constrained)
    text = "".join(tokens[int(t)] for t in constrained.result(0))
    assert text.startswith("{") and text.endswith("}")
    snap = _counters(eng)
    assert snap["unified_steps"] >= 3
    # only a step whose predecessor holds no grammar row is launched ahead:
    # none while the constrained request was the engine's only row
    assert snap["steps_overlapped"] == 0
    plain = eng.submit(prompt, max_new_tokens=6)
    _drain(eng)
    np.testing.assert_array_equal(plain.result(0),
                                  _reference(gpt_tiny, prompt, 6))
    assert _counters(eng)["steps_overlapped"] >= 4
    eng.stop()


# ---- (v) a dispatch that fails ahead of its predecessor ---------------------

def test_a_failed_launch_ahead_retires_its_predecessor_once_and_retries(
        gpt_tiny):
    prompts = _prompts([6, 19])
    # idx 0 the first step, idx 1 launched ahead of it in the same pass;
    # idx 2, launched ahead of idx 1 in the second pass, raises: idx 1 is
    # retired, then idx 3 retries the failed step synchronously
    plan = FaultPlan.from_spec("dispatch_raise@2")
    eng = _engine(gpt_tiny, fault_plan=plan, dispatch_retries=1)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.pump()
    committed = [h.tokens_so_far() for h in handles]
    assert eng._dispatch_idx == 2 and eng.unified_steps == 1
    eng.pump()
    assert plan.log == ["dispatch_raise@2"]
    assert eng._dispatch_idx == 4 and eng.unified_steps == 2
    assert eng._inflight is not None           # the retry, in flight
    # the predecessor was committed once: one step's worth, no more
    for h, before in zip(handles, committed):
        assert len(h.tokens_so_far()) <= len(before) + 1
    _drain(eng)
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(h.result(0),
                                      _reference(gpt_tiny, p, 6))
    snap = _counters(eng)
    assert snap["dispatch_failures"] == {"raise": 1} and snap["failed"] == 0
    # every dispatch but the one that raised became a committed step
    assert snap["unified_steps"] == eng._dispatch_idx - 1
    # the retry ran in today's order; everything after it ahead again
    assert snap["steps_overlapped"] == snap["unified_steps"] - 2
    assert not eng.broken
    eng.pool.check_balance()
    eng.stop()


# ---- (vi) ending the engine with a step in flight ---------------------------

@pytest.mark.parametrize("how", ["drain", "no_drain", "evacuate"])
def test_ending_with_a_step_in_flight_leaves_nothing_behind(gpt_tiny, how):
    prompts = _prompts([6, 19, 30, 4])
    eng = _engine(gpt_tiny, num_slots=2)
    handles = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.pump()
    eng.pump()
    assert eng._inflight is not None and len(eng._active) == 2
    if how == "drain":
        eng.stop(drain=True)
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.result(0),
                                          _reference(gpt_tiny, p, 9))
    elif how == "no_drain":
        eng.stop(drain=False)
        for h in handles:
            with pytest.raises(serving.RejectedError):
                h.result(0)
        assert _counters(eng)["rows_discarded"] == 2
    else:
        assert eng.evacuate("deploy_drain") == 4
        for h in handles:
            with pytest.raises(serving.RejectedError):
                h.result(0)
        assert _counters(eng)["rows_discarded"] == 2
        assert not eng.has_work()
        # the engine serves on, and a swap finds nothing in flight
        eng.replace_params(eng.params, "v2")
        again = eng.submit(prompts[0], max_new_tokens=3)
        _drain(eng)
        np.testing.assert_array_equal(
            again.result(0), _reference(gpt_tiny, prompts[0], 3))
        eng.stop()
    assert all(h.future.done() for h in handles)
    assert eng._inflight is None and not eng._active
    assert eng.pool.active_slots() == 0
    eng.pool.check_balance()


def test_a_swap_is_refused_while_a_step_is_unretired_on_a_threaded_engine(
        gpt_tiny):
    """Under a scheduler thread nobody else may retire the step: the swap
    sees it as work in flight, as `has_work()` does."""
    eng = _engine(gpt_tiny)
    eng._inflight = object()
    eng._thread = object()          # as if `start()` had run
    assert eng.has_work()
    with pytest.raises(serving.llm.llm_engine.WeightSwapError,
                       match="unretired step=True"):
        eng.replace_params(eng.params, "v2")
    eng._inflight = eng._thread = None
    eng.stop()


# ---- (vii) the stray write and the prefix cache -----------------------------

def test_cached_prompt_pages_survive_the_freed_rows_stray_write(gpt_tiny):
    """One full block and a partial tail of the prompt are cached when its
    prefill lands. The request ends by eos; the step in flight still
    writes its row's stripe, at a column past the prompt. The cached
    columns are bit for bit what they were, and a second request over the
    same prompt attaches them and decodes `generate()`'s stream."""
    prompt = _prompts([12], seed=11)[0]
    eos, j, ref = _eos_of(gpt_tiny, prompt)
    eng = _engine(gpt_tiny, num_slots=2, enable_prefix_cache=True)
    first = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    _run_until_done(eng, first)
    assert eng._inflight is not None
    cached = sorted(eng.pool.cached)
    assert len(cached) == 2                    # block 0 whole, 4 of block 1
    widths = [8, 4]
    before = [eng.pool.export_page(p, w) for p, w in zip(cached, widths)]
    eng.pump()                                 # the stray step retires
    assert _counters(eng)["rows_discarded"] == 1 and not eng.has_work()
    after = [eng.pool.export_page(p, w) for p, w in zip(cached, widths)]
    for page_b, page_a in zip(before, after):
        for (kb, vb), (ka, va) in zip(page_b, page_a):
            np.testing.assert_array_equal(kb, ka)
            np.testing.assert_array_equal(vb, va)
    again = eng.submit(prompt, max_new_tokens=6)
    _drain(eng)
    np.testing.assert_array_equal(again.result(0),
                                  _reference(gpt_tiny, prompt, 6))
    assert _counters(eng)["prefix_hit_tokens"] == 11   # all but one token
    eng.pool.check_balance()
    eng.stop()
