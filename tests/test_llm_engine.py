"""Continuous-batching LLM decode engine (ISSUE 5): slot-paged KV pool
accounting, the SimClock acceptance proof (fewer decode iterations than
batch-locked, bit-identical streams), admission control / deadlines on
the serving error vocabulary, LLM metrics exposition, and the subprocess
SIGTERM drain contract for /generate.

Every scheduler test runs the PRODUCTION scheduler (LLMEngine.pump)
under a SimClock — scripted instants, no sleeps, no thread flake."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


# ---- slot-paged KV pool (host-side accounting) ----

def _pool(num_slots=4, block_len=4, n_blocks=2):
    import jax.numpy as jnp
    from paddle_tpu.serving.llm import SlotPagedKVPool

    def init_cache(b, max_len):
        return [(jnp.zeros((b, 2, max_len, 3), jnp.float32),
                 jnp.zeros((b, 2, max_len, 3), jnp.float32))]

    return SlotPagedKVPool(init_cache, num_slots, block_len, n_blocks)


def test_pool_alloc_free_reuse_accounting():
    from paddle_tpu.serving.llm import SlotsExhaustedError
    p = _pool()
    assert p.capacity == 8
    s0 = p.allocate(5)
    assert s0 == 0 and p.active_slots() == 1
    p.set_length(s0, 5)
    assert p.block_table[s0] == [0, 1]     # ceil(5/4) = 2 blocks
    assert p.used_blocks() == 2
    p.free(s0)
    assert p.dirty[s0] and p.free_slots() == 4 and p.used_blocks() == 0
    assert p.allocate(3) == 0              # first-free policy reuses slot 0
    assert p.stats["reuses"] == 1
    with pytest.raises(ValueError, match="capacity"):
        p.allocate(100)                    # can NEVER fit: not exhaustion
    for _ in range(3):
        p.allocate(1)
    with pytest.raises(SlotsExhaustedError):
        p.allocate(1)                      # momentarily full
    assert p.stats["alloc_failures"] == 1
    with pytest.raises(ValueError):
        p.free(0) or p.free(0)             # double free of slot 0
    with pytest.raises(ValueError):
        p.set_length(0, 3)                 # inactive after the free
    snap = p.snapshot()
    assert snap["total_blocks"] == 8 and snap["active_slots"] == 3
    assert snap["allocs"] == 5 and snap["peak_active"] == 4


# ---- what a layer keeps per slot, as the engine reads it (PR 42) ----
# The engine never runs a step here: what it refuses, switches off and
# publishes is decided at construction and at `submit`, from the pool's
# answer (`SlotPagedKVPool.refusal`) about the kinds' table.

class _KeepsPerSlot:
    """`gpt_tiny` to the engine, but `init_cache` answers with one layer of
    `kind` (an entry built by `entry(batch, max_len, window_slab)`) before
    the model's own."""

    def __init__(self, model, entry, windowed=False):
        self._model, self._entry = model, entry
        base = model.init_cache
        if windowed:
            self.init_cache = lambda batch, max_len, dtype=None, \
                window_slab=None: [entry(batch, max_len, window_slab)] \
                + base(batch, max_len, dtype)
        else:
            self.init_cache = lambda batch, max_len, dtype=None: \
                [entry(batch, max_len, None)] + base(batch, max_len, dtype)

    def __getattr__(self, name):
        return getattr(self._model, name)


def _kind_entries():
    import jax.numpy as jnp
    from paddle_tpu.models.generation import (IndexedLatentKV, LatentKV,
                                              RecurrentState, WindowKV)

    def slab(batch, cols, width=4):
        return jnp.zeros((batch, 1, cols, width), jnp.float32)
    return {
        "paged": lambda b, n, w: (slab(b, n), slab(b, n)),
        "recurrent": lambda b, n, w: RecurrentState(
            jnp.zeros((b, 3, 8)), jnp.zeros((b, 4, 8))),
        "window": lambda b, n, w: WindowKV(slab(b, w(32)), slab(b, w(32))),
        "latent": lambda b, n, w: LatentKV(slab(b, n, 6), slab(b, n, 2)),
        "indexed": lambda b, n, w: IndexedLatentKV(
            slab(b, n, 6), slab(b, n, 2), slab(b, n, 3)),
    }


def _refusing_engine(model, draft=None, **cfg):
    from paddle_tpu import serving
    return serving.LLMEngine(
        model, serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=8,
                                       **cfg),
        clock=serving.SimClock(), draft_model=draft)


def _check_engine_refusals(model, target, row, caplog):
    """`target` (a model one of whose layers is of the kind `row`
    describes) through every feature a kind can refuse: refused, or
    switched off, in the row's sentence where the row says so, served
    where it does not."""
    import logging
    from paddle_tpu.models.generation import HOST_TIER, REREAD, REWIND
    layers = f"1 of 3 layers are {row.name} layers"
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving.llm"):
        caplog.clear()
        eng = _refusing_engine(target)
    said = [r.getMessage() for r in caplog.records
            if "enable_prefix_cache is switched off" in r.getMessage()]
    assert eng.pool.layer_kinds == [row.name, "paged", "paged"]
    if REREAD in row.refuses:
        assert len(said) == 1 and said[0].endswith(row.why)
        assert layers in said[0]
        assert eng.enable_prefix_cache is False and eng.prefix_cache is None
        with pytest.raises(ValueError, match="kv_row with a model") as e:
            eng.submit(np.arange(1, 13, dtype=np.int32), max_new_tokens=2,
                       kv_row={"block_len": 8, "length": 8, "layers": []})
        assert str(e.value).endswith(row.why) and layers in str(e.value)
    else:
        assert not said and eng.prefix_cache is not None
        with pytest.raises(ValueError, match="block_len"):   # past the kind
            eng.submit(np.arange(1, 13, dtype=np.int32), max_new_tokens=2,
                       kv_row={"block_len": 4, "length": 8, "layers": []})
    assert eng.config.enable_prefix_cache is True
    if HOST_TIER in row.refuses:
        with pytest.raises(ValueError, match="host_kv_bytes > 0") as e:
            _refusing_engine(target, host_kv_bytes=1 << 20)
        assert str(e.value).endswith(row.why) and layers in str(e.value)
    else:
        assert _refusing_engine(target, host_kv_bytes=1 << 20).host_kv \
            is not None
    for which, (tgt, draft) in {"target": (target, model),
                                "draft": (model, target)}.items():
        if REWIND in row.refuses:
            with pytest.raises(ValueError,
                               match=f"draft_model with a {which}") as e:
                _refusing_engine(tgt, draft=draft)
            assert str(e.value).endswith(row.why) and layers in str(e.value)
        else:
            assert _refusing_engine(tgt, draft=draft).draft_pool is not None
    return eng


@pytest.mark.parametrize("kind", ["paged", "recurrent", "window", "latent",
                                  "indexed"])
def test_engine_refuses_what_a_kinds_row_refuses_in_its_words(
        gpt_tiny, kind, caplog):
    from types import SimpleNamespace
    from paddle_tpu.models.generation import CACHE_KINDS
    row = next(r for r in CACHE_KINDS.values() if r.name == kind)
    target = _KeepsPerSlot(gpt_tiny, _kind_entries()[kind],
                           windowed=kind == "window")
    if kind == "indexed":       # what the engine asks of a sparse model
        target.config = SimpleNamespace(
            vocab_size=gpt_tiny.config.vocab_size, index_topk=16,
            indexer_types=["full", "shared", "shared"])
    eng = _check_engine_refusals(gpt_tiny, target, row, caplog)
    # the bytes the engine publishes are the pool's, under the row's labels
    snap = eng.metrics.snapshot()
    assert snap["recurrent_state_bytes"] == (
        eng.pool.recurrent_state_bytes if kind == "recurrent" else None)
    assert snap["kv_pool_bytes"] == (
        None if kind in ("paged", "recurrent") else eng.pool.kv_bytes())
    assert (eng._sparse is not None) == (kind == "indexed")


def test_engine_takes_a_kind_it_has_never_seen_from_the_table(
        gpt_tiny, monkeypatch, caplog):
    """A NamedTuple defined here and its row of the table: the engine
    refuses by name what the row refuses and publishes its bytes under its
    label, with `llm_engine.py` and `kv_pool.py` as they are."""
    import jax.numpy as jnp
    from typing import NamedTuple
    from paddle_tpu.models import generation
    from paddle_tpu.models.generation import (HOST_TIER, REREAD, REWIND,
                                              CacheKind)

    class MatrixState(NamedTuple):
        m: object

    row = CacheKind(
        "matrix", ("matrix",), frozenset({REREAD, HOST_TIER, REWIND}),
        "a matrix state sums every token the row has seen and keeps none "
        "of them")
    monkeypatch.setitem(generation.CACHE_KINDS, MatrixState, row)
    target = _KeepsPerSlot(
        gpt_tiny, lambda b, n, w: MatrixState(jnp.zeros((b, 5, 5))))
    eng = _check_engine_refusals(gpt_tiny, target, row, caplog)
    assert eng.metrics.snapshot()["kv_pool_bytes"]["matrix"] == 2 * 5 * 5 * 4
    with pytest.raises(NotImplementedError, match="export_rows on a pool"):
        eng.pool.export_rows([eng.pool.allocate(8)])


# ---- the acceptance proof (SimClock, threadless, provable) ----

def test_continuous_batching_beats_batch_locked_bit_identically(gpt_tiny):
    """16 requests with mixed prompt/output lengths through a 4-slot pool,
    staggered arrivals: total decode iterations must be <= 60% of the
    batch-locked equivalent, every per-request stream must equal one-shot
    greedy generate() bit-for-bit, and slot reuse must be exact."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate

    COMBOS = [(4, 16), (6, 2), (10, 2), (12, 2)]   # (prompt_len, new_len)
    N_ROUNDS = 4
    rng = np.random.RandomState(0)
    requests = [(rng.randint(1, 500, size=(plen,)).astype(np.int32), nlen)
                for _ in range(N_ROUNDS) for plen, nlen in COMBOS]

    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=4, block_len=8, n_blocks=4,
                                max_queue_depth=64),
        clock=clock)
    handles = []
    for prompt, nlen in requests:       # staggered: one pump per arrival
        clock.advance(0.01)
        handles.append(eng.submit(prompt, max_new_tokens=nlen))
        eng.pump()
    while eng.has_work():
        eng.pump()

    # batch-locked equivalent: the same 16 requests admitted in arrival
    # order as 4 locked batches of 4; each batch decodes until its longest
    # member finishes, paying max(new_len) - 1 iterations (the first token
    # comes from prefill). Every batch here contains one 16-token request.
    batch_locked = sum(max(n for _, n in requests[i:i + 4]) - 1
                      for i in range(0, len(requests), 4))
    assert batch_locked == 60
    assert eng.decode_iterations <= 0.6 * batch_locked, (
        eng.decode_iterations, batch_locked)

    # slot churn is exact, not approximate: every request got a slot, all
    # four slots saw a first (clean) use, every later alloc reused one
    stats = eng.pool.stats
    assert stats["allocs"] == 16 and stats["frees"] == 16
    assert stats["peak_active"] == 4
    assert stats["reuses"] == 16 - 4
    assert eng.pool.active_slots() == 0

    # bit-identity: batch the four requests sharing each combo into ONE
    # batch-locked generate() call; each continuous-batched stream must
    # equal its row exactly (same jitted numeric path, exact-zero masking)
    for ci, (plen, nlen) in enumerate(COMBOS):
        idxs = [r * len(COMBOS) + ci for r in range(N_ROUNDS)]
        prompts = np.stack([requests[i][0] for i in idxs])
        ref = np.asarray(generate(gpt_tiny, prompts,
                                  max_new_tokens=nlen).numpy())[:, plen:]
        for row, i in enumerate(idxs):
            got = handles[i].result(timeout=0)
            assert np.array_equal(got, ref[row]), (i, got, ref[row])
            assert handles[i].ttft_ms is not None
            assert handles[i].ttft_ms >= 0

    snap = eng.metrics.snapshot()
    assert snap["completed"] == 16 and snap["prefills"] == 16
    assert snap["decode_steps"] == eng.decode_iterations
    assert snap["slots_active"] == 0 and snap["slots_total"] == 4
    eng.stop()


def test_the_steps_tail_on_emission_rows_serves_what_the_block_served(
        gpt_tiny):
    """PR 52: the default engine (4 slots x 16, nothing packed, prefix
    cache on) runs the head, the selection and the log-softmax on its 4
    emission rows, not on 64 positions. Staggered arrivals, prompts that
    share a cached prefix (only the tail is prefilled), chunks of 16, 3 and
    1 columns, decode rows launched ahead through `feed`, free slots: every
    stream and first token is what the step with its tail on the whole
    block gives and what `generate()` gives, the log-probabilities to
    float32 rounding, and `head_positions` counts 4 a step."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from test_packed_step import block_tail_step

    rng = np.random.RandomState(2)
    shared = rng.randint(1, 500, size=(16,)).astype(np.int32)
    prompts = [rng.randint(1, 500, size=(n,)).astype(np.int32)
               for n in (19, 4, 33, 17)]
    prompts += [np.concatenate([shared, t]) for t in
                (prompts[0][:3], prompts[1][:1], prompts[2][:9])]
    news = [5, 9, 3, 7, 4, 6, 8]
    runs = []
    for block_tail in (False, True):
        clock = serving.SimClock()
        eng = serving.LLMEngine(
            gpt_tiny,
            serving.LLMEngineConfig(num_slots=4, block_len=8, n_blocks=8,
                                    max_queue_depth=64),
            clock=clock)
        if block_tail:
            eng._step_jit = block_tail_step(eng)
        handles = []
        for prompt, n in zip(prompts, news):
            clock.advance(0.01)
            handles.append(eng.submit(prompt, max_new_tokens=n,
                                      logprobs=True))
            eng.pump()
        while eng.has_work():
            eng.pump()
        snap = eng.metrics.snapshot()
        assert snap["prefix_hit_tokens"] >= 16
        assert snap["steps_overlapped"] > 0
        assert snap["head_positions"] == 4 * snap["unified_steps"]
        assert snap["step_tokens_computed"] == 64 * snap["unified_steps"]
        runs.append([(h.tokens_so_far(), h.logprobs_so_far())
                     for h in handles])
        eng.stop()
    for (toks, lps), (want, want_lps), prompt, n in zip(*runs, prompts,
                                                       news):
        assert toks == want and len(lps) == n
        np.testing.assert_allclose(lps, want_lps, rtol=1e-5)
        ref = np.asarray(generate(gpt_tiny, prompt[None],
                                  max_new_tokens=n).numpy())[0, len(prompt):]
        assert np.array_equal(toks, ref)


def test_eos_retires_row_early_and_frees_its_slot(gpt_tiny):
    """A per-request eos ends the stream at the token that emitted it; the
    slot frees immediately (no decode-to-max), matching generate()'s
    early-exit semantics row-for-row."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate

    prompt = np.arange(1, 9, dtype=np.int32)
    ref = np.asarray(generate(gpt_tiny, prompt[None, :],
                              max_new_tokens=12).numpy())[0, 8:]
    # pick the eos from the greedy continuation itself (tiny random models
    # may loop on one token, so resolve to its FIRST occurrence)
    eos = int(ref[min(2, len(ref) - 1)])
    j = int(np.argmax(ref == eos))         # index where the stream must end

    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4), clock=clock)
    h = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    while eng.has_work():
        eng.pump()
    got = h.result(timeout=0)
    assert got.shape == (j + 1,) and got[-1] == eos
    assert np.array_equal(got, ref[:j + 1])
    # one iteration per post-prefill token, and the step that was launched
    # before the host saw the eos: its row is discarded
    assert eng.decode_iterations == j + 1
    snap = eng.metrics.snapshot()
    assert snap["rows_discarded"] == 1 and snap["tokens_out"] == j
    assert eng.pool.free_slots() == 1      # retired row released its slot
    ref_eos = generate(gpt_tiny, prompt[None, :], max_new_tokens=12,
                       eos_token_id=eos)
    # one-shot generate() early-exits identically and pads the tail with eos
    assert gpt_tiny._last_decode_steps == j
    assert np.all(np.asarray(ref_eos.numpy())[0, 8 + j + 1:] == eos)
    eng.stop()


# ---- admission control and deadlines (serving error vocabulary) ----

@pytest.mark.fault_matrix
def test_slot_exhaustion_queues_then_rejects_and_recovers(gpt_tiny):
    """Injected fault: more work than slots + queue can hold. Contract:
    exhausted slots mean QUEUEING (never an exception), the full queue
    means RejectedError, an impossible sequence is rejected outright —
    and a drain still finishes every admitted sequence."""
    from paddle_tpu import serving
    from paddle_tpu.serving.llm import SlotsExhaustedError

    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=2, block_len=8,
                                          n_blocks=4, max_queue_depth=2),
        clock=clock)
    decoding = [eng.submit([i + 1, i + 2], max_new_tokens=6)
                for i in range(2)]
    eng.pump()
    assert eng.pool.free_slots() == 0      # both slots decoding
    queued = [eng.submit([9, 9], max_new_tokens=2) for _ in range(2)]
    with pytest.raises(serving.RejectedError, match="queue at capacity"):
        eng.submit([7], max_new_tokens=2)
    with pytest.raises(serving.RejectedError, match="slot capacity"):
        eng.submit(list(range(1, 30)), max_new_tokens=8)  # 29 + 8 > 32
    with pytest.raises(SlotsExhaustedError):
        eng.pool.allocate(4)               # the raw pool DOES throw
    assert eng.pool.stats["alloc_failures"] == 1

    eng.stop(drain=True)                   # recovery: drain runs it all out
    for h, n in zip(decoding + queued, (6, 6, 2, 2)):
        assert len(h.result(timeout=0)) == n
    assert eng.metrics.reject_reasons == {"queue_full": 1,
                                          "prompt_too_long": 1}
    snap = eng.metrics.snapshot()
    assert snap["completed"] == 4 and snap["rejected"] == 2
    assert snap["queue_depth"] == 0 and snap["slots_active"] == 0


def test_queued_deadline_drops_before_prefill(gpt_tiny):
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4), clock=clock)
    hog = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.pump()                             # hog owns THE slot
    doomed = eng.submit([4, 5], max_new_tokens=4, deadline_ms=5.0)
    clock.advance(0.01)                    # 10ms > 5ms, still queued
    eng.pump()
    with pytest.raises(serving.DeadlineExceededError, match="before prefill"):
        doomed.result(timeout=0)
    assert doomed.tokens_so_far() == []    # never prefilled
    while eng.has_work():
        eng.pump()
    assert hog.result(timeout=0).shape == (8,)   # unaffected
    assert eng.metrics.snapshot()["expired"] == 1
    eng.stop()


def test_mid_decode_eviction_keeps_partial_tokens(gpt_tiny):
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4), clock=clock)
    h = eng.submit([1, 2, 3, 4], max_new_tokens=16, deadline_ms=50.0)
    eng.pump()                             # prefill chunk lands: tok0, t=0
    clock.advance(0.1)                     # blow the deadline mid-stream
    eng.pump()                             # decodes once more, then evicts
    with pytest.raises(serving.DeadlineExceededError, match="evicted"):
        h.result(timeout=0)
    partial = h.tokens_so_far()
    assert 1 <= len(partial) < 16          # stream stays readable
    eng.pump()                             # the step launched before the
    #                                        eviction retires: row discarded
    assert h.tokens_so_far() == partial
    assert eng.metrics.snapshot()["rows_discarded"] == 1
    assert not eng.has_work()
    h2 = eng.submit([5, 6], max_new_tokens=2)   # slot came back
    while eng.has_work():
        eng.pump()
    assert len(h2.result(timeout=0)) == 2
    eng.stop()


def test_stop_without_drain_rejects_in_flight(gpt_tiny):
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4), clock=clock)
    h1 = eng.submit([1, 2], max_new_tokens=8)
    eng.pump()
    h2 = eng.submit([3, 4], max_new_tokens=8)   # queued behind h1
    eng.stop(drain=False)
    for h in (h1, h2):
        with pytest.raises(serving.RejectedError, match="shut down"):
            h.result(timeout=0)
    assert h1.tokens_so_far()              # partial tokens survive shutdown
    with pytest.raises(serving.RejectedError, match="draining"):
        eng.submit([5], max_new_tokens=2)
    assert eng.pool.active_slots() == 0


def test_start_refuses_sim_clock(gpt_tiny):
    from paddle_tpu import serving
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4),
        clock=serving.SimClock())
    with pytest.raises(RuntimeError, match="SimClock"):
        eng.start()


# ---- metrics exposition ----

def test_llm_metrics_prometheus_round_trip():
    """render() -> parse_exposition() preserves the LLM families, and the
    pdtpu_llm prefix keeps them disjoint from a predictor engine's
    pdtpu_serving families on a shared /metrics endpoint."""
    from paddle_tpu import serving
    m = serving.LLMMetrics()
    m.on_submit(2)
    m.on_prefill(12.5)
    m.on_decode_step(3, 2.0)
    m.on_decode_step(2, 1.0)
    m.on_complete(40.0)
    m.on_reject("queue_full")
    m.set_slots(3, 4)
    flat = serving.parse_exposition(m.render())
    assert flat["pdtpu_llm_slots_active"] == 3
    assert flat["pdtpu_llm_slots_total"] == 4
    assert flat["pdtpu_llm_slot_occupancy"] == 0.75
    assert flat["pdtpu_llm_tokens_total"] == 5
    assert flat["pdtpu_llm_decode_steps_total"] == 2
    assert flat["pdtpu_llm_prefills_total"] == 1
    # 5 tokens over 3ms of decode wall time
    assert flat["pdtpu_llm_tokens_per_s"] == pytest.approx(5 / 3e-3,
                                                           rel=1e-3)
    assert flat['pdtpu_llm_ttft_ms{quantile="0.5"}'] == 12.5
    assert flat['pdtpu_llm_intertoken_ms{quantile="0.5"}'] == 1.0
    assert flat['pdtpu_llm_intertoken_ms{quantile="0.99"}'] == 2.0
    assert flat['pdtpu_llm_requests_total{outcome="completed"}'] == 1
    assert flat['pdtpu_llm_requests_total{outcome="rejected"}'] == 1
    assert not any(k.startswith("pdtpu_serving_") for k in flat)


# ---- supervision + failure protocol (ISSUE 6 fault matrix) ----
# Every scenario is deterministic: faults fire at exact dispatch/submit
# indices from a programmatic FaultPlan, the engine runs threadless under
# a SimClock, and the proofs are exact (bit-identical survivor streams,
# balanced KV-pool slot ledger, no unresolved futures).


def _sup_engine(gpt_tiny, plan, clock, **cfg_kw):
    from paddle_tpu import serving
    kw = dict(num_slots=2, block_len=8, n_blocks=4)
    kw.update(cfg_kw)
    return serving.LLMEngine(gpt_tiny, serving.LLMEngineConfig(**kw),
                             clock=clock, fault_plan=plan)


def _drain_all(eng):
    while eng.has_work():
        eng.pump()


@pytest.mark.fault_matrix
def test_dispatch_raise_mid_decode_retries_bit_identically(gpt_tiny):
    """Transient decode failure: dispatch_raise fires once inside the 2nd
    decode attempt; the supervised retry succeeds and every stream is
    bit-identical to a fault-free run (the fault raises before the jitted
    call commits, so no state was corrupted). The slot ledger balances and
    the breaker never charges (retry succeeded)."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(11, 15, dtype=np.int32)]
    ref = np.asarray(generate(gpt_tiny, np.stack(prompts),
                              max_new_tokens=6).numpy())[:, 4:]
    # dispatch indices: 0 = the mixed prefill step (both rows, tok0 out),
    # 1/2 = decodes (ok), 3 = decode (raises once), 4 = retry (succeeds)
    plan = FaultPlan.from_spec("dispatch_raise@3")
    eng = _sup_engine(gpt_tiny, plan, serving.SimClock())
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drain_all(eng)
    for h, r in zip(handles, ref):
        assert np.array_equal(h.result(timeout=0), r)
    assert plan.log == ["dispatch_raise@3"]
    assert eng.supervisor.stats["dispatch_failures"] == 1
    assert not eng.broken
    snap = eng.metrics.snapshot()
    assert snap["dispatch_failures"] == {"raise": 1}
    assert snap["completed"] == 2 and snap["failed"] == 0
    assert snap["submitted"] == snap["completed"]
    eng.pool.check_balance()
    eng.stop()


@pytest.mark.fault_matrix
def test_dispatch_hang_maps_to_watchdog_and_recovers(gpt_tiny):
    """Hung decode: dispatch_hang arrives as the supervisor's
    DispatchHungError watchdog path (zero real sleeping under SimClock);
    the retry succeeds and the stream is bit-identical."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    prompt = np.arange(1, 7, dtype=np.int32)
    ref = np.asarray(generate(gpt_tiny, prompt[None, :],
                              max_new_tokens=5).numpy())[0, 6:]
    # idx 0 = prefill, idx 1 = first decode "hangs", idx 2 = retry
    plan = FaultPlan.from_spec("dispatch_hang@1:30.0")
    eng = _sup_engine(gpt_tiny, plan, serving.SimClock(), num_slots=1)
    h = eng.submit(prompt, max_new_tokens=5)
    _drain_all(eng)
    assert np.array_equal(h.result(timeout=0), ref)
    assert eng.supervisor.stats["watchdog_fires"] == 1
    assert eng.metrics.snapshot()["dispatch_failures"] == {"hang": 1}
    assert not eng.broken
    eng.pool.check_balance()
    eng.stop()


@pytest.mark.fault_matrix
def test_poisoned_prefill_quarantines_only_its_request(gpt_tiny):
    """poison_request fires on EVERY dispatch carrying submit-index 0:
    its prefill chunk fails all dispatch_retries+1 attempts, the request is
    quarantined (typed reason 'poisoned', slot freed, breaker absolved)
    and the innocent request streams bit-identically."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(21, 25, dtype=np.int32)]
    ref1 = np.asarray(generate(gpt_tiny, prompts[1][None, :],
                               max_new_tokens=4).numpy())[0, 4:]
    plan = FaultPlan.from_spec("poison_request@0")
    eng = _sup_engine(gpt_tiny, plan, serving.SimClock())
    bad = eng.submit(prompts[0], max_new_tokens=4)      # submit idx 0
    good = eng.submit(prompts[1], max_new_tokens=4)     # submit idx 1
    _drain_all(eng)
    with pytest.raises(serving.DispatchFailedError,
                       match="quarantined") as exc:
        bad.result(timeout=0)
    assert exc.value.reason == "poisoned"
    assert bad.tokens_so_far() == []                    # never prefilled
    assert np.array_equal(good.result(timeout=0), ref1)
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["failed"] == 1
    assert snap["completed"] == 1
    # invariant: every submitted request is accounted for exactly once
    assert snap["submitted"] == (snap["completed"] + snap["rejected"]
                                 + snap["expired"] + snap["failed"])
    assert eng.supervisor.stats["quarantines"] == 1
    assert not eng.broken                               # absolved
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_decode_poison_blame_isolation_quarantines_culprit(gpt_tiny):
    """poison_request@1:decode survives prefill and poisons every decode
    carrying submit-index 1. The whole-batch retries exhaust, the blame
    probes (solo masked dispatches, results discarded) implicate exactly
    request 1, it is quarantined mid-stream, and the survivor's FULL
    stream is bit-identical to a fault-free run — the probes committed
    nothing."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(11, 15, dtype=np.int32)]
    ref0 = np.asarray(generate(gpt_tiny, prompts[0][None, :],
                               max_new_tokens=6).numpy())[0, 4:]
    plan = FaultPlan.from_spec("poison_request@1:decode")
    eng = _sup_engine(gpt_tiny, plan, serving.SimClock())
    survivor = eng.submit(prompts[0], max_new_tokens=6)  # submit idx 0
    poisoned = eng.submit(prompts[1], max_new_tokens=6)  # submit idx 1
    _drain_all(eng)
    assert np.array_equal(survivor.result(timeout=0), ref0)
    with pytest.raises(serving.DispatchFailedError, match="isolation") as exc:
        poisoned.result(timeout=0)
    assert exc.value.reason == "poisoned"
    # it DID prefill (poison was decode-scoped): first token is readable
    assert len(poisoned.tokens_so_far()) >= 1
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["completed"] == 1
    assert snap["submitted"] == (snap["completed"] + snap["rejected"]
                                 + snap["expired"] + snap["failed"])
    assert not eng.broken
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_repeated_engine_failures_trip_circuit_breaker(gpt_tiny):
    """Non-attributable decode failures (the raise reproduces for EVERY
    blame probe, so no single request is implicated) charge the breaker;
    at breaker_threshold consecutive engine-level failures it opens
    terminally: active+queued requests fail typed, new submits reject
    with reason 'circuit_open', on_break fires exactly once."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan

    # round 1: idx 0 = prefill step (ok, tok0 out), idx 1 = decode raises
    # (dispatch_retries=0), blame probes idx 2 and 3 raise too ->
    # unattributable -> engine failure #1.
    # round 2: idx 4 prefill ok, idx 5 decode + probes 6/7 raise ->
    # engine failure #2 -> breaker opens (threshold 2).
    plan = FaultPlan.from_spec(
        "dispatch_raise@1;dispatch_raise@2;dispatch_raise@3;"
        "dispatch_raise@5;dispatch_raise@6;dispatch_raise@7")
    trips = []
    clock = serving.SimClock()
    from paddle_tpu.serving import LLMEngine, LLMEngineConfig
    eng = LLMEngine(
        gpt_tiny,
        LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4,
                        dispatch_retries=0, breaker_threshold=2),
        clock=clock, fault_plan=plan, on_break=lambda: trips.append(1))
    r0 = [eng.submit([i + 1, i + 2], max_new_tokens=4) for i in range(2)]
    eng.pump()                              # prefill-only step succeeds
    eng.pump()                              # decode fails unattributably
    for h in r0:
        with pytest.raises(serving.DispatchFailedError) as exc:
            h.result(timeout=0)
        assert exc.value.reason == "engine"
    assert not eng.broken                   # one failure, threshold is 2
    r1 = [eng.submit([i + 5, i + 6], max_new_tokens=4) for i in range(2)]
    eng.pump()                              # prefill-only step succeeds
    eng.pump()                              # second unattributable failure
    assert eng.broken and trips == [1]
    for h in r1:
        with pytest.raises(serving.DispatchFailedError) as exc:
            h.result(timeout=0)
        assert exc.value.reason == "engine"
    with pytest.raises(serving.RejectedError, match="circuit") as exc:
        eng.submit([9], max_new_tokens=2)
    assert exc.value.reason == "circuit_open"
    snap = eng.metrics.snapshot()
    assert snap["circuit_open"] is True
    assert snap["failed"] == 4 and snap["quarantined"] == 0
    assert eng.metrics.reject_reasons["circuit_open"] == 1
    assert snap["submitted"] == (snap["completed"] + snap["rejected"]
                                 + snap["expired"] + snap["failed"]) - 1
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_overload_sheds_lowest_class_first(gpt_tiny):
    """Scripted overload: with the queue full, an interactive submit sheds
    the NEWEST queued best_effort request (typed reason 'shed') and is
    admitted; with nothing lower-priority queued the submit rejects
    'queue_full' with a Retry-After hint. Shedding never touches the
    submitter's own class or above."""
    from paddle_tpu import serving

    clock = serving.SimClock()
    eng = _sup_engine(gpt_tiny, None, clock, num_slots=1, max_queue_depth=2)
    hog = eng.submit([1, 2], max_new_tokens=8)
    eng.pump()                              # hog owns THE slot
    be1 = eng.submit([3, 3], max_new_tokens=2, slo="best_effort")
    be2 = eng.submit([4, 4], max_new_tokens=2, slo="best_effort")
    inter = eng.submit([5, 5], max_new_tokens=2, slo="interactive")
    with pytest.raises(serving.RejectedError, match="shed") as exc:
        be2.result(timeout=0)               # newest best_effort was shed
    assert exc.value.reason == "shed"
    assert exc.value.retry_after_s is not None
    # queue full again (be1 + inter): a second interactive sheds be1 —
    # never its own class
    inter2 = eng.submit([6, 6], max_new_tokens=2, slo="interactive")
    with pytest.raises(serving.RejectedError) as exc:
        be1.result(timeout=0)
    assert exc.value.reason == "shed"
    # queue now holds ONLY interactive work: best_effort has nothing below
    # it and interactive will not shed its own class — both reject
    # queue_full with backpressure
    for slo in ("best_effort", "interactive"):
        with pytest.raises(serving.RejectedError, match="queue") as exc:
            eng.submit([7], max_new_tokens=2, slo=slo)
        assert exc.value.reason == "queue_full"
        assert exc.value.retry_after_s is not None
    _drain_all(eng)
    assert hog.result(timeout=0).shape == (8,)
    assert len(inter.result(timeout=0)) == 2
    assert len(inter2.result(timeout=0)) == 2
    snap = eng.metrics.snapshot()
    assert snap["shed"] == 2
    assert snap["classes"]["best_effort"]["shed"] == 2
    assert snap["classes"]["interactive"]["shed"] == 0
    assert eng.metrics.reject_reasons == {"shed": 2, "queue_full": 2}
    assert snap["submitted"] == (snap["completed"] + snap["rejected"]
                                 + snap["expired"] + snap["failed"]) - 2
    eng.pool.check_balance()
    eng.stop()


def test_token_budget_admission_and_shed(gpt_tiny):
    """max_inflight_tokens bounds sum(prompt + max_new_tokens) over
    queued + active; an over-budget high-class submit sheds lower-class
    queued work, an over-budget submit with nothing to shed rejects
    'token_budget'."""
    from paddle_tpu import serving

    clock = serving.SimClock()
    eng = _sup_engine(gpt_tiny, None, clock, num_slots=1,
                      max_inflight_tokens=14)
    active = eng.submit([1, 2], max_new_tokens=6)       # cost 8
    eng.pump()                                          # mid-generation
    be = eng.submit([3, 3], max_new_tokens=2, slo="best_effort")  # cost 4
    assert eng.metrics.inflight_tokens == 12
    inter = eng.submit([5, 5], max_new_tokens=2, slo="interactive")
    with pytest.raises(serving.RejectedError) as exc:   # 16 > budget: shed
        be.result(timeout=0)
    assert exc.value.reason == "shed"
    with pytest.raises(serving.RejectedError, match="token budget") as exc:
        eng.submit([6, 6], max_new_tokens=2, slo="interactive")
    assert exc.value.reason == "token_budget"
    _drain_all(eng)
    assert len(active.result(timeout=0)) == 6
    assert len(inter.result(timeout=0)) == 2
    assert eng.metrics.inflight_tokens == 0             # leak-proof: empty
    eng.pool.check_balance()
    eng.stop()


def test_brownout_caps_admitted_max_new_tokens(gpt_tiny):
    """Queue depth at/above brownout_queue_depth enters brownout: newly
    admitted requests get max_new_tokens capped; the mode exits with
    hysteresis at half the threshold and later submits are uncapped."""
    from paddle_tpu import serving

    clock = serving.SimClock()
    eng = _sup_engine(gpt_tiny, None, clock, num_slots=1,
                      brownout_queue_depth=2, brownout_max_new_tokens=2)
    hog = eng.submit([1, 2], max_new_tokens=6)
    eng.pump()
    q = [eng.submit([3, 3], max_new_tokens=6) for _ in range(2)]
    capped = eng.submit([4, 4], max_new_tokens=6)   # depth 2 >= 2: brownout
    assert eng.metrics.brownout is True
    assert capped.max_new_tokens == 2
    _drain_all(eng)
    assert len(capped.result(timeout=0)) == 2       # capped, not 6
    assert len(hog.result(timeout=0)) == 6
    for h in q:
        assert len(h.result(timeout=0)) == 6        # admitted pre-brownout
    assert eng.metrics.brownout is False            # exited as queue drained
    assert eng.metrics.snapshot()["brownout_entries"] == 1
    uncapped = eng.submit([5, 5], max_new_tokens=6)
    _drain_all(eng)
    assert len(uncapped.result(timeout=0)) == 6
    eng.pool.check_balance()
    eng.stop()


def test_llm_drain_timeout_fails_stragglers_typed(gpt_tiny):
    """stop(drain=True, timeout=) on a wedged engine: the scheduler join
    times out and every straggler — queued AND mid-decode — fails with
    RejectedError(reason='drain_timeout') instead of hanging its client
    forever."""
    from paddle_tpu import serving

    release = threading.Event()
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=1, block_len=8,
                                          n_blocks=4))

    real_step = eng._step()                 # build the real unified step
    calls = []

    def wedged_step(*args):
        if len(calls) < 2:                  # let h1's prefill chunk land:
            calls.append(1)                 # its step, and the one launched
            return real_step(*args)         # ahead before that is retired
        release.wait(60)
        raise RuntimeError("released")
    eng._step_jit = wedged_step             # _step() now returns the wedge

    eng.start()
    h1 = eng.submit([1, 2], max_new_tokens=4)       # will wedge mid-decode
    h2 = eng.submit([3, 4], max_new_tokens=4)       # stuck behind h1
    deadline = time.time() + 30
    while not h1.tokens_so_far() and time.time() < deadline:
        time.sleep(0.01)                            # h1 prefilled (TTFT out)
    assert h1.tokens_so_far(), "prefill never landed"
    eng.stop(drain=True, timeout=0.5)
    for h in (h1, h2):
        with pytest.raises(serving.RejectedError, match="drain") as exc:
            h.result(timeout=0)
        assert exc.value.reason == "drain_timeout"
    assert h1.tokens_so_far()                       # partials stay readable
    assert eng.metrics.reject_reasons["drain_timeout"] == 2
    assert eng.pool.active_slots() == 0
    release.set()                                   # unwedge the daemon


# ---- /generate SIGTERM drain (the fault-matrix scenario) ----

def _start_llm_worker(workdir, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(FIXTURES, "llm_serving_worker.py"),
         str(workdir)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    port_file = os.path.join(str(workdir), "port")
    deadline = time.time() + 300           # model build + jit warmup
    while time.time() < deadline:
        if os.path.exists(port_file):
            return proc, int(open(port_file).read())
        if proc.poll() is not None:
            break
        time.sleep(0.05)
    proc.kill()
    _, err = proc.communicate(timeout=30)
    raise AssertionError(f"llm worker never bound a port: {err[-3000:]}")


@pytest.mark.fault_matrix
def test_sigterm_drains_llm_generate_and_exits_zero(tmp_path):
    """LLM drain contract (docs/serving.md): SIGTERM mid-traffic → new
    /generate requests get 503 or connection-refused, every ADMITTED
    sequence still streams to completion, the process exits 0, and the
    final pdtpu_llm snapshot reconciles with what the clients observed."""
    from paddle_tpu import serving

    proc, port = _start_llm_worker(
        tmp_path, {"LLM_SLOTS": "2", "LLM_MAX_NEW": "12",
                   "PDTPU_FLIGHT_DIR": str(tmp_path)})
    base = f"http://127.0.0.1:{port}"
    lock = threading.Lock()
    oks, rejected, conn_failed = [], [], []

    def client(tid):
        rng = np.random.RandomState(tid)
        t_end = time.time() + 60
        while time.time() < t_end:
            prompt = rng.randint(1, 500, size=rng.randint(2, 7)).tolist()
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"input_ids": prompt,
                                 "max_new_tokens": 8}).encode(),
                method="POST")
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    body = json.loads(r.read())
                assert len(body["tokens"]) == 8
                assert body["ttft_ms"] >= 0
                with lock:
                    oks.append(tid)
            except urllib.error.HTTPError as e:
                assert e.code == 503, e.code   # draining fast-fail only
                with lock:
                    rejected.append(tid)
            except (urllib.error.URLError, ConnectionError, OSError):
                with lock:       # accept loop closed: never admitted
                    conn_failed.append(tid)
                return

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    [t.start() for t in threads]
    deadline = time.time() + 120
    while time.time() < deadline:          # let real decode traffic build
        with lock:
            if len(oks) >= 6:
                break
        time.sleep(0.02)
    proc.send_signal(signal.SIGTERM)       # lands with sequences in flight
    _, err = proc.communicate(timeout=180)
    [t.join(timeout=180) for t in threads]

    assert proc.returncode == 0, err[-3000:]
    assert len(oks) >= 6
    metrics_path = tmp_path / "metrics_final.txt"
    assert metrics_path.exists(), "drain must write the final snapshot"
    flat = serving.parse_exposition(metrics_path.read_text())
    # every client 200 is a completed sequence and vice versa: no admitted
    # request was dropped mid-decode, nothing is left holding a slot
    assert flat['pdtpu_llm_requests_total{outcome="completed"}'] == len(oks)
    assert flat['pdtpu_llm_requests_total{outcome="rejected"}'] == \
        len(rejected)
    assert flat['pdtpu_llm_requests_total{outcome="submitted"}'] == len(oks)
    assert flat["pdtpu_llm_queue_depth"] == 0
    assert flat["pdtpu_llm_slots_active"] == 0

    # ISSUE 9: the SIGTERM handler dumps the flight ring before draining
    dump_path = tmp_path / f"pdtpu_flight_{proc.pid}.json"
    assert dump_path.exists(), "SIGTERM handler must dump the flight ring"
    dump = json.loads(dump_path.read_text())
    assert dump["reason"] == "sigterm"
    assert any(e["kind"] == "sigterm" for e in dump["events"])
