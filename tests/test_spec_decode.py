"""Speculative decoding inside the unified step (ISSUE 17): a draft
model proposes K tokens per decode row from its OWN slot-paged KV pool
(one on-device scan dispatch), the target verifies every position of the
window in ONE unified-step dispatch, and greedy acceptance — longest
matching prefix plus the target's corrective token — makes the output
bit-identical to plain greedy decode BY CONSTRUCTION. These tests pin
that construction: bit-identity with matched AND mismatched drafts,
EOS/max-token truncation inside a window, draft-pool rewind accounting,
the serving-ledger draft_compute meters, the draft failure protocol
(quarantine without charging the target breaker), and router failover
mid-draft-window.

Every scheduler test runs the PRODUCTION pump under a SimClock —
scripted instants, no sleeps, no thread flake."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(scope="module")
def gpt_tiny_alt():
    """Same architecture, DIFFERENT weights: a deliberately bad draft
    whose proposals the target mostly rejects."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(123)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    from paddle_tpu.utils.fault_injection import set_global_plan
    set_global_plan(None)
    yield
    set_global_plan(None)


def _engine(model, clock, draft=None, **cfg_kw):
    from paddle_tpu import serving
    kw = dict(num_slots=2, block_len=8, n_blocks=4, max_queue_depth=64)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=clock, draft_model=draft)


def _drain(eng, clock=None, dt=0.01):
    steps = 0
    while eng.has_work():
        if clock is not None:
            clock.advance(dt)
        eng.pump()
        steps += 1
        assert steps < 2000, "engine failed to converge"


def _ref(model, prompt, max_new, eos=None):
    from paddle_tpu.models.generation import generate
    out = generate(model, np.asarray(prompt, np.int32)[None, :],
                   max_new_tokens=max_new, eos_token_id=eos)
    return np.asarray(out.numpy())[0, len(prompt):]


# ---- the acceptance proof: bit-identical, fewer decode iterations ----

def test_spec_bit_identical_with_fewer_decode_iterations(gpt_tiny):
    """The same staggered 4-request trace through a plain engine and a
    spec engine (draft == target, so greedy acceptance is deterministic):
    every stream must match one-shot generate() bit-for-bit on BOTH
    engines, and the spec engine must commit the identical token totals
    in at most half the decode iterations — the dispatch-count collapse
    that IS the perf win."""
    from paddle_tpu import serving

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 500, size=(6,)).astype(np.int32)
               for _ in range(4)]
    refs = [_ref(gpt_tiny, p, 16) for p in prompts]

    iters = {}
    for mode, draft in (("plain", None), ("spec", gpt_tiny)):
        clock = serving.SimClock()
        eng = _engine(gpt_tiny, clock, draft=draft)
        handles = []
        for p in prompts:
            clock.advance(0.01)
            handles.append(eng.submit(p, max_new_tokens=16))
            eng.pump()
        _drain(eng, clock)
        for h, r in zip(handles, refs):
            assert np.array_equal(h.result(timeout=0), r)
        iters[mode] = eng.decode_iterations
        eng.pool.check_balance()
        if draft is not None:
            eng.draft_pool.check_balance()
            assert eng.draft_pool.active_slots() == 0
            snap = eng.metrics.snapshot()
            # draft == target: every window accepts everything
            assert snap["spec_accept_rate"] == 1.0
            assert snap["spec_windows"] == eng.spec_windows > 0
            assert snap["spec_drafted"] == snap["spec_accepted"] > 0
            assert snap["spec_draft_quarantines"] == 0
        eng.stop()

    assert iters["spec"] <= 0.5 * iters["plain"], iters


def test_spec_mismatched_draft_still_bit_identical(gpt_tiny, gpt_tiny_alt):
    """A draft with DIFFERENT weights proposes mostly-wrong windows; the
    verify step's corrective token keeps every stream bit-identical to
    plain greedy decode anyway — acceptance only changes how many tokens
    each dispatch commits, never which tokens."""
    from paddle_tpu import serving

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 500, size=(s,)).astype(np.int32)
               for s in (4, 7, 11)]
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny_alt)
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drain(eng, clock)
    for p, h in zip(prompts, handles):
        assert np.array_equal(h.result(timeout=0), _ref(gpt_tiny, p, 12))
    snap = eng.metrics.snapshot()
    assert snap["spec_windows"] > 0
    assert 0.0 <= snap["spec_accept_rate"] <= 1.0
    assert snap["spec_accepted"] <= snap["spec_drafted"]
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    assert eng.draft_pool.active_slots() == 0
    eng.stop()


def test_spec_eos_inside_window_truncates_exactly(gpt_tiny):
    """An EOS landing INSIDE a verify window must end the stream at that
    token — identical to where sequential decode stops — and release both
    the target and draft rows."""
    from paddle_tpu import serving

    prompt = np.arange(1, 9, dtype=np.int32)
    ref = _ref(gpt_tiny, prompt, 12)
    eos = int(ref[min(2, len(ref) - 1)])
    j = int(np.argmax(ref == eos))       # stream must end exactly here

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny, num_slots=1)
    h = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    _drain(eng, clock)
    got = h.result(timeout=0)
    assert got.shape == (j + 1,) and got[-1] == eos
    assert np.array_equal(got, ref[:j + 1])
    assert eng.pool.free_slots() == 1
    assert eng.draft_pool.active_slots() == 0
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    eng.stop()


def test_spec_capacity_edge_degrades_to_plain_decode(gpt_tiny):
    """A window that would overrun the slot's block capacity is simply
    not proposed: near the end of a capacity-exact stream the engine
    degrades to plain decode for the tail and still finishes
    bit-identically, with both pools balanced."""
    from paddle_tpu import serving

    prompt = np.arange(1, 7, dtype=np.int32)          # 6 + 6 == capacity
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny, num_slots=1,
                  block_len=4, n_blocks=3)
    assert eng.pool.capacity == 12
    h = eng.submit(prompt, max_new_tokens=6)
    _drain(eng, clock)
    assert np.array_equal(h.result(timeout=0), _ref(gpt_tiny, prompt, 6))
    # at least one window ran before the capacity guard kicked in
    assert eng.spec_windows >= 1
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    eng.stop()


def test_spec_prefix_cache_warm_hit_bit_identical(gpt_tiny):
    """Target and draft prefix caches are page-congruent (same block_len,
    same spans): a shared-prefix sibling attaches cached blocks on BOTH
    sides, skips the same token span, and its spec-decoded stream is
    still bit-identical to one-shot generate()."""
    from paddle_tpu import serving

    rng = np.random.RandomState(11)
    shared = rng.randint(1, 500, size=(16,)).astype(np.int32)  # 2 blocks
    sfx = [rng.randint(1, 500, size=(4,)).astype(np.int32) for _ in range(2)]
    prompts = [np.concatenate([shared, s]) for s in sfx]

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny)
    assert eng.draft_prefix_cache is not None
    assert eng.draft_prefix_cache.snapshot()["name"] == "draft"
    h1 = eng.submit(prompts[0], max_new_tokens=8)
    _drain(eng, clock)
    h2 = eng.submit(prompts[1], max_new_tokens=8)     # warm: prefix cached
    _drain(eng, clock)
    for p, h in zip(prompts, (h1, h2)):
        assert np.array_equal(h.result(timeout=0), _ref(gpt_tiny, p, 8))
    assert eng.metrics.snapshot()["prefix_hits"] >= 1
    assert eng.draft_prefix_cache.snapshot()["hits"] >= 1
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    eng.stop()


# ---- draft-pool rewind (the rollback primitive) ----

def test_rewind_length_returns_pages_and_balances():
    import jax.numpy as jnp
    from paddle_tpu.serving.llm import SlotPagedKVPool

    def init_cache(b, max_len):
        return [(jnp.zeros((b, 2, max_len, 3), jnp.float32),
                 jnp.zeros((b, 2, max_len, 3), jnp.float32))]

    p = SlotPagedKVPool(init_cache, 2, 4, 3)
    s = p.allocate(12)
    p.set_length(s, 10)                   # 3 blocks claimed
    assert len(p.block_table[s]) == 3
    freed0 = p.stats["blocks_freed"]
    p.rewind_length(s, 5)                 # back to 2 blocks
    assert int(p.lengths[s]) == 5
    assert len(p.block_table[s]) == 2
    assert p.stats["blocks_freed"] == freed0 + 1
    p.rewind_length(s, 5)                 # same length: no-op
    assert p.stats["blocks_freed"] == freed0 + 1
    with pytest.raises(ValueError, match="shrink"):
        p.rewind_length(s, 9)             # growing is set_length's job
    p.set_length(s, 9)                    # the freed page is reusable
    assert len(p.block_table[s]) == 3
    p.free(s)
    p.check_balance()
    with pytest.raises(ValueError, match="not active"):
        p.rewind_length(s, 1)


# ---- serving-ledger economics under spec (ISSUE 11 x ISSUE 17) ----

def test_ledger_books_draft_compute_and_balances():
    """Draft dispatches book into the draft_compute phase with per-owner
    draft_tokens (never the useful-token meter); verify dispatches keep
    the old prefill/decode split. Per-owner device-seconds still sum to
    compute_seconds exactly, and sum(tenant tokens) == useful_positions
    stays intact because draft positions ride their own meter."""
    from paddle_tpu.obs.serving_ledger import ServingLedger

    t = [0.0]
    led = ServingLedger(clock=lambda: t[0])
    # draft proposal: 5 draft positions, zero useful, zero total
    led.book_dispatch(0.01, prefill_positions=0, decode_positions=0,
                      total_positions=0,
                      owners=[("tA", "interactive", 5)], draft_positions=5)
    # the verify step: 5 useful decode positions out of a 32-wide row
    led.book_dispatch(0.03, prefill_positions=0, decode_positions=5,
                      total_positions=32,
                      owners=[("tA", "interactive", 5)],
                      drafted=4, draft_accepted=3)
    t[0] = 0.1
    snap = led.snapshot()
    ph = snap["phase_seconds"]
    assert ph["draft_compute"] == pytest.approx(0.01, abs=1e-12)
    assert ph["decode_compute"] == pytest.approx(0.03, abs=1e-12)
    assert snap["compute_seconds"] == pytest.approx(0.04, abs=1e-12)
    ten = snap["tenants"]["tA"]
    assert ten["device_seconds"] == pytest.approx(snap["compute_seconds"],
                                                  abs=1e-12)
    assert ten["tokens"] == 5 == snap["useful_positions"]
    assert ten["draft_tokens"] == 5 == snap["draft_positions"]
    assert snap["token_efficiency"] == pytest.approx(5 / 32)
    assert snap["spec_drafted"] == 4 and snap["spec_accepted"] == 3
    assert snap["spec_accept_rate"] == pytest.approx(3 / 4)


def test_spec_rejections_measurably_lower_token_efficiency(gpt_tiny,
                                                           gpt_tiny_alt):
    """Rejected draft positions are pad-waste: they stay in the verify
    row's total_positions but never reach the useful count, so the
    mismatched-draft run's ledger token_efficiency must come out strictly
    below the accept-all run's on the same trace — and per-tenant
    device-seconds must sum to compute_seconds under spec in both."""
    from paddle_tpu import serving

    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 500, size=(6,)).astype(np.int32)
               for _ in range(3)]

    def run(draft):
        clock = serving.SimClock()
        eng = _engine(gpt_tiny, clock, draft=draft, num_slots=1,
                      economics=True)
        handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
        _drain(eng, clock)
        for p, h in zip(prompts, handles):
            assert np.array_equal(h.result(timeout=0),
                                  _ref(gpt_tiny, p, 12))
        led = eng.ledger.snapshot()
        eng.stop()
        return led

    led_all = run(gpt_tiny)
    led_rej = run(gpt_tiny_alt)
    assert led_all["spec_accept_rate"] == 1.0
    assert led_rej["spec_accept_rate"] < 1.0
    assert led_rej["token_efficiency"] < led_all["token_efficiency"]
    for led in (led_all, led_rej):
        tenant_s = sum(v["device_seconds"] for v in led["tenants"].values())
        assert tenant_s == pytest.approx(led["compute_seconds"], abs=1e-9)
        assert sum(v["tokens"] for v in led["tenants"].values()) \
            == led["useful_positions"]
        assert sum(v["draft_tokens"] for v in led["tenants"].values()) \
            == led["draft_positions"] > 0


# ---- the draft failure protocol (fault matrix) ----

@pytest.mark.fault_matrix
def test_poisoned_draft_quarantines_draft_only_stream_bit_identical(
        gpt_tiny):
    """poison_request@0:draft fails every DRAFT dispatch carrying
    submit-index 0. Contract: the solo draft probes implicate exactly
    that request, ONLY its draft is quarantined (spec_off — the target
    stream continues as plain decode, bit-identical), the other request
    keeps speculating, the quarantine flight event names the draft stage,
    and the target breaker/dispatch stats are never charged — draft
    dispatches are breaker-exempt by design."""
    from paddle_tpu import serving
    from paddle_tpu.obs.flight_recorder import flight_recorder
    from paddle_tpu.utils.fault_injection import FaultPlan

    flight_recorder().clear()
    plan = FaultPlan.from_spec("poison_request@0:draft")
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4),
        clock=clock, draft_model=gpt_tiny, fault_plan=plan)
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(11, 16, dtype=np.int32)]
    bad = eng.submit(prompts[0], max_new_tokens=10)    # submit idx 0
    good = eng.submit(prompts[1], max_new_tokens=10)   # submit idx 1
    _drain(eng, clock)

    # BOTH streams complete bit-identically — the poison only ever hit
    # draft work, never the committed token path
    assert np.array_equal(bad.result(timeout=0), _ref(gpt_tiny,
                                                      prompts[0], 10))
    assert np.array_equal(good.result(timeout=0), _ref(gpt_tiny,
                                                       prompts[1], 10))

    snap = eng.metrics.snapshot()
    assert snap["spec_draft_quarantines"] == 1
    assert snap["spec_windows"] > 0          # request 1 kept speculating
    assert snap["completed"] == 2 and snap["failed"] == 0
    assert snap["quarantined"] == 0          # the REQUEST was never touched

    # the blame ladder is on the flight recorder, draft-scoped
    events = flight_recorder().snapshot()["events"]
    probes = [e for e in events if e["kind"] == "solo_probe"
              and e.get("stage") == "draft"]
    assert any(e["outcome"] == "failed" and e["submit_idx"] == 0
               for e in probes)
    quar = [e for e in events if e["kind"] == "draft_quarantine"]
    assert len(quar) == 1
    assert quar[0]["submit_idx"] == 0
    assert quar[0]["reason"] == "poisoned_draft"
    assert quar[0]["rid"] == bad.rid

    # exempt accounting: the target breaker never heard about any of it
    assert eng.supervisor.stats["exempt_failures"] >= 1
    assert eng.supervisor.stats["dispatch_failures"] == 0
    assert eng.supervisor.stats["quarantines"] == 0
    assert not eng.broken
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    assert eng.draft_pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_unattributable_draft_failures_disable_spec_not_engine(gpt_tiny):
    """Draft dispatches that fail for EVERY solo probe are unattributable:
    they count a draft-only failstreak that disables speculation at
    breaker_threshold — the engine itself keeps serving plain decode,
    bit-identically, with the breaker closed."""
    from paddle_tpu import serving
    from paddle_tpu.obs.flight_recorder import flight_recorder
    from paddle_tpu.utils.fault_injection import FaultPlan

    flight_recorder().clear()
    # poison EVERY request's draft scope: the multi-row catch-up dispatch
    # fails AND both solo probes fail, so blame narrows to nobody
    # (len(blamed) == len(rows) > 1) — the textbook unattributable case
    plan = FaultPlan.from_spec(
        "poison_request@0:draft;poison_request@1:draft")
    clock = serving.SimClock()
    eng = serving.LLMEngine(
        gpt_tiny,
        serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=4,
                                breaker_threshold=2),
        clock=clock, draft_model=gpt_tiny, fault_plan=plan)
    prompts = [np.arange(1, 7, dtype=np.int32),
               np.arange(21, 27, dtype=np.int32)]
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    _drain(eng, clock)
    for p, h in zip(prompts, handles):
        assert np.array_equal(h.result(timeout=0), _ref(gpt_tiny, p, 8))
    assert eng._spec_disabled and not eng.broken
    snap = eng.metrics.snapshot()
    assert snap["spec_windows"] == 0
    assert snap["spec_draft_quarantines"] == 0   # disabled, not blamed
    kinds = [e["kind"] for e in flight_recorder().snapshot()["events"]]
    assert kinds.count("draft_failure") == 2
    assert "draft_disabled" in kinds
    assert "draft_quarantine" not in kinds
    assert eng.supervisor.stats["dispatch_failures"] == 0
    assert eng.supervisor.stats["exempt_failures"] >= 2
    eng.pool.check_balance()
    eng.draft_pool.check_balance()
    eng.stop()


# ---- router failover mid-draft-window (ISSUE 14 x ISSUE 17) ----

@pytest.mark.fault_matrix
def test_router_failover_mid_draft_window_resumes_bit_identical(gpt_tiny):
    """Kill a spec-armed replica BETWEEN verify windows, with its draft
    pool run ahead of the committed stream: the router re-prefills every
    victim on the survivor from the handle's tokens — which only ever
    carry VERIFIED tokens, the engine never surfaces speculative state —
    so the resumed streams finish bit-identical to an uninterrupted
    one-shot generate()."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan

    clock = serving.SimClock()
    replicas = [
        serving.InProcessReplica(
            serving.LLMEngine(
                gpt_tiny,
                serving.LLMEngineConfig(num_slots=4, block_len=8,
                                        n_blocks=4, max_queue_depth=64),
                clock=clock, draft_model=gpt_tiny),
            i)
        for i in range(2)]
    router = serving.ReplicaRouter(replicas)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 500, size=(6,)).astype(np.int32)
               for _ in range(4)]
    handles = [router.submit(p, max_new_tokens=14) for p in prompts]
    assert {h._replica.name for h in handles} == {"replica0", "replica1"}
    victims = [h for h in handles if h._replica is replicas[0]]

    for _ in range(2):       # prefill + one committed verify window
        clock.advance(0.01)
        router.pump()
    # the kill lands mid-stream AND mid-speculation: tokens are out, the
    # dead replica's draft pool has optimistically run ahead
    assert all(0 < len(h.tokens_so_far()) < 14 for h in handles)
    assert replicas[0].engine.spec_windows > 0

    set_global_plan(FaultPlan.from_spec("replica_crash@0"))
    steps = 0
    while router.has_work():
        clock.advance(0.01)
        router.pump()
        steps += 1
        assert steps < 2000

    from paddle_tpu.models.generation import generate
    ref = np.asarray(generate(gpt_tiny, np.stack(prompts),
                              max_new_tokens=14).numpy())[:, 6:]
    for i, h in enumerate(handles):
        assert np.array_equal(h.result(timeout=0), ref[i])
    assert all(h.failovers == 1 for h in victims)
    snap = router.metrics.snapshot()
    assert snap["resumed_streams"] == len(victims)
    assert snap["completed"] == 4 and snap["failed"] == 0
    # the survivor (also spec-armed) speculated through the resumed load
    assert replicas[1].engine.spec_windows > 0
    # the fleet healthz advertises per-replica accept rates iff a draft
    # is armed — the accept-rate runbook's fleet-level view
    rates = router.healthz()["spec_accept_rates"]
    assert rates["replica0"] is None          # crashed
    assert 0.0 <= rates["replica1"] <= 1.0
    replicas[1].engine.pool.check_balance()
    replicas[1].engine.draft_pool.check_balance()


# ---- the step's tail on a draft window's columns (PR 52) ----

_TOKENS = {1: "{", 2: "}", 3: '"a"', 4: ":", 5: "1", 6: "23", 7: ",",
           8: '"b"', 9: "true", 10: "false"}
_SCHEMA = {"type": "object",
           "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"}},
           "required": ["a", "b"]}


def _grammar():
    from paddle_tpu.serving.llm.sampling import SamplingParams
    return SamplingParams(temperature=1.0, seed=7,
                          grammar={"schema": _SCHEMA, "tokens": _TOKENS})


@pytest.mark.parametrize("slots,matched", [(3, True), (3, False),
                                           (40, True), (40, False)])
def test_a_draft_armed_tail_reads_its_verify_columns(gpt_tiny, gpt_tiny_alt,
                                                    slots, matched):
    """A draft-armed engine's tail runs on `slots x (1 + k)` rows (the
    step's `window`), unpacked (3 slots) and packed (40): verify windows
    of a draft the target accepts whole (itself) or rejects (other
    weights: the corrective token is column 0's), a grammar row that never
    speculates (one column) and prompts that still prefill (the last
    `window` columns of a chunk) ride the same steps. Streams, acceptance
    counts and the steps taken are what the block's tail gives."""
    from paddle_tpu import serving
    from test_packed_step import block_tail_step

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 500, size=(s,)).astype(np.int32)
               for s in (4, 7, 21, 40)]
    runs = []
    for block_tail in (False, True):
        clock = serving.SimClock()
        eng = _engine(gpt_tiny, clock,
                      draft=gpt_tiny if matched else gpt_tiny_alt,
                      num_slots=slots, n_blocks=8, enable_prefix_cache=False)
        assert eng._window == 5 and eng._head_positions == slots * 5
        assert eng._head_positions < eng.step_tokens
        if block_tail:
            eng._step_jit = block_tail_step(eng)
        hs = [eng.submit(p, max_new_tokens=12) for p in prompts[:2]]
        hs.append(eng.submit(np.arange(1, 9, dtype=np.int32),
                             max_new_tokens=10, sampling=_grammar()))
        eng.pump()
        eng.pump()                      # windows ride from here on
        hs += [eng.submit(p, max_new_tokens=6) for p in prompts[2:]]
        _drain(eng, clock)
        snap = eng.metrics.snapshot()
        runs.append(([h.tokens_so_far() for h in hs],
                     {k: snap[k] for k in (
                         "spec_windows", "spec_drafted", "spec_accepted",
                         "unified_steps", "constrained_tokens")}))
        assert snap["head_positions"] == slots * 5 * snap["unified_steps"]
        eng.stop()
    assert runs[0] == runs[1]
    streams, counts = runs[0]
    assert counts["spec_windows"] > 0 and counts["constrained_tokens"] > 0
    assert (counts["spec_accepted"] > counts["spec_drafted"] // 2) == matched
    for p, toks, n in zip(prompts, [streams[0], streams[1], *streams[3:]],
                          (12, 12, 6, 6)):
        assert np.array_equal(toks, _ref(gpt_tiny, p, n))


@pytest.mark.parametrize("slots", [4, 40])
def test_one_step_of_a_window_a_decode_row_and_a_chunk(gpt_tiny, slots):
    """One step that holds a full verify window (`adv` 5), a one-column
    decode row (a grammar row), a chunk of 16 and one of 5 columns: `sel`
    holds the block's tail's selections at a row's last `min(adv, 5)`
    columns and zeros in every other, so the chunk of 16 shows columns
    11-15; the DFA states are the block's tail's."""
    from paddle_tpu import serving
    from test_packed_step import both_tails, step_args

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny, num_slots=slots,
                  n_blocks=8, enable_prefix_cache=False)
    rng = np.random.RandomState(3)
    eng.submit(rng.randint(1, 500, size=(6,)).astype(np.int32),
               max_new_tokens=12)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=10,
               sampling=_grammar())
    eng.pump()
    eng.pump()
    eng.submit(rng.randint(1, 500, size=(30,)).astype(np.int32),
               max_new_tokens=4)
    eng.submit(rng.randint(1, 500, size=(5,)).astype(np.int32),
               max_new_tokens=4)
    eng._admit()
    adv, args = step_args(eng, eng._draft_phase())
    assert sorted(int(a) for a in adv if a) == [1, 5, 5, 16]
    (want_sel, want_lp, want_state), (sel, lp, state) = both_tails(eng, args)
    cols = np.arange(eng.config.prefill_chunk)[None, :]
    read = (cols < adv[:, None]) & (cols >= (adv - 5)[:, None])
    assert read.sum() == 1 + 5 + 5 + 5
    np.testing.assert_array_equal(sel[read], want_sel[read])
    np.testing.assert_allclose(lp[read], want_lp[read], rtol=1e-5)
    assert not sel[~read].any() and not lp[~read].any()
    np.testing.assert_array_equal(state, want_state)
    assert int(state.max()) > 0         # the grammar row stepped
    eng.stop()
