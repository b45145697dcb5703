"""Per-slot seeded sampling + grammar-constrained decoding in the
unified step (ISSUE 18).

The determinism contract under test: token `i` of a request's stream is
drawn from RNG lane `(request_seed, i)` — never from batch composition,
slot index, or wall clock — so a seeded sampled stream is bit-identical
across batch-mate changes, engine restart, and a mid-stream router
failover whose re-prefill restores the lane counter (`sample_offset`).
Grammar-constrained slots additionally never emit a token their
compiled token-DFA forbids, and speculative decoding composes with
sampling by drafting and verifying on the SAME lanes (seeded-replay
acceptance), keeping the output literally identical to plain sampled
decode.

Every scheduler test runs the PRODUCTION pump under a SimClock —
scripted instants, no sleeps, no thread flake."""
import json

import numpy as np
import pytest


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    from paddle_tpu.utils.fault_injection import set_global_plan
    set_global_plan(None)
    yield
    set_global_plan(None)


def _engine(model, clock, draft=None, **cfg_kw):
    from paddle_tpu import serving
    kw = dict(num_slots=2, block_len=8, n_blocks=8, max_queue_depth=64)
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


def _params(**kw):
    from paddle_tpu.serving.llm.sampling import SamplingParams
    return SamplingParams(**kw)


_PROMPT = np.arange(1, 9, dtype=np.int32)

# nested-schema fixture: an object holding an integer, a nested object,
# and a boolean — every structural token the compiler supports
_TOKENS = {1: "{", 2: "}", 3: '"a"', 4: ":", 5: "1", 6: "23", 7: ",",
           8: '"b"', 9: "true", 10: "false", 11: '"o"', 12: '"x"'}
_SCHEMA = {
    "type": "object",
    "properties": {
        "a": {"type": "integer"},
        "o": {"type": "object",
              "properties": {"x": {"type": "boolean"}},
              "required": ["x"]},
        "b": {"type": "boolean"},
    },
    "required": ["a", "o", "b"],
}


def _grammar_params(seed=7):
    return _params(temperature=1.0, seed=seed,
                   grammar={"schema": _SCHEMA, "tokens": _TOKENS})


# ---- SamplingParams surface ----

def test_sampling_params_validation_and_payload():
    from paddle_tpu.serving.llm.sampling import SamplingParams
    for bad in (dict(temperature=0.0), dict(temperature=-1.0),
                dict(top_k=-1), dict(top_p=0.0), dict(top_p=1.5),
                dict(seed=-1), dict(seed=2 ** 31),
                dict(grammar={"schema": {}})):
        with pytest.raises(ValueError):
            SamplingParams(**bad).validate()
    # payload round trip: absent sampling fields -> None (pure greedy)
    assert SamplingParams.from_payload({"input_ids": [1, 2]}) is None
    sp = SamplingParams.from_payload(
        {"temperature": 0.8, "top_k": 40, "top_p": 0.9, "seed": 5})
    sp.validate()
    assert sp.do_sample and sp.seed == 5 and not sp.constrained


# ---- the seeding contract: bit-identity given (seed, params) ----

def test_seeded_bit_identity_across_batch_composition(gpt_tiny):
    """The same seeded request decoding ALONE and decoding beside a
    batch-mate (different slot, different step cadence) must emit the
    identical stream: the lane key is (seed, stream index), nothing
    else."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    sp = _params(temperature=0.9, top_p=0.95, seed=42)
    h_solo = eng.submit(_PROMPT, max_new_tokens=10, sampling=sp)
    _drain(eng, clock)
    solo = h_solo.result(0)

    mate = eng.submit(np.arange(3, 12, dtype=np.int32), max_new_tokens=12)
    h_batched = eng.submit(_PROMPT, max_new_tokens=10, sampling=sp)
    _drain(eng, clock)
    mate.result(0)
    np.testing.assert_array_equal(solo, h_batched.result(0))
    # and a different seed actually changes the draw
    h_other = eng.submit(_PROMPT, max_new_tokens=10,
                         sampling=_params(temperature=0.9, top_p=0.95,
                                          seed=43))
    _drain(eng, clock)
    assert not np.array_equal(solo, h_other.result(0))


def test_seeded_bit_identity_across_engine_restart(gpt_tiny):
    from paddle_tpu import serving
    sp = _params(temperature=0.8, top_k=50, seed=99)
    streams = []
    for _ in range(2):      # two fresh engines = restart
        clock = serving.SimClock()
        eng = _engine(gpt_tiny, clock)
        h = eng.submit(_PROMPT, max_new_tokens=12, sampling=sp)
        _drain(eng, clock)
        streams.append(h.result(0))
    np.testing.assert_array_equal(streams[0], streams[1])


def test_sample_offset_resumes_mid_stream(gpt_tiny):
    """The failover re-prefill contract, exercised at the engine level:
    resubmitting prompt+emitted with sample_offset=len(emitted) makes
    the survivor's first draw use stream index len(emitted) — the
    suffix matches the uninterrupted run exactly."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    sp = _params(temperature=0.9, top_p=0.9, seed=11)
    h_full = eng.submit(_PROMPT, max_new_tokens=12, sampling=sp)
    _drain(eng, clock)
    full = h_full.result(0)

    h_head = eng.submit(_PROMPT, max_new_tokens=4, sampling=sp)
    _drain(eng, clock)
    head = h_head.result(0)
    np.testing.assert_array_equal(head, full[:4])

    h_tail = eng.submit(np.concatenate([_PROMPT, head]), max_new_tokens=8,
                        sampling=sp, sample_offset=4)
    _drain(eng, clock)
    np.testing.assert_array_equal(h_tail.result(0), full[4:])


# ---- grammar-constrained decoding ----

def test_constrained_emits_only_grammar_valid_json(gpt_tiny):
    """Nested-schema fixture: every emitted token must be legal from the
    DFA state reached by its predecessors (checked token-by-token on the
    host against the compiled TokenDFA), and the finished stream must
    parse as JSON matching the schema's required keys — including the
    nested object."""
    from paddle_tpu import serving
    from paddle_tpu.serving.llm.sampling import compile_grammar
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    h = eng.submit(_PROMPT, max_new_tokens=40, sampling=_grammar_params())
    _drain(eng, clock)
    toks = h.result(0)

    dfa = compile_grammar({"schema": _SCHEMA, "tokens": _TOKENS},
                          gpt_tiny.config.vocab_size, None)
    state = 0
    for t in toks:
        nxt = int(dfa.trans[state, int(t)])
        assert nxt >= 0, f"token {t} illegal from DFA state {state}"
        state = nxt
    assert bool(dfa.accept[state]), "stream ended in a non-accepting state"

    obj = json.loads("".join(_TOKENS[int(t)] for t in toks))
    assert set(obj) == {"a", "o", "b"}
    assert isinstance(obj["a"], int)
    assert isinstance(obj["o"], dict) and set(obj["o"]) == {"x"}
    assert isinstance(obj["b"], bool)


def test_constrained_replay_and_dfa_fast_forward(gpt_tiny):
    """Same seed -> same JSON; and a mid-object resume (sample_offset>0)
    fast-forwards the DFA through the emitted tail so the continuation
    is token-identical — the constrained half of the failover contract."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    sp = _grammar_params(seed=21)
    h1 = eng.submit(_PROMPT, max_new_tokens=40, sampling=sp)
    _drain(eng, clock)
    full = h1.result(0)
    h2 = eng.submit(_PROMPT, max_new_tokens=40, sampling=sp)
    _drain(eng, clock)
    np.testing.assert_array_equal(full, h2.result(0))

    k = 3
    h3 = eng.submit(np.concatenate([_PROMPT, full[:k]]),
                    max_new_tokens=40 - k, sampling=sp, sample_offset=k)
    _drain(eng, clock)
    np.testing.assert_array_equal(h3.result(0), full[k:])

    # a resume tail that VIOLATES the grammar is rejected at submit
    from paddle_tpu.serving import RejectedError
    bad_tail = np.array([2, 2, 2], np.int32)    # "}}}" from the start
    with pytest.raises((ValueError, RejectedError)):
        eng.submit(np.concatenate([_PROMPT, bad_tail]),
                   max_new_tokens=8, sampling=sp, sample_offset=3)


def test_grammar_compile_rejections(gpt_tiny):
    """Free-form strings are out of the supported schema subset
    (ValueError), and a full grammar bank rejects the NEXT distinct
    grammar with reason=grammar_capacity instead of corrupting slots."""
    from paddle_tpu import serving
    from paddle_tpu.serving import RejectedError
    with pytest.raises(ValueError):
        from paddle_tpu.serving.llm.sampling import compile_grammar
        compile_grammar({"schema": {"type": "string"}, "tokens": _TOKENS},
                        512, None)

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, max_grammars=1)
    h = eng.submit(_PROMPT, max_new_tokens=40, sampling=_grammar_params())
    other = {"type": "object", "properties": {"b": {"type": "boolean"}},
             "required": ["b"]}
    with pytest.raises(RejectedError) as ei:
        eng.submit(_PROMPT, max_new_tokens=8, sampling=_params(
            temperature=1.0, seed=1,
            grammar={"schema": other, "tokens": _TOKENS}))
    assert ei.value.reason == "grammar_capacity"
    _drain(eng, clock)
    h.result(0)


# ---- speculative decoding composes with sampling ----

def test_spec_sampled_stream_identical_to_plain_sampled(gpt_tiny):
    """Distribution-parity smoke, strengthened to exactness: because the
    draft proposes and the target verifies on the SAME (seed, index)
    lanes, rejection-sampled spec output is not merely unbiased — it is
    bit-identical to spec-off sampled decode, while still accepting
    drafts (the PR 17 speedup survives leaving greedy-land)."""
    from paddle_tpu import serving
    sp = _params(temperature=0.8, top_k=50, top_p=0.95, seed=99)

    clock = serving.SimClock()
    plain = _engine(gpt_tiny, clock)
    h = plain.submit(_PROMPT, max_new_tokens=16, sampling=sp)
    _drain(plain, clock)
    ref = h.result(0)

    clock2 = serving.SimClock()
    spec = _engine(gpt_tiny, clock2, draft=gpt_tiny)
    h2 = spec.submit(_PROMPT, max_new_tokens=16, sampling=sp)
    _drain(spec, clock2)
    np.testing.assert_array_equal(ref, h2.result(0))
    snap = spec.metrics.snapshot()
    assert snap["spec_accepted"] > 0, \
        "draft==target on shared lanes must accept proposals"
    assert snap["sampled_tokens"] == 16


def test_constrained_requests_never_speculate(gpt_tiny):
    """A grammar-constrained request on a spec-armed engine decodes
    WITHOUT draft windows (its mask depends on the in-step DFA state, so
    it takes exactly one emission per step), while an unconstrained
    batch-mate keeps speculating."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, draft=gpt_tiny, num_slots=2)
    h_con = eng.submit(_PROMPT, max_new_tokens=40,
                       sampling=_grammar_params())
    h_greedy = eng.submit(np.arange(2, 10, dtype=np.int32),
                          max_new_tokens=12)
    _drain(eng, clock)
    toks = h_con.result(0)
    h_greedy.result(0)
    snap = eng.metrics.snapshot()
    assert snap["spec_windows"] > 0          # the greedy mate speculated
    assert snap["constrained_tokens"] == toks.size
    # constrained stream is still grammar-clean next to speculation
    json.loads("".join(_TOKENS[int(t)] for t in toks))


# ---- router failover: the RNG-lane counter handoff ----

@pytest.mark.fault_matrix
def test_failover_mid_sampled_stream_token_identical(gpt_tiny):
    """Kill the hosting replica mid-sampled-stream: the survivor's
    re-prefill must restore the RNG-lane counter (sample_offset =
    harvested prefix length), making the resumed stream token-identical
    to the uninterrupted seeded run — the greedy failover bit-identity
    contract, extended to sampling."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan

    def fleet(clock):
        reps = [serving.InProcessReplica(
            _engine(gpt_tiny, clock, num_slots=4), i) for i in range(2)]
        return serving.ReplicaRouter(reps), reps

    def drive(router, clock):
        steps = 0
        while router.has_work():
            clock.advance(0.01)
            router.pump()
            steps += 1
            assert steps < 3000

    sp = _params(temperature=0.8, top_p=0.9, seed=1234)

    clock = serving.SimClock()
    router, _ = fleet(clock)
    h = router.submit(_PROMPT, max_new_tokens=14, sampling=sp)
    drive(router, clock)
    ref = h.result(0)

    clock = serving.SimClock()
    router, _ = fleet(clock)
    h = router.submit(_PROMPT, max_new_tokens=14, sampling=sp)
    for _ in range(6):              # decode far enough to be MID-stream
        clock.advance(0.01)
        router.pump()
    n_emitted = len(h.tokens_so_far())
    assert n_emitted > 0
    set_global_plan(FaultPlan.from_spec(
        f"replica_crash@{h._replica.index}"))
    drive(router, clock)
    assert h.failovers == 1
    np.testing.assert_array_equal(h.result(0), ref)


# ---- generate() jit cache: top-p keying + LRU churn bound ----

def test_generate_cache_keys_top_p_and_bounds_evictions(gpt_tiny):
    """top_p is part of the one-shot generate() jit-cache key (a
    distinct nucleus cutoff is a distinct compiled filter), and
    per-request param sweeps stay LRU-bounded: size never exceeds cap,
    evictions are counted, and a repeated key is a HIT."""
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.jit_cache import JitLRUCache
    ids = np.arange(1, 5, dtype=np.int32)[None, :]
    # pin a tiny fresh cache so the sweep exercises eviction cheaply
    gpt_tiny.__dict__["_generate_jit_cache"] = JitLRUCache(
        2, name="generate")
    cache = gpt_tiny.__dict__["_generate_jit_cache"]
    try:
        out_a = generate(gpt_tiny, ids, max_new_tokens=2, do_sample=True,
                         temperature=0.9, top_p=0.9, seed=3)
        out_b = generate(gpt_tiny, ids, max_new_tokens=2, do_sample=True,
                         temperature=0.9, top_p=0.5, seed=3)
        assert cache.stats()["misses"] == 2     # top_p changed the key
        generate(gpt_tiny, ids, max_new_tokens=2, do_sample=True,
                 temperature=0.9, top_p=0.5, seed=3)
        assert cache.stats()["hits"] == 1       # repeat is a hit
        generate(gpt_tiny, ids, max_new_tokens=2, do_sample=True,
                 temperature=0.9, top_p=0.7, seed=3)
        st = cache.stats()
        assert st["size"] <= 2 and st["evictions"] == 1
        # determinism given the seed holds per compiled entry
        out_a2 = generate(gpt_tiny, ids, max_new_tokens=2, do_sample=True,
                          temperature=0.9, top_p=0.9, seed=3)
        np.testing.assert_array_equal(np.asarray(out_a.numpy()),
                                      np.asarray(out_a2.numpy()))
        assert np.asarray(out_b.numpy()).shape == (1, 6)
    finally:
        del gpt_tiny.__dict__["_generate_jit_cache"]


# ---- observability ----

def test_sampling_metrics_and_lane_export(gpt_tiny):
    """pdtpu_llm_sample_* families render; sampled/constrained token
    counters partition non-greedy traffic; the sample_mask ledger phase
    exists; and export_sampling_lanes serializes a live slot's lane
    (seed, next stream index, DFA state) mid-decode."""
    from paddle_tpu import serving
    from paddle_tpu.obs.serving_ledger import SERVING_LEDGER_PHASES
    assert "sample_mask" in SERVING_LEDGER_PHASES

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, economics=True)
    sp = _params(temperature=0.9, seed=5)
    h = eng.submit(_PROMPT, max_new_tokens=8, sampling=sp)
    for _ in range(4):
        clock.advance(0.01)
        eng.pump()
    n_now = len(h.tokens_so_far())
    assert n_now > 0 and eng.has_work()
    slot = next(iter(eng._active))
    lanes = eng.export_sampling_lanes([slot])
    assert lanes[slot]["seed"] == 5
    assert lanes[slot]["next_index"] == n_now
    assert lanes[slot]["grammar_key"] is None
    _drain(eng, clock)
    h.result(0)

    hc = eng.submit(_PROMPT, max_new_tokens=40, sampling=_grammar_params())
    _drain(eng, clock)
    n_con = hc.result(0).size

    snap = eng.metrics.snapshot()
    assert snap["sampled_tokens"] == 8
    assert snap["constrained_tokens"] == n_con
    assert snap["grammars_compiled"] == 1
    text = eng.metrics.render()
    for fam in ("pdtpu_llm_sample_slots", "pdtpu_llm_sample_tokens_total",
                "pdtpu_llm_sample_mask_overhead_ms",
                "pdtpu_llm_sample_grammars_compiled"):
        assert fam in text, fam
    led = eng.ledger.snapshot()
    assert "sample_mask" in led["phase_seconds"]


# ---- the conditional, one-sort sampler (ISSUE 24) ----

def _two_sort_oracle(logits, do_sample, temperature, top_k, key, top_p):
    """The batched branch of `_select_token` as it stood before ISSUE
    24, copied: unconditional top-k filter (one full sort), top-p
    filter (a second full sort of the filtered rows), a draw for every
    row, and the greedy/sampled choice last."""
    import jax
    import jax.numpy as jnp
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    lg = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        srt, (jnp.clip(k, 1, V) - 1)[:, None], axis=-1)
    lg = jnp.where((lg >= kth) | (k[:, None] <= 0), lg, -1e30)
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.maximum(jnp.sum(cum_before < p[:, None], axis=-1), 1)
    thr = jnp.take_along_axis(srt, (n_keep - 1)[:, None], axis=-1)
    lg = jnp.where((lg >= thr) | (p[:, None] >= 1.0), lg, -1e30)
    sampled = jax.vmap(jax.random.categorical)(key, lg).astype(jnp.int32)
    return jnp.where(jnp.asarray(do_sample, bool), sampled, greedy)


_B, _V = 12, 97


def _tied_logits(seed):
    """Seeded [B, V] float32 logits on a coarse grid, so every row holds
    runs of equal values: the k-th largest and the nucleus threshold
    both fall inside a tie."""
    rng = np.random.default_rng(seed)
    return (rng.integers(-6, 7, size=(_B, _V)) * 0.5).astype(np.float32)


def _mix(name):
    """Per-row (do_sample, temperature, top_k, top_p) for a named mix."""
    samp = np.ones(_B, bool)
    temp = np.linspace(0.6, 1.4, _B).astype(np.float32)
    topk = np.zeros(_B, np.int32)
    topp = np.ones(_B, np.float32)
    ks = np.array([1, 2, 5, 9, 20, 40, _V, _V + 50, 3, 7, 11, 13], np.int32)
    ps = np.linspace(0.05, 0.99, _B).astype(np.float32)
    if name == "all_greedy":
        samp[:] = False
        topk, topp = ks, ps         # filters set on rows that never draw
    elif name == "all_sampled":
        topk, topp = ks, ps
    elif name == "temperature_only":
        pass
    elif name == "top_k_only":
        topk = ks
    elif name == "top_p_only":
        topp = ps
    elif name == "both":
        topk, topp = ks[::-1].copy(), ps
    elif name == "one_sampled_among_greedy":
        samp[:] = False
        samp[5] = True
        topk, topp = ks, ps
    elif name == "no_filter_rows":
        # k <= 0 and p >= 1 rows beside filtered ones, greedy rows between
        topk = np.array([0, -1, 5, 0, 9, -3, 0, 2, 0, 40, 0, 1], np.int32)
        topp = np.array([1.0, 1.5, 1.0, 0.5, 1.0, 0.9, 2.0, 1.0, 0.3, 1.0,
                         1.0, 0.7], np.float32)
        samp[[3, 8]] = False
    else:
        raise AssertionError(name)
    return samp, temp, topk, topp


_MIXES = ("all_greedy", "all_sampled", "temperature_only", "top_k_only",
          "top_p_only", "both", "one_sampled_among_greedy",
          "no_filter_rows")


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("mix", _MIXES)
def test_one_sort_sampler_matches_two_sort_oracle(mix, jitted):
    """Bit-for-bit tokens against the parent's two-sort formulation, on
    logits with ties at both thresholds, for every mix of rows."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import _select_token
    from paddle_tpu.serving.llm.sampling import lane_key
    samp, temp, topk, topp = _mix(mix)
    new, old = _select_token, _two_sort_oracle
    if jitted:
        new, old = jax.jit(new), jax.jit(old)
    for seed in (0, 1, 2**31 - 5):
        logits = jnp.asarray(_tied_logits(seed % 1000))
        keys = jax.vmap(lambda i: lane_key(seed % 2**31, i))(
            jnp.arange(_B, dtype=jnp.int32))
        got = new(logits, jnp.asarray(samp), jnp.asarray(temp),
                  jnp.asarray(topk), keys, jnp.asarray(topp))
        want = old(logits, jnp.asarray(samp), jnp.asarray(temp),
                   jnp.asarray(topk), keys, jnp.asarray(topp))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert got.dtype == jnp.int32
        if mix == "all_greedy":
            np.testing.assert_array_equal(
                np.asarray(got), np.argmax(np.asarray(logits), axis=-1))


def test_one_sort_filter_matches_under_a_grammar_mask():
    """Masked logits (-1e30 before the temperature, so below -1e30 after
    a temperature under 1) keep the equivalence, also when k exceeds the
    number of legal tokens."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import _select_token
    from paddle_tpu.serving.llm.sampling import lane_key
    samp, temp, topk, topp = _mix("all_sampled")
    logits = _tied_logits(11)
    legal = np.zeros((_B, _V), bool)
    legal[:, 3:11] = True
    legal[4, :] = False
    legal[4, 50] = True             # one legal token
    masked = jnp.asarray(np.where(legal, logits, -1e30).astype(np.float32))
    keys = jax.vmap(lambda i: lane_key(9, i))(
        jnp.arange(_B, dtype=jnp.int32))
    args = (masked, jnp.asarray(samp), jnp.asarray(temp),
            jnp.asarray(topk), keys, jnp.asarray(topp))
    got = np.asarray(_select_token(*args))
    np.testing.assert_array_equal(got, np.asarray(_two_sort_oracle(*args)))
    assert legal[np.arange(_B), got].all()


def _count_eqns(jaxpr, name, inside_cond=False):
    """(total, outside any cond) count of `name` equations, through
    every nested jaxpr."""
    import jax
    total = top = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            total += 1
            top += not inside_cond
        nested_in_cond = inside_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            t, o = _count_eqns(sub, name, nested_in_cond)
            total += t
            top += o
    return total, top


@pytest.mark.parametrize("fn", ["select_tokens", "select_next"])
def test_sampler_holds_one_sort_and_only_inside_a_cond(fn):
    """The CPU-checkable form of "an all-greedy step runs no sort": the
    traced selection holds exactly one `sort`, inside a `cond` branch,
    where the draw's random bits and the nucleus cumsum sit too."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.llm import sampling
    N, C, V = 3, 4, 64
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    f32 = lambda *s: jnp.ones(s, jnp.float32)
    samp = jnp.zeros((N,), bool)
    if fn == "select_tokens":
        bank = jnp.zeros((2, 5, V), jnp.int32)
        jaxpr = jax.make_jaxpr(sampling.select_tokens)(
            f32(N, C, V), i32(N), f32(N), i32(N), f32(N), samp, i32(N),
            i32(N), i32(N), i32(N), bank)
    else:
        jaxpr = jax.make_jaxpr(sampling.select_next)(
            f32(N, V), f32(N), i32(N), f32(N), samp, i32(N), i32(N))
    assert _count_eqns(jaxpr.jaxpr, "sort") == (1, 0)
    # one three-way conditional: greedy / draw / filter and draw; and, in
    # `select_tokens`, the one that keeps the grammar bank out of a step
    # with no constrained row (PR 52)
    conds = 2 if fn == "select_tokens" else 1
    assert _count_eqns(jaxpr.jaxpr, "cond") == (conds, conds)
    for prim in ("cumsum", "random_bits", "div"):
        total, outside = _count_eqns(jaxpr.jaxpr, prim)
        assert total > 0 and outside == 0, prim
    # the argmax every row needs stays outside
    assert _count_eqns(jaxpr.jaxpr, "argmax")[1] == 1


def _masked_select_tokens(logits, adv, temperature, top_k, top_p, do_sample,
                          seed, ctr, dfa_state, grammar_id, bank):
    """`select_tokens` as it was before PR 52, written out: every row's mask
    gathered from the bank in every step, the pass-through row included."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import _select_token
    from paddle_tpu.serving.llm.sampling import lane_key
    N, C, V = logits.shape
    allowed = bank[grammar_id, dfa_state] >= 0
    masked = jnp.where(allowed[:, None, :], logits.astype(jnp.float32),
                       -1e30)
    cols = jnp.arange(C, dtype=jnp.int32)
    keys = jax.vmap(
        lambda s, c0: jax.vmap(lambda t: lane_key(s, c0 + t))(cols)
    )(seed, ctr)
    rep = lambda a: jnp.repeat(a, C)
    toks = _select_token(
        masked.reshape(N * C, V), rep(jnp.asarray(do_sample, bool)),
        rep(temperature), rep(top_k), keys.reshape(N * C, 2),
        rep(top_p)).reshape(N, C)
    tok_e = jnp.take_along_axis(
        toks, jnp.maximum(adv - 1, 0)[:, None], axis=1)[:, 0]
    stepped = bank[grammar_id, dfa_state, tok_e]
    return toks, jnp.where((grammar_id > 0) & (adv > 0),
                           jnp.maximum(stepped, 0), dfa_state)


def _tail_operands(constrained, dtype):
    """Six rows of three columns over 64 tokens: greedy and drawing rows,
    free ones, and (`constrained`) two rows held to grammar 1 or 2."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    N, C, V = 6, 3, 64
    bank = np.full((3, 4, V), -1, np.int32)
    bank[0, 0] = 0
    # grammar 1: token v moves state s to (s + v) % 3, odd tokens illegal;
    # grammar 2: only tokens 8..15, each to state 1
    bank[1, :3] = (np.arange(3)[:, None] + np.arange(V)[None, :]) % 3
    bank[1, :, 1::2] = -1
    bank[2, :2, 8:16] = 1
    gid = [0, 1, 0, 0, 2, 0] if constrained else [0] * N
    return (jnp.asarray(rng.normal(size=(N, C, V)) * 3, dtype),
            jnp.asarray([0, 3, 1, 0, 2, 3], jnp.int32),
            jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32),
            jnp.asarray([0, 5, 0, 0, 9, 0], jnp.int32),
            jnp.asarray([1, 0.9, 1, 1, 1, 0.8], jnp.float32),
            jnp.asarray([False, True, False, False, True, True]),
            jnp.asarray(rng.integers(0, 1 << 30, N), jnp.int32),
            jnp.asarray(rng.integers(0, 50, N), jnp.int32),
            jnp.asarray([0, 2, 0, 0, 1, 0], jnp.int32) * (1 if constrained
                                                         else 0),
            jnp.asarray(gid, jnp.int32), jnp.asarray(bank))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("constrained", [False, True])
@pytest.mark.parametrize("jitted", [False, True])
def test_the_bank_is_read_only_for_a_constrained_row_and_changes_no_bit(
        constrained, jitted, dtype):
    """PR 52: with every row on the pass-through row 0 the mask is not
    built and the selections are the masked form's, bit for bit; a step
    with a grammar row builds it and every
    row, constrained or not, selects what it selected, with the same new
    DFA states."""
    import jax
    from paddle_tpu.serving.llm import sampling
    args = _tail_operands(constrained, dtype)
    want, want_state = _masked_select_tokens(*args)
    fn = jax.jit(sampling.select_tokens) if jitted \
        else sampling.select_tokens
    got, state = fn(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(state), np.asarray(want_state))
    if constrained:
        got = np.asarray(got)
        assert (got[1] % 2 == 0).all() and ((got[4] >= 8) & (got[4] < 16)
                                            ).all()
        assert np.asarray(state).tolist() == [
            0, (2 + int(got[1, 2])) % 3, 0, 0, 1, 0]


def test_the_grammar_bank_is_gathered_only_inside_the_conditional():
    """The `[N, V]` mask is no equation of `select_tokens`' own jaxpr: the
    rows of the bank are gathered inside a branch of the conditional on
    `grammar_id`, and what stays outside reads N scalars of it, the
    stepped states."""
    import jax
    from paddle_tpu.serving.llm import sampling
    args = _tail_operands(True, "float32")
    jaxpr = jax.make_jaxpr(sampling.select_tokens)(*args).jaxpr
    N, _, V = args[0].shape

    def gathers(jp, inside_cond=False):
        for eqn in jp.eqns:
            if eqn.primitive.name in ("gather", "dynamic_slice"):
                yield (inside_cond, eqn.invars[0].aval.shape,
                       eqn.outvars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from gathers(
                    sub, inside_cond or eqn.primitive.name == "cond")

    of_bank = [g for g in gathers(jaxpr) if g[1] == args[-1].shape]
    assert sorted(of_bank) == [(False, args[-1].shape, (N,)),
                               (True, args[-1].shape, (N, V))]


def test_a_prompts_last_chunk_draws_on_its_streams_own_lane(gpt_tiny):
    """A seeded sampling request whose prompt ends in a chunk of 3 and one
    of 16 columns: the tail's one column is the chunk's last, and its
    lane `(seed, stream index)` is the one the block's tail gave that
    column (`ctr` of the tail's column 0 is `ctr + adv - window`): the
    streams through the step and through `block_tail_step` are the same,
    a draft window's verify columns included."""
    from paddle_tpu import serving
    from test_packed_step import block_tail_step
    sp = _params(temperature=0.9, top_k=16, top_p=0.95, seed=4242)
    prompts = [np.arange(1, 20, dtype=np.int32),        # 16 + 3
               np.arange(3, 35, dtype=np.int32)]        # 16 + 16
    for draft in (None, gpt_tiny):
        outs = []
        for block_tail in (False, True):
            clock = serving.SimClock()
            eng = _engine(gpt_tiny, clock, draft=draft, n_blocks=16,
                          enable_prefix_cache=False)
            if block_tail:
                eng._step_jit = block_tail_step(eng)
            hs = [eng.submit(p, max_new_tokens=9, sampling=sp)
                  for p in prompts]
            _drain(eng, clock)
            outs.append([h.tokens_so_far() for h in hs])
            if draft is not None:
                assert eng.metrics.snapshot()["spec_windows"] > 0
            eng.stop()
        assert outs[0] == outs[1] and all(len(t) == 9 for t in outs[0])
    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock, n_blocks=16)
    greedy = eng.submit(prompts[0], max_new_tokens=9)
    _drain(eng, clock)
    assert greedy.tokens_so_far() != outs[0][0]
    eng.stop()


def test_engine_counts_sampling_steps_and_never_recompiles(gpt_tiny):
    """Greedy, then sampled, then greedy requests run one `jit_step`
    executable; `sampler_filter_steps` counts the committed steps that
    held a sampling row (0 before the first) and `sampled_rows` rides
    the dispatch span."""
    from paddle_tpu import profiler, serving
    from paddle_tpu.obs.goodput import RecompileSentinel
    from paddle_tpu.models.generation import generate
    from paddle_tpu.profiler import SPAN_SERVE_DISPATCH

    clock = serving.SimClock()
    # no prefix cache: its copy-on-write executable compiles on the
    # first repeated prompt, and this test repeats one on purpose
    eng = _engine(gpt_tiny, clock, enable_prefix_cache=False)

    def dispatches():
        return [e for e in profiler.get_events()
                if e["name"] == SPAN_SERVE_DISPATCH]

    profiler.start_profiler()       # the in-memory sink only
    try:
        g1 = eng.submit(_PROMPT, max_new_tokens=6)
        _drain(eng, clock)                      # compiles jit_step
        warm_steps = eng.unified_steps
        assert warm_steps > 0
        assert eng.metrics.snapshot()["sampler_filter_steps"] == 0
        assert all(e["args"]["sampled_rows"] == 0 for e in dispatches())

        sentinel = RecompileSentinel().install()
        try:
            sp = _params(temperature=0.9, top_k=16, top_p=0.95, seed=3)
            hs = eng.submit(_PROMPT, max_new_tokens=5, sampling=sp)
            hg = eng.submit(_PROMPT + 1, max_new_tokens=9)   # a batch-mate
            _drain(eng, clock)
            mixed_steps = eng.unified_steps - warm_steps
            ht = eng.submit(_PROMPT, max_new_tokens=4,
                            sampling=_params(temperature=1.2, seed=8))
            _drain(eng, clock)
            temp_steps = eng.unified_steps - warm_steps - mixed_steps
            g2 = eng.submit(_PROMPT, max_new_tokens=6)
            _drain(eng, clock)
        finally:
            sentinel.uninstall()
    finally:
        profiler._SINK.enabled = False
    eng.stop()
    assert sentinel.compiles == 0
    assert eng._step()._cache_size() == 1

    spans = dispatches()
    assert len(spans) == eng.unified_steps
    with_rows = [e for e in spans if e["args"]["sampled_rows"] > 0]
    assert {e["args"]["sampled_rows"] for e in with_rows} == {1}
    # the sampled request held a row for its prefill step and one step
    # per further token; the greedy batch-mate outlived it
    assert len(with_rows) == 5 + temp_steps
    assert mixed_steps > 5 and temp_steps == 4
    snap = eng.metrics.snapshot()
    assert snap["sampler_filter_steps"] == len(with_rows)
    assert snap["unified_steps"] == eng.unified_steps
    assert (f"pdtpu_llm_sampler_filter_steps_total {len(with_rows)}"
            in eng.metrics.render())

    # greedy rows are what one-shot generate() gives, before, beside and
    # after the sampling rows; the sampled stream differs from greedy
    want = np.asarray(generate(gpt_tiny, _PROMPT[None, :],
                               max_new_tokens=6))[0, len(_PROMPT):]
    np.testing.assert_array_equal(g1.result(0), want)
    np.testing.assert_array_equal(g2.result(0), want)
    mate = np.asarray(generate(gpt_tiny, (_PROMPT + 1)[None, :],
                               max_new_tokens=9))[0, len(_PROMPT):]
    np.testing.assert_array_equal(hg.result(0), mate)
    assert hs.result(0).size == 5 and ht.result(0).size == 4
