"""The unified step with its live tokens packed (ISSUE 28): an engine whose
`[slots, chunk]` block is wider than `step_tokens` (40 x 16 = 640 > 512)
computes 512 positions a step and every stream stays the bits `generate()`
gives; the scheduler keeps `sum(adv)` inside the budget, a prefill row that
does not fit waits a step; at `step_tokens == slots x chunk` nothing of the
pack is traced. CPU, float32, tiny models under a `SimClock`.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import generate
from paddle_tpu.models.gpt import GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs.goodput import RecompileSentinel
from paddle_tpu.ops.attention import token_pack
from paddle_tpu.profiler import SPAN_SERVE_DISPATCH
from paddle_tpu.serving.llm import llm_engine
from paddle_tpu.serving.llm.sampling import SamplingParams, select_tokens
from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan

from test_lora import _mk_tree         # a synthetic adapter that flips tokens

SLOTS, CHUNK = 40, 16                   # 640 positions, 512 computed
TINY_LLAMA = dict(vocab_size=128, hidden_size=64, intermediate_size=32,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128)


def _build(family):
    paddle.seed(0)
    if family == "gpt2-tiny":
        model = GPTForCausalLM.from_preset("gpt2-tiny")
    elif family == "llama-tiny":        # GQA: 2 KV heads for 4
        model = LlamaForCausalLM(LlamaConfig(**TINY_LLAMA))
    else:                               # the tiny OLMoE of tests/test_olmoe.py
        model = LlamaForCausalLM(LlamaConfig(
            **{**TINY_LLAMA, "num_key_value_heads": 4, "num_experts": 8,
               "num_experts_per_tok": 2, "qk_norm": True}))
    model.eval()
    return model


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(family):
        if family not in built:
            built[family] = _build(family)
        return built[family]

    return get


@pytest.fixture(scope="module")
def gpt_tiny(models):
    return models("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    set_global_plan(None)
    yield
    set_global_plan(None)


def _engine(model, slots=SLOTS, draft=None, plan=None, **cfg_kw):
    kw = dict(num_slots=slots, block_len=8, n_blocks=8, max_queue_depth=128,
              enable_prefix_cache=False)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=serving.SimClock(), draft_model=draft,
                             fault_plan=plan)


def _drain(eng):
    steps = 0
    while eng.has_work():
        eng.pump()
        steps += 1
        assert steps < 2000, "engine failed to converge"


def _prompts(vocab, lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in lengths]


def _generate(model, prompt, max_new):
    return np.asarray(generate(model, prompt[None], max_new_tokens=max_new
                               ).data)[0, len(prompt):]


def _dispatch_spans():
    return [e["args"] for e in profiler.get_events()
            if e["name"] == SPAN_SERVE_DISPATCH]


# ---- the pack index ---------------------------------------------------------

def test_step_tokens_follows_from_the_engines_shapes(gpt_tiny):
    """min(N*C, max(N*(1+k) + C, 512)), k the draft window of an engine
    that has a draft model and 0 of every other; no option sets it."""
    assert llm_engine.MIN_STEP_TOKENS == 512
    assert _engine(gpt_tiny).step_tokens == 512                 # 640 wide
    assert _engine(gpt_tiny, slots=4).step_tokens == 64         # not packed
    assert _engine(gpt_tiny, slots=32).step_tokens == 512       # == N*C
    # spec_k defaults to 4 and counts only beside a draft model
    assert _engine(gpt_tiny, slots=128, n_blocks=2).step_tokens == 512
    assert _engine(gpt_tiny, slots=128, n_blocks=2,
                   draft=gpt_tiny).step_tokens == 128 * 5 + 16
    assert "step_tokens" not in {
        f.name for f in serving.LLMEngineConfig.__dataclass_fields__.values()}


def test_token_pack_is_a_cumulative_sum_and_its_inverse():
    adv = jnp.asarray([0, 3, 1, 0, 4, 0], jnp.int32)
    pos = jnp.asarray([99, 10, 7, 99, 0, 99], jnp.int32)
    pack = token_pack(adv, pos, chunk=4, step_tokens=10)
    assert pack.live.tolist() == [True] * 8 + [False] * 2
    assert pack.slot.tolist() == [1, 1, 1, 2, 4, 4, 4, 4, 0, 0]
    assert pack.col.tolist() == [0, 1, 2, 0, 0, 1, 2, 3, 0, 0]
    assert pack.pos.tolist()[:8] == [10, 11, 12, 7, 0, 1, 2, 3]
    assert pack.src.tolist() == [4, 5, 6, 8, 16, 17, 18, 19, 0, 0]
    assert pack.last.tolist()[1:3] == [2, 3] and pack.last[4] == 7
    x = jnp.arange(6 * 4 * 2, dtype=jnp.float32).reshape(6, 4, 2)
    packed = pack.pack(x)
    assert packed.shape == (10, 1, 2)
    back = pack.unpack(packed)
    live = np.arange(4)[None, :] < np.asarray(adv)[:, None]
    np.testing.assert_array_equal(np.asarray(back)[live], np.asarray(x)[live])
    # columns past adv read the last packed position, whatever it holds
    np.testing.assert_array_equal(np.asarray(back)[0, 0],
                                  np.asarray(packed)[9, 0])
    # a full step: every packed position is live
    full = token_pack(jnp.asarray([4, 4], jnp.int32),
                      jnp.asarray([0, 0], jnp.int32), 4, 8)
    assert full.live.all() and full.src.tolist() == list(range(8))


# ---- (a) bit-identity with generate(), three decoder stacks -----------------

LENGTHS = [5, 16, 19, 33, 40]


@pytest.mark.parametrize("family", ["gpt2-tiny", "llama-tiny", "olmoe-tiny"])
def test_packed_streams_equal_generate(models, family):
    """44 requests on 40 slots x 16 (512 of 640 positions computed): every
    stream is one-shot `generate()`'s, through `models/gpt.py`, a GQA
    `models/llama.py` and its sparse-expert FFN alike."""
    model = models(family)
    eng = _engine(model)
    assert eng.step_tokens == 512 < SLOTS * CHUNK
    prompts = _prompts(model.config.vocab_size, LENGTHS * 9)[:44]
    handles = [eng.submit(p, max_new_tokens=6, logprobs=True)
               for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(np.asarray(h.result(0)),
                                      _generate(model, p, 6))
        assert all(np.isfinite(h.logprobs_so_far()))
    snap = eng.metrics.snapshot()
    assert snap["step_tokens_computed"] == 512 * snap["unified_steps"]
    assert snap["step_tokens_live"] == sum(len(p) + 5 for p in prompts)
    if family == "olmoe-tiny":
        # dead packed positions reach no expert: live tokens x top-k a layer
        np.testing.assert_array_equal(
            eng.moe_expert_tokens().sum(1),
            [snap["step_tokens_live"] * 2] * 2)
    eng.stop()


# ---- (b) every kind of row --------------------------------------------------

_TOKENS = {1: "{", 2: "}", 3: '"a"', 4: ":", 5: "1", 6: "23", 7: ",",
           8: '"b"', 9: "true", 10: "false"}
_SCHEMA = {"type": "object",
           "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"}},
           "required": ["a", "b"]}
_PROMPT = np.arange(1, 20, dtype=np.int32)      # 19 tokens: two chunks


def _kind(kind, model):
    """(engine options, submit options) of one kind of row."""
    if kind == "greedy":
        return {}, {}
    if kind == "sampled":
        return {}, dict(sampling=SamplingParams(
            temperature=0.8, top_k=20, top_p=0.9, seed=4242))
    if kind == "grammar":
        return {}, dict(sampling=SamplingParams(
            temperature=1.0, seed=7,
            grammar={"schema": _SCHEMA, "tokens": _TOKENS}))
    if kind == "lora":
        return dict(max_adapters=2, lora_rank=4), dict(adapter="ad1")
    assert kind == "draft"
    return dict(draft=model), {}


@pytest.mark.parametrize("kind", ["greedy", "sampled", "grammar", "lora",
                                  "draft"])
def test_packed_row_kinds_equal_the_unpacked_engine(gpt_tiny, kind):
    """The same request through a 2-slot engine (32 positions, nothing
    packed) and, among 30 greedy batch-mates, through a 40-slot one (512
    of 640 packed): the same tokens, and log-probabilities to float32
    rounding, whatever the row is: greedy, drawn with filters on its seeded lane, held to a
    grammar (the DFA state behind its last packed token), through a LoRA
    adapter (gathered per token), or verified as a draft window."""
    eng_kw, sub_kw = _kind(kind, gpt_tiny)
    outs = []
    for slots in (2, SLOTS):
        eng = _engine(gpt_tiny, slots=slots, **eng_kw)
        assert (eng.step_tokens < slots * CHUNK) == (slots == SLOTS)
        if kind == "lora":
            eng.register_adapter("ad1", _mk_tree(gpt_tiny, 1))
        mates = []
        if slots == SLOTS:
            mates = [eng.submit(p, max_new_tokens=7) for p in _prompts(
                gpt_tiny.config.vocab_size, [4, 9, 17, 30, 21, 12] * 5)]
        h = eng.submit(_PROMPT, max_new_tokens=10, logprobs=True, **sub_kw)
        _drain(eng)
        outs.append((h.tokens_so_far(), h.logprobs_so_far()))
        snap = eng.metrics.snapshot()
        if kind == "draft":
            assert snap["spec_accepted"] > 0
        if kind == "grammar":
            assert snap["constrained_tokens"] > 0
        for p, m in zip(_prompts(gpt_tiny.config.vocab_size,
                                 [4, 9, 17, 30, 21, 12] * 5), mates):
            if kind != "draft" or m is mates[0]:    # one generate() a length
                np.testing.assert_array_equal(np.asarray(m.result(0)),
                                              _generate(gpt_tiny, p, 7))
        eng.stop()
    assert outs[0][0] == outs[1][0] and len(outs[0][0]) > 0
    # the float32 log-softmax reduces a [T, 1, V] block in another order
    # than a [N, C, V] one: the last bits of a log-probability may differ
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-5)
    if kind in ("greedy", "draft"):
        np.testing.assert_array_equal(outs[1][0],
                                      _generate(gpt_tiny, _PROMPT, 10))
    if kind == "lora":
        assert outs[1][0] != list(_generate(gpt_tiny, _PROMPT, 10))


def test_select_tokens_on_packed_rows_equals_the_block(gpt_tiny):
    """`select_tokens` over `[T, 1, V]` with each slot's operands gathered
    per token gives, unpacked, what it gives over `[N, C, V]` at every live
    column, and the DFA state behind each slot's last live token."""
    rng = np.random.default_rng(3)
    N, C, V, T = 6, 4, 64, 12
    adv = jnp.asarray([0, 3, 1, 0, 4, 2], jnp.int32)
    logits = jnp.asarray(rng.normal(size=(N, C, V)), jnp.float32)
    temp = jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32)
    topk = jnp.asarray([0, 5, 0, 0, 9, 0], jnp.int32)
    topp = jnp.asarray([1, 0.9, 1, 1, 1, 0.8], jnp.float32)
    samp = jnp.asarray([False, True, False, False, True, True])
    seed = jnp.asarray(rng.integers(0, 1 << 30, N), jnp.int32)
    ctr = jnp.asarray(rng.integers(0, 50, N), jnp.int32)
    # grammar 1: token v moves state s to (s + v) % 3, odd tokens illegal
    bank = np.full((2, 3, V), 0, np.int32)
    bank[1] = (np.arange(3)[:, None] + np.arange(V)[None, :]) % 3
    bank[1, :, 1::2] = -1
    bank = jnp.asarray(bank)
    gid = jnp.asarray([0, 1, 0, 0, 0, 1], jnp.int32)
    dstate = jnp.asarray([0, 2, 0, 0, 0, 1], jnp.int32)
    want, want_state = select_tokens(logits, adv, temp, topk, topp, samp,
                                     seed, ctr, dstate, gid, bank)
    pack = token_pack(adv, jnp.zeros((N,), jnp.int32), C, T)
    g = lambda a: a[pack.slot]
    got, state = select_tokens(
        pack.pack(logits), pack.live.astype(jnp.int32), g(temp), g(topk),
        g(topp), g(samp) & pack.live, g(seed), g(ctr) + pack.col, g(dstate),
        g(gid), bank)
    live = np.arange(C)[None, :] < np.asarray(adv)[:, None]
    np.testing.assert_array_equal(np.asarray(pack.unpack(got))[live],
                                  np.asarray(want)[live])
    np.testing.assert_array_equal(
        np.where(np.asarray(adv) > 0, np.asarray(state)[pack.last], dstate),
        want_state)
    assert (np.asarray(want)[1, :3] % 2 == 0).all()     # the mask held


# ---- (c) the scheduler's budget ---------------------------------------------

def test_prefill_rows_that_do_not_fit_wait_in_admission_order(gpt_tiny):
    """40 prompts of 24 tokens at once want 640 positions of a 512-wide
    step: 32 ride it, the 8 admitted last wait with `adv = 0` and ride the
    next with their whole chunk. `live_tokens <= step_tokens` in every
    dispatch span; nobody starves; streams are `generate()`'s."""
    eng = _engine(gpt_tiny)
    prompts = _prompts(gpt_tiny.config.vocab_size, [24] * SLOTS, seed=9)
    profiler.start_profiler()           # the in-memory sink only
    try:
        handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
        # admits all 40, launches the first step and, ahead of it, the
        # second (built from what the first will commit), retires the first
        eng.pump()
        by_age = sorted(eng._active.values(), key=lambda r: r.submit_idx)
        assert [r.chunk_off for r in by_age] == [16] * 32 + [0] * 8
        first, second = _dispatch_spans()[-2:]
        assert first["live_tokens"] == 512 and first["deferred_rows"] == 8
        assert first["prefill_rows"] == 32 and first["in_flight"] == 0
        # second step: 8 whole first chunks beside 32 eight-token tails
        assert second["live_tokens"] == 32 * 8 + 8 * 16
        assert second["in_flight"] == 1
        eng.pump()                      # retires the second
        assert [r.chunk_off for r in by_age] == [24] * 32 + [16] * 8
        _drain(eng)
        spans = _dispatch_spans()
    finally:
        profiler._SINK.enabled = False
    assert all(s["live_tokens"] <= s["step_tokens"] == 512 for s in spans)
    snap = eng.metrics.snapshot()
    assert snap["prefill_rows_deferred"] == 8 \
        == sum(s["deferred_rows"] for s in spans)
    assert snap["step_tokens_live"] == sum(s["live_tokens"] for s in spans) \
        == SLOTS * (24 + 4)
    assert snap["completed"] == SLOTS
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(np.asarray(h.result(0)),
                                      _generate(gpt_tiny, p, 5))
    text = eng.metrics.render()
    assert "pdtpu_llm_prefill_rows_deferred_total 8" in text
    assert f"pdtpu_llm_step_tokens_live_total {SLOTS * 28}" in text
    assert (f"pdtpu_llm_step_tokens_computed_total "
            f"{512 * snap['unified_steps']}") in text
    eng.stop()


def test_decode_rows_are_placed_first_and_a_smaller_chunk_may_fill(gpt_tiny):
    """Decode rows always ride. Of the prefill rows each rides if its whole
    chunk still fits when its turn comes, oldest first: a chunk that does
    not fit waits, a younger, smaller one behind it may take the room."""
    eng = _engine(gpt_tiny)
    vocab = gpt_tiny.config.vocab_size
    early = [eng.submit(p, max_new_tokens=30)
             for p in _prompts(vocab, [6] * 7, seed=1)]
    eng.pump()
    eng.pump()                          # 7 decode rows now
    # 31 x 16 = 496 beside 7 decode tokens: 503; the 32nd 16-token chunk
    # does not fit into the 9 left, the 3-token prompt behind it does
    late = [eng.submit(p, max_new_tokens=2)
            for p in _prompts(vocab, [16] * 31 + [16, 3], seed=2)]
    profiler.start_profiler()
    try:
        eng.pump()
        span = _dispatch_spans()[-1]
    finally:
        profiler._SINK.enabled = False
    assert span["decode_rows"] == 7 and span["prefill_rows"] == 32
    assert span["live_tokens"] == 7 + 31 * 16 + 3
    assert span["deferred_rows"] == 1
    eng.pump()                          # that step, launched ahead, retires
    waiting = [r for r in eng._active.values() if r.chunk_off == 0]
    assert [len(r.prompt) for r in waiting] == [16]
    _drain(eng)
    assert all(len(h.result(0)) == 30 for h in early)
    assert all(len(h.result(0)) == 2 for h in late)
    eng.stop()


# ---- (d) what a step lowers to ----------------------------------------------

def step_args(eng, drafts=None):
    """(`adv`, the operands of the next step as `_launch` would build
    them): the queue admitted, rows from the committed state."""
    with eng._cond:
        eng._admit()
        toks, pos, adv, ctr, *_ = eng._build_rows_locked(drafts or {})
        args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(adv), eng.pool.device_block_table(),
                eng.pool.slabs) + eng._sampling_args_locked(ctr) \
            + eng._feedback_args() + eng._tail_args_locked()
    return adv, args


def both_tails(eng, args):
    """One step's `(sel, lp, state)` through `block_tail_step` and through
    the engine's own step, each donated a copy of the pool."""
    copy = lambda: jax.tree_util.tree_map(jnp.copy, eng.pool.slabs)
    return [tuple(np.asarray(a) for a in fn(*args[:5], copy(), *args[6:])[:3])
            for fn in (block_tail_step(eng), eng._step())]


def _lowered(eng, prompt):
    eng.submit(prompt, max_new_tokens=2)
    _, args = step_args(eng)
    return args, eng._step().lower(*args).as_text()


def _exp_shapes(text, vocab):
    """Shapes of the float32 exponentials over the vocabulary in a lowered
    step: the log-softmax's, so the rows the tail runs on."""
    return set(re.findall(
        rf"stablehlo\.exponential %\d+ : tensor<(\d+x\d+x{vocab})xf32>", text))


def block_tail_step(eng):
    """The unified step with its tail over the whole block, written out: the
    form every step had before the tail was narrowed (PR 52) and the one an
    engine still traces where `slots x window` is no fewer than the
    positions it computes. The head, the selection and the log-softmax run
    on every computed position (`[step_tokens, 1, V]` under a pack, `[N, C,
    V]` without), with each slot's sampling operands gathered per packed
    token, and `sel` / `lp` hold a selection at every column. Takes
    `eng._step()`'s operands and gives its results: set as `eng._step_jit`
    before an engine's first step, the engine runs it instead."""
    view, prefill = eng.pool.view, eng._prefill_fn
    chunk, step_tokens = eng.config.prefill_chunk, eng.step_tokens
    packed = step_tokens < eng.pool.num_slots * chunk

    def step(params, toks, pos, adv, table, slabs, temp, topk, topp, samp,
             seed, ctr, dstate, gid, bank, feed, prev_sel, adapters=None,
             moe_totals=None):
        # `slabs` is donated: the new slabs take its buffers
        # the input token of a row launched ahead of its predecessor
        fed = jnp.take_along_axis(
            prev_sel, jnp.maximum(feed, 0)[:, None], axis=1)[:, 0]
        toks = toks.at[:, 0].set(
            jnp.where(feed >= 0, fed.astype(toks.dtype), toks[:, 0]))
        paged = view(table, (pos + adv).astype(jnp.int32))
        pack = None
        rows_adv, rows_dstate = adv, dstate
        if packed:
            pack = token_pack(adv, pos, chunk, step_tokens)
            toks, pos = pack.pack(toks), pack.pos
            adv = pack.live.astype(jnp.int32)
            temp, topk, topp, seed, dstate, gid = (
                a[pack.slot] for a in (temp, topk, topp, seed, dstate, gid))
            samp = samp[pack.slot] & pack.live
            ctr = ctr[pack.slot] + pack.col
            if adapters is not None:
                banks, adapter_idx, scale = adapters
                adapters = (banks, adapter_idx[pack.slot], scale)
        with llm_engine.moe.collect_expert_counts() as counts:
            logits, new_slabs = prefill(params, toks, slabs, pos,
                                        paged=paged, adapters=adapters,
                                        pack=pack)
        sel, state = select_tokens(logits, adv, temp, topk, topp, samp,
                                   seed, ctr, dstate, gid, bank)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
            sel[..., None].astype(jnp.int32), axis=-1)[..., 0]
        if packed:
            sel, lp = pack.unpack(sel), pack.unpack(lp)
            state = jnp.where(rows_adv > 0, state[pack.last], rows_dstate)
        if moe_totals is None:
            return sel, lp, state, new_slabs
        return sel, lp, state, new_slabs, moe_totals + jnp.stack(counts)

    return jax.jit(step, donate_argnames=("slabs",))


@pytest.mark.parametrize("family", ["gpt2-tiny", "llama-tiny", "olmoe-tiny"])
def test_an_engine_no_wider_than_step_tokens_lowers_to_the_old_step(
        models, family, monkeypatch):
    """`step_tokens == slots x chunk` (every engine the suite built before
    this file, and the benchmark's prefill cells at 32 x 16): the step's
    text is the text of the step written out here without a pack, its tail
    on the slots' emission rows (`n * C + adv - 1`); `token_pack` is never
    called; a packed engine's text differs, and its float32 logits are
    `[slots, 1, V]`, not the 512 packed positions'."""
    model = models(family)
    prompt = _prompts(model.config.vocab_size, [11])[0]
    eng = _engine(model, slots=4)
    assert eng.step_tokens == 4 * CHUNK

    def no_pack(*a, **k):
        raise AssertionError("token_pack traced at step_tokens == N * C")

    monkeypatch.setattr(llm_engine, "token_pack", no_pack)
    args, text = _lowered(eng, prompt)
    monkeypatch.undo()

    view = eng.pool.view
    prefill = eng._prefill_fn

    def step(params, toks, pos, adv, table, slabs, temp, topk, topp, samp,
             seed, ctr, dstate, gid, bank, feed, prev_sel, moe_totals=None):
        # `slabs` is donated: the new slabs take its buffers
        # the input token of a row launched ahead of its predecessor
        fed = jnp.take_along_axis(
            prev_sel, jnp.maximum(feed, 0)[:, None], axis=1)[:, 0]
        toks = toks.at[:, 0].set(
            jnp.where(feed >= 0, fed.astype(toks.dtype), toks[:, 0]))
        paged = view(table, (pos + adv).astype(jnp.int32))
        # the one column a row's host reads, as a position of `[N * C]`
        first = jnp.maximum(adv - 1, 0)
        cols = first[:, None] + jnp.arange(1, dtype=jnp.int32)
        emit = (cols + CHUNK * jnp.arange(4, dtype=jnp.int32)[:, None]
                ).reshape(-1)
        with llm_engine.moe.collect_expert_counts() as counts:
            logits, new_slabs = prefill(params, toks, slabs, pos,
                                        paged=paged, adapters=None,
                                        pack=None, emit=emit)
        logits = logits.reshape(4, 1, -1)
        adv, ctr = jnp.minimum(adv, 1), ctr + first
        sel, state = select_tokens(logits, adv, temp, topk, topp, samp,
                                   seed, ctr, dstate, gid, bank)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
            sel[..., None].astype(jnp.int32), axis=-1)[..., 0]
        # `[N, C]` again: the column at its own place, zeros elsewhere
        j = jnp.arange(CHUNK, dtype=jnp.int32) - first[:, None]
        read = (j >= 0) & (j < adv[:, None])
        j = jnp.clip(j, 0, 0)
        sel, lp = (jnp.where(read, jnp.take_along_axis(a, j, axis=1), 0)
                   for a in (sel, lp))
        if moe_totals is None:
            return sel, lp, state, new_slabs
        return sel, lp, state, new_slabs, moe_totals + jnp.stack(counts)

    if len(args) == 19:                 # a sparse model: (None, totals)
        args = args[:17] + (args[18],)
    assert text == jax.jit(
        step, donate_argnames=("slabs",)).lower(*args).as_text()

    vocab = model.config.vocab_size
    packed_eng = _engine(model)
    _, packed_text = _lowered(packed_eng, prompt)
    # the log-softmax's exponential says what the tail runs on (gpt2-tiny's
    # MLP is as wide as its vocabulary: a bare shape would not)
    assert _exp_shapes(packed_text, vocab) == {f"{SLOTS}x1x{vocab}"}
    assert _exp_shapes(text, vocab) == {f"4x1x{vocab}"}
    assert packed_text.count("stablehlo.gather") > text.count(
        "stablehlo.gather")


@pytest.mark.parametrize("family", ["gpt2-tiny", "llama-tiny"])
def test_a_tail_no_narrower_than_the_block_lowers_to_the_block_tail_step(
        models, family, monkeypatch):
    """`slots x window >= step_tokens`: an unpacked engine whose draft
    window is as wide as its chunk reads every column of every row.
    Nothing is gathered (`take_positions` is never called) and the step's
    text is, to the character, the step whose tail runs on the block; one
    column narrower, the tail is `[slots, window, V]`."""
    model = models(family)
    prompt = _prompts(model.config.vocab_size, [11])[0]
    eng = _engine(model, slots=4, draft=model, prefill_chunk=4, spec_k=3)
    assert eng.step_tokens == 4 * 4 == eng._head_positions

    def no_gather(*a, **k):
        raise AssertionError("emission rows gathered at slots x window "
                             ">= step_tokens")

    from paddle_tpu.models import gpt, llama
    monkeypatch.setattr(gpt, "take_positions", no_gather)
    monkeypatch.setattr(llama, "take_positions", no_gather)
    args, text = _lowered(eng, prompt)
    monkeypatch.undo()
    assert text == block_tail_step(eng).lower(*args).as_text()
    vocab = model.config.vocab_size
    assert _exp_shapes(text, vocab) == {f"4x4x{vocab}"}

    narrow = _engine(model, slots=4, draft=model, prefill_chunk=4, spec_k=2)
    assert narrow._head_positions == 4 * 3 < narrow.step_tokens
    _, narrow_text = _lowered(narrow, prompt)
    assert _exp_shapes(narrow_text, vocab) == {f"4x3x{vocab}"}


def _run(eng, prompts, news, **kw):
    hs = [eng.submit(p, max_new_tokens=n, logprobs=True, **kw)
          for p, n in zip(prompts, news)]
    _drain(eng)
    return [(h.tokens_so_far(), h.logprobs_so_far()) for h in hs]


@pytest.mark.parametrize("slots", [4, SLOTS])
def test_the_narrowed_tail_reads_what_the_block_tail_read(gpt_tiny, slots):
    """Every kind of row in one run, through the step as it is and through
    `block_tail_step`: a prompt's middle chunk and its last (24 and 40
    tokens), plain decode rows, rows launched ahead through `feed` (every
    step but the first), slots that stand free while others still decode
    (streams of 3 to 9 tokens), and on the packed engine rows deferred for
    room (40 prompts want 640 positions). Tokens and first tokens are the
    same and `generate()`'s; the log-probabilities agree to float32
    rounding (the CPU's matrix product gives a row of `[slots, hidden] x
    [hidden, V]` other last bits than the same row of `[512, hidden] x
    [hidden, V]`; given the same logits the tail is bit for bit the
    block's, `tests/test_sampling.py`)."""
    vocab = gpt_tiny.config.vocab_size
    lengths = ([24, 40, 5, 19, 33, 17] * 7)[:slots + 2]
    prompts = _prompts(vocab, lengths, seed=11)
    news = [3 + i % 7 for i in range(len(prompts))]
    runs = []
    for block_tail in (False, True):
        eng = _engine(gpt_tiny, slots=slots)
        if block_tail:
            eng._step_jit = block_tail_step(eng)
        runs.append(_run(eng, prompts, news))
        snap = eng.metrics.snapshot()
        assert snap["steps_overlapped"] == snap["unified_steps"] - 1
        assert (snap["prefill_rows_deferred"] > 0) == (slots == SLOTS)
        assert snap["head_positions"] == slots * snap["unified_steps"]
        eng.stop()
    for (toks, lps), (want_toks, want_lps), p, n in zip(*runs, prompts,
                                                        news):
        assert toks == want_toks and len(toks) == n
        np.testing.assert_allclose(lps, want_lps, rtol=1e-5)
    for (toks, _), p, n in list(zip(runs[0], prompts, news))[:6]:
        np.testing.assert_array_equal(toks, _generate(gpt_tiny, p, n))


@pytest.mark.parametrize("slots", [4, SLOTS])
def test_columns_nobody_reads_hold_zeros(gpt_tiny, slots):
    """One step over chunks, a decode row, a free slot and (packed) deferred
    rows: `sel` and `lp` hold at column `adv - 1` what the block's tail
    gives there (the token; the log-probability to float32 rounding),
    zeros in every other column, and all zeros in a row that rode with
    `adv` 0; the DFA states are the same."""
    vocab = gpt_tiny.config.vocab_size
    eng = _engine(gpt_tiny, slots=slots)
    first = eng.submit(_prompts(vocab, [3])[0], max_new_tokens=4)
    eng._admit()
    eng._retire(eng._launch())          # one row decodes from here on
    assert len(first.tokens_so_far()) == 1
    lengths = [24] * (slots - 2) if slots == SLOTS else [24, 7]
    for p in _prompts(vocab, lengths, seed=2):
        eng.submit(p, max_new_tokens=2)
    adv, args = step_args(eng)
    kinds = {int(a) for a in adv}
    assert kinds == ({0, 1, 16} if slots == SLOTS else {0, 1, 7, 16})
    if slots == SLOTS:                  # 38 chunks want 608 positions
        assert int(adv.sum()) <= 512 and (adv == 0).sum() > 1
    (want_sel, want_lp, want_state), (sel, lp, state) = both_tails(eng, args)
    read = np.arange(CHUNK)[None, :] == (adv - 1)[:, None]
    np.testing.assert_array_equal(sel[read], want_sel[read])
    np.testing.assert_allclose(lp[read], want_lp[read], rtol=1e-5)
    assert read.sum() == (adv > 0).sum() and (lp[read] < 0).all()
    assert not sel[~read].any() and not lp[~read].any()
    np.testing.assert_array_equal(state, want_state)
    eng.stop()


def test_an_unpacked_engine_counts_the_whole_block_a_step(gpt_tiny):
    eng = _engine(gpt_tiny, slots=4)
    hs = [eng.submit(p, max_new_tokens=4)
          for p in _prompts(gpt_tiny.config.vocab_size, [7, 20, 3, 18, 9])]
    profiler.start_profiler()
    try:
        _drain(eng)
        spans = _dispatch_spans()
    finally:
        profiler._SINK.enabled = False
    snap = eng.metrics.snapshot()
    assert snap["step_tokens_computed"] == 4 * CHUNK * snap["unified_steps"]
    assert snap["prefill_rows_deferred"] == 0
    assert snap["step_tokens_live"] == 7 + 20 + 3 + 18 + 9 + 5 * 3
    assert all(s["step_tokens"] == 4 * CHUNK and s["deferred_rows"] == 0
               for s in spans)
    assert all(len(h.result(0)) == 4 for h in hs)
    eng.stop()


# ---- (e) one executable; probes pack themselves -----------------------------

def test_a_mix_of_every_row_kind_never_recompiles_the_packed_step(gpt_tiny):
    """Greedy, sampled, constrained, adapter and draft-window rows, a
    budget that binds and one that does not: one `jit_step`, no compile
    after the warm-up."""
    eng = _engine(gpt_tiny, draft=gpt_tiny, max_adapters=2, lora_rank=4)
    eng.register_adapter("ad1", _mk_tree(gpt_tiny, 1))
    vocab = gpt_tiny.config.vocab_size
    warm = eng.submit(_prompts(vocab, [21], seed=8)[0], max_new_tokens=12)
    _drain(eng)         # compiles jit_step, the draft step and its scan
    assert len(warm.result(0)) == 12
    sentinel = RecompileSentinel().install()
    sentinel.mark_warm()
    try:
        hs = [eng.submit(p, max_new_tokens=6)
              for p in _prompts(vocab, [24] * 36, seed=4)]
        hs.append(eng.submit(_PROMPT, max_new_tokens=8,
                             sampling=_kind("sampled", None)[1]["sampling"]))
        hs.append(eng.submit(_PROMPT, max_new_tokens=8,
                             sampling=_kind("grammar", None)[1]["sampling"]))
        hs.append(eng.submit(_PROMPT, max_new_tokens=8, adapter="ad1"))
        _drain(eng)
    finally:
        sentinel.uninstall()
    assert sentinel.recompiles == 0
    assert eng._step()._cache_size() == 1
    snap = eng.metrics.snapshot()
    assert snap["prefill_rows_deferred"] > 0 and snap["spec_windows"] > 0
    assert snap["completed"] == 40 and all(len(h.result(0)) > 0 for h in hs)
    eng.stop()


@pytest.mark.fault_matrix
def test_a_quarantine_probe_packs_itself(gpt_tiny):
    """`poison_request@3:decode` on a packed engine: the solo probes run
    the same executable over rows rebuilt from `toks/pos/adv`, blame
    request 3 alone, and the 11 survivors' streams are `generate()`'s."""
    plan = FaultPlan.from_spec("poison_request@3:decode")
    eng = _engine(gpt_tiny, plan=plan)
    prompts = _prompts(gpt_tiny.config.vocab_size, [5, 16, 19, 33] * 3)
    handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
    _drain(eng)
    for i, (p, h) in enumerate(zip(prompts, handles)):
        if i == 3:
            with pytest.raises(serving.DispatchFailedError,
                               match="isolation") as exc:
                h.result(timeout=0)
            assert exc.value.reason == "poisoned"
            assert len(h.tokens_so_far()) >= 1      # it did prefill
        else:
            np.testing.assert_array_equal(np.asarray(h.result(0)),
                                          _generate(gpt_tiny, p, 5))
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["completed"] == 11
    assert eng._step()._cache_size() == 1 and not eng.broken
    eng.pool.check_balance()
    eng.stop()
