"""Mellum 2's block on the serving path: window and full attention layers
in one model (`LlamaConfig.layer_types`), the window layers' keys in a
ring beside full-length pages, rotary parameters by layer type (YaRN), a
head width of its own and an expert share. The uncached forward, the
cached forward through the ring and `LLMEngine` against the benchmark's
plain reference (`benchmark/reference/mellum.py`, logits) and against
`generate()` (bits); what a ring rules out refused by name. CPU, float32,
tiny widths: hidden 48, 4 / 2 heads of 16 (so q is 64 wide), two periods
of sliding x 3 + full, window 32, 8 experts of width 32 (2 per token),
YaRN over 64 original positions.

Initial values: q and k projections N(0, 0.2), the other matrices N(0,
0.1), so that attention is far from uniform and a key dropped from or
added to a query's view moves the logits by 1 to 3, against a tolerance of
1e-4 (`test_the_window_and_yarn_carry_the_logits`); larger still, and
float32 rounding grows tenfold every two layers.
"""
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import (WindowKV, generate,
                                          make_decoder_fns)
from paddle_tpu.models.llama import (FULL, SLIDING, LlamaConfig,
                                     LlamaForCausalLM, rope_inv_freq)
from paddle_tpu.nn.layer.moe import DroplessMoE
from paddle_tpu.profiler import SPAN_SERVE_DISPATCH
from paddle_tpu.serving.llm.kv_pool import SlotPagedKVPool, WindowRingError

from benchmark.reference import mellum as ref

VOCAB, WINDOW, CHUNK = 128, 32, 16
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 64, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
TINY = dict(vocab_size=VOCAB, hidden_size=48, intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=512, rms_norm_eps=1e-6,
            layer_types=[SLIDING, SLIDING, SLIDING, FULL] * 2,
            sliding_window=WINDOW, rope_parameters=ROPE, num_experts=8,
            num_experts_per_tok=2, norm_topk_prob=True)
# the same sizes as the reference reads them (the benchmark's keys)
REF = dict(num_hidden_layers=8, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
           layer_types=TINY["layer_types"], rope_parameters=ROPE,
           sliding_window=WINDOW, num_experts_per_tok=2,
           norm_topk_prob=True)


def _seed_weights(model, seed=5):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if len(p.shape) < 2:
            continue                                  # norm scales stay 1
        std = 0.2 if ("q_proj" in name or "k_proj" in name) else 0.1
        p.data = jnp.asarray(rng.normal(0.0, std, p.shape), p.data.dtype)
    return model


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(0)
    model = _seed_weights(LlamaForCausalLM(LlamaConfig(**TINY)))
    model.eval()
    return model


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32) for n in lengths]


def _engine(model, block_len=8, num_slots=3, tokens=256, **kw):
    cfg = serving.LLMEngineConfig(
        num_slots=num_slots, block_len=block_len,
        n_blocks=tokens // block_len, max_new_tokens=48,
        max_queue_depth=128, **kw)
    return serving.LLMEngine(model, cfg, clock=serving.SimClock())


def _drain(eng):
    while eng.has_work():
        eng.pump()


# ---- the model against the plain reference ----

def test_head_width_is_its_own_and_layers_differ_by_type(tiny):
    attn = tiny.llama.layers[0].self_attn
    assert tiny.config.head_dim == 16 != 48 // 4
    assert tuple(attn.q_proj.weight.shape) == (48, 64)
    assert tuple(attn.k_proj.weight.shape) == (48, 32)
    assert tuple(attn.o_proj.weight.shape) == (64, 48)
    kinds = [layer.self_attn.window for layer in tiny.llama.layers]
    assert kinds == [32, 32, 32, None] * 2
    assert tiny.llama.layers[3].self_attn.rope["rope_type"] == "yarn"
    assert tiny.llama.layers[0].self_attn.rope["rope_type"] == "default"
    # a preset's derived head width follows an overridden size
    assert LlamaForCausalLM.from_preset(
        "llama2-tiny", hidden_size=64, num_hidden_layers=1
    ).config.head_dim == 16
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig(num_hidden_layers=2, layer_types=[FULL])
    with pytest.raises(ValueError, match="sliding_window"):
        LlamaConfig(num_hidden_layers=1, layer_types=[SLIDING])


def test_yarn_frequencies_against_hand_computed_values():
    """Mellum 2's own parameters: head 128, theta 500,000, factor 16 over
    8,192 positions. low = floor(128 ln(8192 / (32 * 2 pi)) / (2 ln 5e5))
    = floor(18.08) = 18, high = ceil(128 ln(8192 / (2 pi)) / (2 ln 5e5)) =
    ceil(34.99) = 35: dimensions up to 18 keep their frequency, from 35 on
    take a sixteenth, a ramp of seventeenths between."""
    yarn = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    base = 500000.0 ** (-np.arange(64) / 64.0)
    for compute in (rope_inv_freq, ref.inv_freq):
        inv, factor = compute(128, yarn)
        inv = np.asarray(inv, np.float64)
        assert factor == 1.2772588722239782
        np.testing.assert_allclose(inv[:19], base[:19], rtol=2e-6)
        np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=2e-6)
        np.testing.assert_allclose(
            inv[26], base[26] * (1 - 8 / 17 + 8 / 17 / 16), rtol=2e-6)
        assert inv[19] < base[19] and inv[34] > base[34] / 16
        plain, one = compute(128, {"rope_type": "default",
                                   "rope_theta": 500000})
        np.testing.assert_allclose(np.asarray(plain), base, rtol=2e-6)
        assert one == 1.0
    # the published attention_factor is 0.1 ln(factor) + 1, the default
    del yarn["attention_factor"]
    assert rope_inv_freq(128, yarn)[1] == pytest.approx(
        0.1 * math.log(16) + 1, rel=1e-12)
    assert 0.1 * math.log(16) + 1 == pytest.approx(1.2772588722239782)


def test_uncached_forward_equals_reference(tiny):
    ids = np.stack(_prompts([120, 120], seed=2))
    got = np.asarray(tiny(paddle.to_tensor(ids)).data)
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_the_window_and_yarn_carry_the_logits(tiny):
    """What the comparisons below would miss if attention were uniform:
    the reference without the window, and with plain rotary embedding in
    the full layers, is far from the model; behind the window's reach the
    first still agrees."""
    ids = np.stack(_prompts([120], seed=3))
    got = np.asarray(tiny(paddle.to_tensor(ids)).data)
    w = _weights(tiny)
    no_window = np.asarray(ref.logits(
        w, jnp.asarray(ids), {**REF, "sliding_window": None}))
    plain_rope = np.asarray(ref.logits(
        w, jnp.asarray(ids),
        {**REF, "rope_parameters": {**ROPE, FULL: ROPE[SLIDING]}}))
    assert np.abs(got - no_window)[:, WINDOW:].max() > 0.1
    assert np.abs(got - no_window)[:, :WINDOW].max() < 1e-4
    assert np.abs(got - plain_rope).max() > 0.1


# ---- the cached forward through the ring: logits ----

@pytest.mark.parametrize("block_len", [8, 16])
def test_chunked_prefill_and_decode_through_the_ring_equal_reference(
        tiny, block_len):
    """The engine's own cached forward (`make_decoder_fns`' prefill with
    the pool's slabs and its `paged` operand) over prompts shorter than
    the window, crossing it, and twice round the ring: chunks of 16, then
    one token at a time, logits at every position against the reference's
    full forward. The ring is 48 columns (3 or 6 pages)."""
    lengths = [20, 43, 130]            # < window; across; > 2 x ring
    decode = 12
    pool = SlotPagedKVPool(tiny.init_cache, len(lengths), block_len,
                           160 // block_len, pad_tokens=CHUNK)
    assert pool.ring_len == 48 and pool.ring_pages == 48 // block_len
    params, prefill, _ = make_decoder_fns(tiny)
    seqs = _prompts([n + decode for n in lengths], seed=4)
    paged = jax.jit(lambda toks, pos, adv, slabs: prefill(
        params, toks, slabs, pos,
        paged=pool.view(pool.device_block_table(), pos + adv)))
    got = [[] for _ in lengths]
    done = np.zeros(len(lengths), np.int32)
    slabs = pool.slabs
    while (done < [len(s) for s in seqs]).any():
        toks = np.zeros((len(lengths), CHUNK), np.int32)
        adv = np.zeros(len(lengths), np.int32)
        pos = np.full(len(lengths), pool.capacity, np.int32)   # parked
        for b, seq in enumerate(seqs):
            if done[b] >= len(seq):
                continue
            # chunks through the prompt, then single tokens
            n = min(CHUNK, lengths[b] - done[b]) \
                if done[b] < lengths[b] else 1
            toks[b, :n], adv[b], pos[b] = seq[done[b]:done[b] + n], n, \
                done[b]
        logits, slabs = paged(jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray(adv), slabs)
        for b in range(len(lengths)):
            got[b].append(np.asarray(logits[b, :adv[b]]))
            done[b] += adv[b]
    for b, seq in enumerate(seqs):
        want = np.asarray(ref.logits(_weights(tiny),
                                     jnp.asarray(seq[None]), REF))[0]
        np.testing.assert_allclose(np.concatenate(got[b]), want,
                                   atol=1e-4, rtol=0)


# ---- LLMEngine ----

@pytest.mark.parametrize("block_len,num_slots", [(8, 3), (16, 3), (8, 40)])
def test_engine_streams_equal_generate_and_the_reference(tiny, block_len,
                                                         num_slots):
    """Prompts below, across and twice round the ring through the engine
    (unpacked step, and packed at 40 slots), mixed prefill and decode
    rows, slots reused: every stream is `generate()`'s (bit-identical at
    its block size, 8), and every token's log-probability the
    reference's."""
    eng = _engine(tiny, block_len, num_slots)
    assert eng.pool.layer_kinds == ["window"] * 3 + ["paged"] \
        + ["window"] * 3 + ["paged"]
    prompts = _prompts([9, 20, 43, 130, 31, 97, 48, 64], seed=6)
    handles = [eng.submit(p, max_new_tokens=24, logprobs=True)
               for p in prompts]
    _drain(eng)
    w = _weights(tiny)
    for p, h in zip(prompts, handles):
        out = np.asarray(h.result(timeout=10))
        want = np.asarray(generate(tiny, p[None],
                                   max_new_tokens=24).data)[0, len(p):]
        if block_len == 8:
            assert np.array_equal(out, want), len(p)
        ids = np.concatenate([p, out])
        lg = np.asarray(ref.logits(w, jnp.asarray(ids[None]), REF))[0]
        lp = np.asarray(jax.nn.log_softmax(lg, -1))
        at = np.arange(len(p) - 1, len(ids) - 1)
        np.testing.assert_allclose(
            np.asarray(h.logprobs_so_far()), lp[at, out], atol=1e-4, rtol=0)
        if block_len != 8:          # the same token unless a near-tie
            assert (lg[at].max(-1) - lg[at, out]).max() < 1e-3
    assert eng.pool.check_balance()
    assert eng.metrics.snapshot()["rows_discarded"] == 0
    assert eng._step()._cache_size() == 1


def test_counters_gauges_and_span_args_of_a_windowed_engine(tiny, caplog):
    with caplog.at_level(logging.WARNING):
        eng = _engine(tiny)
    assert "enable_prefix_cache is switched off" in caplog.text
    assert eng.enable_prefix_cache is False and eng.prefix_cache is None
    assert eng.config.enable_prefix_cache is True
    by_kind = eng.pool.kv_bytes()
    # 3 slots x 2 KV heads x 16 x float32, K and V; 2 full layers of 256 +
    # 16 columns, 6 window layers of 48 + 16
    assert by_kind == {"full": 2 * 2 * 3 * 2 * 272 * 16 * 4,
                       "window": 6 * 2 * 3 * 2 * 64 * 16 * 4}
    assert serving.metrics.KV_POOL_BYTES == by_kind
    profiler.start_profiler()
    try:
        for p in _prompts([70, 10], seed=8):
            eng.submit(p, max_new_tokens=4)
        _drain(eng)
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    snap = eng.metrics.snapshot()
    assert snap["kv_pool_bytes"] == by_kind
    # per committed step, summed over its active rows: the keys one full
    # layer's call reads (the row's length after the step) and one window
    # layer's (at most the window)
    assert spans and all("window_rows" in s for s in spans)
    assert max(s["wrapped_rows"] for s in spans) == 1          # 70 > 48
    assert max(s["window_rows"] for s in spans) == 2
    assert 0 < snap["window_kv_tokens"] < snap["full_kv_tokens"]
    # 70 tokens: five chunks, then three decode steps (the fourth token
    # ends the request with the step that computes it); 10: one chunk
    full = sum(min(16 * (i + 1), 70) for i in range(5)) + 10 \
        + sum(70 + i for i in range(1, 4)) + sum(10 + i for i in range(1, 4))
    assert snap["full_kv_tokens"] == full
    window = sum(min(16 * (i + 1), 70, 32) for i in range(5)) + 10 \
        + 3 * 32 + sum(10 + i for i in range(1, 4))
    assert snap["window_kv_tokens"] == window
    text = eng.metrics.render()
    assert 'pdtpu_llm_kv_pool_bytes{kind="window"}' in text
    assert "pdtpu_llm_window_kv_tokens_total" in text
    assert "pdtpu_llm_full_kv_tokens_total" in text


def test_what_a_ring_cannot_serve_is_refused_by_name(tiny):
    with pytest.raises(ValueError, match="window layers"):
        _engine(tiny, host_kv_bytes=1 << 20)
    eng = _engine(tiny)
    p = _prompts([40], seed=9)[0]
    with pytest.raises(ValueError, match="window layers"):
        eng.submit(p, kv_row={"block_len": 8, "length": 8, "layers": []})
    h = eng.submit(p, max_new_tokens=8)
    for _ in range(6):
        eng.pump()
    with pytest.raises(WindowRingError, match="export_rows"):
        eng.export_stream(h.rid)
    _drain(eng)
    assert len(h.result(timeout=10)) == 8


def test_draft_windows_ride_the_ring(tiny):
    """Speculative decoding with a windowed target and draft (the same
    model, so every draft token is accepted): verify windows of spec_k + 1
    columns start at odd positions, straddle the ring's end and are split,
    the draft pool rewinds inside the ring's slack; streams stay
    `generate()`'s."""
    cfg = serving.LLMEngineConfig(num_slots=2, block_len=8, n_blocks=24,
                                  max_new_tokens=48, spec_k=4)
    eng = serving.LLMEngine(tiny, cfg, draft_model=tiny,
                            clock=serving.SimClock())
    prompts = _prompts([37, 90], seed=10)
    handles = [eng.submit(p, max_new_tokens=40) for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        want = np.asarray(generate(tiny, p[None],
                                   max_new_tokens=40).data)[0, len(p):]
        assert np.array_equal(np.asarray(h.result(timeout=10)), want)
    assert eng.metrics.snapshot()["spec_windows"] > 0
    assert eng.pool.check_balance() and eng.draft_pool.check_balance()


def test_a_model_without_window_layers_builds_the_pool_it_built():
    paddle.seed(0)
    plain = LlamaForCausalLM(LlamaConfig(**{
        **TINY, "layer_types": None, "sliding_window": None,
        "num_hidden_layers": 2}))
    plain.eval()
    eng = _engine(plain)
    pool = eng.pool
    assert pool.layer_kinds == ["paged", "paged"] and not pool.windowed
    assert pool.ring_len is None and pool.ring_pages is None
    assert all(k.shape == (3, 2, 256 + 16, 16) for k, _ in pool.slabs)
    assert not any(isinstance(e, WindowKV) for e in pool.slabs)
    assert pool.view("table", "lens") == (       # no ring in the operand
        "table", "lens", pool.block_len, pool.n_blocks, None)
    assert eng.enable_prefix_cache is True
    assert eng.metrics.snapshot()["kv_pool_bytes"] is None
    assert "kv_pool_bytes" not in eng.metrics.render()
    # and generate()'s cache of a windowed model is full-length everywhere
    windowed = LlamaForCausalLM(LlamaConfig(**TINY))
    assert {k.shape[2] for k, _ in windowed.init_cache(1, 100)} == {100}


# ---- the expert share ----

def test_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One expert layer of 8 experts (2 per token, renormalised over the
    chosen), computed whole by the reference and as four shares of 2
    experts by `DroplessMoE(held=...)` with the same router: the shares'
    parts add up to the whole."""
    rng = np.random.default_rng(12)
    hidden, width, experts = 48, 32, 8
    router = rng.normal(0, 0.5, (hidden, experts)).astype(np.float32)
    wg = rng.normal(0, 0.15, (experts, hidden, width)).astype(np.float32)
    wu = rng.normal(0, 0.15, (experts, hidden, width)).astype(np.float32)
    wd = rng.normal(0, 0.15, (experts, width, hidden)).astype(np.float32)
    x = rng.normal(0, 1.0, (3, 7, hidden)).astype(np.float32)
    whole = np.asarray(ref._experts(jnp.asarray(x.reshape(-1, hidden)),
                                    router, wg, wu, wd, 2, True))
    total = np.zeros_like(whole)
    for first in range(0, experts, 2):
        layer = DroplessMoE(hidden, width, experts, 2, norm_topk_prob=True,
                            held=(first, 2))
        layer.router_weight.data = jnp.asarray(router)
        layer.w_gate.data = jnp.asarray(wg[first:first + 2])
        layer.w_up.data = jnp.asarray(wu[first:first + 2])
        layer.w_down.data = jnp.asarray(wd[first:first + 2])
        part = np.asarray(layer(paddle.to_tensor(x)).data)
        share = np.asarray(ref._experts(
            jnp.asarray(x.reshape(-1, hidden)), router, wg[first:first + 2],
            wu[first:first + 2], wd[first:first + 2], 2, True))
        # the reference given the first two experts only reads them as
        # experts 0 and 1: the program's share (first, 2) of those
        if first == 0:
            np.testing.assert_allclose(part.reshape(-1, hidden), share,
                                       atol=1e-5)
        total += part.reshape(-1, hidden)
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=0)
    assert np.abs(whole).max() > 0.05


def test_llama_config_carries_the_share_to_every_expert_layer(tiny):
    paddle.seed(0)
    cfg = LlamaConfig(**{**TINY, "experts_held": (2, 4)})
    model = LlamaForCausalLM(cfg)
    model.eval()
    for layer in model.llama.layers:
        assert layer.mlp.held == (2, 4)
        assert tuple(layer.mlp.w_gate.shape) == (4, 48, 32)
        assert tuple(layer.mlp.router_weight.shape) == (48, 8)
    # the model with a share equals the reference given the same share
    _seed_weights(model)
    ids = np.stack(_prompts([60], seed=13))
    got = np.asarray(model(paddle.to_tensor(ids)).data)
    w = _weights(model)
    # the reference holds the FIRST experts of the router: move the held
    # ones' router columns to the front
    for i in range(cfg.num_hidden_layers):
        key = f"llama.layers.{i}.mlp.router_weight"
        r = np.asarray(w[key])
        w[key] = jnp.asarray(np.concatenate(
            [r[:, 2:6], r[:, :2], r[:, 6:]], axis=1))
    want = np.asarray(ref.logits(w, jnp.asarray(ids), REF))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    eng = _engine(model)
    h = eng.submit(ids[0], max_new_tokens=6)
    _drain(eng)
    assert np.array_equal(
        np.asarray(h.result(timeout=10)),
        np.asarray(generate(model, ids, max_new_tokens=6).data)[0, 60:])
    assert eng.moe_expert_tokens().shape == (8, 4)
