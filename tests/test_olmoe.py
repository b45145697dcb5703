"""A sparse-expert Llama (OLMoE's block) on the serving path: the dropless
expert layer against a per-token loop, the model against the benchmark's
plain reference, the cached path, and the `LLMEngine` invariants a router
could break (a request's stream must not depend on its batchmates; padding
must reach no expert). CPU, float32, a tiny OLMoE: hidden 64, 4 heads, 8
experts (2 or all 8 per token) of width 32, 2 layers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import generate, make_decoder_fns
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nn.layer import moe
from paddle_tpu.ops.grouped_matmul import grouped_matmul

from benchmark.reference import llama as ref_llama, olmoe as ref_olmoe

VOCAB, HIDDEN, WIDTH, EXPERTS, LAYERS = 128, 64, 32, 8, 2
TINY = dict(vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=WIDTH,
            num_hidden_layers=LAYERS, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=128)
TOP_KS = [2, 8]


def _model(top_k, seed=0, **over):
    paddle.seed(seed)
    cfg = LlamaConfig(**{**TINY, "num_experts": EXPERTS,
                         "num_experts_per_tok": top_k, "qk_norm": True,
                         **over})
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module", params=TOP_KS)
def olmoe_tiny(request):
    return _model(request.param)


def _ref_config(model):
    cfg = model.config
    return {**dataclasses.asdict(cfg), "head_dim": cfg.head_dim}


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _ids(shape, seed=1):
    return np.random.default_rng(seed).integers(
        1, VOCAB, shape).astype(np.int32)


# ---- the dropless core ------------------------------------------------------

def _layer_weights(rng, scale=0.3):
    return (jnp.asarray(rng.normal(size=(HIDDEN, EXPERTS)), jnp.float32),
            *(jnp.asarray(scale * rng.normal(size=s), jnp.float32)
              for s in ((EXPERTS, HIDDEN, WIDTH), (EXPERTS, HIDDEN, WIDTH),
                        (EXPERTS, WIDTH, HIDDEN))))


def _per_token_loop(x, router, wg, wu, wd, top_k, norm, live):
    """What the layer means, one position and one expert at a time."""
    x, router, wg, wu, wd = (np.asarray(a, np.float64)
                             for a in (x, router, wg, wu, wd))
    out = np.zeros_like(x)
    counts = np.zeros(EXPERTS, np.int64)
    for t in range(x.shape[0]):
        if not live[t]:
            continue
        z = x[t] @ router
        p = np.exp(z - z.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:top_k]
        w = p[chosen] / (p[chosen].sum() if norm else 1.0)
        for e, w_e in zip(chosen, w):
            g = x[t] @ wg[e]
            out[t] += w_e * ((g / (1 + np.exp(-g)) * (x[t] @ wu[e]))
                             @ wd[e])
            counts[e] += 1
    return out, counts


@pytest.mark.parametrize("top_k", TOP_KS)
@pytest.mark.parametrize("norm", [False, True])
@pytest.mark.parametrize("case", ["all_live", "ragged", "one_token",
                                  "nothing_live"])
def test_dropless_core_equals_a_per_token_loop(top_k, norm, case):
    """<= 1e-5 in float32 (the sums are over 64 and 32 terms of O(1)).
    Uneven groups come with random routing; feature 0 is constant and the
    router sends it against expert 3, so that (at top-2) one expert gets
    no token at all."""
    rng = np.random.default_rng(7)
    router, wg, wu, wd = _layer_weights(rng, scale=0.3)
    router = (0.2 * router).at[0, 3].set(-30.0)
    T = 24
    x = jnp.asarray(rng.normal(size=(T, HIDDEN)), jnp.float32).at[:, 0].set(
        1.0)
    live = {"all_live": np.ones(T, bool),
            "ragged": rng.random(T) < 0.6,
            "one_token": np.arange(T) == 5,
            "nothing_live": np.zeros(T, bool)}[case]
    out, counts = moe.moe_dropless_forward(
        x, router, wg, wu, wd, top_k, norm,
        None if case == "all_live" else jnp.asarray(live))
    want, want_counts = _per_token_loop(x, router, wg, wu, wd, top_k, norm,
                                        live)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert int(counts.sum()) == int(live.sum()) * top_k    # nothing dropped
    if top_k < EXPERTS:
        assert int(counts[3]) == 0 and len(set(want_counts.tolist())) > 1 \
            or not live.any()
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5, rtol=0)
    assert not np.asarray(out)[~live].any()


@pytest.mark.parametrize("top_k", TOP_KS)
def test_garbage_in_pad_positions_changes_no_live_row_and_no_count(top_k):
    rng = np.random.default_rng(11)
    router, wg, wu, wd = _layer_weights(rng)
    live = np.zeros((4, 6), bool)
    live[0, :1] = live[1, :6] = live[3, :3] = True     # row 2: a free slot
    x = rng.normal(size=(4, 6, HIDDEN)).astype(np.float32)
    other = x.copy()
    other[~live] = 1e4 * rng.normal(size=other[~live].shape)
    outs = [moe.moe_dropless_forward(jnp.asarray(a), router, wg, wu, wd,
                                     top_k, False, jnp.asarray(live))
            for a in (x, other)]
    np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                  np.asarray(outs[1][1]))
    np.testing.assert_array_equal(np.asarray(outs[0][0])[live],
                                  np.asarray(outs[1][0])[live])
    assert int(outs[0][1].sum()) == int(live.sum()) * top_k


@pytest.mark.parametrize("sizes", [[64] * 8, [100, 0, 3, 200, 0, 50, 7, 1],
                                   [0, 0, 0, 0, 0, 0, 0, 5], [0] * 8])
def test_mosaic_grouped_matmul_interpreted_equals_ragged_dot(sizes):
    """The TPU kernel, interpreted, against the CPU's parity path, on
    groups that straddle row tiles, empty groups and rows of no group."""
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(8, 256, 384)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    n = int(sizes.sum())
    got = grouped_matmul(lhs, rhs, sizes, impl="pallas")
    want = grouped_matmul(lhs, rhs, sizes, impl="ragged_dot")
    assert got.shape == want.shape == (512, 384)
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-4, rtol=0)


# ---- the model --------------------------------------------------------------

# float32 on both sides, but not the same sums: the program computes only
# the chosen experts, as grouped matmuls over sorted rows, attention through
# the flash / paged code; the reference every expert for every position and
# a plain softmax. Logits are O(1); measured largest difference 2e-6 (top-2)
# to 5e-6 (top-8). 1e-4 leaves room for another platform's sums and is 30
# times under what bf16 arithmetic gives (3e-3 and more, the last test).
LOGIT_TOL = 1e-4


def _reference_logits(model, ids):
    return np.asarray(ref_olmoe.logits(_weights(model), jnp.asarray(ids),
                                       _ref_config(model)))


def test_model_logits_equal_the_plain_reference(olmoe_tiny):
    ids = _ids((3, 20))
    got = np.asarray(olmoe_tiny(paddle.to_tensor(ids)).data)
    np.testing.assert_allclose(got, _reference_logits(olmoe_tiny, ids),
                               atol=LOGIT_TOL, rtol=0)


def test_reference_without_experts_or_norms_is_not_this_model(olmoe_tiny):
    """The dense Llama reference on the shared weights differs by O(1): the
    tolerance above is not met by leaving the new mathematics out."""
    ids = _ids((2, 12))
    weights = _weights(olmoe_tiny)
    dense = {k: v for k, v in weights.items() if "mlp." not in k}
    for i in range(LAYERS):
        p = f"llama.layers.{i}.mlp."
        dense[p + "gate_proj.weight"] = weights[p + "w_gate"][0]
        dense[p + "up_proj.weight"] = weights[p + "w_up"][0]
        dense[p + "down_proj.weight"] = weights[p + "w_down"][0]
    other = np.asarray(ref_llama.logits(dense, jnp.asarray(ids),
                                        _ref_config(olmoe_tiny)))
    assert np.abs(other - _reference_logits(olmoe_tiny, ids)).max() > 1e-2


@pytest.mark.parametrize("top_k", TOP_KS)
def test_a_bf16_computed_model_fails_the_reference_tolerance(top_k):
    """Same weights (rounded to bf16 on both sides), the program computing
    in bf16: outside LOGIT_TOL, so the tolerance tells float32 from the
    nearest precision below it."""
    model = _model(top_k, dtype="bfloat16")
    assert {str(p.dtype) for p in model.parameters()} == {"bfloat16"}
    ids = _ids((3, 20))
    got = np.asarray(model(paddle.to_tensor(ids)).data, np.float32)
    assert np.abs(got - _reference_logits(model, ids)).max() > 10 * LOGIT_TOL


def test_constructor_honours_config_dtype_and_defaults_to_float32():
    assert {str(p.dtype) for p in _model(2).parameters()} == {"float32"}
    dense = LlamaForCausalLM(LlamaConfig(**TINY))
    assert {str(p.dtype) for p in dense.parameters()} == {"float32"}
    assert not any(isinstance(s, moe.DroplessMoE) for s in dense.sublayers())
    assert "q_norm" not in dict(dense.named_sublayers())
    bf16 = LlamaForCausalLM(LlamaConfig(**TINY, dtype="bfloat16"))
    assert {str(p.dtype) for p in bf16.parameters()} == {"bfloat16"}


def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        olmoe_tiny):
    ids = _ids((2, 14))
    full = np.asarray(olmoe_tiny(paddle.to_tensor(ids)).data)
    params, prefill, decode_step = make_decoder_fns(olmoe_tiny)
    caches = olmoe_tiny.init_cache(2, 16)
    lg, caches = prefill(params, jnp.asarray(ids[:, :9]), caches,
                         jnp.int32(0))
    steps = [np.asarray(lg)]
    for t in range(9, 14):
        lg, caches = decode_step(params, jnp.asarray(ids[:, t]),
                                 jnp.int32(t), caches)
        steps.append(np.asarray(lg)[:, None])
    np.testing.assert_allclose(np.concatenate(steps, 1), full, atol=2e-5,
                               rtol=0)


def test_training_a_sparse_configuration_is_refused_by_name(olmoe_tiny):
    ids = paddle.to_tensor(_ids((1, 8)))
    with pytest.raises(NotImplementedError, match="load-balancing"):
        olmoe_tiny(ids, labels=ids)


def test_lora_on_expert_projections_is_refused():
    from paddle_tpu.tuning.lora import LoRAConfig, inject_lora
    with pytest.raises(ValueError, match="expert"):
        inject_lora(_model(2), LoRAConfig(rank=2, alpha=4.0))


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_generate_method_passes_eos_and_seed_by_keyword(family):
    """`model.generate(..., eos_token_id, seed)` used to land them in
    `generation.generate()`'s `top_p, eos_token_id` slots."""
    if family == "llama":
        model = _model(2)
    else:
        from paddle_tpu.models.gpt import GPTForCausalLM
        paddle.seed(0)
        model = GPTForCausalLM.from_preset("gpt2-tiny")
        model.eval()
    ids = _ids((2, 6))
    greedy = np.asarray(model.generate(paddle.to_tensor(ids),
                                       max_new_tokens=6).data)
    eos = int(greedy[0, 7])          # row 0's second new token
    out = np.asarray(model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                                    eos_token_id=eos, seed=5).data)
    want = np.asarray(generate(model, ids, max_new_tokens=6,
                               eos_token_id=eos, seed=5).data)
    np.testing.assert_array_equal(out, want)
    assert (out[0, 7:] == eos).all()            # padded with eos after it
    np.testing.assert_array_equal(out[0, :8], greedy[0, :8])


# ---- through LLMEngine ------------------------------------------------------

def _engine(model, slots=8):
    return serving.LLMEngine(
        model, serving.LLMEngineConfig(num_slots=slots, block_len=8,
                                       n_blocks=8, max_queue_depth=64),
        clock=serving.SimClock())


def _drain(eng):
    while eng.has_work():
        eng.pump()


PROMPT_LENS = [5, 19, 11, 33, 7, 26, 16, 3]      # 19, 33, 26: several chunks


def _prompts():
    rng = np.random.default_rng(21)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in PROMPT_LENS]


def test_a_stream_is_bit_identical_alone_and_among_seven_others(olmoe_tiny):
    """Tokens and log-probabilities of every request, served alone and
    served with the seven others in one batch, are the same bits; and the
    tokens are one-shot `generate()`'s. A router that let a token's
    experts, or the order of its sum, depend on its batchmates would
    break this."""
    prompts = _prompts()
    eng = _engine(olmoe_tiny)
    handles = [eng.submit(p, max_new_tokens=10, logprobs=True)
               for p in prompts]
    _drain(eng)
    solo = _engine(olmoe_tiny)       # one request at a time
    for p, h in zip(prompts, handles):
        hs = solo.submit(p, max_new_tokens=10, logprobs=True)
        _drain(solo)
        assert hs.tokens_so_far() == h.tokens_so_far()
        assert hs.logprobs_so_far() == h.logprobs_so_far()
        want = np.asarray(generate(olmoe_tiny, p[None], max_new_tokens=10
                                   ).data)[0, len(p):]
        np.testing.assert_array_equal(np.asarray(h.result()), want)


def test_engine_counts_every_live_assignment_and_never_recompiles(
        olmoe_tiny):
    """Sum over experts of the device's totals = live tokens x top-k, in
    every layer: padding reached no expert and nothing was dropped. One
    executable, no compilation after the first step."""
    from paddle_tpu.obs.goodput import RecompileSentinel
    from paddle_tpu import profiler
    from paddle_tpu.profiler import SPAN_SERVE_DISPATCH
    top_k = olmoe_tiny.config.num_experts_per_tok
    prompts = _prompts()
    eng = _engine(olmoe_tiny, slots=4)
    before = sum(moe.EXPERT_TOKENS.values())
    warm = _ids((9,), seed=99)     # shares no prefix with the others
    eng.submit(warm, max_new_tokens=3)
    _drain(eng)                                    # warm-up: compiles
    sentinel = RecompileSentinel().install()
    sentinel.mark_warm()
    profiler.start_profiler()       # the in-memory sink only
    try:
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        _drain(eng)
    finally:
        profiler._SINK.enabled = False
        sentinel.uninstall()
    assert sentinel.recompiles == 0
    assert eng._step()._cache_size() == 1
    assert all(len(h.result()) == 6 for h in handles)
    # live tokens: every prompt token prefilled + one decode position per
    # emitted token but a request's last
    live = len(warm) + 2 + sum(len(p) + 5 for p in prompts)
    totals = eng.moe_expert_tokens()
    assert totals.shape == (LAYERS, EXPERTS)
    np.testing.assert_array_equal(totals.sum(1), [live * top_k] * LAYERS)
    snap = eng.metrics.snapshot()
    assert snap["moe_assignments"] == live * top_k * LAYERS == totals.sum()
    spans = [e for e in profiler.get_events()
             if e["name"] == SPAN_SERVE_DISPATCH]
    assert sum(e["args"]["live_tokens"] for e in spans) \
        == live - len(warm) - 2
    text = eng.metrics.render()
    assert f"pdtpu_llm_moe_assignments_total {totals.sum()}" in text
    assert (f'pdtpu_llm_moe_expert_tokens_total{{layer="1",expert="0"}} '
            f"{totals[1, 0]}") in text
    # the process-wide table holds what this engine added, once
    eng.stop()
    assert sum(moe.EXPERT_TOKENS.values()) - before == totals.sum()


def _lowered_step(model):
    eng = _engine(model, slots=2)
    eng.submit(_prompts()[0], max_new_tokens=2)
    with eng._cond:
        eng._admit()
        toks, pos, adv, ctr, *_ = eng._build_rows_locked({})
        args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(adv), eng.pool.device_block_table(),
                eng.pool.slabs) + eng._sampling_args_locked(ctr) \
            + eng._feedback_args() + eng._tail_args_locked()
    lowered = eng._step().lower(*args)
    return eng, args, lowered


def test_a_dense_configuration_lowers_to_the_step_it_always_had():
    """New fields at their defaults: the dense step takes the operands and
    gives the results it had (no totals), and holds no operation of the
    sparse path: no top-k (the router's), no sort but the sampler's one
    (the dispatch sorts once a layer), no `[L, E]` totals."""
    paddle.seed(0)
    dense = LlamaForCausalLM(LlamaConfig(**TINY))
    dense.eval()
    eng, args, lowered = _lowered_step(dense)
    assert eng.moe_expert_tokens() is None and eng._tail_args_locked() == ()
    assert "moe_assignments_total" not in eng.metrics.render()
    n_params = len(jax.tree_util.tree_leaves(eng.params))
    n_slabs = len(jax.tree_util.tree_leaves(eng.pool.slabs))
    assert len(jax.tree_util.tree_leaves(args)) \
        == n_params + 4 + n_slabs + 9 + 2   # rows, table; the pool, once;
    #                                         sampling; token feedback
    out = jax.tree_util.tree_leaves(lowered.out_info)
    assert len(out) == 3 + n_slabs                 # sel, lp, state, slabs
    text = lowered.as_text()
    assert "top_k" not in text
    assert text.count("stablehlo.sort") == 1

    sparse_eng, sparse_args, sparse = _lowered_step(_model(2))
    assert len(jax.tree_util.tree_leaves(sparse_args)) \
        == len(jax.tree_util.tree_leaves(sparse_eng.params)) \
        + 4 + n_slabs + 9 + 2 + 1
    outs = jax.tree_util.tree_leaves(sparse.out_info)
    assert len(outs) == 3 + n_slabs + 1
    assert outs[-1].shape == (LAYERS, EXPERTS)
    text = sparse.as_text()
    assert text.count("chlo.top_k") == LAYERS
    assert text.count("stablehlo.sort") > 1
