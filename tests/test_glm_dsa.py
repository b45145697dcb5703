"""GLM-5.2's block (`models/deepseek.py` with `indexer_types`) on the
serving path: the indexer, the exact selection shared by the layers behind
it, index-key pages in the pool, interleaved RoPE, the selection bias over an
expert share, and sessions that come back onto their cached prefixes. The
uncached forward, the cached forward and `LLMEngine` (unpacked and packed
step) against the benchmark's plain reference
(`benchmark/reference/glm_moe_dsa.py`, logits).
CPU, float32, tiny widths: hidden 48, 4 heads of 24 + 8 (q, k) / 32 (v),
ranks 24 / 32, indexers of 4 x 16 that keep 16 keys, layers [full, shared,
shared, full, shared], one dense layer and four expert layers of 16 experts
of width 32, 4 per token.

Initial values: matrices N(0, 0.15), the router N(0, 0.3), the selection
bias and the index key's LayerNorm bias N(0, 0.3), so that attention, the
selection and the gates are far from uniform and a mechanism dropped from
the reference moves the logits by 0.05 to 3 against a tolerance of 1e-4
(`test_each_mechanism_carries_the_logits`). The tolerance: both sides run
float32; the program's cached path computes the absorbed form over gathered
or masked latent pages where the reference expands keys and values under a
0/1 matrix, so the two differ by float32 rounding of differently ordered
sums over five layers, 1e-6 to 1e-5 on logits of size 1 to 4; a selection
never flips at that size of error because no two index scores of a row are
that close (the nearest pair in these tests is 1e-3 apart).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models import deepseek
from paddle_tpu.models.deepseek import (DeepseekConfig, DeepseekForCausalLM,
                                        DeepseekMoE, MLAttention,
                                        _deinterleave)
from paddle_tpu.models.generation import (IndexedLatentKV, LatentKV,
                                          generate, make_decoder_fns)
from paddle_tpu.models.llama import _apply_rope, _rope_cos_sin
from paddle_tpu.profiler import SPAN_SERVE_DISPATCH

from benchmark.reference import glm_moe_dsa as ref

VOCAB = 128
KINDS = ["full", "shared", "shared", "full", "shared"]
TINY = dict(vocab_size=VOCAB, hidden_size=48, intermediate_size=64,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
            n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
            first_k_dense_replace=1, n_group=1, topk_group=1,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            scoring_func="sigmoid", select_bias=True,
            max_position_embeddings=512, rms_norm_eps=1e-5, rope_theta=8e6,
            rope_interleave=True, indexer_types=KINDS, index_n_heads=4,
            index_head_dim=16, index_topk=16)
# the same sizes as the reference reads them (the benchmark's keys)
REF = {k: v for k, v in TINY.items()
       if k not in ("vocab_size", "max_position_embeddings", "rope_theta",
                    "select_bias")}
REF["rope_parameters"] = {"rope_theta": 8e6, "rope_type": "default"}
TOL = 1e-4


def _seed_weights(model, seed=5):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith(("select_bias", "k_norm.bias")):
            std = 0.3
        elif len(p.shape) < 2:
            continue                                  # norm scales stay 1
        else:
            std = 0.3 if "router" in name else 0.15
        p.data = jnp.asarray(rng.normal(0.0, std, p.shape), jnp.float32)
    return model


def _model(**overrides):
    paddle.seed(0)
    model = _seed_weights(DeepseekForCausalLM(
        DeepseekConfig(**{**TINY, **overrides})))
    model.eval()
    return model


@pytest.fixture(scope="module")
def tiny():
    return _model()


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


def _reference_logprobs(model, prompt, out, config=REF):
    full = np.concatenate([prompt, out])[None]
    lp = jax.nn.log_softmax(ref.logits(_weights(model), jnp.asarray(full),
                                       config)[0], -1)
    return np.asarray([lp[len(prompt) - 1 + i, t] for i, t in enumerate(out)])


# ---- the model against the plain reference ----

def test_shapes_are_the_familys(tiny):
    layers = tiny.model.layers
    assert [layer.self_attn.kind for layer in layers] == KINDS
    with_indexer = [layer.self_attn.indexer is not None for layer in layers]
    assert with_indexer == [k == "full" for k in KINDS]
    idx = layers[0].self_attn.indexer
    assert tuple(idx.wq_b.weight.shape) == (24, 4 * 16)
    assert tuple(idx.wk.weight.shape) == (48, 16)
    assert tuple(idx.weights_proj.weight.shape) == (48, 4)
    assert tuple(idx.k_norm.weight.shape) == tuple(idx.k_norm.bias.shape) \
        == (16,)
    # a shared layer has no indexer weights at all
    assert not [n for n, _ in layers[1].named_parameters() if "indexer" in n]
    assert tuple(layers[1].mlp.experts.select_bias.shape) == (16,)
    kinds = [type(layer.mlp).__name__ for layer in layers]
    assert kinds == ["LlamaMLP"] + ["DeepseekMoE"] * 4
    # the leading-dense count and the indexer pattern are independent lists
    other = DeepseekConfig(**{**TINY, "first_k_dense_replace": 3})
    assert other.indexer_types == KINDS


@pytest.mark.parametrize("kinds", [["shared"] * 5, ["full"] * 4,
                                   ["full", "sparse", "full", "full", "full"]])
def test_indexer_types_are_checked(kinds):
    with pytest.raises(ValueError, match="indexer_types"):
        DeepseekConfig(**{**TINY, "indexer_types": kinds})


def test_interleaved_rope_is_the_pair_rotation():
    """De-interleaving once and rotating halves gives every q . k the
    interleaved rotation gives."""
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 12, 8)), jnp.float32)
            for _ in range(2))
    cos, sin = _rope_cos_sin(12, 8, {"rope_theta": 100.0,
                                     "rope_type": "default"})
    ours = [_apply_rope(_deinterleave(x), cos, sin) for x in (q, k)]
    theirs = [ref._rope(x[0, 0], 100.0) for x in (q, k)]
    np.testing.assert_allclose(
        np.einsum("td,sd->ts", *(np.asarray(x[0, 0]) for x in ours)),
        np.einsum("td,sd->ts", *(np.asarray(x) for x in theirs)), atol=1e-5)


def test_uncached_forward_equals_the_reference(tiny):
    ids = np.stack(_prompts([70, 70]))
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))
    got = tiny(paddle.to_tensor(ids)).numpy()
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("widths", [[70], [16] * 5, [9, 3] + [1] * 6,
                                    [40] + [1] * 3],
                         ids=["whole", "chunks", "under-topk", "decode"])
def test_cached_forward_equals_the_reference(tiny, widths):
    """Prefill then decode through the cache at contexts below and above
    `index_topk` (16): the gathered form (width 1) and the walk under the
    columns' masks (wider) against the reference's full forward."""
    ids = np.stack(_prompts([sum(widths)] * 2, seed=3))
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))
    params, prefill, _ = make_decoder_fns(tiny)
    caches, at = tiny.init_cache(2, sum(widths) + 3), 0
    for w in widths:
        lg, caches = prefill(params, jnp.asarray(ids[:, at:at + w]), caches,
                             jnp.int32(at))
        np.testing.assert_allclose(np.asarray(lg), want[:, at:at + w],
                                   atol=TOL)
        at += w


def test_the_cache_keeps_an_index_key_in_the_full_layers(tiny):
    caches = tiny.init_cache(2, 40)
    assert [type(c) for c in caches] == [
        IndexedLatentKV, LatentKV, LatentKV, IndexedLatentKV, LatentKV]
    assert caches[0].k_index.shape == (2, 1, 40, 16)
    assert caches[0].c.shape == (2, 1, 40, 32)
    assert caches[0].r.shape == (2, 1, 40, 128)
    params, prefill, _ = make_decoder_fns(tiny)
    ids = np.stack(_prompts([20, 20]))
    _, new = prefill(params, jnp.asarray(ids), caches, jnp.int32(0))
    assert [len(c) for c in new] == [3, 2, 2, 3, 2]
    assert np.asarray(new[3][2][:, :, :20]).any()
    assert not np.asarray(new[3][2][:, :, 20:]).any()


def test_a_shared_layer_uses_the_full_layers_selection_and_no_other(
        tiny, monkeypatch):
    """Inside one forward pass a "full" layer ignores what it is handed
    and makes a selection; every "shared" layer behind it is handed that
    very object and hands it on."""
    seen = []
    plain = MLAttention.forward

    def spy(self, hidden, **kw):
        out = plain(self, hidden, **kw)
        seen.append((self.kind, kw.get("sel"), out[-1]))
        return out

    monkeypatch.setattr(MLAttention, "forward", spy)
    params, prefill, _ = make_decoder_fns(tiny)
    ids = np.stack(_prompts([24, 24]))
    prefill(params, jnp.asarray(ids), tiny.init_cache(2, 32), jnp.int32(0))
    assert [k for k, _, _ in seen] == KINDS
    given, used = [s for _, s, _ in seen], [s for _, _, s in seen]
    assert given[0] is None
    assert used[1] is used[0] and used[2] is used[0] and given[3] is used[0]
    assert used[3] is not used[0] and used[4] is used[3]
    # and the second selection differs from the first
    assert not np.array_equal(np.asarray(used[0].mask.data),
                              np.asarray(used[3].mask.data))
    # what is selected: at most index_topk keys a query, all of them
    # while fewer are visible, never a key behind the query
    mask = np.asarray(used[0].mask.data)[0]
    assert mask[:, :24].sum(-1).tolist() == [min(t + 1, 16)
                                             for t in range(24)]
    assert not np.triu(mask[:, :24], 1).any()


FAULTS = {
    "no selection": {"index_topk": 1 << 20},
    "no sharing": {"index_share": False},
    "no relu": {"index_relu": False},
    "no head weights": {"index_head_weights": False},
    "no selection bias": None,
    "second indexer unused": {"indexer_types": ["full"] + ["shared"] * 4},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_mechanism_carries_the_logits(tiny, fault):
    """Taken out of the reference alone, each mechanism moves the logits
    far past the tolerance: the comparison can see it."""
    ids = np.stack(_prompts([70, 70]))
    got = tiny(paddle.to_tensor(ids)).numpy()
    weights = _weights(tiny)
    if FAULTS[fault] is None:
        weights = {k: v * 0 if k.endswith("select_bias") else v
                   for k, v in weights.items()}
    config = {**REF, **(FAULTS[fault] or {})}
    faulty = np.asarray(ref.logits(weights, jnp.asarray(ids), config))
    assert np.abs(got - faulty).max() > 5e-2


# ---- through the engine ----

@pytest.mark.parametrize("num_slots", [3, 40], ids=["unpacked", "packed"])
def test_engine_logprobs_equal_the_references_full_forward(tiny, num_slots):
    """Prompts below `index_topk`, across it and far above it, prefilled in
    chunks and decoded through index-key and latent pages, in the step that
    keeps the slots' layout and in the one that packs its live tokens."""
    eng = _engine(tiny, num_slots=num_slots)
    assert eng.pool.layer_kinds == ["indexed", "latent", "latent",
                                    "indexed", "latent"]
    assert eng.enable_prefix_cache          # nothing is switched off
    assert (eng.step_tokens < num_slots * 16) == (num_slots == 40)
    prompts = _prompts([9, 43, 130])
    handles = [eng.submit(p, max_new_tokens=12, logprobs=True)
               for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        out = np.asarray(h.result(timeout=0))
        np.testing.assert_allclose(np.asarray(h.logprobs_so_far()),
                                   _reference_logprobs(tiny, p, out),
                                   atol=TOL)
        # one-shot generation takes the same two forms of the attention
        want = np.asarray(generate(tiny, p[None], max_new_tokens=12).data)
        assert out.tolist() == want[0, len(p):].tolist()


def test_a_turn_on_a_prefix_hit_gives_the_cold_prompts_logits(tiny):
    """A session's second turn is admitted onto its first turn's cached
    pages (index-key pages with their latent pages), into the same row,
    evicting nothing; its log-probabilities are those of the same prompt
    served cold by a fresh engine, and the reference's."""
    history, new1, new2, other = _prompts([70, 9, 11, 50], seed=8)
    eng = _engine(tiny, num_slots=2)
    first = eng.submit(np.concatenate([history, new1]), max_new_tokens=6)
    eng.submit(other, max_new_tokens=2)      # another session's row
    _drain(eng)
    turn = np.concatenate([history, new1, np.asarray(first.result(timeout=0)),
                           new2])
    blocks, row = eng.prefix_cache.probe_row("default", turn, len(turn) - 1)
    assert blocks == 9 and eng.pool._cached_at[1 - row].any()
    before = eng.prefill_tokens
    warm = eng.submit(turn, max_new_tokens=8, logprobs=True)
    eng.pump()
    assert eng.pool.active[row] and eng.pool.active.sum() == 1
    _drain(eng)
    snap = eng.metrics.snapshot()
    # the first turn's prompt, 79 tokens: nine whole pages are attached;
    # its tail of 7, which sat where the turn writes, went to clear the row
    assert snap["prefix_hit_tokens"] == 72
    assert eng.prefill_tokens - before == len(turn) - 72
    assert eng.prefix_cache.stats["evictions"] == 1
    cold_eng = _engine(tiny, num_slots=2)
    cold = cold_eng.submit(turn, max_new_tokens=8, logprobs=True)
    _drain(cold_eng)
    assert warm.result(timeout=0).tolist() == cold.result(timeout=0).tolist()
    np.testing.assert_allclose(warm.logprobs_so_far(),
                               cold.logprobs_so_far(), atol=1e-6)
    np.testing.assert_allclose(
        warm.logprobs_so_far(),
        _reference_logprobs(tiny, turn, np.asarray(warm.result(timeout=0))),
        atol=TOL)
    eng.pool.check_balance()


def test_sessions_come_back_into_their_rows(tiny):
    """Two sessions on two slots, three turns each: every turn after the
    first is a prefix hit, a session that starts over from its history
    drops its own stale turns alone, and every stream is the reference's."""
    eng = _engine(tiny, num_slots=2)
    rng = np.random.default_rng(11)
    history = _prompts([60, 45], seed=12)
    context, rows = list(history), [None, None]
    for turn in range(3):
        handles = []
        for s in range(2):
            if turn == 2 and s == 0:
                context[s] = history[s]               # starts over
            prompt = np.concatenate(
                [context[s], rng.integers(0, VOCAB, (7,)).astype(np.int32)])
            handles.append((prompt, eng.submit(prompt, max_new_tokens=5,
                                               logprobs=True)))
        eng.pump()
        for s, (_, h) in enumerate(handles):
            slot = next(i for i, r in eng._active.items() if r.handle is h)
            if turn == 0:
                rows[s] = slot
            else:
                assert slot == rows[s]
        _drain(eng)
        for s, (prompt, h) in enumerate(handles):
            out = np.asarray(h.result(timeout=0))
            np.testing.assert_allclose(
                h.logprobs_so_far(), _reference_logprobs(tiny, prompt, out),
                atol=TOL)
            context[s] = np.concatenate([prompt, out])
    snap = eng.metrics.snapshot()
    assert snap["prefix_hits"] == 4 and snap["prefix_misses"] == 2
    # both histories are still cached whole
    assert eng.prefix_cache.probe("default", history[0]) >= 56
    assert eng.prefix_cache.probe("default", history[1]) >= 40
    eng.pool.check_balance()


def test_sparse_counters_gauge_and_span(tiny):
    eng = _engine(tiny, num_slots=2)
    h = eng.submit(_prompts([40])[0], max_new_tokens=3)
    profiler.start_profiler()
    try:
        _drain(eng)
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    assert len(h.result(timeout=0)) == 3
    snap = eng.metrics.snapshot()
    steps = snap["unified_steps"]
    assert snap["index_layers_full"] == 2 * steps
    assert snap["index_layers_shared"] == 3 * steps
    # live query positions 0..41: each sees p + 1 keys, attends to <= 16
    assert snap["sparse_keys_resident"] == sum(range(1, 43))
    assert snap["sparse_keys_selected"] == sum(min(p, 16)
                                               for p in range(1, 43))
    assert eng.pool.kv_bytes()["index"] == 2 * 2 * (256 + 16) * 16 * 4
    text = eng.metrics.render()
    assert 'pdtpu_llm_kv_pool_bytes{kind="index"}' in text
    assert "pdtpu_llm_sparse_keys_selected_total" in text
    assert "pdtpu_llm_index_layers_full_total" in text
    assert spans and all(s["sparse_rows"] == 1 for s in spans)
    # a model without an indexer counts and renders none of it
    assert "sparse_keys" not in serving.LLMMetrics().render()


def test_the_host_tier_is_refused_by_name(tiny):
    with pytest.raises(ValueError, match="index-key pages"):
        _engine(tiny, host_kv_bytes=1 << 20)


# ---- the expert share ----

def test_the_shares_of_all_32_chips_add_up_to_the_uncut_layer():
    """64 experts held as 32 shares of 2, the selection bias on: the routed
    parts of all the shares plus the shared expert once equal the uncut
    layer, in the program and in the reference."""
    paddle.seed(0)
    wide = {**TINY, "n_routed_experts": 64}
    whole = DeepseekMoE(DeepseekConfig(**wide))
    _seed_weights(whole, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(0, 1, (2, 9, 48)),
                    jnp.float32)
    want = whole(paddle.to_tensor(x)).numpy()
    shared = whole.shared_experts(paddle.to_tensor(x)).numpy()
    total = np.zeros_like(want)
    for first in range(0, 64, 2):
        share = DeepseekMoE(DeepseekConfig(**{**wide,
                                              "experts_held": (first, 2)}))
        assert tuple(share.experts.w_gate.shape) == (2, 48, 32)
        assert tuple(share.experts.router_weight.shape) == (48, 64)
        share.experts.router_weight.data = whole.experts.router_weight.data
        share.experts.select_bias.data = whole.experts.select_bias.data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share.experts, name).data = getattr(
                whole.experts, name).data[first:first + 2]
        total += share.experts(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    # the bias chooses and does not weigh: without it the layer differs
    whole.experts.select_bias.data = whole.experts.select_bias.data * 0
    assert np.abs(whole(paddle.to_tensor(x)).numpy() - want).max() > 1e-2
    # the reference's uncut layer is the program's
    _seed_weights(whole, seed=11)
    leaves = {"mlp.experts." + k: p.data
              for k, p in whole.experts.named_parameters()}
    leaves.update({"mlp.shared_experts." + k: p.data
                   for k, p in whole.shared_experts.named_parameters()})
    with jax.default_matmul_precision("highest"):
        uncut = ref._moe(x.reshape(-1, 48), leaves.__getitem__, REF)
    np.testing.assert_allclose(np.asarray(uncut), want.reshape(-1, 48),
                               atol=1e-5)


def test_a_model_without_an_indexer_is_the_program_it_was():
    """`indexer_types` None: no indexer, two slabs a layer, the layer's
    forward hands back no selection (A.X-K1's path)."""
    model = _model(indexer_types=None, rope_interleave=False)
    assert all(layer.self_attn.kind is None for layer in model.model.layers)
    assert all(type(c) is LatentKV for c in model.init_cache(1, 8))
    assert not [n for n, _ in model.named_parameters() if "indexer" in n]
    assert deepseek.Selection._fields == ("mask", "idx", "count")
