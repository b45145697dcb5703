"""A.X-K1's block (the DeepSeek-V3 family's, `models/deepseek.py`) on the
serving path: multi-head latent attention in its two forms, latent pages in
the pool, the `paged_latent` walk, YaRN by `mscale`, a dense layer before
sparse ones, the group-limited sigmoid router beside a shared expert over
an expert share, and `LazyGuard`. The uncached forward, the cached forward
and `LLMEngine` against the benchmark's plain reference
(`benchmark/reference/axk1.py`, logits) and against `generate()` (bits).
CPU, float32, tiny widths: hidden 48, 4 heads of 16 + 8 (q, k) / 16 (v),
ranks 24 / 32, one dense layer and two expert layers, 16 experts of width 32
in 4 groups of which 2 are eligible, 4 per token, YaRN factor 8 over 32
original positions (prompts run past them).

Initial values: matrices N(0, 0.15), the router N(0, 0.3), so that attention
and the gates are far from uniform and a mechanism dropped from the
reference moves the logits by 0.1 to 3 against a tolerance of 1e-4
(`test_each_mechanism_carries_the_logits`). The tolerance: both sides run
float32; the program's cached path computes the absorbed form (q through
W_kvb's key part, then the latent) where the reference expands keys and
values, so the two differ by float32 rounding of differently ordered sums
over three layers, 1e-6 to 1e-5 on logits of size 1 to 4.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, profiler, serving
from paddle_tpu.core.tensor import Unassigned, UnassignedParameterError
from paddle_tpu.models.deepseek import (DeepseekConfig, DeepseekForCausalLM,
                                        DeepseekMoE)
from paddle_tpu.models.generation import LatentKV, generate
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     rope_inv_freq, yarn_mscale)
from paddle_tpu.nn.layer import moe
from paddle_tpu.profiler import SPAN_SERVE_DISPATCH

from benchmark.reference import axk1 as ref

VOCAB = 128
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 1.0}
TINY = dict(vocab_size=VOCAB, hidden_size=48, intermediate_size=64,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
            first_k_dense_replace=1, n_group=4, topk_group=2,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            scoring_func="sigmoid", max_position_embeddings=512,
            rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=YARN)
# the same sizes as the reference reads them (the benchmark's keys)
REF = {k: v for k, v in TINY.items()
       if k not in ("vocab_size", "max_position_embeddings")}
TOL = 1e-4


def _seed_weights(model, seed=5):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if len(p.shape) < 2:
            continue                                  # norm scales stay 1
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


# ---- the model against the plain reference ----

def test_shapes_are_the_familys(tiny):
    attn = tiny.model.layers[0].self_attn
    assert tuple(attn.q_a_proj.weight.shape) == (48, 24)
    assert tuple(attn.q_b_proj.weight.shape) == (24, 4 * 24)
    assert tuple(attn.kv_a_proj_with_mqa.weight.shape) == (48, 32 + 8)
    assert tuple(attn.kv_b_proj.weight.shape) == (32, 4 * 32)
    assert tuple(attn.o_proj.weight.shape) == (4 * 16, 48)
    assert attn.q_a_proj.bias is None
    kinds = [type(layer.mlp).__name__ for layer in tiny.model.layers]
    assert kinds == ["LlamaMLP", "DeepseekMoE", "DeepseekMoE"]
    experts = tiny.model.layers[1].mlp.experts
    assert experts.router == dict(scoring="sigmoid", n_group=4, topk_group=2,
                                  routed_scale=2.5)
    assert experts.select_bias is None
    assert tuple(tiny.model.layers[1].mlp.shared_experts
                 .gate_proj.weight.shape) == (48, 32)
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        DeepseekConfig(num_hidden_layers=2, first_k_dense_replace=3)


def test_uncached_forward_equals_the_reference(tiny):
    ids = np.stack(_prompts([70, 70]))
    got = tiny(paddle.to_tensor(ids)).numpy()
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("widths", [[70], [16] * 5, [64] + [1] * 6],
                         ids=["whole", "chunks of 16", "then one at a time"])
def test_absorbed_form_equals_the_expanded(tiny, widths):
    """The cached forward (absorbed, through the latent cache, at the query
    widths the engine and `generate()` send) against the uncached forward
    (expanded)."""
    ids = np.stack(_prompts([70, 70], seed=3))
    want = tiny(paddle.to_tensor(ids)).numpy()
    caches = [tuple(paddle.to_tensor(a) for a in entry)
              for entry in tiny.init_cache(2, 96)]
    got, start = [], 0
    for width in widths:
        lg, caches = tiny.forward_with_cache(
            paddle.to_tensor(ids[:, start:start + width]), caches,
            jnp.int32(start))
        got.append(lg.numpy())
        start += width
    np.testing.assert_allclose(np.concatenate(got, 1)[:, :70], want,
                               atol=TOL)


def test_the_cache_is_a_latent_and_a_rotary_key(tiny):
    entries = tiny.init_cache(2, 40)
    assert all(isinstance(e, LatentKV) for e in entries) and len(entries) == 3
    # 32 latent columns; the 8-wide rotary key in a whole lane tile
    assert entries[0].c.shape == (2, 1, 40, 32)
    assert entries[0].r.shape == (2, 1, 40, 128)
    ids = np.stack(_prompts([12, 12]))
    caches = [tuple(paddle.to_tensor(a) for a in e) for e in entries]
    _, caches = tiny.forward_with_cache(paddle.to_tensor(ids), caches,
                                        jnp.int32(0))
    c, r = (np.asarray(a.data) for a in caches[0])
    assert np.abs(c[:, :, :12]).min() > 0 and not c[:, :, 12:].any()
    assert np.abs(r[:, :, :12, :8]).min() > 0 and not r[..., 8:].any()


def test_yarn_by_mscale(tiny):
    """The family's convention: cos and sin take `yarn_mscale(f, mscale) /
    yarn_mscale(f, mscale_all_dim)`, the softmax scale the square of the
    latter; the ramp is Hugging Face's."""
    rope = tiny.config.rope
    assert rope["rope_type"] == "yarn" and "type" not in rope
    inv, factor = rope_inv_freq(8, rope)
    want_inv, want_factor, want_scale = ref.rotary(REF)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(want_inv),
                               rtol=1e-6)
    assert factor == want_factor == 1.0
    m = 0.1 * math.log(8) + 1.0
    assert yarn_mscale(8, 1.0) == pytest.approx(m) and yarn_mscale(1) == 1.0
    assert tiny.config.softmax_scale == pytest.approx(24 ** -0.5 * m * m)
    assert tiny.config.softmax_scale == pytest.approx(want_scale)
    # unequal mscales leave cos and sin their ratio
    _, ratio = rope_inv_freq(8, {**rope, "mscale": 0.707,
                                 "mscale_all_dim": 1.0})
    assert ratio == pytest.approx((0.0707 * math.log(8) + 1.0) / m)
    # the published model's numbers
    real = DeepseekConfig(num_attention_heads=64, rope_scaling={
        **YARN, "factor": 32, "original_max_position_embeddings": 4096})
    assert real.softmax_scale == pytest.approx(192 ** -0.5 * 1.34657 ** 2,
                                               rel=1e-5)
    # no scaling: plain RoPE and 1/sqrt(width)
    plain = DeepseekConfig(rope_scaling=None)
    assert plain.rope == {"rope_type": "default", "rope_theta": 10000.0}
    assert plain.softmax_scale == 192 ** -0.5


FAULTS = {
    "without YaRN": dict(rope_scaling=None),
    "softmax for sigmoid": dict(scoring_func="softmax"),
    "without the group limit": dict(n_group=1, topk_group=1),
    "without the shared expert": dict(n_shared_experts=0),
    "without the scaling": dict(routed_scaling_factor=1.0),
    "the dense layer sparse": None,       # needs other weights: below
}


@pytest.mark.parametrize("fault", [f for f in FAULTS if FAULTS[f]])
def test_each_mechanism_carries_the_logits(tiny, fault):
    """A mechanism taken out of the reference moves the logits by far more
    than the tolerance: the comparisons above see each of them."""
    ids = np.stack(_prompts([70], seed=4))
    got = tiny(paddle.to_tensor(ids)).numpy()
    wrong = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids),
                                  {**REF, **FAULTS[fault]}))
    assert np.abs(got - wrong).max() > 100 * TOL


# ---- through the engine ----

def test_engine_logprobs_equal_the_references_full_forward(tiny):
    """Prefill in chunks of 16 and then decoding, through latent pages,
    against one full forward of the reference over prompt + output."""
    eng = _engine(tiny, num_slots=3)
    assert eng.pool.layer_kinds == ["latent"] * 3
    assert eng.enable_prefix_cache is True          # nothing switched off
    prompts = _prompts([9, 43, 130], seed=2)
    handles = [eng.submit(p, max_new_tokens=6, logprobs=True)
               for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        out = np.asarray(h.result(timeout=0))
        ids = np.concatenate([p, out])[None]
        lg = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))[0]
        lp = np.asarray(jax.nn.log_softmax(lg, -1))
        want = [lp[len(p) - 1 + i, t] for i, t in enumerate(out)]
        np.testing.assert_allclose(h.logprobs_so_far(), want, atol=TOL)
        # and the greedy token is the reference's best
        assert [int(np.argmax(lg[len(p) - 1 + i]))
                for i in range(len(out))] == out.tolist()


@pytest.mark.parametrize("block_len", [8, 16])
def test_engine_streams_equal_generate(tiny, block_len):
    eng = _engine(tiny, block_len=block_len)
    prompts = _prompts([5, 16, 17, 60, 129], seed=6)
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        want = np.asarray(generate(tiny, p[None], max_new_tokens=8).data)
        got = np.asarray(h.result(timeout=0))
        if block_len == 8:              # generate()'s own block size: bits
            assert got.tolist() == want[0, len(p):].tolist()
        else:
            assert (got == want[0, len(p):]).mean() >= 0.75
    eng.pool.check_balance()


def test_packed_step_serves_latent_layers(tiny):
    """An engine wider than 512 positions packs its live tokens: the MLA
    layer unpacks the latents and rotary keys for the cache write; its
    queries stay on the packed block through RoPE and the walk."""
    eng = _engine(tiny, num_slots=40, tokens=64)
    assert eng.step_tokens == 512 < 40 * 16
    prompts = _prompts([24] * 40, seed=7)
    handles = [eng.submit(p, max_new_tokens=3) for p in prompts]
    _drain(eng)
    for p, h in list(zip(prompts, handles))[::13]:
        want = np.asarray(generate(tiny, p[None], max_new_tokens=3).data)
        assert np.asarray(h.result(timeout=0)).tolist() \
            == want[0, len(p):].tolist()
    # three latent layers, each over the packed block's 512 positions and
    # not the slots' 640: what attention computes is what the step computes
    snap = eng.metrics.snapshot()
    assert snap["attn_query_positions"] == 3 * snap["step_tokens_computed"] \
        == 3 * 512 * snap["unified_steps"]


def _parents_cached_attention(layer, hidden, caches, pos, paged):
    """PR 49's `MLAttention._forward_cached` for a layer without a
    selection, written out: the step's queries `[N, C, .]` heads first,
    RoPE, the absorption and the value projection over every column of
    every slot round `decode_attention(q_rope=)`."""
    from paddle_tpu.models import deepseek as D
    from paddle_tpu.ops.attention import decode_attention, update_kv_cache
    cfg = layer.config
    H, nope, dr, dv, rank = (
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.kv_lora_rank)
    qa, ca, kr = (t.data for t in layer._down(paddle.to_tensor(hidden))[:3])
    B, T = qa.shape[:2]
    qh = jnp.swapaxes(qa.reshape(B, T, H, nope + dr), 1, 2)
    cos_t, sin_t = D._rope_rows(
        *D._rope_cos_sin(caches[0].shape[2], dr, cfg.rope), pos, T, qa.dtype)
    pad = ((0, 0), (0, 0), (0, 0), (0, caches[1].shape[3] - dr))
    q_rot = jnp.pad(D._apply_rope(qh[..., nope:], cos_t, sin_t), pad)
    k_rot = jnp.pad(D._apply_rope(kr[:, None], cos_t, sin_t), pad)
    caches = update_kv_cache(*caches, ca[:, None], k_rot, pos)
    w = layer.kv_b_proj.weight.data.reshape(rank, H, nope + dv)
    q_lat = jnp.einsum("bhtd,rhd->bhtr", qh[..., :nope], w[..., :nope])
    out = decode_attention(q_lat, caches[0], caches[1], pos,
                           scale=cfg.softmax_scale, paged=paged, q_rope=q_rot)
    out = jnp.einsum("bhtr,rhd->bthd", out, w[..., nope:])
    return layer.o_proj(paddle.to_tensor(
        out.reshape(B, T, H * dv))).data, caches


@pytest.mark.parametrize("adv", [
    [1, 16, 3, 1, 0, 16, 1, 7], [0, 1, 1, 0, 16, 5], [16, 16, 1]],
    ids=["mixed", "free slots", "a full block"])
def test_packed_queries_give_the_unpacked_forms_context(tiny, adv):
    """`MLAttention._forward_cached` under a `TokenPack` (queries left on
    the packed block, one position each) and without one (`[N x C, H, .]`
    as it lies) against the parent's unpacked form: the same context at
    every live token and the same caches."""
    from paddle_tpu.ops.attention import PagedView, token_pack
    layer = tiny.model.layers[1].self_attn
    rng = np.random.default_rng(4)
    adv = np.asarray(adv, np.int32)
    N, C, bl, nb = len(adv), 16, 8, 12
    pos = np.where(adv > 0, rng.integers(0, nb * bl - C, N), 0) \
        .astype(np.int32)
    T = int(adv.sum())
    pack = token_pack(jnp.asarray(adv), jnp.asarray(pos), C, T)
    hidden = jnp.asarray(rng.normal(0, 1, (T, 1, 48)), jnp.float32)
    caches = tuple(jnp.asarray(rng.normal(0, 1, (N, 1, nb * bl + C, w)),
                               jnp.float32) for w in (32, 128))
    # a slot writes its own slab row: its pages are that row's
    table = np.arange(N * nb, dtype=np.int32).reshape(N, nb)
    paged = PagedView(jnp.asarray(table), jnp.asarray(pos + adv), bl, nb)

    def run(x, pack):
        out, new = layer(paddle.to_tensor(x), cache=tuple(
            paddle.to_tensor(a) for a in caches), pos=jnp.asarray(pos),
            paged=paged, pack=pack)
        return out.data, tuple(a.data for a in new)
    want, want_caches = _parents_cached_attention(
        layer, pack.unpack(hidden), caches, jnp.asarray(pos), paged)
    live = np.asarray(pack.live)
    assert live.all() and np.abs(np.asarray(want)).max() > 0.1
    for got, got_caches in (run(hidden, pack),
                            (lambda o, c: (pack.pack(o), c))(
                                *run(pack.unpack(hidden), None))):
        np.testing.assert_allclose(np.asarray(got), pack.pack(want),
                                   atol=1e-5)
        for a, b in zip(got_caches, want_caches):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


@pytest.mark.parametrize("num_slots", [3, 34], ids=["unpacked", "packed"])
def test_the_kernel_serves_the_engines_latent_layers(tiny, monkeypatch,
                                                     num_slots):
    """The engine's step with the `paged_latent` kernel in it (interpreted;
    the CPU's own path is the scan): the model hands it the packed block
    and the rows' starts (a `TokenPack`'s under 34 slots, n x 16 under 3),
    prompts in chunks beside decode rows and free slots, and every
    log-probability is the reference's."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    walk = PA.packed_latent_attention
    monkeypatch.setattr(
        PA, "packed_latent_attention",
        lambda *a, **kw: walk(*a, **{**kw, "impl": "pallas"}))
    pallas_mode.KERNEL_TRACES.clear()
    eng = _engine(tiny, num_slots=num_slots, tokens=64)
    assert (eng.step_tokens < num_slots * 16) == (num_slots == 34)
    prompts = _prompts([9, 43, 20, 33][:min(num_slots, 4)], seed=2)
    handles = [eng.submit(p, max_new_tokens=4, logprobs=True)
               for p in prompts]
    _drain(eng)
    assert pallas_mode.KERNEL_TRACES[(PA.LATENT_KERNEL, "interpret")] >= 3
    assert not pallas_mode.KERNEL_TRACES[(PA.LATENT_KERNEL, "scan")]
    for p, h in zip(prompts, handles):
        out = np.asarray(h.result(timeout=0))
        ids = np.concatenate([p, out])[None]
        lg = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))[0]
        lp = np.asarray(jax.nn.log_softmax(lg, -1))
        np.testing.assert_allclose(
            h.logprobs_so_far(),
            [lp[len(p) - 1 + i, t] for i, t in enumerate(out)], atol=TOL)


def test_prefix_cache_hits_on_latent_pages(tiny):
    eng = _engine(tiny, num_slots=2)
    shared = _prompts([40], seed=8)[0]
    tails = _prompts([5, 7], seed=9)
    first = eng.submit(np.concatenate([shared, tails[0]]), max_new_tokens=4)
    _drain(eng)
    before = eng.prefill_tokens
    second = eng.submit(np.concatenate([shared, tails[1]]), max_new_tokens=4)
    _drain(eng)
    # five whole pages of the shared prefix are attached, not recomputed
    assert eng.prefill_tokens - before == 47 - 40
    assert eng.metrics.snapshot()["prefix_hit_tokens"] == 40
    for h, tail in ((first, tails[0]), (second, tails[1])):
        p = np.concatenate([shared, tail])
        want = np.asarray(generate(tiny, p[None], max_new_tokens=4).data)
        assert np.asarray(h.result(timeout=0)).tolist() \
            == want[0, len(p):].tolist()


def test_latent_counters_gauge_and_span(tiny):
    eng = _engine(tiny)
    by_kind = eng.pool.kv_bytes()
    # 3 slots x 3 layers x (256 + 16) columns x (32 + 128) x float32
    assert by_kind == {"full": 0, "window": 0,
                       "latent": 3 * 3 * 272 * 160 * 4}
    assert serving.metrics.KV_POOL_BYTES == by_kind
    profiler.start_profiler()
    try:
        for p in _prompts([20, 10], seed=8):
            eng.submit(p, max_new_tokens=3)
        _drain(eng)
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    assert spans and max(s["latent_rows"] for s in spans) == 2
    assert all("window_rows" not in s for s in spans)
    snap = eng.metrics.snapshot()
    # 20 tokens: two chunks then two decode steps; 10: one chunk and two
    full = 16 + 20 + 21 + 22 + 10 + 11 + 12
    assert snap["full_kv_tokens"] == full and snap["window_kv_tokens"] == 0
    text = eng.metrics.render()
    assert 'pdtpu_llm_kv_pool_bytes{kind="latent"}' in text
    assert "pdtpu_llm_full_kv_tokens_total" in text
    # the router's totals: two sparse layers, every expert held
    table = eng.moe_expert_tokens()
    assert table.shape == (2, 16)
    live = 30 + 2 * 2          # prompt tokens + decode tokens computed
    assert table.sum(1).tolist() == [live * 4] * 2
    assert moe.ROUTED_TOKENS[0] >= live


def test_full_kv_tokens_counted_on_an_engine_without_a_second_kind():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128))
    model.eval()
    eng = _engine(model, tokens=64)
    eng.submit(_prompts([10])[0], max_new_tokens=3)
    _drain(eng)
    snap = eng.metrics.snapshot()
    assert snap["full_kv_tokens"] == 10 + 11 + 12
    assert snap["kv_pool_bytes"] is None
    text = eng.metrics.render()
    assert "pdtpu_llm_full_kv_tokens_total 33" in text
    assert "kv_pool_bytes" not in text and "window_kv_tokens" not in text


# ---- the shares add up ----

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 groups held as 4 shares of 4 (a share is one whole
    routing group here): the routed parts of all the shares plus the shared
    expert once equal the uncut layer, in the program and in the
    reference."""
    paddle.seed(0)
    cfg = DeepseekConfig(**TINY)
    whole = DeepseekMoE(cfg)
    _seed_weights(whole, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(0, 1, (2, 9, 48)),
                    jnp.float32)
    want = whole(paddle.to_tensor(x)).numpy()
    shared = whole.shared_experts(paddle.to_tensor(x)).numpy()
    total = np.zeros_like(want)
    for first in range(0, 16, 4):
        share = DeepseekMoE(DeepseekConfig(**{**TINY,
                                              "experts_held": (first, 4)}))
        assert tuple(share.experts.w_gate.shape) == (4, 48, 32)
        assert tuple(share.experts.router_weight.shape) == (48, 16)
        share.experts.router_weight.data = whole.experts.router_weight.data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share.experts, name).data = getattr(
                whole.experts, name).data[first:first + 4]
        total += share.experts(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    # the reference's share: the same sum, from its dense gates
    leaves = {"mlp.experts." + k: p.data
              for k, p in whole.experts.named_parameters()}
    leaves.update({"mlp.shared_experts." + k: p.data
                   for k, p in whole.shared_experts.named_parameters()})
    h = x.reshape(-1, 48)
    with jax.default_matmul_precision("highest"):
        uncut = ref._moe(h, leaves.__getitem__, REF)
        parts = sum(
            ref._moe(h, {**leaves, **{
                "mlp.experts." + n: jnp.roll(leaves["mlp.experts." + n],
                                             -first, 0)[:4]
                for n in ("w_gate", "w_up", "w_down")},
                "mlp.experts.router_weight": jnp.roll(
                    leaves["mlp.experts.router_weight"], -first, 1)
            }.__getitem__, {**REF, "n_shared_experts": 0})
            for first in range(0, 16, 4))
    np.testing.assert_allclose(np.asarray(uncut), want.reshape(-1, 48),
                               atol=1e-5)
    # (rolling the router's columns by whole groups keeps the groups: the
    # reference holds "the first 4" of a router whose experts are renamed)
    np.testing.assert_allclose(np.asarray(parts) + shared.reshape(-1, 48),
                               want.reshape(-1, 48), atol=1e-5)


# ---- the router ----

def _plain_route(logits, top_k, norm, scoring, n_group, topk_group, bias,
                 scale):
    """numpy, a position at a time: scores, eligible groups by the sum of
    their two best, the top_k best eligible, gates from the scores."""
    T, E = logits.shape
    gates, chosen = np.zeros((T, top_k)), np.zeros((T, top_k), np.int64)
    for t in range(T):
        z = logits[t].astype(np.float64)
        s = 1 / (1 + np.exp(-z)) if scoring == "sigmoid" \
            else np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        c = s + (0 if bias is None else bias)
        ok = np.ones(E, bool)
        if n_group > 1:
            g = c.reshape(n_group, -1)
            score = np.sort(g, -1)[:, -2:].sum(-1)
            best = np.argsort(-score, kind="stable")[:topk_group]
            ok = np.repeat(np.isin(np.arange(n_group), best), E // n_group)
        order = np.argsort(-np.where(ok, c, -np.inf), kind="stable")[:top_k]
        w = s[order]
        if norm:
            w = w / w.sum()
        gates[t], chosen[t] = w * scale, order
    return gates, chosen


ROUTERS = {
    "softmax top-k (the default)": dict(),
    "softmax renormalised": dict(norm=True),
    "sigmoid": dict(scoring="sigmoid"),
    "sigmoid, 2 of 4 groups, renormalised, scaled":
        dict(scoring="sigmoid", n_group=4, topk_group=2, norm=True,
             scale=2.5),
    "the same with a selection bias":
        dict(scoring="sigmoid", n_group=4, topk_group=2, norm=True,
             scale=2.5, bias=True),
    "a bias without groups": dict(scoring="sigmoid", bias=True),
    "softmax, 1 of 2 groups": dict(n_group=2, topk_group=1, norm=True),
}


@pytest.mark.parametrize("case", sorted(ROUTERS))
def test_router_against_a_plain_top_k(case):
    kw = {"norm": False, "scoring": "softmax", "n_group": 1, "topk_group": 1,
          "bias": None, "scale": 1.0, **ROUTERS[case]}
    rng = np.random.default_rng(21)
    logits = rng.normal(0, 1.5, (64, 16)).astype(np.float32)
    # a bias large enough to change the choice of a good part of the rows
    bias = rng.normal(0, 0.3, (16,)).astype(np.float32) if kw["bias"] \
        else None
    w, idx = moe.route(jnp.asarray(logits), 4, kw["norm"], kw["scoring"],
                       kw["n_group"], kw["topk_group"],
                       None if bias is None else jnp.asarray(bias),
                       kw["scale"])
    want_w, want_idx = _plain_route(logits, 4, kw["norm"], kw["scoring"],
                                    kw["n_group"], kw["topk_group"], bias,
                                    kw["scale"])
    assert np.asarray(idx).tolist() == want_idx.tolist()
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-6)
    if bias is not None:
        # the bias chose otherwise somewhere, and never entered a gate
        _, plain_idx = _plain_route(logits, 4, kw["norm"], kw["scoring"],
                                    kw["n_group"], kw["topk_group"], None,
                                    kw["scale"])
        assert (plain_idx != want_idx).any()
        s = 1 / (1 + np.exp(-logits.astype(np.float64)))
        picked = np.take_along_axis(s, want_idx, 1)
        if kw["norm"]:
            picked = picked / picked.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w), picked * kw["scale"],
                                   rtol=2e-6)


def test_router_defaults_leave_the_softmax_router_bit_identical():
    """OLMoE's, granite's and Mellum's arithmetic: softmax, top-k, the
    optional renormalisation, on the operations they always took."""
    rng = np.random.default_rng(22)
    x = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    rw = jnp.asarray(rng.normal(0, 0.5, (32, 8)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(0, 0.1, (8, 32, 16)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(0, 0.1, (8, 16, 32)), jnp.float32)
    for norm in (False, True):
        logits = x @ rw
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 2)
        if norm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        got_w, got_idx = moe.route(logits, 2, norm)
        assert np.array_equal(np.asarray(got_w), np.asarray(w))
        assert np.array_equal(np.asarray(got_idx), np.asarray(idx))
        # and through the layer's forward: explicit defaults change nothing
        out0, counts0 = moe.moe_dropless_forward(x, rw, wg, wu, wd, 2, norm)
        out1, counts1 = moe.moe_dropless_forward(
            x, rw, wg, wu, wd, 2, norm, None, None, scoring="softmax",
            n_group=1, topk_group=1, select_bias=None, routed_scale=1.0)
        assert np.array_equal(np.asarray(out0), np.asarray(out1))
        assert np.array_equal(np.asarray(counts0), np.asarray(counts1))
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x @ rw, 2, scoring="tanh")
    with pytest.raises(ValueError, match="groups"):
        moe.route(x @ rw, 2, n_group=3, topk_group=1)


def test_selection_bias_is_a_parameter_of_the_layer_when_asked_for():
    paddle.seed(0)
    layer = moe.DroplessMoE(16, 8, 8, 2, True, scoring="sigmoid", n_group=2,
                            topk_group=1, select_bias=True, routed_scale=2.0)
    assert tuple(layer.select_bias.shape) == (8,)
    assert not layer.select_bias.trainable
    assert not np.asarray(layer.select_bias.data).any()
    x = paddle.to_tensor(np.random.default_rng(3).normal(
        0, 1, (6, 16)).astype(np.float32))
    with moe.collect_expert_counts() as sink:
        before = layer(x).numpy()
        layer.select_bias.data = jnp.asarray([5.0] + [0.0] * 7)
        after = layer(x).numpy()
    # every position now routes to expert 0 (and one more of its group)
    assert np.asarray(sink[1])[0] == 6 and np.asarray(sink[1])[4:].sum() == 0
    assert np.abs(before - after).max() > 1e-4
    assert "select_bias" in dict(layer.named_parameters())
    plain = moe.DroplessMoE(16, 8, 8, 2)
    assert plain.select_bias is None
    assert "select_bias" not in dict(plain.named_parameters())


# ---- LazyGuard ----

def test_lazy_guard_allocates_nothing_until_assigned():
    live_before = {id(a) for a in jax.live_arrays()}
    with paddle.LazyGuard():
        model = DeepseekForCausalLM(DeepseekConfig(**TINY))
        inner = nn.Linear(4, 3)
    new = [a for a in jax.live_arrays() if id(a) not in live_before]
    assert new == []                                  # nothing on a device
    named = dict(model.named_parameters())
    assert all(isinstance(p.data, Unassigned) for p in named.values())
    p = named["model.layers.0.self_attn.kv_b_proj.weight"]
    assert p.shape == [32, 128] and p.dtype == np.float32 and p.size == 4096
    assert p.partition_spec is not None               # attributes are kept
    # outside the guard construction is what it was
    assert isinstance(nn.Linear(4, 3).weight.data, jax.Array)
    # using one before it is assigned raises by name
    with pytest.raises(UnassignedParameterError, match=r"Linear\.weight"):
        inner(paddle.ones([2, 4]))
    with pytest.raises(UnassignedParameterError,
                       match="model.embed_tokens.weight"):
        model.functional_state()
    # assigning makes it real
    inner.weight.data, inner.bias.data = jnp.ones((4, 3)), jnp.zeros((3,))
    assert inner(paddle.ones([2, 4])).numpy().tolist() == [[4.0] * 3] * 2
    sound = _model()
    for name, q in sound.named_parameters():
        named[name].data = q.data
    model.eval()
    ids = np.stack(_prompts([20]))
    assert np.array_equal(model(paddle.to_tensor(ids)).numpy(),
                          sound(paddle.to_tensor(ids)).numpy())


def test_lazy_guard_nests_and_decorates():
    with paddle.LazyGuard():
        with paddle.LazyGuard():
            pass
        assert isinstance(nn.Linear(2, 2).weight.data, Unassigned)
    assert isinstance(nn.Linear(2, 2).weight.data, jax.Array)

    @paddle.LazyGuard()
    def build():
        return nn.Linear(2, 2)

    assert isinstance(build().weight.data, Unassigned)
    assert "Unassigned(Linear.weight, [2, 2], float32)" == repr(
        build().weight.data)
