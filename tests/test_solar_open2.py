"""Solar-Open2-250B's block on the serving path: the Kimi Delta Attention
mixer (`nn/layer/kda.py`), its recurrence (`ops/kda.py`: the interpreted
kernel against the scan) and the model (`models/solar_open2.py`) against the
benchmark's plain reference (`benchmark/reference/solar_open2.py`): the
uncached forward, prefill in chunks and token-by-token decode through the
cache against the reference's one full forward, `LLMEngine` (chunked prefill
beside decode rows, a slot used again by a second request, the packed step)
against the same, the float32 state under a bfloat16 model, the shares of
the experts against the uncut layer, the published model's parameter count
by shape alone, and the faults the comparison must see. CPU, float32, tiny
widths: hidden 64; KDA, GQA (the benchmark's tiny cell serves GQA, KDA,
KDA, KDA, GQA, and `tests/test_mosaic_aot.py` a step of three KDA layers); 4 KDA heads of 16 behind a conv of 4; 4 query heads on
2 key/value heads of 16 with an element-wise gate; a router over 16 experts
of width 32, 4 a token, beside a shared one; an untied head.

Initial values: the constructor's are flat (`A_log` 0, no `dt_bias`); the
tests draw `A_log` from U(0, 2.77), `dt_bias` from U(-4, 0), the conv's
taps from N(0, 0.5) and matrices from N(0, 0.15), under which what the state
carries is a visible share of every logit (the faults below move them).
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import (CACHE_KINDS, RecurrentState,
                                          generate, make_decoder_fns)
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2DecoderLayer,
                                           SolarOpen2ForCausalLM)
from paddle_tpu.ops import kda, pallas_mode

from benchmark.families import solar_open2 as family
from benchmark.reference import solar_open2 as ref

VOCAB, HIDDEN = 128, 64
TINY = dict(vocab_size=VOCAB, hidden_size=HIDDEN, moe_intermediate_size=32,
            num_hidden_layers=2, gqa_layers=(1,), num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, linear_num_heads=4,
            linear_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=128)
PUBLISHED = dict(
    vocab_size=196608, hidden_size=4096, moe_intermediate_size=1280,
    num_hidden_layers=48, gqa_interval=3, gqa_layers=list(range(0, 48, 4)),
    num_attention_heads=64, num_key_value_heads=8, head_dim=128,
    use_gqa_gate=True, n_routed_experts=320, n_shared_experts=1,
    num_experts_per_tok=8,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 128,
                        "num_heads": 64, "num_kv_heads": None})


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """The file's engines and interpreted kernels are large CPU programs
    held by module-level `jax.jit` caches: let them go with the file
    (ROADMAP C8)."""
    yield
    jax.clear_caches()


def _seed_weights(model, seed=3):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            value = rng.uniform(0.0, 2.77, p.shape)
        elif name.endswith("dt_bias"):
            value = rng.uniform(-4.0, 0.0, p.shape)
        elif name.endswith("g_up_proj.bias"):
            value = rng.normal(0.0, 0.3, p.shape)
        elif name.endswith("conv_weight"):
            value = rng.normal(0.0, 0.5, p.shape)
        elif name.endswith("embed_tokens.weight"):
            value = rng.normal(0.0, 0.05, p.shape)
        elif name.endswith("o_norm_weight"):          # not all ones
            value = rng.uniform(0.5, 1.5, p.shape)
        elif len(p.shape) >= 2:
            value = rng.normal(0.0, 0.15, p.shape)
        else:
            continue                               # the blocks' norm scales
        p.data = jnp.asarray(value, p.data.dtype)
    return model


def _model(**over):
    paddle.seed(0)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(**{**TINY, **over}))
    model.eval()
    return _seed_weights(model)


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _ref_config(model, **fault):
    return {**dataclasses.asdict(model.config), **fault}


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _ids(shape, seed=1):
    return np.random.default_rng(seed).integers(
        1, VOCAB, shape).astype(np.int32)


# ---- the shape of the model --------------------------------------------------

@pytest.mark.parametrize("layers,gqa,attention", [
    (48, None, list(range(0, 48, 4))), (5, None, [0, 4]), (4, None, [0]),
    (6, [1, 5, 9], [1, 5])])
def test_layer_order_from_the_gqa_layers(layers, gqa, attention):
    cfg = SolarOpen2Config(**{**TINY, "num_hidden_layers": layers,
                              "gqa_layers": gqa})
    kinds = cfg.layer_types
    assert [i for i, k in enumerate(kinds) if k == "attention"] == attention
    assert kinds.count("kda") == layers - len(attention)
    assert kinds == ref.layer_types(dataclasses.asdict(cfg))


def test_the_published_model_is_250_billion_parameters_by_shape_alone():
    """No array is made: the constructor runs under `LazyGuard` and the
    count is of the shapes it declares (four layers built: one period;
    the family's arithmetic carries it to 48)."""
    with paddle.LazyGuard():
        model = SolarOpen2ForCausalLM(SolarOpen2Config(
            num_hidden_layers=4, dtype="bfloat16"))
    shapes = {k: tuple(p.shape) for k, p in model.named_parameters()}
    period = {**PUBLISHED, "num_hidden_layers": 4}
    assert sum(int(np.prod(s)) for s in shapes.values()) \
        == family.total_params(period)
    assert family.total_params(PUBLISHED) == 250_288_089_856
    assert sum(family._kda(PUBLISHED)) == 137_740_480
    assert family._attention(PUBLISHED) == 109_051_904
    one = "model.layers.1.kda."
    assert shapes[one + "qkv_proj.weight"] == (4096, 24576)
    assert shapes[one + "conv_weight"] == (24576, 4)
    assert shapes[one + "fgb_proj.weight"] == (4096, 128 + 128 + 64)
    assert shapes[one + "f_up_proj.weight"] == (128, 8192)
    assert shapes[one + "g_up_proj.bias"] == (8192,)
    assert shapes[one + "A_log"] == (64,)
    assert shapes[one + "o_norm_weight"] == (128,)
    assert shapes["model.layers.0.self_attn.g_proj.weight"] == (4096, 8192)
    assert shapes["model.layers.0.experts.router_weight"] == (4096, 320)
    assert shapes["model.layers.0.experts.w_gate"] == (320, 4096, 1280)
    assert shapes["lm_head.weight"] == (4096, 196608)
    assert family.attention_shape(PUBLISHED) == {
        "heads": 64, "kv_heads": 8, "head_dim": 128}
    # 14.7 B active with the embedding's rows counted as the head's are
    assert round((family.matmul_params(PUBLISHED)
                  + 4096 * 196608) / 1e9, 1) == 14.7
    # the head-wise gate, the other reading of `use_gqa_gate`
    assert round(family.total_params(
        {**PUBLISHED, "gqa_gate": "headwise"}) / 1e9, 1) == 249.9


def test_what_the_family_does_not_serve_is_refused():
    for key, value in (("use_rope", True), ("kda_use_full_proj", True),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(NotImplementedError, match=key):
            SolarOpen2Config(**{**TINY, key: value})
    with pytest.raises(ValueError, match="gqa_gate"):
        SolarOpen2Config(**{**TINY, "gqa_gate": "rank"})
    model = _model(num_hidden_layers=1)
    with pytest.raises(NotImplementedError, match="not wired"):
        model(paddle.to_tensor(_ids((1, 4))),
              labels=paddle.to_tensor(_ids((1, 4))))


def test_cache_entries_say_what_each_layer_keeps(tiny):
    caches = tiny.init_cache(3, 40)
    assert [isinstance(c, RecurrentState) for c in caches] \
        == [True, False]
    assert caches[0].conv.shape == (3, 3, 3 * 64)
    assert caches[0].ssm.shape == (3, 16, 64)
    assert caches[1][0].shape == (3, 2, 40, 16)
    assert len(CACHE_KINDS) == 5                      # no sixth kind
    assert tiny.query_heads_by_layer() == [0, 4]
    # the engine counts no row as advanced in matrix form: the kernel has
    # the column loop alone
    assert not any(getattr(m, "matrix_columns", None)
                   for m in tiny.sublayers())


def test_the_state_is_float32_under_a_bfloat16_model():
    model = _model(dtype="bfloat16")
    caches = model.init_cache(2, 16)
    assert caches[0].conv.dtype == jnp.bfloat16
    assert caches[0].ssm.dtype == jnp.float32
    assert caches[1][0].dtype == jnp.bfloat16
    params, prefill, _ = make_decoder_fns(model)
    logits, caches = prefill(params, jnp.asarray(_ids((2, 8))), caches,
                             jnp.int32(0))
    assert logits.dtype == jnp.bfloat16
    assert [c.dtype for c in caches[0]] == [jnp.bfloat16, jnp.float32]
    assert float(jnp.abs(caches[0][1]).max()) > 0


# ---- the recurrence's kernel -------------------------------------------------

def _kda_case(adv, fresh, heads=2, d=128, seed=0):
    """Operands of one call: rows of `adv` live columns laid out as token
    rows one behind the other, unit q and k, log-decays down to -1.5."""
    rng = np.random.default_rng(seed)
    adv = np.asarray(adv, np.int32)
    rows, tokens = len(adv), int(adv.sum()) + 3        # three dead tokens
    start = np.concatenate([[0], np.cumsum(adv)[:-1]]).astype(np.int32)

    def unit(x):
        x = x.reshape(tokens, heads, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            tokens, heads * d)

    q = unit(rng.normal(size=(tokens, heads * d))) * d ** -0.5
    k = unit(rng.normal(size=(tokens, heads * d)))
    v = rng.normal(size=(tokens, heads * d))
    g = -rng.uniform(0.001, 1.5, size=(tokens, heads * d))
    beta = rng.uniform(0.0, 2.0, size=(tokens, heads))
    state = rng.normal(size=(rows, d, heads * d))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, state)] \
        + [jnp.asarray(start), jnp.asarray(adv),
           jnp.asarray(fresh, jnp.int32)]


@pytest.mark.parametrize("adv,fresh", [
    ([1, 1, 1], [0, 0, 0]), ([2, 1, 2, 0], [0, 1, 0, 0]),
    ([16, 1, 0, 16, 5], [1, 0, 0, 0, 1])],
    ids=["one column", "two columns", "sixteen columns"])
def test_kernel_interpreted_equals_the_scan(monkeypatch, adv, fresh):
    """Rows of 1, 2 and 16 live columns beside dead columns, a row of none
    and fresh rows; blocks narrowed so that a small call has several row
    blocks (the last one ragged)."""
    monkeypatch.setattr(kda, "ROWS_BLOCK", 2)
    args = _kda_case(adv, fresh)
    before = dict(pallas_mode.KERNEL_TRACES)
    o_scan, s_scan = kda.kda_update(*args, columns=16, impl="scan")
    o_kern, s_kern = kda.kda_update(*args, columns=16, impl="pallas")
    np.testing.assert_allclose(np.asarray(o_kern), np.asarray(o_scan),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_kern), np.asarray(s_scan),
                               rtol=1e-5, atol=1e-6)
    assert pallas_mode.KERNEL_TRACES[("kda_update", "scan")] \
        == before.get(("kda_update", "scan"), 0) + 1
    assert pallas_mode.KERNEL_TRACES[("kda_update", "interpret")] \
        > before.get(("kda_update", "interpret"), 0)
    state, start = np.asarray(args[5]), np.asarray(args[6])
    for r, (n, new) in enumerate(zip(adv, fresh)):
        if n == 0:        # nothing live: the state passes (zeroed if fresh)
            np.testing.assert_array_equal(
                np.asarray(s_kern)[r], 0 * state[r] if new else state[r])
    # token rows no row owns come back as zeros
    live = np.zeros(args[0].shape[0], bool)
    for s0, n in zip(start, adv):
        live[s0:s0 + n] = True
    assert not np.asarray(o_kern)[~live].any()
    assert np.abs(np.asarray(o_kern)[live]).min(axis=-1).max() > 0


def test_scan_equals_the_recurrence_written_out():
    """numpy, a head and a position at a time, from the four lines of
    `ops/kda.py`'s docstring."""
    adv, fresh = [3, 1, 0], [0, 1, 0]
    args = _kda_case(adv, fresh, heads=2, d=8, seed=4)
    q, k, v, g, beta, state, start = (np.asarray(a, np.float64)
                                      for a in args[:7])
    o, s = kda.kda_update(*args, columns=4, impl="scan")
    want_s = np.where(np.asarray(fresh)[:, None, None] != 0, 0.0,
                      state).reshape(3, 8, 2, 8)
    want_o = np.zeros((q.shape[0], 2, 8))
    for r, n in enumerate(adv):
        for t in range(n):
            tok = int(start[r]) + t
            for h in range(2):
                sl = slice(8 * h, 8 * h + 8)
                S = want_s[r, :, h] * np.exp(g[tok, sl])[:, None]
                S = S + np.outer(k[tok, sl],
                                 beta[tok, h] * (v[tok, sl] - S.T @ k[tok, sl]))
                want_o[tok, h], want_s[r, :, h] = S.T @ q[tok, sl], S
    np.testing.assert_allclose(np.asarray(o), want_o.reshape(-1, 16),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s), want_s.reshape(3, 8, 16),
                               rtol=1e-5, atol=1e-6)


def test_rows_layout_walks_a_long_sequence_in_chunks(monkeypatch):
    """`kda_update_rows` over more tokens than one call takes carries the
    state from chunk to chunk: the same as one call over all of them."""
    rng = np.random.default_rng(6)
    rows, T, heads, d = 2, 11, 2, 8
    shape = (rows, T, heads * d)
    q, k, v = (jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
               for _ in range(3))
    g = jnp.asarray(-rng.uniform(0.01, 1.0, size=shape), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, size=(rows, T, heads)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(rows, d, heads * d)), jnp.float32)
    adv = jnp.asarray([11, 7], jnp.int32)
    o_whole, s_whole = kda.kda_update_rows(q, k, v, g, beta, state, adv)
    monkeypatch.setattr(kda, "MAX_TOKENS", 8)          # chunks of 4 columns
    o_parts, s_parts = kda.kda_update_rows(q, k, v, g, beta, state, adv)
    np.testing.assert_allclose(np.asarray(s_parts), np.asarray(s_whole),
                               rtol=1e-5, atol=1e-6)
    live = np.arange(T)[None, :] < np.asarray(adv)[:, None]
    np.testing.assert_allclose(np.asarray(o_parts)[live],
                               np.asarray(o_whole)[live], rtol=1e-5,
                               atol=1e-6)


# ---- against the reference ---------------------------------------------------

def test_model_equals_the_reference(tiny):
    ids = _ids((2, 16))
    got = np.asarray(tiny(paddle.to_tensor(ids)).data)
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids),
                                 _ref_config(tiny)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert np.abs(want).max() > 0.5             # not a flat distribution
    x, head = ref.hidden_and_head(_weights(tiny), jnp.asarray(ids),
                                  _ref_config(tiny))
    np.testing.assert_allclose(np.asarray(x @ head), want, rtol=1e-5,
                                atol=1e-5)


def test_the_headwise_gate_is_one_constructor_argument():
    model = _model(gqa_gate="headwise")
    assert tuple(model.model.layers[1].self_attn.g_proj.weight.shape) \
        == (HIDDEN, 4)
    ids = _ids((1, 12))
    got = np.asarray(model(paddle.to_tensor(ids)).data)
    want = np.asarray(ref.logits(_weights(model), jnp.asarray(ids),
                                 _ref_config(model)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


S = 11
SPLITS = [(4, 7)]


@pytest.fixture(scope="module")
def continuation(tiny):
    """(prompt + nine greedy tokens [2, 20], the reference's logits of one
    full forward over them)."""
    out = np.asarray(generate(tiny, _ids((2, S)), max_new_tokens=9).data)
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(out),
                                 _ref_config(tiny)))
    # the greedy continuation is the reference's argmax at every step
    assert np.array_equal(out[:, S:], want[:, S - 1:-1].argmax(-1))
    return out, want


@pytest.mark.parametrize("chunks", SPLITS, ids=lambda c: "-".join(map(str, c)))
def test_prefill_in_chunks_then_decode_equals_one_full_forward(
        tiny, continuation, chunks):
    """The prompt through the cache in chunks of any split (a chunk of one
    is a decode step), then nine tokens one at a time, every logit against
    the reference's single pass over prompt + continuation."""
    out, want = continuation
    params, prefill, decode = make_decoder_fns(tiny)
    caches = tiny.init_cache(2, 24)
    off = 0
    for n in chunks:
        logits, caches = prefill(params, jnp.asarray(out[:, off:off + n]),
                                 caches, jnp.int32(off))
        np.testing.assert_allclose(np.asarray(logits),
                                   want[:, off:off + n], rtol=1e-4,
                                   atol=1e-4)
        off += n
    for t in range(S, S + 9):
        step, caches = decode(params, jnp.asarray(out[:, t]), jnp.int32(t),
                              caches)
        np.testing.assert_allclose(np.asarray(step), want[:, t], rtol=1e-4,
                                   atol=1e-4)


# what a fault changes: the KDA mixer, the attention layer or the FFN
FAULTS = {
    "beta without the 2": ("kda", {"kda_allow_neg_eigval": False}),
    "no delta term": ("kda", {"kda_delta": False}),
    "a decay a head": ("kda", {"kda_decay_per_head": True}),
    "q and k not normalised": ("kda", {"kda_qk_l2norm": False}),
    "no conv": ("kda", {"kda_conv": False}),
    "state rounded to bfloat16": ("kda", {"kda_state_dtype": "bfloat16"}),
    "GQA gate off": ("attention", {"use_gqa_gate": False}),
    "the head-wise gate for the element-wise one":
        ("attention", {"gqa_gate": "headwise"}),
    "shared expert off": ("ffn", {"n_shared_experts": 0}),
}


@pytest.fixture(scope="module")
def parts(tiny):
    """(a normed input [24, hidden], {part: (the program's output for it,
    the reference's function of a configuration)}): the three parts of a
    block that the controls of `benchmark/jobs/solar_open2_controls.py`
    take a mechanism out of. The benchmark's tiny cell reads the same
    controls at the log-probabilities (`test_solar_open2_cell.py`)."""
    h = jnp.asarray(np.random.default_rng(7).normal(size=(1, 24, HIDDEN)),
                    jnp.float32)
    kda_layer, gqa_layer = tiny.model.layers
    weights = _weights(tiny)

    def under(prefix, change=lambda k, v: v):
        return lambda name: change(name, weights[prefix + name]).astype(
            jnp.float32)

    def headwise(name, v):     # a gate a head: each head's first column
        return v[:, ::16] if name == "g_proj.weight" else v

    def attention(cfg):
        change = headwise if cfg.get("gqa_gate") == "headwise" \
            else lambda k, v: v
        return ref._attention(h[0], under("model.layers.1.self_attn.",
                                          change), cfg)

    x = paddle.to_tensor(h)
    return {
        "kda": (kda_layer.kda(x).numpy()[0], lambda cfg: ref._kda(
            h[0], under("model.layers.0.kda."), cfg)),
        "attention": (gqa_layer.self_attn(x).numpy()[0], attention),
        "ffn": (kda_layer.ffn(x).numpy()[0], lambda cfg: ref._ffn(
            h[0], lambda name: weights["model.layers.0." + name], cfg)),
    }


@pytest.mark.parametrize("part", ["kda", "attention", "ffn"])
def test_each_part_of_a_block_equals_the_reference(tiny, parts, part):
    got, reference = parts[part]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference(_ref_config(tiny)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("fault", list(FAULTS), ids=list(FAULTS))
def test_the_comparison_sees_the_mechanism(tiny, parts, fault):
    """Each control of `benchmark/jobs/solar_open2_controls.py`, computed
    by the reference alone, is far outside the 1e-4 the program holds
    against the sound one."""
    part, change = FAULTS[fault]
    got, reference = parts[part]
    with jax.default_matmul_precision("highest"):
        faulty = np.asarray(reference(_ref_config(tiny, **change)))
    least = 1e-3 if "bfloat16" in fault else 1e-2
    assert np.abs(got - faulty)[4:].max() > least


# ---- the shares add up -------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts held as 4 shares of 4: the routed parts of all the shares
    plus the shared expert once equal the uncut layer, in the program and
    in the reference (which holds "the first 4" of a router whose columns
    are rolled)."""
    paddle.seed(0)
    whole = SolarOpen2DecoderLayer(SolarOpen2Config(**TINY), "kda")
    _seed_weights(whole, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(0, 1, (2, 9, HIDDEN)),
                    jnp.float32)
    want = whole.ffn(paddle.to_tensor(x)).numpy()
    shared = whole.shared_experts(paddle.to_tensor(x)).numpy()
    total = np.zeros_like(want)
    for first in range(0, 16, 4):
        share = SolarOpen2DecoderLayer(SolarOpen2Config(
            **{**TINY, "experts_held": (first, 4)}), "kda")
        assert tuple(share.experts.w_gate.shape) == (4, HIDDEN, 32)
        assert tuple(share.experts.router_weight.shape) == (HIDDEN, 16)
        share.experts.router_weight.data = whole.experts.router_weight.data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share.experts, name).data = getattr(
                whole.experts, name).data[first:first + 4]
        total += share.experts(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(total + shared, want, atol=1e-5)
    leaves = {"experts." + k: p.data
              for k, p in whole.experts.named_parameters()}
    leaves.update({"shared_experts." + k: p.data
                   for k, p in whole.shared_experts.named_parameters()})
    cfg = {"num_experts_per_tok": 4}
    h = x.reshape(-1, HIDDEN)
    with jax.default_matmul_precision("highest"):
        uncut = ref._ffn(h, leaves.__getitem__, cfg)
        parts = sum(
            ref._ffn(h, {**leaves, **{
                "experts." + n: jnp.roll(leaves["experts." + n],
                                         -first, 0)[:4]
                for n in ("w_gate", "w_up", "w_down")},
                "experts.router_weight": jnp.roll(
                    leaves["experts.router_weight"], -first, 1)
            }.__getitem__, {**cfg, "n_shared_experts": 0})
            for first in range(0, 16, 4))
    np.testing.assert_allclose(np.asarray(uncut), want.reshape(-1, HIDDEN),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(parts) + shared.reshape(-1, HIDDEN),
        want.reshape(-1, HIDDEN), atol=1e-5)


# ---- LLMEngine ---------------------------------------------------------------

def _engine(model, slots, **cfg_kw):
    kw = dict(num_slots=slots, block_len=8, n_blocks=8, max_queue_depth=128)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=serving.SimClock())


def _drain(eng, after_pump=None):
    steps = 0
    while eng.has_work():
        eng.pump()
        if after_pump is not None:
            after_pump(eng)
        steps += 1
        assert steps < 2000, "engine failed to converge"


LENGTHS = (5, 24, 33)


def _prompts(seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in LENGTHS]


@pytest.fixture(scope="module")
def held():
    """The model the engine tests serve: a share of the experts (8 of 16)."""
    return _model(experts_held=(0, 8))


@pytest.fixture(scope="module")
def streams(held):
    """(prompts, the reference's greedy continuation of each and its
    log-probabilities): one full forward of prompt + continuation."""
    out = []
    for p in _prompts():
        ids = np.asarray(generate(held, p[None], max_new_tokens=10).data)
        lg = np.asarray(ref.logits(_weights(held), jnp.asarray(ids),
                                   _ref_config(held)))[0]
        assert np.array_equal(ids[0, len(p):], lg[len(p) - 1:-1].argmax(-1))
        lp = lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(
            -1, keepdims=True)) - lg.max(-1, keepdims=True)
        out.append((ids[0, len(p):],
                    lp[np.arange(len(p) - 1, ids.shape[1] - 1),
                       ids[0, len(p):]]))
    return _prompts(), out


@pytest.mark.parametrize("slots", [2, 40], ids=["unpacked", "packed"])
def test_engine_streams_are_the_references(held, streams, slots):
    """Three requests through two slots (a slot is used again by a
    later request, admitted fresh: stale state must not leak into it;
    prefill chunks ride beside decode rows) and through 40 (the packed
    step): every stream is the reference's greedy continuation and every
    log-probability its own, and the counters say what ran."""
    prompts, want = streams
    eng = _engine(held, slots)
    assert (eng.step_tokens < slots * 16) == (slots == 40)
    handles = [eng.submit(p, max_new_tokens=10, logprobs=True)
               for p in prompts]
    _drain(eng)
    for h, (tokens, lp) in zip(handles, want):
        assert np.array_equal(np.asarray(h.result(timeout=5)), tokens)
        np.testing.assert_allclose(np.asarray(h.logprobs_so_far()), lp,
                                   rtol=1e-4, atol=1e-4)
    snap = eng.metrics.snapshot()
    assert snap["recurrent_rows_started"] == len(prompts)
    # the kernel walks every row a column at a time
    assert snap["recurrent_rows_matrix"] == 0 < snap["recurrent_rows_loop"]
    # each array at its own width: the conv's columns in the model's type
    # (float32 here), the state in float32
    assert snap["recurrent_state_bytes"] == eng.pool.recurrent_state_bytes \
        == slots * (3 * 192 + 16 * 64) * 4
    assert eng.pool.layer_kinds == ["recurrent", "paged"]
    assert eng.enable_prefix_cache is False and eng.prefix_cache is None
    if slots == 2:
        assert eng.pool.stats["reuses"] >= len(prompts) - slots


def test_the_gauge_counts_the_state_in_float32_under_a_bfloat16_model():
    model = _model(dtype="bfloat16")
    eng = _engine(model, 4)
    assert eng.pool.recurrent_state_bytes \
        == 4 * (3 * 192 * 2 + 16 * 64 * 4)
    assert f"pdtpu_llm_recurrent_state_bytes " \
           f"{eng.pool.recurrent_state_bytes}" in eng.metrics.render()
    from paddle_tpu.serving import metrics
    assert metrics.RECURRENT_STATE_BYTES == eng.pool.recurrent_state_bytes
    assert eng.pool.kv_bytes() == {
        "full": 2 * 4 * (8 * 8 + 16) * 2 * 16 * 2, "window": 0}


def test_a_wiped_state_changes_the_streams(held, streams):
    """With the recurrent layers' state wiped between steps (the conv's
    columns and the K/V slabs left alone) the streams are no longer the
    reference's."""
    prompts, want = streams

    def wipe(eng):
        eng.pool.slabs = [
            (a, jnp.zeros_like(b)) if kind == "recurrent" else (a, b)
            for (a, b), kind in zip(eng.pool.slabs, eng.pool.layer_kinds)]

    eng = _engine(held, 2)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng, after_pump=wipe)
    got = [np.asarray(h.result(timeout=5)) for h in handles]
    assert sum(not np.array_equal(g, w) for g, (w, _) in zip(got, want)) \
        >= len(prompts) // 2


def test_the_new_modules_stay_off_the_packages_import_path():
    """`import paddle_tpu` (and its serving and models packages) loads none
    of what this model added: the cells that do not serve it pay nothing at
    start-up (`setup_s`)."""
    code = ("import sys, paddle_tpu, paddle_tpu.serving, paddle_tpu.models\n"
            "bad = [m for m in ('paddle_tpu.models.solar_open2', "
            "'paddle_tpu.models.hybrid', 'paddle_tpu.nn.layer.kda', "
            "'paddle_tpu.ops.kda', 'paddle_tpu.ops.ssm', 'benchmark') "
            "if m in sys.modules]\n"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
