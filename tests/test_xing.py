"""Xing4.0's residual path (`DeepseekConfig.hc_mult` streams joined by
manifold-constrained hyper-connections, `nn/layer/hyper_connection.py`,
`ops/hyper_connection.py`) round the DeepSeek-V3 family's block on the
serving path, with every expert held and the router's selection bias. The
uncached forward, the cached forward and `LLMEngine` against the
benchmark's plain reference (`benchmark/reference/xing4_0.py`, logits) and
against `generate()` (bits). CPU, float32, tiny widths: hidden 48, 4 streams
(a connection's `phi` is 192 x 24), 4 heads of 16 + 8 (q, k) / 16 (v), ranks
24 / 32, one dense layer and two expert layers, 16 experts of width 32, 4 per
token, YaRN factor 8 over 32 original positions.

Initial values: matrices N(0, 0.15), the router and the selection bias
N(0, 0.3), a connection's `phi` N(0, 0.08) (logits of deviation 0.08 x
sqrt(192) = 1.1 at a gain of 1), its gains U(0.5, 1.5), its bias N(0, 1): the
mixing depends on the token and every part of a connection moves the logits
by far more than the tolerance (`test_each_mechanism_carries_the_logits`).
The tolerance: both sides run float32 and differ by the rounding of
differently ordered sums (the absorbed attention against the expanded; the
product `x phi` scaled after against scaled before) through three layers and
six connections: 1e-6 to 2e-5 on logits of size 1 to 4, against 1e-4.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.core.tensor import Unassigned
from paddle_tpu.models.deepseek import DeepseekConfig, DeepseekForCausalLM
from paddle_tpu.models.generation import LatentKV, generate

from benchmark.families import xing4_0 as family
from benchmark.reference import xing4_0 as ref

VOCAB = 128
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 1.0}
TINY = dict(vocab_size=VOCAB, hidden_size=48, intermediate_size=64,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
            first_k_dense_replace=1, n_group=1, topk_group=1,
            norm_topk_prob=True, routed_scaling_factor=2.0,
            scoring_func="sigmoid", select_bias=True,
            max_position_embeddings=512, rms_norm_eps=1e-6,
            rope_theta=10000.0, rope_scaling=YARN, hc_mult=4,
            hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=30.0)
# the same sizes as the reference reads them (the benchmark's keys)
REF = {**{k: v for k, v in TINY.items()
          if k not in ("vocab_size", "max_position_embeddings",
                       "select_bias", "hc_res_clamp")},
       "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30}
TOL = 1e-4


def _seed_weights(model, seed=5):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("_hc.alpha"):
            value = rng.uniform(0.5, 1.5, p.shape)
        elif name.endswith("_hc.bias"):
            value = rng.normal(0.0, 1.0, p.shape)
        elif name.endswith("_hc.phi"):
            value = rng.normal(0.0, 0.08, p.shape)
        elif name.endswith("select_bias") or "router" in name:
            value = rng.normal(0.0, 0.3, p.shape)
        elif len(p.shape) < 2:
            continue                                  # norm scales stay 1
        else:
            value = rng.normal(0.0, 0.15, p.shape)
        p.data = jnp.asarray(value, jnp.float32)
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


def _reference_logits(model, ids, **fault):
    return np.asarray(ref.logits(_weights(model), jnp.asarray(ids),
                                 {**REF, **fault}))


# ---- the model against the plain reference ----

def test_a_layer_carries_two_connections(tiny):
    layer = tiny.model.layers[1]
    for hc in (layer.attn_hc, layer.mlp_hc):
        assert tuple(hc.phi.shape) == (4 * 48, 24)
        assert tuple(hc.bias.shape) == (24,) and tuple(hc.alpha.shape) == (3,)
        # float32 whatever the model's type
        assert str(hc.phi.dtype) == "float32"
    assert layer.mlp.experts.select_bias is not None
    assert tuple(layer.mlp.experts.w_gate.shape) == (16, 48, 32)
    names = [k for k, _ in tiny.named_parameters() if "_hc." in k]
    assert len(names) == 3 * 2 * 3
    paddle.seed(0)
    bf16 = DeepseekForCausalLM(DeepseekConfig(**{**TINY,
                                                 "dtype": "bfloat16"}))
    assert str(bf16.model.layers[0].attn_hc.phi.dtype) == "float32"
    assert str(bf16.model.layers[0].self_attn.o_proj.weight.dtype) \
        == "bfloat16"
    with pytest.raises(ValueError, match="hc_mult"):
        DeepseekConfig(hc_mult=0)


def test_one_stream_builds_no_connection():
    paddle.seed(0)
    model = DeepseekForCausalLM(DeepseekConfig(**{**TINY, "hc_mult": 1}))
    assert model.model.layers[0].attn_hc is None
    assert not [k for k, _ in model.named_parameters() if "_hc." in k]


def test_uncached_forward_equals_the_reference(tiny):
    ids = np.stack(_prompts([70, 70]))
    got = tiny(paddle.to_tensor(ids)).numpy()
    want = _reference_logits(tiny, ids)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_layer_equals_the_reference(tiny):
    """One sparse layer alone, four different streams in."""
    layer = tiny.model.layers[2]
    rng = np.random.default_rng(3)
    X = rng.normal(0, 1, (1, 21, 4 * 48)).astype(np.float32)
    got = layer(paddle.to_tensor(X))[0].numpy()
    leaves = {k: p.data for k, p in layer.named_parameters()}
    cfg = ref._whole({**REF, "rope_scaling": YARN})

    def norm(name, u):
        return ref._rms_norm(u, leaves[name + ".weight"], 1e-6)

    with jax.default_matmul_precision("highest"):
        S = jnp.asarray(X[0]).reshape(21, 4, 48)
        S = ref.connect(S, lambda u: ref._attention(
            norm("input_layernorm", u), leaves.__getitem__, cfg),
            leaves.__getitem__, "attn_hc", cfg)
        S = ref.connect(S, lambda u: ref._moe(
            norm("post_attention_layernorm", u), leaves.__getitem__, cfg),
            leaves.__getitem__, "mlp_hc", cfg)
    np.testing.assert_allclose(got[0], np.asarray(S).reshape(21, -1),
                               atol=TOL)


@pytest.mark.parametrize("widths", [[70], [16] * 5, [64] + [1] * 6],
                         ids=["whole", "chunks of 16", "then one at a time"])
def test_chunks_then_decode_equal_one_full_forward(tiny, widths):
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


def test_the_cache_is_the_latent_layers(tiny):
    """The residual path adds nothing a slot keeps: the cache kinds are
    A.X-K1's."""
    entries = tiny.init_cache(2, 40)
    assert all(isinstance(e, LatentKV) for e in entries) and len(entries) == 3
    assert entries[0].c.shape == (2, 1, 40, 32)


FAULTS = {
    "one Sinkhorn pass": dict(hc_sinkhorn_iters=1),
    "static mixing (alpha = 0)": dict(hc_dynamic=False),
    "H_post without its factor 2": dict(hc_post_gain=1.0),
    "one stream (H_res the identity, H_pre uniform)": dict(hc_mixing=False),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_mechanism_carries_the_logits(tiny, fault):
    """A mechanism taken out of the reference moves the logits by far more
    than the tolerance: the comparisons above see each of them."""
    ids = np.stack(_prompts([70], seed=4))
    got = tiny(paddle.to_tensor(ids)).numpy()
    wrong = _reference_logits(tiny, ids, **FAULTS[fault])
    assert np.abs(got - wrong).max() > 100 * TOL


def test_the_selection_bias_is_seen_with_every_expert_held(tiny):
    """With 16 of 16 experts held every choice the bias changes lands on
    the chip: the reference without it is off by far more than the
    tolerance, and the program's own choices change."""
    ids = np.stack(_prompts([70], seed=4))
    got = tiny(paddle.to_tensor(ids)).numpy()
    unbiased = {k: v * 0 if k.endswith("select_bias") else v
                for k, v in _weights(tiny).items()}
    wrong = np.asarray(ref.logits(unbiased, jnp.asarray(ids), REF))
    assert np.abs(got - wrong).max() > 100 * TOL


def test_bfloat16_coefficients_would_be_seen(tiny):
    """The coefficients are float32: the reference's logits with every
    connection's `phi`, bias and gains rounded to bfloat16 differ from the
    program's by more than the tolerance."""
    ids = np.stack(_prompts([70], seed=4))
    got = tiny(paddle.to_tensor(ids)).numpy()
    rounded = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
               if "_hc." in k else v for k, v in _weights(tiny).items()}
    wrong = np.asarray(ref.logits(rounded, jnp.asarray(ids), REF))
    assert np.abs(got - wrong).max() > 3 * TOL


# ---- through the engine ----

@pytest.mark.parametrize("num_slots", [3, 40], ids=["unpacked", "packed"])
def test_engine_logprobs_equal_the_references_full_forward(tiny, num_slots):
    """Prefill in chunks of 16 and then decoding through latent pages, the
    slots reused, against one full forward of the reference over prompt +
    output: tokens and log-probabilities."""
    eng = _engine(tiny, num_slots=num_slots,
                  tokens=256 if num_slots == 3 else 160)
    assert eng.pool.layer_kinds == ["latent"] * 3
    assert (eng.step_tokens == 512) == (num_slots == 40)
    lengths = [9, 43, 130, 5, 17, 60, 31] if num_slots == 3 \
        else [9, 43, 130] + [24] * 45
    prompts = _prompts(lengths, seed=2)
    handles = [eng.submit(p, max_new_tokens=6, logprobs=True)
               for p in prompts]
    _drain(eng)
    picked = list(zip(prompts, handles))
    for p, h in picked if num_slots == 3 else picked[:3] + picked[3::11]:
        out = np.asarray(h.result(timeout=0))
        ids = np.concatenate([p, out])[None]
        lg = _reference_logits(tiny, ids)[0]
        lp = np.asarray(jax.nn.log_softmax(lg, -1))
        want = [lp[len(p) - 1 + i, t] for i, t in enumerate(out)]
        np.testing.assert_allclose(h.logprobs_so_far(), want, atol=TOL)
        assert [int(np.argmax(lg[len(p) - 1 + i]))
                for i in range(len(out))] == out.tolist()
    eng.pool.check_balance()


@pytest.mark.parametrize("adv", [[1, 16, 3, 1, 0, 16, 1, 7], [16, 1, 16]],
                         ids=["mixed", "a full block"])
def test_a_packed_step_gives_the_unpacked_steps_logits(tiny, adv):
    """The whole model over one step's tokens packed (`TokenPack`: the
    latent layers' queries stay on the packed block) and as `[slots, 16]`
    rows: the same logits at every live token, the same cache columns up
    to each row's length."""
    from paddle_tpu.ops.attention import PagedView, token_pack
    rng = np.random.default_rng(6)
    adv = np.asarray(adv, np.int32)
    N, C, bl, nb = len(adv), 16, 8, 10
    pos = np.where(adv > 0, rng.integers(0, nb * bl - C, N), 0) \
        .astype(np.int32)
    T = int(adv.sum())
    pack = token_pack(jnp.asarray(adv), jnp.asarray(pos), C, T)
    ids = jnp.asarray(rng.integers(0, VOCAB, (T, 1)), jnp.int32)
    caches = [tuple(paddle.to_tensor(jnp.asarray(
        rng.normal(0, 1, (N, 1, nb * bl + C, a.shape[3])), jnp.float32))
        for a in entry) for entry in tiny.init_cache(N, 8)]
    # a slot writes its own slab row: its pages are that row's
    table = np.arange(N * nb, dtype=np.int32).reshape(N, nb)
    paged = PagedView(jnp.asarray(table), jnp.asarray(pos + adv), bl, nb)
    got, got_caches = tiny.forward_with_cache(
        paddle.to_tensor(ids), caches, jnp.asarray(pos), paged=paged,
        pack=pack)
    want, want_caches = tiny.forward_with_cache(
        paddle.to_tensor(pack.unpack(ids)), caches, jnp.asarray(pos),
        paged=paged)
    np.testing.assert_allclose(got.numpy(), pack.pack(want.data), atol=TOL)
    assert np.abs(got.numpy()).max() > 1.0
    # (the columns past a row's live ones hold what nobody reads)
    held = (np.arange(nb * bl + C) < (pos + adv)[:, None])[:, None, :, None]
    for a, b in zip(got_caches, want_caches):
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.where(held, x.numpy(), 0),
                                       np.where(held, y.numpy(), 0),
                                       atol=TOL)


def test_engine_streams_equal_generate(tiny):
    eng = _engine(tiny)
    prompts = _prompts([5, 16, 17, 60, 129], seed=6)
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    _drain(eng)
    for p, h in zip(prompts, handles):
        want = np.asarray(generate(tiny, p[None], max_new_tokens=8).data)
        assert np.asarray(h.result(timeout=0)).tolist() \
            == want[0, len(p):].tolist()
    # every expert held: the table is as wide as the router
    assert eng.moe_expert_tokens().shape == (2, 16)


# ---- one stream is the parent's model ----

def _one_stream_pair(kind):
    if kind == "axk1":
        from test_axk1 import TINY as base, _seed_weights as seed
    else:
        from test_glm_dsa import TINY as base, _seed_weights as seed
    models = []
    for extra in ({}, {"hc_mult": 1, "hc_sinkhorn_iters": 7}):
        paddle.seed(0)
        model = seed(DeepseekForCausalLM(DeepseekConfig(**{**base,
                                                           **extra})))
        model.eval()
        models.append(model)
    return models


@pytest.mark.parametrize("kind", ["axk1", "glm"])
def test_one_stream_is_bit_identical_to_the_parents_model(kind):
    """`hc_mult` 1 (the default) is the path every accepted cell runs: the
    same parameters, the same logits to the bit, the same lowered step."""
    plain, one = _one_stream_pair(kind)
    assert [k for k, _ in plain.named_parameters()] \
        == [k for k, _ in one.named_parameters()]
    ids = np.stack(_prompts([40, 40], seed=9))
    assert np.array_equal(plain(paddle.to_tensor(ids)).numpy(),
                          one(paddle.to_tensor(ids)).numpy())
    texts = []
    for model in (plain, one):
        eng = _engine(model)
        eng.submit(_prompts([20])[0], max_new_tokens=3)
        with eng._cond:
            eng._admit()
            toks, pos, adv, ctr, *_ = eng._build_rows_locked({})
            args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(adv), eng.pool.device_block_table(),
                    eng.pool.slabs) + eng._sampling_args_locked(ctr) \
                + eng._feedback_args() + eng._tail_args_locked()
        texts.append(eng._step().lower(*args).as_text())
    assert texts[0] == texts[1]
    assert "hyper_connection" not in texts[0]


# ---- the benchmark's arithmetic, and who imports what ----

def test_parameter_count_by_shape_under_lazy_guard():
    config = {**REF, "vocab_size": VOCAB, "max_position_embeddings": 512,
              "name": "x", "dtype": "float32", "topk_method": "noaux_tc",
              "tie_word_embeddings": False}
    model = family.build(config)
    params = dict(model.named_parameters())
    assert all(isinstance(p.data, Unassigned) for p in params.values())
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert n == family.total_params(config)
    assert family.connection_params(config) == 4 * 48 * 24 + 24 + 3
    # the published configuration's: 4,047,680,782 (8.10 GB in bf16)
    import json
    import os
    with open(os.path.join(os.path.dirname(ref.__file__), "..", "configs",
                           "xing4.0-29b-a4b-d5.json")) as f:
        real = json.load(f)
    assert family.total_params(real) == 4_047_680_782
    assert family.connection_params(real) == 344_091


def test_no_other_model_loads_the_residual_path():
    """`import paddle_tpu`, the serving tier and a Mistral-shaped engine
    load no `hyper_connection` module: no other cell imports, traces or
    compiles a line more."""
    code = """
import sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models import deepseek
model = LlamaForCausalLM(LlamaConfig(
    vocab_size=64, hidden_size=32, intermediate_size=32,
    num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
    max_position_embeddings=64))
model.eval()
eng = serving.LLMEngine(model, serving.LLMEngineConfig(
    num_slots=2, block_len=8, n_blocks=4), clock=serving.SimClock())
eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
while eng.has_work():
    eng.pump()
deepseek.DeepseekForCausalLM(deepseek.DeepseekConfig(
    vocab_size=64, hidden_size=32, intermediate_size=32,
    moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=2,
    q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4,
    num_experts_per_tok=2, n_group=1, topk_group=1,
    first_k_dense_replace=1, max_position_embeddings=64))
print(sorted(m for m in sys.modules if "hyper_connection" in m))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
