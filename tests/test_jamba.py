"""AI21-Jamba2-3B's block on the serving path: the Mamba-1 mixer and the
model against the benchmark's plain reference (`benchmark/reference/
jamba.py`), prefill and token-by-token decode through the cache against the
reference's one full forward, `LLMEngine` (chunked prefill beside decode
rows, a slot used again by a second request, the packed step) against the
same, the layer order from period and offset, the published model's
parameter count by shape alone, and two faults the comparison must see. CPU,
float32, tiny widths: hidden 64, a period of 4 with the attention layer
inside it (mamba, mamba, attention, mamba, mamba), 128 channels of 16 state
elements, a step-size bottleneck of 8, 5 query heads on 1 key/value head.

Initial values: the constructor's are flat (`A_log` 0, no step-size bias);
the tests draw `A_log` from U(-1, 2.77), `dt_proj.bias` from U(-4, -1), the
conv's taps from N(0, 0.5) (the benchmark configuration's `leaf_seeding`)
and matrices from N(0, 0.15), under which what the state carries is a
visible share of every logit (the two faults below move them).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import (CACHE_KINDS, RecurrentState,
                                          generate, make_decoder_fns)
from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
from paddle_tpu.nn.layer.mamba import Mamba1Mixer

from benchmark.families import jamba as family
from benchmark.reference import jamba as ref

VOCAB, HIDDEN = 128, 64
TINY = dict(vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=5,
            num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
            mamba_d_state=16, mamba_dt_rank=8, max_position_embeddings=128)
PUBLISHED = dict(
    vocab_size=65536, hidden_size=2560, intermediate_size=8192,
    num_hidden_layers=28, num_attention_heads=20, num_key_value_heads=1,
    head_dim=128, attn_layer_period=14, attn_layer_offset=7,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160)


def _seed_weights(model, seed=3):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            value = rng.uniform(-1.0, 2.77, p.shape)
        elif name.endswith("dt_proj.bias"):
            value = rng.uniform(-4.0, -1.0, p.shape)
        elif name.endswith("conv_bias"):
            value = rng.normal(0.0, 0.1, p.shape)
        elif name.endswith("conv_weight"):
            value = rng.normal(0.0, 0.5, p.shape)
        elif name.endswith("embed_tokens.weight"):
            value = rng.normal(0.0, 0.05, p.shape)
        elif name.endswith("layernorm"):       # dt / b / c: not all ones
            value = rng.uniform(0.5, 1.5, p.shape)
        elif len(p.shape) >= 2:
            value = rng.normal(0.0, 0.15, p.shape)
        else:
            continue                      # the blocks' norm scales and D
        p.data = jnp.asarray(value, p.data.dtype)
    return model


def _model(**over):
    paddle.seed(0)
    model = JambaForCausalLM(JambaConfig(**{**TINY, **over}))
    model.eval()
    return _seed_weights(model)


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _ref_config(model, **fault):
    cfg = dataclasses.asdict(model.config)
    return {**cfg, "head_dim": model.config.head_dim, **fault}


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _ids(shape, seed=1):
    return np.random.default_rng(seed).integers(
        1, VOCAB, shape).astype(np.int32)


# ---- the shape of the model --------------------------------------------------

@pytest.mark.parametrize("layers,period,offset,attention", [
    (28, 14, 7, [7, 21]), (5, 4, 2, [2]), (8, 8, 4, [4]), (6, 2, 1, [1, 3, 5]),
    (3, 14, 7, [])])
def test_layer_order_from_period_and_offset(layers, period, offset,
                                            attention):
    cfg = JambaConfig(**{**TINY, "num_hidden_layers": layers,
                         "attn_layer_period": period,
                         "attn_layer_offset": offset})
    kinds = cfg.layer_types
    assert [i for i, k in enumerate(kinds) if k == "attention"] == attention
    assert kinds.count("mamba") == layers - len(attention)
    assert kinds == ref.layer_types(dataclasses.asdict(cfg))


def test_the_published_model_is_three_billion_parameters_by_shape_alone():
    """No array is made: the constructor runs under `LazyGuard` and the
    count is of the shapes it declares."""
    with paddle.LazyGuard():
        model = JambaForCausalLM(JambaConfig(dtype="bfloat16"))
    shapes = {k: tuple(p.shape) for k, p in model.named_parameters()}
    assert sum(int(np.prod(s)) for s in shapes.values()) \
        == family.total_params(PUBLISHED) == 3_029_337_472
    one = "model.layers.0.mamba."
    assert shapes[one + "in_proj.weight"] == (2560, 10240)
    assert shapes[one + "x_proj.weight"] == (5120, 192)
    assert shapes[one + "dt_proj.weight"] == (160, 5120)
    assert shapes[one + "A_log"] == (5120, 16)
    assert shapes["model.layers.7.self_attn.k_proj.weight"] == (2560, 128)
    assert sum(1 for k in shapes if k.endswith("mamba.A_log")) == 26
    assert family.attention_shape(PUBLISHED) == {
        "heads": 20, "kv_heads": 1, "head_dim": 128}
    assert round(family.matmul_params(PUBLISHED) / 1e9, 2) == 3.03


def test_what_the_family_does_not_serve_is_refused():
    with pytest.raises(NotImplementedError, match="num_experts"):
        JambaConfig(**{**TINY, "num_experts": 16})
    with pytest.raises(ValueError, match="attn_layer_offset"):
        JambaConfig(**{**TINY, "attn_layer_offset": 4})
    model = _model(num_hidden_layers=1)
    with pytest.raises(NotImplementedError, match="not wired"):
        model(paddle.to_tensor(_ids((1, 4))),
              labels=paddle.to_tensor(_ids((1, 4))))


def test_cache_entries_say_what_each_layer_keeps(tiny):
    caches = tiny.init_cache(3, 40)
    assert [isinstance(c, RecurrentState) for c in caches] \
        == [True, True, False, True, True]
    assert caches[0].conv.shape == (3, 3, 128)
    assert caches[0].ssm.shape == (3, 16, 128)
    assert caches[2][0].shape == (3, 1, 40, 12)       # one key/value head
    assert len(CACHE_KINDS) == 5                      # no sixth kind


def test_the_state_is_float32_under_a_bfloat16_model():
    model = _model(dtype="bfloat16")
    caches = model.init_cache(2, 16)
    assert caches[0].conv.dtype == jnp.bfloat16
    assert caches[0].ssm.dtype == jnp.float32
    assert caches[2][0].dtype == jnp.bfloat16
    params, prefill, _ = make_decoder_fns(model)
    logits, caches = prefill(params, jnp.asarray(_ids((2, 8))), caches,
                             jnp.int32(0))
    assert logits.dtype == jnp.bfloat16
    assert [c.dtype for c in caches[0]] == [jnp.bfloat16, jnp.float32]
    assert float(jnp.abs(caches[0][1]).max()) > 0


# ---- against the reference ---------------------------------------------------

def test_mixer_full_sequence_equals_the_reference(tiny):
    mixer = tiny.model.layers[0].mamba
    assert isinstance(mixer, Mamba1Mixer)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 20, HIDDEN)),
                    jnp.float32)
    got = np.asarray(mixer(paddle.to_tensor(h)).data)
    w = {k: p.data for k, p in mixer.named_parameters()}
    for row in range(2):
        want = ref._mamba1(h[row], lambda name: w[name], _ref_config(tiny))
        np.testing.assert_allclose(got[row], np.asarray(want), rtol=2e-4,
                                   atol=2e-5)


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


S = 11
SPLITS = [(S,), (1,) * S, (4, 7), (3, 1, 1, 6)]


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


@pytest.mark.parametrize("fault,least", [
    ({"mamba_carry": False}, 1e-2), ({"mamba_inner_norms": False}, 1e-2)],
    ids=["state wiped every step", "dt_layernorm and its two mates dropped"])
def test_the_comparison_sees_the_mechanism(tiny, fault, least):
    """A reference whose state starts from zero at every position, or that
    leaves the three inner norms out, is far outside the 1e-4 the model
    holds against the sound one."""
    ids = jnp.asarray(_ids((2, 16)))
    got = np.asarray(tiny(paddle.to_tensor(np.asarray(ids))).data)
    faulty = np.asarray(ref.logits(_weights(tiny), ids,
                                   _ref_config(tiny, **fault)))
    assert np.abs(got - faulty)[:, 4:].max() > least


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


LENGTHS = (5, 24, 17, 9, 30, 40)


def _prompts(seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in LENGTHS]


@pytest.fixture(scope="module")
def streams(tiny):
    """(prompts, the reference's greedy continuation of each and its
    log-probabilities): one full forward of prompt + continuation."""
    out = []
    for p in _prompts():
        ids = np.asarray(generate(tiny, p[None], max_new_tokens=10).data)
        lg = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids),
                                   _ref_config(tiny)))[0]
        assert np.array_equal(ids[0, len(p):], lg[len(p) - 1:-1].argmax(-1))
        lp = lg - np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(
            -1, keepdims=True)) - lg.max(-1, keepdims=True)
        out.append((ids[0, len(p):],
                    lp[np.arange(len(p) - 1, ids.shape[1] - 1),
                       ids[0, len(p):]]))
    return _prompts(), out


@pytest.mark.parametrize("slots", [3, 40], ids=["unpacked", "packed"])
def test_engine_streams_are_the_references(tiny, streams, slots):
    """Eight requests through three slots (every slot is used again by a
    later request: stale state must not leak into it; prefill chunks ride
    beside decode rows) and through 40 (the packed step): every stream is
    the reference's greedy continuation and every log-probability its own,
    and the counters say what ran."""
    prompts, want = streams
    eng = _engine(tiny, slots)
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
    # Mamba-1's kernel walks every row a column at a time: no row is
    # counted as advanced in matrix form
    assert snap["recurrent_rows_matrix"] == 0 < snap["recurrent_rows_loop"]
    # each array at its own width: the conv's columns in the model's type
    # (float32 here), the state in float32
    assert snap["recurrent_state_bytes"] == eng.pool.recurrent_state_bytes \
        == 4 * slots * (3 * 128 + 16 * 128) * 4
    assert eng.pool.layer_kinds == ["recurrent", "recurrent", "paged",
                                    "recurrent", "recurrent"]
    assert eng.enable_prefix_cache is False and eng.prefix_cache is None
    if slots == 3:
        assert eng.pool.stats["reuses"] >= len(prompts) - slots


def test_the_gauge_counts_the_state_in_float32_under_a_bfloat16_model():
    model = _model(dtype="bfloat16")
    eng = _engine(model, 4)
    assert eng.pool.recurrent_state_bytes \
        == 4 * 4 * (3 * 128 * 2 + 16 * 128 * 4)
    assert f"pdtpu_llm_recurrent_state_bytes " \
           f"{eng.pool.recurrent_state_bytes}" in eng.metrics.render()
    from paddle_tpu.serving import metrics
    assert metrics.RECURRENT_STATE_BYTES == eng.pool.recurrent_state_bytes
    assert eng.pool.kv_bytes() == {
        "full": 2 * 4 * (8 * 8 + 16) * 12 * 2, "window": 0}


def test_a_wiped_state_changes_the_streams(tiny, streams):
    """With the recurrent layers' state wiped between steps (the conv's
    columns and the K/V slabs left alone) the streams are no longer the
    reference's."""
    prompts, want = streams

    def wipe(eng):
        eng.pool.slabs = [
            (a, jnp.zeros_like(b)) if kind == "recurrent" else (a, b)
            for (a, b), kind in zip(eng.pool.slabs, eng.pool.layer_kinds)]

    eng = _engine(tiny, 3)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng, after_pump=wipe)
    got = [np.asarray(h.result(timeout=5)) for h in handles]
    assert sum(not np.array_equal(g, w) for g, (w, _) in zip(got, want)) \
        >= len(prompts) // 2


@pytest.mark.parametrize("what,kw,match", [
    ("host tier", dict(host_kv_bytes=1 << 20), "host_kv_bytes"),
    ("draft model", dict(draft=True), "draft_model with")])
def test_what_a_recurrent_state_rules_out_is_refused(tiny, what, kw, match):
    draft = tiny if kw.pop("draft", False) else None
    with pytest.raises(ValueError, match=match):
        serving.LLMEngine(
            tiny, serving.LLMEngineConfig(
                num_slots=2, block_len=8, n_blocks=8,
                enable_prefix_cache=False, **kw),
            clock=serving.SimClock(), draft_model=draft)


def test_the_new_modules_stay_off_the_packages_import_path():
    import subprocess
    import sys
    code = ("import sys, paddle_tpu, paddle_tpu.serving, paddle_tpu.models\n"
            "bad = [m for m in ('paddle_tpu.models.jamba', "
            "'paddle_tpu.models.hybrid', 'paddle_tpu.nn.layer.mamba', "
            "'paddle_tpu.ops.ssm') if m in sys.modules]\n"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
