"""granite-4.0-h-small's block on the serving path: the Mamba-2 recurrence
(`ops/ssm.py`) against a per-head loop and its kernel against its scan, the
mixer against the benchmark's plain reference, the carried-state form
against the full sequence at every split, the model and `generate()`
against the reference, `LLMEngine` (packed and unpacked step, mixed prefill
and decode rows, a slot used by a second request) against `generate()`, an
expert layer that holds a share, and each thing a recurrent state rules
out refused by name. CPU, float32, tiny widths: hidden 64, mamba /
attention / mamba, 2 state-space heads of 64 with 16 channels, 8 experts
of width 32 (2 per token), a shared expert of width 48.

Initial values: the constructor's are flat (`A_log` 0, no `dt_bias`), and
under them, as under the benchmark's seeding rule, the `D` skip is nearly
all of the mixer's `y`. The tests draw `A_log` from U(-2, -0.5) (slow
decay), `dt_bias` from U(0, 1.5), the conv's taps from N(0, 0.6) and matrices
from N(0, 0.15):
`test_the_recurrence_carries_the_mixer` checks that what comes through the
state is then at least the skip's size, so a broken carry moves every
comparison below.
"""
import dataclasses
import logging
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler, serving
from paddle_tpu.models.generation import (RecurrentState, generate,
                                          make_decoder_fns)
from paddle_tpu.models.granitemoehybrid import (GraniteMoeHybridConfig,
                                                GraniteMoeHybridForCausalLM)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nn.layer import moe
from paddle_tpu.nn.layer.mamba import Mamba2Mixer
from paddle_tpu.ops import pallas_mode, ssm
from paddle_tpu.profiler import SPAN_SERVE_DISPATCH
from paddle_tpu.serving.llm.kv_pool import (RecurrentStateError,
                                            SlotPagedKVPool)

from benchmark.reference import granitemoehybrid as ref

VOCAB, HIDDEN, EXPERTS = 128, 64, 8
TINY = dict(vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=32,
            shared_intermediate_size=48, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"],
            num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=EXPERTS, num_experts_per_tok=2,
            mamba_n_heads=2, mamba_d_head=64, mamba_d_state=16,
            attention_multiplier=0.0625, embedding_multiplier=2.0,
            logits_scaling=2.0, max_position_embeddings=128)


def _seed_weights(model, seed=3):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            value = rng.uniform(-2.0, -0.5, p.shape)
        elif name.endswith("dt_bias"):
            value = rng.uniform(0.0, 1.5, p.shape)
        elif name.endswith("conv_bias"):
            value = rng.normal(0.0, 0.1, p.shape)
        elif name.endswith("conv_weight"):
            value = rng.normal(0.0, 0.6, p.shape)
        elif name.endswith("embed_tokens.weight"):
            # small beside the mixers' output, or the tied head echoes the
            # last token whatever the layers do
            value = rng.normal(0.0, 0.05, p.shape)
        elif len(p.shape) >= 2:
            value = rng.normal(0.0, 0.15, p.shape)
        else:
            continue                      # norm scales and D stay 1
        p.data = jnp.asarray(value, p.data.dtype)
    return model


def _model(seed=0, **over):
    paddle.seed(seed)
    model = GraniteMoeHybridForCausalLM(
        GraniteMoeHybridConfig(**{**TINY, **over}))
    model.eval()
    return _seed_weights(model)


def _llama_tiny(layers):
    """A model with no recurrent layer, for what must stay as it was."""
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=32,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128))


@pytest.fixture(scope="module")
def tiny():
    return _model()


def _ref_config(model):
    cfg = dataclasses.asdict(model.config)
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str, bool))} \
        | {"head_dim": model.config.head_dim}


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _ids(shape, seed=1):
    return np.random.default_rng(seed).integers(
        1, VOCAB, shape).astype(np.int32)


def _ssm_inputs(rows, T, H=4, P=64, N=16, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(x=f(rows, T, H * P).astype(dtype),
                dt=jax.nn.softplus(f(rows, T, H)), a=-jnp.exp(f(H)),
                b=f(rows, T, N).astype(dtype), c=f(rows, T, N).astype(dtype),
                state=f(rows, N, H * P).astype(dtype))


# ---- the recurrence ---------------------------------------------------------

def test_ssm_update_is_the_per_head_recurrence():
    """The scan against the equations written per row, head and column in
    numpy (float64), Hugging Face's [H, P, N] state layout; a dead row
    keeps its state, a fresh one starts from zero."""
    rows, T, H, P, N = 3, 5, 4, 64, 16
    k = _ssm_inputs(rows, T, H, P, N)
    adv, fresh = np.array([5, 2, 0]), np.array([0, 1, 0])
    y, s = ssm.ssm_update(k["x"], k["dt"], k["a"], k["b"], k["c"],
                          k["state"], jnp.asarray(adv), jnp.asarray(fresh))
    S = np.asarray(k["state"], np.float64).reshape(rows, N, H, P) \
        .transpose(0, 2, 3, 1)
    S[fresh != 0] = 0.0
    x = np.asarray(k["x"], np.float64).reshape(rows, T, H, P)
    dt, a = np.asarray(k["dt"], np.float64), np.asarray(k["a"], np.float64)
    b, c = np.asarray(k["b"], np.float64), np.asarray(k["c"], np.float64)
    for r in range(rows):
        for t in range(adv[r]):
            for h in range(H):
                S[r, h] = np.exp(dt[r, t, h] * a[h]) * S[r, h] \
                    + dt[r, t, h] * np.outer(x[r, t, h], b[r, t])
                np.testing.assert_allclose(
                    np.asarray(y[r, t]).reshape(H, P)[h], S[r, h] @ c[r, t],
                    rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(s), S.transpose(0, 3, 1, 2).reshape(rows, N, H * P),
        rtol=2e-5, atol=2e-5)
    assert np.array_equal(np.asarray(s[2]), np.asarray(k["state"][2]))


@pytest.mark.parametrize("T,lane_block,dtype", [
    (5, 128, jnp.float32), (16, 256, jnp.float32), (1, 128, jnp.float32),
    (70, 128, jnp.float32), (16, 128, jnp.bfloat16),
    (64, 128, jnp.float32), (64, 256, jnp.bfloat16), (70, 256, jnp.float32),
    (16, 256, jnp.bfloat16), (3, 128, jnp.float32)])
def test_ssm_kernel_equals_its_scan(T, lane_block, dtype, monkeypatch):
    """The Mosaic kernel, interpreted, against the scan it stands beside:
    the same float32 arithmetic, column after column in a row of few live
    columns and all of a row's columns at once from `MATRIX_COLUMNS` on, so
    the same numbers. One call holds rows on both sides of that threshold
    (`adv` 0, 1, one under it, at it, every column), rows that start from
    zero in either body, more than one lane block a row, and a sequence
    longer than one call's columns."""
    m = ssm.MATRIX_COLUMNS
    k = _ssm_inputs(7, T, dtype=dtype)
    adv = jnp.asarray([min(n, T) for n in (T, 3, 0, 1, m - 1, m, T)],
                      jnp.int32)
    fresh = jnp.asarray([0, 1, 0, 0, 0, 1, 1], jnp.int32)
    args = (k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"], adv, fresh)
    y0, s0 = ssm.ssm_update(*args, impl="scan")
    monkeypatch.setattr(ssm, "LANE_BLOCK", lane_block)
    pallas_mode.KERNEL_TILINGS.clear()
    y1, s1 = ssm.ssm_update(*args, impl="pallas")
    # a call none of whose rows can reach the threshold traces the loop alone
    assert all(dict(t)["matrix_from"] == (m if T >= m else 0)
               for _, t in pallas_mode.KERNEL_TILINGS)
    live = (np.arange(T)[None] < np.asarray(adv)[:, None])[..., None]
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.where(live, np.asarray(y1, np.float32), 0),
        np.where(live, np.asarray(y0, np.float32), 0), **tol)
    np.testing.assert_allclose(np.asarray(s1, np.float32),
                               np.asarray(s0, np.float32), **tol)
    assert s1.dtype == k["state"].dtype and y1.dtype == k["x"].dtype


@pytest.mark.parametrize("T", [16, 64])
def test_a_head_that_forgets_everything_in_a_column_stays_finite(T):
    """Decays are `exp` of differences of log-decays, never a ratio of two
    `exp`s: a head that decays by e^-20 a column (its sixteen columns
    underflow float32: a ratio would read 0 / 0) beside one that hardly
    decays at all (whose long sums must keep their low bits) gives finite
    `y` and state, the scan's; what a dead column holds, NaN included,
    reaches nothing."""
    k = _ssm_inputs(3, T, H=4)
    k["a"] = jnp.asarray([-20.0, -1e-3, -1.0, -5.0], jnp.float32)
    k["dt"] = k["dt"].at[..., 0].set(1.0)
    adv = jnp.asarray([T, T - 3, T], jnp.int32)
    fresh = jnp.asarray([0, 0, 1], jnp.int32)
    dead = np.arange(T)[None, :, None] >= np.asarray(adv)[:, None, None]
    y0, s0 = ssm.ssm_update(k["x"], k["dt"], k["a"], k["b"], k["c"],
                            k["state"], adv, fresh, impl="scan")
    x, b, c = (jnp.where(dead, jnp.nan, k[name]) for name in "xbc")
    y1, s1 = ssm.ssm_update(x, k["dt"], k["a"], b, c, k["state"], adv,
                            fresh, impl="pallas")
    y0, y1 = (np.where(dead, 0, np.asarray(y)) for y in (y0, y1))
    assert np.isfinite(y1).all() and np.isfinite(np.asarray(s1)).all()
    # the slow head's y reaches 100-150 out of sums that cancel: either
    # implementation is 1.4e-5 to 3.0e-5 from the float64 recurrence
    tol = dict(rtol=1e-5, atol=4e-7 * float(np.abs(y0).max()))
    np.testing.assert_allclose(y1, y0, **tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), **tol)
    # the first head's state is its last live column's input alone
    assert float(jnp.abs(s1[0, :, :64]).max()) > 0


def _calls(jaxpr, name):
    """Equations of `jaxpr`, nested ones included, of primitive `name`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for value in eqn.params.values():
            sub = getattr(value, "jaxpr", value)
            if hasattr(sub, "eqns") and eqn.primitive.name != "pallas_call":
                found += _calls(sub, name)
    return found


@pytest.mark.parametrize("T", [2, 16])
def test_ssm_kernel_writes_the_new_state_into_the_states_buffer(T):
    """A grid step reads and writes its own state tile alone, so the
    kernel aliases the state to the new state: inside a step that is
    donated its pool nothing is copied round the call (without it XLA
    copies the whole state behind every layer: +6.8 ms a step in
    `granite-4.0-h-small.serve-decode`, my chip run, PR 35). One
    `pallas_call` named `ssm_update`, with the matrix body (T = 16) and
    without it."""
    k = _ssm_inputs(3, T, dtype=jnp.bfloat16)
    adv, fresh = jnp.asarray([T, 2, 0]), jnp.asarray([0, 1, 0])
    jaxpr = jax.make_jaxpr(lambda *a: ssm.ssm_update(*a, impl="pallas"))(
        k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"], adv, fresh)
    call, = _calls(jaxpr.jaxpr, "pallas_call")
    assert call.params["name"] == ssm.KERNEL == "ssm_update"
    (src, dst), = call.params["input_output_aliases"]
    assert call.invars[src].aval.shape == k["state"].shape
    assert call.outvars[dst].aval.shape == k["state"].shape


def test_the_layers_of_a_step_share_one_ssm_kernel_body():
    """The `pallas_call` sits under one module-level `jax.jit` whose
    integers are static (`_ssm_call`), so a step's nine layers trace and
    lower the kernel, both of its bodies, once (PR 32's lesson: a body a
    call site is traced and lowered in every process, before the compile
    cache can be asked)."""
    k = _ssm_inputs(3, 16, dtype=jnp.bfloat16)
    adv, fresh = jnp.asarray([16, 2, 0]), jnp.asarray([0, 1, 0])

    def three_layers(x, dt, a, b, c, state):
        for _ in range(3):
            x, state = ssm.ssm_update(x, dt, a, b, c, state, adv, fresh,
                                      impl="pallas")
        return x, state
    jaxpr = jax.make_jaxpr(three_layers)(
        k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"])
    sites = [e for e in jaxpr.jaxpr.eqns
             if e.params.get("name") == "_ssm_call"]
    assert len(sites) == 3
    assert len({id(e.params["jaxpr"]) for e in sites}) == 1


def test_ssm_update_refuses_what_it_cannot_tile():
    k = _ssm_inputs(2, 4, H=4, P=48)          # 48 lanes a head: no whole
    with pytest.raises(ValueError, match="whole heads"):     # register
        ssm.ssm_update(k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"],
                       impl="pallas")
    with pytest.raises(ValueError, match="impl must be"):
        ssm.ssm_update(k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"],
                       impl="xla")
    with pytest.raises(ValueError, match="ssm_update: x"):
        ssm.ssm_update(k["x"], k["dt"][:, :2], k["a"], k["b"], k["c"],
                       k["state"])


def test_causal_conv_carries_its_last_inputs():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 6, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    zero = jnp.zeros((2, 3, 8), jnp.float32)
    full, carried = ssm.causal_conv_update(u, zero, w, bias)
    padded = np.pad(np.asarray(u), ((0, 0), (3, 0), (0, 0)))
    want = np.asarray(bias) + sum(
        np.asarray(w)[:, j] * padded[:, j:j + 6] for j in range(4))
    np.testing.assert_allclose(np.asarray(full), jax.nn.silu(want),
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(carried), np.asarray(u[:, 3:]))
    # two calls, the second on the first's carry; row 1 takes 2 of its 4
    # columns in the first call
    a, carry = ssm.causal_conv_update(u[:, :4], zero, w, bias,
                                      adv=jnp.asarray([4, 2]))
    assert np.array_equal(np.asarray(carry[1, 1:]), np.asarray(u[1, :2]))
    b, _ = ssm.causal_conv_update(u[:1, 4:], carry[:1], w, bias)
    np.testing.assert_allclose(np.asarray(b), np.asarray(full[:1, 4:]),
                               rtol=1e-5, atol=1e-6)
    # a fresh row forgets what the slot held
    c, _ = ssm.causal_conv_update(u[:, :4], carry + 5.0, w, bias,
                                  fresh=jnp.asarray([1, 1]))
    np.testing.assert_allclose(np.asarray(c), np.asarray(full[:, :4]),
                               rtol=1e-5, atol=1e-6)


# ---- the mixer --------------------------------------------------------------

def _mixer(tiny):
    return tiny.model.layers[0].mamba


def _mixer_leaf(tiny):
    w = _weights(tiny)
    return lambda name: w["model.layers.0.mamba." + name].astype(jnp.float32)


def _hidden(shape, seed=2):
    h = np.random.default_rng(seed).normal(size=shape)
    return jnp.asarray(h / np.sqrt((h * h).mean(-1, keepdims=True)),
                       jnp.float32)


def test_the_recurrence_carries_the_mixer(tiny):
    """How the initial values were checked: with layer 0's weights and
    unit-RMS inputs, the part of `y` that comes through the state is at
    least as large (RMS) as the `D` skip; under the constructor's own
    values it is a small part, which is why the tests do not use them."""
    def shares(model):
        mixer, h = model.model.layers[0].mamba, _hidden((2, 24, HIDDEN))
        proj = h @ mixer.in_proj.weight.data
        d, n = mixer.d_inner, mixer.state_size
        conv, ssm_state = mixer.init_state(2, jnp.float32)
        xbc, _ = ssm.causal_conv_update(
            proj[..., d:d + mixer.conv_dim], conv, mixer.conv_weight.data,
            mixer.conv_bias.data)
        dt = jax.nn.softplus(proj[..., d + mixer.conv_dim:]
                             + mixer.dt_bias.data)
        y, _ = ssm.ssm_update(xbc[..., :d], dt, -jnp.exp(mixer.A_log.data),
                              xbc[..., d:d + n], xbc[..., d + n:], ssm_state)
        skip = jnp.repeat(mixer.D.data, mixer.head_dim) * xbc[..., :d]
        rms = lambda v: float(jnp.sqrt(jnp.mean(v * v)))   # noqa: E731
        return rms(y), rms(skip)
    carried, skip = shares(tiny)
    assert carried >= skip > 0
    paddle.seed(0)
    flat = GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(**TINY))
    carried, skip = shares(flat)
    assert carried < 0.5 * skip


def test_mixer_full_sequence_equals_the_reference(tiny):
    h = _hidden((2, 20, HIDDEN))
    got = _mixer(tiny)(paddle.to_tensor(h)).data
    want = ref._mamba2(h, _mixer_leaf(tiny), _ref_config(tiny))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


S = 9
SPLITS = [(k, S - k) for k in range(1, S)] + [
    (1,) * S, (2, 3, 4), (4, 1, 1, 3), (3, 3, 3), (8, 1), (1, 1, 7)]


@pytest.mark.parametrize("chunks", SPLITS, ids=lambda c: "-".join(map(str, c)))
def test_carried_state_equals_the_full_sequence_at_every_split(tiny, chunks):
    """The sequence fed in chunks, each in a step `width` columns wide of
    which `adv` = the chunk are live (adv < width included: the dead
    columns must not advance the state), against one full pass."""
    mixer, width = _mixer(tiny), 8
    h = _hidden((2, S, HIDDEN))
    want = np.asarray(mixer(paddle.to_tensor(h)).data)
    cache = mixer.init_state(2, jnp.float32)
    off, out = 0, []
    for n in chunks:
        step = jnp.zeros((2, max(width, n), HIDDEN), jnp.float32) \
            .at[:, :n].set(h[:, off:off + n]) + 7.0 * (
                jnp.arange(max(width, n))[None, :, None] >= n)   # garbage
        y, cache = mixer(paddle.to_tensor(step), cache=cache,
                         pos=jnp.full((2,), off, jnp.int32),
                         adv=jnp.full((2,), n, jnp.int32))
        cache = tuple(c.data for c in cache)
        out.append(np.asarray(y.data)[:, :n])
        off += n
    np.testing.assert_allclose(np.concatenate(out, axis=1), want,
                               rtol=2e-4, atol=2e-5)


def test_mixer_refuses_more_than_one_group():
    with pytest.raises(NotImplementedError, match="groups of B and C"):
        Mamba2Mixer(64, 2, 64, 16, n_groups=2)


# ---- the model --------------------------------------------------------------

@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "share"])
def test_model_equals_the_reference(held):
    """Full forward against the plain reference: every multiplier, the
    attention without rotary embedding at its own scale, the router's
    top-k softmax, the shared expert, the tied head. A share is held as
    experts 0..count-1 of the reference's weights, so the reference's
    share is the program's only for first = 0: (2, 4) runs against the
    reference given the same four experts' weights and a router whose
    columns are rolled to match."""
    model = _model(experts_held=held)
    ids = _ids((2, 16))
    got = np.asarray(model(paddle.to_tensor(ids)).data)
    weights = _weights(model)
    if held is not None:
        for k in [k for k in weights if k.endswith("router_weight")]:
            weights[k] = jnp.roll(weights[k], -held[0], axis=1)
    want = np.asarray(ref.logits(weights, jnp.asarray(ids),
                                 _ref_config(model)))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)
    assert np.abs(want).max() > 0.05            # not a flat distribution


def test_generate_equals_the_reference_logits(tiny):
    """Prefill, then decoding through the cache token by token, against
    one full pass of the reference over prompt + continuation."""
    prompt = _ids((2, 11))
    out = np.asarray(generate(tiny, prompt, max_new_tokens=9).data)
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(out),
                                 _ref_config(tiny)))
    # the greedy continuation is the reference's argmax at every step
    assert np.array_equal(out[:, 11:], want[:, 10:-1].argmax(-1))
    params, prefill, decode = make_decoder_fns(tiny)
    caches = tiny.init_cache(2, 24)
    logits, caches = prefill(params, jnp.asarray(out[:, :11]), caches,
                             jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits), want[:, :11],
                               rtol=5e-4, atol=5e-5)
    for t in range(11, 20):
        step, caches = decode(params, jnp.asarray(out[:, t]), jnp.int32(t),
                              caches)
        np.testing.assert_allclose(np.asarray(step), want[:, t],
                                   rtol=5e-4, atol=5e-5)


def test_cache_entries_say_what_each_layer_keeps(tiny):
    caches = tiny.init_cache(3, 40)
    assert [isinstance(c, RecurrentState) for c in caches] \
        == [True, False, True]
    assert caches[0].conv.shape == (3, 3, 128 + 2 * 16)
    assert caches[0].ssm.shape == (3, 16, 2 * 64)
    assert caches[1][0].shape == (3, 2, 40, 16)
    with pytest.raises(NotImplementedError, match="not wired"):
        tiny(paddle.to_tensor(_ids((1, 4))), labels=paddle.to_tensor(
            _ids((1, 4))))
    with pytest.raises(ValueError, match="layer_types"):
        GraniteMoeHybridConfig(**{**TINY, "layer_types": ["mamba", "rnn"]})
    published = GraniteMoeHybridConfig()
    assert published.layer_types.count("attention") == 4 \
        and published.layer_types[5] == "attention"


def test_a_bfloat16_model_stays_bfloat16():
    """The scalar multipliers sit inside the traced functions: a Python
    scalar times a bfloat16 `Tensor` would make the residual stream (and
    the experts' rows, which Mosaic then refuses) float32."""
    model = _model(dtype="bfloat16")
    params, prefill, _ = make_decoder_fns(model)
    logits, caches = prefill(params, jnp.asarray(_ids((2, 8))),
                             model.init_cache(2, 16), jnp.int32(0))
    assert logits.dtype == jnp.bfloat16
    assert {a.dtype for pair in caches for a in pair} == {
        jnp.dtype(jnp.bfloat16)}
    assert model(paddle.to_tensor(_ids((1, 4)))).data.dtype == jnp.bfloat16


def test_llama_attention_is_unchanged_by_default():
    cfg = LlamaConfig()
    assert cfg.rope is True and cfg.attention_multiplier is None


# ---- the expert share -------------------------------------------------------

def _expert_layer(held=None, seed=4):
    paddle.seed(seed)
    layer = moe.DroplessMoE(HIDDEN, 32, EXPERTS, 2, norm_topk_prob=True,
                            held=held)
    return layer


def test_the_shares_add_up():
    """Four shares of two experts each, given the whole layer's weights
    for their experts: their routed parts, plus the shared expert once,
    are the uncut layer; each share counts its own assignments only."""
    model = _model()
    layer = model.model.layers[0]
    whole, shared = layer.block_sparse_moe, layer.shared_mlp
    h = paddle.to_tensor(_hidden((3, 7, HIDDEN)))
    with moe.collect_expert_counts() as counts:
        want = whole(h).data + shared(h).data
    parts = jnp.zeros_like(want)
    with moe.collect_expert_counts() as held_counts:
        for first in range(0, EXPERTS, 2):
            share = _expert_layer(held=(first, 2))
            assert share.num_held == 2 and share.w_gate.shape[0] == 2
            share.router_weight.data = whole.router_weight.data
            for name in ("w_gate", "w_up", "w_down"):
                getattr(share, name).data = \
                    getattr(whole, name).data[first:first + 2]
            parts = parts + share(h).data
    np.testing.assert_allclose(np.asarray(parts + shared(h).data),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.concatenate([np.asarray(c)
                                          for c in held_counts]),
                          np.asarray(counts[0]))
    assert int(counts[0].sum()) == 3 * 7 * 2
    # and the reference's uncut layer says the same
    w = _weights(model)
    p = "model.layers.0.block_sparse_moe."
    flat = h.data.reshape(-1, HIDDEN)
    uncut = ref._experts(flat, w[p + "router_weight"], w[p + "w_gate"],
                         w[p + "w_up"], w[p + "w_down"], 2)
    np.testing.assert_allclose(
        np.asarray(whole(h).data).reshape(-1, HIDDEN), np.asarray(uncut),
        rtol=2e-4, atol=2e-5)


def test_a_whole_layer_is_the_layer_it_was():
    """`held=None` is the default and holds every expert; holding every
    expert as a share gives the same bits."""
    whole, share = _expert_layer(), _expert_layer(held=(0, EXPERTS))
    assert whole.held is None and whole.num_held == EXPERTS
    h = paddle.to_tensor(_hidden((2, 5, HIDDEN)))
    live = jnp.asarray([[True] * 5, [True, True, False, False, False]])
    assert np.array_equal(np.asarray(whole(h, live=live).data),
                          np.asarray(share(h, live=live).data))
    with pytest.raises(ValueError, match="held"):
        moe.DroplessMoE(HIDDEN, 32, EXPERTS, 2, held=(6, 4))


def test_a_position_that_is_not_live_reaches_no_held_expert():
    share = _expert_layer(held=(0, 4))
    h = paddle.to_tensor(_hidden((1, 6, HIDDEN)))
    live = jnp.asarray([[True, False, True, False, False, True]])
    with moe.collect_expert_counts() as counts:
        out = np.asarray(share(h, live=live).data)
    assert not out[0, [1, 3, 4]].any()
    with moe.collect_expert_counts() as all_counts:
        share(h)
    assert int(counts[0].sum()) <= int(all_counts[0].sum()) <= 6 * 2
    assert counts[0].shape == (4,)


# ---- LLMEngine --------------------------------------------------------------

def _engine(model, slots, draft=None, **cfg_kw):
    kw = dict(num_slots=slots, block_len=8, n_blocks=8, max_queue_depth=128)
    kw.update(cfg_kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**kw),
                             clock=serving.SimClock(), draft_model=draft)


def _drain(eng, after_pump=None):
    steps = 0
    while eng.has_work():
        eng.pump()
        if after_pump is not None:
            after_pump(eng)
        steps += 1
        assert steps < 2000, "engine failed to converge"


def _prompts(lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n).astype(np.int32) for n in lengths]


def _generate(model, prompt, max_new):
    return np.asarray(generate(model, prompt[None], max_new_tokens=max_new
                               ).data)[0, len(prompt):]


LENGTHS = (5, 24, 17, 9, 30, 3, 40, 16)


@pytest.fixture(scope="module")
def streams(tiny):
    prompts = _prompts(LENGTHS)
    return prompts, [_generate(tiny, p, 10) for p in prompts]


@pytest.mark.parametrize("slots", [3, 40], ids=["unpacked", "packed"])
def test_engine_streams_are_generates(tiny, streams, slots):
    """Eight requests through three slots (every slot is used again by a
    later request, prefill chunks ride beside decode rows) and through 40
    (the packed step: 640 positions, 512 computed): every stream is the
    bits one-shot `generate()` gives, and the counters say what ran."""
    prompts, want = streams
    eng = _engine(tiny, slots)
    assert (eng.step_tokens < slots * 16) == (slots == 40)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng)
    for h, w in zip(handles, want):
        assert np.array_equal(np.asarray(h.result(timeout=5)), w)
    snap = eng.metrics.snapshot()
    assert snap["recurrent_rows_started"] == len(prompts)
    assert snap["recurrent_state_bytes"] == eng.pool.recurrent_state_bytes \
        == 2 * slots * (3 * 160 + 16 * 128) * 4
    assert eng.pool.layer_kinds == ["recurrent", "paged", "recurrent"]
    if slots == 3:
        assert eng.pool.stats["reuses"] >= len(prompts) - slots
    totals = eng.moe_expert_tokens()
    assert totals.shape == (3, EXPERTS)
    live = sum(LENGTHS) + len(prompts) * 9       # the last token is not fed
    assert (totals.sum(1) == live * 2).all()
    assert eng.metrics.snapshot()["moe_assignments"] == totals.sum()


def test_rows_are_counted_by_the_body_that_advances_them(tiny, streams):
    """The recurrence's kernel advances a row of `MATRIX_COLUMNS` live
    columns or more in matrix form and walks a shorter one column by
    column; the host knows each row's `adv` when it builds the step, so it
    counts them: a prompt's whole chunks and its longer tails on one side,
    its short tails and every decode row on the other, together the rows
    the steps advanced."""
    prompts, want = streams
    eng = _engine(tiny, 3)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng)
    assert all(np.array_equal(np.asarray(h.result(timeout=5)), w)
               for h, w in zip(handles, want))
    m = ssm.MATRIX_COLUMNS
    assert eng._matrix_columns == m == tiny.model.layers[0].mamba \
        .matrix_columns
    tails = [n % 16 for n in LENGTHS]
    matrix = sum(n // 16 for n in LENGTHS) + sum(t >= m for t in tails)
    loop = sum(0 < t < m for t in tails) + 9 * len(prompts)
    snap = eng.metrics.snapshot()
    assert (snap["recurrent_rows_matrix"], snap["recurrent_rows_loop"]) \
        == (matrix, loop) == (12, 73)
    assert snap["rows_discarded"] == 0
    text = eng.metrics.render()
    assert f"pdtpu_llm_recurrent_rows_matrix_total {matrix}" in text
    assert f"pdtpu_llm_recurrent_rows_loop_total {loop}" in text


def test_a_zeroed_state_changes_the_streams(tiny, streams):
    """The comparison above can see the mechanism: with the recurrent
    layers' state wiped between steps (the K/V slabs left alone) the
    streams are no longer `generate()`'s."""
    prompts, want = streams

    def wipe(eng):
        eng.pool.slabs = [
            (jnp.zeros_like(a), jnp.zeros_like(b)) if kind == "recurrent"
            else (a, b)
            for (a, b), kind in zip(eng.pool.slabs, eng.pool.layer_kinds)]

    eng = _engine(tiny, 3)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drain(eng, after_pump=wipe)
    got = [np.asarray(h.result(timeout=5)) for h in handles]
    assert sum(not np.array_equal(g, w) for g, w in zip(got, want)) \
        >= len(prompts) // 2


def test_engine_holds_a_share_of_the_experts():
    """A model whose expert layers hold experts 0..3 of 8: the streams are
    `generate()`'s, the table is [layers, held], `moe_assignments` is its
    sum as of the fetch (only the device knows which assignments fell on
    held experts), and the process-wide `ROUTED_TOKENS` holds the live
    positions each layer routed, held or not."""
    model = _model(experts_held=(0, 4))
    prompts = _prompts((6, 20, 11))
    eng = _engine(model, 4)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drain(eng)
    for h, p in zip(handles, prompts):
        assert np.array_equal(np.asarray(h.result(timeout=5)),
                              _generate(model, p, 6))
    moe.EXPERT_TOKENS.clear()
    moe.ROUTED_TOKENS.clear()
    assert eng.metrics.snapshot()["moe_assignments"] == 0    # no fetch yet
    totals = eng.moe_expert_tokens()
    live = sum(len(p) for p in prompts) + 3 * 5
    assert dict(moe.ROUTED_TOKENS) == {0: live, 1: live, 2: live}
    assert sum(moe.EXPERT_TOKENS.values()) == totals.sum()
    eng.moe_expert_tokens()                       # nothing new: nothing added
    assert dict(moe.ROUTED_TOKENS) == {0: live, 1: live, 2: live}
    assert totals.shape == (3, 4) and (totals.sum(1) < live * 2).all() \
        and (totals.sum(1) > 0).all()
    assert eng.metrics.snapshot()["moe_assignments"] == totals.sum()
    assert set(moe.EXPERT_TOKENS) <= {(layer, e) for layer in range(3)
                                      for e in range(4)}
    text = eng.metrics.render()
    assert 'pdtpu_llm_moe_expert_tokens_total{expert="3",layer="2"}' in text \
        or 'pdtpu_llm_moe_expert_tokens_total{layer="2",expert="3"}' in text
    assert 'expert="4"' not in text


def test_metrics_and_span_name_the_recurrent_state(tiny):
    profiler.start_profiler()
    try:
        eng = _engine(tiny, 4)
        eng.submit(_prompts((20,))[0], max_new_tokens=3)
        eng.pump()
        eng.submit(_prompts((4,))[0], max_new_tokens=3)
        _drain(eng)
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    # the first pass launches two steps, the second ahead of the first,
    # before the second request is there
    assert [s["recurrent_rows"] for s in spans[:3]] == [1, 1, 2]
    assert [s["in_flight"] for s in spans[:3]] == [0, 1, 1]
    text = eng.metrics.render()
    assert f"pdtpu_llm_recurrent_state_bytes " \
           f"{eng.pool.recurrent_state_bytes}" in text
    assert "pdtpu_llm_recurrent_rows_started_total 2" in text
    from paddle_tpu.serving import metrics
    assert metrics.RECURRENT_STATE_BYTES == eng.pool.recurrent_state_bytes
    # a model without recurrent layers has neither family nor the argument
    llama = _llama_tiny(1)
    dense = _engine(llama, 2)
    assert "recurrent" not in dense.metrics.render()
    assert dense.metrics.snapshot()["recurrent_state_bytes"] is None
    assert dense.pool.recurrent is False and dense.enable_prefix_cache


# ---- what a recurrent state rules out, refused by name ----------------------

def test_prefix_sharing_is_switched_off_and_says_so(tiny, caplog):
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving.llm"):
        eng = _engine(tiny, 2)
    said = [r for r in caplog.records
            if "enable_prefix_cache is switched off" in r.getMessage()]
    assert len(said) == 1
    assert eng.config.enable_prefix_cache is True       # the default stays
    assert eng.enable_prefix_cache is False and eng.prefix_cache is None
    assert serving.LLMEngineConfig().enable_prefix_cache is True
    # two requests that share a prefix both prefill all of it
    prompt = _prompts((24,))[0]
    first = eng.submit(prompt, max_new_tokens=4)
    _drain(eng)
    second = eng.submit(prompt, max_new_tokens=4)
    _drain(eng)
    assert eng.prefill_tokens == 48
    assert np.array_equal(np.asarray(first.result(timeout=5)),
                          np.asarray(second.result(timeout=5)))
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.serving.llm"):
        caplog.clear()
        _engine(tiny, 2, enable_prefix_cache=False)
    assert not caplog.records


@pytest.mark.parametrize("which", ["target", "draft"])
def test_a_draft_model_is_refused(tiny, which):
    llama = _llama_tiny(1)
    target, draft = (tiny, llama) if which == "target" else (llama, tiny)
    with pytest.raises(ValueError, match="draft_model with"):
        _engine(target, 2, draft=draft, enable_prefix_cache=False)


def test_the_host_tier_and_imported_pages_are_refused(tiny):
    with pytest.raises(ValueError, match="host_kv_bytes"):
        _engine(tiny, 2, host_kv_bytes=1 << 20)
    eng = _engine(tiny, 2)
    with pytest.raises(ValueError, match="kv_row with a model"):
        eng.submit(_prompts((12,))[0], max_new_tokens=2,
                   kv_row={"block_len": 8, "length": 8, "layers": []})


@pytest.mark.parametrize("what", ["rewind_length", "export_rows",
                                  "export_page", "import_page", "cow_copy",
                                  "attach_blocks", "register_cached"])
def test_the_pool_refuses_to_rebuild_a_recurrent_row(tiny, what):
    pool = SlotPagedKVPool(tiny.init_cache, 2, 8, 4, pad_tokens=8)
    slot = pool.allocate(16)
    pool.set_length(slot, 12)
    calls = {
        "rewind_length": lambda: pool.rewind_length(slot, 4),
        "export_rows": lambda: pool.export_rows([slot]),
        "export_page": lambda: pool.export_page(0),
        "import_page": lambda: pool.import_page(slot, 0, []),
        "cow_copy": lambda: pool.cow_copy(5, slot),
        "attach_blocks": lambda: pool.attach_blocks(slot, [4]),
        "register_cached": lambda: pool.register_cached(4),
    }
    with pytest.raises(RecurrentStateError, match=what):
        calls[what]()
    pool.rewind_length(slot, 12)              # not a rewind: allowed
    pool.free(slot)
    assert pool.check_balance()


def test_a_live_stream_cannot_be_exported(tiny):
    eng = _engine(tiny, 2)
    h = eng.submit(_prompts((6,))[0], max_new_tokens=8, rid="r1")
    for _ in range(3):
        eng.pump()
    with pytest.raises(RecurrentStateError, match="export_rows"):
        eng.export_stream("r1")
    _drain(eng)
    assert len(h.result(timeout=5)) == 8


def test_a_paged_pool_is_the_pool_it_was():
    llama = _llama_tiny(2)
    pool = SlotPagedKVPool(llama.init_cache, 2, 8, 4)
    assert pool.layer_kinds == ["paged", "paged"] and not pool.recurrent
    assert pool.recurrent_state_bytes == 0
    slot = pool.allocate(16)
    pool.set_length(slot, 12)
    pool.rewind_length(slot, 4)
    assert pool.export_rows([slot])["rows"][slot]["length"] == 4


# ---- the import path --------------------------------------------------------

def test_the_new_modules_stay_off_the_packages_import_path():
    code = ("import sys, paddle_tpu, paddle_tpu.serving\n"
            "bad = [m for m in ('paddle_tpu.models.granitemoehybrid', "
            "'paddle_tpu.nn.layer.mamba', 'paddle_tpu.ops.ssm') "
            "if m in sys.modules]\n"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
