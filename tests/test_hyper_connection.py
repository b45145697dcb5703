"""`ops/hyper_connection.py`: the two halves of a manifold-constrained
hyper-connection, each in its plain `jax.numpy` form (the CPU's path and the
oracle) and as a Mosaic kernel (`hc_pre`, `hc_post`; interpreted here), and
`nn/layer/hyper_connection.py`'s parameters. Tolerances: the kernel and the
plain form run the same float32 arithmetic and differ in the order of the
sums in `x phi` (a product over n C columns, taken 512 at a time by the
kernel): 1e-5 on coefficients of size 1; `u` and `X'` in float32 likewise,
in bf16 one rounding of the result on each side (a bf16 ulp of the largest
value, 2^-6 at 4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.layer.hyper_connection import HyperConnection
from paddle_tpu.ops import hyper_connection as hc
from paddle_tpu.ops import pallas_mode

KW = dict(n=4, iters=20, eps=1e-6, clamp=30.0, norm_eps=1e-6)


def _operands(rows, C, dtype=jnp.float32, n=4, seed=0):
    """Streams and a sublayer's result N(0, 1); `phi` such that the
    coefficients' logits have deviation 1 at a gain of 1."""
    rng = np.random.default_rng(seed)
    k = n * n + 2 * n
    return (jnp.asarray(rng.normal(0, 1, (rows, n * C)), dtype),
            jnp.asarray(rng.normal(0, (n * C) ** -0.5, (n * C, k)),
                        jnp.float32),
            jnp.asarray(rng.normal(0, 1, (k,)), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, (3,)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (rows, C)), dtype))


def _f32(a):
    return np.asarray(a, np.float32)


# ---- the plain form is the equations ----

def test_h_res_is_doubly_stochastic_after_twenty_passes_not_after_one():
    x, phi, bias, alpha, _ = _operands(64, 128)
    # logits of deviation 0.4: twenty passes converge to float32's rounding
    bias, alpha = 0.3 * bias, 0.3 * alpha
    _, _, res = hc.coefficients(x, phi, bias, alpha, **KW)
    res = np.asarray(res)
    assert res.shape == (64, 4, 4) and (res > 0).all()
    assert np.abs(res.sum(1) - 1).max() < 1e-5      # every column
    assert np.abs(res.sum(2) - 1).max() < 1e-5      # every row
    _, _, once = hc.coefficients(x, phi, bias, alpha, **{**KW, "iters": 1})
    once = np.asarray(once)
    assert np.abs(once.sum(2) - 1).max() < 1e-5     # the last half-pass
    assert np.abs(once.sum(1) - 1).max() > 1e-2


def test_the_plain_form_against_numpy_a_token_at_a_time():
    x, phi, bias, alpha, y = _operands(5, 128, seed=3)
    u, post, res = hc._pre_plain(x, phi, bias, alpha, **KW)
    out = hc._post_plain(x, y, post, res)
    X, P, b, a, Y = (np.asarray(v, np.float64)
                     for v in (x, phi, bias, alpha, y))
    for t in range(5):
        v = X[t] / np.sqrt(np.mean(X[t] ** 2) + 1e-6)
        h = v @ P
        pre = 1 / (1 + np.exp(-(a[0] * h[:4] + b[:4])))
        po = 2 / (1 + np.exp(-(a[1] * h[4:8] + b[4:8])))
        m = np.exp(np.clip(a[2] * h[8:] + b[8:], -30, 30)).reshape(4, 4)
        for _ in range(20):
            m = m / (m.sum(0, keepdims=True) + 1e-6)
            m = m / (m.sum(1, keepdims=True) + 1e-6)
        streams = X[t].reshape(4, 128)
        np.testing.assert_allclose(_f32(u[t]), pre @ streams, atol=1e-5)
        np.testing.assert_allclose(_f32(post[t]), po, atol=1e-5)
        np.testing.assert_allclose(_f32(res[t]), m, atol=1e-5)
        np.testing.assert_allclose(
            _f32(out[t]).reshape(4, 128),
            m @ streams + po[:, None] * Y[t][None], atol=1e-5)


def test_the_clamp_bounds_the_logits_before_exp():
    x, phi, bias, alpha, _ = _operands(8, 128, seed=4)
    huge = bias.at[8:].set(jnp.linspace(-80.0, 80.0, 16))
    _, _, res = hc.coefficients(x, phi * 0, huge, alpha, **KW)
    _, _, at_30 = hc.coefficients(x, phi * 0, jnp.clip(huge, -30, 30), alpha,
                                  **KW)
    assert np.isfinite(np.asarray(res)).all()
    np.testing.assert_array_equal(np.asarray(res), np.asarray(at_30))


# ---- the kernels against the plain form ----

@pytest.mark.parametrize("rows,C,dtype", [
    (256, 128, jnp.float32), (48, 256, jnp.float32), (200, 128, jnp.bfloat16),
    (37, 512, jnp.bfloat16), (1, 128, jnp.float32)],
    ids=["two grid steps", "an unpacked step's rows", "a ragged last block",
         "fewer rows than a block", "one row"])
def test_kernels_equal_the_plain_form(rows, C, dtype):
    x, phi, bias, alpha, y = _operands(rows, C, dtype, seed=rows)
    u0, post0, res0 = hc._pre_plain(x, phi, bias, alpha, **KW)
    u1, post1, res1 = hc.pre_kernel(x, phi, bias, alpha, **KW)
    assert u1.shape == (rows, C) and u1.dtype == dtype
    assert post1.dtype == res1.dtype == jnp.float32
    np.testing.assert_allclose(_f32(post1), _f32(post0), atol=1e-5)
    np.testing.assert_allclose(_f32(res1), _f32(res0), atol=1e-5)
    ulp = 1e-5 if dtype == jnp.float32 else 2.0 ** -6
    np.testing.assert_allclose(_f32(u1), _f32(u0), atol=ulp)
    out0 = hc._post_plain(x, y, post0, res0)
    out1 = hc.post_kernel(x, y, post0, res0)
    assert out1.shape == x.shape and out1.dtype == dtype
    np.testing.assert_allclose(_f32(out1), _f32(out0), atol=2 * ulp)


def test_bf16_streams_keep_float32_coefficients():
    """The coefficients of bf16 streams are those of the same values in
    float32: nothing of the mixing is computed in the streams' type."""
    x, phi, bias, alpha, _ = _operands(128, 128, jnp.bfloat16, seed=7)
    for form in (hc._pre_plain, hc.pre_kernel):
        _, post, res = form(x, phi, bias, alpha, **KW)
        _, post32, res32 = form(x.astype(jnp.float32), phi, bias, alpha, **KW)
        np.testing.assert_array_equal(np.asarray(post), np.asarray(post32))
        np.testing.assert_array_equal(np.asarray(res), np.asarray(res32))
    # parameters held in bf16 (the benchmark's weights) are read as float32
    low = [a.astype(jnp.bfloat16) for a in (phi, bias, alpha)]
    _, post, res = hc.pre_kernel(x, *low, **KW)
    _, post0, res0 = hc._pre_plain(
        x, *(a.astype(jnp.float32) for a in low), **KW)
    np.testing.assert_allclose(_f32(res), _f32(res0), atol=1e-5)
    np.testing.assert_allclose(_f32(post), _f32(post0), atol=1e-5)


def test_hc_post_writes_over_the_streams_and_one_body_serves_every_site():
    x, phi, bias, alpha, y = _operands(128, 128, seed=9)

    def three_connections(x, y):
        for _ in range(3):
            u, post, res = hc.pre_kernel(x, phi, bias, alpha, **KW)
            x = hc.post_kernel(x, y + u, post, res)
        return x

    jaxpr = jax.make_jaxpr(three_connections)(x, y)
    calls = []

    def visit(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        visit(sub)
    visit(jaxpr.jaxpr)
    names = [str(e.params.get("name") or e.params["name_and_src_info"])
             for e in calls]
    assert sum("hc_pre" in n for n in names) == 3
    assert sum("hc_post" in n for n in names) == 3
    for e in calls:
        aliases = tuple(e.params["input_output_aliases"])
        name = str(e.params.get("name") or e.params["name_and_src_info"])
        assert aliases == (((0, 0),) if "hc_post" in name else ())
    text = jax.jit(three_connections).lower(x, y).as_text()
    assert text.count("func.func private @_pre_call") == 1
    assert text.count("func.func private @_post_call") == 1


# ---- which form a trace takes ----

def test_the_platform_and_the_shapes_choose_the_form(monkeypatch):
    x, phi, bias, alpha, y = _operands(6, 128, seed=11)
    x3, y3 = x.reshape(2, 3, -1), y.reshape(2, 3, -1)
    pallas_mode.KERNEL_TRACES.clear()
    u, post, res = hc.hc_pre(x3, phi, bias, alpha, n=4)
    out = hc.hc_post(x3, y3, post, res)
    assert u.shape == (2, 3, 128) and post.shape == (2, 3, 4)
    assert res.shape == (2, 3, 4, 4) and out.shape == x3.shape
    assert pallas_mode.KERNEL_TRACES == {("hc_pre", "reference"): 1,
                                         ("hc_post", "reference"): 1}
    monkeypatch.setattr(pallas_mode, "platform", lambda: "tpu")
    pallas_mode.KERNEL_TRACES.clear()
    text = str(jax.make_jaxpr(
        lambda a, b: hc.hc_post(a, b, *hc.hc_pre(a, phi, bias, alpha,
                                                 n=4)[1:]))(x3, y3))
    assert text.count("pallas_call") == 2
    assert pallas_mode.KERNEL_TRACES == {("hc_pre", "mosaic"): 1,
                                         ("hc_post", "mosaic"): 1}
    assert any(k == "hc_pre" and dict(t)["passes"] == 20
               for k, t in pallas_mode.KERNEL_TILINGS)
    # a stream that is not whole lane registers keeps the plain form
    narrow = _operands(6, 48, seed=12)
    pallas_mode.KERNEL_TRACES.clear()
    text = str(jax.make_jaxpr(lambda a: hc.hc_pre(
        a, narrow[1], narrow[2], narrow[3], n=4)[0])(narrow[0]))
    assert "pallas_call" not in text
    assert pallas_mode.KERNEL_TRACES == {("hc_pre", "reference"): 1}


# ---- one connection's parameters ----

def test_a_connection_as_constructed_is_close_to_the_plain_residual():
    paddle.seed(0)
    layer = HyperConnection(64, 4)
    assert [(k, tuple(p.shape)) for k, p in layer.named_parameters()] \
        == [("phi", (256, 24)), ("bias", (24,)), ("alpha", (3,))]
    x = paddle.to_tensor(np.random.default_rng(1).normal(
        0, 1, (2, 5, 256)).astype("float32"))
    with paddle.no_grad():
        u, (post, res) = layer.pre(x)
        y = paddle.to_tensor(np.ones((2, 5, 64), "float32"))
        out = layer.post(x, y, (post, res)).numpy()
    np.testing.assert_allclose(res.numpy(), np.broadcast_to(
        np.eye(4, dtype=np.float32), (2, 5, 4, 4)), atol=2e-3)
    np.testing.assert_allclose(post.numpy(), 1.0, atol=2e-2)
    np.testing.assert_allclose(out, x.numpy() + 1.0, atol=2e-2)
    assert u.shape == [2, 5, 64]
    with pytest.raises(ValueError, match="n >= 2"):
        HyperConnection(64, 1)


def test_the_plain_form_is_differentiable():
    """The CPU path is `jax.numpy`: a connection trains there."""
    x, phi, bias, alpha, y = _operands(4, 128, seed=13)

    def loss(phi, bias, alpha):
        u, post, res = hc.hc_pre(x, phi, bias, alpha, n=4)
        return jnp.sum(hc.hc_post(x, y + u, post, res) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(phi, bias, alpha)
    assert all(np.isfinite(np.asarray(g)).all() and np.abs(g).max() > 0
               for g in grads)
