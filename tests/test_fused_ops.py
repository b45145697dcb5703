"""Layer norm and Adam against hand-written references (reference:
operators/layer_norm_op.cu, operators/optimizers/adam_op.h): the plain XLA
math the train cells compile.
"""
import jax
import jax.numpy as jnp
import numpy as np


def _ref_ln(x, w, b, eps):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    out = (h - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(
        x.dtype)


def test_functional_layer_norm_uses_same_math():
    # value parity with the explicit reference
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(4, 8, 256).astype(np.float32))
    w = paddle.to_tensor(rng.randn(256).astype(np.float32))
    b = paddle.to_tensor(rng.randn(256).astype(np.float32))
    y = F.layer_norm(x, 256, weight=w, bias=b)
    want = _ref_ln(x.data, w.data, b.data, 1e-5)
    np.testing.assert_allclose(np.asarray(y.data), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _ref_adam(p, g, m1, m2, lr, b1p, b2p, wd, b1, b2, eps, decoupled):
    g = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if not decoupled:
        g = g + wd * p32
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * g * g
    upd = (m1n / (1 - b1p)) / (jnp.sqrt(m2n / (1 - b2p)) + eps)
    if decoupled:
        upd = upd + wd * p32
    return (p32 - lr * upd).astype(p.dtype), m1n, m2n


def test_adam_optimizer_matches_unfused_rule():
    # two steps through the optimizer (Adam._rule -> _adam_math) against
    # the hand-rolled reference sequence
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim

    rng = np.random.RandomState(6)
    w0 = rng.randn(64, 32).astype(np.float32)
    lin = paddle.nn.Linear(64, 32)
    lin.weight.set_value(w0)
    opt = optim.Adam(learning_rate=1e-2, parameters=lin.parameters())
    x = paddle.to_tensor(rng.randn(8, 64).astype(np.float32))
    for _ in range(2):
        loss = paddle.mean(lin(x) ** 2)
        loss.backward()
        opt.step()
        opt.clear_grad()

    p = jnp.asarray(w0)
    bias = jnp.zeros((32,), jnp.float32)
    m1 = jnp.zeros_like(p)
    m2 = jnp.zeros_like(p)
    bm1 = jnp.zeros_like(bias)
    bm2 = jnp.zeros_like(bias)
    b1p = b2p = 1.0
    xv = jnp.asarray(x.numpy())
    for _ in range(2):
        def loss_fn(w, b):
            return jnp.mean((xv @ w + b) ** 2)
        gw, gb = jax.grad(loss_fn, argnums=(0, 1))(p, bias)
        b1p, b2p = b1p * 0.9, b2p * 0.999
        p, m1, m2 = _ref_adam(p, gw, m1, m2, 1e-2, b1p, b2p, 0.0, 0.9,
                              0.999, 1e-8, False)
        bias, bm1, bm2 = _ref_adam(bias, gb, bm1, bm2, 1e-2, b1p, b2p, 0.0,
                                   0.9, 0.999, 1e-8, False)
    np.testing.assert_allclose(np.asarray(lin.weight.numpy()),
                               np.asarray(p), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(lin.bias.numpy()),
                               np.asarray(bias), atol=1e-5, rtol=1e-5)
