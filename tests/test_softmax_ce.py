"""Fused chunked lm-head+CE parity tests (ops/softmax_ce.py; reference:
softmax_with_cross_entropy + c_softmax_with_cross_entropy_op.cu).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.softmax_ce import fused_linear_cross_entropy


def _dense_ce(h, w, labels, ignore_index=-100):
    logits = (h @ w).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(
        logits, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    loss = lse - tl
    return jnp.where(labels == ignore_index, 0.0, loss)


@pytest.mark.parametrize("V,n_chunks", [(1000, 8), (1024, 4), (777, 8),
                                        (50, 8)])
def test_fused_ce_forward_parity(V, n_chunks):
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(32, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64, V).astype(np.float32) * 0.05)
    y = jnp.asarray(rng.randint(0, V, (32,)).astype(np.int32))
    got = fused_linear_cross_entropy(h, w, y, -100, n_chunks)
    want = _dense_ce(h, w, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


def test_fused_ce_grad_parity():
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    w = jnp.asarray(rng.randn(32, 500).astype(np.float32) * 0.05)
    y = jnp.asarray(rng.randint(0, 500, (16,)).astype(np.int32))

    def f_fused(h, w):
        return jnp.mean(fused_linear_cross_entropy(h, w, y, -100, 8))

    def f_dense(h, w):
        return jnp.mean(_dense_ce(h, w, y))

    gh1, gw1 = jax.grad(f_fused, argnums=(0, 1))(h, w)
    gh2, gw2 = jax.grad(f_dense, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gh1), np.asarray(gh2), atol=1e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2), atol=1e-5,
                               rtol=1e-4)


def test_fused_ce_ignore_index():
    rng = np.random.RandomState(2)
    h = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 100).astype(np.float32) * 0.1)
    y = jnp.asarray(np.array([3, -100, 7, -100, 1, 2, 3, 4], np.int32))
    loss = fused_linear_cross_entropy(h, w, y, -100, 4)
    arr = np.asarray(loss)
    assert arr[1] == 0.0 and arr[3] == 0.0
    assert (arr[[0, 2, 4, 5, 6, 7]] > 0).all()
    # ignored tokens contribute zero gradient
    gh = jax.grad(lambda h: jnp.sum(
        fused_linear_cross_entropy(h, w, y, -100, 4)))(h)
    gh = np.asarray(gh)
    assert np.abs(gh[1]).max() == 0.0 and np.abs(gh[3]).max() == 0.0
    assert np.abs(gh[0]).max() > 0.0


def test_fused_ce_bf16_compute():
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(16, 32).astype(np.float32)).astype(
        jnp.bfloat16)
    w = (jnp.asarray(rng.randn(32, 300).astype(np.float32)) * 0.05).astype(
        jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 300, (16,)).astype(np.int32))
    got = fused_linear_cross_entropy(h, w, y, -100, 8)
    assert got.dtype == jnp.float32
    want = _dense_ce(h.astype(jnp.float32), w.astype(jnp.float32), y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.05,
                               rtol=0.05)
    gh, gw = jax.grad(
        lambda h, w: jnp.mean(fused_linear_cross_entropy(h, w, y, -100, 8)),
        argnums=(0, 1))(h, w)
    assert gh.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16


def test_gpt_model_loss_matches_dense_path(monkeypatch):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM.from_preset("gpt2-tiny")
    rng = np.random.RandomState(4)
    ids = paddle.to_tensor(rng.randint(
        0, model.config.vocab_size, (2, 32)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(
        0, model.config.vocab_size, (2, 32)).astype(np.int32))
    loss_fused = float(model(ids, labels).item())
    monkeypatch.setattr(GPTForCausalLM, "_can_fuse_lm_ce",
                        staticmethod(lambda: False))
    loss_dense = float(model(ids, labels).item())
    np.testing.assert_allclose(loss_fused, loss_dense, rtol=2e-4)
