"""One manifold-constrained hyper-connection: the parameters that carry a
decoder's n residual streams round one sublayer (`ops/hyper_connection.py`
has the equations and the two ops, `hc_pre` and `hc_post`).

    u, carry = connection.pre(X)        X [..., n * hidden]; u [..., hidden]
    X = connection.post(X, F(norm(u)), carry)

`phi [n hidden, n^2 + 2 n]`, `bias [n^2 + 2 n]` and `alpha [3]` (the gains
on the pre, post and res logits) are float32 whatever the model's type: they
decide a softmax-like mixing of the streams and are 344 thousand numbers
beside a layer's hundreds of millions. As constructed a connection is close
to the plain residual path on every stream: `alpha` 0.01 (the mHC paper's),
H_post = 1, and a `bias` that makes H_res the identity to 3e-4.

Imported by `models/deepseek.py` alone, and only for a configuration with
more than one stream: no package `__init__` names this module.
"""
from __future__ import annotations

import jax
import numpy as np

from ...core.tensor import apply
from ...ops.hyper_connection import hc_post, hc_pre
from .. import initializer as I
from .layers import Layer


class HyperConnection(Layer):
    def __init__(self, hidden_size: int, n: int, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, res_clamp: float = 30.0,
                 norm_eps: float = 1e-6):
        super().__init__()
        if n < 2:
            raise ValueError(f"a hyper-connection mixes n >= 2 streams, "
                             f"got {n}")
        self.n = n
        self.options = dict(n=n, iters=int(sinkhorn_iters), eps=float(eps),
                            clamp=float(res_clamp), norm_eps=float(norm_eps))
        K = n * n + 2 * n
        bias = np.zeros(K, np.float32)
        bias[2 * n:] = (-8.0 * (1.0 - np.eye(n))).reshape(-1)
        self.phi = self.create_parameter(
            [n * hidden_size, K], dtype="float32",
            default_initializer=I.Normal(0.0, 0.02))
        self.bias = self.create_parameter(
            [K], dtype="float32", default_initializer=I.Assign(bias))
        self.alpha = self.create_parameter(
            [3], dtype="float32", default_initializer=I.Constant(0.01))

    def pre(self, streams):
        """streams `[..., n hidden]` -> (the sublayer's input `[...,
        hidden]`, what `post` needs of the coefficients)."""
        options = self.options

        def f(x, phi, bias, alpha):
            with jax.named_scope("hyper_connection"):
                return hc_pre(x, phi, bias, alpha, **options)

        u, h_post, h_res = apply(f, streams, self.phi, self.bias, self.alpha)
        return u, (h_post, h_res)

    def post(self, streams, out, carry):
        """The streams after the sublayer's result `out [..., hidden]`."""
        def f(x, y, h_post, h_res):
            with jax.named_scope("hyper_connection"):
                return hc_post(x, y, h_post, h_res)

        return apply(f, streams, out, *carry)
