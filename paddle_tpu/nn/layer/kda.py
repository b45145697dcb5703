"""Kimi Delta Attention mixer (KDA: Kimi Linear, arXiv:2510.26692, as
flash-linear-attention's `KimiDeltaAttention` layer runs it; written from
memory): a linear-attention layer in the place of attention in a decoder
block. H heads of width d, u the block's normed input:

    [q~ | k~ | v] = silu(causal depthwise conv of width K over u W_qkv)
    q = q~ / |q~|_2 * d^-1/2,  k = k~ / |k~|_2        per head, float32
    [f | z | b] = u W_fgb                             widths d, d, H
    g    = -exp(A_log[h]) * softplus(f W_f + dt_bias) [H, d] log-decay a
                                                      key channel (<= 0)
    beta = 2 sigmoid(b)                               [H]; 1 sigmoid(b)
                                                      without `neg_eigval`
    S <- Diag(exp(g)) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    out  = [RMSNorm_d(o) * sigmoid(z W_g + b_g)] W_o  a norm a head (one
                                                      weight [d]), rank-d
                                                      decay and gate

The conv has no bias; `f` and the gate go through a bottleneck of d
(`kda_use_full_proj` false). What a sequence carries from one call to the
next is fixed in size: the last K - 1 columns of `u W_qkv` in the model's
type and the state `S`, `[d, H * d]` float32 whatever the model's type
(`ops/kda.py` says how it is laid out). `forward` is
`Mamba2Mixer.forward`'s contract; with `pack` nothing is unpacked: the conv
and the recurrence take a step's packed block as it stands, each token a
column of its slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.tensor import apply
from ...ops.kda import kda_update, kda_update_rows
from ...ops.ssm import causal_conv_tokens, causal_conv_update
from .. import initializer as I
from .common import Linear
from .layers import Layer

F32 = jnp.float32
L2_EPS = 1e-6


class KimiDeltaAttention(Layer):
    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 rms_norm_eps=1e-5, neg_eigval=True):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.conv_kernel, self.eps = conv_kernel, rms_norm_eps
        self.neg_eigval = neg_eigval
        self.d_inner = inner = num_heads * head_dim
        normal = I.Normal(0.0, 0.02)
        self.qkv_proj = Linear(hidden_size, 3 * inner, weight_attr=normal,
                               bias_attr=False)
        self.conv_weight = self.create_parameter(
            [3 * inner, conv_kernel], default_initializer=normal)
        # the bottlenecks of the decay and of the output gate, and beta
        self.fgb_proj = Linear(hidden_size, 2 * head_dim + num_heads,
                               weight_attr=normal, bias_attr=False)
        self.f_up_proj = Linear(head_dim, inner, weight_attr=normal,
                                bias_attr=False)
        self.g_up_proj = Linear(head_dim, inner, weight_attr=normal)
        self.dt_bias = self.create_parameter([inner], is_bias=True)
        # the decay's rate a head is exp(A_log): A_log = 0 forgets with
        # rate softplus(.)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=I.Constant(0.0))
        self.o_norm_weight = self.create_parameter(
            [head_dim], default_initializer=I.Constant(1.0))
        self.o_proj = Linear(inner, hidden_size, weight_attr=normal,
                             bias_attr=False)
        for p in (self.g_up_proj.bias, self.dt_bias, self.A_log,
                  self.o_norm_weight):
            p.partition_spec = P(None)

    def init_state(self, batch_size: int, dtype):
        """(conv `[batch, K - 1, 3 H d]` in `dtype`, state `[batch, d,
        H * d]` float32), zeros: the recurrence's state is held in float32
        between steps whatever the model's type."""
        return (jnp.zeros((batch_size, self.conv_kernel - 1,
                           3 * self.d_inner), dtype),
                jnp.zeros((batch_size, self.head_dim, self.d_inner), F32))

    def forward(self, hidden, cache=None, pos=None, adv=None, pack=None):
        """As `Mamba2Mixer.forward`: hidden `[B, T, hidden]`, or the packed
        `[tokens, 1, hidden]` of a serving step with `pack`. Returns out,
        or (out, (conv, state)) with a cache."""
        qkv = self.qkv_proj(hidden)
        fgb = self.fgb_proj(hidden)
        inner, heads, d = self.d_inner, self.num_heads, self.head_dim
        eps, beta_scale = self.eps, 2.0 if self.neg_eigval else 1.0

        def unit(x):                # [..., H * d] -> each head of norm 1
            x = x.astype(F32).reshape(*x.shape[:-1], heads, d)
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

        def mix(proj, low, conv_w, f_w, g_w, g_b, dt_bias, a_log, norm_w,
                conv_state, state, pos_):
            rows = conv_state.shape[0]
            if pack is not None:
                pos_ = pack.slot_pos
            fresh = None
            if pos_ is not None:
                fresh = jnp.broadcast_to(jnp.asarray(pos_) == 0, (rows,))
            no_bias = jnp.zeros((3 * inner,), F32)
            with jax.named_scope("kda_conv"):
                if pack is None:
                    c, new_conv = causal_conv_update(
                        proj, conv_state, conv_w, no_bias, adv, fresh)
                else:
                    start = pack.last + 1 - adv
                    c, new_conv = causal_conv_tokens(
                        proj[:, 0], conv_state, conv_w, no_bias, pack.slot,
                        pack.col, start, adv, fresh)
                    low = low[:, 0]
                c = c.astype(proj.dtype)
            with jax.named_scope("kda_gates"):
                q = (unit(c[..., :inner]) * d ** -0.5).reshape(
                    *c.shape[:-1], inner)
                k = unit(c[..., inner:2 * inner]).reshape(
                    *c.shape[:-1], inner)
                v = c[..., 2 * inner:]
                f = (low[..., :d] @ f_w).astype(F32) + dt_bias.astype(F32)
                rate = jnp.repeat(jnp.exp(a_log.astype(F32)), d)
                g = -rate * jax.nn.softplus(f)
                beta = beta_scale * jax.nn.sigmoid(
                    low[..., 2 * d:].astype(F32))
            with jax.named_scope("kda_update"):
                if pack is None:
                    o, new_state = kda_update_rows(q, k, v, g, beta, state,
                                                   adv, fresh)
                else:
                    o, new_state = kda_update(
                        q, k, v, g, beta, state, start, adv, fresh,
                        columns=pack.dst.shape[1])
            with jax.named_scope("kda_out"):
                o = o.reshape(*o.shape[:-1], heads, d)
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, -1, keepdims=True) + eps) \
                    * norm_w.astype(F32)
                z = (low[..., d:2 * d] @ g_w).astype(F32) + g_b.astype(F32)
                y = o.reshape(z.shape) * jax.nn.sigmoid(z)
                if pack is not None:
                    y = y[:, None]
            return y.astype(proj.dtype), new_conv, new_state

        if cache is None:
            conv_state, state = self.init_state(hidden.shape[0],
                                                hidden.dtype)
        else:
            conv_state, state = cache
        y, new_conv, new_state = apply(
            mix, qkv, fgb, self.conv_weight, self.f_up_proj.weight,
            self.g_up_proj.weight, self.g_up_proj.bias, self.dt_bias,
            self.A_log, self.o_norm_weight, conv_state, state, pos)
        out = self.o_proj(y)
        if cache is None:
            return out
        return out, (new_conv, new_state)
