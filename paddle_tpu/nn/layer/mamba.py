"""Mamba-2 mixer (Dao & Gu 2024, as Hugging Face's `modeling_bamba.py` /
`modeling_granitemoehybrid.py` run it; written from memory): a state-space
layer in the place of attention in a decoder block (Mamba-1's: below).

    [z | u | dt] = h W_in                    widths d_inner, d_conv, heads
    u'  = silu(conv_bias + causal depthwise conv of width K over u)
    [x | B | C] = u'                         d_inner -> [H, P]; N; N
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    out = RMSNorm(y * silu(z)) W_out         (gate first, then the norm)

with d_inner = expand x hidden = H heads x P, one group of B and C (the
layer refuses more), d_conv = d_inner + 2 N. What a sequence carries from
one call to the next is fixed in size: the last K - 1 columns of `u` and
the state `S` (`ops/ssm.py` says how both are laid out). `forward(h)` runs
a whole sequence from zero state; `forward(h, cache=(conv, ssm), ...)`
runs one step's columns on the carried state and returns the new one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.tensor import apply
from ...ops import ssm
from ...ops.ssm import (causal_conv_tokens, causal_conv_update,
                        selective_scan, selective_scan_rows, ssm_update)
from .. import initializer as I
from .common import Linear
from .layers import Layer


class Mamba2Mixer(Layer):
    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 conv_kernel=4, n_groups=1, rms_norm_eps=1e-5):
        super().__init__()
        if n_groups != 1:
            raise NotImplementedError(
                f"Mamba2Mixer: {n_groups} groups of B and C; the recurrence "
                "(ops/ssm.py) shares one B and C among all heads")
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.conv_kernel = state_size, conv_kernel
        self.d_inner = num_heads * head_dim
        self.conv_dim = self.d_inner + 2 * state_size
        self.eps = rms_norm_eps
        normal = I.Normal(0.0, 0.02)
        self.in_proj = Linear(hidden_size,
                              self.d_inner + self.conv_dim + num_heads,
                              weight_attr=normal, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [self.conv_dim, conv_kernel], default_initializer=normal)
        self.conv_bias = self.create_parameter([self.conv_dim], is_bias=True)
        self.dt_bias = self.create_parameter([num_heads], is_bias=True)
        # A = -exp(A_log): A_log = 0 forgets with rate dt
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [num_heads], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [self.d_inner], default_initializer=I.Constant(1.0))
        self.out_proj = Linear(self.d_inner, hidden_size,
                               weight_attr=normal, bias_attr=False)
        for p in (self.conv_bias, self.dt_bias, self.A_log, self.D,
                  self.norm_weight):
            p.partition_spec = P(None)

    @property
    def matrix_columns(self) -> int:
        """Live columns from which the recurrence's kernel advances a row
        in matrix form (a serving engine counts its rows by it)."""
        return ssm.MATRIX_COLUMNS

    def init_state(self, batch_size: int, dtype):
        """(conv `[batch, K - 1, d_conv]`, ssm `[batch, N, H * P]`), zeros."""
        return (jnp.zeros((batch_size, self.conv_kernel - 1, self.conv_dim),
                          dtype),
                jnp.zeros((batch_size, self.state_size, self.d_inner),
                          dtype))

    def forward(self, hidden, cache=None, pos=None, adv=None, pack=None):
        """hidden `[B, T, hidden]`, or the packed `[tokens, 1, hidden]` of
        a serving step with `pack` (`ops.attention.TokenPack`). `cache`
        (conv, ssm) rows are the batch's, or the slots'; `pos` (scalar or
        `[rows]`) is where each row's columns start: a row at 0 starts
        from zero state. `adv [rows]`: how many of a row's columns are
        live (None: all). Returns out, or (out, (conv, ssm)) with a cache."""
        zxbcdt = self.in_proj(hidden)
        d_inner, n_state, heads = self.d_inner, self.state_size, \
            self.num_heads
        conv_dim, eps = self.conv_dim, self.eps

        def mix(proj, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
                conv_state, ssm_state, pos_):
            z, rest = proj[..., :d_inner], proj[..., d_inner:]
            if pack is not None:
                # the conv and the recurrence need a token's slot and its
                # chunk mates: the slots' own layout, as attention
                rest, pos_ = pack.unpack(rest), pack.slot_pos
            u, dt = rest[..., :conv_dim], rest[..., conv_dim:]
            rows, T = u.shape[0], u.shape[1]
            fresh = None
            if pos_ is not None:
                fresh = jnp.broadcast_to(jnp.asarray(pos_) == 0, (rows,))
            xbc, new_conv = causal_conv_update(u, conv_state, conv_w, conv_b,
                                               adv, fresh)
            xbc = xbc.astype(proj.dtype)
            x = xbc[..., :d_inner]
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + dt_bias.astype(jnp.float32))
            y, new_ssm = ssm_update(
                x, dt, -jnp.exp(a_log.astype(jnp.float32)),
                xbc[..., d_inner:d_inner + n_state],
                xbc[..., d_inner + n_state:], ssm_state, adv, fresh)
            skip = jnp.repeat(d_skip.astype(jnp.float32), d_inner // heads)
            y = y.astype(jnp.float32) + skip * x.astype(jnp.float32)
            if pack is not None:
                y = pack.pack(y)
            y = y * jax.nn.silu(z.astype(jnp.float32))
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
            return ((y * norm_w.astype(jnp.float32)).astype(proj.dtype),
                    new_conv, new_ssm)

        if cache is None:
            conv_state, ssm_state = self.init_state(
                hidden.shape[0], hidden.dtype)
        else:
            conv_state, ssm_state = cache
        y, new_conv, new_ssm = apply(
            mix, zxbcdt, self.conv_weight, self.conv_bias, self.dt_bias,
            self.A_log, self.D, self.norm_weight, conv_state, ssm_state,
            pos)
        out = self.out_proj(y)
        if cache is None:
            return out
        return out, (new_conv, new_ssm)


class Mamba1Mixer(Layer):
    """Mamba-1's mixer (Gu & Dao 2023) as Jamba runs it (Hugging Face's
    `modeling_jamba.py`, written from memory): no heads, a step size a
    channel from a low-rank bottleneck, a decay a channel and state
    element, and RMSNorms on the step size's bottleneck, on B and on C.

        [u | z] = h W_in                         widths d_inner, d_inner
        c   = silu(conv_bias + causal depthwise conv of width K over u)
        [r | B | C] = c W_x                      widths dt_rank, N, N
        r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)
        dt  = softplus(r W_dt + dt_bias);  A = -exp(A_log)     [d_inner, N]
        h_t = exp(dt_t A) h_{t-1} + (dt_t c_t) (x) B_t;  y_t = h_t C_t + D c_t
        out = (y * silu(z)) W_out                (no norm after the gate)

    What a sequence carries from one call to the next: the last K - 1
    columns of `u` in the model's type and the state `h` in float32
    (`ops/ssm.py` says how both are laid out). `forward` is
    `Mamba2Mixer.forward`'s contract."""

    def __init__(self, hidden_size, d_inner, state_size, dt_rank,
                 conv_kernel=4, rms_norm_eps=1e-6):
        super().__init__()
        self.d_inner, self.state_size = d_inner, state_size
        self.dt_rank, self.conv_kernel = dt_rank, conv_kernel
        self.eps = rms_norm_eps
        normal = I.Normal(0.0, 0.02)
        self.in_proj = Linear(hidden_size, 2 * d_inner, weight_attr=normal,
                              bias_attr=False)
        self.conv_weight = self.create_parameter(
            [d_inner, conv_kernel], default_initializer=normal)
        self.conv_bias = self.create_parameter([d_inner], is_bias=True)
        self.x_proj = Linear(d_inner, dt_rank + 2 * state_size,
                             weight_attr=normal, bias_attr=False)
        self.dt_proj = Linear(dt_rank, d_inner, weight_attr=normal)
        # A = -exp(A_log), as published [d_inner, N]: A_log = 0 forgets
        # with rate dt
        self.A_log = self.create_parameter(
            [d_inner, state_size], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [d_inner], default_initializer=I.Constant(1.0))
        one = I.Constant(1.0)
        self.dt_layernorm = self.create_parameter(
            [dt_rank], default_initializer=one)
        self.b_layernorm = self.create_parameter(
            [state_size], default_initializer=one)
        self.c_layernorm = self.create_parameter(
            [state_size], default_initializer=one)
        self.out_proj = Linear(d_inner, hidden_size, weight_attr=normal,
                               bias_attr=False)
        for p in (self.conv_bias, self.dt_proj.bias, self.D,
                  self.dt_layernorm, self.b_layernorm, self.c_layernorm):
            p.partition_spec = P(None)

    def init_state(self, batch_size: int, dtype):
        """(conv `[batch, K - 1, d_inner]` in `dtype`, ssm `[batch, N,
        d_inner]` float32), zeros: the recurrence's state is held in
        float32 between steps whatever the model's type."""
        return (jnp.zeros((batch_size, self.conv_kernel - 1, self.d_inner),
                          dtype),
                jnp.zeros((batch_size, self.state_size, self.d_inner),
                          jnp.float32))

    def forward(self, hidden, cache=None, pos=None, adv=None, pack=None):
        """As `Mamba2Mixer.forward`. With `pack` nothing is unpacked: the
        conv and the recurrence take a step's packed block as it stands,
        each token a column of its slot (`ops/ssm.py`)."""
        uz = self.in_proj(hidden)
        d_inner, n_state, rank = self.d_inner, self.state_size, self.dt_rank
        eps = self.eps

        def norm(v, w):
            v = v.astype(jnp.float32)
            v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
            return v * w.astype(jnp.float32)

        def mix(proj, conv_w, conv_b, x_w, dt_w, dt_b, a_log, d_skip, dt_n,
                b_n, c_n, conv_state, ssm_state, pos_):
            u, z = proj[..., :d_inner], proj[..., d_inner:]
            rows = conv_state.shape[0]
            if pack is not None:
                pos_ = pack.slot_pos
            fresh = None
            if pos_ is not None:
                fresh = jnp.broadcast_to(jnp.asarray(pos_) == 0, (rows,))
            if pack is None:
                c, new_conv = causal_conv_update(u, conv_state, conv_w,
                                                 conv_b, adv, fresh)
            else:
                start = pack.last + 1 - adv
                c, new_conv = causal_conv_tokens(
                    u[:, 0], conv_state, conv_w, conv_b, pack.slot,
                    pack.col, start, adv, fresh)
            c = c.astype(proj.dtype)
            rbc = c @ x_w
            r = norm(rbc[..., :rank], dt_n).astype(proj.dtype)
            b = norm(rbc[..., rank:rank + n_state], b_n)
            c_t = norm(rbc[..., rank + n_state:], c_n)
            dt = jax.nn.softplus((r @ dt_w).astype(jnp.float32)
                                 + dt_b.astype(jnp.float32))
            a = -jnp.exp(a_log.astype(jnp.float32)).T
            if pack is None:
                y, new_ssm = selective_scan_rows(c, dt, a, b, c_t,
                                                 ssm_state, adv, fresh)
            else:
                y, new_ssm = selective_scan(
                    c, dt, a, b, c_t, ssm_state, start, adv, fresh,
                    columns=pack.dst.shape[1])
            y = y + d_skip.astype(jnp.float32) * c.astype(jnp.float32)
            y = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
            return y.astype(proj.dtype), new_conv, new_ssm

        if cache is None:
            conv_state, ssm_state = self.init_state(
                hidden.shape[0], hidden.dtype)
        else:
            conv_state, ssm_state = cache
        y, new_conv, new_ssm = apply(
            mix, uz, self.conv_weight, self.conv_bias, self.x_proj.weight,
            self.dt_proj.weight, self.dt_proj.bias, self.A_log, self.D,
            self.dt_layernorm, self.b_layernorm, self.c_layernorm,
            conv_state, ssm_state, pos)
        out = self.out_proj(y)
        if cache is None:
            return out
        return out, (new_conv, new_ssm)
