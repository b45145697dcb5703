"""Fused layer-norm Pallas kernels (reference: the hand-fused CUDA layernorm
family — operators/fused/fused_fc_elementwise_layernorm_op.cu,
operators/fused/skip_layernorm_op.cu, operators/layer_norm_op.cu — and the
layer_norm_fuse_pass at framework/ir/layer_norm_fuse_pass.cc).

TPU-native design: one VMEM-resident pass per row block computes the fp32
mean/rstd and the normalized output (the reference needs two CUDA kernels +
a separate grad kernel chain). The backward is a second Pallas kernel that
produces dx in one pass and accumulates dgamma/dbeta across the sequential
TPU grid — no atomics, no workspace, matching the math of
operators/layer_norm_op.h's LayerNormGrad.

Numerics match paddle_tpu.nn.functional.layer_norm exactly: statistics and
affine are computed in fp32 regardless of input dtype, output is cast back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

_VMEM_BUDGET = 6 * 1024 * 1024  # conservative per-buffer working-set bound


def _block_rows(R: int, N: int) -> int:
    for br in (512, 256, 128, 64, 32, 16, 8):
        if R % br == 0 and br * N * 4 <= _VMEM_BUDGET:
            return br
    return 0


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    h = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (h - mu) * rstd
    w = w_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    y_ref[:] = (xhat * w + b).astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, mu_ref, rstd_ref, dy_ref,
                dx_ref, dw_ref, db_ref):
    i = pl.program_id(0)
    h = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mu = mu_ref[:]
    rstd = rstd_ref[:]
    xhat = (h - mu) * rstd
    w = w_ref[:].astype(jnp.float32)
    a = dy * w
    c1 = jnp.mean(a * xhat, axis=-1, keepdims=True)
    c2 = jnp.mean(a, axis=-1, keepdims=True)
    dx_ref[:] = ((a - c2 - xhat * c1) * rstd).astype(dx_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _fused_fwd(x2d, w, b, eps):
    R, N = x2d.shape
    br = _block_rows(R, N)
    kernel = functools.partial(_fwd_kernel, eps=eps)
    y, mu, rstd = pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2d.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=pallas_mode.interpret("layernorm_fwd"),
        name="layernorm_fwd",
    )(x2d, w.reshape(1, N), b.reshape(1, N))
    return y, mu, rstd


def _fused_bwd(x2d, w, mu, rstd, dy2d):
    R, N = x2d.shape
    br = _block_rows(R, N)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel,
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, N), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2d.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=pallas_mode.interpret("layernorm_bwd"),
        name="layernorm_bwd",
    )(x2d, w.reshape(1, N), mu, rstd, dy2d)
    return dx, dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_layer_norm(x2d, w, b, eps):
    y, _, _ = _fused_fwd(x2d, w, b, eps)
    return y


def _fused_vjp_fwd(x2d, w, b, eps):
    y, mu, rstd = _fused_fwd(x2d, w, b, eps)
    return y, (x2d, w, b, mu, rstd)


def _fused_vjp_bwd(eps, res, dy2d):
    x2d, w, b, mu, rstd = res
    dx, dw, db = _fused_bwd(x2d, w, mu, rstd, dy2d)
    return dx, dw.reshape(w.shape).astype(w.dtype), \
        db.reshape(b.shape).astype(b.dtype)


_fused_layer_norm.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)


def eligible(shape, n_axes, has_weight, has_bias) -> bool:
    """Fused path: normalize over the last axis only, lane-aligned width,
    row count tileable into (8k, N) fp32 VMEM blocks."""
    if n_axes != 1 or not (has_weight and has_bias):
        return False
    if len(shape) < 2:
        return False
    N = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    return N % 128 == 0 and _block_rows(R, N) > 0


def fused_layer_norm(x, weight, bias, eps=1e-5, force_pallas=False):
    """x: [..., N] jax array; weight/bias: [N]. Returns layer-normalized x
    with fp32 statistics, differentiable via the Pallas backward kernel.
    Falls back to plain XLA math when the shape is not tile-eligible."""
    # OPT-IN (FLAGS_use_fused_layernorm=1): measured on v5e GPT-125M, XLA's
    # fused layernorm is marginally faster end-to-end (the pallas call is a
    # fusion barrier for the surrounding elementwise ops), so the kernel is
    # kept for fused/ layernorm parity and for wide-row cases where the
    # one-pass fp32-stats walk wins. Single-device only (c.f.
    # ops.fused_adam): under multi-device GSPMD a pallas_call without a
    # partitioning rule replicates its operands.
    import os
    flag = os.environ.get("FLAGS_use_fused_layernorm", "0")
    on = force_pallas or (flag == "1" and pallas_mode.platform() != "cpu"
                          and jax.device_count() == 1)
    if not on or not eligible(x.shape, 1, True, True):
        h = x.astype(jnp.float32)
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        out = (h - mu) * jax.lax.rsqrt(var + eps)
        out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
        return out.astype(x.dtype)
    lead = x.shape[:-1]
    N = x.shape[-1]
    x2d = x.reshape(-1, N)
    y = _fused_layer_norm(x2d, weight, bias, eps)
    return y.reshape(*lead, N)
