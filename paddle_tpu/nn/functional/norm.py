"""Normalization functionals (reference: operators/batch_norm_op.*, layer_norm_op.*).

layer_norm computes in fp32 regardless of input dtype (matching the reference's CUDA
kernel behavior) — essential for bf16 training stability on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, apply
from ...tensor.creation import _t


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    x = _t(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(normalized_shape)

    def f(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        orig = a.dtype
        h = a.astype(jnp.float32)
        mu = jnp.mean(h, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=axes, keepdims=True)
        out = (h - mu) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32)
        return out.astype(orig)

    args = [x]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply(f, *args)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05, data_format="NCHW",
               use_global_stats=None, name=None):
    x = _t(x)
    rm, rv = _t(running_mean), _t(running_var)
    use_batch_stats = training and not use_global_stats

    def f(a, *wb):
        ch_axis = a.ndim - 1 if data_format[-1] == "C" and a.ndim > 2 else 1
        if a.ndim <= 2:
            ch_axis = 1 if a.ndim == 2 else 0
        reduce_axes = tuple(i for i in range(a.ndim) if i != ch_axis)
        orig = a.dtype
        h = a.astype(jnp.float32)
        if use_batch_stats:
            mu = jnp.mean(h, axis=reduce_axes)
            var = jnp.var(h, axis=reduce_axes)
        else:
            mu = wb[-2].astype(jnp.float32)
            var = wb[-1].astype(jnp.float32)
        shape = [1] * a.ndim
        shape[ch_axis] = h.shape[ch_axis]
        out = (h - mu.reshape(shape)) / jnp.sqrt(var.reshape(shape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        return out.astype(orig)

    args = [x]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    args.extend([rm, rv])
    out = apply(f, *args)

    # update running stats eagerly (matches reference's in-kernel update)
    if use_batch_stats:
        ch_axis = (x.data.ndim - 1 if data_format[-1] == "C" and x.data.ndim > 2
                   else (1 if x.data.ndim >= 2 else 0))
        reduce_axes = tuple(i for i in range(x.data.ndim) if i != ch_axis)
        h = x.data.astype(jnp.float32)
        mu = jnp.mean(h, axis=reduce_axes)
        n = h.size // h.shape[ch_axis]
        var = jnp.var(h, axis=reduce_axes) * (n / max(n - 1, 1))
        rm.data = (momentum * rm.data.astype(jnp.float32)
                   + (1 - momentum) * mu).astype(rm.data.dtype)
        rv.data = (momentum * rv.data.astype(jnp.float32)
                   + (1 - momentum) * var).astype(rv.data.dtype)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    x = _t(x)

    def f(a, *wb):
        # NC* layout: normalize over spatial dims per (N, C)
        axes = tuple(range(2, a.ndim))
        orig = a.dtype
        h = a.astype(jnp.float32)
        mu = jnp.mean(h, axis=axes, keepdims=True)
        var = jnp.var(h, axis=axes, keepdims=True)
        out = (h - mu) / jnp.sqrt(var + eps)
        shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        return out.astype(orig)

    args = [x]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply(f, *args)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = _t(x)

    def f(a, *wb):
        orig = a.dtype
        h = a.astype(jnp.float32)
        if data_format == "NHWC":
            h = jnp.moveaxis(h, -1, 1)
        N, C = h.shape[0], h.shape[1]
        spatial = h.shape[2:]
        g = h.reshape(N, num_groups, C // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        mu = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mu) / jnp.sqrt(var + epsilon)).reshape(N, C, *spatial)
        shape = [1, C] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(jnp.float32).reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(jnp.float32).reshape(shape)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out.astype(orig)

    args = [x]
    if weight is not None:
        args.append(_t(weight))
    if bias is not None:
        args.append(_t(bias))
    return apply(f, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = _t(x)

    def f(a):
        sq = jnp.square(a)
        half = size // 2
        pad_cfg = [(0, 0)] * a.ndim
        pad_cfg[1] = (half, size - 1 - half)
        padded = jnp.pad(sq, pad_cfg)
        acc = sum(padded[:, i:i + a.shape[1]] for i in range(size))
        # 2.x convention (nn/functional/norm.py local_response_norm in the
        # reference builds the window with avg_pool): alpha scales the
        # window MEAN, matching torch — the fluid lrn_op scaled the sum
        return a / jnp.power(k + alpha * acc / size, beta)

    return apply(f, x)
