"""Fused Adam update as a Pallas TPU kernel (reference:
operators/optimizers/adam_op.cu AdamKernelMEM / adam_op.h — one CUDA kernel
updating param + moment1 + moment2 in a single pass).

TPU-native design: the parameter is viewed as lane-aligned (rows, 128)
blocks; one sequential Pallas grid walks the row blocks updating p/m1/m2 in
VMEM with fp32 math, with the hyperparameters (lr, beta1^t, beta2^t, wd) as
SMEM scalars so LR schedules do not retrace. The ragged tail (< 1152
elements) is updated by an XLA epilogue. Under jit, XLA fuses the unfused
formula well already — the kernel's win is guaranteed single-pass HBM
traffic for the large weights and exact parity with the reference's fused
semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

_LANES = 128


def _pick_block_rows(rows_main: int) -> int:
    # 7 fp32 in/out buffers of (br, 128) are VMEM-resident (double-buffered
    # by the pipeline): cap br so the working set stays well under 16MiB
    for br in (512, 256, 128, 64, 32, 16, 8):
        if rows_main % br == 0:
            return br
    return 0


def _adam_math(p32, g, m1, m2, lr, b1p, b2p, wd, *, b1, b2, eps, decoupled):
    g = g.astype(jnp.float32)
    if not decoupled:
        g = g + wd * p32
    m1n = b1 * m1 + (1.0 - b1) * g
    m2n = b2 * m2 + (1.0 - b2) * g * g
    update = (m1n / (1.0 - b1p)) / (jnp.sqrt(m2n / (1.0 - b2p)) + eps)
    if decoupled:
        update = update + wd * p32
    return p32 - lr * update, m1n, m2n


def _adam_kernel(s_ref, p_ref, g_ref, m1_ref, m2_ref,
                 po_ref, m1o_ref, m2o_ref, *, b1, b2, eps, decoupled):
    lr, b1p, b2p, wd = s_ref[0], s_ref[1], s_ref[2], s_ref[3]
    newp, m1n, m2n = _adam_math(
        p_ref[:].astype(jnp.float32), g_ref[:], m1_ref[:], m2_ref[:],
        lr, b1p, b2p, wd, b1=b1, b2=b2, eps=eps, decoupled=decoupled)
    po_ref[:] = newp.astype(po_ref.dtype)
    m1o_ref[:] = m1n
    m2o_ref[:] = m2n


def eligible(n: int) -> bool:
    return n >= 8 * _LANES


def fused_adam(p, g, m1, m2, lr, b1p, b2p, wd, *, beta1, beta2, epsilon,
               decoupled, force_pallas=False):
    """Single-pass Adam update. p: any shape/dtype; g same shape; m1/m2
    fp32. lr/b1p/b2p/wd: traced fp32 scalars. Returns (new_p, new_m1,
    new_m2). beta1/beta2/epsilon/decoupled are trace-time constants."""
    import os
    n = p.size
    # OPT-IN (FLAGS_use_fused_adam=1): measured on v5e, XLA's elementwise
    # fusion of the plain update is ~1.5% MFU faster end-to-end than this
    # kernel (the reshape/tail epilogue costs more than the single-pass
    # saves), so the kernel exists for adam_op.cu parity and for shapes/
    # schedules where a guaranteed one-pass update wins. Also single-device
    # only: under multi-device GSPMD a pallas_call has no partitioning rule
    # and would force the sharded param/moments to replicate.
    flag = os.environ.get("FLAGS_use_fused_adam", "0")
    use_pallas = (force_pallas or (flag == "1"
                                   and pallas_mode.platform() != "cpu"
                                   and jax.device_count() == 1)) and \
        eligible(n)
    lr = jnp.asarray(lr, jnp.float32)
    b1p = jnp.asarray(b1p, jnp.float32)
    b2p = jnp.asarray(b2p, jnp.float32)
    wd = jnp.asarray(wd, jnp.float32)
    if not use_pallas:
        newp, m1n, m2n = _adam_math(
            p.astype(jnp.float32), g, m1, m2, lr, b1p, b2p, wd,
            b1=beta1, b2=beta2, eps=epsilon, decoupled=decoupled)
        return newp.astype(p.dtype), m1n, m2n

    rows = n // _LANES
    rows_main = rows - rows % 8
    br = _pick_block_rows(rows_main)
    n_main = rows_main * _LANES
    shape = p.shape

    pf = p.reshape(-1)
    gf = g.reshape(-1)
    m1f = m1.reshape(-1)
    m2f = m2.reshape(-1)
    scal = jnp.stack([lr, b1p, b2p, wd])

    kernel = functools.partial(_adam_kernel, b1=beta1, b2=beta2, eps=epsilon,
                               decoupled=decoupled)
    p2 = pf[:n_main].reshape(rows_main, _LANES)
    g2 = gf[:n_main].reshape(rows_main, _LANES)
    m12 = m1f[:n_main].reshape(rows_main, _LANES)
    m22 = m2f[:n_main].reshape(rows_main, _LANES)
    newp, m1n, m2n = pl.pallas_call(
        kernel,
        grid=(rows_main // br,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_main, _LANES), p.dtype),
            jax.ShapeDtypeStruct((rows_main, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows_main, _LANES), jnp.float32),
        ],
        interpret=pallas_mode.interpret("fused_adam"),
        name="fused_adam",
    )(scal, p2, g2, m12, m22)

    newp = newp.reshape(-1)
    m1n = m1n.reshape(-1)
    m2n = m2n.reshape(-1)
    if n_main < n:
        tp, t1, t2 = _adam_math(
            pf[n_main:].astype(jnp.float32), gf[n_main:], m1f[n_main:],
            m2f[n_main:], lr, b1p, b2p, wd,
            b1=beta1, b2=beta2, eps=epsilon, decoupled=decoupled)
        newp = jnp.concatenate([newp, tp.astype(p.dtype)])
        m1n = jnp.concatenate([m1n, t1])
        m2n = jnp.concatenate([m2n, t2])
    return newp.reshape(shape), m1n.reshape(shape), m2n.reshape(shape)
