"""Quantized gradient collectives (EQuARX analog: "EQuARX: Efficient
Quantized AllReduce in XLA", PAPERS.md).

Gradient synchronization is the dominant wire cost of the data-parallel and
ZeRO paths. EQuARX shows a blockwise-scaled quantized all-reduce — built as
reduce-scatter + all-gather with dequant/requant at the reduction hop —
recovers 2-4x of the wire bytes with negligible quality loss. This module is
that collective for every grad-sync path in the framework:

- `quantized_allreduce(x, axis, cfg, key)`: the real RS+AG collective for
  explicit shard_map steps. Per-rank blockwise absmax int8 quantization, an
  int8 `lax.all_to_all` (the reduce-scatter wire phase), local dequant + sum,
  requantization of the reduced chunk, and an int8 `lax.all_gather`. Wire
  bytes per rank drop from `2(W-1)/W * 4n` (fp32 ring RS+AG) to
  `2(W-1)/W * n * (1 + 2/B)` — ~3.9x at block 256.
- `quant_dequant(x, cfg, key)`: the quantization numeric contract alone, for
  the GSPMD-compiled steps where XLA inserts the reduction itself (the same
  boundary treatment `fp16_allreduce` uses in ShardedTrainStep).
- stochastic rounding (`floor(x/s + u)`, u~U[0,1)) keeps every quantization
  unbiased: E[dequant(quantize(x))] == x, so banked/merged gradients do not
  drift; an optional error-feedback residual (carried in optimizer extras by
  ShardedTrainStep) re-injects the rounding error into the next sync.

Scales are bfloat16 (full fp32 exponent range — an fp16 scale overflows past
|g| ~ 65504 * 127) at one scale per `block_size` elements: 2/B bytes of
overhead per payload byte.

Config knobs surface as `DistributedStrategy.quant_allreduce(_configs)` /
`FLAGS_quant_allreduce`, compiled by StrategyCompiler into `plan.comm_quant`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .strategy import QuantAllreduceConfig

# symmetric int8: payload values live in [-127, 127] (-128 unused so the
# range is sign-symmetric and |x|/absmax maps exactly onto +-QMAX)
QMAX = 127
_SCALE_DTYPE = jnp.bfloat16


def _as_config(cfg) -> QuantAllreduceConfig:
    """Accept a QuantAllreduceConfig, a dict of its fields, or True."""
    if isinstance(cfg, QuantAllreduceConfig):
        return cfg.validate()
    if isinstance(cfg, dict):
        fields = {f.name for f in dataclasses.fields(QuantAllreduceConfig)}
        return QuantAllreduceConfig(
            **{k: v for k, v in cfg.items() if k in fields}).validate()
    return QuantAllreduceConfig().validate()


# ---- blockwise int8 quantize / dequantize ----

def quantize_blockwise(x, block_size: int = 256, stochastic: bool = True,
                       key=None):
    """Blockwise absmax int8 quantization over the LAST dim.

    x: [..., n] with n % block_size == 0 (pad first; see _pad_blocks).
    Returns (payload int8 [..., n], scales bf16 [..., n // block_size]).
    With stochastic=True the rounding is floor(v + u), u ~ U[0, 1) — exactly
    unbiased per element; deterministic round-to-nearest otherwise.
    """
    *lead, n = x.shape
    if n % block_size != 0:
        raise ValueError(f"last dim {n} not a multiple of block {block_size}")
    blocks = x.reshape(*lead, n // block_size, block_size).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scale = absmax / QMAX
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    v = blocks * inv
    if stochastic:
        if key is None:
            key = jax.random.PRNGKey(0)
        q = jnp.floor(v + jax.random.uniform(key, blocks.shape))
    else:
        q = jnp.round(v)
    q = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)
    return (q.reshape(x.shape),
            scale.squeeze(-1).astype(_SCALE_DTYPE))


def dequantize_blockwise(payload, scales, out_dtype=jnp.float32):
    """Inverse of quantize_blockwise: payload [..., n], scales [..., n/B]."""
    *lead, n = payload.shape
    nb = scales.shape[-1]
    blocks = payload.reshape(*lead, nb, n // nb).astype(jnp.float32)
    out = blocks * scales[..., None].astype(jnp.float32)
    return out.reshape(payload.shape).astype(out_dtype)


def quant_dequant(x, cfg: Optional[QuantAllreduceConfig] = None, key=None):
    """Round-trip a tensor through the wire quantization (numeric contract
    for GSPMD-reduced steps, where the collective itself is compiler-owned).
    Tensors below min_quant_numel pass through untouched."""
    cfg = _as_config(cfg)
    if x.size < cfg.min_quant_numel:
        return x
    flat, pad = _pad_blocks(x.reshape(-1), cfg.block_size)
    payload, scales = quantize_blockwise(
        flat, cfg.block_size, cfg.stochastic_rounding, key)
    deq = dequantize_blockwise(payload, scales, jnp.float32)
    if pad:
        deq = deq[:x.size]
    return deq.reshape(x.shape).astype(x.dtype)


def _pad_blocks(flat, multiple: int):
    """Zero-pad a 1-D array up to a multiple (static shapes only)."""
    pad = (-flat.shape[0]) % multiple
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


# ---- the collective: quantized reduce-scatter + all-gather ----

def quantized_allreduce(x, axis: str,
                        cfg: Optional[QuantAllreduceConfig] = None,
                        key=None, average: bool = True):
    """EQuARX-style quantized all-reduce over a shard_map axis.

    quantize -> int8 all_to_all (reduce-scatter wire phase) -> local
    dequant+sum -> requantize the reduced chunk -> int8 all_gather ->
    dequant. Must be called inside shard_map with `axis` mapped. Identity
    (exact) at axis size 1; small tensors fall back to plain psum/pmean.
    """
    cfg = _as_config(cfg)
    W = lax.psum(1, axis)  # static axis size
    if W == 1:
        return x
    if x.size < cfg.min_quant_numel:
        return lax.pmean(x, axis) if average else lax.psum(x, axis)
    if key is None:
        key = jax.random.PRNGKey(0)
    # decorrelate rounding noise across ranks (each rank quantizes its own
    # local gradient) and between the two wire phases
    key_rs = jax.random.fold_in(key, lax.axis_index(axis))
    key_ag = jax.random.fold_in(key, W + lax.axis_index(axis))

    flat, _pad = _pad_blocks(x.reshape(-1), W * cfg.block_size)
    C = flat.shape[0] // W
    rows = flat.reshape(W, C)

    # phase 1 — reduce-scatter on an int8 wire: row r of the all_to_all
    # output is MY chunk (index = my rank) as quantized by rank r
    payload, scales = quantize_blockwise(
        rows, cfg.block_size, cfg.stochastic_rounding, key_rs)
    p_recv = lax.all_to_all(payload, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    s_recv = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    partial = dequantize_blockwise(p_recv, s_recv).sum(axis=0)  # fp32 [C]
    if average:
        partial = partial / W

    # phase 2 — all-gather the requantized reduced chunk on an int8 wire
    p_red, s_red = quantize_blockwise(
        partial, cfg.block_size, cfg.stochastic_rounding, key_ag)
    p_all = lax.all_gather(p_red, axis, axis=0, tiled=True)   # [W*C] int8
    s_all = lax.all_gather(s_red, axis, axis=0, tiled=True)
    out = dequantize_blockwise(p_all, s_all)[: x.size]
    return out.reshape(x.shape).astype(x.dtype)


def quantized_pmean(grads, axis: str,
                    cfg: Optional[QuantAllreduceConfig] = None, key=None,
                    average: bool = True):
    """Tree-mapped quantized all-reduce for grad pytrees (the
    sync_gradients_fn backend). Per-leaf keys are folded in by index so
    leaves draw independent rounding noise."""
    cfg = _as_config(cfg)
    if key is None:
        key = jax.random.PRNGKey(0)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out = [quantized_allreduce(g, axis, cfg, jax.random.fold_in(key, i),
                               average=average)
           for i, g in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---- eager bucket path (DataParallel.apply_collective_grads) ----

def quantize_bucket_host(flat, cfg: QuantAllreduceConfig, key):
    """Quantize one flattened grad bucket on THIS process before it is
    device_put for the cross-process reduce: the gathered rows are int8
    payload + bf16 scales instead of full-precision grads. Returns
    (payload, scales, padded_n)."""
    cfg = _as_config(cfg)
    flat, _ = _pad_blocks(flat, cfg.block_size)
    payload, scales = quantize_blockwise(
        flat, cfg.block_size, cfg.stochastic_rounding, key)
    return payload, scales, flat.shape[0]


def dequant_mean_rows(payload_rows, scales_rows, out_dtype):
    """Mean over gathered per-process rows: payload [P, n] int8, scales
    [P, n/B] bf16 -> [n] in out_dtype. jit-compiled by the caller with a
    replicated out_sharding, so GSPMD gathers the int8 rows (the bytes
    saved) and the fp math happens after the wire."""
    return jnp.mean(dequantize_blockwise(payload_rows, scales_rows),
                    axis=0).astype(out_dtype)


# ---- wire-byte accounting (analytic; read by tests/test_compression.py) ----

def comm_bytes_per_step(n: int, world: int,
                        cfg: Optional[QuantAllreduceConfig] = None,
                        dtype_bytes: int = 4) -> int:
    """Bytes each rank moves per all-reduce of n elements (ring RS+AG).

    cfg=None: the full-precision baseline, 2 * (W-1)/W * n * dtype_bytes.
    With a quant config: int8 payload both phases plus bf16 scale sidecar,
    2 * (W-1) * (C + 2*ceil(C/B)) where C is the padded per-rank chunk.
    """
    if world <= 1:
        return 0
    if cfg is None:
        return int(2 * (world - 1) * _ceil_div(n, world) * dtype_bytes)
    cfg = _as_config(cfg)
    n_pad = _ceil_div(n, world * cfg.block_size) * world * cfg.block_size
    chunk = n_pad // world
    scale_bytes = 2 * (chunk // cfg.block_size)  # bf16 sidecar
    return int(2 * (world - 1) * (chunk + scale_bytes))


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def make_error_feedback_state(grads):
    """Zero residuals matching a grad pytree (ShardedTrainStep extras)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


__all__ = [
    "QMAX", "QuantAllreduceConfig", "quantize_blockwise",
    "dequantize_blockwise", "quant_dequant", "quantized_allreduce",
    "quantized_pmean", "quantize_bucket_host", "dequant_mean_rows",
    "comm_bytes_per_step", "make_error_feedback_state",
]
