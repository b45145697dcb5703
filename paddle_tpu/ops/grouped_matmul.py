"""Grouped matrix multiplication: one matmul per group of consecutive rows.

    out[r] = lhs[r] @ rhs[g]   for the rows r of group g

`lhs` [M, K] holds its rows sorted by group: the first `group_sizes[0]` rows
belong to group 0, the next `group_sizes[1]` to group 1, and so on; rows
past `sum(group_sizes)` belong to no group and their result is unspecified
(the caller masks them). `rhs` is [G, K, N]. This is the arithmetic of a
dropless sparse-expert layer (`nn/layer/moe.py::moe_dropless_forward`):
assignments sorted by expert, one stacked weight per projection, no
capacity and so no `[T, E, C]` one-hot tensor.

One path per platform, chosen like the repo's other kernels
(`ops.pallas_mode`), no flag:

- on a TPU, the Mosaic kernel `moe_gmm`: grid (N tiles, row tiles that hold
  a live row, K tiles), the group of each row tile scalar-prefetched so the
  index map fetches that expert's weight tile only, a float32 accumulator
  in VMEM across K, and a store masked to the rows of the tile's group (a
  row tile that straddles two groups is visited once for each). The number
  of row tiles visited is a traced value: work follows the live rows, not
  M. The tiling scheme and its metadata are those of JAX's
  `pallas.ops.tpu.megablox.gmm`; the kernel is this repo's own because
  megablox's takes neither a kernel name (the trace finds a kernel by it)
  nor a precision for its in-kernel dot (Mosaic refuses a bf16 dot under
  the package-wide "highest", PR 21);
- on the CPU, `jax.lax.ragged_dot`: the parity path of the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from . import pallas_mode
from .attention import _dot

KERNEL = "moe_gmm"
# rows per tile: the MXU's height on a v5e; a decode step gives an expert
# 16-32 rows, so a taller tile would only multiply zeros
TILE_M = 128
# [TILE_K, TILE_N] bf16 is 2 MB of weights per grid step, double-buffered:
# 2.4 us of HBM time against ~0.35 us of grid-step overhead
TILE_K = 2048
TILE_N = 512


def _tile(dim: int, tile: int) -> int:
    """The largest tile <= `tile` that divides `dim` into whole lane-aligned
    tiles; `dim` itself when it is small."""
    if dim <= tile:
        return dim
    for t in range(tile, 127, -128):
        if dim % t == 0:
            return t
    raise ValueError(f"grouped_matmul: {dim} has no tile that is a multiple "
                     f"of 128 and at most {tile}")


def _gmm_kernel(offsets_ref, group_ids_ref, m_tile_ids_ref, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm, tn):
    tile, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(lhs_ref[...], rhs_ref[...], 1, 0)

    @pl.when(k_i == pl.num_programs(2) - 1)
    def _store():
        # only the rows of this visit's group: the others are another
        # group's (visited next, the tile stays resident) or nobody's
        group = group_ids_ref[tile]
        row = m_tile_ids_ref[tile] * tm \
            + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _mosaic(lhs, rhs, group_sizes):
    m, k = lhs.shape
    n = rhs.shape[2]
    pad = -m % TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tm, tk, tn = TILE_M, _tile(k, TILE_K), _tile(n, TILE_N)
    (offsets, group_ids, m_tile_ids), n_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=rhs.shape[0],
        visit_empty_groups=False)

    def lhs_map(n_i, tile, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[tile], k_i

    def rhs_map(n_i, tile, k_i, offsets, group_ids, m_tile_ids):
        return group_ids[tile], k_i, n_i

    def out_map(n_i, tile, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[tile], n_i

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_tiles, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=pallas_mode.interpret(KERNEL),
        name=KERNEL,
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
    return out[:m] if pad else out


def grouped_matmul(lhs, rhs, group_sizes, impl: str = None):
    """lhs [M, K] (rows sorted by group) x rhs [G, K, N] -> [M, N] in
    `lhs.dtype`, accumulated in float32. `group_sizes` [G] int32 may sum to
    less than M; the rows past the sum are unspecified.
    impl: None = `ragged_dot` on the CPU, the kernel on a TPU; or name
    "ragged_dot" / "pallas" (on the CPU the kernel runs interpreted: the
    parity test does that)."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, "
                         f"group_sizes {group_sizes.shape}")
    if impl is None:
        impl = "ragged_dot" if pallas_mode.platform() == "cpu" else "pallas"
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "pallas":
        return _mosaic(lhs, rhs, group_sizes)
    if impl != "ragged_dot":
        raise ValueError(f'impl must be "ragged_dot" or "pallas", got '
                         f'{impl!r}')
    pallas_mode.count(KERNEL, "ragged_dot")
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)
