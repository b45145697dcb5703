"""Fused attention.

Reference capability anchors: softmax_mask_fuse_upper_triangle_op.cu (fused causal
mask+softmax for GPT) and multihead_matmul_op.cu — the reference has NO flash
attention (SURVEY header); this is a parity-plus op named in the north star.

Design (pallas_guide.md):
- a grid step holds a causal ROW of score tiles, not one tile: the forward
  kernel (and dq) owns a block of queries, keeps K and V of the batch-head
  whole in VMEM (fetched once a batch-head) and loops inside the kernel over
  key sub-tiles up to the diagonal; dk/dv owns a block of keys, keeps q and
  dO whole and loops over query sub-tiles from the diagonal on. The trip
  counts are the causal bounds, so a dead tile is neither visited nor
  fetched and the causal path does half the FLOPs. Extents too long for
  VMEM are cut into chunks the grid's last axis walks (`_choose_tiles`
  says where).
- the causal iota/compare/select runs only on the sub-tiles the diagonal
  cuts; tiles wholly under it take an unmasked body. Masked entries score
  `_NEG_INF` and contribute exactly 0.
- forward: online softmax with VMEM scratch (acc, m, l) carried across the
  loop; QK^T and PV hit the MXU with fp32 accumulation. The row logsumexp
  is written lane-dense, `[bh, 1, Sq]`.
- backward: two Pallas kernels recomputing probabilities from the saved
  logsumexp, O(S·block) memory. Per-query statistics stay lane-dense end
  to end: dq relays its block's `[1, block_q]` slice of lse to a column
  once a grid step and makes delta = rowsum(dO·O) from its own dO and O
  blocks (also writing it out as a row); dk/dv computes the tile
  transposed, sT = k qT, so lse and delta broadcast as the rows they are
  and dv += pT dO, dk += dsT q are plain products. No `[bh, Sq, 1]` array
  (128 x padded on a TPU) exists.
- tile sizes come from the shapes (`_choose_tiles`): one sub-tile shared by
  the three kernels, so that dropout regenerates the same bits in each.
- rectangular (cross) attention: causal masking uses the bottom-right offset
  (q_offset = Sk - Sq), matching the XLA reference path; the diagonal's
  place and the loops' bounds take the offset.
- additive mask: [B, 1|H, Sq, Sk] read a sub-tile at a time in every kernel
  (every sub-tile then takes the masked body).
- dropout: in-kernel TPU PRNG seeded per (bh, q sub-tile, k sub-tile) so
  forward and backward regenerate identical keep-masks without storing
  O(S²) bits. The keep-mask applies to the normalized probs (acc uses
  dropped p, the softmax denominator uses undropped p — algebraically
  identical to dropout(softmax)). Not available in CPU interpret mode
  (pltpu.prng has no CPU lowering).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kv_write as kvw, pallas_mode

# trace-time flag: the SPMD step sets this while the sequence dim is
# GSPMD-sharded over the `sep` axis. With a mesh attached, attention drops
# into a shard_map island running ring/Ulysses attention over the sep axis
# (O(S_local^2) memory, k/v rotating over ICI ppermute) — the production
# long-context path. Without a mesh (or with an additive mask/dropout, which
# the ring kernels don't take), it falls back to the XLA reference, which the
# partitioner slices by all-gathering k/v.
import threading as _threading

_SEQ_SHARDED = _threading.local()


def sequence_sharded_trace() -> bool:
    return getattr(_SEQ_SHARDED, "on", False)


class sequence_sharded:
    """Context manager marking the enclosed trace as sequence-sharded.

    mesh/batch_axes/impl: when given, flash_attention routes to the
    ring/Ulysses shard_map island over the mesh's `sep` axis."""

    def __init__(self, mesh=None, batch_axes=None, impl: str = "ring"):
        self._mesh = mesh
        self._batch_axes = batch_axes
        self._impl = impl

    def __enter__(self):
        self._prev = (getattr(_SEQ_SHARDED, "on", False),
                      getattr(_SEQ_SHARDED, "mesh", None),
                      getattr(_SEQ_SHARDED, "batch_axes", None),
                      getattr(_SEQ_SHARDED, "impl", "ring"))
        _SEQ_SHARDED.on = True
        _SEQ_SHARDED.mesh = self._mesh
        _SEQ_SHARDED.batch_axes = self._batch_axes
        _SEQ_SHARDED.impl = self._impl
        return self

    def __exit__(self, *exc):
        (_SEQ_SHARDED.on, _SEQ_SHARDED.mesh, _SEQ_SHARDED.batch_axes,
         _SEQ_SHARDED.impl) = self._prev
        return False


def _sequence_parallel_island(q, k, v, causal, scale, impl="ring"):
    """Drop into a shard_map over the sep axis and run ring/Ulysses attention
    on the local sequence shards (PAPERS.md blockwise ring attention /
    DeepSpeed-Ulysses; no reference analog — SURVEY §5 long-context).
    Inside the island the trace-time flag is cleared so the Ulysses inner
    flash_attention doesn't recurse back here."""
    from jax.sharding import PartitionSpec as P
    mesh = _SEQ_SHARDED.mesh
    batch_axes = _SEQ_SHARDED.batch_axes
    from ..parallel.ring_attention import ring_attention, ulysses_attention
    fn = ulysses_attention if impl in ("ulysses", "all_to_all") \
        else ring_attention
    mp = ("model" if "model" in mesh.axis_names and mesh.shape["model"] > 1
          else None)
    spec = P(batch_axes, mp, "sep", None)

    def body(ql, kl, vl):
        prev = _SEQ_SHARDED.on
        _SEQ_SHARDED.on = False
        try:
            return fn(ql, kl, vl, axis="sep", causal=causal, scale=scale)
        finally:
            _SEQ_SHARDED.on = prev

    island = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return island(q, k, v)


# trace-time flag: the SPMD step sets this while it traces the model under
# GSPMD over a mesh of more than one device. A Mosaic kernel has no
# partitioning rule — JAX refuses to lower a pallas_call whose operands are
# sharded ("Mosaic kernels cannot be automatically partitioned") — so with it
# set the flash kernels run inside a shard_map island over the batch and head
# axes, where each device sees its local [B/dp, H/mp, S, D] block and
# attention needs no communication.
_SPMD = _threading.local()


class spmd_mesh:
    """Context manager marking the enclosed trace as GSPMD-partitioned over
    `mesh`, batch dim sharded over `batch_axes` (a name, a tuple, or None)
    and heads over `model` where that axis is real."""

    def __init__(self, mesh, batch_axes):
        self._mesh = mesh
        self._batch_axes = batch_axes

    def __enter__(self):
        self._prev = (getattr(_SPMD, "mesh", None),
                      getattr(_SPMD, "batch_axes", None))
        _SPMD.mesh, _SPMD.batch_axes = self._mesh, self._batch_axes
        return self

    def __exit__(self, *exc):
        _SPMD.mesh, _SPMD.batch_axes = self._prev
        return False


def _flash_spmd_island(q, k, v, mask, seed, causal, scale, block_q, block_k,
                       dropout_p):
    """`_flash_attention` on each device's local batch/head block. A dim
    whose size the axis does not divide stays unsharded in the island (its
    operand is gathered and the work repeated) rather than failing."""
    from jax.sharding import PartitionSpec as P
    mesh = _SPMD.mesh
    B, H = q.shape[0], q.shape[1]

    def names(axes):
        return axes if isinstance(axes, tuple) else (axes,) if axes else ()

    def fits(axes, size):
        n = math.prod(mesh.shape[a] for a in names(axes))
        return axes if n > 1 and size % n == 0 else None

    b_ax = fits(_SPMD.batch_axes, B)
    h_ax = fits("model" if "model" in mesh.axis_names else None, H)
    spec = P(b_ax, h_ax, None, None)
    operands, in_specs = [q, k, v], [spec, spec, spec]
    if mask is not None:
        m4 = mask if mask.ndim == 4 else mask[:, None]
        operands.append(m4)
        in_specs.append(P(b_ax if m4.shape[0] == B else None,
                          h_ax if m4.shape[1] == H else None, None, None))
    sharded = names(b_ax) + names(h_ax)

    def body(seed_, ql, kl, vl, *ml):
        if dropout_p > 0.0 and sharded:
            # the kernels seed per LOCAL (bh, q block, k block): without
            # this every shard would drop the same positions
            seed_ = seed_ + jax.lax.axis_index(sharded).astype(jnp.int32) * (
                ql.shape[0] * ql.shape[1] * 4099)
        return _flash_attention(ql, kl, vl, ml[0] if ml else None, seed_,
                                causal, scale, block_q, block_k, dropout_p)

    island = jax.shard_map(body, mesh=mesh, in_specs=(P(), *in_specs),
                           out_specs=spec, check_vma=False)
    return island(seed, *operands)


_NEG_INF = -1e30


def causal_mask(n_rows: int, n_cols: int, q_offset=0, k_offset=0):
    """Boolean [n_rows, n_cols] mask: True where query position >= key
    position (with absolute offsets). Shared by the XLA reference, the Pallas
    kernel blocks, and incubate's fused softmax."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_cols), 1)
    return (q_offset + rows) >= (k_offset + cols)


def _attention_reference(q, k, v, causal, scale, mask=None, dropout_p=0.0,
                         dropout_key=None):
    """Plain-XLA reference (fp32 softmax). Used for short sequences, CPU, and
    as the numerics oracle in tests."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    Sq, Sk = logits.shape[-2], logits.shape[-1]
    cm = None
    if causal:
        cm = causal_mask(Sq, Sk, q_offset=Sk - Sq)
        logits = jnp.where(cm, logits, _NEG_INF)
    if mask is not None:
        if mask.ndim == 3:  # [B,Sq,Sk] -> broadcast over heads, like _mask_3d
            mask = mask[:, None]
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if cm is not None:
        # rows with no causally-visible key (Sq > Sk cross attention) output
        # zeros, matching the kernel's skipped-block convention
        probs = jnp.where(jnp.any(cm, axis=-1, keepdims=True), probs, 0.0)
    if dropout_p > 0.0:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _dot(a, b, a_dim, b_dim):
    """In-kernel MXU dot contracting a[a_dim] with b[b_dim], fp32 accumulate.
    Sub-fp32 operands (bf16 on TPU: full MXU rate) name the one-pass
    precision themselves: the package-wide "highest" default
    (paddle_tpu/__init__.py) reaches in-kernel dots too, and Mosaic rejects
    it for bf16 operands ("Bad lhs type")."""
    prec = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _block_keep(seed_ref, b, qi, kb, n_qb, n_kb, shape, dropout_p):
    """Deterministic per-tile dropout keep-mask from the TPU PRNG; the same
    (seed, tile) pair regenerates the same bits in forward and backward:
    the three kernels share one sub-tile (`FlashTiles`), count its index
    over the whole sequence and draw `shape` = `[block_q, block_k]`
    (`flash_bwd_dkv`, which computes the tile transposed, transposes the
    bits). seed_ref is a traced SMEM scalar, so a fresh per-step seed does
    NOT retrace/recompile the kernel."""
    pltpu.prng_seed(seed_ref[0] + ((b * n_qb + qi) * n_kb + kb))
    bits = pltpu.prng_random_bits(shape)  # uint32
    thresh = jnp.uint32(int(dropout_p * (2 ** 32 - 1)))
    return bits >= thresh


class FlashTiles(NamedTuple):
    """What one grid step of each flash kernel holds (`_choose_tiles`).

    A score tile is `block_q` queries by `block_k` keys in all three
    kernels. `flash_fwd` and `flash_bwd_dq` own `block_q` queries a grid
    step and loop over the key sub-tiles of the `chunk_k` keys they hold;
    `flash_bwd_dkv` owns `block_k` keys and loops over the query sub-tiles
    of the `chunk_q` queries it holds. A chunk is the whole extent unless
    that does not fit (the grid's last axis then walks the chunks)."""
    block_q: int
    block_k: int
    chunk_q: int
    chunk_k: int
    vmem_limit: Optional[int]   # Mosaic's scoped limit, where the default
    #                             16 MB is short; None leaves it alone


# the edge a sub-tile takes where the sequence allows: at 2,048 x 128 on a
# v5e (PR 45's chip runs, PERF.md §6) all three kernels are fastest here. A
# smaller tile pays a loop trip's fixed cost more often; of a larger one
# the diagonal block, computed whole, wastes more (80% of the computed
# scores are live at 512, 89% at 256, 67% at 1,024)
_TILE = 512
# operand bytes one grid step may keep double-buffered: the other side's
# K and V (q and dO for `flash_bwd_dkv`), and an additive mask's block
_OPERAND_BUDGET = 8 << 20
_SCOPED_DEFAULT = 16 << 20
_VMEM_MOST = 96 << 20      # of the 128 MB a v5e core has


def _divisor_tile(extent: int, want: int) -> Optional[int]:
    """The largest lane-aligned size up to `want` that divides `extent`;
    an extent no longer than `want` is one tile (a whole dimension is
    always a legal block)."""
    if extent <= want:
        return extent
    for size in range(want - want % 128, 0, -128):
        if extent % size == 0:
            return size
    return None


def _chunk(extent: int, tile: int, most: int) -> int:
    """The most tiles of `extent` (a divisor of their number) that stay
    within `most` rows; one tile at the least."""
    n = extent // tile
    return tile * max(per for per in range(1, n + 1)
                      if n % per == 0 and (per * tile <= most or per == 1))


def _choose_tiles(Sq: int, Sk: int, D: int, itemsize: int,
                  has_mask: bool = False, block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> Optional[FlashTiles]:
    """The tiles of the three flash kernels, from the shapes alone; None
    where no tile divides a sequence (the caller takes the XLA reference).

    Sub-tiles are `_TILE` on a side, or the largest multiple of 128 under
    it that divides the sequence (768 takes 384), or the sequence itself
    when shorter; `block_q` / `block_k` name them instead where a caller
    does. The other operand's extent is held whole while K and V (q and dO)
    double-buffered stay inside `_OPERAND_BUDGET`: up to 8,192 rows at D =
    128 in bf16. Beyond that (or beyond 2,048 keys under an additive mask,
    whose float32 block rides along) the extent is cut into chunks that do
    fit, the grid's last axis walks them with the softmax state carried in
    scratch, and the index maps stop at the diagonal so that a causally
    dead chunk is never fetched. Sized for the extents the cells run
    (2,048); the chunked form is there so that 8k-32k compile, not tuned."""
    bq = min(block_q, Sq) if block_q else _divisor_tile(Sq, _TILE)
    bk = min(block_k, Sk) if block_k else _divisor_tile(Sk, _TILE)
    if not bq or not bk or Sq % bq or Sk % bk:
        return None
    rows = _OPERAND_BUDGET // (4 * D * itemsize)   # two arrays, two buffers
    chunk_k = _chunk(Sk, bk, min(rows, _OPERAND_BUDGET // (8 * bq))
                     if has_mask else rows)
    chunk_q = _chunk(Sq, bq, min(rows, _OPERAND_BUDGET // (8 * bk))
                     if has_mask else rows)
    # what a step holds of the other operand: K and V double-buffered and
    # the forward kernel's transposed V (dkv: q and dO, the lse and delta
    # rows), with an additive mask's float32 block
    held = max(chunk_k * (5 * D * itemsize + (8 * bq if has_mask else 0)),
               chunk_q * (4 * D * itemsize + (8 * bk if has_mask else 0)
                          + 128))
    own = 8 * max(bq, bk) * D * itemsize           # q o / k v dk dv blocks
    state = 2 * max(bq, bk) * (D + 128) * 4        # accumulators, m and l
    scores = 6 * bq * bk * 4       # s, p, dp, ds and the bf16 copies of two
    need = held + own + state + scores
    limit = None if need <= _SCOPED_DEFAULT // 2 \
        else min(max(2 * need, 2 * _SCOPED_DEFAULT), _VMEM_MOST)
    return FlashTiles(bq, bk, chunk_q, chunk_k, limit)


def _tile_counts(Sq: int, Sk: int, bq: int, bk: int, causal: bool,
                 has_mask: bool) -> tuple:
    """(live, masked): the sub-tiles one batch-head of a call computes and
    those of them that take the masked body, counted from the definition
    (a tile is live if one of its queries sees one of its keys, cut by the
    diagonal if one does not) rather than from the kernels' loop bounds."""
    off = Sk - Sq
    live = cut = 0
    for q0 in range(0, Sq, bq):
        for k0 in range(0, Sk, bk):
            sees_some = not causal or q0 + bq - 1 + off >= k0
            sees_all = not causal or q0 + off >= k0 + bk - 1
            live += sees_some
            cut += sees_some and not sees_all
    return live, (live if has_mask else cut)


def _key_tile_bounds(q_start, bq: int, bk: int, n_kb: int, off: int):
    """`(full, live)` for the `bq` queries from `q_start`: key sub-tiles
    `[0, full)` lie wholly under the diagonal, `[full, live)` are cut by
    it, the rest are dead."""
    Sk = n_kb * bk
    return (jnp.clip(q_start + off + 1, 0, Sk) // bk,
            (jnp.clip(q_start + bq + off, 0, Sk) + bk - 1) // bk)


def _query_tile_bounds(k_start, bq: int, bk: int, n_qb: int, off: int):
    """`(first, full)` for the `bk` keys from `k_start`: query sub-tiles
    `[first, full)` are cut by the diagonal, `[full, n_qb)` lie wholly
    under it, those before `first` are dead."""
    Sq = n_qb * bq
    return (jnp.clip(k_start - off, 0, Sq) // bq,
            (jnp.clip(k_start + bk - 1 - off, 0, Sq) + bq - 1) // bq)


def _key_tile_spans(causal, q_start, bq, bk, n_kb, off) -> tuple:
    """`_tile_loops`' (full, cut) spans of key sub-tiles; none if the call
    is not causal."""
    if not causal:
        return ()
    full, live = _key_tile_bounds(q_start, bq, bk, n_kb, off)
    return (0, full), (full, live)


def _causal_keep(shape, q_pos, k_pos, q_axis: int):
    """Boolean score tile: True where the query sees the key. Axis `q_axis`
    runs over queries from `q_pos` (the causal offset included), the other
    over keys from `k_pos`."""
    qs = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) + q_pos
    ks = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) + k_pos
    return qs >= ks


def _tile_loops(body, held, full=None, cut=None):
    """Run `body(t, diag)` over the sub-tiles a grid step holds, `held` =
    `(lo, hi)`: the unmasked body on those of `full`, the masked one on
    those of `cut` (both `(lo, hi)`; without them, a call that is not
    causal, the unmasked body on all). The trip counts are the causal
    bounds, so a dead tile is not visited."""
    def loop(span, diag):
        jax.lax.fori_loop(jnp.maximum(span[0], held[0]),
                          jnp.minimum(span[1], held[1]),
                          lambda t, c: (body(t, diag), c)[1], 0)
    if full is None:
        loop(held, False)
    else:
        loop(full, False)
        loop(cut, True)


def _split_refs(refs, n_in, has_mask, dropout_p):
    """(inputs, mask_ref, seed_ref, outputs and scratch)."""
    i = n_in
    mask_ref = refs[i] if has_mask else None
    i += 1 if has_mask else 0
    seed_ref = refs[i] if dropout_p > 0.0 else None
    i += 1 if dropout_p > 0.0 else 0
    return refs[:n_in], mask_ref, seed_ref, refs[i:]


def _fwd_kernel(*refs, scale, causal, tiles, causal_offset, has_mask,
                dropout_p, n_qb, n_kb):
    """Grid (batch*heads, q blocks, key chunks): a step owns `block_q`
    queries, holds a chunk of K and V and loops over its key sub-tiles up
    to the diagonal. The tile is computed transposed, sT = k qT
    `[block_k, block_q]`, so that the online-softmax state (running max and
    sum, one value a query) is lane-dense rows `[1, block_q]`, the max and
    sum over keys run down sublanes, and lse leaves as the row it is stored
    as; the accumulator is oT `[D, block_q]` += vT pT, with V transposed
    once a chunk held (not once a tile) and oT once a grid step."""
    (q_ref, k_ref, v_ref), mask_ref, seed_ref, rest = _split_refs(
        refs, 3, has_mask, dropout_p)
    o_ref, lse_ref, acc_ref, m_ref, l_ref, vt_ref = rest
    bq, bk = tiles.block_q, tiles.block_k
    per = tiles.chunk_k // bk
    b, qi, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_start = qi * bq

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the chunk held changes with the batch-head alone when it is the whole
    # extent (the q blocks of a batch-head run in order: "arbitrary")
    @pl.when(qi == 0 if per == n_kb else True)
    def _transpose_v():
        vt_ref[...] = v_ref[0].T

    def tile(kb, diag):
        # dots take the input dtype (bf16 on TPU — full MXU rate; fp32 dots
        # run at a fraction of it) and accumulate fp32 via
        # preferred_element_type; scale applies post-dot in fp32
        at = pl.ds(pl.multiple_of((kb - c * per) * bk, bk), bk)
        sT = _dot(k_ref[0, at, :], q_ref[0], 1, 1) * scale      # [bk, bq]
        if diag:
            sT = jnp.where(_causal_keep(sT.shape, q_start + causal_offset,
                                        kb * bk, 1), sT, _NEG_INF)
        if has_mask:
            sT = sT + mask_ref[0, :, at].astype(jnp.float32).T
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sT, axis=0, keepdims=True))
        pT = jnp.exp(sT - m_new)
        if diag or has_mask:
            # structurally-masked entries contribute exactly 0 even when a
            # whole row is masked (else exp(s - m) with m == s == -1e30
            # would give 1 for every key and rows with no visible key would
            # emit mean(v)); a tile under the diagonal has none
            pT = jnp.where(sT <= _NEG_INF / 2, 0.0, pT)
        alpha = jnp.exp(m_prev - m_new)
        # denominator uses the full p; dropout applies only to the numerator
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pT, axis=0, keepdims=True)
        if dropout_p > 0.0:
            keepT = _block_keep(seed_ref, b, qi, kb, n_qb, n_kb, (bq, bk),
                                dropout_p).astype(jnp.int32).T > 0
            pT = jnp.where(keepT, pT / (1.0 - dropout_p), 0.0)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            vt_ref[:, at], pT.astype(vt_ref.dtype), 1, 0)
        m_ref[...] = m_new

    _tile_loops(tile, (c * per, (c + 1) * per), *_key_tile_spans(
        causal, q_start, bq, bk, n_kb, causal_offset))

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        # lse buffer is [bh, 1, Sq]: a trailing dim of 1 would get a
        # T(8,128) padded layout (128x HBM expansion — OOMs 1B+ models),
        # so the whole row lives in lanes and each q block writes its
        # slice of it
        lse_ref[0] = (m_ref[...] + jnp.log(l)).astype(jnp.float32)


def _bwd_dq_kernel(*refs, scale, causal, tiles, causal_offset, has_mask,
                   dropout_p, n_qb, n_kb):
    """Grid (bh, q blocks, key chunks): dq of one q block, over the key
    sub-tiles up to the diagonal. The step's first chunk makes what the
    tiles need as columns, once: `lse` relaid from its lane-dense row, and
    delta = rowsum(dO * O), which it also hands to `flash_bwd_dkv` as a
    lane-dense row."""
    (q_ref, k_ref, v_ref, g_ref, out_ref, lse_ref), mask_ref, seed_ref, \
        rest = _split_refs(refs, 6, has_mask, dropout_p)
    dq_ref, delta_ref, acc_ref, lse_col, delta_col = rest
    bq, bk = tiles.block_q, tiles.block_k
    per = tiles.chunk_k // bk
    b, qi, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_start = qi * bq

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_col[...] = lse_ref[0].reshape(bq, 1)
        delta = jnp.sum(g_ref[0].astype(jnp.float32)
                        * out_ref[0].astype(jnp.float32), axis=-1,
                        keepdims=True)
        delta_col[...] = delta
        delta_ref[0, 0, :] = delta.reshape(bq)

    def tile(kb, diag):
        # bf16-in/fp32-accum dots (see _fwd_kernel note)
        at = pl.ds(pl.multiple_of((kb - c * per) * bk, bk), bk)
        kblk = k_ref[0, at, :]
        s = _dot(q_ref[0], kblk, 1, 1) * scale
        if diag:
            s = jnp.where(_causal_keep(s.shape, q_start + causal_offset,
                                       kb * bk, 0), s, _NEG_INF)
        if has_mask:
            s = s + mask_ref[0, :, at].astype(jnp.float32)
        p = jnp.exp(s - lse_col[...])
        if diag or has_mask:
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        dp = _dot(g_ref[0], v_ref[0, at, :], 1, 1)
        if dropout_p > 0.0:
            keep = _block_keep(seed_ref, b, qi, kb, n_qb, n_kb, p.shape,
                               dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        # ds without its factor `scale`: the sum takes it once, below
        ds = p * (dp - delta_col[...])
        acc_ref[...] += _dot(ds.astype(kblk.dtype), kblk, 1, 0)

    _tile_loops(tile, (c * per, (c + 1) * per), *_key_tile_spans(
        causal, q_start, bq, bk, n_kb, causal_offset))

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, tiles, causal_offset, has_mask,
                    dropout_p, n_qb, n_kb):
    """Grid (bh, k blocks, query chunks): dk/dv of one k block, over the
    query sub-tiles from the diagonal on. The tile is computed transposed,
    sT = k qT `[block_k, block_q]`, so that the per-query statistics
    broadcast as the rows `[1, block_q]` they are stored as and dv += pT dO,
    dk += dsT q are plain products."""
    (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref), mask_ref, seed_ref, \
        rest = _split_refs(refs, 6, has_mask, dropout_p)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    bq, bk = tiles.block_q, tiles.block_k
    per = tiles.chunk_q // bq
    b, kb, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_start = kb * bk

    @pl.when(c == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(qi, diag):
        # bf16-in/fp32-accum dots (see _fwd_kernel note)
        at = pl.ds(pl.multiple_of((qi - c * per) * bq, bq), bq)
        q = q_ref[0, at, :]
        g = g_ref[0, at, :]
        sT = _dot(k_ref[0], q, 1, 1) * scale               # [bk, bq]
        if diag:
            sT = jnp.where(_causal_keep(sT.shape, qi * bq + causal_offset,
                                        k_start, 1), sT, _NEG_INF)
        if has_mask:
            sT = sT + mask_ref[0, at, :].astype(jnp.float32).T
        pT = jnp.exp(sT - lse_ref[0, :, at])
        if diag or has_mask:
            pT = jnp.where(sT <= _NEG_INF / 2, 0.0, pT)
        dpT = _dot(v_ref[0], g, 1, 1)
        if dropout_p > 0.0:
            keepT = _block_keep(seed_ref, b, qi, kb, n_qb, n_kb, (bq, bk),
                                dropout_p).astype(jnp.int32).T > 0
            inv = 1.0 - dropout_p
            dpT = jnp.where(keepT, dpT / inv, 0.0)
            p_drop = jnp.where(keepT, pT / inv, 0.0)
        else:
            p_drop = pT
        dsT = pT * (dpT - delta_ref[0, :, at])     # `scale`: once, below
        dv_acc[...] += _dot(p_drop.astype(g.dtype), g, 1, 0)
        dk_acc[...] += _dot(dsT.astype(q.dtype), q, 1, 0)

    spans = ()
    if causal:
        first, full = _query_tile_bounds(k_start, bq, bk, n_qb,
                                         causal_offset)
        spans = (full, n_qb), (first, full)
    _tile_loops(tile, (c * per, (c + 1) * per), *spans)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _mask_3d(mask, B, H, Sq, Sk):
    """Normalize an additive mask to [rows, Sq, Sk] + the bh->row divisor for
    the BlockSpec index map (row = bh // divisor). [B,1,Sq,Sk] stays
    un-broadcast: every head of batch b reads row b."""
    if mask.ndim == 3:
        mask = mask[:, None]
    mb, mh = mask.shape[0], mask.shape[1]
    if mb not in (1, B):
        raise ValueError(
            f"additive mask batch dim {mb} must be 1 or match batch {B}")
    if mh == 1:
        if mb == 1:
            return mask.reshape(1, Sq, Sk), B * H  # bh // (B*H) == 0 always
        return mask.reshape(B, Sq, Sk), H
    flat = jnp.broadcast_to(mask, (B, H, Sq, Sk)).reshape(B * H, Sq, Sk)
    return flat, 1


def _compiler_params(tiles: FlashTiles, q_blocks_in_order: bool = False):
    """Every flash grid is (batch*heads, owned blocks, chunks of the other
    operand): the scratch accumulators carry across the last axis; the
    forward kernel also keeps V transposed across a batch-head's blocks."""
    return pltpu.CompilerParams(
        dimension_semantics=(
            "parallel", "arbitrary" if q_blocks_in_order else "parallel",
            "arbitrary"),
        vmem_limit_bytes=tiles.vmem_limit)


def _tiles_for(q, k, mask, block_q, block_k) -> FlashTiles:
    tiles = _choose_tiles(q.shape[2], k.shape[2], q.shape[3],
                          q.dtype.itemsize, mask is not None, block_q,
                          block_k)
    assert tiles is not None, (
        "flash_attention requires a tile that divides the sequence; "
        "callers fall back to the XLA reference otherwise")
    return tiles


def _key_chunk_specs(tiles: FlashTiles, Sq, Sk, D, causal):
    """Block specs of a grid (bh, q block i, key chunk c), the forward's
    and dq's: a block of queries, a chunk of keys, and the additive mask's
    block for both (given its bh -> row divisor). Past the diagonal the key
    chunk's index stays at the last live one, so a dead chunk is not
    fetched."""
    bq, ck, off = tiles.block_q, tiles.chunk_k, Sk - Sq

    def chunk(i, c):
        if not causal or ck == Sk:
            return c
        return jnp.minimum(c, (jnp.clip(i * bq + bq + off, 1, Sk) - 1) // ck)
    return (pl.BlockSpec((1, bq, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, ck, D), lambda b, i, c: (b, chunk(i, c), 0)),
            lambda div: pl.BlockSpec(
                (1, bq, ck), lambda b, i, c: (b // div, i, chunk(i, c))))


def _extra_operands(mask, seed, dropout_p, B, H, Sq, Sk, mask_spec):
    """The specs and operands an additive mask and a dropout seed add."""
    specs, operands = [], []
    if mask is not None:
        mflat, div = _mask_3d(mask, B, H, Sq, Sk)
        specs.append(mask_spec(div))
        operands.append(mflat)
    if dropout_p > 0.0:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))
    return specs, operands


def _note_tiling(kernel, q, k, mask, causal, tiles: FlashTiles):
    """Record what `kernel` computes at `tiles` and say where it runs
    (`interpret=` of its pallas_call). Outside the jitted calls below, so
    that every traced call site counts."""
    (B, H, Sq, _), Sk = q.shape, k.shape[2]
    bq, bk = tiles.block_q, tiles.block_k
    live, masked = _tile_counts(Sq, Sk, bq, bk, causal, mask is not None)
    if kernel == "flash_bwd_dkv":
        held = dict(chunk_q=tiles.chunk_q,
                    grid=(B * H, Sk // bk, Sq // tiles.chunk_q))
    else:
        held = dict(chunk_k=tiles.chunk_k,
                    grid=(B * H, Sq // bq, Sk // tiles.chunk_k))
    pallas_mode.note_tiling(kernel, block_q=bq, block_k=bk, live_tiles=live,
                            masked_tiles=masked, **held)
    return pallas_mode.interpret(kernel)


def _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k, dropout_p,
               seed):
    tiles = _tiles_for(q, k, mask, block_q, block_k)
    return _fwd_call(
        q, k, v, mask, seed, causal=causal, scale=scale, tiles=tiles,
        dropout_p=dropout_p,
        interpret=_note_tiling("flash_fwd", q, k, mask, causal, tiles))


# The pallas_calls sit under module-level jits whose integers are static,
# as `paged_attention._paged_call` does: the call sites of one traced
# program that agree on shapes (a model's layers) share one jaxpr and the
# program lowers one kernel body for them, not one a layer. Tracing and
# lowering run in every process before the compile cache can be asked.
@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "tiles", "dropout_p", "interpret"))
def _fwd_call(q, k, v, mask, seed, *, causal, scale, tiles, dropout_p,
              interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk, ck = tiles.block_q, tiles.block_k, tiles.chunk_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, tiles=tiles,
        causal_offset=Sk - Sq, has_mask=mask is not None,
        dropout_p=dropout_p, n_qb=Sq // bq, n_kb=Sk // bk)
    q_spec, kv_spec, mask_spec = _key_chunk_specs(tiles, Sq, Sk, D, causal)
    extra_specs, extra = _extra_operands(mask, seed, dropout_p, B, H, Sq, Sk,
                                         mask_spec)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, Sk // ck),
        in_specs=[q_spec, kv_spec, kv_spec] + extra_specs,
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, bq), lambda b, i, c: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, bq), jnp.float32),   # acc, transposed
            pltpu.VMEM((1, bq), jnp.float32),   # running max
            pltpu.VMEM((1, bq), jnp.float32),   # running sum
            pltpu.VMEM((D, ck), v.dtype),       # V of the chunk, transposed
        ],
        compiler_params=_compiler_params(tiles, q_blocks_in_order=True),
        interpret=interpret,
        name="flash_fwd",
    )(q.reshape(B * H, Sq, D), k.reshape(B * H, Sk, D),
      v.reshape(B * H, Sk, D), *extra)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)


def _flash_bwd(q, k, v, mask, out, lse, g, causal, scale, block_q, block_k,
               dropout_p, seed):
    """(dq, dk, dv, delta `[B, H, Sq]`). `lse` and delta = rowsum(dO * O)
    stay lane-dense `[bh, 1, Sq]` from the forward kernel's result to the
    backward kernels' operands: `flash_bwd_dq` makes delta from its dO and
    O blocks and hands it on, and no `[bh, Sq, 1]` column (128 x padded on
    a TPU) is materialized."""
    tiles = _tiles_for(q, k, mask, block_q, block_k)
    return _bwd_call(
        q, k, v, mask, out, lse, g, seed, causal=causal, scale=scale,
        tiles=tiles, dropout_p=dropout_p, interpret=tuple(
            _note_tiling(kernel, q, k, mask, causal, tiles)
            for kernel in ("flash_bwd_dq", "flash_bwd_dkv")))


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "tiles", "dropout_p", "interpret"))
def _bwd_call(q, k, v, mask, out, lse, g, seed, *, causal, scale, tiles,
              dropout_p, interpret):
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk, cq, ck = tiles.block_q, tiles.block_k, tiles.chunk_q, \
        tiles.chunk_k
    off = Sk - Sq
    qr = q.reshape(B * H, Sq, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    gr = g.reshape(B * H, Sq, D)
    lser = lse.reshape(B * H, 1, Sq)
    common = dict(scale=scale, causal=causal, tiles=tiles, causal_offset=off,
                  has_mask=mask is not None, dropout_p=dropout_p,
                  n_qb=Sq // bq, n_kb=Sk // bk)

    q_spec, kv_spec, mask_spec = _key_chunk_specs(tiles, Sq, Sk, D, causal)
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, c: (b, 0, i))
    extra_specs, extra = _extra_operands(mask, seed, dropout_p, B, H, Sq, Sk,
                                         mask_spec)
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B * H, Sq // bq, Sk // ck),
        # q, k, v, dO, O, lse
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec]
        + extra_specs,
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),   # dq
                        pltpu.VMEM((bq, 1), jnp.float32),   # lse, a column
                        pltpu.VMEM((bq, 1), jnp.float32)],  # delta, a column
        compiler_params=_compiler_params(tiles),
        interpret=interpret[0],
        name="flash_bwd_dq",
    )(qr, kr, vr, gr, out.reshape(B * H, Sq, D), lser, *extra)

    # dkv grid: (bh, k blocks, query chunks); before the diagonal the query
    # chunk's index stays at the first live one
    def q_chunk(j, c):
        if not causal or cq == Sq:
            return c
        return jnp.maximum(c, jnp.clip(j * bk - off, 0, Sq - 1) // cq)
    own = pl.BlockSpec((1, bk, D), lambda b, j, c: (b, j, 0))
    held = pl.BlockSpec((1, cq, D), lambda b, j, c: (b, q_chunk(j, c), 0))
    rows = pl.BlockSpec((1, 1, cq), lambda b, j, c: (b, 0, q_chunk(j, c)))
    extra_specs, extra = _extra_operands(
        mask, seed, dropout_p, B, H, Sq, Sk, lambda div: pl.BlockSpec(
            (1, cq, bk), lambda b, j, c: (b // div, q_chunk(j, c), j)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B * H, Sk // bk, Sq // cq),
        in_specs=[held, own, own, held, rows, rows] + extra_specs,
        out_specs=[own, own],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=_compiler_params(tiles),
        interpret=interpret[1],
        name="flash_bwd_dkv",
    )(qr, kr, vr, gr, lser, delta, *extra)
    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D), delta.reshape(B, H, Sq))


def _mask_grad(q, k, v, mask, lse, g, delta, causal, scale, block_k):
    """d(loss)/d(additive mask), chunked over k blocks (XLA): the cotangent at
    the mask-add point is p * (dp - delta) (no scale factor — the mask is
    added after the QK^T scaling). Reduced over the mask's broadcast dims."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bk = min(block_k, Sk)
    n_kb = Sk // bk
    was_3d = mask.ndim == 3
    if was_3d:
        mask = mask[:, None]
    mb, mh = mask.shape[0], mask.shape[1]
    q32 = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    lse4 = lse.reshape(B, H, Sq, 1)
    delta4 = delta.reshape(B, H, Sq, 1)

    def body(_, kb):
        k_start = kb * bk
        kb32 = jax.lax.dynamic_slice_in_dim(k, k_start, bk, 2).astype(
            jnp.float32)
        vb32 = jax.lax.dynamic_slice_in_dim(v, k_start, bk, 2).astype(
            jnp.float32)
        mblk = jax.lax.dynamic_slice_in_dim(
            mask.astype(jnp.float32), k_start, bk, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb32) * scale
        if causal:
            cm = causal_mask(Sq, bk, q_offset=Sk - Sq, k_offset=k_start)
            s = jnp.where(cm[None, None], s, _NEG_INF)
        s = s + mblk
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - lse4))
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, vb32)
        dm = p * (dp - delta4)  # [B,H,Sq,bk]
        if mh == 1:
            dm = jnp.sum(dm, axis=1, keepdims=True)
        if mb == 1:
            dm = jnp.sum(dm, axis=0, keepdims=True)
        return 0, dm

    _, blocks = jax.lax.scan(body, 0, jnp.arange(n_kb))
    dmask = jnp.concatenate(
        [blocks[i] for i in range(n_kb)], axis=-1) if n_kb > 1 else blocks[0]
    if was_3d:  # cotangent must match the primal's 3D shape
        dmask = dmask[:, 0]
    return dmask.astype(mask.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention(q, k, v, mask, seed, causal, scale, block_q, block_k,
                     dropout_p):
    out, _ = _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k,
                        dropout_p, seed)
    return out


def _flash_vjp_fwd(q, k, v, mask, seed, causal, scale, block_q, block_k,
                   dropout_p):
    out, lse = _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k,
                          dropout_p, seed)
    # named so that a per-layer recompute (KEEP_FLASH_RESIDUALS in
    # fleet/utils/recompute.py) keeps the kernel's two results and its replay
    # of the layer holds no second forward call; identities elsewhere
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, mask, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, dropout_p, res, g):
    import numpy as np
    q, k, v, mask, seed, out, lse = res
    dq, dk, dv, delta = _flash_bwd(q, k, v, mask, out, lse, g, causal, scale,
                                   block_q, block_k, dropout_p, seed)
    if mask is None:
        dmask = None
    elif dropout_p > 0.0:
        # the keep-mask lives in the TPU PRNG and is not recomputable in XLA
        # (the flash_attention wrapper routes mask+dropout to the reference
        # path; only direct _flash_attention callers can land here)
        raise NotImplementedError(
            "mask gradients are unavailable with in-kernel dropout; use "
            "flash_attention(), which falls back to the XLA reference for "
            "mask + dropout")
    else:
        dmask = _mask_grad(q, k, v, mask, lse, g, delta, causal, scale,
                           _tiles_for(q, k, mask, block_q, block_k).block_k)
    dseed = np.zeros(np.shape(seed), jax.dtypes.float0)
    return dq, dk, dv, dmask, dseed


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    force_pallas: bool = False, mask=None,
                    dropout_p: float = 0.0, dropout_seed: int = 0,
                    window: Optional[int] = None):
    """q,k,v: [B, H, S, D] jax arrays; optional additive mask [B, 1|H, Sq, Sk].
    Returns [B, H, Sq, D]. Supports rectangular (cross) attention: causal uses
    bottom-right alignment when Sq != Sk. `window=W`: a query also sees no
    key more than W - 1 positions behind it (itself included in the W); it
    rides as an additive mask, so a kernel that skips the blocks below the
    window is later work.

    Uses the Pallas kernels (fwd + dq/dkv bwd) on TPU for seqs >= 512 and
    the fused XLA reference for short sequences, indivisible lengths and the
    CPU; every reference return is counted, and on a TPU logged once per
    shape with its reason (`ops.pallas_mode`). Dropout on the Pallas path
    uses the in-kernel TPU PRNG (TPU only).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    on_cpu = pallas_mode.platform() == "cpu"
    Sq, Sk = q.shape[2], k.shape[2]
    if window is not None:
        # a key W or more positions behind its query is out of the window
        out = causal_mask(Sq, Sk, q_offset=Sk - Sq, k_offset=int(window))
        low = jnp.where(out, _NEG_INF, 0.0)[None, None]
        mask = low if mask is None else mask + low
    # why this call takes the XLA reference instead of the kernel, if it does
    reason = None
    if sequence_sharded_trace() and not force_pallas:
        mesh = getattr(_SEQ_SHARDED, "mesh", None)
        # strategy-configured; "gspmd" is the partitioner-sliced reference
        # path (no island)
        impl = getattr(_SEQ_SHARDED, "impl", "ring") or "ring"
        # ring/Ulysses need the sep axis and take no additive mask/dropout;
        # cross-attention (Sq != Sk) keeps the GSPMD-sliced reference too
        if (mesh is not None and "sep" in mesh.axis_names
                and mesh.shape["sep"] > 1 and mask is None
                and dropout_p == 0.0 and Sq == Sk and impl != "gspmd"):
            return _sequence_parallel_island(q, k, v, causal, scale, impl)
        reason = "sequence-sharded trace, no ring island"
    elif _choose_tiles(Sq, Sk, q.shape[-1], q.dtype.itemsize,
                       mask is not None, block_q, block_k) is None:
        reason = ("no tile divides the sequence" if block_q is block_k is None
                  else f"sequence not divisible by block {block_q}x{block_k}")
    elif dropout_p > 0.0 and on_cpu:
        reason = "in-kernel dropout needs the TPU PRNG"
    elif dropout_p > 0.0 and mask is not None:
        # the keep-mask lives in the TPU PRNG and cannot be recomputed in
        # XLA for d(mask), so a differentiable mask would silently get zero
        # grads — route the combination to the reference path
        reason = "additive mask + dropout"
    elif not force_pallas and on_cpu:
        reason = "cpu"
    elif not force_pallas and Sq < 512:
        reason = "query shorter than 512"
    if reason is not None:
        pallas_mode.note_reference("flash_attention", reason, q.shape,
                                   k.shape, str(q.dtype))
        key = jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.uint32)) \
            if dropout_p > 0.0 else None
        return _attention_reference(q, k, v, causal, scale, mask, dropout_p,
                                    key)
    seed = jnp.asarray(dropout_seed, jnp.int32)
    mesh = getattr(_SPMD, "mesh", None)
    if mesh is not None and mesh.size > 1:
        return _flash_spmd_island(q, k, v, mask, seed, causal, scale,
                                  block_q, block_k, dropout_p)
    return _flash_attention(q, k, v, mask, seed, causal, scale, block_q,
                            block_k, dropout_p)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention parity wrapper.
    Tensors are [B, S, H, D] in paddle convention."""
    from ..core.random import next_key
    from ..core.tensor import apply
    from ..tensor.creation import _t

    q, k, v = _t(query), _t(key), _t(value)
    pd = dropout_p if training else 0.0
    # traced seed: fresh per call in eager, threaded through jit without
    # retracing (it enters the Pallas kernels as an SMEM scalar)
    seed = jax.random.randint(next_key(), (), 0, 2 ** 31 - 1) if pd > 0 \
        else 0

    def f(qa, ka, va, *m):
        qt = jnp.swapaxes(qa, 1, 2)
        kt = jnp.swapaxes(ka, 1, 2)
        vt = jnp.swapaxes(va, 1, 2)
        out = flash_attention(qt, kt, vt, causal=is_causal,
                              mask=m[0] if m else None, dropout_p=pd,
                              dropout_seed=seed)
        return jnp.swapaxes(out, 1, 2)

    if attn_mask is not None:
        return apply(f, q, k, v, _t(attn_mask))
    return apply(f, q, k, v)


# ---- static-cache decode primitives (ISSUE 5: slot-paged LLM decode) ----
# One numeric path shared by GPTAttention/LlamaAttention decode and the
# serving LLM engine, so one-shot generate() and continuous batching are
# bit-identical per row: masked columns score _NEG_INF, and
# exp(-1e30 - row_max) underflows to exact fp32 0.0, so padded cache tail
# and foreign batch rows contribute nothing to any softmax numerator or
# denominator.

class TokenPack(NamedTuple):
    """Where the live tokens of a `[slots N, chunk C]` step sit in one
    `[T, 1]` block, and back (`token_pack` builds it inside the step).

    A serving step gives every slot a C-wide row and `adv[n]` says how many
    of its columns hold a token: one for a decode row, none for a free
    slot. Whatever is a function of one token (embedding, norms,
    projections, MLP or experts, the head, the sampler) runs on the packed
    block, T rows of width one, token t at its own position `pos[t]`: to
    that code a packed step is a batch of T one-token sequences. Attention
    alone needs a token's slot (its cache row, its page table, its chunk
    mates), so the decoder stacks `unpack` q/k/v into `[N, C, ·]` just
    before RoPE, the KV write and `decode_attention`, run those at
    `slot_pos` exactly as an unpacked step does, and `pack` the context
    again. A latent layer that attends to every key (`models/deepseek.py`)
    unpacks only what it writes to the cache: its queries stay packed
    through RoPE (each token at its own `pos`) and the walk, which finds a
    slot's queries by their first packed position, `dst[:, 0]`
    (`packed_latent_attention`). Packed positions past the live ones
    repeat slot 0's column 0 and columns past `adv` read packed position
    T - 1: both hold finite values nobody reads, as the padding of an
    unpacked step does.
    """
    src: jax.Array       # [T] n * C + c of the column token t holds
    dst: jax.Array       # [N, C] packed position of column c of slot n
    slot: jax.Array      # [T] the slot n of token t
    col: jax.Array       # [T] its column c in that slot's row
    pos: jax.Array       # [T] its absolute position, slot_pos[n] + c
    live: jax.Array      # [T] bool: t < sum(adv)
    last: jax.Array      # [N] packed position of slot n's last live column
    slot_pos: jax.Array  # [N] each slot's write offset (the step's `pos`)

    def pack(self, x):
        """`[N, C, ...]` -> `[T, 1, ...]`."""
        flat = x.reshape((-1,) + x.shape[2:])
        return jnp.take(flat, self.src, axis=0, mode="clip")[:, None]

    def unpack(self, x):
        """`[T, 1, ...]` -> `[N, C, ...]`."""
        return jnp.take(x[:, 0], self.dst, axis=0, mode="clip")


def token_pack(adv, pos, chunk: int, step_tokens: int) -> TokenPack:
    """The pack index of one step from its `adv [N]` and `pos [N]` alone:
    slot n's live columns follow slot n - 1's (a cumulative sum; every
    shape is static). The caller keeps `sum(adv) <= step_tokens`."""
    adv = adv.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    end = jnp.cumsum(adv)
    start = end - adv
    t = jnp.arange(step_tokens, dtype=jnp.int32)
    live = t < end[-1]
    # slots that end at or before t: t's own slot (free slots are passed)
    slot = jnp.where(live, jnp.sum(t[:, None] >= end[None, :], axis=1,
                                   dtype=jnp.int32), 0)
    col = jnp.where(live, t - start[slot], 0)
    c = jnp.arange(chunk, dtype=jnp.int32)
    dst = jnp.where(c[None, :] < adv[:, None], start[:, None] + c[None, :],
                    step_tokens - 1)
    return TokenPack(src=slot * chunk + col, dst=dst, slot=slot, col=col,
                     pos=pos[slot] + col, live=live,
                     last=jnp.maximum(end - 1, 0), slot_pos=pos)


def take_positions(block, positions):
    """Rows `positions [E]` of `block [..., H]` viewed `[positions, H]`, as
    `[E, H]`: a serving step's emission rows, the only ones whose hidden
    state the vocabulary head reads (`LLMEngine._step`). Flat positions:
    `TokenPack.dst[n, c]` into a packed block, `n * C + c` into `[N, C]`."""
    return jnp.take(block.reshape((-1, block.shape[-1])), positions, axis=0,
                    mode="clip")


class PagedView(NamedTuple):
    """The page operand of one serving step, as every layer of the model is
    handed it (`paged=`): where each row's pages lie and how long the row is
    once the step has run. `SlotPagedKVPool.view` builds it inside the
    step; the three integers are Python integers the trace closes over."""
    table: Optional[jax.Array]   # [rows, pages_per_row] page ids; None: each
    #                              row's own pages in their order (a ring)
    seq_lens: jax.Array          # [rows] a row's length after this step
    block_len: int               # tokens a page
    pages_per_row: int           # pages a row's table addresses
    ring_pages: Optional[int] = None   # pages of a window layer's ring, on
    #                              a pool that keeps one

    @property
    def ring(self) -> int:
        """Columns of a window layer's ring."""
        return int(self.ring_pages) * int(self.block_len)

    @property
    def positions(self) -> int:
        """Positions a row can hold: what a rotary table must reach, short
        of the chunk-wide stripe a free row writes past it."""
        return int(self.pages_per_row) * int(self.block_len)

    def window_view(self) -> "PagedView":
        """A window layer's view of itself: no table, the ring's pages."""
        return PagedView(None, self.seq_lens, self.block_len,
                         self.ring_pages)

    def advance(self, pos):
        """`[rows]`: the columns of a row at `pos [rows]` that hold a token
        (none for a free slot)."""
        return jnp.reshape(self.seq_lens, (-1,)) - pos

    def live(self, pos, width: int):
        """`[rows, width]` bool: column t of a row at `pos [rows]` holds a
        token while `pos + t` is short of the row's length after the step;
        the rest of a decode row, and all of a free slot, is padding."""
        t = jnp.arange(width, dtype=jnp.int32)
        return jnp.reshape(pos, (-1, 1)) + t \
            < jnp.reshape(self.seq_lens, (-1, 1))


def _ring_write(cache, new, pos, ring: int):
    """One row: `new [Hkv, T, D]` at column `pos mod ring` of a ring of
    `ring` columns that lies in `cache [Hkv, ring + T, D]`. The stripe is
    written where it starts (its end may run into the T columns behind the
    ring), then what ran over is brought round to the ring's first
    columns: a write that straddles the ring's end is split in two."""
    from jax import lax
    T = new.shape[1]
    c0 = pos % ring
    cache = lax.dynamic_update_slice(cache, new, (0, c0, 0))
    over = c0 + T - ring                                 # columns past it
    wrapped = jnp.arange(T, dtype=jnp.int32)[None, :, None] < over
    head = jnp.where(wrapped, cache[:, ring:ring + T], cache[:, :T])
    return cache.at[:, :T].set(head)


def _row_writes(k_cache, v_cache, k_new, v_new, pos, ring=None):
    """`update_kv_cache` at a `[B]` position vector as a vmapped
    `dynamic_update_slice` (`_ring_write` on a ring): the CPU path, and
    what the `kv_write` kernel is held to, bit for bit."""
    from jax import lax
    pos = jnp.broadcast_to(pos, k_cache.shape[:1]).astype(jnp.int32)
    if ring is None:
        write = jax.vmap(
            lambda c, u, p: lax.dynamic_update_slice(c, u, (0, p, 0)))
    else:
        write = jax.vmap(lambda c, u, p: _ring_write(c, u, p, int(ring)))
    return write(k_cache, k_new, pos), write(v_cache, v_new, pos)


def update_kv_cache(k_cache, v_cache, k_new, v_new, pos, ring=None):
    """Write k/v [B, Hkv, T, D] into static [B, Hkv, L, D] caches at `pos`.

    `pos` is the absolute position of the first new token: a scalar writes
    every row at the same offset (the batch-locked generate() path); a [B]
    vector writes each row at its own offset (slot-paged decode, where each
    slot sits at a different sequence length). All shapes stay static:
    vector writes are a vmapped dynamic_update_slice on the CPU
    (`_row_writes`, also the parity reference) and, on a TPU, the
    `kv_write` kernel (`ops/kv_write.py`: K's and V's stripes of every row
    in one call, the slabs aliased), which leaves the same bits in every
    column.

    `ring` (a window layer's slab in the serving pool): the first `ring`
    columns of the cache are a ring, position p lives at column `p mod
    ring`, and L >= ring + T (`_ring_write`).
    """
    from jax import lax
    pos = jnp.asarray(pos)
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    if ring is not None and k_cache.shape[2] < ring + k_new.shape[2]:
        raise ValueError(
            f"a ring of {ring} columns written {k_new.shape[2]} at a "
            f"time needs {ring + k_new.shape[2]} columns, the cache "
            f"has {k_cache.shape[2]}")
    if ring is None and pos.ndim == 0:
        return (lax.dynamic_update_slice(k_cache, k_new, (0, 0, pos, 0)),
                lax.dynamic_update_slice(v_cache, v_new, (0, 0, pos, 0)))
    if pallas_mode.platform() == "cpu":
        return _row_writes(k_cache, v_cache, k_new, v_new, pos, ring)
    # a stripe a row: for a TPU XLA makes the vmapped write a loop of one
    # trip a row and cache
    if kvw.kv_write_supported(k_cache, v_cache, k_new, v_new, ring):
        return kvw.kv_write(k_cache, v_cache, k_new, v_new, pos,
                            ring=None if ring is None else int(ring))
    pallas_mode.note_reference(
        kvw.KERNEL, "a slab the aligned windows do not fit",
        k_cache.shape, v_cache.shape, k_new.shape[2], ring)
    return _row_writes(k_cache, v_cache, k_new, v_new, pos, ring)


def update_caches(caches, news, pos):
    """`update_kv_cache` for a layer that keeps more than two slabs a token
    (`caches[j] [B, Hkv, L, Dj]`, `news[j] [B, Hkv, T, Dj]`, no ring): a
    sparse-attention layer's latent, rotary key and index key. One
    `kv_write` call takes them all on a TPU."""
    from jax import lax
    pos = jnp.asarray(pos)
    news = tuple(n.astype(c.dtype) for n, c in zip(news, caches))
    if pos.ndim == 0:
        return tuple(lax.dynamic_update_slice(c, n, (0, 0, pos, 0))
                     for c, n in zip(caches, news))
    if pallas_mode.platform() != "cpu":
        if kvw.kv_write_many_supported(caches, news):
            return kvw.kv_write_many(caches, news, pos)
        pallas_mode.note_reference(
            kvw.KERNEL, "a slab the aligned windows do not fit",
            *(c.shape for c in caches), news[0].shape[2])
    pos = jnp.broadcast_to(pos, caches[0].shape[:1]).astype(jnp.int32)
    write = jax.vmap(lambda c, u, p: lax.dynamic_update_slice(c, u, (0, p, 0)))
    return tuple(write(c, n, pos) for c, n in zip(caches, news))


def decode_attention(q, k_cache, v_cache, pos, scale=None, paged=None,
                     window=None, q_rope=None, sel=None):
    """Length-masked attention of q [B, H, T, D] over padded static caches
    [B, Hkv, L, D] (GQA: Hkv divides H; kv heads are repeated).

    `pos` — scalar or [B] — is the absolute position of q's first token in
    each row; cache columns beyond pos+t are masked to _NEG_INF, so slots
    longer than a row's real length (and garbage beyond it) never perturb
    the output.

    Both shapes route through `ops.paged_attention.ragged_paged_attention`
    (ISSUE 7): with `paged=None` each row attends its own contiguous cache
    via a trivial block table at DEFAULT_KV_BLOCK; `paged` (a `PagedView`)
    addresses slot-pool pages directly (the serving engine's
    chunked-prefill/decode mixed dispatch). One numeric path means
    continuous-batched streams stay bit-identical to one-shot generate()
    whenever both sides use the same kv block size — the flash-accumulation
    grouping, and therefore the bits, depend on block_len alone.

    `window=W`: a query sees the W keys up to itself. With `paged` the
    caches are then rings (`update_kv_cache(ring=)`) and `paged` the
    layer's `PagedView.window_view()`; left as None the contiguous cache is
    walked from the window's first block.
    Either way the blocks walked are the logical ones, so both give the
    same bits at one block size.

    `q_rope`: the caches are a latent and its rotary key and q has the key
    projection absorbed (`ragged_paged_attention(q_rope=)`); `scale` is
    then required.

    `sel` (an `ops.index_select.Selection`, with `q_rope`): the softmax is
    over the selected keys alone (`paged_attention.sparse_latent_attention`;
    the selection was made against the same `paged`, so its columns are
    this walk's logical columns).
    """
    from .paged_attention import (DEFAULT_KV_BLOCK, ragged_paged_attention,
                                  sparse_latent_attention)
    B, H, T, D = q.shape
    if scale is None and q_rope is None:
        scale = 1.0 / (D ** 0.5)
    walk = ragged_paged_attention if sel is None else functools.partial(
        sparse_latent_attention, sel=sel)
    if paged is not None:
        # pool slabs may carry chunk write-padding past the page region,
        # so the caller names the addressable page geometry explicitly
        return walk(
            q, k_cache, v_cache, paged.table, paged.seq_lens,
            jnp.asarray(pos), block_len=int(paged.block_len),
            pages_per_row=int(paged.pages_per_row), scale=scale,
            window=window, q_rope=q_rope)
    (k_cache, v_cache), table, seq_lens, q_pos, nb = contiguous_paged(
        (k_cache, v_cache), pos, T)
    return walk(q, k_cache, v_cache, table, seq_lens, q_pos,
                block_len=DEFAULT_KV_BLOCK, pages_per_row=nb, scale=scale,
                window=window, q_rope=q_rope)


def decode_attention_packed(q, q_rope, c_cache, r_cache, pos, starts,
                            width: int, scale: float, paged=None):
    """`decode_attention(q_rope=)` for a layer whose queries stay
    token-major, `q [P, H, R]` and `q_rope [P, H, Dr]`: row b's are the
    positions from `starts[b]`, `width` at most
    (`ops.paged_attention.packed_latent_attention`). `pos`, `paged` and the
    caches as `decode_attention`'s; the result is `[P, H, R]`."""
    from . import paged_attention as pa
    if paged is not None:
        return pa.packed_latent_attention(
            q, q_rope, c_cache, r_cache, paged.table, paged.seq_lens,
            jnp.asarray(pos), starts, width=width,
            block_len=int(paged.block_len),
            pages_per_row=int(paged.pages_per_row), scale=scale)
    (c_cache, r_cache), table, seq_lens, q_pos, nb = contiguous_paged(
        (c_cache, r_cache), pos, width)
    return pa.packed_latent_attention(
        q, q_rope, c_cache, r_cache, table, seq_lens, q_pos, starts,
        width=width, block_len=pa.DEFAULT_KV_BLOCK, pages_per_row=nb,
        scale=scale)


def contiguous_paged(caches, pos, T: int):
    """A contiguous per-row cache as the paged walks read one: (the caches
    padded to whole pages of `DEFAULT_KV_BLOCK`, the trivial block table,
    seq_lens `pos + T`, q_pos, pages a row)."""
    from .paged_attention import DEFAULT_KV_BLOCK, trivial_block_table
    B, L = caches[0].shape[0], caches[0].shape[2]
    table, nb = trivial_block_table(B, L, DEFAULT_KV_BLOCK)
    pad = nb * DEFAULT_KV_BLOCK - L
    if pad:
        caches = tuple(jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for c in caches)
    q_pos = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)
    return tuple(caches), table, q_pos + T, q_pos, nb
