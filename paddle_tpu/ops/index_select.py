"""Learned sparse attention's selection (DeepSeek-V3.2's "lightning
indexer", as GLM-5.2's `glm_moe_dsa` has it): which keys a query attends to.

A layer with an indexer keeps, beside its latent pages, one *index key* of
`D` = 128 columns a token (`k_index [N, 1, L_slab, D]`, paged exactly as the
latent slabs are: same block table, same columns). For a query token t with
`Hi` = 32 index queries `q[t, j]` and head weights `w[t, j]`:

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k_index[s])     s <= t, float32
    S_t     = the `topk` positions s of largest I[t, s]        (all while
              fewer than `topk` are visible); ties to the lower position

`select` computes both and returns a `Selection`, which
`paged_attention.sparse_latent_attention` consumes in this layer and in the
layers that share its selection. Two kernels, each with a plain-XLA twin that
the CPU runs and the kernel is held to:

- **`index_score`** (grid (B,), one slot's walk a step): the loop of the
  paged kernels over the row's live groups of 128 keys, each page found
  through its own block-table entry and brought by its own copy into a
  double-buffered `[128, D]` scratch; a group is one `[Hi * T, D] x [D, 128]`
  product, a ReLU, a multiply by the head weights and a sum over the heads
  (the `[Hi, T, 128]` view's leading axis), masked causally and written at
  the group's columns of the row's `[T, L]` float32 block, which starts at
  -inf: what no group wrote is not a key.
- **`index_topk`** (grid (B,)): the *exact* top-k of each of a row's T score
  vectors as a 0/1 mask, without a sort. Scores are read as integers whose
  order is the floats' (`_ordered`), the k-th largest is built bit by bit
  from the top (32 counts of `x >= candidate` over the `[T, L]` block in
  VMEM), and where more keys tie with it than there is room for, a second
  search of 16 counts finds the column up to which ties are taken: the lower
  positions. A `lax.top_k` over 36,864 columns for each of 256 queries is a
  sort of 9.4 M elements a layer and step; this is 48 passes over 2.4 MB of
  VMEM a row.

A decode row (one live column) also gets its selection as `topk` positions
(`lax.top_k` of that one column: 16 x 36,864 elements), because its
attention gathers the selected tokens instead of walking the row's pages.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .attention import _dot, contiguous_paged
from .paged_attention import DEFAULT_KV_BLOCK, _GROUP_KEYS, _group_pages

SCORE_KERNEL = "index_score"
TOPK_KERNEL = "index_topk"

_INT_MIN = -2 ** 31
# `_ordered(-inf)`: below every score of a visible key
_NO_KEY = (0x007FFFFF ^ 0x80000000) - 2 ** 32


class Selection(NamedTuple):
    """What a layer with an indexer hands the layers that share it, for one
    step: `mask [B, T, L]` float32, 1 where query column t of row b attends
    to logical column s; `idx [B, K]` int32, the positions column 0
    selected, best first, and `count [B]`, how many of them are keys (the
    rest of `idx` is padding): the form a decode row's gather reads."""
    mask: jax.Array
    idx: jax.Array
    count: jax.Array


def _ordered(scores):
    """float32 -> int32 whose signed order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    flipped = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jax.lax.bitcast_convert_type(flipped ^ jnp.uint32(1 << 31),
                                        jnp.int32)


# ---- the scores ----

def _scores_reference(q, w, k_cache, block_table, seq_lens, q_pos,
                      block_len: int, pages_per_row: int):
    """Plain XLA: the row's keys gathered page by page, one product."""
    B, Hi, T, D = q.shape
    g = jnp.maximum(block_table, 0)                           # [B, nb]
    rows = g // pages_per_row
    cols = (g % pages_per_row * block_len)[..., None] \
        + jnp.arange(block_len, dtype=jnp.int32)               # [B, nb, bl]
    keys = k_cache[rows[..., None], 0, cols].reshape(B, -1, D)  # [B, L, D]
    s = jnp.einsum("bhtd,bsd->bhts", q, keys,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("bhts,bht->bts", jnp.maximum(s, 0.0),
                        w.astype(jnp.float32))
    col = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, None]
    t = q_pos[:, None, None] + jnp.arange(T, dtype=jnp.int32)[None, :, None]
    keep = (col <= t) & (col < seq_lens[:, None, None])
    return jnp.where(keep, scores, -jnp.inf)


def _score_kernel(table_ref, lens_ref, pos_ref, q_ref, w_ref, k_hbm, o_ref,
                  kbuf, sem, *, block_len, pages, pages_per_row, Hi, T):
    """One slot's walk: q_ref [1, Hi * T, D] (row h * T + t), w_ref
    [1, Hi * T, 1], the index-key slab in HBM, o_ref [1, T, L]."""
    b = pl.program_id(0)
    keys = pages * block_len
    n_blocks = table_ref.shape[1]
    n = jnp.minimum((lens_ref[b] + keys - 1) // keys,
                    -(-n_blocks * block_len // keys))
    last = jnp.maximum(lens_ref[b] - 1, 0) // block_len

    def fetch(j, slot):
        for p in range(pages):
            blk = jnp.minimum(jnp.minimum(j * pages + p, last), n_blocks - 1)
            at = table_ref[b, blk]
            r = at // pages_per_row
            c = pl.multiple_of(at % pages_per_row * block_len, block_len)
            pltpu.make_async_copy(
                k_hbm.at[r, :, pl.ds(c, block_len), :],
                kbuf.at[slot, :, pl.ds(p * block_len, block_len), :],
                sem.at[slot]).start()

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(n > 0)
    def _():
        fetch(0, 0)

    t = jax.lax.broadcasted_iota(jnp.int32, (T, keys), 0) + pos_ref[b]
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, keys), 1)

    def group(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n)
        def _():
            fetch(i + 1, 1 - slot)

        # the group's copies share the buffer's semaphore, which counts
        # bytes: one wait for the buffer's size takes all
        pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot],
                              sem.at[slot]).wait()
        s = _dot(q_ref[0], kbuf[slot, 0], 1, 1)               # [Hi*T, keys]
        s = jnp.maximum(s, 0.0) * w_ref[0]
        scores = jnp.sum(s.reshape(Hi, T, keys), axis=0)      # [T, keys]
        col = i * keys + lane
        keep = (col <= t) & (col < lens_ref[b])
        at = pl.multiple_of(i * keys, keys)
        o_ref[0, :, pl.ds(at, keys)] = jnp.where(keep, scores, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n, group, None)


@functools.partial(jax.jit, static_argnames=(
    "block_len", "pages_per_row", "interpret"))
def _score_call(q, w, k_cache, block_table, seq_lens, q_pos, *, block_len,
                pages_per_row, interpret):
    B, Hi, T, D = q.shape
    P = _group_pages(block_len)
    L = block_table.shape[1] * block_len
    Lp = -(-L // _GROUP_KEYS) * _GROUP_KEYS     # whole groups of columns

    def row(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b, table_ref, lens_ref, pos_ref:
                            (b, 0, 0))

    out = pl.pallas_call(
        functools.partial(_score_kernel, block_len=block_len, pages=P,
                          pages_per_row=pages_per_row, Hi=Hi, T=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[row((Hi * T, D)), row((Hi * T, 1)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row((T, Lp)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, P * block_len, D), k_cache.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, T, Lp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=SCORE_KERNEL,
    )(jnp.maximum(block_table, 0), seq_lens, q_pos,
      q.reshape(B, Hi * T, D),
      w.astype(jnp.float32).reshape(B, Hi * T, 1), k_cache)
    return out[:, :, :L]


def index_scores(q, w, k_cache, block_table, seq_lens, q_pos, *,
                 block_len: int, pages_per_row: int, impl: str = None):
    """I [B, T, L] float32 (module docstring) of index queries q [B, Hi, T,
    D] with head weights w [B, Hi, T] over the index keys `k_cache [N, 1,
    L_slab, D]` that `block_table [B, max_blocks]` names; L = `max_blocks *
    block_len` logical columns, -inf where column s is past query t
    (`q_pos[b] + t`) or past the row's `seq_lens[b]`. impl: None = plain XLA
    on the CPU, the kernel on a TPU; or "reference" / "pallas"."""
    block_table = jnp.asarray(block_table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if impl is None:
        impl = "reference" if pallas_mode.platform() == "cpu" else "pallas"
    if impl == "reference":
        pallas_mode.count(SCORE_KERNEL, "scan")
        return _scores_reference(q, w, k_cache, block_table, seq_lens,
                                 q_pos, block_len, pages_per_row)
    T = q.shape[2]
    Tp = -(-T // 8) * 8               # whole float32 sublane tiles of rows
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, Tp - T)))
    pallas_mode.note_tiling(SCORE_KERNEL, grid=(q.shape[0],),
                            pages=_group_pages(block_len),
                            rows=q.shape[1] * Tp)
    return _score_call(q, w, k_cache, block_table, seq_lens, q_pos,
                       block_len=block_len, pages_per_row=pages_per_row,
                       interpret=pallas_mode.interpret(SCORE_KERNEL))[:, :T]


# ---- the exact top-k, as a mask ----

def _topk_reference(scores, k: int):
    """Plain XLA: the k-th largest by `lax.top_k`, ties by a running count."""
    L = scores.shape[-1]
    kth = jax.lax.top_k(scores, min(k, L))[0][..., -1:]
    above = scores > kth
    room = min(k, L) - jnp.sum(above, -1, keepdims=True)
    tie = scores == kth
    keep = above | (tie & (jnp.cumsum(tie, -1) <= room))
    return (keep & (scores > -jnp.inf)).astype(jnp.float32)


def _topk_kernel(x_ref, o_ref, *, k):
    """x_ref [1, T, L] int32 (`_ordered` scores), o_ref [1, T, L] float32."""
    x = x_ref[0]
    T, L = x.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1)

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

    def value_bit(i, best):
        # `best` holds the k-th largest's top i bits, in the unsigned view
        # whose order is the scores' (signed view: the top bit flipped)
        cand = best | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(x >= (cand ^ _INT_MIN)) >= k
        return jnp.where(enough, cand, best)

    kth = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros((T, 1), jnp.int32)) ^ _INT_MIN
    above = x > kth
    tie = x == kth
    room = k - count(above)

    def column_bit(i, upto):
        # the largest column bound under which the ties still fit
        cand = upto | jnp.left_shift(jnp.int32(1), 15 - i)
        fits = count(tie & (col < cand)) <= room
        return jnp.where(fits, cand, upto)

    upto = jax.lax.fori_loop(0, 16, column_bit,
                             jnp.zeros((T, 1), jnp.int32))
    keep = (above | (tie & (col < upto))) & (x > _NO_KEY)
    o_ref[0] = keep.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _topk_call(scores, *, k, interpret):
    B, T, L = scores.shape

    def row():
        return pl.BlockSpec((1, T, L), lambda b: (b, 0, 0))

    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(B,), in_specs=[row()], out_specs=row(),
        out_shape=jax.ShapeDtypeStruct((B, T, L), jnp.float32),
        # a row's block in and out, twice each for the pipeline, and the
        # counts' temporaries: 2.4 MB apiece at 16 x 36,864
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=TOPK_KERNEL,
    )(_ordered(scores))


def topk_mask(scores, k: int, impl: str = None):
    """[B, T, L] float32 scores (-inf: no key) -> [B, T, L] float32, 1 at
    the k largest of each `[L]` vector (at every key where fewer than k
    are), ties to the lower column. Exact. impl as `index_scores`'."""
    if scores.shape[-1] >= 1 << 16:
        raise ValueError(f"{scores.shape[-1]} columns: the tie search "
                         "counts columns in 16 bits")
    if impl is None:
        impl = "reference" if pallas_mode.platform() == "cpu" else "pallas"
    if impl == "reference":
        pallas_mode.count(TOPK_KERNEL, "scan")
        return _topk_reference(scores, k)
    B, T, L = scores.shape
    Tp, Lp = -(-T // 8) * 8, -(-L // 128) * 128
    if (Tp, Lp) != (T, L):
        scores = jnp.pad(scores, ((0, 0), (0, Tp - T), (0, Lp - L)),
                         constant_values=-jnp.inf)
    pallas_mode.note_tiling(TOPK_KERNEL, grid=(B,), rows=Tp, columns=Lp)
    return _topk_call(scores, k=int(k),
                      interpret=pallas_mode.interpret(TOPK_KERNEL))[:, :T, :L]


# ---- both, for a layer ----

def select(q, w, k_cache, pos, topk: int, paged=None) -> Selection:
    """The selection of one step's queries (module docstring): q [B, Hi, T,
    D], w [B, Hi, T], the layer's index-key cache `[N, 1, L_slab, D]`
    holding this step's keys already, `pos` and `paged` as
    `ops.attention.decode_attention` takes them."""
    B, _, T, _ = q.shape
    if paged is not None:
        block_table, seq_lens = paged.table, paged.seq_lens
        block_len, pages_per_row = paged.block_len, paged.pages_per_row
        q_pos = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)
    else:
        block_len = DEFAULT_KV_BLOCK
        (k_cache,), block_table, seq_lens, q_pos, pages_per_row = \
            contiguous_paged((k_cache,), pos, T)
    scores = index_scores(q, w, k_cache, block_table, seq_lens, q_pos,
                          block_len=int(block_len),
                          pages_per_row=int(pages_per_row))
    mask = topk_mask(scores, topk)
    # column 0 as positions, best first: what a decode row's gather reads
    # (`lax.top_k` takes the lower index of two equal scores first)
    k = min(int(topk), scores.shape[-1])
    best, idx = jax.lax.top_k(scores[:, 0], k)
    count = jnp.sum(best > -jnp.inf, axis=-1).astype(jnp.int32)
    return Selection(mask, idx.astype(jnp.int32), count)
