"""Ragged paged attention (ISSUE 7 tentpole; PAPERS.md arxiv 2604.15464).

One attention primitive for every cached-decode query shape: each batch
row attends over the KV blocks its *block table* names, masked to its own
ragged length — so a single dispatch serves mixed prefill-chunk rows
(query width C, dozens of occupied blocks) and decode rows (1 real query
token) at once. This is what lets the LLM engine replace its
per-pow2-bucket prefill executable zoo with chunked prefill folded into
the decode step (serving/llm/llm_engine.py).

Layout contract — shared with `SlotPagedKVPool`:

    k_cache/v_cache  [N, Hkv, L_slab, D]   static slabs, one row per slot
    pages            the first pages_per_row*block_len columns of each row,
                     cut into `block_len`-wide pages; page id
                     g = row * pages_per_row + col_page
    block_table      [B, max_blocks] int32: logical block j of batch row b
                     lives in page table[b, j] (-1 pads; padded entries are
                     clamped to page 0 and fully masked)
    seq_lens         [B] int32: KV columns >= seq_lens[b] are masked
                     (garbage beyond a row's committed+incoming tokens)
    q_pos            [B] int32: absolute position of q's first token in
                     row b; causal mask is col <= q_pos[b] + t

Two implementations of one function; both read the slabs as stored (a
page is `block_len` columns of one slab row, for every KV head at once),
so nothing slices, transposes or copies the pool on the way in. They run
the same masked-score -> exact-zero-p -> alpha-rescale arithmetic in the
same precision (operands as stored, float32 scores, softmax state and
accumulator) over every key a query may see and no other, but group the
accumulation differently — a page a step in the scan, 128 keys a step in
the kernel — so they agree to a documented tolerance (1e-6 in float32 at
the tests' shapes, chip_smoke.py's 2e-2 in bf16 on the chip), not by op
sequence:

- `_scan_impl` — plain XLA `lax.scan` over logical blocks, one page a
  step. What `impl=None` picks on the CPU: interpret-mode Pallas unrolls
  every grid cell into the jaxpr, which makes tier-1 compile times explode,
  while this path compiles once. Also the parity reference the kernel is
  checked against on the chip (chip_smoke.py).
- `_pallas_impl` — the TPU kernel, what `impl=None` picks on a TPU: grid
  (B, G), one step per (slot, head group), and inside it a loop over the
  row's live *groups*. A group is the `_group_pages(block_len)` consecutive
  logical pages that hold `_GROUP_KEYS` = 128 keys, one lane tile (8 pages
  of 16, 16 of 8), aligned on absolute logical blocks (group g = blocks
  [g*P, (g+1)*P)): one masked score matrix `[heads, rows, 128]`, one
  online-softmax update and one p.V product a group, where a page a step
  left seven eighths of the lanes and of the MXU's width idle and paid a
  grid step's fixed cost sixteen keys at a time. The slabs stay in HBM
  (`memory_space=ANY`); each page of a group is still found through its
  own block-table entry and brought by its own async copy into a
  double-buffered `[heads, 128, D]` scratch, the next group (or the next
  grid step's first) in flight while one is computed. The copies are
  issued at one place in the body, by a loop over the group's pages (eight
  written out a pass), and awaited by one wait a buffer (its semaphore counts bytes), so the body a
  process traces and lowers is a few hundred equations at either
  `block_len`; and the `pallas_call` sits under one module-level `jax.jit`
  whose integers are static (`_paged_call`), so the call sites of a traced
  program that agree on shapes and window (a step's layers) share one
  jaxpr and the lowered module holds one kernel body for them. Both
  matter because tracing and lowering run in every process before the
  compile cache can be asked (PR 32's unrolled copies, a body a layer: +15
  s of an engine's start). A KV head's `n_rep`
  query heads are folded into the rows of its q tile (`q` viewed as
  `[B, Hkv, n_rep*Tq, D]`: row r is token `r mod Tq`), so a GQA group
  reads its pages once. The block table / lengths / positions are
  scalar-prefetched; the loop ends with the group that holds the row's
  length ("only over occupied KV blocks"), and in that group a page past
  the length is the last live page again, masked by column. `_choose_tile`
  takes the tile from the shapes and one VMEM budget: every head in one
  tile (G = 1) at the engine's shapes, fewer KV heads or a part of one GQA
  group for a whole-prompt prefill of hundreds of rows — heads, never
  keys: the group is a constant of the kernel, so the accumulation
  grouping follows from nothing a caller chooses, `block_len` included.
  `pallas_mode.KERNEL_TILINGS` records the choice of each trace (`grid`,
  `groups` = the most a row's loop takes, `pages`, `heads`, `rows`,
  `one_column_rows`).
  Mosaic (jax 0.9.0 / libtpu 0.0.34, v5e) lowers it in bf16 at `block_len`
  8 and 16, query widths 1 to 2,048, MHA, GQA and 20:1 multi-query (a
  one-token row folds 20 rows, 32 in packed bf16 tiles: 37.5% of them pad;
  a step's 16-wide rows fold 320, whole tiles) — with the 8-row bf16 page
  and the 1-row q tile; tests/test_mosaic_aot.py pins those and the cells'.

A row with one live column (PR 48). A step's decode rows, and a prompt's
one-token tail, hold one live column of the tile's `Tq`: fifteen sixteenths
of the rows a group computes would be read by nobody. Where the tile is
smaller with one column than with all of them (`_one_column_rows`, from the
shapes alone: `Tq` > 1, no `sel`, and `fold` rows take fewer packed sublane
tiles than `fold * Tq`: every GQA and multi-query tile of a step,
not an MHA tile in bf16, whose sixteen rows are one packed tile either way;
a dense latent layer's tile always, in its own way: "Packed queries" below)
the kernel's trace holds the group's arithmetic twice, each behind a
`pl.when` on `lens - pos == 1`, read on the scalar core per grid step: such
a row takes column 0 of its q tile out once, into `[heads, fold, D]`
scratch, runs its groups over `fold` rows a head in the first `fold` rows
of the softmax state and the accumulator, and writes column 0's rows of
its o tile, zeros in the dead columns'. Every other row (a chunk, a verify
window, a whole prompt) runs the wide body. The grid, the copies, the wait
and the groups' boundaries are shared, and a (query, key) pair goes through
the same arithmetic in either, so a live position's bits do not depend on
which body its row took (on a TPU; on the CPU the interpreted kernel's
products are XLA's CPU dots, which round a float32 tile of one row
otherwise). `KERNEL_TILINGS`' `one_column_rows` is `fold` for a trace that
holds both bodies and 0 for one that holds the wide one alone; no caller
chooses.

A window (PR 31). With `window=W` a query at position p sees the W keys
`p - W < col <= p` (itself included) and the cache may be a *ring*: logical
block j lives in page `table[b, j mod R]`, R = `block_table.shape[1]`
pages a row (position p at ring column `p mod (R * block_len)`; a
contiguous cache is the ring that never wraps). The walk then starts at the
first block that cuts the row's window (the kernel: at the group that holds
it) and takes at most `ceil((W + Tq) / block_len) + 1` scan steps
(`ceil((W + Tq - 1) / 128) + 1` groups) whatever the row's length, under
its own `pallas_call` name `paged_window`, so that a trace tells its time
from the full walk's `paged_attention`. The mask is taken on logical
columns and a ring page is found per page (`(g*P + p) mod R`: R need be no
multiple of P), so a ring page visited under two logical blocks (the
walk's first and last may share one) shows each its own columns.

A latent cache (PR 36). With `q_rope=` the two slabs are multi-head latent
attention's: `c [N, 1, L_slab, R]`, the compressed latent every head's keys
and values are projections of, and `r [N, 1, L_slab, Dr]`, the one rotary
key the heads share. The caller has absorbed the key projection into its
queries (`q [B, H, Tq, R]`) and applies the value projection to the result,
so a step's scores are `scale * (q . c + q_rope . r)` (one masked matrix
from two products), its values the latent page itself, read once: the H
query heads are one "GQA group" over one KV head whose K is 576 wide and
whose V is its first 512 columns, already in VMEM. The same walk, pipeline
and masking under the `pallas_call` name `paged_latent`; `_choose_tile`
folds as many heads into a tile's rows as the budget holds (32 x 16 rows at
the serve cell's shape, so G = 2 and the small page is fetched twice). In
the kernel this head-major form is the sparse layers' alone ("A
selection"); a layer that attends to every key has the next one.

Packed queries (PR 50). A dense latent layer's queries never leave the
token block they were projected on (`packed_latent_attention`): `q [P, H,
R]` and `q_rope [P, H, Dr]` are token-major, a `start` a row (scalar-
prefetched beside table / lens / pos) names the row's first position, and
the result comes back `[P, H, R]`. Under a `TokenPack` P is the step's
packed block (512 positions for 256 slots x 16 columns, and `Tq` - 1 of
pad for the last row's window); an engine that does not pack hands its
`[N, C, .]` block viewed `[N x C, .]`, row n from n x C; the head-major
entry (`ragged_paged_attention(q_rope=)`, no selection) transposes into it.
So RoPE and the two absorbed products round the walk run once a packed
position, not once a column of every slot. In that layout the token axis
is the array's leading axis and the tiled axes are `(H, width)`: a window
of `Tq` positions at any start is an aligned window (what a token of a
packed bf16 slab is not), and flattened it is `[Tq x heads, width]`, row r
token `r // heads` (the one thing the mask arithmetic needs to know). The
one KV head makes the transposition a GQA tile needs unnecessary. Queries
and result stay in HBM and move by the kernel's own copies: a row's q
tiles are set going a grid step ahead (two buffers), the result's write is
awaited before the next one starts. A row with one live column moves one
position in and one out, and its queries are the tile's first `heads` rows
as they lie: no `column0` scratch, no reshape, no zero columns. A wide row
writes all `Tq` positions from its start; those past its live columns are
the next rows' and are written again, after it (the grid is sequential and
the writes are ordered), so no row's live position holds a neighbour's dead
column; a row with no live column (a free slot, a deferred prefill row)
walks no group and writes nothing, and the result starts as zeros aliased
to an operand. A tile's head slice is every
head or whole packed sublane tiles of heads (32 of A.X-K1's 64); a row
wider than `_PACKED_COLUMNS` (a whole prompt through `generate()`) is
walked as rows of that many columns, so the tile is the engine's whatever
the prompt. On the CPU the entry unpacks the rows and calls `_scan_impl` as
it stands, which is also what the kernel is compared with.

A selection (PR 39). A layer of learned sparse attention
(`ops/index_select.py`) attends to the `topk` keys its indexer chose for
each query, the same for every head, over the same latent pages
(`sparse_latent_attention`, `pallas_call` name `paged_sparse`):

- a row with one live column (a decode row) *gathers*: the selected tokens'
  `[c | r]` are taken by position through the block table into a compact
  cache of `topk` columns a row, and the latent walk runs over that: key
  reads and arithmetic are bounded by `topk`, whatever the row's context;
- a row with more (a prefill chunk: sixteen selections over one slab) is
  *one walk over the union*: the latent walk with `sel=`, each query
  column's own 0/1 mask of the group's columns brought beside the group's
  pages and ANDed into `keep`. Sixteen gathers would copy 16 x `topk`
  tokens of 1,280 B a row and layer through XLA's gather (a token is no
  aligned window of a packed bf16 slab, so no DMA can take it); the walk
  streams the row's pages once at HBM speed and the sixteen columns share
  them. A masked key is an exact zero of the softmax, as a key past the
  row's length is, so the result is the gather's up to the grouping of the
  sum.

Numerics: flash-style online softmax with the repo's exact-zero masking
convention (ops/attention.py `_fwd_kernel`): masked scores sit at
`_NEG_INF`, `p = where(s <= _NEG_INF/2, 0, exp(s - m_new))` contributes an
exact fp32 0.0, and a fully-masked block leaves (m, l, acc) bit-unchanged
(`alpha = exp(m - m) = 1.0`). That no-op property is what makes chunked
prefill *bit-identical* to whole-prompt prefill within one implementation:
its steps are aligned on absolute logical blocks (pages in the scan, groups
in the kernel), so the result for a query at absolute position P depends
only on (q, K[0..P], V[0..P]) and the step order — never on the query
width, the chunk boundary, or how many trailing dead steps the walk
carries. The scan at different `block_len`s groups the accumulation
differently and is documented-tolerance-identical only; the kernel's
grouping is 128 keys at every `block_len` that divides it. The window
keeps it: steps wholly outside a row's window are exact no-ops, so the
windowed walk through a ring gives the bits of a walk over every block of
a full-length cache, in the scan and in the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .attention import _NEG_INF, _dot

# The kv block size the trivial (non-paged) decode path uses. Engine pools
# that want streams bit-identical to one-shot generate() on the CPU must
# use the SAME block_len (the scan's accumulation grouping differs across
# block sizes; see module docstring). 8 divides every cache length the
# tests use and keeps the CPU scan short; on a TPU the kernel lowers at 8
# too (module docstring), so the one-shot path keeps it there.
DEFAULT_KV_BLOCK = 8

# The windowed walk's name: of its `pallas_call` (so of its instruction in
# a device trace) and in `pallas_mode`'s counters. It does not hold the
# full walk's name, because trace readers find a kernel by substring.
WINDOW_KERNEL = "paged_window"
# The latent walk's name (multi-head latent attention's cache): likewise
# neither of the other two's.
LATENT_KERNEL = "paged_latent"
# The latent walk of a layer whose keys an indexer selected (a mask a query
# column, or a gathered compact cache): neither of the others' either.
SPARSE_KERNEL = "paged_sparse"


def _kernel_name(window, latent: bool, sparse: bool = False) -> str:
    if sparse:
        return SPARSE_KERNEL
    return LATENT_KERNEL if latent else \
        "paged_attention" if window is None else WINDOW_KERNEL


# Keys one grid step of the kernel covers: one lane tile. A step takes the
# `_group_pages(block_len)` consecutive logical pages that hold them (8 of
# 16, 16 of 8), so its scores are `[heads, rows, 128]` on full lanes and
# its two products use the MXU's whole width. A constant of the kernel:
# when VMEM is short `_choose_tile` gives up heads, never keys, so the
# accumulation grouping follows from nothing a caller chooses.
_GROUP_KEYS = 128


def _group_pages(block_len: int) -> int:
    """Pages a grid step covers (P): the group is the pages that fill one
    lane tile of keys; a page wider than that is a group of its own."""
    return max(_GROUP_KEYS // block_len, 1)


# What one grid step of the kernel may hold in VMEM: its q and output
# tiles (double buffered by the pipeline), the two [heads, keys, D] buffers
# each of K and V (one group computed, one in flight), the fp32
# online-softmax scratch, and a group's fp32 scores and probabilities.
# Mosaic's scoped limit on a v5e is 16 MB; half of it leaves the compiler
# its own temporaries. The engine's shapes need ~3 MB, so every head rides
# one tile there; a whole-prompt prefill of hundreds of rows splits the
# heads into groups.
_VMEM_BUDGET = 8 << 20


def _tile_bytes(heads: int, rows: int, block_len: int, D: int,
                itemsize: int) -> int:
    """VMEM of one grid step whose tile holds `heads` KV heads (or pieces
    of one) with `rows` folded query rows each, over groups of
    `_group_pages(block_len)` pages. fp32 rows whose last dim is under a
    lane tile (m, l) pad to 128."""
    keys = _group_pages(block_len) * block_len
    io = 2 * (2 * rows * D + 2 * keys * D) * itemsize          # q, o, k, v
    state = (rows * D + 2 * rows * 128) * 4                    # acc, m, l
    scores = 2 * rows * max(keys, 128) * 4                     # s, p
    return heads * (io + state + scores)


def _choose_tile(H: int, Hkv: int, Tq: int, block_len: int, D: int,
                 itemsize: int, whole: int = 1):
    """(heads, fold): the tile of one grid step, from the shapes alone.

    A tile holds `heads` KV heads, each with `fold` of its `n_rep` query
    heads folded into `fold*Tq` rows. The largest tile inside the budget
    wins: every KV head with its whole GQA group where that fits (G = 1),
    else fewer KV heads, else one KV head with a part of its group (the
    group's pages are then fetched once per part). G = H // (heads*fold).
    The keys of a step are not its to choose (`_GROUP_KEYS`). A part of
    a group is a multiple of `whole` heads (a packed tile's head slice is
    whole sublane tiles of `[positions, H, .]`)."""
    n_rep = H // Hkv
    tiles = [(h, n_rep) for h in range(Hkv, 0, -1) if Hkv % h == 0]
    tiles += [(1, f) for f in range(n_rep - 1, 0, -1)
              if n_rep % f == 0 and f % whole == 0]
    for heads, fold in tiles:
        if (_tile_bytes(heads, fold * Tq, block_len, D, itemsize)
                <= _VMEM_BUDGET):
            return heads, fold
    return tiles[-1]


def _one_column_rows(fold: int, Tq: int, itemsize: int, masked: bool) -> int:
    """Rows a head of the kernel's one-column body (`fold`), or 0 where the
    trace holds the wide body alone: a one-token call, a walk under a
    selection's masks (its one-column rows have length 0 and reach no
    group), and a tile that takes as many packed sublane tiles (16 rows in
    bf16, 8 in float32) with one column as with all of them."""
    packed = 32 // itemsize
    if Tq == 1 or masked or -(-fold // packed) == -(-fold * Tq // packed):
        return 0
    return fold


def _first_block(pos, window: int, block_len: int):
    """The first logical block that cuts the window of a row whose first
    query sits at `pos`."""
    return jnp.maximum(pos - window + 1, 0) // block_len


def _window_steps(window: int, Tq: int, block_len: int, ring_pages: int):
    """Steps of a windowed walk: the blocks that W + Tq - 1 consecutive
    columns can cut, and never more than a ring that holds them has (its
    first and last block may share a page: + 1)."""
    return min(-(-(window + Tq) // block_len) + 1, ring_pages + 1)


def _window_groups(window: int, Tq: int, block_len: int, ring_pages: int):
    """Grid steps of the kernel's windowed walk: the aligned groups that
    the W + Tq - 1 columns a row's queries see can cut, and never more
    than a ring that holds them has (its first and last group may share
    pages: + 1)."""
    P = _group_pages(block_len)
    return min(-(-(window + Tq - 1) // (P * block_len)), -(-ring_pages // P)
               ) + 1


def _scan_impl(q, k_cache, v_cache, block_table, seq_lens, q_pos,
               block_len: int, pages_per_row: int, scale: float,
               window: int = None, q_rope=None, sel=None):
    """lax.scan over logical blocks, carrying (m, l, acc) — the same
    masked-score -> exact-zero-p -> alpha-rescale sequence as the kernel,
    one compiled program regardless of grid size. With `window` step i is
    row b's logical block `first[b] + i`, read from the ring. With
    `q_rope` the caches are a latent and its rotary key (module
    docstring): the scores are the sum of two products and the latent page
    is the values' page too. With `sel [B, Tq, L]` a query column sees the
    columns its mask holds a 1 at, and no other."""
    B, H, Tq, D = q.shape
    Hkv = k_cache.shape[1]
    n_rep = H // Hkv
    row = q_pos[:, None] + jnp.arange(Tq, dtype=jnp.int32)   # [B, Tq]

    m0 = jnp.full((B, H, Tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)

    def page(cache, r, c):   # [Hkv, KB, D]: columns c.. of slab row r
        return jax.lax.dynamic_slice(cache, (r, 0, c, 0),
                                     (1, Hkv, block_len, cache.shape[3]))[0]

    ring_pages = block_table.shape[1]
    if window is not None:
        first = _first_block(q_pos, window, block_len)              # [B]

    def body(carry, j):
        m_prev, l_prev, acc = carry
        if window is None:
            g = block_table[:, j]
        else:
            j = first + j                      # [B]: each row's own block
            g = jnp.take_along_axis(
                block_table, (j % ring_pages)[:, None], axis=1)[:, 0]
        g = jnp.maximum(g, 0)                  # -1 padding clamps to page 0
        r, c = g // pages_per_row, g % pages_per_row * block_len
        k_j = jax.vmap(page, (None, 0, 0))(k_cache, r, c)   # [B,Hkv,KB,D]
        v_j = jax.vmap(page, (None, 0, 0))(v_cache, r, c)
        if n_rep > 1:
            k_j = jnp.repeat(k_j, n_rep, axis=1)
            v_j = jnp.repeat(v_j, n_rep, axis=1)
        s = jnp.einsum("bhtd,bhkd->bhtk", q, k_j,
                       preferred_element_type=jnp.float32)
        if q_rope is not None:
            s, v_j = s + jnp.einsum(
                "bhtd,bhkd->bhtk", q_rope, v_j,
                preferred_element_type=jnp.float32), k_j
        s = s * scale
        if window is None:
            col = j * block_len + jnp.arange(block_len, dtype=jnp.int32)
            col = col[None, None, :]                         # [1, 1, KB]
        else:
            col = (j[:, None] * block_len
                   + jnp.arange(block_len, dtype=jnp.int32))[:, None, :]
        keep = (col <= row[:, :, None]) & (col < seq_lens[:, None, None])
        if window is not None:
            keep &= col > row[:, :, None] - window
        if sel is not None:
            keep &= jax.lax.dynamic_slice_in_dim(
                sel, j * block_len, block_len, axis=2) > 0.5
        s = jnp.where(keep[:, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhtk,bhkd->bhtd", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    steps = ring_pages if window is None \
        else _window_steps(window, Tq, block_len, ring_pages)
    js = jnp.arange(steps, dtype=jnp.int32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), js)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _head_dot(a, b, a_dim, b_dim):
    """`ops.attention._dot` for each head of [heads, ., .] operands: one
    MXU matmul per head (a batched dot_general over dim 0), contracting
    a[h][a_dim] with b[h][b_dim]."""
    return jax.vmap(lambda x, y: _dot(x, y, a_dim, b_dim))(a, b)


def _paged_kernel(table_ref, lens_ref, pos_ref, *refs,
                  block_len, pages, pages_per_row, n_groups, parts, scale,
                  Tq, window=None, latent=False, masked=False, narrow=0,
                  packed=0):
    """Grid (B, G); one step is one slot's whole walk for every head of
    the tile: a loop over the row's live groups of `pages` consecutive
    logical pages. q/o tiles [heads, fold*Tq, D] (a KV head's query heads
    folded into rows) come through the pipeline; the slabs stay in HBM and
    a group's K and V reach VMEM by one copy a page, each found through
    its own block-table entry, side by side along the key axis of a
    double-buffered [heads, keys, D] scratch. The loop is a pipeline
    without a prologue: pass i sets going what comes after group i (group
    i+1, or the first group of the next grid step) and then computes group
    i, so the copies are issued at one place, by a loop over the group's
    pages, and awaited at one place, by one wait a buffer; the grid's very
    first step, with nothing in flight yet, takes one pass more (i = -1),
    and a row with no live group one pass that only fetches.
    table/lens/pos arrive via scalar prefetch. With `window` the walk
    starts at the group that holds the window's first block and the mask
    has a lower edge too. With `latent` a second q tile follows the first
    (`[1, rows, rope width]`), "K" is the latent slab and "V" the rotary
    key's: the scores add the second product, and the values are the
    latent page that is already in VMEM. With `masked` a third slab in HBM
    follows the two (`sel [B, Tq, L]`, a query column's 0/1 mask of the
    logical columns) and a group's `[Tq, keys]` piece of it rides beside
    the group's pages into a buffer of its own. With `narrow` (the
    `fold` of `_one_column_rows`) the trace holds a second body of the
    group's arithmetic, over `narrow` rows a head: a row with one live
    column (`lens - pos == 1`, read on the scalar core) takes its column 0
    out of the q tile once, into `[heads, narrow, D]` scratch, runs its
    groups over that in the first `narrow` rows of the softmax state and
    the accumulator, and writes the result to column 0's rows of the o
    tile and zeros to the fifteen dead columns'; every other row runs the
    wide body. The walk, the copies and the wait are shared.

    With `packed` (a dense latent layer's call: the query heads of a
    tile) the queries and the result are token-major and stay in HBM,
    `[positions, H, .]`, and a fourth prefetched vector, `start`, names
    each row's first position: a row's q tile is the `Tq` positions from
    there, `packed` heads of each, `[Tq, packed, .]` in VMEM and flattened
    `[Tq * packed, .]`, row r token `r // packed`. The tiles come by the
    kernel's own copies, set going a grid step ahead into one of two
    buffers, and the result leaves by one, so a row with one live column
    moves one position in and one out and its queries are the tile's first
    `packed` rows as they lie: no `column0` scratch. A wide row writes all
    `Tq` positions from its start; those past its live columns are the next
    rows', which write after it: the grid is sequential and a write is
    awaited before the next one starts. A row with no live column walks no
    group and writes nothing (the result arrives as zeros, aliased to an
    operand)."""
    refs = list(refs)
    start_ref = refs.pop(0) if packed else None
    q_ref = refs.pop(0)
    qr_ref = refs.pop(0) if latent else None
    k_hbm, v_hbm = refs.pop(0), refs.pop(0)
    sel_hbm = refs.pop(0) if masked else None
    if packed:
        refs.pop(0)           # the zeros the result is aliased to
    o_ref, kbuf, vbuf = refs.pop(0), refs.pop(0), refs.pop(0)
    selbuf = refs.pop(0) if masked else None
    sem, slot_ref, acc_ref, m_ref, l_ref = refs[:5]
    tiles = [q_ref] + ([qr_ref] if latent else [])
    if packed:
        # the two q tiles' buffers (one a parity of the grid step), the
        # result's, their semaphores, and the kind of write in flight
        qbufs, (obuf, qsem, osem, out_ref) = refs[5:7], refs[7:]
    else:
        column0 = refs[5:]    # column 0 of each: the one-column body's q
    b, g = pl.program_id(0), pl.program_id(1)
    B, G = pl.num_programs(0), pl.num_programs(1)
    heads, rows = (1, Tq * packed) if packed else q_ref.shape[1:3]
    ring_pages = table_ref.shape[1]
    keys = pages * block_len

    def walk(row):
        """(first group, live groups) of `row`: a group wholly past the
        row's length cannot contribute (every column masked -> exact
        no-op), so the walk ends with the group that holds the length."""
        end = (lens_ref[row] + keys - 1) // keys
        if packed:            # no live column: nobody to walk for
            end = jnp.where(lens_ref[row] > pos_ref[row], end, 0)
        if window is None:
            return 0, jnp.minimum(end, n_groups)
        first = _first_block(pos_ref[row], window, block_len) // pages
        return first, jnp.clip(end - first, 0, n_groups)

    def fetch(row, tile, j, slot):
        """Set group j of `row` going into buffer `slot`: K and V of each
        page, [heads, block_len, D] cut from the slab as stored. A page
        past the row's length (or the table's width) names the row's last
        live page again: finite, and masked by column."""
        last = jnp.maximum(lens_ref[row] - 1, 0) // block_len
        h0 = tile // parts * heads

        def page(p):
            blk = jnp.minimum(j * pages + p, last)
            blk = blk % ring_pages if window is not None \
                else jnp.minimum(blk, ring_pages - 1)
            at = table_ref[row, blk]
            r = at // pages_per_row
            c = pl.multiple_of(at % pages_per_row * block_len, block_len)
            to = pl.multiple_of(p * block_len, block_len)
            for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                pltpu.make_async_copy(
                    hbm.at[r, pl.ds(h0, heads), pl.ds(c, block_len), :],
                    buf.at[slot, :, pl.ds(to, block_len), :],
                    sem.at[slot]).start()

        # eight pages' copies a pass of the loop, written out, so that the
        # scheduler can lay their scalar work beside the group's vector
        # work (one page a pass: +14% a live group on a v5e); the body is
        # the same size at either block_len
        at_once = math.gcd(pages, 8)

        def issue(k, carry):
            for u in range(at_once):
                page(k * at_once + u)
            return carry

        if pages == at_once:
            issue(0, None)
        else:
            jax.lax.fori_loop(0, pages // at_once, issue, None)
        if masked:
            pltpu.make_async_copy(
                sel_hbm.at[row, :, pl.ds(pl.multiple_of(j * keys, keys),
                                         keys)],
                selbuf.at[slot], sem.at[slot]).start()

    first, n = walk(b)
    # the grid step after this one, whose first group this one sets going
    wrap = g + 1 == G
    nb, ng = b + wrap.astype(jnp.int32), jnp.where(wrap, 0, g + 1)
    nrow = jnp.minimum(nb, B - 1)
    nfirst, nn = walk(nrow)
    has_next = (nb < B) & (nn > 0)
    opening = (b == 0) & (g == 0)             # nothing is in flight yet
    lo = jnp.where(opening, -1, 0)
    hi = jnp.maximum(n, lo + 1)
    slot0 = jnp.where(opening, 0, slot_ref[0])   # holds this row's group 0

    if packed:
        par = (b * G + g) % 2                 # this grid step's q buffers

        def q_copies(row, tile, buf, n):
            """`row`'s first `n` positions, the heads of `tile`, of each
            query operand into buffer `buf`."""
            return [pltpu.make_async_copy(
                hbm.at[pl.ds(start_ref[row], n),
                       pl.ds(pl.multiple_of(tile * packed, packed), packed)],
                to.at[buf, pl.ds(0, n)], qsem.at[buf])
                for hbm, to in zip(tiles, qbufs)]

        def fetch_q(row, tile, buf):
            """Set `row`'s q tiles going: one position of a row with one
            live column, `Tq` of any other."""
            one = (lens_ref[row] - pos_ref[row] == 1) if narrow else False

            @pl.when(jnp.logical_not(one))
            def _():
                for copy in q_copies(row, tile, buf, Tq):
                    copy.start()

            if narrow:
                @pl.when(one)
                def _():
                    for copy in q_copies(row, tile, buf, 1):
                        copy.start()

        def o_copy(n):
            """The first `n` positions of the result's buffer to this
            row's place in the result."""
            return pltpu.make_async_copy(
                obuf.at[pl.ds(0, n)],
                o_ref.at[pl.ds(start_ref[b], n),
                         pl.ds(pl.multiple_of(g * packed, packed), packed)],
                osem.at[0])

        def drain():
            """Await the write in flight, if any: `out_ref` holds the
            positions it carries."""
            for n in (1, Tq) if narrow else (Tq,):
                @pl.when(out_ref[0] == n)
                def _():
                    o_copy(n).wait()

        def put(n, value):
            """`value [1, n * packed, D]` to the row's first `n` positions,
            behind every earlier row's write."""
            drain()
            obuf[pl.ds(0, n)] = value.reshape(n, packed, -1).astype(
                obuf.dtype)
            o_copy(n).start()
            out_ref[0] = n

        @pl.when(opening)
        def _():
            out_ref[0] = 0

    # which body a grid step runs: decorators round each body's pieces (a
    # trace that holds the wide body alone runs it for every row)
    if packed:
        adv = lens_ref[b] - pos_ref[b]
        wide_row = pl.when(adv > (1 if narrow else 0))
        one_column_row = pl.when(adv == 1)
    elif narrow:
        one = lens_ref[b] - pos_ref[b] == 1
        wide_row, one_column_row = pl.when(jnp.logical_not(one)), pl.when(one)
    else:
        def wide_row(body):
            body()

    def state(n):
        """The first `n` rows a head of the accumulator and the softmax
        state: all of them for the wide body."""
        if n == rows:
            return acc_ref, m_ref, l_ref
        return acc_ref.at[:, :n], m_ref.at[:, :n], l_ref.at[:, :n]

    def reset(n):
        acc, m, l = state(n)
        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        m[...] = jnp.full(m.shape, _NEG_INF, m.dtype)
        l[...] = jnp.zeros(l.shape, l.dtype)

    wide_row(lambda: reset(rows))
    if narrow:
        @one_column_row
        def _():
            reset(narrow)
            if packed:        # its queries: the tile's first rows
                return
            for ref, to in zip(tiles, column0):   # row r is token r mod Tq
                to[...] = ref[0].reshape(
                    heads, narrow, Tq, ref.shape[3])[:, :, 0]

    t = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    if packed:                                # row r is token r // packed
        t = t // packed
    elif rows != Tq:                          # folded row r is token r mod Tq
        t = jax.lax.rem(t, Tq)
    row_pos = pos_ref[b] + t
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (narrow, keys), 1) \
        if narrow else None

    def attend(i, slot, n, q_of, row_pos, lane):
        """Group i's arithmetic over `n` rows a head, `q_of(k)` the
        queries of `tiles[k]`: the masked scores, the online-softmax update
        and the p.V product. What a (query, key) pair goes through does not
        depend on `n`."""
        acc, m, l = state(n)
        col = (first + i) * keys + lane
        keep = (col <= row_pos) & (col < lens_ref[b])
        if window is not None:
            keep &= col > row_pos - window
        if masked:                            # folded row r is token r mod Tq
            keep &= jnp.tile(selbuf[slot], (n // Tq, 1)) > 0.5
        vgrp = vbuf[slot]                                 # [heads, keys, D]
        s = _head_dot(q_of(0), kbuf[slot], 1, 1)
        if latent:
            s = s + _head_dot(q_of(1), vgrp, 1, 1)
            vgrp = kbuf[slot]
        s = s * scale
        s = jnp.where(keep[None], s, _NEG_INF)            # [heads, n, keys]
        m_prev = m[...]
        l_prev = l[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + _head_dot(
            p.astype(vgrp.dtype), vgrp, 1, 0)
        m[...] = m_new

    def group(i, carry):
        ahead = (slot0 + i + 1) % 2           # the buffer group i is not in
        more = i + 1 < n      # else: the next grid step's first group

        @pl.when(more | has_next)
        def _():
            fetch(jnp.where(more, b, nrow), jnp.where(more, g, ng),
                  jnp.where(more, first + i + 1, nfirst), ahead)

        @pl.when((i >= 0) & (i < n))
        def _():
            slot = 1 - ahead
            for buf in (kbuf, vbuf) + ((selbuf,) if masked else ()):
                # the group's copies share the buffer's semaphore, which
                # counts bytes: one wait for the buffer's size takes all
                pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                      sem.at[slot]).wait()
            if packed:
                wide_row(lambda: attend(
                    i, slot, rows,
                    lambda k: qbufs[k][par].reshape(1, rows, -1), row_pos,
                    lane))
                if narrow:                    # the tile's first rows
                    one_column_row(lambda: attend(
                        i, slot, narrow, lambda k: qbufs[k][par, 0][None],
                        pos_ref[b], lane1))
                return
            wide_row(lambda: attend(
                i, slot, rows, lambda k: tiles[k][0], row_pos, lane))
            if narrow:                        # every row is token 0
                one_column_row(lambda: attend(
                    i, slot, narrow, lambda k: column0[k][...],
                    pos_ref[b], lane1))
        return carry

    if packed:
        # the next grid step's q tiles, a whole row ahead, into the buffers
        # the last step has done with (the grid's first step brings its
        # own); then this row's, long since there
        @pl.when(opening & (n > 0))
        def _():
            fetch_q(b, g, par)

        @pl.when(has_next)
        def _():
            fetch_q(nrow, ng, 1 - par)

        for kind, n_q in ((wide_row, Tq),) + (
                ((one_column_row, 1),) if narrow else ()):
            @kind
            def _():
                for copy in q_copies(b, g, par, n_q):
                    copy.wait()

    jax.lax.fori_loop(lo, hi, group, None)
    slot_ref[0] = (slot0 + hi) % 2

    def result(n):
        acc, _, l = state(n)
        l = jnp.maximum(l[...], 1e-30)
        return acc[...] / l

    if packed:
        wide_row(lambda: put(Tq, result(rows)))
        if narrow:
            one_column_row(lambda: put(1, result(narrow)))

        @pl.when((b == B - 1) & (g == G - 1))
        def _():
            drain()
        return

    @wide_row
    def _():
        o_ref[0] = result(rows).astype(o_ref.dtype)

    if narrow:
        @one_column_row
        def _():
            # column 0's rows of the o tile; the dead columns hold zeros
            first_col = jax.lax.broadcasted_iota(
                jnp.int32, (heads, narrow, Tq, o_ref.shape[3]), 2) == 0
            o_ref[0] = jnp.where(
                first_col, result(narrow)[:, :, None], 0.0).reshape(
                    o_ref.shape[1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_len", "pages_per_row", "scale", "window", "heads", "fold",
    "n_groups", "interpret", "sparse", "narrow"))
def _paged_call(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                q_rope=None, sel=None, *, block_len, pages_per_row, scale,
                window, heads, fold, n_groups, interpret, sparse=False,
                narrow=0):
    """The kernel's `pallas_call` at one tile. Jitted at module level with
    every integer static, so the call sites of one traced program that
    agree on shapes and window (a step's layers, unrolled) share one
    jaxpr, and the program lowers one kernel body for them, not one
    each."""
    B, H, Tq, D = q.shape
    n_rep = H // k_cache.shape[1]
    P = _group_pages(block_len)
    rows = fold * Tq
    latent = q_rope is not None
    masked = sel is not None
    name = _kernel_name(window, latent, sparse)

    def tile(width):
        return pl.BlockSpec((1, heads, rows, width),
                            lambda b, g, table_ref, lens_ref, pos_ref:
                            (b, g, 0, 0))

    slab = pl.BlockSpec(memory_space=pl.ANY)

    def group(cache):                          # two buffers: one in flight
        return pltpu.VMEM((2, heads, P * block_len, cache.shape[3]),
                          cache.dtype)

    queries = [q.reshape(B, H // fold, rows, D)]
    if latent:
        queries.append(q_rope.reshape(B, H // fold, rows, -1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // (heads * fold)),
        in_specs=[tile(x.shape[3]) for x in queries]
        + [slab] * (3 if masked else 2),
        out_specs=tile(D),
        scratch_shapes=[
            group(k_cache),
            group(v_cache)]
        + ([pltpu.VMEM((2, Tq, P * block_len), sel.dtype)] if masked else [])
        + [pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),       # the buffer in flight
            pltpu.VMEM((heads, rows, D), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
        ] + ([pltpu.VMEM((heads, narrow, x.shape[3]), x.dtype)
              for x in queries] if narrow else []),
    )
    kernel = functools.partial(
        _paged_kernel, block_len=block_len, pages=P,
        pages_per_row=pages_per_row, n_groups=n_groups,
        parts=n_rep // fold,       # tiles that share one KV head (1: none)
        scale=scale, Tq=Tq, window=window, latent=latent, masked=masked,
        narrow=narrow)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H // fold, rows, D), q.dtype),
        # in order: a step sets the next one's first group going
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(jnp.maximum(block_table, 0), seq_lens, q_pos, *queries, k_cache,
      v_cache, *((sel,) if masked else ()))
    return out.reshape(B, H, Tq, D)


@functools.partial(jax.jit, static_argnames=(
    "block_len", "pages_per_row", "scale", "fold", "n_groups", "interpret",
    "narrow", "Tq"))
def _packed_call(q, q_rope, c_cache, r_cache, block_table, seq_lens, q_pos,
                 starts, *, block_len, pages_per_row, scale, fold,
                 n_groups, interpret, narrow, Tq):
    """`_paged_call` for a dense latent layer: `q [positions, H, R]` and
    `q_rope [positions, H, Dr]` token-major, row b's tile the `Tq`
    positions from `starts[b]`, `fold` heads of each. Queries and result
    stay in HBM and move by the kernel's own copies; the result starts as
    zeros (positions no row writes stay so), aliased to an operand."""
    P, H, D = q.shape
    B = block_table.shape[0]
    pages = _group_pages(block_len)
    keys, rows = pages * block_len, fold * Tq
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, H // fold),
        in_specs=[hbm] * 5,
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, 1, keys, c_cache.shape[3]), c_cache.dtype),
            pltpu.VMEM((2, 1, keys, r_cache.shape[3]), r_cache.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),       # the buffer in flight
            pltpu.VMEM((1, rows, D), jnp.float32),
            pltpu.VMEM((1, rows, 1), jnp.float32),
            pltpu.VMEM((1, rows, 1), jnp.float32),
            pltpu.VMEM((2, Tq, fold, D), q.dtype),
            pltpu.VMEM((2, Tq, fold, q_rope.shape[2]), q_rope.dtype),
            pltpu.VMEM((Tq, fold, D), q.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SMEM((1,), jnp.int32),       # positions being written
        ],
    )
    kernel = functools.partial(
        _paged_kernel, block_len=block_len, pages=pages,
        pages_per_row=pages_per_row, n_groups=n_groups, parts=H // fold,
        scale=scale, Tq=Tq, latent=True, narrow=narrow, packed=fold)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, H, D), q.dtype),
        input_output_aliases={8: 0},           # behind the four vectors
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=LATENT_KERNEL,
    )(jnp.maximum(block_table, 0), seq_lens, q_pos, starts, q, q_rope,
      c_cache, r_cache, jnp.zeros((P, H, D), q.dtype))


def _pallas_impl(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                 block_len: int, pages_per_row: int, scale: float,
                 window: int = None, q_rope=None, sel=None, sparse=False):
    """Choose the tile from the shapes, record it, and call the kernel
    through its one jitted entry."""
    B, H, Tq, D = q.shape
    ring_pages = block_table.shape[1]
    P = _group_pages(block_len)
    n_groups = -(-ring_pages // P) if window is None \
        else _window_groups(window, Tq, block_len, ring_pages)
    # a latent tile holds both q tiles and both pages
    width = D if q_rope is None else D + q_rope.shape[3]
    heads, fold = _choose_tile(H, k_cache.shape[1], Tq, block_len, width,
                               q.dtype.itemsize)
    name = _kernel_name(window, q_rope is not None, sparse)
    narrow = _one_column_rows(fold, Tq, q.dtype.itemsize, sel is not None)
    pallas_mode.note_tiling(name, grid=(B, H // (heads * fold)),
                            groups=n_groups, pages=P, heads=heads,
                            rows=fold * Tq, one_column_rows=narrow)
    more = () if q_rope is None else (q_rope,)
    if sel is not None:
        # whole groups of columns: a group's piece is one aligned copy
        short = n_groups * P * block_len - sel.shape[2]
        more += (jnp.pad(sel, ((0, 0), (0, 0), (0, max(short, 0)))),)
    return _paged_call(
        q, k_cache, v_cache, block_table, seq_lens, q_pos, *more,
        block_len=block_len, pages_per_row=pages_per_row,
        scale=float(scale), window=window, heads=heads, fold=fold,
        n_groups=n_groups, interpret=pallas_mode.interpret(name),
        sparse=sparse, narrow=narrow)


def ragged_paged_attention(q, k_cache, v_cache, block_table, seq_lens,
                           q_pos, *, block_len: int,
                           pages_per_row: int = None, scale: float = None,
                           impl: str = None, window: int = None,
                           q_rope=None, sel=None, sparse: bool = False):
    """Attention of q [B, H, Tq, D] over block-table-addressed KV pages.

    k_cache/v_cache: [N, Hkv, L_slab, D] slabs (N need not equal B — block
    tables address pages globally). block_table [B, max_blocks] int32,
    seq_lens [B], q_pos [B] — see module docstring for the mask contract.
    pages_per_row defaults to L_slab // block_len (pass the pool's
    n_blocks when the slab carries chunk write-padding).
    impl: None = scan on the CPU, the kernel on a TPU; or name "scan" /
    "pallas" (on the CPU the kernel runs interpreted — the parity suite
    does that; `ops.pallas_mode` decides and counts).
    window: None, or W: a query sees the W keys up to itself, and the
    table's `max_blocks` columns are a ring (module docstring); a
    `block_table` of None is then each row's own ring, `pages_per_row`
    pages of slab row b (N == B).
    q_rope [B, H, Tq, Dr]: the caches are a latent `c [N, 1, L_slab, D]`
    and its rotary key `r [N, 1, L_slab, Dr]` (module docstring, "A latent
    cache"): scores `scale * (q . c + q_rope . r)`, values c; the result
    is [B, H, Tq, D] in the latent's space. `scale` is then the caller's
    to give.
    sel [B, Tq, L >= max_blocks * block_len] float32, with `q_rope`: query
    column t of row b sees logical column s only where `sel[b, t, s]` is 1
    (module docstring, "A selection"). `sparse`: the call is a sparse
    layer's and goes by the name `paged_sparse` (`sel` implies it).
    """
    B, H, Tq, D = q.shape
    sparse = sparse or sel is not None
    if sparse and q_rope is None:
        raise ValueError("a selection is over a latent cache (q_rope=)")
    if q_rope is not None:
        if window is not None or scale is None or k_cache.shape[1] != 1 \
                or v_cache.shape[3] != q_rope.shape[3]:
            raise ValueError(
                "a latent cache: one head, no window, the caller's scale, "
                f"and a rotary key as wide as q_rope (c {k_cache.shape}, "
                f"r {v_cache.shape}, q_rope {q_rope.shape})")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if pages_per_row is None:
        pages_per_row = k_cache.shape[2] // block_len
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl not in ("scan", "pallas"):
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    if block_table is None:
        if window is None or k_cache.shape[0] != B:
            raise ValueError("block_table=None names each row's own ring: "
                             "it needs a window and one slab row a query "
                             "row")
        block_table = (jnp.arange(B, dtype=jnp.int32)[:, None]
                       * pages_per_row
                       + jnp.arange(pages_per_row, dtype=jnp.int32)[None])
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    block_table = jnp.asarray(block_table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if k_cache.shape[2] < pages_per_row * block_len:
        raise ValueError(
            f"cache length {k_cache.shape[2]} cannot back {pages_per_row} "
            f"pages of {block_len} tokens")
    if impl == "scan":
        pallas_mode.count(_kernel_name(window, q_rope is not None, sparse),
                          "scan")
        impl_fn = _scan_impl
    else:
        impl_fn = _pallas_impl
    if sparse:
        more = {} if impl == "scan" else {"sparse": True}
        return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                       block_len, pages_per_row, scale, None, q_rope, sel,
                       **more)
    if q_rope is not None and impl == "pallas":
        # the kernel's one form for a layer that attends to every key:
        # token-major, row b's positions from b * Tq
        out = packed_latent_attention(
            *(jnp.swapaxes(x, 1, 2).reshape(B * Tq, H, -1)
              for x in (q, q_rope)), k_cache, v_cache, block_table,
            seq_lens, q_pos, jnp.arange(B, dtype=jnp.int32) * Tq, width=Tq,
            block_len=block_len, pages_per_row=pages_per_row, scale=scale,
            impl=impl)
        return jnp.swapaxes(out.reshape(B, Tq, H, D), 1, 2)
    if q_rope is not None:
        return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                       block_len, pages_per_row, scale, None, q_rope)
    if window is None:
        return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                       block_len, pages_per_row, scale)
    return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                   block_len, pages_per_row, scale, int(window))


# Columns of a packed latent row in the kernel: a wider row (a whole prompt
# through `generate()`) is walked as rows of this many, each with its own
# start, position and length over the one table row. Chunks of a prompt
# give every position the bits of the whole (module docstring, Numerics),
# and the tile stays the engine's whatever the prompt.
_PACKED_COLUMNS = 16


def packed_latent_attention(q, q_rope, c_cache, r_cache, block_table,
                            seq_lens, q_pos, starts, *, width: int,
                            block_len: int, pages_per_row: int,
                            scale: float, impl: str = None):
    """`ragged_paged_attention(q_rope=)` with the queries left where a
    step's tokens lie (module docstring, "Packed queries").

    q [P, H, R] (the key projection absorbed) and q_rope [P, H, Dr] are
    token-major: row b's queries are the `adv[b] = seq_lens[b] - q_pos[b]`
    positions from `starts[b] [B]`, at most `width` of them, and every
    window `[starts[b], starts[b] + width)` of a row with a live column
    lies inside P (the caller pads). c_cache / r_cache, block_table,
    seq_lens, q_pos, block_len, pages_per_row, scale, impl: as there.
    Returns [P, H, R]: at `starts[b] + t`, t < adv[b], what
    `ragged_paged_attention` gives row b's column t; a position no row owns
    holds a finite value nobody reads."""
    P, H, D = q.shape
    B = block_table.shape[0]
    block_table = jnp.asarray(block_table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl == "scan":
        # the reference: the windows unpacked, the scan as it stands
        pallas_mode.count(LATENT_KERNEL, "scan")
        t = jnp.arange(width, dtype=jnp.int32)
        at = starts[:, None] + t                              # [B, width]
        rows = [jnp.swapaxes(jnp.take(x, at, axis=0, mode="clip"), 1, 2)
                for x in (q, q_rope)]                   # [B, H, width, .]
        out = _scan_impl(rows[0], c_cache, r_cache, block_table, seq_lens,
                         q_pos, block_len, pages_per_row, scale, None,
                         rows[1])
        live = t < (seq_lens - q_pos)[:, None]
        return jnp.zeros_like(q).at[jnp.where(live, at, P)].set(
            jnp.swapaxes(out, 1, 2), mode="drop")
    if impl != "pallas":
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    if width > _PACKED_COLUMNS:
        j = jnp.arange(-(-width // _PACKED_COLUMNS),
                       dtype=jnp.int32) * _PACKED_COLUMNS
        starts, q_pos = ((x[:, None] + j).reshape(-1)
                         for x in (starts, q_pos))
        seq_lens = jnp.minimum(jnp.repeat(seq_lens, len(j)),
                               q_pos + _PACKED_COLUMNS)
        block_table = jnp.repeat(block_table, len(j), axis=0)
        # the last piece's window may run past the prompt's positions
        pad = ((0, _PACKED_COLUMNS - 1), (0, 0), (0, 0))
        return packed_latent_attention(
            jnp.pad(q, pad), jnp.pad(q_rope, pad), c_cache, r_cache,
            block_table, seq_lens, q_pos, starts, width=_PACKED_COLUMNS,
            block_len=block_len, pages_per_row=pages_per_row, scale=scale,
            impl=impl)[:P]
    pages = _group_pages(block_len)
    n_groups = -(-block_table.shape[1] // pages)
    _, fold = _choose_tile(H, 1, width, block_len, D + q_rope.shape[2],
                           q.dtype.itemsize, whole=32 // q.dtype.itemsize)
    narrow = fold if width > 1 else 0
    pallas_mode.note_tiling(LATENT_KERNEL, grid=(B, H // fold),
                            groups=n_groups, pages=pages, heads=1,
                            rows=fold * width, one_column_rows=narrow,
                            packed_queries=P)
    return _packed_call(
        q, q_rope, c_cache, r_cache, block_table, seq_lens, q_pos, starts,
        block_len=block_len, pages_per_row=pages_per_row,
        scale=float(scale), fold=fold, n_groups=n_groups,
        interpret=pallas_mode.interpret(LATENT_KERNEL), narrow=narrow,
        Tq=width)


def sparse_latent_attention(q, c_cache, r_cache, block_table, seq_lens,
                            q_pos, *, sel, block_len: int,
                            pages_per_row: int, scale: float, q_rope,
                            window=None, impl: str = None):
    """Latent attention over the keys an indexer selected (module
    docstring, "A selection"): `ragged_paged_attention(q_rope=)`'s operands
    and `sel`, an `ops.index_select.Selection` made against the same table.
    A row whose step has one live column (`seq_lens - q_pos == 1`; every
    row where Tq is 1) takes the gathered form for its column 0, every
    other row the walk under its columns' masks; each call skips the other
    kind's rows (a row of length 0 has no group to walk)."""
    if window is not None:
        raise ValueError("a selection and a window: not a layer kind")
    B, H, Tq, D = q.shape
    N, _, L_slab, _ = c_cache.shape
    block_table = jnp.asarray(block_table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    walk = functools.partial(ragged_paged_attention, scale=scale, impl=impl)
    one = (seq_lens - q_pos == 1) | (Tq == 1)                  # [B]

    # the gathered form: column 0's selected tokens, by position, through
    # the table, into a compact cache of K columns a row
    K = sel.idx.shape[1]
    pad = -K % block_len
    idx = jnp.pad(sel.idx, ((0, 0), (0, pad)))
    page = jnp.take_along_axis(jnp.maximum(block_table, 0),
                               idx // block_len, axis=1)
    at = (page // pages_per_row) * L_slab \
        + page % pages_per_row * block_len + idx % block_len   # [B, K]
    compact = [jnp.take(cache.reshape(N * L_slab, -1), at, axis=0)[:, None]
               for cache in (c_cache, r_cache)]                # [B, 1, K, .]
    table, nb = trivial_block_table(B, K + pad, block_len)
    # every gathered key is behind the query: q_pos past them all
    out = walk(q[:, :, :1], *compact, table,
               jnp.where(one, sel.count, 0),
               jnp.full((B,), K + pad, jnp.int32), block_len=block_len,
               pages_per_row=nb, q_rope=q_rope[:, :, :1], sparse=True)
    if Tq == 1:
        return out
    union = walk(q, c_cache, r_cache, block_table,
                 jnp.where(one, 0, seq_lens), q_pos, block_len=block_len,
                 pages_per_row=pages_per_row, q_rope=q_rope, sel=sel.mask)
    first = jnp.arange(Tq, dtype=jnp.int32)[None, None, :, None] == 0
    return jnp.where(one[:, None, None, None] & first, out, union)


def trivial_block_table(batch: int, cache_len: int,
                        block_len: int = DEFAULT_KV_BLOCK):
    """Identity table for a contiguous per-row cache: logical block j of
    row b is page b*nb + j. Returns (table [B, nb], nb); callers pad the
    cache to nb*block_len columns (padded cols are masked by seq_lens)."""
    nb = -(-cache_len // block_len)
    table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * nb
             + jnp.arange(nb, dtype=jnp.int32)[None, :])
    return table, nb
