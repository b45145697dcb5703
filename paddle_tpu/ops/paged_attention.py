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

Two implementations with the SAME per-block online-softmax op sequence;
both read the slabs as stored (a page is `block_len` columns of one slab
row, for every KV head at once), so nothing slices, transposes or copies
the pool on the way in:

- `_scan_impl` — plain XLA `lax.scan` over logical blocks. What `impl=None`
  picks on the CPU: interpret-mode Pallas unrolls every grid cell into the
  jaxpr, which makes tier-1 compile times explode, while this path compiles
  once and runs the identical arithmetic. Also the parity reference the
  kernel is checked against on the chip (chip_smoke.py).
- `_pallas_impl` — the TPU kernel, what `impl=None` picks on a TPU: grid
  (B, G, n_blocks), one step per (slot, head group, page). A step's K and V
  tiles are `[heads, block_len, D]`, cut from the slab by the index_map; a
  KV head's `n_rep` query heads are folded into the rows of its q tile
  (`q` viewed as `[B, Hkv, n_rep*Tq, D]`: row r is token `r mod Tq`), so a
  GQA group reads its page once. The block table / lengths / positions are
  scalar-prefetched: the index_map fetches only the pages a row occupies
  (a page past its length names the last live one again, which fetches
  nothing) and `@pl.when` skips their compute ("only over occupied KV
  blocks"). `_choose_tile` takes the tile from the shapes and one VMEM
  budget: every head in one tile (G = 1) at the engine's shapes, fewer KV
  heads or a part of one GQA group for a whole-prompt prefill of hundreds
  of rows. `pallas_mode.KERNEL_TILINGS` records the choice of each trace.
  Mosaic (jax 0.9.0 / libtpu 0.0.34, v5e) lowers it in bf16 at `block_len`
  8 and 16 (the two sizes the repo runs), query widths 1 to 2,048, MHA and
  GQA — including the 8-row bf16 KV tile (half a packed sublane tile) of
  `DEFAULT_KV_BLOCK` and the 1-row q tile of the MHA `generate()` decode
  loop; tests/test_mosaic_aot.py pins those and the serve cells' shapes.

A window (PR 31). With `window=W` a query at position p sees the W keys
`p - W < col <= p` (itself included) and the cache may be a *ring*: logical
block j lives in page `table[b, j mod R]`, R = `block_table.shape[1]`
pages a row (position p at ring column `p mod (R * block_len)`; a
contiguous cache is the ring that never wraps). The walk then starts at the
first block that cuts the row's window and takes at most
`ceil((W + Tq) / block_len) + 1` steps whatever the row's length: grid
(B, G, steps), its own `pallas_call` name `paged_window`, so that a trace
tells its time from the full walk's `paged_attention`. The mask is taken
on logical columns, so a ring page visited under two logical blocks (the
walk's first and last may share one) shows each its own columns.

Numerics: flash-style online softmax with the repo's exact-zero masking
convention (ops/attention.py `_fwd_kernel`): masked scores sit at
`_NEG_INF`, `p = where(s <= _NEG_INF/2, 0, exp(s - m_new))` contributes an
exact fp32 0.0, and a fully-masked block leaves (m, l, acc) bit-unchanged
(`alpha = exp(m - m) = 1.0`). That no-op property is what makes chunked
prefill *bit-identical* to whole-prompt prefill at a fixed `block_len`:
the result for a query at absolute position P depends only on
(q, K[0..P], V[0..P]) and the block iteration order — never on the query
width, the chunk boundary, or how many trailing padded blocks the grid
carries. Different `block_len`s group the accumulation differently and are
documented-tolerance-identical only. The window keeps it: blocks wholly
outside a row's window are exact no-ops, so the windowed walk through a
ring gives the bits of a walk over every block of a full-length cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode
from .attention import _GRID_SEMANTICS, _NEG_INF, _dot

# The kv block size the trivial (non-paged) decode path uses. Engine pools
# that want streams bit-identical to one-shot generate() must use the SAME
# block_len (flash accumulation grouping differs across block sizes; see
# module docstring). 8 divides every cache length the tests use and keeps
# the CPU scan short; on a TPU the kernel lowers at 8 too (module
# docstring), so the one-shot path keeps it there.
DEFAULT_KV_BLOCK = 8

# The windowed walk's name: of its `pallas_call` (so of its instruction in
# a device trace) and in `pallas_mode`'s counters. It does not hold the
# full walk's name, because trace readers find a kernel by substring.
WINDOW_KERNEL = "paged_window"


# What one grid step of the kernel may hold in VMEM: its q, K, V and output
# tiles (double buffered by the pipeline), the fp32 online-softmax scratch,
# and the step's fp32 scores and probabilities. Mosaic's scoped limit on a
# v5e is 16 MB; half of it leaves the compiler its own temporaries. The
# engine's shapes need ~1.5 MB, so every head rides one tile there; a
# whole-prompt prefill of hundreds of rows splits the heads into groups.
_VMEM_BUDGET = 8 << 20


def _tile_bytes(heads: int, rows: int, block_len: int, D: int,
                itemsize: int) -> int:
    """VMEM of one grid step whose tile holds `heads` KV heads (or pieces
    of one) with `rows` folded query rows each. fp32 rows whose last dim
    is under a lane tile (m, l, scores over a 16-wide page) pad to 128."""
    io = 2 * (2 * rows * D + 2 * block_len * D) * itemsize     # q, o, k, v
    state = (rows * D + 2 * rows * 128) * 4                    # acc, m, l
    scores = 2 * rows * max(block_len, 128) * 4                # s, p
    return heads * (io + state + scores)


def _choose_tile(H: int, Hkv: int, Tq: int, block_len: int, D: int,
                 itemsize: int):
    """(heads, fold): the tile of one grid step, from the shapes alone.

    A tile holds `heads` KV heads, each with `fold` of its `n_rep` query
    heads folded into `fold*Tq` rows. The largest tile inside the budget
    wins: every KV head with its whole GQA group where that fits (G = 1),
    else fewer KV heads, else one KV head with a part of its group (the
    page is then fetched once per part). G = H // (heads*fold)."""
    n_rep = H // Hkv
    tiles = [(h, n_rep) for h in range(Hkv, 0, -1) if Hkv % h == 0]
    tiles += [(1, f) for f in range(n_rep - 1, 0, -1) if n_rep % f == 0]
    for heads, fold in tiles:
        if (_tile_bytes(heads, fold * Tq, block_len, D, itemsize)
                <= _VMEM_BUDGET):
            return heads, fold
    return tiles[-1]


def _first_block(pos, window: int, block_len: int):
    """The first logical block that cuts the window of a row whose first
    query sits at `pos`."""
    return jnp.maximum(pos - window + 1, 0) // block_len


def _window_steps(window: int, Tq: int, block_len: int, ring_pages: int):
    """Steps of a windowed walk: the blocks that W + Tq - 1 consecutive
    columns can cut, and never more than a ring that holds them has (its
    first and last block may share a page: + 1)."""
    return min(-(-(window + Tq) // block_len) + 1, ring_pages + 1)


def _scan_impl(q, k_cache, v_cache, block_table, seq_lens, q_pos,
               block_len: int, pages_per_row: int, scale: float,
               window: int = None):
    """lax.scan over logical blocks, carrying (m, l, acc) — the same
    masked-score -> exact-zero-p -> alpha-rescale sequence as the kernel,
    one compiled program regardless of grid size. With `window` step i is
    row b's logical block `first[b] + i`, read from the ring."""
    B, H, Tq, D = q.shape
    Hkv = k_cache.shape[1]
    n_rep = H // Hkv
    row = q_pos[:, None] + jnp.arange(Tq, dtype=jnp.int32)   # [B, Tq]

    m0 = jnp.full((B, H, Tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)

    def page(cache, r, c):   # [Hkv, KB, D]: columns c.. of slab row r
        return jax.lax.dynamic_slice(cache, (r, 0, c, 0),
                                     (1, Hkv, block_len, D))[0]

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
                       preferred_element_type=jnp.float32) * scale
        if window is None:
            col = j * block_len + jnp.arange(block_len, dtype=jnp.int32)
            col = col[None, None, :]                         # [1, 1, KB]
        else:
            col = (j[:, None] * block_len
                   + jnp.arange(block_len, dtype=jnp.int32))[:, None, :]
        keep = (col <= row[:, :, None]) & (col < seq_lens[:, None, None])
        if window is not None:
            keep &= col > row[:, :, None] - window
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


def _paged_kernel(table_ref, lens_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_len, scale, Tq,
                  window=None):
    """Grid (B, G, n_blocks), pages innermost; one step is one page of one
    slot for every head of the tile. q/o tiles [heads, fold*Tq, D] (a KV
    head's query heads folded into rows), K/V tiles [heads, block_len, D]
    cut from the slab by the index_map, online-softmax state in VMEM
    scratch across a (b, g) row's pages. table/lens/pos arrive via scalar
    prefetch. With `window`, step i is logical block `first + i` of the
    row's own walk and the mask has a lower edge too."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    rows = q_ref.shape[2]
    j = i if window is None \
        else _first_block(pos_ref[b], window, block_len) + i

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # occupied-blocks-only: a block wholly past this row's length cannot
    # contribute (every column masked -> exact no-op), so skip its compute
    @pl.when(j * block_len < lens_ref[b])
    def _compute():
        col = (j * block_len
               + jax.lax.broadcasted_iota(jnp.int32, (rows, block_len), 1))
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, block_len), 0)
        if rows != Tq:                        # folded row r is token r mod Tq
            t = jax.lax.rem(t, Tq)
        keep = (col <= pos_ref[b] + t) & (col < lens_ref[b])
        if window is not None:
            keep &= col > pos_ref[b] + t - window
        vblk = v_ref[0]                       # [heads, KB, D]
        s = _head_dot(q_ref[0], k_ref[0], 1, 1) * scale  # [heads,rows,KB]
        s = jnp.where(keep[None], s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _head_dot(
            p.astype(vblk.dtype), vblk, 1, 0)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pallas_impl(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                 block_len: int, pages_per_row: int, scale: float,
                 window: int = None):
    B, H, Tq, D = q.shape
    Hkv = k_cache.shape[1]
    n_rep = H // Hkv
    ring_pages = block_table.shape[1]
    n_blocks = ring_pages if window is None \
        else _window_steps(window, Tq, block_len, ring_pages)
    heads, fold = _choose_tile(H, Hkv, Tq, block_len, D, q.dtype.itemsize)
    rows = fold * Tq
    G = H // (heads * fold)
    parts = n_rep // fold          # tiles that share one KV head (1: none)
    grid = (B, G, n_blocks)
    name = "paged_attention" if window is None else WINDOW_KERNEL
    pallas_mode.note_tiling(name, grid=grid, heads=heads, rows=rows)
    table = jnp.maximum(block_table, 0)

    def q_map(b, g, j, table_ref, lens_ref, pos_ref):
        return (b, g, 0, 0)

    def kv_map(b, g, j, table_ref, lens_ref, pos_ref):
        # a page past the row's length names the row's last live page
        # again: the block index does not change, so nothing is fetched
        last = jnp.maximum(lens_ref[b] - 1, 0) // block_len
        if window is not None:     # the row's own walk, through the ring
            j = (_first_block(pos_ref[b], window, block_len) + j)
            page = table_ref[b, jnp.minimum(j, last) % ring_pages]
        else:
            page = table_ref[b, jnp.minimum(j, last)]
        return (page // pages_per_row, g // parts, page % pages_per_row, 0)

    tile = pl.BlockSpec((1, heads, rows, D), q_map)
    kv_tile = pl.BlockSpec((1, heads, block_len, D), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[tile, kv_tile, kv_tile],
        out_specs=tile,
        scratch_shapes=[
            pltpu.VMEM((heads, rows, D), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_len=block_len,
                               scale=scale, Tq=Tq, window=window)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H // fold, rows, D), q.dtype),
        compiler_params=_GRID_SEMANTICS,  # (B, G, pages): same shape
        interpret=pallas_mode.interpret(name),
        name=name,
    )(table, seq_lens, q_pos, q.reshape(B, H // fold, rows, D),
      k_cache, v_cache)
    return out.reshape(B, H, Tq, D)


def ragged_paged_attention(q, k_cache, v_cache, block_table, seq_lens,
                           q_pos, *, block_len: int,
                           pages_per_row: int = None, scale: float = None,
                           impl: str = None, window: int = None):
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
    """
    B, H, Tq, D = q.shape
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
        pallas_mode.count(
            "paged_attention" if window is None else WINDOW_KERNEL, "scan")
        impl_fn = _scan_impl
    else:
        impl_fn = _pallas_impl
    if window is None:
        return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                       block_len, pages_per_row, scale)
    return impl_fn(q, k_cache, v_cache, block_table, seq_lens, q_pos,
                   block_len, pages_per_row, scale, int(window))


def trivial_block_table(batch: int, cache_len: int,
                        block_len: int = DEFAULT_KV_BLOCK):
    """Identity table for a contiguous per-row cache: logical block j of
    row b is page b*nb + j. Returns (table [B, nb], nb); callers pad the
    cache to nb*block_len columns (padded cols are masked by seq_lens)."""
    nb = -(-cache_len // block_len)
    table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * nb
             + jnp.arange(nb, dtype=jnp.int32)[None, :])
    return table, nb
