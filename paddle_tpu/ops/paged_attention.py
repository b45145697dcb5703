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

Two implementations with the SAME per-block online-softmax op sequence:

- `_scan_impl` — plain XLA `lax.scan` over logical blocks. What `impl=None`
  picks on the CPU: interpret-mode Pallas unrolls every grid cell into the
  jaxpr, which makes tier-1 compile times explode, while this path compiles
  once and runs the identical arithmetic. Also the parity reference the
  kernel is checked against on the chip (chip_smoke.py).
- `_pallas_impl` — the TPU kernel, what `impl=None` picks on a TPU: grid
  (B, H, n_blocks) with the block table / lengths / positions
  scalar-prefetched so the index_map fetches only the pages a row actually
  occupies, and `@pl.when` skips compute for blocks past the row's length
  ("only over occupied KV blocks"). Mosaic (jax 0.9.0 / libtpu 0.0.34, v5e)
  lowers it at `block_len` 8, 16, 32 and 128, in bf16 and fp32, at query
  widths 1 and 16 — including the 8-row bf16 KV tile (half a packed
  sublane tile) of `DEFAULT_KV_BLOCK` and the 1-row q tile of the
  `generate()` decode loop; tests/test_mosaic_aot.py pins 8 and 16 in
  bf16, the two sizes the repo runs.

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
documented-tolerance-identical only.
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


def _as_pages(cache, block_len: int, pages_per_row: int):
    """[N, Hkv, L_slab, D] slab -> [N*pages_per_row, Hkv, block_len, D]
    pages. Columns past pages_per_row*block_len (slab write-padding for
    chunked prefill's fixed-width stripes) are never addressable by a
    block table and are sliced off here."""
    N, Hkv, L, D = cache.shape
    need = pages_per_row * block_len
    if L < need:
        raise ValueError(
            f"cache length {L} cannot back {pages_per_row} pages of "
            f"{block_len} tokens")
    pages = cache[:, :, :need, :].reshape(N, Hkv, pages_per_row, block_len,
                                          D)
    return jnp.transpose(pages, (0, 2, 1, 3, 4)).reshape(
        N * pages_per_row, Hkv, block_len, D)


def _scan_impl(q, k_pages, v_pages, block_table, seq_lens, q_pos,
               block_len: int, scale: float):
    """lax.scan over logical blocks, carrying (m, l, acc) — the same
    masked-score -> exact-zero-p -> alpha-rescale sequence as the kernel,
    one compiled program regardless of grid size."""
    B, H, Tq, D = q.shape
    Hkv = k_pages.shape[1]
    n_rep = H // Hkv
    row = q_pos[:, None] + jnp.arange(Tq, dtype=jnp.int32)   # [B, Tq]

    m0 = jnp.full((B, H, Tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)

    def body(carry, jt):
        m_prev, l_prev, acc = carry
        j, tcol = jt                         # scalar block idx, [B] page ids
        idx = jnp.maximum(tcol, 0)           # -1 padding clamps to page 0
        k_j = k_pages[idx]                   # [B, Hkv, KB, D]
        v_j = v_pages[idx]
        if n_rep > 1:
            k_j = jnp.repeat(k_j, n_rep, axis=1)
            v_j = jnp.repeat(v_j, n_rep, axis=1)
        s = jnp.einsum("bhtd,bhkd->bhtk", q, k_j,
                       preferred_element_type=jnp.float32) * scale
        col = j * block_len + jnp.arange(block_len, dtype=jnp.int32)  # [KB]
        keep = ((col[None, None, :] <= row[:, :, None])
                & (col[None, None, :] < seq_lens[:, None, None]))
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

    n_blocks = block_table.shape[1]
    js = jnp.arange(n_blocks, dtype=jnp.int32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (js, block_table.T))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _paged_kernel(table_ref, lens_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_len, scale):
    """Grid (B, H, n_blocks), kv innermost; online-softmax state in VMEM
    scratch across one (b, h) row's blocks. table/lens/pos arrive via
    scalar prefetch so the index_map already routed k_ref/v_ref to THIS
    block's page."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    Tq = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # occupied-blocks-only: a block wholly past this row's length cannot
    # contribute (every column masked -> exact no-op), so skip its compute
    @pl.when(j * block_len < lens_ref[b])
    def _compute():
        q = q_ref[0, 0]                       # [Tq, D]
        kblk = k_ref[0, 0]                    # [KB, D] (head picked by map)
        vblk = v_ref[0, 0]
        s = _dot(q, kblk, 1, 1) * scale
        col = (j * block_len
               + jax.lax.broadcasted_iota(jnp.int32, (Tq, block_len), 1))
        row = pos_ref[b] + jax.lax.broadcasted_iota(jnp.int32,
                                                    (Tq, block_len), 0)
        s = jnp.where((col <= row) & (col < lens_ref[b]), s, _NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(p.astype(vblk.dtype),
                                                   vblk, 1, 0)
        m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pallas_impl(q, k_pages, v_pages, block_table, seq_lens, q_pos,
                 block_len: int, scale: float):
    B, H, Tq, D = q.shape
    Hkv = k_pages.shape[1]
    n_rep = H // Hkv
    n_blocks = block_table.shape[1]
    table = jnp.maximum(block_table, 0).astype(jnp.int32)

    def q_map(b, h, j, table_ref, lens_ref, pos_ref):
        return (b, h, 0, 0)

    def kv_map(b, h, j, table_ref, lens_ref, pos_ref):
        return (table_ref[b, j], h // n_rep, 0, 0)

    def o_map(b, h, j, table_ref, lens_ref, pos_ref):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, Tq, D), q_map),
            pl.BlockSpec((1, 1, block_len, D), kv_map),
            pl.BlockSpec((1, 1, block_len, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Tq, D), o_map),
        scratch_shapes=[
            pltpu.VMEM((Tq, D), jnp.float32),
            pltpu.VMEM((Tq, 1), jnp.float32),
            pltpu.VMEM((Tq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_len=block_len,
                               scale=scale)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
        compiler_params=_GRID_SEMANTICS,  # (B, H, kv blocks): same shape
        interpret=pallas_mode.interpret("paged_attention"),
        name="paged_attention",
    )(table, seq_lens.astype(jnp.int32), q_pos.astype(jnp.int32),
      q, k_pages, v_pages)


def ragged_paged_attention(q, k_cache, v_cache, block_table, seq_lens,
                           q_pos, *, block_len: int,
                           pages_per_row: int = None, scale: float = None,
                           impl: str = None):
    """Attention of q [B, H, Tq, D] over block-table-addressed KV pages.

    k_cache/v_cache: [N, Hkv, L_slab, D] slabs (N need not equal B — block
    tables address pages globally). block_table [B, max_blocks] int32,
    seq_lens [B], q_pos [B] — see module docstring for the mask contract.
    pages_per_row defaults to L_slab // block_len (pass the pool's
    n_blocks when the slab carries chunk write-padding).
    impl: None = scan on the CPU, the kernel on a TPU; or name "scan" /
    "pallas" (on the CPU the kernel runs interpreted — the parity suite
    does that; `ops.pallas_mode` decides and counts).
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
    block_table = jnp.asarray(block_table, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    k_pages = _as_pages(k_cache, block_len, pages_per_row)
    v_pages = _as_pages(v_cache, block_len, pages_per_row)
    if impl == "scan":
        pallas_mode.count("paged_attention", "scan")
        return _scan_impl(q, k_pages, v_pages, block_table, seq_lens,
                          q_pos, block_len, scale)
    return _pallas_impl(q, k_pages, v_pages, block_table, seq_lens, q_pos,
                        block_len, scale)


def trivial_block_table(batch: int, cache_len: int,
                        block_len: int = DEFAULT_KV_BLOCK):
    """Identity table for a contiguous per-row cache: logical block j of
    row b is page b*nb + j. Returns (table [B, nb], nb); callers pad the
    cache to nb*block_len columns (padded cols are masked by seq_lens)."""
    nb = -(-cache_len // block_len)
    table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * nb
             + jnp.arange(nb, dtype=jnp.int32)[None, :])
    return table, nb
