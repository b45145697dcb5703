"""Ragged paged attention + chunked prefill suite (ISSUE 7).

Parity: `ragged_paged_attention` (ops/paged_attention.py) against the
fp32 `_attention_reference` oracle at <= 1e-5, over ragged lengths
(1, block_len-1, block_len, multi-block), mixed prefill-chunk + decode
rows, fragmented vs defragged block tables, bf16 inputs, and the real
Pallas kernel in interpret mode on CPU. Plus the satellite units — the
shared JitLRUCache policy, the pool's version-gated device block
tables / fragmentation gauge — and the engine-level acceptance
scenarios: chunk-granular poison blame (co-scheduled decode rows
survive bit-identically) and the SimClock TTFT win over the retired
pow2-bucket prefill.
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """This file's interpreted kernels are a hundred large CPU programs
    held by module-level `jax.jit` caches: let them go with the file, so
    that the worker's next files compile in a process that holds none."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


# ---- kernel parity vs the fp32 reference oracle ----

def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32),
                       dtype)


def _ref_paged(q, k_cache, v_cache, table, seq_lens, q_pos, block_len,
               pages_per_row, scale=None):
    """Oracle: gather each row's pages into contiguous KV, then run
    `_attention_reference` in fp32 with the ragged causal+length mask
    (col <= q_pos+t AND col < seq_len) as an additive mask."""
    from paddle_tpu.ops.attention import _NEG_INF, _attention_reference
    B, H, Tq, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    table = np.asarray(table)
    n_blocks = table.shape[1]
    Sk = n_blocks * block_len
    outs = []
    for b in range(B):
        ks, vs = [], []
        for j in range(n_blocks):
            g = max(int(table[b, j]), 0)
            r, p = divmod(g, pages_per_row)
            ks.append(k_cache[r, :, p * block_len:(p + 1) * block_len, :])
            vs.append(v_cache[r, :, p * block_len:(p + 1) * block_len, :])
        kb = jnp.concatenate(ks, axis=1)[None]     # [1, Hkv, Sk, D]
        vb = jnp.concatenate(vs, axis=1)[None]
        if kb.shape[1] != H:
            rep = H // kb.shape[1]
            kb = jnp.repeat(kb, rep, axis=1)
            vb = jnp.repeat(vb, rep, axis=1)
        col = np.arange(Sk)
        row = int(q_pos[b]) + np.arange(Tq)[:, None]
        keep = (col[None, :] <= row) & (col[None, :] < int(seq_lens[b]))
        mask = jnp.asarray(np.where(keep, 0.0, _NEG_INF),
                           jnp.float32)[None]
        outs.append(_attention_reference(
            q[b:b + 1].astype(jnp.float32), kb.astype(jnp.float32),
            vb.astype(jnp.float32), causal=False, scale=scale, mask=mask))
    return jnp.concatenate(outs, 0)


def _one_column_dead(lens, q_pos, fold, Tq):
    """[B, 1, Tq, 1]: the dead columns of the rows with one live column of
    several. Where the kernel's float32 trace holds the one-column body (PR
    48) they are zeros, in the scan a key-less query's finite values."""
    from paddle_tpu.ops.paged_attention import _one_column_rows
    one = (lens - q_pos == 1) & bool(_one_column_rows(fold, Tq, 4, False))
    return jnp.asarray(one)[:, None, None, None] \
        & (jnp.arange(Tq) > 0)[None, None, :, None]


def _identity_table(batch, n_blocks):
    return (np.arange(batch, dtype=np.int32)[:, None] * n_blocks
            + np.arange(n_blocks, dtype=np.int32)[None, :])


def test_scan_parity_ragged_decode_lengths():
    """Decode-shaped rows (Tq=1) at every ragged length class: 1,
    block_len-1, block_len, and multi-block — plus GQA head repeat."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(0)
    B, H, Hkv, D, bl, nb = 4, 4, 2, 16, 8, 4
    k = _rand(rng, (B, Hkv, nb * bl, D))
    v = _rand(rng, (B, Hkv, nb * bl, D))
    lens = np.array([1, bl - 1, bl, 3 * bl + 3], np.int32)
    q = _rand(rng, (B, H, 1, D))
    table = _identity_table(B, nb)
    q_pos = lens - 1                       # the newest token's position
    out = ragged_paged_attention(q, k, v, table, lens, q_pos,
                                 block_len=bl, impl="scan")
    ref = _ref_paged(q, k, v, table, lens, q_pos, bl, nb)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


def test_scan_parity_mixed_prefill_decode_rows():
    """One dispatch, four row flavors: chunk-0 prefill, chunk-1 prefill,
    a 1-valid-token decode row, and a near-capacity decode row. Only each
    row's valid query slice (t < adv) is compared — trailing chunk
    padding is garbage by contract."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(1)
    B, H, Hkv, D, bl, nb, C = 4, 4, 4, 16, 8, 4, 8
    k = _rand(rng, (B, Hkv, nb * bl, D))
    v = _rand(rng, (B, Hkv, nb * bl, D))
    q = _rand(rng, (B, H, C, D))
    q_pos = np.array([0, 8, 13, 29], np.int32)
    adv = np.array([8, 8, 1, 1], np.int32)
    lens = (q_pos + adv).astype(np.int32)
    table = _identity_table(B, nb)
    out = ragged_paged_attention(q, k, v, table, lens, q_pos,
                                 block_len=bl, impl="scan")
    ref = _ref_paged(q, k, v, table, lens, q_pos, bl, nb)
    for b in range(B):
        n = int(adv[b])
        diff = jnp.max(jnp.abs(out[b, :, :n] - ref[b, :, :n]))
        assert float(diff) <= 1e-5, f"row {b}"


def test_fragmented_table_matches_defragged_layout():
    """The same logical KV served through a scattered page layout must
    produce bitwise the result of the contiguous (defragged) layout: the
    block table is pure indirection, never arithmetic."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(2)
    H, Hkv, D, bl = 2, 2, 8, 4
    n_logical = 3
    kv_len = n_logical * bl
    k_log = _rand(rng, (1, Hkv, kv_len, D))
    v_log = _rand(rng, (1, Hkv, kv_len, D))
    q = _rand(rng, (1, H, 5, D))
    lens = np.array([10], np.int32)
    q_pos = np.array([5], np.int32)

    # defragged: one slab row, identity pages [0, 1, 2] (+1 pad block)
    k_a = jnp.pad(k_log, ((0, 0), (0, 0), (0, bl), (0, 0)))
    table_a = np.array([[0, 1, 2, -1]], np.int32)
    out_a = ragged_paged_attention(q, k_a, jnp.pad(
        v_log, ((0, 0), (0, 0), (0, bl), (0, 0))), table_a, lens, q_pos,
        block_len=bl, impl="scan")

    # fragmented: 2 slab rows (8 pages), logical block j lives at page
    # perm[j], the rest of the slab is noise the table never names
    perm = [5, 2, 7]
    k_b = _rand(rng, (2, Hkv, 4 * bl, D))
    v_b = _rand(rng, (2, Hkv, 4 * bl, D))
    for j, g in enumerate(perm):
        r, p = divmod(g, 4)
        sl = slice(p * bl, (p + 1) * bl)
        k_b = k_b.at[r, :, sl].set(k_log[0, :, j * bl:(j + 1) * bl])
        v_b = v_b.at[r, :, sl].set(v_log[0, :, j * bl:(j + 1) * bl])
    table_b = np.array([perm + [-1]], np.int32)
    out_b = ragged_paged_attention(q, k_b, v_b, table_b, lens, q_pos,
                                   block_len=bl, pages_per_row=4,
                                   impl="scan")
    assert np.array_equal(np.asarray(out_a), np.asarray(out_b))


def test_bf16_parity_documented_tolerance():
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(3)
    B, H, Hkv, D, bl, nb = 2, 2, 2, 16, 8, 3
    k32 = _rand(rng, (B, Hkv, nb * bl, D))
    v32 = _rand(rng, (B, Hkv, nb * bl, D))
    q32 = _rand(rng, (B, H, 4, D))
    lens = np.array([20, 7], np.int32)
    q_pos = np.array([16, 3], np.int32)
    table = _identity_table(B, nb)
    out = ragged_paged_attention(
        q32.astype(jnp.bfloat16), k32.astype(jnp.bfloat16),
        v32.astype(jnp.bfloat16), table, lens, q_pos, block_len=bl,
        impl="scan")
    ref = _ref_paged(q32, k32, v32, table, lens, q_pos, bl, nb)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) <= 2e-2


# (H, Hkv, Tq, block_len, table width, layout, tile): the cells' head
# layouts at small sizes (GQA n_rep 4, MHA), a decode row and a chunk, both
# block sizes the repo runs; a `tile` (KV heads, folded query heads) shrinks
# the module's VMEM budget to what that tile needs, so that the heads split
# into groups (G > 1), down to a part of one GQA group. Then what a group of
# P = 128 // block_len pages adds: table widths over P and no multiple of
# it, so that a row ends inside its second group (live and dead pages in
# one step), on a group's edge or inside its first; a prefix shared by two
# rows and a row scattered over other slots' slabs inside one group; the
# heads split over several tiles with more than one group a row
_KERNEL_CASES = [
    pytest.param(8, 2, 1, 8, 3, "fragmented", None, id="gqa4-tq1-bl8-frag"),
    pytest.param(8, 2, 4, 16, 3, "shared", None, id="gqa4-chunk-bl16-shared"),
    pytest.param(4, 4, 1, 16, 3, "fragmented", None, id="mha-tq1-bl16-frag"),
    pytest.param(4, 4, 4, 8, 3, "shared", None, id="mha-chunk-bl8-shared"),
    pytest.param(8, 2, 4, 8, 3, "identity", None,
                 id="gqa4-chunk-bl8-identity"),
    pytest.param(8, 4, 4, 8, 3, "fragmented", (2, 2), id="gqa2-chunk-bl8-G2"),
    pytest.param(8, 2, 4, 16, 3, "shared", (1, 2),
                 id="gqa4-chunk-bl16-G4-split-group"),
    pytest.param(4, 2, 4, 16, 11, "fragmented", None,
                 id="bl16-width11-fragmented"),
    pytest.param(4, 2, 1, 16, 11, "shared", None, id="bl16-width11-shared"),
    pytest.param(4, 4, 4, 8, 19, "fragmented", None,
                 id="bl8-width19-fragmented"),
    pytest.param(8, 2, 16, 8, 35, "shared", None, id="bl8-width35-shared"),
    pytest.param(4, 2, 4, 16, 16, "identity", None, id="bl16-width16-whole"),
    pytest.param(8, 4, 4, 16, 11, "fragmented", (2, 2),
                 id="bl16-width11-G2"),
    # multi-query, 20 query heads on one KV head (a ratio that is no power
    # of two): the step's chunk rows (80 folded rows), a one-token row (20:
    # not a whole sublane tile), and the group split over tiles of 5 heads
    pytest.param(20, 1, 4, 16, 3, "fragmented", None,
                 id="mqa20-chunk-bl16-frag"),
    pytest.param(20, 1, 1, 16, 11, "shared", None, id="mqa20-tq1-width11"),
    pytest.param(20, 1, 4, 8, 3, "identity", (1, 5),
                 id="mqa20-chunk-bl8-G4-split-group"),
]


@pytest.mark.parametrize("H,Hkv,Tq,bl,nb,layout,tile", _KERNEL_CASES)
def test_pallas_interpret_matches_scan_and_reference(monkeypatch, H, Hkv, Tq,
                                                     bl, nb, layout, tile):
    """The REAL kernel body (grid over (slot, head group), a loop over the
    row's live groups of 128 keys, a copy a page out of the slabs as
    stored, each page through its own table entry, VMEM online-softmax
    scratch over the folded rows) runs interpreted on the CPU and must
    agree with the scan path (a page a step) to 1e-6 and the oracle to
    1e-5 — tier-1 proof that the TPU kernel computes the same function.
    Every case: a slab with write-padding past the page region (filled
    with NaN: never addressed), `-1` table padding past each row's length,
    a full row, rows that end inside a page, an empty row, a one-token
    row, and an indirection through other slots' slabs."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    rng = np.random.RandomState(4)
    P = 128 // bl
    keys, cap, D, pad = P * bl, nb * bl, 8, 8
    # full, inside the second page, empty, one token, inside the third
    # page; and where the table is that wide: inside the second group, on
    # the first group's edge
    lens = np.array([n for n in (cap, bl + 3, 0, 1, 2 * bl + 5,
                                 keys + bl + 3, keys) if n <= cap], np.int32)
    B = len(lens)
    q_pos = np.maximum(lens - Tq, 0).astype(np.int32)

    def slab():
        x = _rand(rng, (B, Hkv, cap + pad, D))
        return x.at[:, :, cap:].set(jnp.nan)
    k, v = slab(), slab()
    q = _rand(rng, (B, H, Tq, D))
    if layout == "identity":
        table = _identity_table(B, nb)
    else:           # every row's pages scattered over other slots' slabs
        table = rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
        if layout == "shared":      # rows 0 and 1 read the same first page
            table[1, 0] = table[0, 0]
    table = np.where(np.arange(nb)[None, :] * bl < lens[:, None], table,
                     -1).astype(np.int32)
    heads, fold = tile or (Hkv, H // Hkv)
    if tile:
        monkeypatch.setattr(PA, "_VMEM_BUDGET",
                            PA._tile_bytes(heads, fold * Tq, bl, D, 4))
    pallas_mode.KERNEL_TILINGS.clear()
    run = {impl: PA.ragged_paged_attention(
        q, k, v, table, lens, q_pos, block_len=bl, pages_per_row=nb,
        impl=impl) for impl in ("scan", "pallas")}
    G = H // (heads * fold)
    assert (G > 1) == bool(tile)
    assert dict(pallas_mode.KERNEL_TILINGS) == {
        ("paged_attention", (
            ("grid", (B, G)), ("groups", -(-nb // P)), ("heads", heads),
            ("one_column_rows", PA._one_column_rows(fold, Tq, 4, False)),
            ("pages", P), ("rows", fold * Tq))): 1}
    assert bool(jnp.all(jnp.isfinite(run["pallas"])))
    dead = _one_column_dead(lens, q_pos, fold, Tq)
    assert not bool(jnp.any(jnp.where(dead, run["pallas"], 0.0)))
    assert float(jnp.max(jnp.abs(jnp.where(
        dead, 0.0, run["pallas"] - run["scan"])))) <= 1e-6
    assert not np.asarray(run["pallas"][2]).any()      # the empty row
    ref = _ref_paged(q, jnp.nan_to_num(k), jnp.nan_to_num(v), table, lens,
                     q_pos, bl, nb)
    for b in range(B):
        n = int(lens[b] - q_pos[b])        # valid query rows
        assert float(jnp.max(jnp.abs(run["pallas"][b, :, :n]
                                     - ref[b, :, :n]), initial=0.0)) <= 1e-5


# Where the kernel's loop has nothing in flight to wait for, or nobody to
# fetch for: (block_len, lengths in grid order, tile). 300 is three groups
# at either block size (its last inside a page), 128 one group to its edge
_PIPELINE_CASES = [
    pytest.param(16, (0, 300, 0, 0, 128, 0), None, id="bl16-grid-opens-empty"),
    pytest.param(8, (0, 0, 5, 300), None, id="bl8-opens-on-two-empty-rows"),
    pytest.param(16, (300, 0, 0), None, id="bl16-ends-on-empty-rows"),
    pytest.param(8, (0, 0, 0), None, id="bl8-every-row-empty"),
    pytest.param(16, (0, 300, 0, 129), (1, 2), id="bl16-G2-between-empty"),
    pytest.param(8, (128, 0, 257), (1, 1), id="bl8-G4-between-empty"),
]


@pytest.mark.parametrize("bl,lens,tile", _PIPELINE_CASES)
def test_kernel_pipeline_with_nothing_in_flight(monkeypatch, bl, lens, tile):
    """A grid step's first group is set going by the step before it, the
    grid's first step fetches its own (one pass more), and a row with no
    live group only fetches for the next: empty rows at the grid's start,
    at its end and in runs, alone and with the heads over several tiles
    (the next step is then the same row's next tile), a whole prompt as
    the query block. Against the scan, and zeros for an empty row."""
    from paddle_tpu.ops import paged_attention as PA
    rng = np.random.RandomState(21)
    H, Hkv, D, Tq, nb = 4, 2, 8, 24, 320 // bl
    lens = np.array(lens, np.int32)
    B = len(lens)
    q_pos = np.maximum(lens - Tq, 0).astype(np.int32)
    k = _rand(rng, (B, Hkv, nb * bl, D))
    v = _rand(rng, (B, Hkv, nb * bl, D))
    q = _rand(rng, (B, H, Tq, D))
    table = rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
    table = np.where(np.arange(nb)[None, :] * bl < lens[:, None], table,
                     -1).astype(np.int32)
    if tile:
        monkeypatch.setattr(PA, "_VMEM_BUDGET",
                            PA._tile_bytes(tile[0], tile[1] * Tq, bl, D, 4))
    run = {impl: np.asarray(PA.ragged_paged_attention(
        q, k, v, table, lens, q_pos, block_len=bl, pages_per_row=nb,
        impl=impl)) for impl in ("scan", "pallas")}
    assert np.isfinite(run["pallas"]).all()
    assert np.abs(run["pallas"] - run["scan"]).max() <= 1e-6
    assert not run["pallas"][lens == 0].any()
    assert lens.max() == 0 or run["pallas"][lens > 0].any()


def _ring_of(logical, length, ring, pad, written=None):
    """A ring slab `[Hkv, ring + pad, D]` as the pool holds it for a row
    of `length` committed positions (or `written`, counting a stripe's
    garbage past them): column c holds the newest position p with p = c
    (mod ring), a large finite value where nothing was written (a masked
    column is multiplied by an exact zero, so it must be finite, as a
    pool's stale keys are); the pad, never addressed, is NaN."""
    Hkv, _, D = logical.shape
    slab = np.full((Hkv, ring + pad, D), np.nan, np.float32)
    slab[:, :ring] = 1e3
    for p in range(written or length):
        slab[:, p % ring] = logical[:, p]
    return slab


def _ref_window(q, k_log, v_log, lens, q_pos, window, scale=None):
    """Oracle: `_attention_reference` over each row's contiguous logical
    keys, the mask `q_pos + t - window < col <= q_pos + t`, `col < len`."""
    from paddle_tpu.ops.attention import _NEG_INF, _attention_reference
    B, H, Tq, D = q.shape
    scale = scale or 1.0 / D ** 0.5
    outs = []
    for b in range(B):
        kb, vb = k_log[b][None], v_log[b][None]
        rep = H // kb.shape[1]
        kb, vb = jnp.repeat(kb, rep, axis=1), jnp.repeat(vb, rep, axis=1)
        col = np.arange(kb.shape[2])[None, :]
        row = int(q_pos[b]) + np.arange(Tq)[:, None]
        keep = (col <= row) & (col > row - window) & (col < int(lens[b]))
        mask = jnp.asarray(np.where(keep, 0.0, _NEG_INF), jnp.float32)[None]
        outs.append(_attention_reference(q[b:b + 1], kb, vb, causal=False,
                                         scale=scale, mask=mask))
    return jnp.concatenate(outs, 0)


# (H, Hkv, Tq, block_len, window, ring pages): a decode row and a chunk of
# the unified step, both block sizes, a window that is and is not a
# multiple of the block, GQA and MHA; the ring holds window + Tq, rounded
# up to whole pages
_WINDOW_CASES = [
    pytest.param(8, 2, 1, 8, 32, 6, id="gqa4-tq1-bl8-w32"),
    pytest.param(8, 2, 16, 16, 32, 3, id="gqa4-chunk16-bl16-w32"),
    pytest.param(8, 2, 16, 8, 20, 5, id="gqa4-chunk16-bl8-w20-ragged"),
    pytest.param(4, 4, 1, 16, 27, 3, id="mha-tq1-bl16-w27-ragged"),
    pytest.param(4, 4, 4, 8, 13, 3, id="mha-chunk4-bl8-w13-ragged"),
    # the window/full cell's ring: 65 pages are no multiple of a group's 8
    pytest.param(4, 2, 16, 16, 1024, 65, id="gqa2-chunk16-bl16-w1024-ring65"),
]


@pytest.mark.parametrize("H,Hkv,Tq,bl,window,ring_pages", _WINDOW_CASES)
def test_window_kernel_matches_scan_and_reference(H, Hkv, Tq, bl, window,
                                                  ring_pages):
    """The windowed walk (`paged_window`: a loop over the groups that cut
    a row's window, logical block -> ring page, both mask edges) interpreted
    on the CPU = the scan path = the oracle over each row's contiguous
    keys, at ragged lengths: shorter than the window, crossing it, twice
    and five times round the ring, an empty row and a one-token row.
    Columns the ring never wrote hold garbage, its write pad NaN."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    rng = np.random.RandomState(11)
    D, ring = 8, ring_pages * bl
    assert ring >= window + Tq
    lens = np.array([window - 3, window + bl + 1, 2 * ring + 5,
                     5 * ring + bl - 1, 0, 1], np.int32)
    lens = np.maximum(lens, 0)
    B, S = len(lens), int(lens.max()) + Tq
    q_pos = np.maximum(lens - Tq, 0).astype(np.int32)
    k_log = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v_log = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    k = jnp.asarray(np.stack([_ring_of(k_log[b], lens[b], ring, Tq)
                              for b in range(B)]))
    v = jnp.asarray(np.stack([_ring_of(v_log[b], lens[b], ring, Tq)
                              for b in range(B)]))
    q = _rand(rng, (B, H, Tq, D))
    pallas_mode.KERNEL_TILINGS.clear()
    run = {impl: PA.ragged_paged_attention(
        q, k, v, None, lens, q_pos, block_len=bl, pages_per_row=ring_pages,
        impl=impl, window=window) for impl in ("scan", "pallas")}
    P = 128 // bl
    groups = min(-(-(window + Tq - 1) // 128), -(-ring_pages // P)) + 1
    assert dict(pallas_mode.KERNEL_TILINGS) == {
        (PA.WINDOW_KERNEL, (
            ("grid", (B, 1)), ("groups", groups), ("heads", Hkv),
            ("one_column_rows",
             PA._one_column_rows(H // Hkv, Tq, 4, False)),
            ("pages", P), ("rows", H // Hkv * Tq))): 1}
    ref = _ref_window(q, jnp.asarray(k_log), jnp.asarray(v_log), lens,
                      q_pos, window)
    for b in range(B):
        n = int(lens[b] - q_pos[b])        # valid query rows
        for impl in ("scan", "pallas"):
            got = run[impl][b, :, :n]
            assert bool(jnp.all(jnp.isfinite(got))), (impl, b)
            assert float(jnp.max(jnp.abs(got - ref[b, :, :n]),
                                 initial=0.0)) <= 1e-5, (impl, b)
    dead = _one_column_dead(lens, q_pos, H // Hkv, Tq)
    assert not bool(jnp.any(jnp.where(dead, run["pallas"], 0.0)))
    assert float(jnp.max(jnp.abs(jnp.where(
        dead, 0.0, jnp.nan_to_num(run["pallas"])
        - jnp.nan_to_num(run["scan"]))))) <= 1e-6
    assert not np.asarray(run["pallas"][4]).any()      # the empty row


@pytest.mark.parametrize("impl,bl,window,Tq,ring_pages,lens,S", [
    ("scan", 8, 20, 4, 4, (70, 19, 33), 72),
    ("pallas", 8, 20, 4, 4, (70, 19, 33), 72),
    # more than one group a walk; neither the ring's 14 pages nor the
    # cache's 45 are a multiple of a group's 8
    ("pallas", 16, 200, 16, 14, (700, 199, 330, 225), 720),
])
def test_window_walk_through_a_ring_is_bitwise_the_walk_of_a_full_cache(
        impl, bl, window, Tq, ring_pages, lens, S):
    """The same keys in a ring and in a full-length contiguous cache, the
    windowed walk over either: the same bits, in the scan and in the
    kernel. The steps walked are the logical ones (aligned pages, aligned
    groups), and a step outside the window is an exact no-op."""
    from paddle_tpu.ops import paged_attention as PA
    rng = np.random.RandomState(12)
    H, Hkv, D = 4, 2, 8
    lens = np.array(lens, np.int32)
    B, nb = len(lens), S // bl
    ring = ring_pages * bl
    assert ring >= window + Tq and nb * bl == S
    q_pos = (lens - Tq).astype(np.int32)
    k_log = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v_log = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    kr = jnp.asarray(np.stack([_ring_of(k_log[b], lens[b], ring, Tq)
                               for b in range(B)]))
    vr = jnp.asarray(np.stack([_ring_of(v_log[b], lens[b], ring, Tq)
                               for b in range(B)]))
    q = _rand(rng, (B, H, Tq, D))
    through_ring = PA.ragged_paged_attention(
        q, kr, vr, None, lens, q_pos, block_len=bl,
        pages_per_row=ring_pages, impl=impl, window=window)
    contiguous = PA.ragged_paged_attention(
        q, jnp.asarray(k_log), jnp.asarray(v_log), _identity_table(B, nb),
        lens, q_pos, block_len=bl, pages_per_row=nb, impl=impl,
        window=window)
    assert np.array_equal(np.asarray(through_ring), np.asarray(contiguous))


def test_ring_write_splits_a_stripe_that_straddles_the_rings_end():
    """`update_kv_cache(ring=)`: position p lands at column p mod ring; a
    stripe that runs past the ring's end continues at its start (two
    parts, not clamped back onto live keys), row by row."""
    from paddle_tpu.ops.attention import update_kv_cache
    ring, T, Hkv, D = 24, 8, 2, 4
    pos = np.array([0, 16, 20, 23 + 2 * ring, 8], np.int32)
    B = len(pos)
    kc = jnp.zeros((B, Hkv, ring + T, D))
    new = jnp.asarray(np.arange(1, B * Hkv * T * D + 1, dtype=np.float32)
                      .reshape(B, Hkv, T, D))
    k2, v2 = update_kv_cache(kc, kc, new, -new, pos, ring=ring)
    for b in range(B):
        for t in range(T):
            col = (int(pos[b]) + t) % ring
            assert np.array_equal(np.asarray(k2[b, :, col]),
                                  np.asarray(new[b, :, t])), (b, t)
            assert np.array_equal(np.asarray(v2[b, :, col]),
                                  -np.asarray(new[b, :, t])), (b, t)
        untouched = sorted(set(range(ring))
                           - {(int(pos[b]) + t) % ring for t in range(T)})
        assert not np.asarray(k2[b][:, untouched]).any()
    with pytest.raises(ValueError, match="needs 32 columns"):
        update_kv_cache(kc[:, :, :ring], kc[:, :, :ring], new, new, pos,
                        ring=ring)


@pytest.mark.parametrize("name,q_shape,hkv,bl,want", [
    # the serve cells: everything in one tile, G = 1
    ("mistral decode", (128, 32, 16, 128), 8, 16, (8, 4)),
    ("mistral prefill", (32, 32, 16, 128), 8, 16, (8, 4)),
    ("olmoe decode", (128, 16, 16, 128), 16, 16, (16, 1)),
    ("mellum step", (32, 32, 16, 128), 4, 16, (4, 8)),
    ("mqa 20:1 reasoning step", (256, 20, 16, 128), 1, 16, (1, 20)),
    ("mqa 20:1 decode loop", (8, 20, 1, 128), 1, 16, (1, 20)),
    ("mqa 20:1 prompt 512", (1, 20, 512, 128), 1, 16, (1, 4)),
    # one-shot generate(): the decode loop, then whole-prompt prefills
    ("gqa decode loop", (8, 32, 1, 128), 8, 8, (8, 4)),
    ("gqa prompt 512", (2, 32, 512, 128), 8, 8, (1, 4)),
    ("gqa prompt 1024", (1, 32, 1024, 128), 8, 8, (1, 2)),
    ("mha prompt 2048", (1, 16, 2048, 128), 16, 8, (1, 1)),
])
def test_tile_choice_follows_shapes_and_budget(name, q_shape, hkv, bl, want):
    """`_choose_tile` at the shapes the cells and generate() run, bf16: the
    whole head set in one tile wherever it fits the module's one budget,
    fewer KV heads, then a part of one GQA group, where it does not. The
    budget counts a group's 128 keys at either block size: heads are given
    up, never keys."""
    from paddle_tpu.ops import paged_attention as PA
    _, H, Tq, D = q_shape
    assert PA._group_pages(bl) * bl == PA._GROUP_KEYS == 128
    assert PA._tile_bytes(1, Tq, 8, D, 2) == PA._tile_bytes(1, Tq, 16, D, 2)
    assert PA._tile_bytes(1, Tq, bl, D, 2) >= 2 * 2 * 128 * D * 2
    heads, fold = PA._choose_tile(H, hkv, Tq, bl, D, 2)
    assert (heads, fold) == want, name
    assert hkv % heads == 0 and (H // hkv) % fold == 0
    if heads * fold > 1:
        assert PA._tile_bytes(heads, fold * Tq, bl, D, 2) <= PA._VMEM_BUDGET
    # the next larger tile would not have fit
    if (heads, fold) != (hkv, H // hkv):
        bigger = (heads * 2, fold) if fold == H // hkv else (1, fold * 2)
        assert PA._tile_bytes(bigger[0], bigger[1] * Tq, bl, D, 2) \
            > PA._VMEM_BUDGET


def test_tpu_path_hands_the_slabs_to_the_kernel_as_stored(monkeypatch):
    """On the TPU path nothing slices, transposes, reshapes or copies a
    slab before the kernel: the function's own k_cache / v_cache variables
    go to the kernel's one jitted entry (`_paged_call`) and inside it to
    its one `pallas_call`, once each (the kernel copies a group's pages out
    of the slab in HBM itself, so a slab is one operand whatever the pages
    a group), and no other equation reads them."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    monkeypatch.setattr(pallas_mode, "platform", lambda: "tpu")
    B, H, Hkv, Tq, D, bl, nb = 4, 8, 2, 4, 128, 16, 3
    slab = jax.ShapeDtypeStruct((B, Hkv, nb * bl + 16, D), jnp.bfloat16)

    def f(q, k, v, table, lens, q_pos):
        return PA.ragged_paged_attention(q, k, v, table, lens, q_pos,
                                         block_len=bl, pages_per_row=nb)
    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((B, H, Tq, D), jnp.bfloat16), slab, slab,
        jax.ShapeDtypeStruct((B, nb), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).jaxpr
    for name, want in (("jit", "_paged_call"), ("pallas_call",
                                                "paged_attention")):
        k_var, v_var = jaxpr.invars[1], jaxpr.invars[2]
        readers = [e for e in jaxpr.eqns
                   if any(x is k_var or x is v_var for x in e.invars)]
        assert [e.primitive.name for e in readers] == [name]
        call, = readers
        assert sum(x is k_var for x in call.invars) == 1
        assert sum(x is v_var for x in call.invars) == 1
        assert call.params["name"] == want
        assert sum(e.primitive.name == name for e in jaxpr.eqns) == 1
        for e in jaxpr.eqns:                 # and nothing slab-sized is made
            for o in e.outvars:
                if e is not call:
                    assert o.aval.size < slab.size, e
        if name == "jit":                    # the same inside the entry
            jaxpr = call.params["jaxpr"].jaxpr


@pytest.mark.parametrize("impl,bl,nb,L,C", [
    ("scan", 8, 3, 20, 8),
    ("pallas", 8, 3, 20, 8),
    # three groups of 128 keys; chunk edges inside a group and on one
    ("pallas", 16, 19, 300, 32),
])
def test_chunked_prefill_bitwise_equals_whole_prompt(impl, bl, nb, L, C):
    """Chunk invariance, the property the engine's bit-identity rests on:
    within one implementation a query row's output depends only on its
    absolute position and the committed KV — never on the chunk boundary
    — so chunked outputs match the whole-prompt dispatch BITWISE, in the
    scan (a page a step) and in the kernel (128 keys a step)."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(5)
    H, Hkv, D = 2, 2, 8
    k = _rand(rng, (1, Hkv, nb * bl, D))
    v = _rand(rng, (1, Hkv, nb * bl, D))
    q = _rand(rng, (1, H, L, D))
    table = _identity_table(1, nb)
    attend = jax.jit(lambda q, lens, q_pos: ragged_paged_attention(
        q, k, v, table, lens, q_pos, block_len=bl, impl=impl))
    whole = attend(q, np.array([L], np.int32), np.array([0], np.int32))
    for off in range(0, L, C):
        n = min(C, L - off)
        qc = jnp.zeros((1, H, C, D), q.dtype).at[:, :, :n].set(
            q[:, :, off:off + n])
        out = attend(qc, np.array([off + n], np.int32),
                     np.array([off], np.int32))
        assert np.array_equal(np.asarray(out[:, :, :n]),
                              np.asarray(whole[:, :, off:off + n])), \
            f"chunk at offset {off} diverged from whole-prompt prefill"


# ---- the latent walk (`paged_latent`, PR 36) ----

def _ref_latent(q, qr, c, r, table, lens, q_pos, block_len, pages_per_row,
                scale):
    """Dense oracle, float64, a row and a query at a time: scores from the
    two products over the keys the query may see, softmax, values = the
    latent."""
    q, qr, c, r = (np.asarray(a, np.float64) for a in (q, qr, c, r))
    B, H, Tq, R = q.shape
    out = np.zeros((B, H, Tq, R))
    for b in range(B):
        pages = [max(int(g), 0) for g in np.asarray(table)[b]]
        cols = np.concatenate([
            (g // pages_per_row, g % pages_per_row * block_len + i)
            for g in pages for i in range(block_len)]).reshape(-1, 2)
        cb, rb = c[cols[:, 0], 0, cols[:, 1]], r[cols[:, 0], 0, cols[:, 1]]
        for t in range(Tq):
            n = min(int(q_pos[b]) + t + 1, int(lens[b]))
            if n <= 0:
                continue
            s = scale * (q[b, :, t] @ cb[:n].T + qr[b, :, t] @ rb[:n].T)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, t] = (p / p.sum(-1, keepdims=True)) @ cb[:n]
    return out


@pytest.mark.parametrize("impl", ["scan", "pallas"])
@pytest.mark.parametrize("Tq,bl,layout", [
    (1, 8, "identity"), (16, 8, "fragmented"), (16, 16, "identity"),
    (1, 16, "fragmented")])
def test_latent_walk_matches_the_dense_oracle(impl, Tq, bl, layout):
    """Ragged lengths (one key, inside a page, a page's edge, past the
    first group of 128 keys, the whole slot, an empty row), every query
    head over the one latent, a write pad of NaN behind the pages, the
    block table's indirection: the scan and the kernel (interpreted)
    against the dense sum."""
    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.paged_attention import (LATENT_KERNEL,
                                                ragged_paged_attention)
    rng = np.random.RandomState(7)
    H, R, Dr, nb, pad = 4, 16, 8, 160 // bl + 1, 8
    cap = nb * bl
    lens = np.array([1, bl + 3, 2 * bl, 128 + bl + 3, cap, 0], np.int32)
    lens = np.maximum(lens, np.where(lens > 0, Tq, 0)).astype(np.int32)
    B = len(lens)
    q_pos = np.maximum(lens - Tq, 0).astype(np.int32)
    c = _rand(rng, (B, 1, cap + pad, R)).at[:, :, cap:].set(jnp.nan)
    r = _rand(rng, (B, 1, cap + pad, Dr)).at[:, :, cap:].set(jnp.nan)
    q, qr = _rand(rng, (B, H, Tq, R)), _rand(rng, (B, H, Tq, Dr))
    table = _identity_table(B, nb) if layout == "identity" \
        else rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
    pallas_mode.KERNEL_TRACES.clear()
    out = ragged_paged_attention(q, c, r, table, lens, q_pos, block_len=bl,
                                 pages_per_row=nb, scale=0.2, impl=impl,
                                 q_rope=qr)
    path = "scan" if impl == "scan" else "interpret"
    assert dict(pallas_mode.KERNEL_TRACES) == {(LATENT_KERNEL, path): 1}
    want = _ref_latent(q, qr, c, r, table, lens, q_pos, bl, nb, 0.2)
    live = (q_pos[:, None] + np.arange(Tq)[None] < lens[:, None])
    err = np.abs(np.asarray(out) - want)[
        np.broadcast_to(live[:, None, :, None], want.shape)]
    assert np.isfinite(np.asarray(out)).all() and err.max() <= 2e-5


@pytest.mark.parametrize("impl,bl,nb,L,C", [
    ("scan", 8, 3, 20, 8),
    ("pallas", 8, 3, 20, 8),
    ("scan", 16, 19, 300, 16),
    ("pallas", 16, 19, 300, 16),
])
def test_latent_chunked_prefill_bitwise_equals_whole_prompt(impl, bl, nb, L,
                                                            C):
    """Chunk invariance on a latent cache, in both implementations: the
    exact-zero masking is the walk's, whatever the page holds."""
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rng = np.random.RandomState(8)
    H, R, Dr = 4, 16, 8
    c, r = _rand(rng, (1, 1, nb * bl, R)), _rand(rng, (1, 1, nb * bl, Dr))
    q, qr = _rand(rng, (1, H, L, R)), _rand(rng, (1, H, L, Dr))
    table = _identity_table(1, nb)
    attend = jax.jit(lambda q, qr, lens, q_pos: ragged_paged_attention(
        q, c, r, table, lens, q_pos, block_len=bl, scale=0.25, impl=impl,
        q_rope=qr))
    whole = attend(q, qr, np.array([L], np.int32), np.array([0], np.int32))
    for off in range(0, L, C):
        n = min(C, L - off)
        qc, qrc = (jnp.zeros((1, H, C, x.shape[3]), x.dtype)
                   .at[:, :, :n].set(x[:, :, off:off + n]) for x in (q, qr))
        out = attend(qc, qrc, np.array([off + n], np.int32),
                     np.array([off], np.int32))
        assert np.array_equal(np.asarray(out[:, :, :n]),
                              np.asarray(whole[:, :, off:off + n])), \
            f"chunk at offset {off} diverged from whole-prompt prefill"


def test_latent_walk_refuses_what_it_is_not():
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    z = jnp.zeros
    args = (z((1, 2, 1, 8)), z((1, 1, 16, 8)), z((1, 1, 16, 4)),
            _identity_table(1, 2), np.array([3]), np.array([2]))
    for kw in (dict(scale=None), dict(scale=1.0, window=4),
               dict(scale=1.0, q_rope=z((1, 2, 1, 2)))):
        with pytest.raises(ValueError, match="latent cache"):
            ragged_paged_attention(*args, block_len=8, **{
                "q_rope": z((1, 2, 1, 4)), **kw})
    with pytest.raises(ValueError, match="latent cache"):      # two heads
        ragged_paged_attention(
            args[0], z((1, 2, 16, 8)), z((1, 2, 16, 4)), *args[3:],
            block_len=8, scale=1.0, q_rope=z((1, 2, 1, 4)))


def test_latent_tile_folds_heads_inside_the_budget():
    """One KV "head": the tile takes as many query heads as the budget
    holds in its rows. The serve cell's step (64 heads x 16 columns over
    512 + 128 columns) splits in two; a decode row of `generate()` does
    not."""
    from paddle_tpu.ops import paged_attention as PA
    assert PA._choose_tile(64, 1, 16, 16, 512 + 128, 2) == (1, 32)
    assert PA._choose_tile(64, 1, 1, 16, 512 + 128, 2) == (1, 64)
    assert PA._kernel_name(None, True) == "paged_latent"
    for other in ("paged_attention", "paged_window"):
        assert other not in PA.LATENT_KERNEL


# ---- the one-column body (PR 48): a row with one live column runs its
# ---- groups over `fold` rows a head, not `fold x Tq` ----

def _mixed_step(walk, dtype, seed=5):
    """A unified step's rows at Tq = 16 over pages of 16: one-column rows
    (a decode row one key long, inside the first group, past it, at the
    slot's end), chunk rows (every column live), a verify-width row (three
    live columns), a short prompt tail, and free rows (length 0, one
    between live rows and one at the grid's end). Returns (call, q,
    (q_pos, adv), fold): `call(q, impl)` runs `walk` over the step."""
    rng = np.random.RandomState(seed)
    Tq, bl, D = 16, 16, 64
    H, Hkv = {"gqa4": (8, 2), "mqa20": (20, 1), "mha": (4, 4),
              "window-ring": (8, 2)}[walk]
    #                one  chunk one  free verify one  chunk tail one  free
    ends = np.array([1,   48,   130, 0,   77,    37,  259,  5,   272, 0],
                    np.int32)
    adv = np.array([1,    16,   1,   0,   3,     1,   16,   5,   1,   0],
                   np.int32)
    B, nb = len(ends), 17
    q_pos = ends - adv
    q = _rand(rng, (B, H, Tq, D), dtype)
    kw = dict(block_len=bl)
    if walk == "window-ring":
        window, ring_pages = 40, 4             # a ring of 64 >= 40 + 16
        ring = ring_pages * bl
        logical = rng.standard_normal((2, B, Hkv, nb * bl, D)) \
            .astype(np.float32)
        k, v = (jnp.asarray(np.stack([
            _ring_of(x[b], ends[b], ring, Tq) for b in range(B)]), dtype)
            for x in logical)
        table = None
        kw.update(pages_per_row=ring_pages, window=window)
    else:
        k = _rand(rng, (B, Hkv, nb * bl, D), dtype)
        v = _rand(rng, (B, Hkv, nb * bl, D), dtype)
        table = rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
        kw.update(pages_per_row=nb)

    def call(q, impl, columns=slice(None)):
        from paddle_tpu.ops.paged_attention import ragged_paged_attention
        return np.asarray(ragged_paged_attention(
            q[:, :, columns], k, v, table, ends, q_pos, impl=impl,
            **kw).astype(jnp.float32))
    return call, q, (q_pos, adv), H // Hkv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("walk", ["gqa4", "mqa20", "mha", "window-ring"])
def test_one_column_rows_take_the_one_column_body_and_keep_every_bit(
        monkeypatch, walk, dtype):
    """A mixed step through the kernel (interpreted) with the one-column
    body in its trace, against the same step with the wide body alone (the
    static rule `_one_column_rows` patched to 0: there is no run-time
    switch): every live position the same bits; a one-column row also the
    bits of today's one-token call on `q[:, :, :1]`; its fifteen dead
    columns zeros; everything within the documented tolerance of the scan.
    `KERNEL_TILINGS` says which traces hold the second body: `fold` rows a
    head, and none where one column takes as many packed sublane tiles as
    sixteen (MHA in bf16: 1 row of 16 beside 16 of 16).

    On the CPU the kernel's products are XLA's CPU dots, which contract a
    float32 tile of one row (MHA: `fold` = 1) as a matrix-vector product,
    with other roundings than a tile of sixteen: there the wide body's
    bits are the one-token call's neither today, and the comparison with
    the wide body is by tolerance. On a TPU a row of the MXU's result does
    not depend on how many rows ride with it."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    call, q, (q_pos, adv), fold = _mixed_step(walk, dtype)
    name = PA.WINDOW_KERNEL if walk == "window-ring" else "paged_attention"
    engaged = not (walk == "mha" and dtype == jnp.bfloat16)

    def one_column_rows():
        (kernel, tiling), = pallas_mode.KERNEL_TILINGS
        assert kernel == name
        return dict(tiling)["one_column_rows"]

    pallas_mode.KERNEL_TILINGS.clear()
    both = call(q, "pallas")
    assert one_column_rows() == (fold if engaged else 0)
    pallas_mode.KERNEL_TILINGS.clear()
    token = call(q, "pallas", slice(0, 1))
    assert one_column_rows() == 0                       # Tq == 1: one body
    monkeypatch.setattr(PA, "_one_column_rows", lambda *a: 0)
    pallas_mode.KERNEL_TILINGS.clear()
    wide = call(q, "pallas")
    assert one_column_rows() == 0
    scan = call(q, "scan")

    exact = not (walk == "mha" and dtype == jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    assert np.isfinite(both).all()
    for b in range(len(adv)):
        live = both[b, :, :adv[b]]
        if exact:
            assert np.array_equal(live, wide[b, :, :adv[b]]), b
        assert np.abs(live - wide[b, :, :adv[b]]).max(initial=0) <= tol, b
        assert np.abs(live - scan[b, :, :adv[b]]).max(initial=0) <= tol, b
        if adv[b] == 1:
            assert live.any()
            assert np.array_equal(live, token[b]), b
            if engaged:
                assert not both[b, :, 1:].any(), b
    assert not both[adv == 0].any()                     # the free rows


def test_a_walk_under_a_selection_holds_the_wide_body_alone():
    """The union walk's `sel=` call: its one-column rows have length 0 and
    reach no group, so its trace holds one body (`one_column_rows` 0) at a
    shape where the plain latent walk holds two; the gathered call is a
    one-token call."""
    from paddle_tpu.ops import index_select as IX
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.attention import PagedView
    case = _sparse_case(np.random.RandomState(3), 16, 16, "identity")
    sel = IX.select(case["qi"], case["w"], case["ki"], case["q_pos"], 32,
                    paged=PagedView(case["table"], case["lens"], 16,
                                    case["nb"]))
    pallas_mode.KERNEL_TILINGS.clear()
    PA.sparse_latent_attention(
        case["q"], case["c"], case["r"], case["table"], case["lens"],
        case["q_pos"], sel=sel, block_len=16, pages_per_row=case["nb"],
        scale=0.2, q_rope=case["qr"], impl="pallas")
    said = {dict(t)["rows"]: dict(t)["one_column_rows"]
            for k, t in pallas_mode.KERNEL_TILINGS if k == PA.SPARSE_KERNEL}
    H = case["q"].shape[1]
    assert said == {H: 0, H * 16: 0}
    assert PA._one_column_rows(H, 16, 4, False) == H


# ---- packed queries (PR 50): a dense latent layer's queries and result
# ---- stay token-major on the step's packed block ----

# (live columns a row): ISSUE 50's mixed step; free slots between live ones
# and at both ends; a step that fills its block, whose last row's window
# runs into the pad; decode rows alone; chunk rows alone
_PACKED_STEPS = {
    "mixed": [1, 16, 3, 1, 0, 16, 1, 7, 1, 1, 16, 2],
    "free-slots": [0, 1, 0, 0, 16, 0, 1, 5, 0],
    "last-into-pad": [16, 1, 1, 3],
    "decode-rows": [1] * 9,
    "chunk-rows": [16] * 3,
}


def _packed_step(adv, H, dtype, seed=11, slack=0):
    """A packed step's operands for the latent walk at chunk 16 over pages
    of 16: `adv [N]` live columns a slot at random positions of fragmented
    tables, the live tokens packed into `T = sum(adv) + slack` positions
    (`token_pack`) and padded by 15. Returns (call, pack, operands): `call(
    impl, lens=, starts=, width=, q=, rows=)` runs the packed walk (over
    the slots `rows`)."""
    from paddle_tpu.ops.attention import token_pack
    from paddle_tpu.ops.paged_attention import packed_latent_attention
    rng = np.random.RandomState(seed)
    C, bl, nb, R, Dr = 16, 16, 19, 32, 16
    adv = np.asarray(adv, np.int32)
    N, T = len(adv), int(adv.sum()) + slack
    # one key long, inside the first group, past it, at the slot's end
    ends = np.resize(np.array([0, 37, 130, 259, nb * bl, 48], np.int32), N)
    ends = np.where(adv > 0, np.maximum(ends, adv), 0).astype(np.int32)
    ends[np.flatnonzero(adv == 1)[:1]] = 1
    pos = ends - adv
    pack = token_pack(jnp.asarray(adv), jnp.asarray(pos), C, T)
    q, qr = _rand(rng, (T + C - 1, H, R), dtype), \
        _rand(rng, (T + C - 1, H, Dr), dtype)
    c = _rand(rng, (N, 1, nb * bl + C, R), dtype)
    r = _rand(rng, (N, 1, nb * bl + C, Dr), dtype)
    table = rng.permutation(N * nb).astype(np.int32).reshape(N, nb)

    def call(impl, lens=ends, starts=pack.dst[:, 0], width=C, q=(q, qr),
             rows=slice(None)):
        return np.asarray(packed_latent_attention(
            *q, c, r, table[rows], lens[rows], pos[rows], starts,
            width=width, block_len=bl, pages_per_row=nb, scale=0.2,
            impl=impl).astype(jnp.float32))
    return call, pack, (q, qr, c, r, table, ends, pos)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,tiles", [(32, 1), (64, 2)],
                         ids=["32-heads-G1", "64-heads-G2"])
@pytest.mark.parametrize("step", list(_PACKED_STEPS))
def test_packed_latent_walk_matches_the_scan(monkeypatch, step, heads, tiles,
                                             dtype):
    """The kernel (interpreted) over a packed step against `_scan_impl` on
    the same rows unpacked: every live position within the documented
    tolerance, whatever its neighbours' dead columns wrote before it; a
    row with one live column has the bits of today's one-token call (its
    queries are the tile's first rows as they lie: PR 48's column-0
    cases); positions no row writes arrive as zeros; `KERNEL_TILINGS`
    records the packed positions and both bodies; 64 heads split into two
    tiles of 32 (a tile's head slice is whole sublane tiles)."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    adv = np.asarray(_PACKED_STEPS[step], np.int32)
    call, pack, (q, qr, c, r, table, ends, pos) = _packed_step(
        adv, heads, dtype)
    if tiles == 2:      # a budget that holds 32 heads' 512 rows, not 64's
        item = jnp.dtype(dtype).itemsize
        monkeypatch.setattr(PA, "_VMEM_BUDGET",
                            PA._tile_bytes(1, 32 * 16, 16, 48, item))
    pallas_mode.KERNEL_TILINGS.clear()
    got = call("pallas")
    (name, tiling), = pallas_mode.KERNEL_TILINGS
    tiling = dict(tiling)
    assert name == PA.LATENT_KERNEL
    assert (tiling["grid"], tiling["rows"], tiling["one_column_rows"],
            tiling["packed_queries"]) == (
        (len(adv), tiles), 32 * 16, 32, q.shape[0])

    # the reference: the rows unpacked, the scan as it stands
    def unpack(x):
        return jnp.swapaxes(pack.unpack(x[:-15, None]), 1, 2)
    want = np.asarray(PA._scan_impl(
        unpack(q), c, r, *map(jnp.asarray, (table, ends, pos)), 16,
        table.shape[1], 0.2, None,
        unpack(qr)).astype(jnp.float32))                   # [N, H, 16, R]
    scan = call("scan")
    n_live = int(adv.sum())
    live = np.asarray(pack.live)[:n_live]
    slot, col = np.asarray(pack.slot)[:n_live], np.asarray(pack.col)[:n_live]
    assert live.all()
    assert np.array_equal(scan[:n_live], want[slot, :, col])
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-6
    assert np.isfinite(got).all()
    assert np.abs(got[:n_live] - want[slot, :, col]).max() <= tol
    # behind the last row's window nobody writes
    end = max(int(s) + (16 if a > 1 else 1)
              for s, a in zip(np.asarray(pack.dst[:, 0]), adv) if a)
    assert not got[end:].any() and not scan[n_live:].any()
    # a one-column row: the one-token call's bits at its position
    ones = np.flatnonzero(adv == 1)
    if len(ones):
        at = np.asarray(pack.dst[:, 0])[ones]
        token = call("pallas", rows=ones, width=1, q=(q[at], qr[at]),
                     starts=np.arange(len(ones), dtype=np.int32))
        # (on the CPU a float32 product of 64 rows, the one-token call's
        # whole tile, rounds otherwise than the same rows 32 at a time)
        assert np.abs(got[at] - token).max() <= tol
        if tiles == 1 or dtype == jnp.bfloat16:
            assert np.array_equal(got[at], token)


@pytest.mark.parametrize("heads,tiles", [(32, 1), (64, 2)],
                         ids=["32-heads-G1", "64-heads-G2"])
def test_no_live_packed_position_is_changed_by_a_neighbours_dead_columns(
        monkeypatch, heads, tiles):
    """A wide row writes sixteen positions from its start whatever its
    live columns, so those past them are the next rows'. The whole step
    against its rows run one at a time (every other row given no live
    column: such a row walks no group and writes nothing): each live
    position the same bits, and a row alone leaves every position outside
    its window zero."""
    from paddle_tpu.ops import paged_attention as PA
    adv = np.asarray(_PACKED_STEPS["mixed"], np.int32)
    call, pack, (q, *_, ends, pos) = _packed_step(adv, heads, jnp.float32,
                                                  slack=3)
    if tiles == 2:
        monkeypatch.setattr(PA, "_VMEM_BUDGET",
                            PA._tile_bytes(1, 32 * 16, 16, 48, 4))
    whole = call("pallas")
    starts = np.asarray(pack.dst[:, 0])
    for n in np.flatnonzero(adv):
        alone = np.array(call(
            "pallas", lens=np.where(np.arange(len(adv)) == n, ends, pos)))
        mine = slice(starts[n], starts[n] + adv[n])
        assert np.array_equal(alone[mine], whole[mine]), n
        window = slice(starts[n], starts[n] + (16 if adv[n] > 1 else 1))
        alone[window] = 0
        assert not alone.any(), n


def test_a_whole_prompt_is_walked_as_rows_of_sixteen_columns():
    """`generate()`'s prefill through the kernel: a row wider than
    `_PACKED_COLUMNS` is cut into rows of that many, each with its own
    start, position and length over the one table row, so the tile is the
    engine's whatever the prompt; every position within tolerance of the
    scan over the whole width, a tail that is no whole piece included."""
    from paddle_tpu.ops import paged_attention as PA
    from paddle_tpu.ops import pallas_mode
    rng = np.random.RandomState(4)
    B, W, H, R, Dr, bl, nb = 2, 37, 32, 32, 16, 8, 6
    q, qr = _rand(rng, (B * W, H, R)), _rand(rng, (B * W, H, Dr))
    c, r = _rand(rng, (B, 1, nb * bl, R)), _rand(rng, (B, 1, nb * bl, Dr))
    args = (c, r, _identity_table(B, nb), np.full(B, W, np.int32),
            np.zeros(B, np.int32), np.arange(B, dtype=np.int32) * W)
    kw = dict(width=W, block_len=bl, pages_per_row=nb, scale=0.2)
    pallas_mode.KERNEL_TILINGS.clear()
    got = PA.packed_latent_attention(q, qr, *args, impl="pallas", **kw)
    (_, tiling), = pallas_mode.KERNEL_TILINGS
    assert dict(tiling)["grid"] == (B * 3, 1)
    assert dict(tiling)["rows"] == H * PA._PACKED_COLUMNS
    want = PA.packed_latent_attention(q, qr, *args, impl="scan", **kw)
    assert got.shape == want.shape == q.shape
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 5e-6


# ---- a selection over the latent cache (`paged_sparse`, `index_score`,
# ---- `index_topk`; PR 39) ----

def _sparse_case(rng, Tq, bl, layout, H=4, R=16, Dr=8, Hi=2, Di=128):
    """Rows that decode (one live column), chunk rows below and above the
    selection's size, an empty row; index keys beside the latent pages."""
    nb, pad = 160 // bl + 1, 8
    cap = nb * bl
    adv = np.array([1, Tq, Tq, 1, Tq, 0], np.int32)
    lens = np.array([bl + 3, Tq, 128 + bl + 3, cap, cap, 0], np.int32)
    lens = np.maximum(lens, adv).astype(np.int32)
    B = len(lens)
    q_pos = (lens - adv).astype(np.int32)
    c = _rand(rng, (B, 1, cap + pad, R)).at[:, :, cap:].set(jnp.nan)
    r = _rand(rng, (B, 1, cap + pad, Dr)).at[:, :, cap:].set(jnp.nan)
    ki = _rand(rng, (B, 1, cap + pad, Di))
    q, qr = _rand(rng, (B, H, Tq, R)), _rand(rng, (B, H, Tq, Dr))
    qi, w = _rand(rng, (B, Hi, Tq, Di)), _rand(rng, (B, Hi, Tq))
    table = _identity_table(B, nb) if layout == "identity" \
        else rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
    return dict(c=c, r=r, ki=ki, q=q, qr=qr, qi=qi, w=w, table=table,
                lens=lens, q_pos=q_pos, adv=adv, nb=nb, cap=cap)


def _logical(cache, table, bl, nb):
    rows, cols = table // nb, (table % nb * bl)[..., None] + np.arange(bl)
    return np.asarray(cache, np.float64)[rows[..., None], 0, cols].reshape(
        table.shape[0], table.shape[1] * bl, -1)


def _ref_selection(case, bl, K):
    """Dense oracle: the index scores of every live query and, by a stable
    sort, its K best keys (ties to the lower position)."""
    kl = _logical(case["ki"], case["table"], bl, case["nb"])
    s = np.einsum("bhtd,bsd->bhts", np.asarray(case["qi"], np.float64), kl)
    scores = np.einsum("bhts,bht->bts", np.maximum(s, 0),
                       np.asarray(case["w"], np.float64))
    chosen = {}
    for b, (p, a) in enumerate(zip(case["q_pos"], case["adv"])):
        for t in range(a):
            order = np.argsort(-scores[b, t, :p + t + 1], kind="stable")
            chosen[b, t] = np.sort(order[:K])
    return scores, chosen


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("Tq,bl,layout", [
    (16, 8, "fragmented"), (16, 16, "identity"), (8, 16, "fragmented")])
def test_index_scores_and_exact_topk_match_the_dense_oracle(impl, Tq, bl,
                                                            layout):
    from paddle_tpu.ops import index_select as IX, pallas_mode
    case = _sparse_case(np.random.RandomState(11), Tq, bl, layout)
    K = 24
    want, chosen = _ref_selection(case, bl, K)
    pallas_mode.KERNEL_TRACES.clear()
    scores = IX.index_scores(case["qi"], case["w"], case["ki"],
                             case["table"], case["lens"], case["q_pos"],
                             block_len=bl, pages_per_row=case["nb"],
                             impl=impl)
    mask = np.asarray(IX.topk_mask(scores, K, impl=impl))
    path = "scan" if impl == "reference" else "interpret"
    assert dict(pallas_mode.KERNEL_TRACES) == {
        (IX.SCORE_KERNEL, path): 1, (IX.TOPK_KERNEL, path): 1}
    scores = np.asarray(scores)
    assert scores.shape == (6, Tq, case["cap"])
    for (b, t), keys in chosen.items():
        p = case["q_pos"][b] + t
        assert np.abs(scores[b, t, :p + 1] - want[b, t, :p + 1]).max() < 1e-3
        assert np.isneginf(scores[b, t, p + 1:]).all()   # causal, and no key
        assert np.flatnonzero(mask[b, t]).tolist() == keys.tolist()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_topk_ties_go_to_the_lower_position_and_short_rows_keep_all(impl):
    from paddle_tpu.ops import index_select as IX
    scores = np.full((2, 8, 256), -np.inf, np.float32)
    scores[0, :, :200] = 0.0                  # two hundred keys that tie
    scores[0, :, 5], scores[0, :, 150] = 1.0, -1.0
    scores[1, :, :3] = [0.5, -2.0, 0.5]       # fewer keys than k
    mask = np.asarray(IX.topk_mask(jnp.asarray(scores), 4, impl=impl))
    assert np.flatnonzero(mask[0, 0]).tolist() == [0, 1, 2, 5]
    assert np.flatnonzero(mask[1, 7]).tolist() == [0, 1, 2]
    with pytest.raises(ValueError, match="16 bits"):
        IX.topk_mask(jnp.zeros((1, 1, 1 << 16)), 4, impl=impl)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
@pytest.mark.parametrize("Tq,bl,layout", [
    (16, 8, "fragmented"), (16, 16, "identity"), (1, 16, "fragmented")])
def test_sparse_latent_attention_matches_the_dense_oracle(impl, Tq, bl,
                                                          layout):
    """A decode row's gathered walk and a chunk row's walk under its
    columns' masks, in one call, against the dense softmax over each
    query's selected keys alone."""
    from paddle_tpu.ops import index_select as IX, pallas_mode
    from paddle_tpu.ops.attention import PagedView
    from paddle_tpu.ops.paged_attention import (SPARSE_KERNEL,
                                                sparse_latent_attention)
    case = _sparse_case(np.random.RandomState(13), Tq, bl, layout)
    if Tq == 1:
        case["adv"] = np.minimum(case["adv"], 1)
    K = 32
    _, chosen = _ref_selection(case, bl, K)
    sel = IX.select(case["qi"], case["w"], case["ki"], case["q_pos"], K,
                    paged=PagedView(case["table"], case["lens"], bl,
                                    case["nb"]))
    pallas_mode.KERNEL_TRACES.clear()
    out = np.asarray(sparse_latent_attention(
        case["q"], case["c"], case["r"], case["table"], case["lens"],
        case["q_pos"], sel=sel, block_len=bl, pages_per_row=case["nb"],
        scale=0.2, q_rope=case["qr"], impl=impl))
    path = "scan" if impl == "scan" else "interpret"
    assert dict(pallas_mode.KERNEL_TRACES) == {
        (SPARSE_KERNEL, path): 1 if Tq == 1 else 2}
    cl = _logical(case["c"], case["table"], bl, case["nb"])
    rl = _logical(case["r"], case["table"], bl, case["nb"])
    q, qr = (np.asarray(case[k], np.float64) for k in ("q", "qr"))
    assert np.isfinite(out).all()
    for (b, t), keys in chosen.items():
        s = 0.2 * (q[b, :, t] @ cl[b, keys].T + qr[b, :, t] @ rl[b, keys].T)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ cl[b, keys]
        assert np.abs(out[b, :, t] - want).max() <= 2e-5, (b, t)


def test_sparse_walk_refuses_what_it_is_not():
    from paddle_tpu.ops import paged_attention as PA
    z = jnp.zeros
    with pytest.raises(ValueError, match="a selection is over a latent"):
        PA.ragged_paged_attention(
            z((1, 2, 8, 8)), z((1, 2, 16, 8)), z((1, 2, 16, 8)),
            z((1, 2), jnp.int32), z((1,), jnp.int32), z((1,), jnp.int32),
            block_len=8, sel=z((1, 8, 16)))
    assert PA._kernel_name(None, True, True) == "paged_sparse"
    for other in ("paged_attention", "paged_window", "paged_latent"):
        assert other not in PA.SPARSE_KERNEL and PA.SPARSE_KERNEL not in other


# ---- JitLRUCache: the one shared executable-cache policy ----

def test_jit_lru_caches_hits_and_evicts_oldest():
    from paddle_tpu.utils.jit_cache import JitLRUCache
    built = []
    c = JitLRUCache(cap=2, name="t")
    for key in ("a", "b", "a", "c"):       # 'a' refreshed before 'c' lands
        c.get_or_build(key, lambda k=key: built.append(k) or k.upper())
    assert built == ["a", "b", "c"]        # hit on the second 'a'
    assert "b" not in c and "a" in c and "c" in c   # LRU evicted 'b'
    assert len(c) == 2
    assert c.stats() == {"size": 2, "cap": 2, "hits": 1, "misses": 3,
                         "evictions": 1}
    assert c.get_or_build("a", lambda: "REBUILT") == "A"


def test_jit_lru_churn_warning(caplog):
    from paddle_tpu.utils.jit_cache import JitLRUCache
    c = JitLRUCache(cap=1, name="churny", churn_window=4)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.jit_cache"):
        for i in range(4):                 # every build evicts: 100% churn
            c.get_or_build(i, lambda i=i: i)
    assert any("churny jit cache churning" in r.message
               for r in caplog.records)
    assert c.evictions == 3


def test_jit_lru_rejects_senseless_cap():
    from paddle_tpu.utils.jit_cache import JitLRUCache
    with pytest.raises(ValueError, match="cap"):
        JitLRUCache(cap=0)


def test_generate_uses_shared_lru_cache(gpt_tiny):
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.jit_cache import JitLRUCache
    generate(gpt_tiny, np.array([[1, 2, 3]], dtype=np.int32),
             max_new_tokens=2)
    cache = gpt_tiny.__dict__["_generate_jit_cache"]
    assert isinstance(cache, JitLRUCache)
    assert cache.stats()["size"] >= 1
    generate(gpt_tiny, np.array([[1, 2, 3]], dtype=np.int32),
             max_new_tokens=2)             # same shapes: pure cache hit
    assert cache.hits >= 1


# ---- pool device mirrors (block table / seq_lens / fragmentation) ----

def _pool(num_slots=2, block_len=4, n_blocks=3, pad_tokens=0):
    from paddle_tpu.serving.llm import SlotPagedKVPool

    def init_cache(batch, max_len, **kw):
        return [(jnp.zeros((batch, 1, max_len, 4)),
                 jnp.zeros((batch, 1, max_len, 4)))]

    return SlotPagedKVPool(init_cache, num_slots=num_slots,
                           block_len=block_len, n_blocks=n_blocks,
                           pad_tokens=pad_tokens)


def test_device_block_table_identity_and_version_gating():
    p = _pool(num_slots=2, n_blocks=3)
    t1 = p.device_block_table()
    assert np.array_equal(np.asarray(t1), [[0, 1, 2], [3, 4, 5]])
    assert p.device_block_table() is t1    # no change -> no re-upload
    p.set_block_row(0, [4, 2])             # incremental row update
    t2 = p.device_block_table()
    assert t2 is not t1
    assert np.array_equal(np.asarray(t2)[0], [4, 2, 0])
    p.set_block_row(0, [4, 2])             # identical row: version steady
    assert p.device_block_table() is t2
    with pytest.raises(ValueError, match="at most"):
        p.set_block_row(1, [0, 1, 2, 3])


def test_device_seq_lens_upload_only_on_change():
    p = _pool()
    s = p.allocate(8)
    l1 = p.device_seq_lens()
    assert p.device_seq_lens() is l1
    p.set_length(s, 5)
    l2 = p.device_seq_lens()
    assert l2 is not l1 and int(np.asarray(l2)[s]) == 5
    p.set_length(s, 5)                     # no-op write: no re-upload
    assert p.device_seq_lens() is l2
    p.free(s)                              # length 5 -> 0 is a change
    assert p.device_seq_lens() is not l2


def test_pad_tokens_extend_slab_not_address_space():
    p = _pool(num_slots=2, block_len=4, n_blocks=3, pad_tokens=4)
    k, _ = p.slabs[0]
    assert k.shape[2] == p.capacity + 4 == p.slab_len
    # the device table can never name a page inside the pad region
    assert int(np.asarray(p.device_block_table()).max()) \
        * p.block_len + p.block_len <= p.num_slots * p.capacity


def test_fragmentation_ratio_gauge():
    p = _pool(block_len=4)
    assert p.fragmentation_ratio() == 0.0  # idle pool
    s = p.allocate(8)
    p.set_length(s, 5)                     # 2 blocks back 5 tokens
    assert p.fragmentation_ratio() == pytest.approx(1 - 5 / 8)
    p.set_length(s, 8)
    assert p.fragmentation_ratio() == 0.0


# ---- engine acceptance: bit-identity, one dispatch per pump, TTFT ----

def _cfg(**kw):
    from paddle_tpu import serving
    base = dict(num_slots=4, block_len=8, n_blocks=4, prefill_chunk=8)
    base.update(kw)
    return serving.LLMEngineConfig(**base)


def test_engine_chunked_streams_bit_identical_to_generate(gpt_tiny):
    """Mixed lengths — including a prompt longer than prefill_chunk, so
    chunked prefill actually splits it — stream exactly what one-shot
    greedy generate() produces, with every pump issuing exactly ONE
    unified dispatch (no per-row or per-bucket dispatch fanout)."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    prompts = [np.arange(1, 5, dtype=np.int32),
               np.arange(3, 15, dtype=np.int32),      # 12 > chunk of 8
               np.arange(40, 49, dtype=np.int32),     # 9 -> 2 chunks
               np.arange(7, 9, dtype=np.int32)]
    refs = [np.asarray(generate(gpt_tiny, p[None, :],
                                max_new_tokens=6).numpy())[0, len(p):]
            for p in prompts]
    eng = serving.LLMEngine(gpt_tiny, _cfg(), clock=serving.SimClock())
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    while eng.has_work():
        eng.pump()
    for h, r in zip(handles, refs):
        assert np.array_equal(h.result(timeout=0), r)
    # every pump that did work issued exactly one dispatch: the lifetime
    # dispatch count is the committed step count (no retries, no probes,
    # no per-bucket prefill executables)
    assert eng._dispatch_idx == eng.decode_iterations \
        + eng.prefill_dispatches
    assert eng.metrics.snapshot()["kv_fragmentation"] == 0.0  # idle again
    eng.pool.check_balance()
    eng.stop()


def test_engine_counts_its_rows_by_their_live_columns(gpt_tiny):
    """`paged_rows_one_column` / `paged_rows_wide`: the rows of committed
    steps with one live column (every decode row, a prompt's one-token
    tail: what the paged kernels give the one-column body) and with more (a
    chunk, a longer tail); free rows in neither. The host knows `adv` when
    it builds a step, and the `dispatch` span carries the step's
    `one_column_rows`."""
    from paddle_tpu import profiler, serving
    from paddle_tpu.profiler import SPAN_SERVE_DISPATCH
    # chunks of 8: 4 -> [4]; 12 -> [8, 4]; 9 -> [8, 1]; 17 -> [8, 8, 1]
    lengths, new = (4, 12, 9, 17), 6
    prompts = [np.arange(1, n + 1, dtype=np.int32) for n in lengths]
    eng = serving.LLMEngine(gpt_tiny, _cfg(n_blocks=6),
                            clock=serving.SimClock())
    profiler.start_profiler()           # the in-memory sink only
    try:
        handles = [eng.submit(p, max_new_tokens=new) for p in prompts]
        while eng.has_work():
            eng.pump()
        spans = [e["args"] for e in profiler.get_events()
                 if e["name"] == SPAN_SERVE_DISPATCH]
    finally:
        profiler._SINK.enabled = False
    assert all(len(h.result(timeout=0)) == new for h in handles)
    tails = [n % 8 for n in lengths]
    # the last token is sampled, not fed: new - 1 decode rows a request
    one = sum(t == 1 for t in tails) + (new - 1) * len(prompts)
    wide = sum(n // 8 for n in lengths) + sum(t > 1 for t in tails)
    snap = eng.metrics.snapshot()
    assert (snap["paged_rows_one_column"], snap["paged_rows_wide"]) \
        == (one, wide) == (22, 6)
    assert snap["rows_discarded"] == 0
    assert sum(s["one_column_rows"] for s in spans) == one
    assert all(s["one_column_rows"] <= s["prefill_rows"] + s["decode_rows"]
               for s in spans)
    text = eng.metrics.render()
    assert f"pdtpu_llm_paged_rows_one_column_total {one}" in text
    assert f"pdtpu_llm_paged_rows_wide_total {wide}" in text
    # a `(k, v)` layer unpacks its queries: slots x chunk positions a layer
    layers = len(eng.pool.layer_kinds)
    positions = layers * eng.config.num_slots * eng.config.prefill_chunk \
        * snap["unified_steps"]
    assert snap["attn_query_positions"] == positions > 0
    assert f"pdtpu_llm_attn_query_positions_total {positions}" in text
    # the step's tail runs on the emission rows: one a slot (no draft
    # window), a sixteenth of what the step computes
    head = eng.config.num_slots * snap["unified_steps"]
    assert snap["head_positions"] == head > 0
    assert snap["head_positions"] * eng.config.prefill_chunk \
        == snap["step_tokens_computed"]
    assert f"pdtpu_llm_head_positions_total {head}" in text
    eng.stop()


def test_chunked_short_prompt_ttft_beats_bucket_baseline(gpt_tiny):
    """SimClock TTFT acceptance: a short prompt arriving behind a long
    one gets its first token after ONE chunk-width dispatch (it rides the
    long prompt's next chunk), vs the retired bucket engine where it
    waited out the long prompt's whole pow2-bucket prefill dispatch plus
    its own. Cost model: a dispatch costs its query width in ms."""
    from paddle_tpu import serving
    clock = serving.SimClock()
    C = 8
    eng = serving.LLMEngine(
        gpt_tiny, _cfg(num_slots=2, n_blocks=16, prefill_chunk=C),
        clock=clock)
    long = eng.submit(np.arange(1, 61, dtype=np.int32), max_new_tokens=4)
    eng.pump()                             # long's chunk 0 (prefill-only)
    #                                        retired, chunk 1 in flight
    clock.advance(C / 1e3)
    short = eng.submit(np.arange(70, 76, dtype=np.int32),
                       max_new_tokens=4)
    idx0 = eng._dispatch_idx
    pumps = 0
    while not short.tokens_so_far():
        eng.pump()                         # mixed: long chunk + short row
        clock.advance(C / 1e3)
        pumps += 1
    # tok0 on its FIRST ride-along: the step launched in the pass that
    # admits it, behind the one in flight, retired by the pass after
    assert pumps == 2
    assert eng._dispatch_idx - idx0 == 2   # one dispatch per mixed pump
    # bucket baseline: pow2(60)=64-wide long prefill, then pow2(6)=8-wide
    # short prefill, sequential dispatches -> 72ms before short's tok0
    baseline_ms = 64 + 8
    assert short.ttft_ms is not None
    assert short.ttft_ms <= 0.5 * baseline_ms
    while eng.has_work():
        eng.pump()
    assert len(long.result(timeout=0)) == 4
    assert len(short.result(timeout=0)) == 4
    # one dispatch per pump, lifetime: prefill-only steps (long's chunks
    # with no decode rider) plus decode-carrying steps account for every
    # dispatch index — there is no separate prefill executable
    assert eng._dispatch_idx == eng.prefill_dispatches \
        + eng.decode_iterations
    eng.pool.check_balance()
    eng.stop()


# ---- chunk-granular blame (the fault-matrix scenarios) ----

@pytest.mark.fault_matrix
def test_poisoned_prefill_chunk_spares_co_scheduled_decode(gpt_tiny):
    """poison_request on a chunked-prefill row: the mixed dispatch
    (poisoned prefill chunk + innocent decode row) fails, blame probes
    implicate only the prefilling request, and the co-scheduled decode
    row is NOT evicted — its full stream stays bit-identical because
    probe results are never committed."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    good_p = np.arange(1, 4, dtype=np.int32)
    ref = np.asarray(generate(gpt_tiny, good_p[None, :],
                              max_new_tokens=6).numpy())[0, 3:]
    plan = FaultPlan.from_spec("poison_request@1")
    eng = serving.LLMEngine(
        gpt_tiny, _cfg(num_slots=2, prefill_chunk=4, dispatch_retries=0),
        clock=serving.SimClock(), fault_plan=plan)
    good = eng.submit(good_p, max_new_tokens=6)          # submit idx 0
    eng.pump()                             # good prefills solo (idx 0)
    assert good.tokens_so_far()
    bad = eng.submit(np.arange(10, 20, dtype=np.int32),  # submit idx 1,
                     max_new_tokens=4)     # 10 toks -> 3 chunks of 4
    eng.pump()      # mixed step poisoned -> probes -> quarantine bad,
    while eng.has_work():                  # good decodes on unharmed
        eng.pump()
    with pytest.raises(serving.DispatchFailedError, match="isolation") \
            as exc:
        bad.result(timeout=0)
    assert exc.value.reason == "poisoned"
    assert bad.tokens_so_far() == []       # poisoned at chunk 0
    assert np.array_equal(good.result(timeout=0), ref)
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["completed"] == 1
    assert not eng.broken                  # blame absolved the breaker
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_chunk1_failure_blames_mid_prefill_row_only(gpt_tiny):
    """A persistent failure first manifesting at prefill chunk k=1 (the
    request's chunk 0 already committed KV): the step + the mid-prefill
    row's solo probe raise, the decode row's probe is clean, so the
    half-prefilled request is quarantined — slot freed with its partial
    KV — while the co-scheduled decode row streams bit-identically."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    good_p = np.arange(1, 4, dtype=np.int32)
    ref = np.asarray(generate(gpt_tiny, good_p[None, :],
                              max_new_tokens=6).numpy())[0, 3:]
    # idx 0: good's solo prefill; idx 1, launched ahead in the same pass:
    # good's first decode. idx 2: bad chunk0 + good decode (ok).
    # idx 3: bad chunk1 + good decode, launched ahead of idx 2, RAISES
    # (retries=0): idx 2 is retired first, then probes — good solo decode
    # idx 4 (clean), bad solo prefill idx 5 (raises) -> the mid-prefill
    # row is blamed; survivors re-step at idx 6.
    plan = FaultPlan.from_spec("dispatch_raise@3;dispatch_raise@5")
    eng = serving.LLMEngine(
        gpt_tiny, _cfg(num_slots=2, prefill_chunk=4, dispatch_retries=0),
        clock=serving.SimClock(), fault_plan=plan)
    good = eng.submit(good_p, max_new_tokens=6)          # submit idx 0
    eng.pump()                                           # idx 0, 1
    bad = eng.submit(np.arange(10, 20, dtype=np.int32),  # submit idx 1
                     max_new_tokens=4)
    eng.pump()                                           # idx 2: chunk 0
    assert eng._inflight.adv[bad_slot(eng, bad)] == 4    # ... in flight
    assert eng._active[bad_slot(eng, bad)].chunk_off == 0
    eng.pump()          # idx 3 fails -> idx 2 commits -> blame -> idx 6
    with pytest.raises(serving.DispatchFailedError, match="isolation") \
            as exc:
        bad.result(timeout=0)
    assert exc.value.reason == "poisoned"
    assert bad.tokens_so_far() == []       # died mid-prefill: no tokens
    while eng.has_work():
        eng.pump()
    assert np.array_equal(good.result(timeout=0), ref)
    assert sorted(plan.log) == ["dispatch_raise@3", "dispatch_raise@5"]
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["completed"] == 1
    assert not eng.broken
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


def bad_slot(eng, handle):
    for slot, req in eng._active.items():
        if req.handle is handle:
            return slot
    raise AssertionError("request not active")


# ---- the step's page operand as a named view (PR 42) ----

def test_paged_view_is_a_window_layers_view_of_itself():
    """What `LlamaAttention._forward_cached` worked out by position: the
    ring's columns, the positions the rotary table must reach, and the
    operand without a table over the ring's pages."""
    from paddle_tpu.ops.attention import PagedView
    table, lens = jnp.zeros((3, 20), jnp.int32), jnp.arange(3)
    view = PagedView(table, lens, 8, 20, 6)
    assert (view.ring, view.positions) == (6 * 8, 20 * 8)
    own = view.window_view()
    assert own.table is None and own.seq_lens is lens
    assert tuple(own) == (None, lens, 8, 6, None)
    # a pool without a ring builds the view without one, and no layer asks
    plain = PagedView(table, lens, 8, 20)
    assert plain.ring_pages is None and plain.positions == 160


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_view_live_mask_is_the_three_models_old_expressions(seed):
    """`live` against the masks `LlamaModel`, `DeepseekModel` and
    `GraniteMoeHybridModel` each computed from `paged[1]`, and `advance`
    against the last one's `adv`, on seeded steps with free slots, decode
    rows and chunks."""
    from paddle_tpu.ops.attention import PagedView
    rng = np.random.default_rng(seed)
    rows, width = 12, 16
    adv = rng.choice([0, 0, 1, 1, 7, 16], rows).astype(np.int32)
    pos = rng.integers(0, 200, rows).astype(np.int32)
    lens = jnp.asarray(pos + adv)
    view = PagedView(None, lens, 8, 32)
    pos_j = jnp.asarray(pos)
    live = np.asarray(view.live(pos_j, width))
    assert live.shape == (rows, width) and live.dtype == bool
    # llama.py / deepseek.py: pos[b] + t short of the row's length
    t = jnp.arange(width, dtype=jnp.int32)
    old = jnp.reshape(pos_j, (-1, 1)) + t < jnp.reshape(lens, (-1, 1))
    assert np.array_equal(live, np.asarray(old))
    # granitemoehybrid.py: t short of the row's live columns
    old_adv = jnp.reshape(lens, (-1,)) - pos_j
    assert np.array_equal(np.asarray(view.advance(pos_j)), adv)
    assert np.array_equal(np.asarray(old_adv), adv)
    assert np.array_equal(
        live, np.asarray(jnp.arange(width, dtype=jnp.int32)
                         < old_adv[:, None]))
    assert live.sum(axis=1).tolist() == adv.tolist()
    # a `[rows, 1]` position, as a model hands it, is the same mask
    assert np.array_equal(np.asarray(view.live(pos_j[:, None], width)), live)
    # and the same equations: the view adds nothing to the trace
    def models(p, s):
        t = jnp.arange(width, dtype=jnp.int32)
        return jnp.reshape(p, (-1, 1)) + t < jnp.reshape(s, (-1, 1))
    assert str(jax.make_jaxpr(models)(pos_j, lens)) == str(jax.make_jaxpr(
        lambda p, s: PagedView(None, s, 8, 32).live(p, width))(pos_j, lens))


def test_decode_attention_and_select_take_the_view_and_nothing_else():
    """No plain tuple is accepted beside the view."""
    from paddle_tpu.ops import index_select as IX
    from paddle_tpu.ops.attention import PagedView, decode_attention
    rng = np.random.RandomState(3)
    B, H, T, D, bl, nb = 2, 2, 4, 8, 8, 3
    q = _rand(rng, (B, H, T, D))
    k, v = _rand(rng, (B, H, bl * nb, D)), _rand(rng, (B, H, bl * nb, D))
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    pos = jnp.asarray([3, 9], jnp.int32)
    lens = pos + T
    out = decode_attention(q, k, v, pos, paged=PagedView(table, lens, bl, nb))
    ringed = decode_attention(q, k, v, pos,
                              paged=PagedView(table, lens, bl, nb, 2))
    assert np.array_equal(np.asarray(out), np.asarray(ringed))
    with pytest.raises(AttributeError):
        decode_attention(q, k, v, pos, paged=(table, lens, bl, nb))
    with pytest.raises(AttributeError):
        IX.select(q, jnp.ones((B, H, T)), k[:, :1], pos, 4,
                  paged=(table, lens, bl, nb))
