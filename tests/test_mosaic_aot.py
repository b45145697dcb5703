"""Tier-1 guard for the Mosaic lowering: tools/mosaic_aot_check.py compiles
the flash, paged (the full walk, the windowed walk through a ring at the
window/full cell's shapes and the latent walk at the latent cell's),
K/V-write (`kv_write`, at every serve cell's slabs), grouped-matmul and
state-recurrence Pallas
kernels for a TPU v5e through the installed libtpu, with no chip attached (ISSUE 21: CPU interpret-mode tests say
nothing of whether Mosaic accepts a kernel). Runs in a subprocess so the
libtpu lock and the TPU_* environment stay out of the test process."""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    """One run of the tool for every test here (one process at a time may
    hold libtpu, and a run takes half a minute): its stdout."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mosaic_aot_check.py")],
        capture_output=True, text=True, timeout=400)
    if r.returncode == 3:
        pytest.skip(f"libtpu gave no v5e topology: {r.stderr[-300:]}")
    cases = [ln for ln in r.stdout.splitlines()
             if ln.startswith(("[OK]", "[FAIL]"))]
    assert r.returncode == 0, "\n".join(cases) + r.stderr[-1500:]
    return r.stdout


def test_pallas_kernels_compile_for_v5e_without_a_chip(tool):
    cases = [ln for ln in tool.splitlines()
             if ln.startswith(("[OK]", "[FAIL]"))]
    assert len(cases) == 88 and all(c.startswith("[OK]") for c in cases)
    paged = [c for c in cases if c.startswith("[OK] paged bf16")]
    assert len(paged) == 19         # tools/mosaic_aot_check.py's two lists
    window = [c for c in cases if "'paged_window': 1" in c]
    assert [c.split("slab=")[1].split(":")[0] for c in window] == [
        "[32, 4, 1056, 128]"] * 2 + ["[128, 8, 544, 128]"] * 2
    # a grid step's loop takes 128 keys: 8 pages of 16 at every cell's
    # shape, 16 pages of 8 on the one-shot path
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling paged_")]
    assert tilings and all("'pages': 8" in t or "'pages': 16" in t
                           for t in tilings)
    for grid, groups in (("(128, 1)", 2), ("(32, 1)", 9), ("(32, 1)", 65),
                         ("(32, 1)", 10), ("(256, 1)", 20), ("(128, 1)", 20),
                         ("(128, 1)", 6)):
        assert any(f"'grid': {grid}, 'groups': {groups}," in t
                   and "'pages': 8" in t for t in tilings), (grid, groups)
    # multi-query 20:1: the whole group folds into one tile's rows, 320 at
    # the step's 16-wide rows and 20 at a one-token row
    for rows, one in ((320, 20), (20, 0)):
        assert any(f"'heads': 1, 'one_column_rows': {one}, 'pages': 8, "
                   f"'rows': {rows}" in t for t in tilings), rows
    # the grouped matmul at 256 groups of [2048, 512] (PR 51): 16 rows a
    # group at 512 positions x 8, less than one row tile of 128
    gmm = [c for c in cases if c.startswith("[OK] moe_gmm bf16")]
    assert len(gmm) == 10
    for shape in ("[4096,2048] x [256,2048,512]",
                  "[4096,512] x [256,512,2048]"):
        assert any(shape in c for c in gmm), shape


def test_the_paged_walks_hold_the_one_column_body_where_the_tile_shrinks(
        tool):
    """PR 48: a trace whose tile takes fewer packed sublane tiles with one
    column than with sixteen holds the group's arithmetic twice, and both
    bodies compile for the v5e in one kernel at the cells' shapes: Xing's
    32 of 512 rows, A.X-K1's 32 of 512 in two tiles, Jamba's 20 of 320,
    Mistral's 4 of 64 a KV head (decode and prefill cells), Mellum's 8 of
    128 in the ring and the full walk, Laguna's 6 of 96 in the full walk
    and 8 of 128 in its ring (two group sizes in one model's step), and the
    whole-prompt tiles. A
    one-token call, OLMoE's MHA tile (sixteen bf16 rows are one packed tile
    either way) and both of the sparse layer's calls hold one body: their
    kernels are the parent's to the equation (493 at 8 pages a group; the
    gathered and the masked latent walk 497 and 505)."""
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling paged_")]
    rows_of = {("paged_latent", "(256, 1)", 512): 32,
               ("paged_latent", "(32, 2)", 512): 32,
               ("paged_attention", "(256, 1)", 320): 20,
               ("paged_attention", "(128, 1)", 64): 4,
               ("paged_attention", "(32, 1)", 64): 4,
               ("paged_window", "(32, 1)", 128): 8,
               ("paged_attention", "(32, 1)", 128): 8,
               ("paged_attention", "(2, 8)", 2048): 4,
               # head counts by layer type (PR 51): 6 of 96 rows a KV head
               # in the full walk (6 of a packed tile's 16 rows), 8 of 128
               # in a ring of 33 pages, every KV head in one tile
               ("paged_attention", "(128, 1)", 96): 6,
               ("paged_window", "(128, 1)", 128): 8,
               ("paged_attention", "(128, 1)", 6): 0,
               ("paged_window", "(128, 1)", 8): 0,
               # one body: OLMoE's step and the one-token calls
               ("paged_attention", "(128, 1)", 16): 0,
               ("paged_attention", "(8, 1)", 20): 0,
               ("paged_attention", "(8, 1)", 4): 0,
               ("paged_window", "(32, 1)", 8): 0,
               ("paged_latent", "(32, 1)", 64): 0}
    for (kernel, grid, rows), one in rows_of.items():
        assert any(t.startswith(f"tiling {kernel} ") and f"'grid': {grid}, "
                   in t and f"'one_column_rows': {one}, " in t
                   and f"'rows': {rows}}}" in t for t in tilings), \
            (kernel, grid, rows, tilings)
    assert all("'one_column_rows': 0, " in t for t in tilings
               if t.startswith("tiling paged_sparse"))
    body = {m[1]: int(m[2]) for m in re.finditer(
        r"^body paged_attention (mistral decode|olmoe decode) cell: (\d+) "
        r"equations$", tool, re.M)}
    assert body["olmoe decode"] == 493 < body["mistral decode"] < 650
    assert "body paged_sparse chunk rows: [497, 505] equations" in tool


def test_the_latent_walk_compiles_in_its_packed_layout(tool):
    """PR 50: a dense latent layer's queries and result are token-major
    `[positions, H, .]` and move by the kernel's own copies. Pinned for
    the v5e at the two cells' shapes: Xing's 512 packed positions and 15
    of pad, 32 heads in one tile, 256 rows whose starts are a
    `TokenPack`'s; A.X-K1's 32 x 16 positions as they lie, 64 heads in two
    tiles of 32 (a head slice of whole sublane tiles); and the one-token
    rows of `generate()`. A call without `q_rope` (Mistral's, OLMoE's,
    Jamba's, Mellum's shapes) and the sparse layers' two record what they
    recorded before: no `packed_queries`."""
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] paged latent bf16")]
    assert len(cases) == 3 and all("{'paged_latent': 1}" in c for c in cases)
    assert any("q=[527, 32, 512 | 128] rows=256 x 16" in c for c in cases)
    assert any("q=[512, 64, 512 | 128] rows=32 x 16" in c for c in cases)
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling paged_")]
    latent = [t for t in tilings if t.startswith("tiling paged_latent ")]
    for grid, one, positions, rows in (("(256, 1)", 32, 527, 512),
                                       ("(32, 2)", 32, 512, 512),
                                       ("(32, 1)", 0, 32, 64)):
        assert any(f"'grid': {grid}, " in t and f"'heads': 1, "
                   f"'one_column_rows': {one}, 'packed_queries': "
                   f"{positions}, 'pages': 8, 'rows': {rows}}}" in t
                   for t in latent), (grid, latent)
    assert all("packed_queries" in t for t in latent)
    others = [t for t in tilings if t not in latent]
    assert len(others) >= 15 and not any("packed_queries" in t
                                         for t in others)


def test_the_mamba2_recurrence_compiles_with_both_bodies(tool):
    """`ssm_update` for the v5e at granite-4.0-h-small's widths (a bf16
    state of 128 x 8,192 a row, 2,048 lanes a grid step): the prefill and
    the decode cell's steps (32 and 128 rows of 16 columns) hold the column
    loop and the matrix body, each behind its `pl.when`, in one kernel; a
    call of 64 columns the matrix body over eight tiles of columns; a
    one-column call the loop alone."""
    from paddle_tpu.ops.ssm import MATRIX_COLUMNS
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] ssm_update bf16")]
    assert len(cases) == 5 and all("{'ssm_update': 1}" in c for c in cases)
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling ssm_update")]
    for columns, grid, matrix_from in (
            (16, "(32, 4)", MATRIX_COLUMNS), (16, "(128, 4)", MATRIX_COLUMNS),
            (64, "(2, 4)", MATRIX_COLUMNS), (1, "(2, 4)", 0)):
        assert any(f"'columns': {columns}, 'grid': {grid}, 'matrix_from': "
                   f"{matrix_from}, 'state_tile': (128, 2048)" in t
                   for t in tilings), (columns, grid, tilings)


def test_the_mamba1_recurrence_compiles_at_the_reasoning_cells_shapes(tool):
    """`selective_scan` for the v5e at Jamba2-3B's widths: a float32 state
    of 16 x 5,120 a row, the columns the packed step's 512 token rows, 32
    rows and 1,280 lanes a grid step at the cell's 256 slots; a prompt of
    3,000 columns in chunks whose tokens narrow the lane block; and in an
    engine's step the Mamba-1 layers share one body."""
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] selective_scan bf16")]
    assert len(cases) == 3 and all("{'selective_scan': 1}" in c
                                   for c in cases)
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling selective_scan")]
    assert any("'columns': 16, 'grid': (4, 8), 'state_tile': (32, 16, 1280)"
               in t for t in tilings), tilings
    assert any("'columns': 2048, 'grid': (40, 1), 'state_tile': (2, 16, 128)"
               in t for t in tilings), tilings
    conv = [ln for ln in tool.splitlines()
            if ln.startswith("[OK] conv_tokens bf16")]
    assert len(conv) == 3 and all("{'conv_tokens': 1}" in c for c in conv)
    assert any(ln.startswith("tiling conv_tokens") and "'grid': (4, 8), "
               "'tile': (32, 3, 1280)" in ln for ln in tool.splitlines())
    step, = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] serve step, 3 Mamba-1 layers")]
    assert ": 3 Mosaic bodies " in step
    assert "'selective_scan': 3" in step and "'paged_attention': 1" in step


def test_the_delta_rule_recurrence_compiles_at_the_linear_cells_shapes(tool):
    """`kda_update` for the v5e at Solar-Open2's widths: a float32 state of
    128 x 8,192 a row, the columns the packed step's 512 token rows, 4 rows
    and 8 heads a grid step at the cell's 256 slots (the q, k and decay
    tiles of a token through one 128 x 128 transpose); one-shot
    `generate()`'s decode step; a prompt of 3,000 columns in chunks; the
    conv in front of it over 24,576 channels; and in an engine's step the
    three KDA layers share one body and the state is aliased."""
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] kda_update rows=")]
    assert len(cases) == 3 and all("{'kda_update': 1}" in c for c in cases)
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling kda_update")]
    assert any("'columns': 16, 'grid': (8, 64), 'state_tile': (4, 128, 1024)"
               in t for t in tilings), tilings
    assert any("'columns': 256, 'grid': (8, 1), 'state_tile': (2, 128, 1024)"
               in t for t in tilings), tilings
    assert any(ln.startswith("[OK] conv_tokens bf16 rows=256 tokens=512 "
                             "carried=[3,24576]") for ln in tool.splitlines())
    step, = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] serve step, 3 KDA layers")]
    assert ": 15 Mosaic bodies " in step       # 12 of them the experts'
    assert "'kda_update': 3" in step and "'paged_attention': 1" in step


def test_a_recomputed_layer_holds_one_flash_forward_for_the_v5e(tool):
    """Per-layer recompute keeps the forward kernel's output and
    log-sum-exp (PR 41): compiled for the chip at the train cells' shapes,
    the gradient of two layers under `recompute()` holds two `flash_fwd`
    custom calls, not four (the replay of a layer ran the kernel again),
    and says what it holds."""
    case, = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] recompute of 2 layers")]
    assert "[8,16,2048,128]: {'flash_fwd': 2, 'flash_bwd_dq': 2, " \
        "'flash_bwd_dkv': 2}; " in case
    assert re.search(r"\d+ bytes of arguments, \d+ of results, \d+ of "
                     r"temporaries in \d+\.\ds$", case), case
    # `lse` and delta reach the backward kernels as the lane-dense rows the
    # forward kernel wrote (PR 45): no `[128, 2048, 1]` float32 column, 134
    # MB on a v5e for 1 MB of values, and none of the copies that made them
    assert r"; no f32\[\d+,2048,1\]; " in case, case


def test_the_flash_kernels_compile_at_the_tiles_their_shapes_choose(tool):
    """PR 45: a grid step owns a row of sub-tiles. At the train cells'
    shape every kernel's step holds the other operand whole (`chunk` =
    2,048: K and V, or q and dO, fetched once a batch-head), computes the
    ten 512 x 512 sub-tiles the causal mask leaves of sixteen (none dead
    is visited: the loops' trip counts are the bounds) and masks the four
    on the diagonal alone; the narrow-head rehearsal shape, a length 512
    does not divide, a rectangle and a chunked 32k compile too."""
    flash = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] flash fwd+bwd bf16")]
    assert len(flash) == 7 and all(
        "{'flash_fwd': 1, 'flash_bwd_dq': 1, 'flash_bwd_dkv': 1}" in c
        for c in flash)
    for shape in ("[8, 16, 2048, 128]:", "[1, 2, 512, 64]:",
                  "[2, 4, 768, 128]:", "[1, 2, 32768, 128] in chunks:"):
        assert any(shape in c for c in flash), shape
    tilings = [ln for ln in tool.splitlines() if ln.startswith("tiling flash")]
    for kernel, chunk in (("flash_fwd", "chunk_k"), ("flash_bwd_dq", "chunk_k"),
                          ("flash_bwd_dkv", "chunk_q")):
        assert any(
            ln.startswith(f"tiling {kernel} ")
            and f"'block_k': 512, 'block_q': 512, '{chunk}': 2048, 'grid': "
                "(128, 4, 1), 'live_tiles': 10, 'masked_tiles': 4" in ln
            for ln in tilings), (kernel, tilings)
        assert any(ln.startswith(f"tiling {kernel} ")
                   and f"'block_k': 384, 'block_q': 384, '{chunk}': 768" in ln
                   for ln in tilings), kernel
        assert any(ln.startswith(f"tiling {kernel} ")
                   and f"'{chunk}': 8192, 'grid': (2, 64, 4)" in ln
                   for ln in tilings), kernel


def test_lowered_step_holds_one_kernel_body_a_shape_not_one_a_layer(tool):
    """Tracing and lowering run in every process before the compile cache
    can be asked, so a kernel body a layer is paid on every start (PR 32:
    +15 s of set-up over 8 layers). The kernel's entry is one jitted
    function: the lowered unified step of a 3-layer engine holds one
    Mosaic body, that of an engine with window and full layers two, and
    the compiled step still a custom call a layer."""
    steps = [ln for ln in tool.splitlines() if ln.startswith("[OK] serve")]
    assert len(steps) == 8
    full, mixed, hybrid, _, _, latent, indexed, streams = steps
    # four residual streams (PR 47): two layers' four connections share one
    # `hc_pre` and one `hc_post` body; the walk, the write and the sparse
    # layer's three grouped matmuls as in the latent engine
    assert "in 4 hyper-connected streams: 7 Mosaic bodies " in streams
    assert "{'hc_pre': 4, 'kv_write': 2, 'paged_latent': 2, 'hc_post': 4, " \
        "'moe_gmm': 3} in the compiled one" in streams
    # an indexer's layers (PR 39): the three-slab write and the two-slab
    # one, the gathered and the masked walk, one scoring and one top-k for
    # both "full" layers, three sparse layers' grouped matmuls
    assert "2 indexed + 2 shared latent layers: 15 Mosaic bodies " in indexed
    assert "{'kv_write': 4, 'index_score': 2, 'paged_sparse': 8, " \
        "'index_topk': 2, 'moe_gmm': 9} in the compiled one" in indexed
    # three MLA layers share one `paged_latent` body and one `kv_write`
    # body; the grouped matmuls of the two sparse layers behind the dense
    # one are a body a call site
    assert "3 latent layers: 8 Mosaic bodies " in latent
    assert "{'kv_write': 3, 'paged_latent': 3, 'moe_gmm': 6} in the " \
        "compiled one" in latent
    assert "'ssm_update': 2" in hybrid and "'paged_attention': 1" in hybrid
    # two state-space layers share one `ssm_update` body since PR 46
    assert "'kv_write': 1" in hybrid and ": 12 Mosaic bodies " in hybrid
    # a walk and a write: one body each for three layers
    assert "3 full layers: 2 Mosaic bodies " in full
    assert "{'kv_write': 3, 'paged_attention': 3} in the compiled one" in full
    # a (shapes, ring) pair each: the ring's write is a body of its own
    assert "3 window layers + 1 full: 4 Mosaic bodies " in mixed
    assert "'paged_window': 3" in mixed and "'paged_attention': 1" in mixed
    assert "'kv_write': 4" in mixed
    for ln in steps:        # the three phases are timed apart
        assert re.search(r"trace \d+\.\ds \+ lower \d+\.\ds \+ compile "
                         r"\d+\.\ds$", ln), ln


def test_the_compiled_step_aliases_the_whole_pool_for_the_v5e(tool):
    """The step is donated its pool (PR 35): compiled for the chip, every
    byte of a full-length, a ring and a recurrent engine's slabs is
    aliased to the result, or the step would copy the pool again; latent
    pages (PR 36) likewise."""
    steps = [ln for ln in tool.splitlines() if ln.startswith("[OK] serve")]
    for ln in steps:
        m = re.search(r"; (\d+) bytes aliased of a pool of (\d+); ", ln)
        assert m and int(m[1]) >= int(m[2]) > 0, ln
        # nor does XLA loop over a K/V slab a row at a time (the vmapped
        # write's scatter, PR 37) or copy one (PR 35)
        assert "; 0 loops over a slab, 0 copies of one; " in ln, ln


def test_kernel_body_does_not_grow_with_the_pages_of_a_group(tool):
    """The copies of a group's pages are issued by a loop and awaited by
    one wait a buffer: the body at `block_len` 8 (16 pages a group) is
    within 10% of the body at 16 (8 pages), and a few hundred equations
    (PR 32's unrolled copies: 1,334 and 2,454)."""
    body = {m[1]: int(m[2]) for m in re.finditer(
        r"^body paged_attention block_len=(\d+) Tq=16: (\d+) equations$",
        tool, re.M)}
    assert set(body) == {"8", "16"}
    assert abs(body["8"] - body["16"]) <= 0.1 * body["16"]
    assert body["8"] < 700


def test_the_latent_walk_compiles_at_the_latent_cells_shapes(tool):
    """`paged_latent` for the v5e: 64 query heads over a 512-wide latent
    and a rotary key stored in whole lane tiles (Mosaic refuses a page copy
    of 64 lanes). The step's 16-wide rows split the heads over two tiles
    of 512 rows inside the VMEM budget; a one-token row takes one tile."""
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] paged latent bf16")]
    assert len(cases) == 3 and all("{'paged_latent': 1}" in c for c in cases)
    assert sum("slab=[32, 1, 8304, 512 | 128]" in c for c in cases) == 2
    # the hyper-connected cell's decode-heavy walk (PR 47): 256 slots of 160
    # pages, 32 heads x 16 columns in one tile of 512 rows
    assert sum("slab=[256, 1, 2576, 512 | 128]" in c for c in cases) == 1
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling paged_latent")]
    for grid, groups, rows in (("(32, 2)", 65, 512), ("(32, 1)", 65, 64),
                               ("(256, 1)", 20, 512)):
        assert any(f"'grid': {grid}, 'groups': {groups}, 'heads': 1, "
                   in t and f"'pages': 8, 'rows': {rows}" in t
                   for t in tilings), (grid, tilings)


def test_the_hyper_connection_kernels_compile_at_the_cells_shapes(tool):
    """`hc_pre` and `hc_post` for the v5e (PR 47) at a packed step's 512
    positions of 4 streams x 3,584 in bf16 with the parameters as the
    benchmark holds them, and at an unpacked step's 48 rows: a grid step
    holds 128 rows of the streams whole (the coefficients' tokens lie along
    the lanes), so 512 rows are four grid steps and 48 one."""
    for kernel in ("hc_pre", "hc_post"):
        cases = [ln for ln in tool.splitlines()
                 if ln.startswith(f"[OK] {kernel} bf16 rows=")]
        assert len(cases) == 2 and all(f"{{'{kernel}': 1}}" in c
                                       for c in cases)
        tilings = [ln for ln in tool.splitlines()
                   if ln.startswith(f"tiling {kernel} ")]
        for grid in ("(4,)", "(1,)"):
            assert any(f"'grid': {grid}" in t and "'tile': (128, 14336)" in t
                       for t in tilings), (kernel, grid, tilings)
    assert any("'coefficients': 24" in t and "'passes': 20" in t
               for t in tool.splitlines() if t.startswith("tiling hc_pre"))


def test_sparse_attention_compiles_at_the_sessions_cells_shapes(tool):
    """`index_score`, `index_topk` and `paged_sparse` for the v5e (PR 39)
    at 16 slots of 2,304 pages: an indexer of 32 x 128 over index-key
    pages, the exact top-2,048 of sixteen score vectors of 36,864 as a mask
    (a row's block in VMEM: the call raises Mosaic's scoped limit), a
    chunk row's walk under its columns' masks beside a decode row's walk
    over its gathered keys (two calls), and a one-token step's gathered
    walk alone."""
    lines = tool.splitlines()
    assert any(ln.startswith("[OK] index_score bf16 q=[16, 32, 16, 128] "
                             "slab=[16, 1, 36880, 128]: {'index_score': 1}")
               for ln in lines)
    assert any(ln.startswith("[OK] index_topk k=2048 scores=[16, 16, 36864]"
                             ": {'index_topk': 1}") for ln in lines)
    sparse = [ln for ln in lines if ln.startswith("[OK] paged sparse bf16")]
    assert len(sparse) == 2
    assert "chunk rows" in sparse[0] and "{'paged_sparse': 2}" in sparse[0]
    assert "Tq=1" in sparse[1] and "{'paged_sparse': 1}" in sparse[1]
    tilings = [ln for ln in lines if ln.startswith("tiling paged_sparse")]
    # the masked walk over the slot's 288 groups, the heads in two tiles;
    # the gathered walk over 16 groups, one tile
    assert any("'grid': (16, 2), 'groups': 288, 'heads': 1, "
               "'one_column_rows': 0, 'pages': 8, 'rows': 512" in t
               for t in tilings), tilings
    assert any("'grid': (16, 1), 'groups': 16, 'heads': 1, "
               "'one_column_rows': 0, 'pages': 8, 'rows': 64" in t
               for t in tilings), tilings


def test_the_kv_write_compiles_at_every_serve_cells_slabs(tool):
    """`kv_write` for the v5e (PR 37): K's and V's stripes of every row in
    one Mosaic call a layer, both slabs aliased to the byte, no loop or
    copy of a slab beside it; at Mistral's 128 and 32 rows, OLMoE's 16
    heads (4 rows a grid step inside the VMEM budget, 8 elsewhere),
    Mellum's full-length and ring slabs, A.X-K1's unequal latent pair,
    granite's one attention layer and a batch of one. A direct copy of a
    stripe into the slab is refused by Mosaic (a bf16 slab's tiled axis is
    addressed 16 columns at a time, a row's position is any integer), so a
    row is a read-modify-write of the 32 aligned columns that hold it."""
    cases = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] kv_write bf16")]
    assert len(cases) == 14
    # a sparse layer's three slabs (latent, rotary key, index key) in the
    # one call, at the sessions cell's 16 rows of 36,880 columns
    assert any("slab=[16, 1, 36880] x 512 | 128 | 128" in ln for ln in cases)
    for ln in cases:
        m = re.search(r": \{'kv_write': 1\}, (\d+) bytes aliased of (\d+), "
                      r"0 loops over a slab, 0 copies of one", ln)
        assert m and m[1] == m[2], ln
    for slab in ("[128, 8, 240] x 128 | 128", "[32, 8, 1056] x 128 | 128",
                 "[128, 16, 240] x 128 | 128", "[32, 4, 8304] x 128 | 128",
                 "[32, 4, 1056] x 128 | 128 T=16 ring=1040",
                 "[32, 1, 8304] x 512 | 128", "[256, 1, 2576] x 128 | 128",
                 "[256, 1, 2576] x 512 | 128",
                 "[128, 8, 2576] x 128 | 128",
                 "[128, 8, 544] x 128 | 128 T=16 ring=528",
                 "[1, 8, 2064] x 128 | 128"):
        assert any(f"slab={slab}" in ln for ln in cases), slab
    tilings = [ln for ln in tool.splitlines()
               if ln.startswith("tiling kv_write")]
    for want in ("'grid': (16,), 'heads': 8, 'ring': 0, 'rows': 8",
                 "'grid': (32,), 'heads': 16, 'ring': 0, 'rows': 4",
                 "'grid': (4,), 'heads': 4, 'ring': 1040, 'rows': 8",
                 "'grid': (4,), 'heads': 1, 'ring': 0, 'rows': 8",
                 "'grid': (1,), 'heads': 8, 'ring': 0, 'rows': 1"):
        assert any("'columns': 32, " + want in t for t in tilings), want
    # what every process traces and lowers for it, the ring's second
    # merge included: under the paged kernel's body
    body = re.search(r"^body kv_write ring=1040: (\d+) equations$", tool,
                     re.M)
    assert body and int(body[1]) < 400


def test_the_steps_tail_compiles_on_its_emission_rows_without_the_bank(tool):
    """PR 52: the unified step's tail at the two reasoning cells' widths.
    The head's product is `[rows, hidden] x [hidden, vocab]` over the 256 /
    128 emission rows, not the block's 512 positions; no instruction of the
    entry computation makes an array of the grammar bank's shape or of a
    piece of it (XLA's gather copies the bank: inside the conditional's
    branch alone); beyond the bank the temporaries are under two float32
    `[rows, vocab]` arrays (the 512-row tail held 1.21 GB at Xing's
    widths)."""
    tails = [ln for ln in tool.splitlines()
             if ln.startswith("[OK] step tail bf16")]
    assert len(tails) == 2
    for line, rows, vocab in zip(tails, (256, 128), (131072, 100352)):
        assert f"head product [[{rows}, {vocab}]] over [{rows}, " in line
        found = re.search(
            r"(\d+) instructions of the entry computation make an array of "
            rf"the bank's shape \[9, 128, {vocab}\] or a piece of it, (\d+) "
            r"inside the conditional; (\d+) bytes of temporaries", line)
        entry, inside, temps = map(int, found.groups())
        assert entry == 0 and inside > 0
        assert temps < (9 * 128 + 2 * rows) * vocab * 4
