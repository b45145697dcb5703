"""The write of a step's new keys and values into the pool's slabs, as one
Mosaic kernel a layer (`kv_write`, PR 37).

`ops.attention.update_kv_cache` with a `[B]` position vector lands row b's
stripe `new[b] [Hkv, T, D]` at column `pos[b]` of slab row b. Written as a
vmapped `dynamic_update_slice` XLA expands it, for a TPU, into a `while` of
one trip a row and cache, six small instructions a trip (~4.7 us: 9.6 ms of
a 25 ms step at 128 rows and 8 layers); a `lax.scatter` compiles to the
same loop. This kernel does the same write, K and V in one call:

    k_cache/v_cache  [B, Hkv, L, Dk] / [B, Hkv, L, Dv]   in HBM, aliased
    k_new/v_new      [B, Hkv, T, Dk] / [B, Hkv, T, Dv]   the step's stripes
    pos              [B] int32

A stripe may start at any column, and Mosaic refuses a copy into HBM whose
start on the tiled (second-minor) axis it cannot prove aligned. So a row is
a read-modify-write of the *aligned window* that holds its stripe: the `W =
ceil(T / A) * A + A` columns from `a = min(col // A * A, L - W)`, A the
slab type's packed sublane tile (16 in bf16, 8 in float32), are copied into
VMEM, the stripe is rolled to its offset and merged in by a column mask,
and the window is copied back: the slab afterwards is the vmapped form's,
bit for bit, in every column. The grid is over groups of `rows` slab rows
(`_choose_rows`: the most that divide B and fit one VMEM budget); the
windows live in a ring of three sets of VMEM buffers, so that while one
group is merged the next group's windows are already in flight and the last
group's drain: a step waits for the set it fetched a step ago, merges it in
place, starts its write-back, and before it fetches into a set waits for
the writes that set started two steps ago. One semaphore a set and
direction, counting bytes, so one wait a set and cache takes all its rows'
copies (the paged kernel's pattern).

A window layer's ring (`ring=`): the stripe starts at `pos mod ring` and
what runs past the ring is brought round to the ring's first columns
(`ops.attention._ring_write`). The second piece is a second, synchronous
read-modify-write of the slab row's first aligned columns under
`pl.when(over > 0)`: one step in `ring / T` wraps.

A layer that keeps a third slab a token (the index keys of a sparse-
attention layer, `models/deepseek.py`) writes all three in the one call
(`kv_write_many`): the body loops over its caches, two or three.

Both slabs are aliased to the results (`input_output_aliases`), so a step
that is donated its pool copies no slab round the call, and the
`pallas_call` sits under one module-level `jax.jit` with static integers
(`_kv_write_call`), so a lowered step holds one body a distinct (shapes,
ring) pair, not one a layer. `kv_write_supported` says which shapes the
kernel takes (a slab of whole sublane tiles that holds a window; a ring
whose head window the wrapping stripe's own window cannot touch); for the
others, and on the CPU, `update_kv_cache` keeps the vmapped form, which is
also the parity reference (tests/test_kv_write.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

# The `pallas_call`'s name, so the instruction's in a device trace, and the
# key in `pallas_mode`'s counters. It holds no other kernel's name: trace
# readers find a kernel by substring.
KERNEL = "kv_write"

# Sets of window buffers: one merged, one in flight, one draining.
_SETS = 3
# Slab rows a grid step takes at most, and what its buffers may hold in
# VMEM (three sets of windows and the pipeline's two of stripes, K and V):
# 4.5 MB at 8 rows of 8 heads x 128, 5 MB at 4 rows of 16 heads.
_MAX_ROWS = 8
_VMEM_BUDGET = 6 << 20


def _tile(dtype) -> int:
    """Columns of a slab's packed sublane tile: what a window is aligned
    to."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def _window(T: int, A: int) -> int:
    """Aligned columns that hold a stripe of T wherever it starts."""
    return -(-T // A) * A + A


def _row_bytes(Hkv: int, T: int, W: int, widths: int, itemsize: int) -> int:
    return (_SETS * W + 2 * T) * Hkv * widths * itemsize


def _choose_rows(B: int, Hkv: int, T: int, W: int, widths: int,
                 itemsize: int) -> int:
    """Slab rows a grid step takes: the most that divide B, up to
    `_MAX_ROWS`, whose buffers fit the budget."""
    fit = max(_VMEM_BUDGET // _row_bytes(Hkv, T, W, widths, itemsize), 1)
    return max(r for r in range(1, min(B, _MAX_ROWS, fit) + 1) if B % r == 0)


def kv_write_supported(k_cache, v_cache, k_new, v_new, ring=None) -> bool:
    """Whether the kernel takes these shapes (module docstring)."""
    return kv_write_many_supported((k_cache, v_cache), (k_new, v_new), ring)


def kv_write_many_supported(caches, news, ring=None) -> bool:
    """`kv_write_supported` for any number of slabs a token."""
    k_cache, k_new = caches[0], news[0]
    if k_cache.ndim != 4 \
            or any(c.shape[:3] != k_cache.shape[:3]
                   or c.dtype != k_cache.dtype for c in caches) \
            or any(n.shape[:3] != k_new.shape[:3] for n in news):
        return False
    L, T = k_cache.shape[2], k_new.shape[2]
    A = _tile(k_cache.dtype)
    W = _window(T, A)
    if L % A or L < W:
        return False
    if ring is None:
        return True
    # a stripe that wraps starts past `ring - T`: its window must begin
    # behind the head's, or the two writes would cross
    head = W - A
    first = min((ring - T + 1) // A * A, L - W)
    return ring >= T and L >= ring + T and first >= head


def _kernel(col_ref, *refs, n, rows, T, A, ring):
    """Grid (B / rows,); step i merges the stripes of slab rows
    [i * rows, (i + 1) * rows) into their aligned windows (module
    docstring). `col_ref [B]` (scalar prefetch) is each row's first column;
    the `n` stripes come through the pipeline; the slabs stay in HBM and
    are read and written through the aliased results alone."""
    news, hbms, wins = refs[:n], refs[2 * n:3 * n], refs[3 * n:4 * n]
    in_sem, out_sem = refs[4 * n:4 * n + 2]
    head = refs[4 * n + 2:]
    i, steps = pl.program_id(0), pl.num_programs(0)
    L, W = hbms[0].shape[2], wins[0].shape[3]
    caches = tuple(zip(news, hbms, wins))

    def start_of(row):
        return pl.multiple_of(jnp.minimum(col_ref[row] // A * A, L - W), A)

    def each_row(body):
        jax.lax.fori_loop(0, rows, lambda r, c: body(r) or c, None)

    def fetch(step, s):
        def one(r):
            row = step * rows + r
            a = start_of(row)
            for _, hbm, win in caches:
                pltpu.make_async_copy(hbm.at[row, :, pl.ds(a, W), :],
                                      win.at[s, r], in_sem.at[s]).start()
        each_row(one)

    def wait(sem, s):
        # the set's copies share its semaphore, which counts bytes: one
        # wait for a cache's set takes all its rows'
        for _, _, win in caches:
            pltpu.make_async_copy(win.at[s], win.at[s], sem.at[s]).wait()

    def place(new, shift, width):
        """`new [Hkv, T, D]` in `width` columns, its first at `shift`.
        Mosaic rotates 32-bit data alone: a narrower type makes the trip
        through its 32-bit widening, which holds every value of it."""
        dtype = new.dtype
        new = new.astype(jnp.float32)
        if width > T:
            new = jnp.concatenate(
                [new, jnp.zeros((new.shape[0], width - T, new.shape[2]),
                                new.dtype)], axis=1)
        return pltpu.roll(new, shift, 1).astype(dtype)

    s = i % _SETS
    ahead = (i + 1) % _SETS

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    @pl.when(i + 1 < steps)
    def _():
        @pl.when(i + 1 >= _SETS)
        def _():
            wait(out_sem, ahead)    # what step i + 1 - _SETS wrote from it
        fetch(i + 1, ahead)

    wait(in_sem, s)

    def merge(r):
        row = i * rows + r
        a = start_of(row)
        off = col_ref[row] - a
        for new_ref, hbm, win in caches:
            old = win[s, r]
            c = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
            win[s, r] = jnp.where((c >= off) & (c < off + T),
                                  place(new_ref[r], off, W), old)
            pltpu.make_async_copy(win.at[s, r],
                                  hbm.at[row, :, pl.ds(a, W), :],
                                  out_sem.at[s]).start()
        if ring is None:
            return
        heads, head_sem = head[:n], head[n]
        over = col_ref[row] + T - ring      # columns past the ring's end
        Wh = heads[0].shape[1]

        @pl.when(over > 0)
        def _():
            for (new_ref, hbm, _), buf in zip(caches, heads):
                first = hbm.at[row, :, pl.ds(0, Wh), :]
                fetch_head = pltpu.make_async_copy(first, buf, head_sem)
                fetch_head.start()
                fetch_head.wait()
                c = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
                # column j < over takes the stripe's column T - over + j
                buf[...] = jnp.where(
                    c < over, place(new_ref[r], (Wh - T + over) % Wh, Wh),
                    buf[...])
                write_head = pltpu.make_async_copy(buf, first, head_sem)
                write_head.start()
                write_head.wait()

    each_row(merge)

    @pl.when(i == steps - 1)
    def _():
        for k in range(_SETS):              # what is still being written
            @pl.when(i >= k)
            def _(k=k):
                wait(out_sem, (i - k) % _SETS)


@functools.partial(jax.jit, static_argnames=("ring", "rows", "interpret"))
def _kv_write_call(caches, news, col, *, ring, rows, interpret):
    """The kernel's `pallas_call`. Jitted at module level with every
    integer static, so the call sites of one traced program that agree on
    shapes and ring (a step's layers, unrolled) share one jaxpr, and the
    program lowers one kernel body for them, not one each."""
    n = len(caches)
    B, Hkv, _, _ = caches[0].shape
    T = news[0].shape[2]
    A = _tile(caches[0].dtype)
    W = _window(T, A)

    def stripes(new):
        return pl.BlockSpec((rows, Hkv, T, new.shape[3]),
                            lambda i, col_ref: (i, 0, 0, 0))

    def windows(cache):
        return pltpu.VMEM((_SETS, rows, Hkv, W, cache.shape[3]), cache.dtype)

    slab = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [windows(c) for c in caches] + [
        pltpu.SemaphoreType.DMA((_SETS,)),
        pltpu.SemaphoreType.DMA((_SETS,))]
    if ring is not None:
        scratch += [pltpu.VMEM((Hkv, W - A, c.shape[3]), c.dtype)
                    for c in caches]
        scratch.append(pltpu.SemaphoreType.DMA(()))
    return pl.pallas_call(
        functools.partial(_kernel, n=n, rows=rows, T=T, A=A, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // rows,),
            in_specs=[stripes(x) for x in news] + [slab] * n,
            out_specs=[slab] * n, scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        # the slabs are written where they lie: a step that is donated its
        # pool copies nothing round the call
        input_output_aliases={1 + n + j: j for j in range(n)},
        # in order: a step sets the next one's windows going
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL,
    )(col, *news, *caches)


def kv_write_many(caches, news, pos, ring=None):
    """`kv_write` for any number of slabs a token (`caches[j] [B, Hkv, L,
    Dj]`, `news[j] [B, Hkv, T, Dj]`): one call, every slab aliased."""
    B, Hkv, L, _ = caches[0].shape
    T = news[0].shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)
    col = jnp.clip(pos, 0, L - T) if ring is None else pos % ring
    W = _window(T, _tile(caches[0].dtype))
    rows = _choose_rows(B, Hkv, T, W, sum(c.shape[3] for c in caches),
                        caches[0].dtype.itemsize)
    pallas_mode.note_tiling(KERNEL, grid=(B // rows,), rows=rows, heads=Hkv,
                            columns=W, ring=ring or 0)
    return tuple(_kv_write_call(
        tuple(caches), tuple(news), col, ring=ring, rows=rows,
        interpret=pallas_mode.interpret(KERNEL)))


def kv_write(k_cache, v_cache, k_new, v_new, pos, ring=None):
    """`update_kv_cache` at a `[B]` position vector through the kernel
    (interpreted on the CPU): the caches with row b's stripe at column
    `pos[b]` (clamped into the slab as `dynamic_update_slice` clamps it),
    or, with `ring`, at `pos[b] mod ring` with its overrun brought round.
    The caller has cast the stripes to the caches' type and checked
    `kv_write_supported`."""
    return kv_write_many((k_cache, v_cache), (k_new, v_new), pos, ring)
