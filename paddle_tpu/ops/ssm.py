"""The selective state recurrence of a Mamba-2 layer over the columns of one
step, with the state carried between steps, the causal depthwise convolution
in front of it with its own carried columns, and below both Mamba-1's.

For one row (a sequence, or a serving slot), head h of width P and a state
of N channels, column t of the step's T columns:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t    [P, N]
    y_t[h] = S_t[h] C_t                                               [P]

`adv` of a row's T columns are live. A dead column passes the state
through; what it returns nobody reads. A row that is `fresh` starts from a
zero state inside the call, so admitting a sequence costs no pass over the
pool. The `D` skip (`y += D x`), the gate and the norm stay with the layer
(`nn/layer/mamba.py`).

**Layout.** The state is stored transposed, `[rows, N, H * P]`: the state's
channels on the sublanes and the flat (head, p) index on the lanes, the
index `x` and `y` have as `[rows, T, H * P]`. Everything is then
elementwise in one layout: `B_t`, `C_t` are columns broadcast along the
lanes (one broadcast a row and column, shared by every head: one group),
`x_t`, `dt_t`, `exp(dt_t A)` are rows broadcast along the sublanes, and
`y_t` is a sum over sublanes. (`[H, P, N]`, as Hugging Face stores it,
needs `x_t` along sublanes and a lane reduction for every `y`.)

One path per platform, as `ops/grouped_matmul.py`:

- on a TPU the Mosaic kernel `ssm_update`: grid (row, lane block); a row's
  state tile is read once and written once, 128 lanes at a time, by one of
  two bodies that the kernel chooses from the row's `adv` on the scalar
  core (both behind a `pl.when` in the one `pallas_call`; a call too narrow
  for any row to reach the threshold traces the first alone). The
  `pallas_call` sits under one module-level `jax.jit` whose integers are
  static (`_ssm_call`), so the layers of one traced step share one jaxpr
  and the lowered module holds one kernel body for them;
- on the CPU the recurrence in `jax.numpy` (`lax.scan` over the columns),
  counted `ssm_update/scan`: the kernel's plain reference.

**The column loop** (`adv < MATRIX_COLUMNS`: a decode row, a short tail).
The state tile sits in registers while the row's live columns are applied
one after the other (a loop of `adv` trips: a decode row costs one column,
not T), each a full pass over the chunk's sixteen registers: a multiply by
the decay, a multiply-add of `B_t`, a multiply by `C_t` and a sublane sum.
The per-head scalars ride in SMEM. State in its storage type between steps
(bfloat16 in a bfloat16 model), float32 inside.

**The matrix body** (`adv >= MATRIX_COLUMNS`: a prefill chunk). With
`d_u = dt_u A[h] <= 0` (0 on a dead column) and `L_t = sum_{u <= t} d_u`,

    y_t = exp(L_t) (C S_0)_t + sum_{s <= t} (C_t . B_s) exp(L_t - L_s) dt_s x_s
    S_T = exp(L_T) S_0       + B^T [exp(L_T - L_s) dt_s x_s]_s

so a 128-lane chunk (two heads) costs, instead of `adv` passes over its
state: `C [T, N] . S_0 [N, 128]` on the MXU (both bfloat16 as stored: one
pass, exact products, float32 sums; a float32 state or float32 `B`, `C`
take the compiler's float32 product: the operand's type decides);
`G = C B^T [T, T]` once a row (every head shares it), masked to `s <= t`
and the live columns, each column of it laid along the lanes; the `[T, T]`
product with `dt x` on the vector unit, an 8-row tile of `y` at a time (a
tile's own columns one by one with `exp(min(within_t - within_s, 0))`, an
earlier tile's columns summed as they leave that tile and brought here by
one `exp`; tiles above the diagonal are skipped); `B^T [N, T] . w [T, 128]`
on the MXU with `w` float32 as three bfloat16 terms down the contraction
(`B` is exact in bfloat16, so that is a float32 product), and **one** pass
over the state. What a head's decays give (`_head_rows`: sums of log-decays
inside and across tiles of eight columns, their `exp`s, `dt`) is computed
per head by XLA on `[rows, T, H]` and laid over the head's lanes inside the
kernel by a product with a 0 / 1 matrix on the MXU, again as three bfloat16
terms. Every decay is the `exp` of a sum of log-decays (all <= 0) or, inside
a tile, of a difference of two sums of at most eight: never a ratio of two
`exp`s (sixteen columns of a strongly decaying head underflow float32),
never a difference of two long sums (a slow head's low bits). Dead columns
have `dt` 0, `B` and `x` masked: what they hold reaches nothing; a `fresh`
row starts from zero in either body. No operand is rounded to bfloat16 that
the loop keeps in float32.

**What chooses.** In the loop a row costs ~5 us whatever it holds (its
4.2 MB of bfloat16 state in and out) plus ~3.2 us a live column; the matrix
body is one cost for up to T columns, ~10 us at T = 16. `MATRIX_COLUMNS` is
the crossover measured on a v5e (the numbers stand beside it).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

KERNEL = "ssm_update"
LANES = 128
# lanes of (head, p) a grid step holds: [N 128, 2048] bfloat16 is 512 KB of
# state in and as much out, 1.3 us of HBM time against ~0.35 us a grid step
LANE_BLOCK = 2048
# columns one call of the kernel takes (its column tables live in VMEM);
# a longer sequence is walked in chunks of this many, state carried
MAX_COLUMNS = 64
# live columns from which a row takes the matrix body, under which the column
# loop. On a v5e at granite-4.0-h-small's widths, 32 rows of a 16-column
# call (my chip runs, PR 46): the loop 5.0 us a row + 3.17 us a live column
# (263 us a call at one column, 365 at two, 462 at three, 1,786 at
# sixteen), the matrix body 10.2 us a row whatever the row holds (326 us a
# call): they cross at 1.6 columns. (A 64-column call's matrix body costs
# four times as much; its rows are whole chunks of a prompt but the last.)
MATRIX_COLUMNS = 2
# columns x 128-lane chunks the matrix body writes out a trip of its loop
# (8 chunks of a 16-column call, 2 of a 64-column one). A chunk alone is a
# chain of latencies; the same call with 1 / 2 / 4 / 8 / 16 chunks a trip:
# 514 / 419 / 349 / 326 / 289 us, the body traced and lowered in 0.6 / - /
# 0.8 / 1.3 / 1.9 s (sandbox) where PR 45's nine bodies took 3.3
TRIP_COLUMNS = 128
F32 = jnp.float32
# the package's default precision asks the compiler for float32 products;
# a product of bfloat16 operands is exact in one pass of the MXU
_ONE_PASS = jax.lax.Precision.DEFAULT


def _bf16_terms(a, axis: int, in_kernel: bool):
    """float32 `a` as three bfloat16 terms side by side along `axis` whose
    sum is `a` to 2**-24 of it: one bfloat16 pass of the MXU over them,
    against an operand that is exact in bfloat16 and repeated three times
    along the contraction, is a float32 product. Outside a kernel each
    term is rounded by `reduce_precision`, which XLA must honour: it is
    allowed excess precision and reads `a - float32(bfloat16(a))` inside
    one fusion as 0, which leaves the first term alone (2**-8 of `a`: seen
    on the chip). Mosaic has no such primitive and converts as written."""
    def rounded(v):
        if in_kernel:
            return v.astype(jnp.bfloat16).astype(F32)
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    hi = rounded(a)
    mid = rounded(a - hi)
    lo = a - hi - mid
    return jnp.concatenate([hi, mid, lo], axis=axis).astype(jnp.bfloat16)


def _product(a, b, dims=(((1,), (0,)), ((), ()))):
    """`a . b` with a float32 accumulator that rounds nothing its operands
    hold: one pass where both are bfloat16 (the products are exact), the
    compiler's float32 product where either is float32."""
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims, precision=_ONE_PASS,
                                   preferred_element_type=F32)
    return jax.lax.dot_general(a.astype(F32), b.astype(F32), dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _kernel(adv_ref, fresh_ref, da_ref, dt_ref, x_ref, b_ref, c_ref, s_ref,
            *rest, heads_per_chunk, head_dim, matrix_from):
    row, block = pl.program_id(0), pl.program_id(1)
    adv = adv_ref[row]
    T, N = b_ref.shape[1], b_ref.shape[2]
    keep = fresh_ref[row] == 0
    if matrix_from is None:
        (y_ref, out_ref, bcol, ccol), by_loop = rest, None
    else:
        heads_ref, spread_ref, bt_ref, y_ref, out_ref, bcol, ccol, gcol, \
            wide = rest
        by_loop = adv < matrix_from

    # ---- a row of few live columns: one pass over the state a column ----
    def loop_columns_body():
        # B_t and C_t as columns along the sublanes, once a row: every lane
        # block of the row (and every head: one group) uses the same ones
        @pl.when(block == 0)
        def _columns():
            for t in range(bcol.shape[0]):      # what a loop row can have
                @pl.when(t < adv)
                def _():
                    for ref, col in ((b_ref, bcol), (c_ref, ccol)):
                        col[t] = jnp.broadcast_to(
                            ref[0, t:t + 1, :].astype(F32).reshape(N, 1),
                            (N, LANES))

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        column = jax.lax.broadcasted_iota(jnp.int32, (T, LANES), 0)

        def per_head(ref, t, chunk):
            """The heads' scalars of column t as one row of lanes."""
            first = chunk * heads_per_chunk
            out = jnp.full((1, LANES), ref[0, 0, t, first], F32)
            for k in range(1, heads_per_chunk):
                out = jnp.where(lane >= k * head_dim,
                                ref[0, 0, t, first + k], out)
            return out

        for chunk in range(s_ref.shape[2] // LANES):
            sl = pl.ds(chunk * LANES, LANES)
            state = jnp.where(keep, s_ref[0, :, sl].astype(F32), 0.0)
            xs = x_ref[0, :, sl].astype(F32)                   # [T, 128]

            def one_column(t, carry, chunk=chunk, xs=xs):
                state, ys = carry
                here = column == t
                x_t = jnp.sum(jnp.where(here, xs, 0.0), axis=0,
                              keepdims=True)
                state = state * per_head(da_ref, t, chunk) \
                    + bcol[t] * (x_t * per_head(dt_ref, t, chunk))
                y_t = jnp.sum(state * ccol[t], axis=0, keepdims=True)
                return state, jnp.where(here, y_t, ys)

            state, ys = jax.lax.fori_loop(
                0, adv, one_column, (state, jnp.zeros((T, LANES), F32)))
            y_ref[0, :, sl] = ys.astype(y_ref.dtype)
            out_ref[0, :, sl] = state.astype(out_ref.dtype)

    if by_loop is None:
        return loop_columns_body()
    pl.when(by_loop)(loop_columns_body)

    # ---- a row of many: its T columns at once (the module docstring) ----
    @pl.when(jnp.logical_not(by_loop))
    def _matrix():
        # G[t, s] = C_t . B_s for s <= t, once a row (every head shares it),
        # each of its columns laid along the lanes
        @pl.when(block == 0)
        def _gram():
            g = _product(c_ref[0], b_ref[0], (((1,), (1,)), ((), ())))
            t_of = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
            s_of = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            g = jnp.where((s_of <= t_of) & (s_of < adv), g, 0.0)
            for s in range(T):
                first = s // 8 * 8      # the 8-row tiles above hold zeros
                gcol[s, first:] = jnp.broadcast_to(g[first:, s:s + 1],
                                                   (T - first, LANES))

        # the block's per-head rows (`_head_rows`), each head over its own
        # lanes: a product with a 0 / 1 matrix, the float32 values as three
        # bfloat16 terms along the contraction
        wide[...] = _product(heads_ref[0, block], spread_ref[...])
        live = jax.lax.broadcasted_iota(jnp.int32, (T, LANES), 0) < adv
        tiles = T // 8
        c, b_t = c_ref[0], bt_ref[0]      # the same for every lane chunk

        def one_chunk(chunk):
            sl = pl.ds(pl.multiple_of(chunk * LANES, LANES), LANES)

            def of_tile(which, k):        # a `[1, 128]` row of `_head_rows`
                at = 2 * T + which * tiles + k
                return wide[at:at + 1, sl]

            within = [wide[8 * k:8 * k + 8, sl] for k in range(tiles)]
            xs = jnp.where(live, x_ref[0, :, sl].astype(F32), 0.0)
            xdt = wide[T:2 * T, sl] * xs                           # [T, 128]
            s0 = s_ref[0, :, sl]
            from_s0 = _product(c, s0)
            # exp(L_t) by tiles: the tiles before t's, then t's own columns
            risen = [jnp.exp(w) for w in within]
            grown = [risen[k] * of_tile(0, k) for k in range(tiles)]
            # a column's input as it leaves its tile
            leaving = [xdt[8 * k:8 * k + 8]
                       * jnp.exp(within[k][7:8] - within[k])
                       for k in range(tiles)]
            # y by 8-row tiles: what the state the row came with gives each
            # column; a tile's own columns one by one; an earlier tile's
            # summed as they leave that tile and brought here by one decay
            y = [jnp.where(keep, grown[k] * from_s0[8 * k:8 * k + 8], 0.0)
                 for k in range(tiles)]
            for j in range(tiles):
                own = slice(8 * j, 8 * j + 8)
                for i in range(8):
                    s = 8 * j + i
                    decay = jnp.exp(jnp.minimum(
                        within[j] - within[j][i:i + 1], 0.0))
                    y[j] = y[j] + gcol[s, own] * decay * xdt[s:s + 1]
                for k in range(j + 1, tiles):
                    rows = slice(8 * k, 8 * k + 8)
                    arriving = sum(gcol[8 * j + i, rows] * leaving[j][i:i + 1]
                                   for i in range(8))
                    reach = risen[k] if k == j + 1 else jnp.exp(
                        within[k] + of_tile(2 + k, j))   # the tiles between
                    y[k] = y[k] + reach * arriving
            y_ref[0, :, sl] = jnp.concatenate(y, axis=0).astype(y_ref.dtype)
            # the state after the row's columns: one pass
            w = jnp.concatenate([leaving[k] * of_tile(1, k)
                                 for k in range(tiles)], axis=0)
            new = _product(b_t, _bf16_terms(w, 0, True)
                           if b_t.dtype == jnp.bfloat16 else w)
            out_ref[0, :, sl] = (
                jnp.where(keep, s0.astype(F32), 0.0) * grown[-1][7:8]
                + new).astype(out_ref.dtype)

        # a few chunks a trip, written out: one chunk's products and `exp`s
        # run under another's vector work (a chunk alone is a chain of
        # latencies: load, latch, product, pop, pass, store)
        chunks = s_ref.shape[2] // LANES
        a_trip = math.gcd(chunks, max(TRIP_COLUMNS // T, 1))

        def some_chunks(trip, _):
            for k in range(a_trip):
                one_chunk(trip * a_trip + k)
            return 0

        jax.lax.fori_loop(0, chunks // a_trip, some_chunks, 0)


def _lane_block(lanes: int) -> int:
    if lanes <= LANE_BLOCK:
        return lanes
    for b in range(LANE_BLOCK, LANES - 1, -LANES):
        if lanes % b == 0:
            return b
    raise ValueError(f"ssm_update: {lanes} lanes have no block that is a "
                     f"multiple of {LANES} and at most {LANE_BLOCK}")


def _head_rows(log_decay, dt_live):
    """What the matrix body needs of a head's decays, `[rows, 2 T + 2 tiles
    (+ tiles squared), H]` float32, with `d_u = dt_u A <= 0` a column's
    log-decay (0 on a dead column) and the T columns cut into tiles of
    eight. Every decay the body takes is the `exp` of a sum of log-decays,
    or inside one tile of a difference of two sums of at most eight: never
    a ratio of two `exp`s (sixteen columns of a strongly decaying head
    underflow float32), never a difference of two long sums (which would
    lose the low bits of a slow head's decay).
    T rows each: `within_t`, the sum of d from t's tile's first column to
    t; `dt_t`. A row a tile each: `exp` of the sum of d over the tiles
    before it; over the tiles after it. Then, beyond two tiles, row
    k * tiles + j: the sum of d over the whole tiles between j and k."""
    rows, T, H = log_decay.shape
    tiles = T // 8
    within = jnp.cumsum(log_decay.reshape(rows, tiles, 8, H), axis=2)
    whole = within[:, :, -1]                              # a tile's sum
    before = jnp.cumsum(whole, axis=1) - whole
    after = jnp.cumsum(whole[:, ::-1], axis=1)[:, ::-1] - whole
    parts = [within.reshape(rows, T, H), dt_live, jnp.exp(before),
             jnp.exp(after)]
    if tiles > 2:
        j = jnp.arange(tiles)
        between = ((j[None, :, None] > j[None, None, :])     # [k, m, j]
                   & (j[None, :, None] < j[:, None, None])).astype(F32)
        parts.append(jnp.einsum(
            "kmj,rmh->rkjh", between, whole,
            precision=jax.lax.Precision.HIGHEST).reshape(rows, -1, H))
    out = jnp.concatenate(parts, axis=1)
    return jnp.pad(out, ((0, 0), (0, -out.shape[1] % 16), (0, 0)))


@functools.partial(jax.jit, static_argnames=("lb", "matrix_from",
                                             "interpret"))
def _ssm_call(x, dt_live, log_decay, b, c, state, adv, fresh, *, lb,
              matrix_from, interpret):
    """The kernel's `pallas_call` at one tiling. Jitted at module level
    with its integers static, so the layers of one traced step share one
    jaxpr and the lowered module holds one kernel body for them.
    `matrix_from`: rows of at least so many live columns take the matrix
    body (None: no row of this call can; the body is the loop alone)."""
    rows, T, lanes = x.shape
    heads, N = dt_live.shape[2], state.shape[1]
    head_dim = lanes // heads
    blocks, heads_per_block = lanes // lb, lb // head_dim

    def scalars(a):       # [rows, n, H] -> [rows, blocks, n, heads a block]
        return a.reshape(rows, -1, blocks, heads_per_block).transpose(
            0, 2, 1, 3)

    smem = pl.BlockSpec((1, 1, T, heads_per_block),
                        lambda r, g, *_: (r, g, 0, 0),
                        memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((1, T, lb), lambda r, g, *_: (r, 0, g))
    narrow = pl.BlockSpec((1, T, N), lambda r, g, *_: (r, 0, 0))
    tile = pl.BlockSpec((1, N, lb), lambda r, g, *_: (r, 0, g))
    operands = [scalars(jnp.exp(log_decay)), scalars(dt_live), x, b, c,
                state]
    in_specs = [smem, smem, wide, narrow, narrow, tile]
    # the loop's column tables hold the columns a loop row can have
    loop_columns = T if matrix_from is None else max(matrix_from - 1, 1)
    scratch = [pltpu.VMEM((loop_columns, N, LANES), F32),
               pltpu.VMEM((loop_columns, N, LANES), F32)]
    if matrix_from is not None:
        per_head = scalars(_head_rows(log_decay, dt_live))
        # head h of a block over its own lanes, three times down the rows
        spread = (jnp.arange(lb, dtype=jnp.int32)[None, :] // head_dim
                  == jnp.arange(3 * heads_per_block, dtype=jnp.int32)[:, None]
                  % heads_per_block).astype(jnp.bfloat16)
        # B^T [rows, N, T], a dead column's B zero (it adds nothing to the
        # state); against `w`'s three bfloat16 terms, three times over
        live = jnp.arange(T, dtype=jnp.int32)[None, None, :] \
            < adv[:, None, None]
        b_t = jnp.where(live, jnp.swapaxes(b, 1, 2), 0)
        if b.dtype == jnp.bfloat16:
            b_t = jnp.broadcast_to(b_t[:, :, None], (rows, N, 3, T)).reshape(
                rows, N, 3 * T)
        operands += [_bf16_terms(per_head, -1, False), spread, b_t]
        in_specs += [
            # a row's blocks in one piece: fetched once a row
            pl.BlockSpec((1, blocks, per_head.shape[2], 3 * heads_per_block),
                         lambda r, g, *_: (r, 0, 0, 0)),
            pl.BlockSpec(spread.shape, lambda r, g, *_: (0, 0)),
            pl.BlockSpec((1, N, b_t.shape[2]), lambda r, g, *_: (r, 0, 0))]
        scratch += [pltpu.VMEM((T, T, LANES), F32),
                    pltpu.VMEM((per_head.shape[2], lb), F32)]
    return pl.pallas_call(
        functools.partial(_kernel, heads_per_chunk=LANES // head_dim,
                          head_dim=head_dim, matrix_from=matrix_from),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, blocks),
            in_specs=in_specs, out_specs=[wide, tile],
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        # the new state takes the state's buffer: a grid step reads and
        # writes its own tile alone, so where the caller donates the state
        # (the serving step its pool) nothing is copied round the kernel
        input_output_aliases={7: 1},
        interpret=interpret,
        name=KERNEL,
    )(adv, fresh, *operands)


def _mosaic(x, dt_live, log_decay, b, c, state, adv, fresh):
    rows, T, lanes = x.shape
    heads, N = dt_live.shape[2], state.shape[1]
    head_dim = lanes // heads
    if lanes % LANES or LANES % head_dim or N % 8:
        raise ValueError(
            f"ssm_update kernel: heads x head_dim {heads} x {head_dim} must "
            f"fill whole {LANES}-lane registers with whole heads, and the "
            f"state's {N} channels whole sublane tiles")
    lb = _lane_block(lanes)
    # a call too narrow for any row to reach the matrix body traces the
    # loop alone; one that can pads its columns to whole packed tiles
    matrix_from = MATRIX_COLUMNS if T >= MATRIX_COLUMNS else None
    pad = -T % 16 if matrix_from else 0
    pallas_mode.note_tiling(KERNEL, grid=(rows, lanes // lb), columns=T,
                            state_tile=(N, lb), matrix_from=matrix_from or 0)
    if pad:
        x, dt_live, log_decay, b, c = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            for a in (x, dt_live, log_decay, b, c))
    y, new_state = _ssm_call(
        x, dt_live, log_decay, b, c, state, adv, fresh, lb=lb,
        matrix_from=matrix_from, interpret=pallas_mode.interpret(KERNEL))
    return y[:, :T], new_state


def _scan(x, dt_live, decay, b, c, state, fresh):
    """The kernel's arithmetic, column after column, in `jax.numpy`."""
    head_dim = x.shape[2] // dt_live.shape[2]

    def lanes(a):               # [rows, T, H] -> [T, rows, H * P]
        return jnp.swapaxes(jnp.repeat(a, head_dim, axis=-1), 0, 1)

    def one_column(s, col):
        x_t, dt_t, da_t, b_t, c_t = col
        s = s * da_t[:, None, :] \
            + b_t[:, :, None] * (x_t * dt_t)[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s0 = jnp.where(fresh[:, None, None] != 0, 0.0, state.astype(F32))
    s, ys = jax.lax.scan(
        one_column, s0,
        (jnp.swapaxes(x.astype(F32), 0, 1), lanes(dt_live), lanes(decay),
         jnp.swapaxes(b.astype(F32), 0, 1), jnp.swapaxes(c.astype(F32), 0, 1)))
    return jnp.swapaxes(ys, 0, 1).astype(x.dtype), s.astype(state.dtype)


def ssm_update(x, dt, a, b, c, state, adv=None, fresh=None,
               impl: str = None):
    """x `[rows, T, H * P]`; dt `[rows, T, H]` float32, positive (after its
    softplus); a `[H]` float32, negative; b, c `[rows, T, N]`; state
    `[rows, N, H * P]` in its storage type; adv `[rows]` live columns of
    each row (None: all T); fresh `[rows]` rows that start from zero (None:
    none). Returns (y like x, without the `D` skip; the state after each
    row's `adv` columns, in `state.dtype`).
    impl: None = the scan on the CPU, the kernel on a TPU; or name "scan" /
    "pallas" (on the CPU the kernel runs interpreted: the parity test,
    which also narrows `LANE_BLOCK` so that a small row has several)."""
    rows, T, _ = x.shape
    if dt.shape != (rows, T, a.shape[0]) or b.shape != c.shape \
            or b.shape[:2] != (rows, T) \
            or state.shape != (rows, b.shape[2], x.shape[2]):
        raise ValueError(f"ssm_update: x {x.shape}, dt {dt.shape}, a "
                         f"{a.shape}, b {b.shape}, c {c.shape}, state "
                         f"{state.shape}")
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl not in ("scan", "pallas"):
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    adv = jnp.full((rows,), T, jnp.int32) if adv is None \
        else adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < adv[:, None]
    dt_live = jnp.where(live[..., None], dt.astype(F32), 0.0)
    log_decay = dt_live * a.astype(F32)              # 0 on a dead column
    if impl == "scan":
        pallas_mode.count(KERNEL, "scan")
        return _scan(x, dt_live, jnp.exp(log_decay), b, c, state, fresh)
    if T <= MAX_COLUMNS:
        return _mosaic(x, dt_live, log_decay, b, c, state, adv, fresh)
    # a long sequence (a whole prompt): chunks of MAX_COLUMNS, state carried
    pad = -T % MAX_COLUMNS

    def chunks(arr):
        arr = jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
        return jnp.swapaxes(arr.reshape(rows, -1, MAX_COLUMNS,
                                        arr.shape[2]), 0, 1)

    def one_chunk(carry, chunk):
        s, left, new = carry
        x_k, dt_k, da_k, b_k, c_k = chunk
        y_k, s = _mosaic(x_k, dt_k, da_k, b_k, c_k, s,
                         jnp.clip(left, 0, MAX_COLUMNS), new)
        return (s, left - MAX_COLUMNS, jnp.zeros_like(new)), y_k

    (state, _, _), ys = jax.lax.scan(
        one_chunk, (state, adv, fresh),
        tuple(chunks(arr) for arr in (x, dt_live, log_decay, b, c)))
    y = jnp.swapaxes(ys, 0, 1).reshape(rows, T + pad, x.shape[2])
    return y[:, :T], state


def causal_conv_update(u, conv_state, weight, bias, adv=None, fresh=None):
    """The causal depthwise convolution of width K over a step's columns,
    with the K - 1 columns before them carried: u `[rows, T, D]`,
    conv_state `[rows, K - 1, D]` (the last K - 1 inputs, oldest first,
    before the activation), weight `[D, K]`, bias `[D]`. Returns
    (`silu(bias + sum_j weight[:, j] * in[t - (K - 1) + j])` `[rows, T, D]`
    float32; the carried columns after each row's `adv` live ones: the last
    K - 1 of `concat(conv_state, u[:adv])`)."""
    rows, T, _ = u.shape
    K = weight.shape[1]
    prev = conv_state.astype(u.dtype)
    if fresh is not None:
        prev = jnp.where(fresh[:, None, None] != 0, 0, prev)
    window = jnp.concatenate([prev, u], axis=1)          # [rows, K-1+T, D]
    w = weight.astype(F32)
    out = bias.astype(F32) + sum(
        w[:, j] * window[:, j:j + T].astype(F32) for j in range(K))
    if adv is None:
        carried = window[:, T:]
    else:
        carried = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, K - 1, 0)
        )(window, adv.astype(jnp.int32))
    return jax.nn.silu(out), carried.astype(conv_state.dtype)


# ---- Mamba-1: a step size a channel, a decay a channel and state element ----
#
# For one row, channel c of `channels` and a state of N elements a channel,
# column t of the row's live columns (`nn/layer/mamba.py::Mamba1Mixer`):
#
#     h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n h_t[n, c] C_t[n]
#
# `ssm_update`'s contract (`adv` live columns, `fresh` rows start from zero
# inside the call, the `D` skip and the gate stay with the layer) and its
# layout of the state (`[rows, N, channels]`: the N elements on the sublanes,
# the channels on the lanes, so that `dt_t`, `x_t` and `y_t` are rows of lanes
# and `B_t`, `C_t` columns broadcast along them). What differs:
#
# - there are no heads, so nothing rides in SMEM: `dt` is a row of lanes like
#   `x`, `A` is a `[N, lanes]` float32 tile held beside the state, and the
#   decay `exp(dt_t A)` is computed inside the column loop (one transcendental
#   a state element a column: precomputed it would be T times the state);
# - **the columns are token rows**: x, dt, B, C and y are `[tokens, .]`, row r's
#   live columns the `adv[r]` consecutive token rows from `start[r]`. A serving
#   step's packed block (`ops.attention.TokenPack`: 512 tokens for 256 slots
#   x 16 columns) is that as it stands, so the kernel reads and writes it in
#   place and nothing of a Mamba-1 layer is ever laid out `[slots, chunk, .]`
#   (eight times the packed block at a decode-heavy step, once a layer); an
#   unpacked `[rows, T, .]` call is the same with `start[r] = r T`;
# - a row's tile is small (`[16, 1280]` float32 is 82 KB, 0.1 us of HBM time
#   against ~0.35 us a grid step), so a grid step takes `ROWS_BLOCK` rows where
#   `ssm_update` takes one: grid (lane block, row block), the lane block
#   outermost so that `A`'s tile and the tokens' are fetched once a lane block
#   and not once a grid step; a last row block that is ragged reads rows nobody
#   owns (their `adv` is padded to 0) and its writes past the end are dropped.
#   Inside, a row's state lives in the output block and each live column is
#   one pass over the block's 128-lane registers (a decode row: one pass).
#
# One path per platform, as above: on a TPU the Mosaic kernel
# `selective_scan`; on the CPU the same arithmetic in `jax.numpy`, counted
# `selective_scan/scan`. The `pallas_call` sits under one module-level
# `jax.jit` whose integers are static (`_scan_call`, as `ops/paged_attention.py`
# keeps its own), so that the Mamba layers of one traced step share one jaxpr
# and the lowered module holds one kernel body for them, not one a layer.

SCAN_KERNEL = "selective_scan"
# lanes a grid step holds of each of its rows: ten 128-lane registers' worth,
# unrolled in the kernel's body (5,120 channels are four such blocks)
SCAN_LANE_BLOCK = 1280
# rows a grid step takes at the most
ROWS_BLOCK = 32
# what the tokens' blocks (x, dt and y over a lane block, float32, each held
# twice by the pipeline) may take of VMEM: a lane block narrows to fit
_SCAN_TOKEN_BUDGET = 20 << 20


def _token_row(row):
    """(the aligned eight token rows round token row `row`, which of a
    register's sublanes it is): Mosaic loads and stores a row at any offset
    only as that group, with the one sublane picked."""
    sublane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    return pl.ds(pl.multiple_of(row // 8 * 8, 8), 8), sublane == row % 8


def _eights(arr):
    """Whole groups of eight token rows, float32."""
    return jnp.pad(arr.astype(F32), ((0, -arr.shape[0] % 8), (0, 0)))


def _scan_kernel(start_ref, adv_ref, fresh_ref, a_ref, dt_ref, x_ref, b_ref,
                 c_ref, s_ref, y_ref, out_ref):
    block = pl.program_id(1)
    first = block * s_ref.shape[0]
    N, tokens = b_ref.shape
    along = jax.lax.broadcasted_iota(jnp.int32, (N, tokens), 1)

    @pl.when(block == 0)
    def _unread():         # token rows no row owns: finite, nobody reads them
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def one_row(r, _):
        start = start_ref[first + r]
        keep = fresh_ref[first + r] == 0
        out_ref[r] = jnp.where(keep, s_ref[r], 0.0).astype(out_ref.dtype)

        def one_column(t, _):
            here = along == start + t

            def column(ref):       # [N, 1] -> along the sublanes, every lane
                return jnp.broadcast_to(
                    jnp.sum(jnp.where(here, ref[...], 0.0), axis=1,
                            keepdims=True), (N, LANES))

            b_t, c_t = column(b_ref), column(c_ref)
            group, pick = _token_row(start + t)

            def picked(ref, sl):                               # [1, 128]
                return jnp.sum(jnp.where(pick, ref[group, sl], 0.0), axis=0,
                               keepdims=True)

            for chunk in range(out_ref.shape[2] // LANES):
                sl = pl.ds(chunk * LANES, LANES)
                dt_t = picked(dt_ref, sl)
                state = jnp.exp(dt_t * a_ref[:, sl]) \
                    * out_ref[r, :, sl].astype(F32) \
                    + b_t * (dt_t * picked(x_ref, sl))
                y_t = jnp.sum(state * c_t, axis=0, keepdims=True)
                y_ref[group, sl] = jnp.where(pick, y_t, y_ref[group, sl])
                out_ref[r, :, sl] = state.astype(out_ref.dtype)
            return 0

        jax.lax.fori_loop(0, adv_ref[first + r], one_column, 0)
        return 0

    jax.lax.fori_loop(0, s_ref.shape[0], one_row, 0)


def _scan_lane_block(lanes: int, tokens: int) -> int:
    """The widest block of whole 128-lane registers, at most
    `SCAN_LANE_BLOCK`, that divides `lanes` and under which the tokens'
    three float32 blocks fit their budget."""
    most = min(lanes, SCAN_LANE_BLOCK,
               max(LANES, _SCAN_TOKEN_BUDGET // (24 * tokens)
                   // LANES * LANES))
    for b in range(most // LANES * LANES, 0, -LANES):
        if lanes % b == 0:
            return b


@functools.partial(jax.jit, static_argnames=("lb", "rb", "interpret"))
def _scan_call(x, dt, a, b, c, state, start, adv, fresh, *, lb, rb,
               interpret):
    """The kernel's `pallas_call` at one tiling; x, dt `[tokens, lanes]`
    float32, b, c `[N, tokens]` float32."""
    tokens, lanes = x.shape
    rows, N, _ = state.shape
    blocks = -(-rows // rb)
    # a ragged last block reads the scalars of rows that are not there
    pad = blocks * rb - rows
    start, adv, fresh = (jnp.pad(v, (0, pad)) for v in (start, adv, fresh))
    wide = pl.BlockSpec((tokens, lb), lambda g, r, *_: (0, g))
    narrow = pl.BlockSpec((N, tokens), lambda g, r, *_: (0, 0))
    tile = pl.BlockSpec((rb, N, lb), lambda g, r, *_: (r, 0, g))
    return pl.pallas_call(
        _scan_kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(lanes // lb, blocks),
            in_specs=[pl.BlockSpec((N, lb), lambda g, r, *_: (0, g)),
                      wide, wide, narrow, narrow, tile],
            out_specs=[wide, tile]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        # as `ssm_update`: the new state takes the state's buffer
        input_output_aliases={8: 1},
        interpret=interpret,
        name=SCAN_KERNEL,
    )(start, adv, fresh, a, dt, x, b, c, state)


def _scan_columns(x, dt, a, b, c, state, start, adv, fresh, columns):
    """The kernel's arithmetic, column after column, in `jax.numpy`: each
    row's columns gathered out of the token rows, and y scattered back."""
    tokens = x.shape[0]
    t = jnp.arange(columns, dtype=jnp.int32)
    live = t[None, :] < adv[:, None]                       # [rows, columns]
    at = jnp.where(live, start[:, None] + t[None, :], tokens)

    def of_rows(arr):       # [tokens, w] -> [columns, rows, w], 0 where dead
        arr = jnp.pad(arr.astype(F32), ((0, 1), (0, 0)))
        return jnp.swapaxes(arr[at], 0, 1)

    def one_column(s, col):
        x_t, dt_t, b_t, c_t = col
        # a dead column's step is 0: its decay 1, its input nothing
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s0 = jnp.where(fresh[:, None, None] != 0, 0.0, state.astype(F32))
    s, ys = jax.lax.scan(one_column, s0,
                         tuple(of_rows(arr) for arr in (x, dt, b, c)))
    y = jnp.zeros((tokens + 1, x.shape[1]), F32).at[at].set(
        jnp.swapaxes(ys, 0, 1))[:tokens]
    return y, s.astype(state.dtype)


def selective_scan(x, dt, a, b, c, state, start, adv, fresh=None, *,
                   columns: int, impl: str = None):
    """x, dt `[tokens, channels]` (dt positive: after its softplus); a
    `[N, channels]` float32, negative (`A` transposed: the state's layout);
    b, c `[tokens, N]`; state `[rows, N, channels]` in its storage type
    (float32 in a serving pool); start `[rows]` the token row of each row's
    first column, adv `[rows]` how many consecutive token rows from there
    are its live columns (at most `columns`, a static bound; rows' runs do
    not overlap); fresh `[rows]` rows that start from zero (None: none).
    Returns (y `[tokens, channels]` float32, without the `D` skip, zero on a
    token row that is no row's live column; the state after each row's `adv`
    columns, in `state.dtype`).
    impl: as `ssm_update` (the parity test narrows `SCAN_LANE_BLOCK` and
    `ROWS_BLOCK` so that a small call has several blocks of each)."""
    tokens, lanes = x.shape
    rows, N = state.shape[:2]
    if dt.shape != x.shape or b.shape != (tokens, N) or c.shape != b.shape \
            or a.shape != (N, lanes) or state.shape != (rows, N, lanes) \
            or start.shape != (rows,) or adv.shape != (rows,):
        raise ValueError(f"selective_scan: x {x.shape}, dt {dt.shape}, a "
                         f"{a.shape}, b {b.shape}, c {c.shape}, state "
                         f"{state.shape}, start {start.shape}, adv "
                         f"{adv.shape}")
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl not in ("scan", "pallas"):
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    start, adv = start.astype(jnp.int32), adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    a = a.astype(F32)
    if impl == "scan":
        pallas_mode.count(SCAN_KERNEL, "scan")
        return _scan_columns(x, dt, a, b, c, state, start, adv, fresh,
                             columns)
    if lanes % LANES or N % 8:
        raise ValueError(
            f"selective_scan kernel: {lanes} channels must fill whole "
            f"{LANES}-lane registers, and the state's {N} elements a "
            "channel whole sublane tiles")
    lb = _scan_lane_block(lanes, tokens)
    rb = min(rows, ROWS_BLOCK)
    pallas_mode.note_tiling(SCAN_KERNEL, grid=(lanes // lb, -(-rows // rb)),
                            columns=columns, state_tile=(rb, N, lb))
    y, state = _scan_call(
        _eights(x), _eights(dt), a, _eights(b).T, _eights(c).T, state, start,
        adv, fresh, lb=lb, rb=rb,
        interpret=pallas_mode.interpret(SCAN_KERNEL))
    return y[:tokens], state


CONV_KERNEL = "conv_tokens"


def _conv_kernel(start_ref, adv_ref, fresh_ref, w_ref, bias_ref, u_ref,
                 prev_ref, out_ref, new_ref):
    """`_scan_kernel`'s walk (rows of a block, a row's live columns, a
    column's 128-lane registers) for the convolution: a row's K - 1 carried
    columns live in the output block and move up one a live column."""
    block = pl.program_id(1)
    first = block * prev_ref.shape[0]
    K = w_ref.shape[0]

    @pl.when(block == 0)
    def _unread():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def one_row(r, _):
        start = start_ref[first + r]
        keep = fresh_ref[first + r] == 0
        new_ref[r] = jnp.where(keep, prev_ref[r], 0.0)

        def one_column(t, _):
            group, pick = _token_row(start + t)
            for chunk in range(new_ref.shape[2] // LANES):
                sl = pl.ds(chunk * LANES, LANES)
                u_t = jnp.sum(jnp.where(pick, u_ref[group, sl], 0.0), axis=0,
                              keepdims=True)
                older = [new_ref[r, j:j + 1, sl] for j in range(K - 1)]
                acc = bias_ref[:, sl] + w_ref[K - 1:K, sl] * u_t
                for j in range(K - 1):
                    acc = acc + w_ref[j:j + 1, sl] * older[j]
                out_ref[group, sl] = jnp.where(pick, jax.nn.silu(acc),
                                               out_ref[group, sl])
                for j, column in enumerate(older[1:] + [u_t]):
                    new_ref[r, j:j + 1, sl] = column
            return 0

        jax.lax.fori_loop(0, adv_ref[first + r], one_column, 0)
        return 0

    jax.lax.fori_loop(0, prev_ref.shape[0], one_row, 0)


@functools.partial(jax.jit, static_argnames=("lb", "rb", "interpret"))
def _conv_call(u, prev, w, bias, start, adv, fresh, *, lb, rb, interpret):
    """The conv's `pallas_call` at one tiling; u `[tokens, lanes]`, prev
    `[rows, K - 1, lanes]`, w `[K, lanes]`, bias `[1, lanes]`, all float32."""
    tokens, lanes = u.shape
    rows, carried, _ = prev.shape
    blocks = -(-rows // rb)
    pad = blocks * rb - rows
    start, adv, fresh = (jnp.pad(v, (0, pad)) for v in (start, adv, fresh))
    wide = pl.BlockSpec((tokens, lb), lambda g, r, *_: (0, g))
    tile = pl.BlockSpec((rb, carried, lb), lambda g, r, *_: (r, 0, g))
    return pl.pallas_call(
        _conv_kernel,
        out_shape=(jax.ShapeDtypeStruct(u.shape, F32),
                   jax.ShapeDtypeStruct(prev.shape, F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(lanes // lb, blocks),
            in_specs=[pl.BlockSpec((carried + 1, lb), lambda g, r, *_: (0, g)),
                      pl.BlockSpec((1, lb), lambda g, r, *_: (0, g)),
                      wide, tile],
            out_specs=[wide, tile]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=CONV_KERNEL,
    )(start, adv, fresh, w, bias, u, prev)


def _conv_gathers(u, prev, w, bias, slot, col, start, adv, fresh):
    """The kernel's arithmetic in `jax.numpy`: a token's older inputs are
    the token rows above it or its row's carried columns."""
    tokens, K = u.shape[0], w.shape[0]
    prev = jnp.where(fresh[:, None, None] != 0, 0.0, prev)
    out = bias + w[K - 1] * u
    own = jnp.take(prev, slot, axis=0)                 # [tokens, K - 1, D]
    for back in range(1, K):
        older = jnp.pad(u, ((back, 0), (0, 0)))[:tokens]
        for c in range(back):      # in the row's first `back` columns
            older = jnp.where((col == c)[:, None], own[:, K - 1 - back + c],
                              older)
        out = out + w[K - 1 - back] * older
    # the last K - 1 of concat(carried, live columns), oldest first
    p = adv[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    live = jnp.take(u, jnp.clip(start[:, None] + p - (K - 1), 0, tokens - 1),
                    axis=0)
    carried = jnp.where(
        (p >= K - 1)[..., None], live,
        jnp.take_along_axis(prev, jnp.minimum(p, K - 2)[..., None], axis=1))
    return jax.nn.silu(out), carried


def causal_conv_tokens(u, conv_state, weight, bias, slot, col, start, adv,
                       fresh=None, impl: str = None):
    """`causal_conv_update` over token rows: u `[tokens, D]`, token t the
    column `col[t]` of row `slot[t]`, row r's live columns the `adv[r]`
    token rows from `start[r]` (`ops.attention.TokenPack`'s layout);
    conv_state `[rows, K - 1, D]`. Returns (the activated convolution
    `[tokens, D]` float32, whatever on a token row that is no live column;
    the carried columns after each row's live ones, in `conv_state.dtype`).
    On a TPU the Mosaic kernel `conv_tokens` (the tiling and the walk of
    `selective_scan`: XLA's row gathers of the same took a quarter of a
    millisecond a layer); on the CPU the same in `jax.numpy`. impl: as
    `ssm_update`."""
    tokens, lanes = u.shape
    rows = conv_state.shape[0]
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl not in ("scan", "pallas"):
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    start, adv = start.astype(jnp.int32), adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    # the inputs as the model holds them (rounded to its type), in float32
    u32 = u.astype(F32)
    prev = conv_state.astype(u.dtype).astype(F32)
    w, b = weight.astype(F32).T, bias.astype(F32)
    if impl == "scan" or lanes % LANES:
        pallas_mode.count(CONV_KERNEL, "scan")
        out, carried = _conv_gathers(u32, prev, w, b, slot, col, start, adv,
                                     fresh)
        return out, carried.astype(conv_state.dtype)
    lb = _scan_lane_block(lanes, tokens)
    rb = min(rows, ROWS_BLOCK)
    pallas_mode.note_tiling(CONV_KERNEL, grid=(lanes // lb, -(-rows // rb)),
                            tile=(rb, w.shape[0] - 1, lb))
    out, carried = _conv_call(
        _eights(u32), prev, w, b[None], start, adv, fresh, lb=lb, rb=rb,
        interpret=pallas_mode.interpret(CONV_KERNEL))
    return out[:tokens], carried.astype(conv_state.dtype)


# tokens one call of the kernel takes at the most (their blocks live in
# VMEM); a longer `[rows, T]` block is walked in chunks of columns, state
# carried
MAX_TOKENS = 4096


def selective_scan_rows(x, dt, a, b, c, state, adv=None, fresh=None,
                        impl: str = None):
    """`selective_scan` for columns laid out `[rows, T, .]` (one-shot
    `generate()`, an unpacked step): x, dt `[rows, T, channels]`, b, c
    `[rows, T, N]`, adv `[rows]` live columns of each row (None: all T).
    Returns (y `[rows, T, channels]` float32, the new state)."""
    rows, T, lanes = x.shape
    adv = jnp.full((rows,), T, jnp.int32) if adv is None \
        else adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    step = max(1, min(T, MAX_TOKENS // rows))
    start = jnp.arange(rows, dtype=jnp.int32) * step

    def call(x_k, dt_k, b_k, c_k, s, n, new):
        y, s = selective_scan(
            *(arr.reshape(rows * step, arr.shape[2])
              for arr in (x_k, dt_k)), a,
            *(arr.reshape(rows * step, arr.shape[2]) for arr in (b_k, c_k)),
            s, start, n, new, columns=step, impl=impl)
        return y.reshape(rows, step, lanes), s

    if T == step:
        return call(x, dt, b, c, state, adv, fresh)
    pad = -T % step

    def chunks(arr):
        arr = jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
        return jnp.swapaxes(arr.reshape(rows, -1, step, arr.shape[2]), 0, 1)

    def one_chunk(carry, chunk):
        s, left, new = carry
        y_k, s = call(*chunk, s, jnp.clip(left, 0, step), new)
        return (s, left - step, jnp.zeros_like(new)), y_k

    (state, _, _), ys = jax.lax.scan(
        one_chunk, (state, adv, fresh),
        tuple(chunks(arr) for arr in (x, dt, b, c)))
    y = jnp.swapaxes(ys, 0, 1).reshape(rows, T + pad, lanes)
    return y[:, :T], state
