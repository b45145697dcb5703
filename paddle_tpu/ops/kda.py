"""The delta-rule recurrence of a Kimi Delta Attention layer (KDA: Kimi
Linear, arXiv:2510.26692) over the columns of one step, with the state
carried between steps.

For one row (a sequence, or a serving slot), head h with key and value
width d and a state `S[h]` of `[d_k, d_v]`, column t of the row's live
columns:

    S   <- Diag(exp(g_t[h])) S          a log-decay a key channel, g <= 0
    u    = S^T k_t[h]                   what the decayed state holds for k
    S   <- S + k_t[h] (x) beta_t[h] (v_t[h] - u)          the delta rule
    o_t[h] = S^T q_t[h]

Where `ops/ssm.py`'s recurrences scale the state and add to it, this one
reads the state before it writes it: a column's write depends on a
reduction over the state it has just decayed. With beta in (0, 2) the
update `I - beta k k^T` has eigenvalues in (-1, 1) along k (the config's
`kda_allow_neg_eigval`). `ssm_update`'s contract otherwise: `adv` of a
row's columns are live and a dead one passes the state through; a row
that is `fresh` starts from a zero state inside the call; q's and k's L2
norms, the gates and the output norm stay with the layer
(`nn/layer/kda.py`).

**Layout.** The state is `ssm_update`'s, `[rows, d_k, H * d_v]` float32:
the key channels on the sublanes and the flat (head, value channel) index
on the lanes, so a head of d_v = 128 is one 128-lane register column and
`S^T k`, `S^T q` are sums over sublanes, `k (x) delta` a column broadcast
along the lanes times a row broadcast along the sublanes. **The columns
are token rows**, as `selective_scan`'s: q, k, v, g and o are
`[tokens, H * d]`, row r's live columns the `adv[r]` consecutive token
rows from `start[r]`. A serving step's packed block
(`ops.attention.TokenPack`) is that as it stands; nothing of a KDA layer
is ever laid out `[slots, chunk, .]`.

One path per platform, as `ops/ssm.py`:

- on a TPU the Mosaic kernel `kda_update`: grid (head block, row block),
  the head block outermost so that the tokens' blocks (`[tokens, heads a
  block, 128]`, a token's heads on the sublanes) are fetched once a head
  block. Inside, a row's state lives in the output block and each live
  column is one pass over its heads (a decode row: one column). What a
  head needs along the sublanes (`q`, `k`, `exp(g)`: a value a key
  channel) arrives along the lanes: a column's three `[heads, 128]` tiles
  are stacked into one `[128, 128]` tile, transposed once on the
  transpose unit, and a head's column is then a static lane of it,
  broadcast. `exp(g)` is taken inside the kernel from the log-decay, which
  is all a caller hands it. The `pallas_call` sits under one module-level
  `jax.jit` whose integers are static (`_kda_call`), so the KDA layers of
  one traced step share one jaxpr and the lowered module holds one kernel
  body for them;
- on the CPU the same arithmetic in `jax.numpy` (`lax.scan` over the
  columns), counted `kda_update/scan`: the kernel's plain reference.

There is no chunked body for a prefill row: its sixteen columns are
sixteen passes over its heads (ROADMAP A has the measured cost of a
16-column row against a 1-column row; the chunked form is a 16 x 16
unit-lower-triangular solve a head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

KERNEL = "kda_update"
LANES = 128
F32 = jnp.float32
# heads a grid step holds of each of its rows: their q, k and exp(g) tiles
# stacked fill 3 x 8 of the 128 rows the transpose takes, and a token
# block `[512, 8, 128]` float32 is 2 MB (five of them, held twice by the
# pipeline: 20 MB of VMEM)
HEAD_BLOCK = 8
# rows a grid step takes at the most: `[4, 128, 1024]` float32 is 2 MB of
# state in and as much out, 5 us of HBM time against ~0.35 us a grid step
ROWS_BLOCK = 4
# tokens one call of the kernel takes at the most (their blocks live in
# VMEM); a longer `[rows, T]` block is walked in chunks of columns, state
# carried
MAX_TOKENS = 512


def _kernel(start_ref, adv_ref, fresh_ref, beta_ref, q_ref, k_ref, g_ref,
            v_ref, s_ref, o_ref, out_ref, cols_ref):
    block = pl.program_id(1)
    rb, d = s_ref.shape[0], s_ref.shape[1]
    hb = q_ref.shape[1]
    first = block * rb

    @pl.when(block == 0)
    def _unread():         # token rows no row owns: finite, nobody reads them
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def one_row(r, _):
        start = start_ref[first + r]
        keep = fresh_ref[first + r] == 0
        out_ref[r] = jnp.where(keep, s_ref[r], 0.0).astype(out_ref.dtype)

        def one_column(t, _):
            tok = start + t
            # a value a key channel of each head, along the sublanes: the
            # token's q, k and decay tiles as columns of one transpose
            tiles = [q_ref[tok].astype(F32), k_ref[tok].astype(F32),
                     jnp.exp(g_ref[tok].astype(F32))]
            if 3 * hb < LANES:
                tiles.append(jnp.zeros((LANES - 3 * hb, d), F32))
            cols_ref[...] = jnp.concatenate(tiles, axis=0).T
            v_t = v_ref[tok].astype(F32)                       # [hb, 128]

            def column(j):            # [d, 1] -> every lane
                return jnp.broadcast_to(cols_ref[:, j:j + 1], (d, LANES))

            outs = []
            for h in range(hb):
                sl = pl.ds(h * LANES, LANES)
                k_h = column(hb + h)
                state = out_ref[r, :, sl].astype(F32) * column(2 * hb + h)
                held = jnp.sum(state * k_h, axis=0, keepdims=True)
                delta = beta_ref[0, 0, tok * hb + h] * (v_t[h:h + 1] - held)
                state = state + k_h * delta
                outs.append(jnp.sum(state * column(h), axis=0,
                                    keepdims=True))
                out_ref[r, :, sl] = state.astype(out_ref.dtype)
            o_ref[tok] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, adv_ref[first + r], one_column, 0)
        return 0

    jax.lax.fori_loop(0, rb, one_row, 0)


@functools.partial(jax.jit, static_argnames=("hb", "rb", "interpret"))
def _kda_call(q, k, v, g, beta, state, start, adv, fresh, *, hb, rb,
              interpret):
    """The kernel's `pallas_call` at one tiling; q, k, v, g `[tokens, H,
    128]` float32, beta `[tokens, H]` float32, state `[rows, 128, H * 128]`.
    Jitted at module level with its integers static, so the layers of one
    traced step share one jaxpr and the lowered module holds one kernel
    body for them."""
    tokens, heads, d = q.shape
    rows = state.shape[0]
    groups, blocks = heads // hb, -(-rows // rb)
    # a ragged last block reads the scalars of rows that are not there
    pad = blocks * rb - rows
    start, adv, fresh = (jnp.pad(a, (0, pad)) for a in (start, adv, fresh))
    # a head block's betas, one scalar a (token, head), in SMEM
    beta = beta.reshape(tokens, groups, hb).transpose(1, 0, 2).reshape(
        groups, 1, tokens * hb)
    wide = pl.BlockSpec((tokens, hb, d), lambda g_, r, *_: (0, g_, 0))
    tile = pl.BlockSpec((rb, d, hb * LANES), lambda g_, r, *_: (r, 0, g_))
    return pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(groups, blocks),
            in_specs=[pl.BlockSpec((1, 1, tokens * hb),
                                   lambda g_, r, *_: (g_, 0, 0),
                                   memory_space=pltpu.SMEM),
                      wide, wide, wide, wide, tile],
            out_specs=[wide, tile],
            scratch_shapes=[pltpu.VMEM((d, LANES), F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        # as `ssm_update`: the new state takes the state's buffer, so where
        # the caller donates the state (the serving step its pool) nothing
        # is copied round the kernel
        input_output_aliases={8: 1},
        interpret=interpret,
        name=KERNEL,
    )(start, adv, fresh, beta, q, k, g, v, state)


def _scan_columns(q, k, v, g, beta, state, start, adv, fresh, columns):
    """The kernel's arithmetic, column after column, in `jax.numpy`: each
    row's columns gathered out of the token rows, and o scattered back."""
    tokens, heads = beta.shape
    rows, d_k = state.shape[:2]
    d_v = state.shape[2] // heads
    t = jnp.arange(columns, dtype=jnp.int32)
    live = t[None, :] < adv[:, None]                       # [rows, columns]
    at = jnp.where(live, start[:, None] + t[None, :], tokens)

    def of_rows(arr, width):  # [tokens, H * w] -> [columns, rows, H, w]
        arr = jnp.pad(arr.astype(F32), ((0, 1), (0, 0)))
        return jnp.swapaxes(arr[at], 0, 1).reshape(columns, rows, heads,
                                                   width)

    def one_column(s, col):               # s [rows, d_k, H, d_v]
        q_t, k_t, v_t, g_t, b_t = col
        # a dead column's inputs are zeros: decay 1, k = 0, nothing written
        k_t = jnp.swapaxes(k_t, 1, 2)[..., None]           # [rows, d_k, H, 1]
        s = s * jnp.exp(jnp.swapaxes(g_t, 1, 2))[..., None]
        held = jnp.sum(s * k_t, axis=1)                    # [rows, H, d_v]
        s = s + k_t * (b_t * (v_t - held))[:, None]
        return s, jnp.sum(s * jnp.swapaxes(q_t, 1, 2)[..., None], axis=1)

    s0 = jnp.where(fresh[:, None, None] != 0, 0.0, state.astype(F32))
    s, os_ = jax.lax.scan(
        one_column, s0.reshape(rows, d_k, heads, d_v),
        (of_rows(q, d_k), of_rows(k, d_k), of_rows(v, d_v), of_rows(g, d_k),
         of_rows(beta, 1)))
    o = jnp.zeros((tokens + 1, heads * d_v), F32).at[at].set(
        jnp.swapaxes(os_, 0, 1).reshape(rows, columns, heads * d_v))[:tokens]
    return o, s.reshape(state.shape).astype(state.dtype)


def _head_block(heads: int) -> int:
    """Heads a grid step holds: whole sublane tiles of them, at most
    `HEAD_BLOCK`; every head where they do not fill one."""
    for hb in range(min(heads, HEAD_BLOCK) // 8 * 8, 0, -8):
        if heads % hb == 0:
            return hb
    return heads


def kda_update(q, k, v, g, beta, state, start, adv, fresh=None, *,
               columns: int, impl: str = None):
    """q, k `[tokens, H * d_k]` (normalised and scaled by the layer), v
    `[tokens, H * d_v]`, g `[tokens, H * d_k]` float32 log-decays (<= 0),
    beta `[tokens, H]`; state `[rows, d_k, H * d_v]` float32; start `[rows]`
    the token row of each row's first column, adv `[rows]` how many
    consecutive token rows from there are its live columns (at most
    `columns`, a static bound; rows' runs do not overlap); fresh `[rows]`
    rows that start from zero (None: none). Returns (o `[tokens, H * d_v]`
    float32, zero on a token row that is no row's live column; the state
    after each row's `adv` columns, in `state.dtype`).
    impl: None = the scan on the CPU, the kernel on a TPU; or name "scan" /
    "pallas" (on the CPU the kernel runs interpreted: the parity test,
    which also narrows `HEAD_BLOCK` and `ROWS_BLOCK` so that a small call
    has several blocks of each)."""
    tokens, heads = beta.shape
    rows, d_k = state.shape[:2]
    d_v = state.shape[2] // heads
    if q.shape != (tokens, heads * d_k) or k.shape != q.shape \
            or g.shape != q.shape or v.shape != (tokens, heads * d_v) \
            or state.shape != (rows, d_k, heads * d_v) \
            or start.shape != (rows,) or adv.shape != (rows,):
        raise ValueError(f"kda_update: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}, g {g.shape}, beta {beta.shape}, state "
                         f"{state.shape}, start {start.shape}, adv "
                         f"{adv.shape}")
    if impl is None:
        impl = "scan" if pallas_mode.platform() == "cpu" else "pallas"
    if impl not in ("scan", "pallas"):
        raise ValueError(f'impl must be "scan" or "pallas", got {impl!r}')
    start, adv = start.astype(jnp.int32), adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    if impl == "scan":
        pallas_mode.count(KERNEL, "scan")
        return _scan_columns(q, k, v, g, beta, state, start, adv, fresh,
                             columns)
    hb = _head_block(heads)
    if d_k != LANES or d_v != LANES or 3 * hb > LANES:
        raise ValueError(
            f"kda_update kernel: a head's keys and values must each fill "
            f"one {LANES}-lane register (got {d_k}, {d_v}), and the q, k "
            f"and decay tiles of a block of {hb} heads one transpose")
    rb = min(rows, ROWS_BLOCK)
    pallas_mode.note_tiling(KERNEL, grid=(heads // hb, -(-rows // rb)),
                            columns=columns, state_tile=(rb, d_k, hb * d_v))

    def tiles(a):
        return a.astype(F32).reshape(tokens, heads, -1)

    o, state = _kda_call(
        tiles(q), tiles(k), tiles(v), tiles(g), beta.astype(F32), state,
        start, adv, fresh, hb=hb, rb=rb,
        interpret=pallas_mode.interpret(KERNEL))
    return o.reshape(tokens, heads * d_v), state


def kda_update_rows(q, k, v, g, beta, state, adv=None, fresh=None,
                    impl: str = None):
    """`kda_update` for columns laid out `[rows, T, .]` (one-shot
    `generate()`, an unpacked step, a whole sequence from zero state): q,
    k, g `[rows, T, H * d_k]`, v `[rows, T, H * d_v]`, beta `[rows, T, H]`,
    adv `[rows]` live columns of each row (None: all T). Returns (o
    `[rows, T, H * d_v]` float32, the new state)."""
    rows, T, _ = q.shape
    adv = jnp.full((rows,), T, jnp.int32) if adv is None \
        else adv.astype(jnp.int32)
    fresh = jnp.zeros((rows,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    step = max(1, min(T, MAX_TOKENS // rows))
    start = jnp.arange(rows, dtype=jnp.int32) * step

    def call(chunk, s, n, new):
        o, s = kda_update(
            *(a.reshape(rows * step, a.shape[2]) for a in chunk), s, start,
            n, new, columns=step, impl=impl)
        return o.reshape(rows, step, -1), s

    if T == step:
        return call((q, k, v, g, beta), state, adv, fresh)
    pad = -T % step

    def chunks(arr):
        arr = jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
        return jnp.swapaxes(arr.reshape(rows, -1, step, arr.shape[2]), 0, 1)

    def one_chunk(carry, chunk):
        s, left, new = carry
        o_k, s = call(chunk, s, jnp.clip(left, 0, step), new)
        return (s, left - step, jnp.zeros_like(new)), o_k

    (state, _, _), os_ = jax.lax.scan(
        one_chunk, (state, adv, fresh),
        tuple(chunks(arr) for arr in (q, k, v, g, beta)))
    o = jnp.swapaxes(os_, 0, 1).reshape(rows, T + pad, -1)
    return o[:, :T], state
