"""The selective state recurrence of a Mamba-2 layer over the columns of one
step, with the state carried between steps, and the causal depthwise
convolution in front of it with its own carried columns.

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
  state tile is read once, held in registers 128 lanes at a time while the
  row's live columns are applied one after the other (a loop of `adv`
  trips: a decode row costs one column, not T), and written once. The
  per-head scalars ride in SMEM. State in its storage type between steps
  (bfloat16 in a bfloat16 model), float32 inside;
- on the CPU the same arithmetic in `jax.numpy` (`lax.scan` over the
  columns), counted `ssm_update/scan`.
"""
from __future__ import annotations

import functools

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
F32 = jnp.float32


def _kernel(adv_ref, fresh_ref, da_ref, dt_ref, x_ref, b_ref, c_ref, s_ref,
            y_ref, out_ref, bcol, ccol, *, heads_per_chunk, head_dim):
    row, block = pl.program_id(0), pl.program_id(1)
    adv = adv_ref[row]
    T, N = b_ref.shape[1], b_ref.shape[2]

    # B_t and C_t as columns along the sublanes, once a row: every lane
    # block of the row (and every head: one group) uses the same ones
    @pl.when(block == 0)
    def _columns():
        for t in range(T):
            @pl.when(t < adv)
            def _():
                for ref, col in ((b_ref, bcol), (c_ref, ccol)):
                    col[t] = jnp.broadcast_to(
                        ref[0, t:t + 1, :].astype(F32).reshape(N, 1),
                        (N, LANES))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    column = jax.lax.broadcasted_iota(jnp.int32, (T, LANES), 0)
    keep = fresh_ref[row] == 0

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
        xs = x_ref[0, :, sl].astype(F32)                       # [T, 128]

        def one_column(t, carry, chunk=chunk, xs=xs):
            state, ys = carry
            here = column == t
            x_t = jnp.sum(jnp.where(here, xs, 0.0), axis=0, keepdims=True)
            state = state * per_head(da_ref, t, chunk) \
                + bcol[t] * (x_t * per_head(dt_ref, t, chunk))
            y_t = jnp.sum(state * ccol[t], axis=0, keepdims=True)
            return state, jnp.where(here, y_t, ys)

        state, ys = jax.lax.fori_loop(
            0, adv, one_column, (state, jnp.zeros((T, LANES), F32)))
        y_ref[0, :, sl] = ys.astype(y_ref.dtype)
        out_ref[0, :, sl] = state.astype(out_ref.dtype)


def _lane_block(lanes: int) -> int:
    if lanes <= LANE_BLOCK:
        return lanes
    for b in range(LANE_BLOCK, LANES - 1, -LANES):
        if lanes % b == 0:
            return b
    raise ValueError(f"ssm_update: {lanes} lanes have no block that is a "
                     f"multiple of {LANES} and at most {LANE_BLOCK}")


def _mosaic(x, dt_live, decay, b, c, state, adv, fresh):
    rows, T, lanes = x.shape
    heads, N = dt_live.shape[2], state.shape[1]
    head_dim = lanes // heads
    if lanes % LANES or LANES % head_dim or N % 8:
        raise ValueError(
            f"ssm_update kernel: heads x head_dim {heads} x {head_dim} must "
            f"fill whole {LANES}-lane registers with whole heads, and the "
            f"state's {N} channels whole sublane tiles")
    lb = _lane_block(lanes)
    blocks, heads_per_block = lanes // lb, lb // head_dim
    pallas_mode.note_tiling(KERNEL, grid=(rows, blocks), columns=T,
                            state_tile=(N, lb))

    def scalars(a):       # [rows, T, H] -> [rows, blocks, T, heads a block]
        return a.reshape(rows, T, blocks, heads_per_block).transpose(
            0, 2, 1, 3)

    smem = pl.BlockSpec((1, 1, T, heads_per_block),
                        lambda r, g, *_: (r, g, 0, 0),
                        memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((1, T, lb), lambda r, g, *_: (r, 0, g))
    narrow = pl.BlockSpec((1, T, N), lambda r, g, *_: (r, 0, 0))
    tile = pl.BlockSpec((1, N, lb), lambda r, g, *_: (r, 0, g))
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, heads_per_chunk=LANES // head_dim,
                          head_dim=head_dim),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows, blocks),
            in_specs=[smem, smem, wide, narrow, narrow, tile],
            out_specs=[wide, tile],
            scratch_shapes=[pltpu.VMEM((T, N, LANES), F32),
                            pltpu.VMEM((T, N, LANES), F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        # the new state takes the state's buffer: a grid step reads and
        # writes its own tile alone, so where the caller donates the state
        # (the serving step its pool) nothing is copied round the kernel
        input_output_aliases={7: 1},
        interpret=pallas_mode.interpret(KERNEL),
        name=KERNEL,
    )(adv, fresh, scalars(decay), scalars(dt_live), x, b, c, state)
    return y, new_state


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
    decay = jnp.exp(dt_live * a.astype(F32))         # 1 on a dead column
    if impl == "scan":
        pallas_mode.count(KERNEL, "scan")
        return _scan(x, dt_live, decay, b, c, state, fresh)
    if T <= MAX_COLUMNS:
        return _mosaic(x, dt_live, decay, b, c, state, adv, fresh)
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
        tuple(chunks(arr) for arr in (x, dt_live, decay, b, c)))
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
