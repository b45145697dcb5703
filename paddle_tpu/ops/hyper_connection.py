"""The two halves of a manifold-constrained hyper-connection (mHC: "mHC:
Manifold-Constrained Hyper-Connections", arXiv:2512.24880, on
"Hyper-Connections", arXiv:2409.19606) round one sublayer `F` of a decoder
whose residual state is n streams a token, `X [rows, n * C]` (stream j the
columns `j C .. (j + 1) C`: the streams side by side and not `[rows, n, C]`,
whose second-minor dimension of 4 would be stored in tiles of 16 rows).

    x'  = X / sqrt(mean(X^2) + norm_eps)                 float32, all n C
    [h_pre | h_post | h_res] = x' phi                    n | n | n^2
    H_pre  = sigmoid(a_pre h_pre + b_pre)                [n]
    H_post = 2 sigmoid(a_post h_post + b_post)           [n]
    M^0    = exp(clip(a_res mat(h_res) + b_res, -clamp, clamp))   [n, n]
    M^t    = T_r(T_c(M^(t-1))), t = 1..iters; T_c: every column over (its
             sum + eps), T_r: every row likewise;  H_res = M^iters
    u      = sum_j H_pre[j] X[j]                         `hc_pre`'s result
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(u)     `hc_post`'s

`mat` is row-major (`h_res[n i + j]` is entry i, j); every coefficient is
float32 whatever `X`'s type; `phi [n C, n^2 + 2 n]`, `bias [n^2 + 2 n]` and
`alpha [3]` (pre, post, res) are one connection's parameters
(`nn.layer.hyper_connection.HyperConnection`).

Each half has two forms of the same sum. The plain one (`_pre_plain`,
`_post_plain`: `jax.numpy`) is the CPU's path and the kernels' parity
oracle. On a TPU each is one Mosaic kernel, found by its name in a trace:

`hc_pre`   a grid step holds 128 rows of `X` whole in VMEM and reads them
           from HBM once: the sum of squares and `phi^T X^T` (so that the
           n^2 + 2 n coefficients of a token lie down a column and the
           tokens along the lanes: the Sinkhorn passes are elementwise
           products of `[1, 128]` rows, no reduction, no `[rows, 4, 4]`
           array with 4 of 128 lanes in use) in one pass over the tile's
           columns, the coefficients, then `u` from the tile in a second
           pass. The coefficients leave transposed, a token a row of 128
           lanes (`[rows, 128]` float32: H_pre, H_post, H_res in the first
           n^2 + 2 n), which is how `hc_post` wants them.
`hc_post`  reads `X`, `F(u)` and the coefficients once and writes `X'` over
           `X` (`input_output_aliases`): inside a step that is donated its
           operands nothing is copied round the call.

Each `pallas_call` sits under one module-level `jax.jit` with static
integers (`_pre_call`, `_post_call`), so a model's every connection lowers
one body. Which form runs is `pallas_mode`'s decision by platform and the
shapes' (a stream that is not whole 128-lane registers keeps the plain form
and says so through `note_reference`); no argument chooses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

PRE_KERNEL = "hc_pre"
POST_KERNEL = "hc_post"
F32 = jnp.float32
LANES = 128
# tokens a grid step holds (the coefficients' lanes)
ROWS = 128
_HIGHEST = lax.Precision.HIGHEST


def _spread(alpha, n: int):
    """alpha `[3]` (pre, post, res) -> a factor a coefficient
    `[n^2 + 2 n]`."""
    a = alpha.astype(F32)
    return jnp.concatenate([jnp.broadcast_to(a[0], (n,)),
                            jnp.broadcast_to(a[1], (n,)),
                            jnp.broadcast_to(a[2], (n * n,))])


def coefficients(x, phi, bias, alpha, *, n: int, iters: int, eps: float,
                 clamp: float, norm_eps: float):
    """x `[R, n C]` -> (H_pre `[R, n]`, H_post `[R, n]`, H_res `[R, n, n]`),
    float32: the module docstring's first six lines in `jax.numpy`."""
    x = x.astype(F32)
    r = lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + norm_eps)
    h = jnp.dot(x, phi.astype(F32), precision=_HIGHEST) * r
    logit = h * _spread(alpha, n) + bias.astype(F32)
    m = jnp.exp(jnp.clip(logit[:, 2 * n:], -clamp, clamp)).reshape(-1, n, n)

    def one(_, m):           # T_c then T_r: entry (i, j) is m[:, i, j]
        m = m * (1.0 / (jnp.sum(m, 1, keepdims=True) + eps))
        return m * (1.0 / (jnp.sum(m, 2, keepdims=True) + eps))

    m = lax.fori_loop(0, iters, one, m)
    return (jax.nn.sigmoid(logit[:, :n]),
            2.0 * jax.nn.sigmoid(logit[:, n:2 * n]), m)


def _streams(x, n: int):
    C = x.shape[-1] // n
    return [x[:, j * C:(j + 1) * C].astype(F32) for j in range(n)]


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "norm_eps"))
def _pre_plain(x, phi, bias, alpha, **kw):
    pre, post, res = coefficients(x, phi, bias, alpha, **kw)
    xs = _streams(x, kw["n"])
    u = pre[:, 0:1] * xs[0]
    for j in range(1, kw["n"]):
        u = u + pre[:, j:j + 1] * xs[j]
    return u.astype(x.dtype), post, res


@jax.jit
def _post_plain(x, y, post, res):
    n = post.shape[-1]
    xs, y = _streams(x, n), y.astype(F32)
    out = []
    for i in range(n):
        acc = post[:, i:i + 1] * y
        for j in range(n):
            acc = acc + res[:, i, j:j + 1] * xs[j]
        out.append(acc.astype(x.dtype))
    return jnp.concatenate(out, -1)


# ---- the kernels ----

def _chunk(C: int, most: int) -> int:
    """Lanes of a stream one pass of a kernel's column loop takes."""
    return next(c for c in (most, 256, LANES) if c <= most and C % c == 0)


def _pre_kernel(x_ref, phit_ref, ab_ref, u_ref, coef_ref, logit_ref,
                stage_ref, *, n, C, iters, eps, clamp, norm_eps):
    """x `[ROWS, n C]`, phit `[K, n C]` (phi transposed, K = n^2 + 2 n in
    whole sublane tiles), ab `[K, 128]` (column 0 the coefficient's alpha,
    column 1 its bias) -> u `[ROWS, C]`, coef `[ROWS, 128]`. `logit_ref`
    `[K, ROWS]` and `stage_ref` `[128, ROWS]` are scratch: rows are read
    and written one at a time."""
    rows, nC = x_ref.shape
    cols = _chunk(C, 512)
    acc = jnp.zeros(logit_ref.shape, F32)
    sq = jnp.zeros((rows, LANES), F32)
    for k in range(nC // cols):
        xc = x_ref[:, k * cols:(k + 1) * cols].astype(F32)
        acc = acc + lax.dot_general(
            phit_ref[:, k * cols:(k + 1) * cols], xc,
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=F32)
        for lane in range(0, cols, LANES):
            part = xc[:, lane:lane + LANES]
            sq = sq + part * part
    ss = jnp.sum(sq.T, axis=0, keepdims=True)                  # [1, ROWS]
    r = lax.rsqrt(ss * (1.0 / nC) + norm_eps)
    logit_ref[...] = acc * r * ab_ref[:, 0:1] + ab_ref[:, 1:2]

    def row(k):
        return logit_ref[k:k + 1, :]

    m = tuple(jnp.exp(jnp.clip(row(2 * n + k), -clamp, clamp))
              for k in range(n * n))

    def columns(m):      # entry (i, j) is m[n i + j]; its column's sum
        sums = [sum(m[n * i + j] for i in range(n)) for j in range(n)]
        return tuple(sums[k % n] for k in range(n * n))

    def lines(m):
        sums = [sum(m[n * i + j] for j in range(n)) for i in range(n)]
        return tuple(sums[k // n] for k in range(n * n))

    def one(_, m):
        m = tuple(a * (1.0 / (s + eps)) for a, s in zip(m, columns(m)))
        return tuple(a * (1.0 / (s + eps)) for a, s in zip(m, lines(m)))

    m = lax.fori_loop(0, iters, one, m)
    stage_ref[...] = jnp.zeros(stage_ref.shape, F32)
    for j in range(n):
        stage_ref[j:j + 1, :] = jax.nn.sigmoid(row(j))
        stage_ref[n + j:n + j + 1, :] = 2.0 * jax.nn.sigmoid(row(n + j))
    for k in range(n * n):
        stage_ref[2 * n + k:2 * n + k + 1, :] = m[k]
    coef = stage_ref[...].T                                     # [ROWS, 128]
    coef_ref[...] = coef
    for c in range(0, C, cols):
        u = coef[:, 0:1] * x_ref[:, c:c + cols].astype(F32)
        for j in range(1, n):
            u = u + coef[:, j:j + 1] \
                * x_ref[:, j * C + c:j * C + c + cols].astype(F32)
        u_ref[:, c:c + cols] = u.astype(u_ref.dtype)


_PARAMS = dict(dimension_semantics=("arbitrary",), vmem_limit_bytes=96 << 20)


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "norm_eps", "interpret"))
def _pre_call(x, phit, ab, *, n, iters, eps, clamp, norm_eps, interpret):
    R, nC = x.shape
    C = nC // n
    tile = lambda width: pl.BlockSpec((ROWS, width), lambda r: (r, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda r: (0, 0))
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, C=C, iters=iters, eps=eps,
                          clamp=clamp, norm_eps=norm_eps),
        out_shape=(jax.ShapeDtypeStruct((R, C), x.dtype),
                   jax.ShapeDtypeStruct((R, LANES), F32)),
        grid=(R // ROWS,),
        in_specs=[tile(nC), whole(phit), whole(ab)],
        out_specs=[tile(C), tile(LANES)],
        scratch_shapes=[pltpu.VMEM((phit.shape[0], ROWS), F32),
                        pltpu.VMEM((LANES, ROWS), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name=PRE_KERNEL,
    )(x, phit, ab)


def _post_kernel(x_ref, y_ref, coef_ref, out_ref, *, n, C):
    """x `[ROWS, n C]`, y `[ROWS, C]`, coef `[ROWS, 128]` as `hc_pre` left
    it -> X' `[ROWS, n C]`, in the order of `_post_plain`'s sum."""
    cols = _chunk(C, 256)
    coef = coef_ref[...]
    for c in range(0, C, cols):
        y = y_ref[:, c:c + cols].astype(F32)
        xs = [x_ref[:, j * C + c:j * C + c + cols].astype(F32)
              for j in range(n)]
        for i in range(n):
            acc = coef[:, n + i:n + i + 1] * y
            for j in range(n):
                k = 2 * n + n * i + j
                acc = acc + coef[:, k:k + 1] * xs[j]
            out_ref[:, i * C + c:i * C + c + cols] = \
                acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _post_call(x, y, coef, *, n, interpret):
    R, nC = x.shape
    tile = lambda width: pl.BlockSpec((ROWS, width), lambda r: (r, 0))
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n, C=nC // n),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(R // ROWS,),
        in_specs=[tile(nC), tile(nC // n), tile(LANES)],
        out_specs=tile(nC),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name=POST_KERNEL,
    )(x, y, coef)


def _whole_tiles(a):
    """`a [R, .]` with R brought up to whole grid steps."""
    return jnp.pad(a, ((0, -a.shape[0] % ROWS), (0, 0)))


def pre_kernel(x, phi, bias, alpha, *, n, iters, eps, clamp, norm_eps):
    """`hc_pre` through its kernel (interpreted on the CPU), x `[R, n C]`."""
    R, nC = x.shape
    K = n * n + 2 * n
    pad = -K % 8
    pallas_mode.note_tiling(PRE_KERNEL, grid=(-(-R // ROWS),),
                            tile=(ROWS, nC), coefficients=K, passes=iters)
    phit = jnp.pad(phi.astype(F32).T, ((0, pad), (0, 0)))
    ab = jnp.stack([_spread(alpha, n), bias.astype(F32)], 1)
    ab = jnp.pad(ab, ((0, pad), (0, LANES - 2)))
    u, coef = _pre_call(
        _whole_tiles(x), phit, ab, n=n, iters=iters, eps=eps, clamp=clamp,
        norm_eps=norm_eps, interpret=pallas_mode.interpret(PRE_KERNEL))
    return (u[:R], coef[:R, n:2 * n],
            coef[:R, 2 * n:K].reshape(R, n, n))


def post_kernel(x, y, post, res):
    """`hc_post` through its kernel (interpreted on the CPU)."""
    R, n = post.shape
    pallas_mode.note_tiling(POST_KERNEL, grid=(-(-R // ROWS),),
                            tile=(ROWS, x.shape[1]))
    coef = jnp.concatenate([jnp.zeros((R, n), F32), post,
                            res.reshape(R, n * n)], -1)
    coef = jnp.pad(coef, ((0, 0), (0, LANES - coef.shape[1])))
    out = _post_call(
        _whole_tiles(x), _whole_tiles(y), _whole_tiles(coef), n=n,
        interpret=pallas_mode.interpret(POST_KERNEL))
    return out[:R]


def _plain_here(kernel: str, x, n: int) -> bool:
    """Whether this trace takes the plain form of `kernel`: the CPU does,
    and a stream that is not whole lane registers."""
    if pallas_mode.platform() == "cpu":
        pallas_mode.count(kernel, "reference")
        return True
    if (x.shape[-1] // n) % LANES:
        pallas_mode.note_reference(
            kernel, "a stream is not whole 128-lane registers",
            tuple(x.shape), n)
        return True
    return False


def hc_pre(x, phi, bias, alpha, *, n: int, iters: int = 20,
           eps: float = 1e-6, clamp: float = 30.0, norm_eps: float = 1e-6):
    """x `[..., n C]` -> (u `[..., C]` in x's type: the sublayer's input;
    H_post `[..., n]` and H_res `[..., n, n]`, float32, for `hc_post`)."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    kw = dict(n=n, iters=int(iters), eps=float(eps), clamp=float(clamp),
              norm_eps=float(norm_eps))
    form = _pre_plain if _plain_here(PRE_KERNEL, x, n) else pre_kernel
    u, post, res = form(flat, phi, bias, alpha, **kw)
    return (u.reshape(lead + (-1,)), post.reshape(lead + (n,)),
            res.reshape(lead + (n, n)))


def hc_post(x, y, post, res):
    """x `[..., n C]`, y = F(u) `[..., C]`, `hc_pre`'s two coefficient
    arrays -> X' `[..., n C]` in x's type."""
    n = post.shape[-1]
    form = _post_plain if _plain_here(POST_KERNEL, x, n) else post_kernel
    out = form(x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]),
               post.reshape(-1, n), res.reshape(-1, n, n))
    return out.reshape(x.shape)
