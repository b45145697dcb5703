"""Mamba-1's recurrence (`ops/ssm.py::selective_scan`) over token rows: its
`jax.numpy` path against a loop over positions written from the equations,
and its kernel (interpreted on the CPU) against the `jax.numpy` path: runs
of live columns that start at any token row, `adv` below the bound, an idle
row, rows that start `fresh`, token rows no row owns, a float32 state under
bfloat16 columns, several rows a grid step with a last block that is ragged,
several lane blocks; the `[rows, T]` form with a sequence longer than one
call's tokens; and the conv over token rows against the conv over `[rows,
T]`. Tiny widths: 256 channels of 16 state elements; no interpreted call
takes more than a few dozen grid steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_mode, ssm
from paddle_tpu.ops.attention import token_pack

N = 16


def _inputs(rows, tokens, channels=256, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(x=f(tokens, channels).astype(dtype),
                dt=jax.nn.softplus(f(tokens, channels) - 1.0),
                a=-jnp.exp(jnp.asarray(rng.uniform(-1.0, 2.5, (N, channels)),
                                       jnp.float32)),
                b=f(tokens, N), c=f(tokens, N), state=f(rows, N, channels))


def _args(k, start, adv, fresh=None):
    return (k["x"], k["dt"], k["a"], k["b"], k["c"], k["state"],
            jnp.asarray(start, jnp.int32), jnp.asarray(adv, jnp.int32),
            None if fresh is None else jnp.asarray(fresh, jnp.int32))


def _naive(k, start, adv, fresh):
    """h[c, n] as the equations have it (Hugging Face's layout), one
    position after the other in numpy, float64."""
    x, dt, b, c = (np.asarray(k[n].astype(jnp.float32), np.float64)
                   for n in ("x", "dt", "b", "c"))
    a = np.asarray(k["a"], np.float64).T                      # [channels, N]
    tokens, channels = x.shape
    ys = np.zeros((tokens, channels))
    states = np.zeros((len(adv), channels, N))
    for r in range(len(adv)):
        h = np.zeros((channels, N)) if fresh[r] \
            else np.asarray(k["state"][r], np.float64).T
        for t in range(start[r], start[r] + adv[r]):
            h = np.exp(dt[t][:, None] * a) * h \
                + (dt[t] * x[t])[:, None] * b[t][None, :]
            ys[t] = h @ c[t]
        states[r] = h
    return ys, np.swapaxes(states, 1, 2)


# (tokens, start, adv, fresh, the bound on a row's columns)
CASES = {
    "a packed step: decode rows, a chunk, an idle slot, unowned rows":
        (24, [0, 1, 2, 2, 13], [1, 1, 0, 11, 1], [0, 0, 0, 1, 0], 16),
    "runs at any offset, with gaps": (40, [3, 17, 29], [9, 5, 11],
                                      [0, 0, 0], 12),
    "fresh rows start from zero": (12, [0, 4, 8, 8], [4, 2, 0, 4],
                                   [1, 0, 1, 1], 4),
    "a decode step": (5, [0, 1, 2, 2, 3], [1, 1, 0, 1, 1], [0, 1, 0, 0, 0],
                      1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_scan_is_the_recurrence_position_by_position(case):
    tokens, start, adv, fresh, columns = CASES[case]
    k = _inputs(len(adv), tokens)
    y, s = ssm.selective_scan(*_args(k, start, adv, fresh), columns=columns,
                              impl="scan")
    want_y, want_s = _naive(k, start, adv, fresh)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)
    assert y.dtype == jnp.float32 and s.dtype == jnp.float32


# (case, lane block, rows a grid step, dtype of the columns)
KERNEL_CASES = {
    "one block": (sorted(CASES)[0], 256, 32, jnp.float32),
    "two lane blocks, rows in twos, ragged last block":
        (sorted(CASES)[0], 128, 2, jnp.float32),
    "a row a grid step": (sorted(CASES)[3], 256, 1, jnp.float32),
    "float32 state, bfloat16 columns": (sorted(CASES)[3], 128, 2,
                                        jnp.bfloat16),
    "fresh rows, rows in threes": (sorted(CASES)[2], 128, 3, jnp.float32),
    "a decode step": (sorted(CASES)[1], 128, 4, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_the_kernel_equals_its_scan(name, monkeypatch):
    case, lane_block, rows_block, dtype = KERNEL_CASES[name]
    tokens, start, adv, fresh, columns = CASES[case]
    rows = len(adv)
    k = _inputs(rows, tokens, dtype=dtype)
    args = _args(k, start, adv, fresh)
    y0, s0 = ssm.selective_scan(*args, columns=columns, impl="scan")
    monkeypatch.setattr(ssm, "SCAN_LANE_BLOCK", lane_block)
    monkeypatch.setattr(ssm, "ROWS_BLOCK", rows_block)
    pallas_mode.KERNEL_TILINGS.clear()
    before = pallas_mode.KERNEL_TRACES[("selective_scan", "interpret")]
    y1, s1 = ssm.selective_scan(*args, columns=columns, impl="pallas")
    assert pallas_mode.KERNEL_TRACES[("selective_scan", "interpret")] \
        == before + 1
    tiling = dict(next(t for (kernel, t) in pallas_mode.KERNEL_TILINGS
                       if kernel == "selective_scan"))
    rb = min(rows, rows_block)
    assert tiling == {"state_tile": (rb, N, lane_block), "columns": columns,
                      "grid": (256 // lane_block, -(-rows // rb))}
    # the same float32 arithmetic on the same float32 values
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-5)
    assert y1.shape == (tokens, 256) and s1.dtype == jnp.float32


@pytest.mark.parametrize("impl,most", [("scan", 4096), ("scan", 24),
                                       ("pallas", 4096), ("pallas", 24)],
                         ids=["scan", "scan in chunks", "kernel",
                              "kernel in chunks"])
def test_rows_of_columns_longer_than_one_calls_tokens(impl, most,
                                                      monkeypatch):
    """`[rows, T]`: one call where the tokens fit, else chunks of columns
    with the state carried, `adv` and `fresh` spent as the chunks go."""
    rows, T = 3, 21
    adv, fresh = [21, 9, 0], [1, 0, 0]
    k = _inputs(rows, rows * T)
    start = [r * T for r in range(rows)]
    want_y, want_s = _naive(k, start, adv, fresh)
    monkeypatch.setattr(ssm, "MAX_TOKENS", most)      # 24 // 3 = 8 columns
    shaped = {n: k[n].reshape(rows, T, -1) for n in ("x", "dt", "b", "c")}
    y, s = ssm.selective_scan_rows(
        shaped["x"], shaped["dt"], k["a"], shaped["b"], shaped["c"],
        k["state"], jnp.asarray(adv), jnp.asarray(fresh), impl=impl)
    np.testing.assert_allclose(np.asarray(y).reshape(rows * T, -1), want_y,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)
    # no adv, no fresh: every column, every row carried
    y, s = ssm.selective_scan_rows(
        shaped["x"], shaped["dt"], k["a"], shaped["b"], shaped["c"],
        k["state"], impl=impl)
    want_y, want_s = _naive(k, start, [T] * rows, [0] * rows)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)


def test_the_kernel_writes_the_new_state_into_the_states_buffer():
    """The pool's state is donated to the serving step: the kernel must
    alias it to its result, or the step copies every row behind it."""
    k = _inputs(3, 12, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda *a: ssm.selective_scan(*a, columns=4, impl="pallas"))(
            *_args(k, [0, 4, 8], [4, 4, 4], [0, 0, 0]))
    text = str(jaxpr)
    assert "selective_scan" in text
    assert "input_output_aliases=((8, 1),)" in text


def test_the_layers_of_a_step_share_one_kernel_body():
    """The `pallas_call` sits under one jitted entry with static integers:
    two call sites that agree on shapes trace it once."""
    k = _inputs(2, 6)

    def two_layers(*a):
        y, s = ssm.selective_scan(*a, columns=3, impl="pallas")
        return ssm.selective_scan(y, *a[1:5], s, *a[6:], columns=3,
                                  impl="pallas")

    jaxpr = jax.make_jaxpr(two_layers)(*_args(k, [0, 3], [3, 1], [0, 0]))
    calls = [e for e in jaxpr.jaxpr.eqns if e.params.get("name")
             == "_scan_call"]
    assert len(calls) == 2
    assert calls[0].params["jaxpr"] is calls[1].params["jaxpr"]


def test_the_lane_block_follows_the_lanes_and_the_tokens():
    # the cell's step: 512 tokens, 5,120 channels: four blocks of 1,280
    assert ssm._scan_lane_block(5120, 512) == 1280
    assert ssm._scan_lane_block(256, 512) == 256
    assert ssm._scan_lane_block(1536, 512) == 768
    # more tokens: their three float32 blocks narrow the lanes to fit
    narrow = ssm._scan_lane_block(5120, 4096)
    assert narrow == 128
    assert 2 * 3 * 4 * 4096 * narrow <= ssm._SCAN_TOKEN_BUDGET
    assert 2 * 3 * 4 * 512 * 1280 <= ssm._SCAN_TOKEN_BUDGET


def test_what_cannot_be_tiled_or_does_not_fit_is_refused():
    k = _inputs(2, 8, channels=192)            # 192 lanes: no whole register
    args = _args(k, [0, 4], [4, 4])
    with pytest.raises(ValueError, match="whole 128-lane registers"):
        ssm.selective_scan(*args, columns=4, impl="pallas")
    ssm.selective_scan(*args, columns=4, impl="scan")      # the scan takes it
    k = _inputs(2, 8)
    args = _args(k, [0, 4], [4, 4])
    with pytest.raises(ValueError, match="selective_scan: x"):
        ssm.selective_scan(k["x"], k["dt"][:2], *args[2:], columns=4)
    with pytest.raises(ValueError, match="selective_scan: x"):
        ssm.selective_scan(k["x"], k["dt"], k["a"].T, *args[3:], columns=4)
    with pytest.raises(ValueError, match='"scan" or "pallas"'):
        ssm.selective_scan(*args, columns=4, impl="mosaic")


def test_the_name_does_not_hold_the_other_kernels():
    """`trace/reduce.py::op_time_s` finds a kernel by a substring of its
    instruction's name."""
    assert ssm.KERNEL not in ssm.SCAN_KERNEL \
        and ssm.SCAN_KERNEL not in ssm.KERNEL


@pytest.mark.parametrize("impl,dtype", [("scan", jnp.float32),
                                        ("pallas", jnp.float32),
                                        ("pallas", jnp.bfloat16)])
@pytest.mark.parametrize("adv,pos", [
    ([4, 1, 0, 3, 1], [0, 7, 0, 0, 12]), ([1, 1, 1, 1, 1], [5, 0, 9, 2, 1]),
    ([4, 4, 4, 4, 4], [0, 4, 8, 0, 16]), ([0, 0, 2, 0, 0], [0, 0, 3, 0, 0])],
    ids=["mixed", "decode rows", "full chunks", "one row"])
def test_the_conv_over_token_rows_is_the_conv_over_rows_of_columns(
        adv, pos, impl, dtype, monkeypatch):
    """A step's packed block against the same step laid out `[slots,
    chunk]`: the activated convolution at every live column and the carried
    columns of every slot, a fresh row's (`pos` 0) from zero."""
    slots, chunk, D, K = 5, 4, 256, 4
    monkeypatch.setattr(ssm, "SCAN_LANE_BLOCK", 128)
    monkeypatch.setattr(ssm, "ROWS_BLOCK", 2)      # a ragged last block
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    u, carried, w, bias = f(slots, chunk, D).astype(dtype), \
        f(slots, K - 1, D).astype(dtype), f(D, K), f(D)
    adv, pos = jnp.asarray(adv, jnp.int32), jnp.asarray(pos, jnp.int32)
    fresh = pos == 0
    want, want_carried = ssm.causal_conv_update(u, carried, w, bias, adv,
                                                fresh)
    pack = token_pack(adv, pos, chunk, slots * chunk)
    got, got_carried = ssm.causal_conv_tokens(
        pack.pack(u)[:, 0], carried, w, bias, pack.slot, pack.col,
        pack.last + 1 - adv, adv, fresh, impl=impl)
    live = np.asarray(jnp.arange(chunk)[None, :] < adv[:, None])
    np.testing.assert_allclose(
        np.asarray(pack.unpack(got[:, None]))[live], np.asarray(want)[live],
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got_carried.astype(jnp.float32)),
        np.asarray(want_carried.astype(jnp.float32)), rtol=0, atol=0)
    assert got.dtype == jnp.float32 and got_carried.dtype == carried.dtype
