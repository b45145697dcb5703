"""flash_attention (Pallas fwd + Pallas dq/dkv bwd) vs reference numerics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.attention import (_attention_reference, _flash_attention,
                                      flash_attention)


def _rand_qkv(B=2, H=2, Sq=256, Sk=None, D=64, seed=0):
    Sk = Sq if Sk is None else Sk
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, Sq, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, Sk, D).astype(np.float32))
    return q, k, v


def _flash(q, k, v, causal, scale, bq=128, bk=128, mask=None):
    return _flash_attention(q, k, v, mask, jnp.int32(0), causal, scale, bq,
                            bk, 0.0)


# (Sq, Sk, block_q, block_k, causal, additive mask, live, masked): the
# sub-tiles one batch-head computes and those of them under the masked
# body, counted by hand. The first two are the square 2 x 2 grids the
# kernels have always been held to; "loop-4x2" is four query blocks over
# two key sub-tiles of [64, 128] (the diagonal cuts tiles (0,0) (1,0) (2,1)
# (3,1); (2,0) and (3,0) lie under it; (0,1) and (1,1) are dead); the
# rectangular ones put the diagonal at Sk - Sq; "chunks" cuts the other
# operand's extent into two chunks so that the state is carried over the
# grid's last axis
_CASES = {
    "noncausal": (128, 128, 64, 64, False, False, 4, 0),
    "causal": (128, 128, 64, 64, True, False, 3, 2),
    "loop-4x2": (256, 256, 64, 128, True, False, 6, 4),
    "wide-q": (256, 256, 128, 64, True, False, 6, 4),
    "Sq<Sk": (128, 256, 64, 64, True, False, 7, 2),
    "Sq>Sk": (256, 128, 128, 64, True, False, 2, 2),
    "mask": (128, 128, 64, 64, False, True, 4, 4),
    "causal+mask": (128, 256, 64, 128, True, True, 4, 4),
    "chunks": (256, 256, 64, 64, True, False, 10, 4),
    "chunks-noncausal": (128, 256, 64, 64, False, False, 8, 0),
}


def _case(name, monkeypatch):
    """(q, k, v, flash, reference, live, masked) of a case."""
    from paddle_tpu.ops import attention as A
    Sq, Sk, bq, bk, causal, masked_case, live, masked = _CASES[name]
    q, k, v = _rand_qkv(Sq=Sq, Sk=Sk, D=32, seed=len(name))
    scale = 1.0 / np.sqrt(q.shape[-1])
    mask = None
    if masked_case:
        rng = np.random.RandomState(1)
        mask = jnp.asarray(np.where(rng.rand(2, 1, Sq, Sk) > 0.1, 0.0, -1e9)
                           .astype(np.float32))
    if name.startswith("chunks"):
        # K and V (q and dO) of 128 rows, double-buffered: two chunks
        monkeypatch.setattr(A, "_OPERAND_BUDGET", 128 * 4 * 32 * 4)
        tiles = A._choose_tiles(Sq, Sk, 32, 4, False, bq, bk)
        assert (Sk // tiles.chunk_k, Sq // tiles.chunk_q) == (2, Sq // 128)

    def flash(q_, k_, v_):
        return _flash(q_, k_, v_, causal, scale, bq, bk, mask=mask)

    def reference(q_, k_, v_):
        return _attention_reference(q_, k_, v_, causal, scale, mask=mask)
    return q, k, v, flash, reference, live, masked


@pytest.mark.parametrize("case", list(_CASES))
def test_flash_forward_matches_reference(case, monkeypatch):
    q, k, v, flash, reference, _, _ = _case(case, monkeypatch)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(reference(q, k, v)), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("case", list(_CASES))
def test_flash_backward_matches_reference(case, monkeypatch):
    """dq, dk and dv of every case against the reference's, and what the
    three kernels said they computed against the count by hand."""
    from paddle_tpu.ops import pallas_mode
    q, k, v, flash, reference, live, masked = _case(case, monkeypatch)
    pallas_mode.KERNEL_TILINGS.clear()
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(reference(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-3)
    said = {kernel: dict(tiling)
            for kernel, tiling in pallas_mode.KERNEL_TILINGS}
    assert sorted(said) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for kernel, tiling in said.items():
        assert (tiling["live_tiles"], tiling["masked_tiles"]) \
            == (live, masked), (kernel, tiling)


# (Sq, Sk, D, itemsize): the cells' shape, its half, a length 512 does not
# divide, the smoke's rehearsal shape, a rectangle, and the long extents
# that no longer fit whole
_SHAPES = [(2048, 2048, 128, 2), (1024, 1024, 128, 2), (768, 768, 128, 2),
           (512, 512, 64, 2), (512, 2048, 128, 2), (2048, 512, 128, 2),
           (8192, 8192, 128, 2), (32768, 32768, 128, 2),
           (16384, 16384, 64, 4)]


@pytest.mark.parametrize("has_mask", [False, True], ids=["plain", "mask"])
@pytest.mark.parametrize("shape", _SHAPES, ids=lambda s: "x".join(
    map(str, s)))
def test_chosen_tiles_divide_the_extents_and_fit(shape, has_mask):
    from paddle_tpu.ops import attention as A
    Sq, Sk, D, itemsize = shape
    t = A._choose_tiles(Sq, Sk, D, itemsize, has_mask)
    assert Sq % t.chunk_q == 0 and t.chunk_q % t.block_q == 0
    assert Sk % t.chunk_k == 0 and t.chunk_k % t.block_k == 0
    # lane-dense rows and aligned sub-tile offsets
    assert t.block_q % 128 == 0 and t.block_k % 128 == 0
    for chunk, tile, other in ((t.chunk_k, t.block_k, t.block_q),
                               (t.chunk_q, t.block_q, t.block_k)):
        held = chunk * (4 * D * itemsize + (8 * other if has_mask else 0))
        assert chunk == tile or held <= 2 * A._OPERAND_BUDGET
    if Sq == Sk == 2048 and not has_mask:
        # the train cells: K and V whole, a grid step a query block
        assert t == A.FlashTiles(512, 512, 2048, 2048, t.vmem_limit)
    if Sq == 768:
        assert (t.block_q, t.block_k) == (384, 384)
    if Sq == 32768:
        assert t.chunk_k < Sk and t.chunk_q < Sq       # chunks are walked
    assert t.vmem_limit is None or 16 << 20 < t.vmem_limit <= 96 << 20


def test_every_shape_the_old_blocks_took_is_still_taken():
    """The kernels took a sequence that 256 (or the sequence, if shorter)
    divides; the chooser takes every one of those, explicit blocks as
    before, and the closed-form loop bounds agree with the count from the
    definition at each."""
    from paddle_tpu.ops import attention as A
    extents = [s for s in range(8, 4097, 8) if s % min(256, s) == 0]
    for Sq in extents:
        for Sk in {Sq, 256, 2048, extents[-1 - extents.index(Sq)]}:
            t = A._choose_tiles(Sq, Sk, 128, 2)
            assert t is not None and Sq % t.block_q == 0 \
                and Sk % t.block_k == 0, (Sq, Sk)
            assert A._choose_tiles(Sq, Sk, 128, 2, False, 256, 256)[:2] \
                == (min(256, Sq), min(256, Sk))
    assert A._choose_tiles(1000, 1000, 128, 2) is None      # as before
    assert A._choose_tiles(512, 512, 128, 2, False, 200, 256) is None
    for Sq, Sk, bq, bk in [(2048, 2048, 512, 512), (256, 256, 64, 128),
                           (128, 256, 64, 64), (256, 128, 128, 64),
                           (256, 192, 128, 64), (768, 768, 384, 384)]:
        n_qb, n_kb, off = Sq // bq, Sk // bk, Sk - Sq
        live = cut = live_t = cut_t = 0
        for i in range(n_qb):
            full, end = A._key_tile_bounds(i * bq, bq, bk, n_kb, off)
            live, cut = live + int(end), cut + int(end) - int(full)
        for j in range(n_kb):
            first, full = A._query_tile_bounds(j * bk, bq, bk, n_qb, off)
            live_t += n_qb - int(first)
            cut_t += int(full) - int(first)
        assert (live, cut) == (live_t, cut_t) \
            == A._tile_counts(Sq, Sk, bq, bk, True, False), (Sq, Sk, bq, bk)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(128, 256), (256, 128)])
def test_flash_rectangular_cross_attention(causal, shape):
    Sq, Sk = shape
    q, k, v = _rand_qkv(Sq=Sq, Sk=Sk, D=32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    ref = _attention_reference(q, k, v, causal, scale)
    out = _flash(q, k, v, causal, scale, 64, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)

    def loss_flash(q_, k_, v_):
        return jnp.sum(_flash(q_, k_, v_, causal, scale, 64, 64) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_attention_reference(q_, k_, v_, causal, scale) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-3)


@pytest.mark.parametrize("mask_heads", [1, 2])
def test_flash_additive_mask(mask_heads):
    B, H, S, D = 2, 2, 128, 32
    q, k, v = _rand_qkv(B=B, H=H, Sq=S, D=D)
    scale = 1.0 / np.sqrt(D)
    rng = np.random.RandomState(1)
    # additive padding-style mask: 0 or -1e9 per key position
    mask = jnp.asarray(
        np.where(rng.rand(B, mask_heads, S, S) > 0.1, 0.0, -1e9)
        .astype(np.float32))
    ref = _attention_reference(q, k, v, False, scale, mask=mask)
    out = _flash(q, k, v, False, scale, 64, 64, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)

    def loss_flash(q_, k_, v_):
        return jnp.sum(_flash(q_, k_, v_, False, scale, 64, 64,
                              mask=mask) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_attention_reference(q_, k_, v_, False, scale,
                                            mask=mask) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3,
                                   atol=5e-3)


@pytest.mark.parametrize("mask_shape", [(2, 1), (2, 2), (1, 1)])
def test_flash_mask_gradient_matches_reference(mask_shape):
    # a differentiable additive bias (ALiBi-style) must receive true grads on
    # the kernel path, reduced over its broadcast dims
    mb, mh = mask_shape
    B, H, S, D = 2, 2, 128, 32
    q, k, v = _rand_qkv(B=B, H=H, Sq=S, D=D)
    scale = 1.0 / np.sqrt(D)
    rng = np.random.RandomState(3)
    mask = jnp.asarray(rng.randn(mb, mh, S, S).astype(np.float32))

    gm_f = jax.grad(lambda m: jnp.sum(
        _flash(q, k, v, False, scale, 64, 64, mask=m) ** 2))(mask)
    gm_r = jax.grad(lambda m: jnp.sum(
        _attention_reference(q, k, v, False, scale, mask=m) ** 2))(mask)
    np.testing.assert_allclose(np.asarray(gm_f), np.asarray(gm_r), rtol=5e-3,
                               atol=5e-3)


def test_flash_mixed_causal_block_zero_rows():
    # Sq > Sk with (Sq-Sk) not a multiple of block_q: the first q block mixes
    # rows with and without visible keys; no-key rows must output exactly 0
    q, k, v = _rand_qkv(Sq=256, Sk=192, D=32)
    scale = 1.0 / np.sqrt(32)
    out = _flash(q, k, v, True, scale, 128, 64)
    ref = _attention_reference(q, k, v, True, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(out)[:, :, :63], 0.0)
    gf = jax.grad(lambda q_: jnp.sum(
        _flash(q_, k, v, True, scale, 128, 64) ** 2))(q)
    gr = jax.grad(lambda q_: jnp.sum(
        _attention_reference(q_, k, v, True, scale) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=5e-3,
                               atol=5e-3)


def test_flash_causal_plus_mask():
    q, k, v = _rand_qkv(Sq=128, D=32)
    scale = 1.0 / np.sqrt(32)
    mask = jnp.zeros((2, 1, 128, 128), jnp.float32).at[:, :, :, :8].set(-1e9)
    ref = _attention_reference(q, k, v, True, scale, mask=mask)
    out = _flash(q, k, v, True, scale, 64, 64, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_wrapper_fallback_on_odd_shapes():
    q, k, v = _rand_qkv(Sq=100)  # not divisible by blocks → reference path
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == q.shape


def test_wrapper_uses_kernel_for_masked_512():
    # masks no longer force the fallback (VERDICT r1 weak #10)
    q, k, v = _rand_qkv(Sq=512, D=32)
    scale = 1.0 / np.sqrt(32)
    mask = jnp.zeros((2, 1, 512, 512), jnp.float32).at[:, :, :, :4].set(-1e9)
    out = flash_attention(q, k, v, causal=False, mask=mask,
                          force_pallas=True)
    ref = _attention_reference(q, k, v, False, scale, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_reference_dropout_unbiased():
    q, k, v = _rand_qkv(Sq=64, D=16)
    scale = 1.0 / np.sqrt(16)
    out0 = _attention_reference(q, k, v, False, scale, dropout_p=0.0)
    outs = [np.asarray(_attention_reference(
        q, k, v, False, scale, dropout_p=0.3,
        dropout_key=jax.random.PRNGKey(i))) for i in range(32)]
    # dropout is unbiased: the average over draws approaches the dropless out
    np.testing.assert_allclose(np.mean(outs, axis=0), np.asarray(out0),
                               rtol=0.35, atol=0.35)
    # and any single draw differs from it
    assert np.abs(outs[0] - np.asarray(out0)).max() > 1e-3


def test_sdpa_paddle_layout():
    import paddle_tpu as paddle
    from paddle_tpu.ops import scaled_dot_product_attention
    x = paddle.randn([2, 16, 4, 8])  # [B, S, H, D]
    out = scaled_dot_product_attention(x, x, x, is_causal=True)
    assert out.shape == [2, 16, 4, 8]


def test_sdpa_dropout_trains():
    import paddle_tpu as paddle
    from paddle_tpu.ops import scaled_dot_product_attention
    x = paddle.randn([2, 16, 4, 8])
    x.stop_gradient = False
    out = scaled_dot_product_attention(x, x, x, dropout_p=0.25,
                                       is_causal=True, training=True)
    out.sum().backward()
    assert x.grad is not None
    assert np.isfinite(x.grad.numpy()).all()


# ---- per-layer recompute keeps the forward kernel's two results ----

_HEADS, _HEAD_DIM = 2, 32


def _attn_block(a, w):
    """A residual attention block on arrays, through the Pallas kernels
    (interpreted here)."""
    B, S, E = a.shape
    qkv = (a @ w).reshape(B, S, 3, _HEADS, _HEAD_DIM).transpose(2, 0, 3, 1, 4)
    o = flash_attention(qkv[0], qkv[1], qkv[2], causal=True, block_q=64,
                        block_k=64, force_pallas=True)
    return a + jnp.tanh(o.transpose(0, 2, 1, 3).reshape(B, S, E))


def _pallas_calls(jaxpr):
    """Names of the `pallas_call`s of a jaxpr, those inside the jaxprs it
    holds (a checkpoint's replay, a `shard_map`'s body) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device",
                                                        "spmd_island"])
@pytest.mark.parametrize("wrapper", ["recompute", "selective_remat"])
def test_layer_replay_holds_no_second_flash_forward(wrapper, on_mesh):
    """The gradient of a block under `recompute()` / `_wrap_forward_remat`
    holds three kernels (forward, dq, dkv): the replay takes the kept `out`
    and `lse` where a `jax.checkpoint` without the policy runs the forward
    kernel again. The gradients are the bits of both other forms."""
    import contextlib
    from jax.sharding import Mesh
    from paddle_tpu.core.tensor import Tensor, apply, no_grad
    from paddle_tpu.distributed.fleet.utils.recompute import recompute
    from paddle_tpu.nn.layer.layers import Layer
    from paddle_tpu.ops.attention import spmd_mesh
    from paddle_tpu.parallel.api import _wrap_forward_remat

    class Block(Layer):
        def __init__(self, w):
            super().__init__()
            self.w = w

        def forward(self, x):
            return apply(lambda a: _attn_block(a, self.w), x)

    def kept(block, a):
        if wrapper == "recompute":
            return recompute(block, Tensor(a)).data
        _wrap_forward_remat(block)
        return block(Tensor(a)).data

    forms = {
        "kept": kept,
        "unpoliced": lambda block, a: jax.checkpoint(
            lambda a_: block(Tensor(a_)).data)(a),
        "plain": lambda block, a: block(Tensor(a)).data,
    }
    trace_ctx = contextlib.nullcontext
    if on_mesh:
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "sharding"))

        def trace_ctx():
            return spmd_mesh(mesh, ("data", "sharding"))

    def grad_of(form):
        def loss(a, w):
            with no_grad(), trace_ctx():
                return jnp.sum(forms[form](Block(w), a) ** 2)
        return jax.grad(loss, argnums=(0, 1))

    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(4, 128, _HEADS * _HEAD_DIM).astype(np.float32))
    w = jnp.asarray(rng.randn(_HEADS * _HEAD_DIM, 3 * _HEADS * _HEAD_DIM)
                    .astype(np.float32) * 0.1)
    calls = {form: sorted(_pallas_calls(
        jax.make_jaxpr(grad_of(form))(a, w).jaxpr)) for form in forms}
    three = ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert calls["plain"] == three
    assert calls["unpoliced"] == three + ["flash_fwd"]
    assert calls["kept"] == three
    grads = {form: jax.jit(grad_of(form))(a, w) for form in forms}
    for form in ("unpoliced", "plain"):
        for got, want in zip(grads["kept"], grads[form]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
