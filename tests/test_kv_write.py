"""`ops/kv_write.py`: the Mosaic kernel that lands a step's new keys and
values in the pool's slabs, held here (interpreted, on the CPU) to the
vmapped `dynamic_update_slice` / `_ring_write` form it replaces on the
chip: the whole slab bit for bit, in every column, for every page kind.
What Mosaic makes of it is `tests/test_mosaic_aot.py`'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention as A
from paddle_tpu.ops import kv_write as kvw
from paddle_tpu.ops import pallas_mode


def _bits(x):
    """The array's bits (a NaN in write-padding compares equal to itself)."""
    return np.asarray(jax.lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))


def _operands(B, Hkv, L, Dk, Dv, T, dtype, seed=0):
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    kc, vc = rand(B, Hkv, L, Dk), rand(B, Hkv, L, Dv)
    # what lies in the slab is kept whatever it is: a NaN a row
    kc = kc.at[:, 0, L // 2, 0].set(jnp.nan)
    return kc, vc, rand(B, Hkv, T, Dk), rand(B, Hkv, T, Dv)


def _check(B, Hkv, L, Dk, Dv, T, dtype, pos, ring=None):
    kc, vc, kn, vn = _operands(B, Hkv, L, Dk, Dv, T, dtype)
    pos = jnp.asarray(pos, jnp.int32)
    assert pos.shape == (B,)
    assert kvw.kv_write_supported(kc, vc, kn, vn, ring)
    want = A._row_writes(kc, vc, kn, vn, pos, ring)
    got = kvw.kv_write(kc, vc, kn, vn, pos, ring=ring)
    for w, g in zip(want, got):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(_bits(g), _bits(w))


BF16, F32 = jnp.bfloat16, jnp.float32
L = 80
# aligned, odd, 15 mod 16, 0, the slab's last stripe, and past it (clamped
# back as `dynamic_update_slice` clamps)
POSITIONS = [0, 16, 7, 15, 31, L - 16, L - 3, L + 40]


@pytest.mark.parametrize("name,Hkv,Dk,Dv,T,dtype,pos", [
    ("GQA 8 x 128", 8, 128, 128, 16, BF16, POSITIONS),
    ("MHA 16 x 128", 16, 128, 128, 16, BF16, POSITIONS),
    ("latent 512 | 128, one head", 1, 512, 128, 16, BF16, POSITIONS),
    ("T 8, bf16", 2, 128, 128, 8, BF16,
     [0, 8, 5, 15, 33, L - 8, L - 3, L + 9]),
    ("T 8, float32", 2, 128, 128, 8, F32,
     [0, 8, 5, 15, 33, L - 8, L - 3, L + 9]),
    ("T 16, float32", 2, 128, 128, 16, F32, POSITIONS),
    ("a batch of one, aligned", 2, 128, 128, 16, BF16, [32]),
    ("a batch of one, odd", 2, 128, 128, 16, BF16, [37]),
    ("a batch of one, the last stripe", 2, 128, 128, 16, BF16, [L - 16]),
    ("every row at one position", 2, 128, 128, 16, BF16, [21] * 5),
    # more grid steps than sets of buffers: a set is fetched into again
    # once its write-back has drained (40 rows = 5 steps of 8)
    ("5 grid steps", 1, 128, 128, 16, BF16,
     [(7 * i) % (L - 10) for i in range(40)]),
    ("2 grid steps of 7", 1, 128, 128, 16, BF16,
     [(11 * i) % L for i in range(14)]),
])
def test_kv_write_is_the_vmapped_write_bit_for_bit(name, Hkv, Dk, Dv, T,
                                                   dtype, pos):
    _check(len(pos), Hkv, L, Dk, Dv, T, dtype, pos)


RING = 64


@pytest.mark.parametrize("name,T,dtype,pos", [
    # the stripe's first column c0 = pos mod ring; it wraps by c0 + T - ring
    ("does not wrap", 16, BF16, [0, 5, 16, RING - 16, 3 * RING + 9]),
    ("wraps by 1", 16, BF16, [RING - 15, 2 * RING - 15]),
    ("wraps by T - 1", 16, BF16, [RING - 1, 5 * RING - 1]),
    ("every overrun, beside rows that do not wrap", 16, BF16,
     list(range(RING - 17, RING + 1))),
    ("float32, every overrun", 16, F32, list(range(RING - 17, RING + 1))),
    ("T 8, wraps by 1 and by 7", 8, BF16, [RING - 7, RING - 1, 3, RING - 8]),
    ("a batch of one that wraps", 16, BF16, [RING - 6]),
])
def test_kv_write_brings_a_rings_overrun_round(name, T, dtype, pos):
    """`ring=`: `_ring_write`'s result, the columns behind the ring (where
    the overrun is written first) included."""
    _check(len(pos), 2, RING + 16, 128, 128, T, dtype, pos, ring=RING)


@pytest.mark.parametrize("name,B,Hkv,slab_len,Dk,Dv,ring,rows", [
    ("mistral decode", 128, 8, 240, 128, 128, None, 8),
    ("mistral prefill", 32, 8, 1056, 128, 128, None, 8),
    ("olmoe decode", 128, 16, 240, 128, 128, None, 4),
    ("granite decode", 128, 8, 240, 128, 128, None, 8),
    ("mellum full layers", 32, 4, 8304, 128, 128, None, 8),
    ("mellum window layers", 32, 4, 1056, 128, 128, 1040, 8),
    ("a.x-k1 latent pair", 32, 1, 8304, 512, 128, None, 8),
    ("xing4.0 latent pair", 256, 1, 2576, 512, 128, None, 8),
    ("laguna full layers", 128, 8, 2576, 128, 128, None, 8),
    ("laguna window layers", 128, 8, 544, 128, 128, 528, 8),
    ("a batch of one", 1, 8, 240, 128, 128, None, 1),
    ("rows that no 8 divides", 14, 8, 240, 128, 128, None, 7),
    ("a prime batch", 13, 8, 240, 128, 128, None, 1),
])
def test_the_cells_shapes_are_taken_and_rows_follow_the_budget(
        name, B, Hkv, slab_len, Dk, Dv, ring, rows):
    """Every serve cell's slabs go through the kernel, and a grid step
    takes the most rows that divide the batch and fit the module's one
    VMEM budget (bf16, stripes of 16)."""
    spec = jax.ShapeDtypeStruct
    assert kvw.kv_write_supported(
        spec((B, Hkv, slab_len, Dk), BF16), spec((B, Hkv, slab_len, Dv), BF16),
        spec((B, Hkv, 16, Dk), BF16), spec((B, Hkv, 16, Dv), BF16), ring)
    assert kvw._choose_rows(B, Hkv, 16, 32, Dk + Dv, 2) == rows
    assert rows * kvw._row_bytes(Hkv, 16, 32, Dk + Dv, 2) \
        <= kvw._VMEM_BUDGET


@pytest.mark.parametrize("name,slab_len,T,dtype,ring", [
    ("a slab that is no whole number of sublane tiles", 70, 16, BF16, None),
    ("a slab shorter than a window", 16, 16, BF16, None),
    ("a ring whose head the wrapping stripe's window touches", 40, 16, BF16,
     24),
])
def test_shapes_the_windows_do_not_fit_keep_the_vmapped_form(
        monkeypatch, name, slab_len, T, dtype, ring):
    """Such a slab is refused by `kv_write_supported`, and on a TPU
    `update_kv_cache` says so (`note_reference`) and writes as before."""
    kc, vc, kn, vn = _operands(3, 2, slab_len, 128, 128, T, dtype)
    assert not kvw.kv_write_supported(kc, vc, kn, vn, ring)
    monkeypatch.setattr(pallas_mode, "platform", lambda: "tpu")
    pallas_mode.KERNEL_TRACES.clear()
    pos = jnp.asarray([0, 3, 9], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: A.update_kv_cache(*a, pos, ring=ring))(kc, vc, kn, vn)
    assert "pallas_call" not in str(jaxpr)
    assert pallas_mode.KERNEL_TRACES[(kvw.KERNEL, "reference")] == 1


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and eqn.primitive.name != "pallas_call":
                    yield from _primitives(sub)


@pytest.mark.parametrize("ring", [None, RING])
def test_tpu_path_is_one_aliased_kernel_for_both_caches(monkeypatch, ring):
    """With a position a row, on a TPU, the write is ONE `pallas_call`
    named `kv_write` with both slabs aliased to its results: no
    `dynamic_update_slice`, no scatter, no loop beside it; the tiling is
    recorded. Traced only: Mosaic's part is the AOT test's."""
    kc, vc, kn, vn = _operands(16, 2, RING + 16, 128, 128, 16, BF16)
    pos = jnp.arange(16, dtype=jnp.int32) * 5
    monkeypatch.setattr(pallas_mode, "platform", lambda: "tpu")
    pallas_mode.KERNEL_TRACES.clear()
    pallas_mode.KERNEL_TILINGS.clear()
    jaxpr = jax.make_jaxpr(
        lambda *a: A.update_kv_cache(*a, pos, ring=ring))(kc, vc, kn, vn)
    eqns = list(_primitives(jaxpr.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call, = calls
    assert call.params["name"] == kvw.KERNEL == "kv_write"
    # operands: the columns (scalar prefetch), the stripes, then the slabs
    assert tuple(call.params["input_output_aliases"]) == ((3, 0), (4, 1))
    names = {e.primitive.name for e in eqns}
    assert not names & {"dynamic_update_slice", "scatter", "while", "scan"}
    assert dict(pallas_mode.KERNEL_TRACES) == {(kvw.KERNEL, "mosaic"): 1}
    assert dict(pallas_mode.KERNEL_TILINGS) == {
        (kvw.KERNEL, (("columns", 32), ("grid", (2,)), ("heads", 2),
                      ("ring", ring or 0), ("rows", 8))): 1}


def test_layers_that_agree_on_shapes_share_one_traced_kernel(monkeypatch):
    """The `pallas_call` sits under one module-level `jax.jit` whose
    integers are static, so a step's layers trace the kernel once a
    (shapes, ring) pair, not once a layer (PR 32's lesson: a body a call
    site is traced and lowered in every process)."""
    kc, vc, kn, vn = _operands(4, 2, RING + 16, 128, 128, 16, BF16)
    pos = jnp.asarray([0, 9, 40, RING - 3], jnp.int32)
    monkeypatch.setattr(pallas_mode, "platform", lambda: "tpu")

    def three_layers_and_a_ring(kc, vc, kn, vn):
        for _ in range(3):
            kc, vc = A.update_kv_cache(kc, vc, kn, vn, pos)
        return A.update_kv_cache(kc, vc, kn, vn, pos, ring=RING)
    jaxpr = jax.make_jaxpr(three_layers_and_a_ring)(kc, vc, kn, vn)
    sites = [e for e in jaxpr.jaxpr.eqns
             if e.params.get("name") == "_kv_write_call"]
    assert len(sites) == 4
    assert len({id(e.params["jaxpr"]) for e in sites}) == 2


def test_the_cpu_and_the_scalar_position_keep_their_form():
    """On the CPU the vmapped form stays (the kernel would be interpreted
    a grid step at a time), and a scalar position (`generate()`) is one
    `dynamic_update_slice` a cache on every platform."""
    kc, vc, kn, vn = _operands(3, 2, 48, 128, 128, 16, F32)
    pallas_mode.KERNEL_TRACES.clear()
    pos = jnp.asarray([0, 3, 9], jnp.int32)
    for p in (pos, jnp.int32(5)):
        jaxpr = jax.make_jaxpr(
            lambda *a: A.update_kv_cache(*a, p))(kc, vc, kn, vn)
        assert "pallas_call" not in str(jaxpr)
    assert not pallas_mode.KERNEL_TRACES
    names = [e.primitive.name for e in _primitives(jaxpr.jaxpr)]
    assert names.count("dynamic_update_slice") == 2
