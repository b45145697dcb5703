"""`SlotPagedKVPool` with a third kind of layer: a window layer's keys in a
ring of window + one chunk of columns beside the other layers' full-length
pages. The ring's geometry as the pool derives it, the byte gauges, what a
ring cannot serve refused by name (`WindowRingError`), the ledger through
allocate / grow / rewind / free / defrag, and a model without window layers
building the pool it built."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.generation import RecurrentState, WindowKV
from paddle_tpu.serving.llm.kv_pool import (PAGED, WINDOW,
                                            RecurrentStateError,
                                            SlotPagedKVPool,
                                            WindowRingError)

HKV, D = 2, 4


def _model(kinds, window=32):
    """An `init_cache` like `LlamaForCausalLM.init_cache`: a window layer
    answers `window_slab` with a `WindowKV` as long as it is told."""
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        def slab(cols):
            shape = (batch, HKV, cols, D)
            return jnp.zeros(shape, dtype or jnp.float32), \
                jnp.zeros(shape, dtype or jnp.float32)
        return [WindowKV(*slab(window_slab(window)))
                if kind == WINDOW and window_slab is not None
                else slab(max_len) for kind in kinds]
    return init_cache


def _pool(kinds=(WINDOW, WINDOW, PAGED), block_len=8, n_blocks=16, pad=16,
          window=32, slots=3):
    return SlotPagedKVPool(_model(kinds, window), slots, block_len,
                           n_blocks, pad_tokens=pad)


@pytest.mark.parametrize("block_len,pad,window,ring", [
    (8, 16, 32, 48),       # window + chunk, whole pages and chunks already
    (16, 16, 32, 48),
    (16, 16, 1024, 1040),  # the published window at the engine's defaults
    (8, 16, 20, 48),       # 36 rounded up to whole chunks
    (16, 8, 30, 48),       # 38 rounded up to whole pages
    (8, 12, 10, 24),       # lcm(8, 12) = 24
])
def test_ring_geometry(block_len, pad, window, ring):
    pool = _pool(block_len=block_len, pad=pad, window=window,
                 n_blocks=2048 // block_len)
    assert pool.layer_kinds == [WINDOW, WINDOW, PAGED] and pool.windowed
    assert (pool.window, pool.ring_len) == (window, ring)
    assert pool.ring_pages == ring // block_len
    assert ring % block_len == 0 and ring % pad == 0
    assert ring >= window + pad
    (wk, wv), _, (fk, fv) = pool.slabs
    assert wk.shape == wv.shape == (3, HKV, ring + pad, D)
    assert fk.shape == fv.shape == (3, HKV, 2048 + pad, D)
    # the logical side is the full layers': capacity, table, lengths
    assert pool.capacity == 2048
    assert pool.device_block_table().shape == (3, 2048 // block_len)


def test_byte_gauges_by_kind():
    pool = _pool()
    item = 4 * HKV * D * 2                      # float32, K and V
    assert pool.kv_bytes() == {"window": 2 * 3 * (48 + 16) * item,
                               "full": 3 * (128 + 16) * item}
    plain = _pool(kinds=(PAGED, PAGED))
    assert plain.kv_bytes() == {"window": 0,
                                "full": 2 * 3 * (128 + 16) * item}


def test_a_pool_without_window_layers_is_the_pool_it_was():
    # an `init_cache` that takes no `window_slab` is never handed one
    def old(batch, max_len):
        z = jnp.zeros((batch, HKV, max_len, D))
        return [(z, z), (z, z)]
    for pool in (SlotPagedKVPool(old, 2, 8, 4, pad_tokens=8),
                 _pool(kinds=(PAGED, PAGED))):
        assert pool.layer_kinds == [PAGED, PAGED]
        assert not pool.windowed and not pool.recurrent
        assert pool.ring_len is None and pool.ring_pages is None \
            and pool.window is None
        assert len({k.shape for k, _ in pool.slabs}) == 1
    # prefix sharing, copy-on-write, exports: as ever
    pool = _pool(kinds=(PAGED, PAGED))
    s = pool.allocate(20)
    pool.set_length(s, 20)
    pool.register_cached(s * pool.n_blocks)
    pool.export_rows([s])
    pool.rewind_length(s, 0)


def test_every_refusal_is_by_name():
    pool = _pool()
    s = pool.allocate(100)
    pool.set_length(s, 70)
    layers = [(np.zeros((HKV, 8, D), np.float32),) * 2] * 3
    for what, call in {
        "attach_blocks": lambda: pool.attach_blocks(s, [0]),
        "register_cached": lambda: pool.register_cached(0),
        "cow_copy": lambda: pool.cow_copy(20, s),
        "export_rows": lambda: pool.export_rows([s]),
        "import_rows": lambda: pool.import_rows(
            {"block_len": 8, "capacity": 128, "rows": {}}),
        "export_page": lambda: pool.export_page(0),
        "import_page": lambda: pool.import_page(s, 0, layers),
        "rewind_length by 17": lambda: pool.rewind_length(s, 53),
    }.items():
        with pytest.raises(WindowRingError, match=what) as e:
            call()
        assert "ring of 48 columns" in str(e.value)
    assert issubclass(WindowRingError, NotImplementedError)
    assert not issubclass(WindowRingError, RecurrentStateError)
    # the ring gives back what its slack holds: a rejected draft window
    pool.rewind_length(s, 54)
    assert int(pool.lengths[s]) == 54
    pool.attach_blocks(s, [])                    # nothing shared: allowed
    assert pool.check_balance()


def test_ledger_balances_through_grow_rewind_free_and_defrag():
    pool = _pool()
    a, b = pool.allocate(128), pool.allocate(64)
    pool.slabs = [(k + 1, v + 1) for k, v in pool.slabs]   # stale keys
    for n in (16, 48, 97, 128):                  # round the ring and on
        pool.set_length(a, n)
        assert pool.check_balance()
    pool.set_length(b, 30)
    assert pool.used_blocks() == 16 + 4          # logical pages
    pool.rewind_length(a, 120)
    assert pool.check_balance()
    pool.free(a)
    assert pool.check_balance() and pool.dirty_blocks() == 16
    assert pool.defrag() == 16
    for (k, v), kind in zip(pool.slabs, pool.layer_kinds):
        # the freed row's slab is scrubbed whole, ring or not; the live
        # row's and the free row's are not touched
        assert not np.asarray(k[a]).any() and not np.asarray(v[a]).any()
        assert np.asarray(k[b]).all() and np.asarray(k[2]).all(), kind
    pool.free(b)
    assert pool.check_balance()
    assert pool.layer_kinds == [WINDOW, WINDOW, PAGED]


def test_a_consumed_pool_is_told_by_its_leaves_and_starts_again_zeroed():
    """What the engine asks after a dispatch that was donated the slabs
    failed, and what it does when they are gone."""
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        ring = jnp.zeros((batch, HKV, window_slab(32), D))
        full = jnp.zeros((batch, HKV, max_len, D), jnp.bfloat16)
        return [WindowKV(ring, ring), (full, full),
                RecurrentState(jnp.zeros((batch, 3, 8)),
                               jnp.zeros((batch, 4, 8)))]
    pool = SlotPagedKVPool(init_cache, 2, 8, 8, pad_tokens=16)
    pool.slabs = [tuple(a + 1 for a in entry) for entry in pool.slabs]
    shapes = [tuple((a.shape, a.dtype) for a in entry)
              for entry in pool.slabs]
    assert not pool.consumed()
    pool.slabs[1][0].delete()                    # one leaf is enough
    assert pool.consumed()
    pool.reset_slabs()
    assert not pool.consumed()
    assert [tuple((a.shape, a.dtype) for a in entry)
            for entry in pool.slabs] == shapes
    assert not any(np.asarray(a).any() for e in pool.slabs for a in e)
    assert pool.layer_kinds == [WINDOW, PAGED, "recurrent"]


def test_a_recurrent_layer_beside_a_ring_refuses_as_recurrent():
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        z = jnp.zeros((batch, HKV, window_slab(32), D))
        return [WindowKV(z, z),
                RecurrentState(jnp.zeros((batch, 3, 8)),
                               jnp.zeros((batch, 4, 8)))]
    pool = SlotPagedKVPool(init_cache, 2, 8, 8, pad_tokens=16)
    assert pool.windowed and pool.recurrent
    s = pool.allocate(40)
    pool.set_length(s, 20)
    with pytest.raises(RecurrentStateError):
        pool.rewind_length(s, 19)
    assert pool.defrag() == 0


def test_rings_of_different_windows_are_refused():
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        return [WindowKV(*(jnp.zeros((batch, HKV, window_slab(w), D)),) * 2)
                for w in (32, 64)]
    with pytest.raises(ValueError, match="different rings"):
        SlotPagedKVPool(init_cache, 2, 8, 32, pad_tokens=16)
