"""`SlotPagedKVPool` with a third kind of layer: a window layer's keys in a
ring of window + one chunk of columns beside the other layers' full-length
pages. The ring's geometry as the pool derives it, the byte gauges, what a
ring cannot serve refused by name (`WindowRingError`), the ledger through
allocate / grow / rewind / free, and a model without window layers
building the pool it built. Then the seam itself: the kinds' table
(`models.generation.CACHE_KINDS`) as the pool reads it, a kind the pool has
never seen, the step's page operand (`PagedView`) and the per-kind counts of
a step."""
import jax.numpy as jnp
import numpy as np
import pytest

from typing import NamedTuple

import jax

from paddle_tpu.models import generation
from paddle_tpu.models.generation import (CACHE_KINDS, HOST_TIER, REREAD,
                                          REWIND, CacheKind, IndexedLatentKV,
                                          LatentKV, RecurrentState, WindowKV,
                                          kind_of)
from paddle_tpu.serving.llm.kv_pool import (INDEXED, LATENT, PAGED, RECURRENT,
                                            WINDOW, RecurrentStateError,
                                            SlotPagedKVPool,
                                            SlotsExhaustedError,
                                            WindowRingError)
from paddle_tpu.serving.llm.prefix_cache import PrefixCache

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


def test_ledger_balances_through_grow_rewind_and_free():
    pool = _pool()
    a, b = pool.allocate(128), pool.allocate(64)
    for n in (16, 48, 97, 128):                  # round the ring and on
        pool.set_length(a, n)
        assert pool.check_balance()
    pool.set_length(b, 30)
    assert pool.used_blocks() == 16 + 4          # logical pages
    pool.rewind_length(a, 120)
    assert pool.check_balance()
    pool.free(a)
    assert pool.check_balance() and pool.dirty_blocks() == 16
    # the freed row is handed out again as it lies, and counted
    assert pool.allocate(8) == a and pool.stats["reuses"] == 1
    assert pool.dirty_blocks() == 0
    pool.free(a)
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


def test_rings_of_different_windows_are_refused():
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        return [WindowKV(*(jnp.zeros((batch, HKV, window_slab(w), D)),) * 2)
                for w in (32, 64)]
    with pytest.raises(ValueError, match="different rings"):
        SlotPagedKVPool(init_cache, 2, 8, 32, pad_tokens=16)


# ---- a fourth kind: latent pages (PR 36) ----

RANK, ROPE = 6, 2


def _latent_pool(layers=2, block_len=8, n_blocks=8, pad=16, slots=3):
    """An `init_cache` like `DeepseekForCausalLM.init_cache`: per layer a
    latent and a rotary key, one "head", unequal widths."""
    def init_cache(batch, max_len, dtype=None):
        dt = dtype or jnp.float32
        return [LatentKV(jnp.zeros((batch, 1, max_len, RANK), dt),
                         jnp.zeros((batch, 1, max_len, ROPE), dt))
                for _ in range(layers)]
    return SlotPagedKVPool(init_cache, slots, block_len, n_blocks,
                           pad_tokens=pad)


def _fill(pool, seed=0):
    """Distinct values in every column of every slab."""
    rng = np.random.default_rng(seed)
    pool.slabs = [tuple(jnp.asarray(rng.normal(size=a.shape), a.dtype)
                        for a in entry) for entry in pool.slabs]


def test_latent_kind_and_bytes_by_kind():
    pool = _latent_pool()
    assert pool.layer_kinds == [LATENT, LATENT]
    assert not pool.windowed and not pool.recurrent
    assert pool.ring_len is None and pool.window is None
    assert [(c.shape, r.shape) for c, r in pool.slabs] == [
        ((3, 1, 80, RANK), (3, 1, 80, ROPE))] * 2
    assert pool.kv_bytes() == {"full": 0, "window": 0,
                               "latent": 2 * 3 * 80 * (RANK + ROPE) * 4}
    assert pool.recurrent_state_bytes == 0
    assert "latent" not in _pool(kinds=(PAGED,)).kv_bytes()


@pytest.mark.parametrize("operation", [
    "attach_blocks", "cow_copy", "export_rows", "export_page",
    "rewind_length", "prefix cache hit"])
def test_every_page_operation_works_on_a_latent_pool(operation):
    """Latent pages are addressed by position like K/V pages: nothing is
    refused, and each operation moves both slabs of the pair, each at its
    own width."""
    pool = _latent_pool()
    _fill(pool)
    a = pool.allocate(40)
    pool.set_length(a, 40)
    before = [tuple(np.asarray(x) for x in e) for e in pool.slabs]
    if operation == "attach_blocks":
        pages = [a * pool.n_blocks + j for j in range(3)]
        for page in pages:
            pool.register_cached(page)
        b = pool.allocate(40)
        pool.attach_blocks(b, pages)
        pool.set_length(b, 30)
        assert pool.block_table[b][:3] == pages
        assert np.asarray(pool.device_block_table())[b, :3].tolist() == pages
        assert all(pool.refcount[page] == 1 for page in pages)
    elif operation == "cow_copy":
        page = a * pool.n_blocks + 2
        pool.register_cached(page)
        b = pool.allocate(40)
        pool.cow_copy(page, b)
        for (c, r), (c0, r0) in zip(pool.slabs, before):
            assert np.array_equal(np.asarray(c[b, :, 16:24]), c0[a, :, 16:24])
            assert np.array_equal(np.asarray(r[b, :, 16:24]), r0[a, :, 16:24])
            assert np.array_equal(np.asarray(c[b, :, :16]), c0[b, :, :16])
    elif operation == "export_rows":
        out = pool.export_rows([a])
        (c, r), = out["rows"][a]["layers"][:1]
        assert c.shape == (1, 40, RANK) and r.shape == (1, 40, ROPE)
        assert np.array_equal(c, before[0][0][a, :, :40])
        assert np.array_equal(r, before[0][1][a, :, :40])
        # an active row of length 0 exports empties of both widths
        e = pool.allocate(8)
        (c, r), = pool.export_rows([e])["rows"][e]["layers"][:1]
        assert c.shape == (1, 0, RANK) and r.shape == (1, 0, ROPE)
    elif operation == "export_page":
        layers = pool.export_page(a * pool.n_blocks + 1, width=5)
        assert [(c.shape, r.shape) for c, r in layers] == [
            ((1, 5, RANK), (1, 5, ROPE))] * 2
        b = pool.allocate(16)
        pool.import_page(b, 1, layers)
        assert np.array_equal(np.asarray(pool.slabs[1][1][b, :, 8:13]),
                              before[1][1][a, :, 8:13])
    elif operation == "rewind_length":
        pool.rewind_length(a, 17)
        assert pool.lengths[a] == 17 and len(pool.block_table[a]) == 3
    else:
        cache = PrefixCache(pool)
        tokens = np.arange(1, 41, dtype=np.int32)
        cache.insert("t", tokens, a, [])
        pool.free(a)
        plan = cache.acquire("t", np.concatenate([tokens[:26], [99, 98]]),
                             27)
        # three whole pages of the shared 26 tokens are attached
        assert plan.pages == [a * pool.n_blocks + j for j in range(3)]
        assert plan.attach_len == 24 + plan.tail_len
        b = pool.allocate(40)
        pool.attach_blocks(b, plan.pages)
        pool.set_length(b, 28)
        assert cache.stats["hits"] == 1 and cache.stats["hit_tokens"] >= 24
    pool.check_balance()


def test_latent_ledger_balances_through_a_rows_life():
    pool = _latent_pool()
    a, b = pool.allocate(64), pool.allocate(30)
    for n in (16, 33, 64):
        pool.set_length(a, n)
        assert pool.check_balance()
    pool.set_length(b, 30)
    assert pool.used_blocks() == 8 + 4
    pool.rewind_length(a, 50)
    pool.free(a)
    pool.free(b)
    assert pool.check_balance() and pool.dirty_blocks() == 16


# ---- a fifth kind: index-key pages beside latent pages (PR 39) ----

INDEX = 3


def _indexed_pool(kinds=(INDEXED, LATENT, INDEXED), block_len=8, n_blocks=8,
                  pad=16, slots=3):
    """An `init_cache` like a sparse-attention `DeepseekForCausalLM`'s: a
    layer with an indexer keeps a third slab, one index key a token."""
    def init_cache(batch, max_len, dtype=None):
        dt = dtype or jnp.float32

        def slab(width):
            return jnp.zeros((batch, 1, max_len, width), dt)
        return [IndexedLatentKV(slab(RANK), slab(ROPE), slab(INDEX))
                if kind == INDEXED else LatentKV(slab(RANK), slab(ROPE))
                for kind in kinds]
    return SlotPagedKVPool(init_cache, slots, block_len, n_blocks,
                           pad_tokens=pad)


def test_indexed_kind_and_bytes_by_kind():
    pool = _indexed_pool()
    assert pool.layer_kinds == [INDEXED, LATENT, INDEXED]
    assert not pool.windowed and not pool.recurrent
    assert [len(e) for e in pool.slabs] == [3, 2, 3]
    assert pool.slabs[0][2].shape == (3, 1, 80, INDEX)
    assert pool.kv_bytes() == {
        "full": 0, "window": 0,
        "latent": 3 * 3 * 80 * (RANK + ROPE) * 4,
        "index": 2 * 3 * 80 * INDEX * 4}
    assert "index" not in _latent_pool().kv_bytes()


@pytest.mark.parametrize("operation", [
    "cow_copy", "export_rows", "export_page", "eviction"])
def test_index_pages_go_where_their_latent_pages_go(operation):
    """Page p of the index slab is page p of `c` and of `r`: whatever
    copies, exports or evicts a page does it to all three slabs of a layer
    that has three."""
    pool = _indexed_pool()
    _fill(pool)
    a = pool.allocate(40)
    pool.set_length(a, 40)
    before = [tuple(np.asarray(x) for x in e) for e in pool.slabs]
    if operation == "cow_copy":
        page = a * pool.n_blocks + 2
        pool.register_cached(page)
        b = pool.allocate(40)
        pool.cow_copy(page, b)
        for entry, old in zip(pool.slabs, before):
            for x, x0 in zip(entry, old):
                assert np.array_equal(np.asarray(x[b, :, 16:24]),
                                      x0[a, :, 16:24])
                assert np.array_equal(np.asarray(x[b, :, :16]), x0[b, :, :16])
    elif operation == "export_rows":
        out = pool.export_rows([a])
        layers = out["rows"][a]["layers"]
        assert [len(e) for e in layers] == [3, 2, 3]
        assert layers[0][2].shape == (1, 40, INDEX)
        assert np.array_equal(layers[2][2], before[2][2][a, :, :40])
    elif operation == "export_page":
        layers = pool.export_page(a * pool.n_blocks + 1, width=5)
        assert [tuple(x.shape for x in e) for e in layers] == [
            ((1, 5, RANK), (1, 5, ROPE), (1, 5, INDEX)),
            ((1, 5, RANK), (1, 5, ROPE)),
            ((1, 5, RANK), (1, 5, ROPE), (1, 5, INDEX))]
        b = pool.allocate(16)
        pool.import_page(b, 1, layers)
        assert np.array_equal(np.asarray(pool.slabs[2][2][b, :, 8:13]),
                              before[2][2][a, :, 8:13])
        # a pool whose layers keep other slabs refuses the payload
        other = _latent_pool(layers=3)
        with pytest.raises(ValueError, match="a layer of 2 slabs given 3"):
            other.import_page(other.allocate(16), 1, layers)
    else:
        cache = PrefixCache(pool)
        tokens = np.arange(1, 41, dtype=np.int32)
        cache.insert("t", tokens, a, [])
        pool.free(a)
        b, c = pool.allocate(8), pool.allocate(8)
        pool.free(b)
        pool.free(c)
        # a fresh sequence for the one pinned row left: its pages go, LRU
        for slot in (pool.allocate(8), pool.allocate(8)):
            assert slot != a
        assert pool.allocate(8) == a and cache.stats["evictions"] == 5
        assert not pool.cached and not cache._where
        # nothing was copied or scrubbed on the way: an evicted page's
        # index keys lie where they lay until the row is written again
        assert np.array_equal(np.asarray(pool.slabs[0][2]), before[0][2])
    pool.check_balance()


# ---- which row a request gets (PR 39) ----

def _session(pool, cache, tokens, slot):
    """A finished request of `tokens` in `slot`: its pages cached."""
    pool.set_length(slot, len(tokens))
    cache.insert("t", tokens, slot, pool._attached.get(slot, []))
    pool.free(slot)


def test_a_turn_goes_back_into_the_row_its_pages_are_in():
    pool = _indexed_pool(slots=2, n_blocks=16)
    cache = PrefixCache(pool)
    rng = np.random.default_rng(3)
    history = [rng.integers(1, 99, (n,)).astype(np.int32) for n in (56, 40)]
    for tokens in history:
        _session(pool, cache, tokens, pool.allocate(48))
    assert not pool._fit_rows().any()
    # a fresh sequence would have to evict a whole session; a turn of
    # session 1 attaches its 5 blocks and fits its own row as it is
    turn = np.concatenate([history[1], rng.integers(1, 99, (20,))])
    assert cache.probe_row("t", turn, len(turn) - 1) == (5, 1)
    assert pool._fit_rows(keep_below=5).tolist() == [False, True]
    slot = pool.allocate(70, keep_below=5, prefer=1)
    assert slot == 1 and cache.stats["evictions"] == 0
    plan = cache.acquire("t", turn, len(turn) - 1)
    assert plan.pages == [1 * 16 + j for j in range(5)]
    pool.attach_blocks(slot, plan.pages)
    cache.release(plan)
    _session(pool, cache, turn, slot)
    assert pool._cached_at[1].sum() == 8         # 7 blocks and a tail
    # the session starts over from its history: no row is fit (row 0 holds
    # the other session's blocks 5 and 6), and its own stale turn goes (the
    # blocks behind the history, deepest first: three pages where row 0
    # would cost two), nobody's history
    again = np.concatenate([history[1], rng.integers(1, 99, (9,))])
    assert cache.probe_row("t", again, len(again) - 1) == (5, 1)
    slot = pool.allocate(60, keep_below=5, prefer=1)
    assert slot == 1 and cache.stats["evictions"] == 3
    assert pool._cached_at[1].tolist() == [True] * 5 + [False] * 11
    assert pool._cached_at[0].sum() == 7
    assert cache.probe("t", history[0]) == 56
    pool.free(slot)
    pool.check_balance()


def test_pressure_clears_the_cheapest_free_row_and_never_a_reader():
    pool = _indexed_pool(slots=3, n_blocks=16)
    cache = PrefixCache(pool)
    rng = np.random.default_rng(4)
    long, short, base = (rng.integers(1, 99, (n,)).astype(np.int32)
                         for n in (96, 24, 32))
    for tokens in (long, short, base):
        _session(pool, cache, tokens, pool.allocate(100))
    # a request that attaches `base`'s 4 blocks while its row is taken by
    # a reader: rows 0 and 1 are free, row 1 costs 3 pages less 0 below 4
    reader = pool.allocate(40, keep_below=4, prefer=2)
    assert reader == 2
    plan = cache.acquire("t", base, 31)
    pool.attach_blocks(reader, plan.pages)
    cache.release(plan)
    slot = pool.allocate(60, keep_below=4, prefer=2)
    assert slot == 1 and cache.stats["evictions"] == 0   # 3 blocks: below 4
    pool.free(slot)
    slot = pool.allocate(60, keep_below=2, prefer=2)
    assert slot == 1 and cache.stats["evictions"] == 1   # its third block
    assert pool._cached_at[0].sum() == 12
    # pages with a reader never go: the reader's row is not free, and a
    # row whose page another row's entry hangs under is left alone
    pool.free(slot)
    with pytest.raises(SlotsExhaustedError):
        for _ in range(3):
            pool.allocate(8, keep_below=0, prefer=2)
    assert all(pool.refcount.get(2 * 16 + j, 0) == 1 for j in range(3))
    assert pool._cached_at[2].sum() == 4


@pytest.mark.parametrize("keep_below", [0, 3])
def test_the_fit_rows_follow_the_pinned_pages(keep_below):
    """`_fit_rows` reads two ledgers of the pinned pages (by row and block;
    counted by row, for a fresh sequence): both follow `cached` through
    pins and evictions, and `check_balance` tells a ledger that does not."""
    pool = _indexed_pool(slots=3, n_blocks=8)
    taken = pool.allocate(8)
    for page in (1 * 8 + 1, 1 * 8 + 5, 2 * 8 + 2, taken * 8):
        pool.register_cached(page)

    def by_hand():
        return [not pool.active[r] and not any(
            r * 8 + j in pool.cached for j in range(keep_below, 8))
            for r in range(3)]
    assert pool._fit_rows(keep_below).tolist() == by_hand() \
        == [False, False, keep_below == 3]
    pool.release_cached(1 * 8 + 5)
    assert pool._fit_rows(keep_below).tolist() == by_hand() \
        == [False, keep_below == 3, keep_below == 3]
    pool.release_cached(1 * 8 + 1)
    assert pool._fit_rows(keep_below).tolist() == by_hand() \
        == [False, True, keep_below == 3]
    assert pool.has_allocatable_row(keep_below)
    pool.check_balance()
    pool._cached_in[2] = 0
    with pytest.raises(AssertionError, match="ledgers disagree"):
        pool.check_balance()


# ---- the seam: kinds declared once, read by the pool (PR 42) ----

def _pool_of(kind, slots=3, block_len=8, n_blocks=16, pad=16):
    """A pool whose first layer is of `kind` and whose second is paged."""
    def init_cache(batch, max_len, dtype=None, window_slab=None):
        def slab(cols, width=D, heads=HKV):
            return jnp.zeros((batch, heads, cols, width), jnp.float32)
        first = {
            PAGED: lambda: (slab(max_len), slab(max_len)),
            RECURRENT: lambda: RecurrentState(jnp.zeros((batch, 3, 8)),
                                              jnp.zeros((batch, 4, 8))),
            WINDOW: lambda: WindowKV(slab(window_slab(32)),
                                     slab(window_slab(32))),
            LATENT: lambda: LatentKV(slab(max_len, RANK, 1),
                                     slab(max_len, ROPE, 1)),
            INDEXED: lambda: IndexedLatentKV(slab(max_len, RANK, 1),
                                             slab(max_len, ROPE, 1),
                                             slab(max_len, INDEX, 1)),
        }[kind]()
        return [first, (slab(max_len), slab(max_len))]
    return SlotPagedKVPool(init_cache, slots, block_len, n_blocks,
                           pad_tokens=pad)


KINDS = (PAGED, RECURRENT, WINDOW, LATENT, INDEXED)


def test_the_table_names_every_kind_by_its_entrys_type():
    assert [row.name for row in CACHE_KINDS.values()] == list(KINDS)
    z = jnp.zeros((1, 1, 8, 2))
    assert kind_of((z, z)).name == PAGED and kind_of([z, z]).name == PAGED
    for entry, name in ((RecurrentState(z, z), RECURRENT),
                        (WindowKV(z, z), WINDOW), (LatentKV(z, z), LATENT),
                        (IndexedLatentKV(z, z, z), INDEXED)):
        assert kind_of(entry).name == name
        assert len(kind_of(entry).bytes_as) == len(entry)
    # a kind that refuses something says why, and only such a kind
    for row in CACHE_KINDS.values():
        assert bool(row.refuses) == bool(row.why)
        assert row.refuses <= {REREAD, HOST_TIER, REWIND}


@pytest.mark.parametrize("kind", KINDS)
def test_the_pool_refuses_what_the_kinds_row_refuses_in_its_words(kind):
    row = next(r for r in CACHE_KINDS.values() if r.name == kind)
    pool = _pool_of(kind)
    assert pool.layer_kinds == [kind, PAGED]
    for feature in (REREAD, HOST_TIER, REWIND):
        err = pool.refusal(feature, "a thing asked")
        if feature not in row.refuses:
            assert err is None
            continue
        assert type(err) is row.error
        assert str(err).startswith(
            f"a thing asked of which 1 of 2 layers are {kind} layers")
        assert str(err).endswith(row.why)
    # and the pool's own operations ask the same table
    s = pool.allocate(100)
    pool.set_length(s, 70)
    calls = {
        REREAD: {"export_rows": lambda: pool.export_rows([s]),
                 "cow_copy": lambda: pool.cow_copy(40, s),
                 "register_cached": lambda: pool.register_cached(0),
                 "rewind_length by 30": lambda: pool.rewind_length(s, 40)},
        REWIND: {"rewind_length by 2": lambda: pool.rewind_length(s, 68)},
    }
    for feature, ops in calls.items():
        for what, call in ops.items():
            if feature in row.refuses:
                with pytest.raises(row.error, match=what) as e:
                    call()
                assert row.why in str(e.value)
    if not row.refuses - {HOST_TIER}:
        pool.rewind_length(s, 68)
        pool.rewind_length(s, 10)
        assert pool.export_rows([s])["rows"][s]["length"] == 10
    pool.free(s)
    assert pool.check_balance()


class MatrixState(NamedTuple):
    """A kind this repository does not have: one fixed `[rows, rows]`
    matrix a slot (a linear-attention layer's state), no pages."""
    m: jax.Array


class MatrixStateError(NotImplementedError):
    pass


MATRIX = CacheKind(
    "matrix", ("matrix",), frozenset({REREAD, HOST_TIER, REWIND}),
    "a matrix state sums every token the row has seen and keeps none of "
    "them", MatrixStateError)


def test_a_kind_the_pool_has_never_seen_is_a_type_and_a_row(monkeypatch):
    """The next kind costs a NamedTuple and a row of the table: the pool
    names it, counts its bytes under its label, keeps the ledger through a
    row's life and refuses what the row refuses, and `kv_pool.py` holds
    nothing about it."""
    monkeypatch.setitem(generation.CACHE_KINDS, MatrixState, MATRIX)

    def init_cache(batch, max_len, dtype=None):
        z = jnp.zeros((batch, HKV, max_len, D), jnp.float32)
        return [MatrixState(jnp.zeros((batch, 5, 5), jnp.float32)), (z, z)]
    pool = SlotPagedKVPool(init_cache, 3, 8, 8, pad_tokens=16)
    assert pool.layer_kinds == ["matrix", PAGED]
    assert not pool.recurrent and not pool.windowed
    assert pool.ring_len is None
    assert pool.kv_bytes() == {"full": 2 * 3 * HKV * 80 * D * 4,
                               "window": 0, "matrix": 3 * 5 * 5 * 4}
    assert pool.recurrent_state_bytes == 0
    assert pool.view("t", "l") == ("t", "l", 8, 8, None)
    a, b = pool.allocate(64), pool.allocate(30)
    for n in (16, 33, 64):
        pool.set_length(a, n)
        assert pool.check_balance()
    pool.set_length(b, 30)
    assert pool.used_blocks() == 8 + 4
    for what, call in {
        "rewind_length by 1": lambda: pool.rewind_length(a, 63),
        "rewind_length by 40": lambda: pool.rewind_length(a, 24),
        "export_rows": lambda: pool.export_rows([a]),
        "export_page": lambda: pool.export_page(0),
        "attach_blocks": lambda: pool.attach_blocks(b, [0]),
    }.items():
        with pytest.raises(MatrixStateError, match=what) as e:
            call()
        assert str(e.value).endswith(MATRIX.why)
        assert "1 of 2 layers are matrix layers" in str(e.value)
    args, started, kv_tokens = pool.step_counts(
        np.array([64, 30, 0], np.int32), np.array([1, 1, 0], np.int32))
    assert (args, started, kv_tokens) == ({}, 0, (0, 65 + 31))
    # the step's donated slabs and a lost pool, as for any kind
    assert not pool.consumed()
    pool.reset_slabs()
    assert pool.slabs[0][0].shape == (3, 5, 5)
    pool.free(a)
    pool.free(b)
    assert pool.check_balance() and pool.dirty_blocks() == 16


# ---- the step's page operand, as the pool builds it ----

@pytest.mark.parametrize("kinds", [(PAGED, PAGED), (WINDOW, WINDOW, PAGED)])
def test_the_pools_view_is_the_old_tuple_field_for_field(kinds):
    from paddle_tpu.ops.attention import PagedView
    pool = _pool(kinds=kinds)
    table, lens = pool.device_block_table(), jnp.arange(3, dtype=jnp.int32)
    view = pool.view(table, lens)
    assert isinstance(view, PagedView)
    # what `LLMEngine._step` built: four fields and, on a pool with a
    # ring, its pages
    old = (table, lens, pool.block_len, pool.n_blocks)
    assert tuple(view)[:4] == old
    assert (view.table, view.seq_lens, view.block_len,
            view.pages_per_row) == old
    if pool.windowed:
        assert tuple(view) == old + (pool.ring_pages,) == old + (6,)
        assert (view.ring, view.positions) == (pool.ring_len, pool.capacity)
    else:
        assert tuple(view) == old + (None,)
    assert type(view.block_len) is int and type(view.pages_per_row) is int


# ---- what a step means to each kind ----

def _old_step_counts(pool, pos, adv):
    """`LLMEngine._launch`'s arithmetic as it stood inline (PR 41)."""
    span_args, started = {}, 0
    if RECURRENT in pool.layer_kinds:
        span_args["recurrent_rows"] = int(np.count_nonzero(adv))
        started = int(np.count_nonzero((adv > 0) & (pos == 0)))
    after = (pos + adv)[adv > 0]
    in_window = 0
    if WINDOW in pool.layer_kinds:
        in_window = int(np.minimum(after, pool.window).sum())
        span_args["window_rows"] = int(after.size)
        span_args["wrapped_rows"] = int(np.count_nonzero(
            after > pool.ring_len))
    if LATENT in pool.layer_kinds or INDEXED in pool.layer_kinds:
        span_args["latent_rows"] = int(after.size)
    return span_args, started, (in_window, int(after.sum()))


@pytest.mark.parametrize("kind", KINDS)
def test_a_steps_counts_by_kind_are_the_old_inline_arithmetic(kind):
    pool = _pool_of(kind, slots=16)
    rng = np.random.default_rng(KINDS.index(kind))
    seen = set()
    for _ in range(40):
        # free slots, rows that start, chunks, decode rows, rows past the
        # ring (48 columns) and inside the window (32)
        adv = rng.choice([0, 0, 1, 1, 5, 16], 16).astype(np.int32)
        pos = np.where(rng.random(16) < 0.3, 0,
                       rng.integers(0, 100, 16)).astype(np.int32)
        got = pool.step_counts(pos, adv)
        assert got == _old_step_counts(pool, pos, adv)
        assert list(got[0]) == list(_old_step_counts(pool, pos, adv)[0])
        seen.add(got[1] > 0)
        seen.add(("wrapped", got[0].get("wrapped_rows", 0) > 0))
    assert pool.step_counts(np.zeros(16, np.int32), np.zeros(16, np.int32)) \
        == _old_step_counts(pool, np.zeros(16, np.int32),
                            np.zeros(16, np.int32))
    assert (True in seen) == (kind == RECURRENT)
    assert (("wrapped", True) in seen) == (kind == WINDOW)
    keys = {PAGED: [], RECURRENT: ["recurrent_rows"],
            WINDOW: ["window_rows", "wrapped_rows"],
            LATENT: ["latent_rows"], INDEXED: ["latent_rows"]}[kind]
    assert list(got[0]) == keys
