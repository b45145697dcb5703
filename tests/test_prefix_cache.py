"""Prefix-sharing radix KV cache + multi-tenant serving (ISSUE 8):
shared-block-pool accounting (attach/refcount/COW/block ledger),
radix lookup/insert/LRU-eviction, the SimClock
acceptance proof (N shared-prefix requests cost ~1 prefill with streams
bit-identical to cold greedy generate()), the fault-matrix scenarios
(poisoned sibling quarantined without corrupting shared blocks; eviction
under pressure never reclaims a block with live readers), tenant-fair
scheduling + quotas, and the X-Tenant-Id HTTP surface.

Module is auto-marked `prefix` (and `llm`) via tests/conftest.py."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


def _pool(num_slots=4, block_len=4, n_blocks=2):
    import jax.numpy as jnp
    from paddle_tpu.serving.llm import SlotPagedKVPool

    def init_cache(b, max_len):
        return [(jnp.zeros((b, 2, max_len, 3), jnp.float32),
                 jnp.zeros((b, 2, max_len, 3), jnp.float32))]

    return SlotPagedKVPool(init_cache, num_slots, block_len, n_blocks)


def _engine(gpt_tiny, clock, plan=None, **cfg_kw):
    from paddle_tpu import serving
    kw = dict(num_slots=4, block_len=8, n_blocks=6, prefill_chunk=16)
    kw.update(cfg_kw)
    return serving.LLMEngine(gpt_tiny, serving.LLMEngineConfig(**kw),
                             clock=clock, fault_plan=plan)


def _drain_all(eng):
    while eng.has_work():
        eng.pump()


# ---- shared block pool (host-side accounting, fake cache fn) ----

def test_attach_refcount_and_block_ledger():
    p = _pool()                       # 4 slots x 2 blocks of 4 tokens
    s0 = p.allocate(8)
    p.set_length(s0, 8)               # claims 2 own pages
    assert p.stats["blocks_allocated"] == 2 and p.blocks_active() == 2
    p.register_cached(0)
    p.register_cached(1)
    p.free(s0)                        # ownership transfers to the cache
    assert p.blocks_cached() == 2 and p.stats["blocks_freed"] == 0
    p.check_balance()
    s1 = p.allocate(8)
    assert s1 == 1                    # row 0 is pinned by cached pages
    p.attach_blocks(s1, [0, 1])
    assert p.refcount == {0: 1, 1: 1}
    p.set_length(s1, 8)               # fully covered by attached pages
    assert p.stats["blocks_allocated"] == 2   # no new own pages
    assert p.block_table[s1] == [0, 1]
    with pytest.raises(ValueError, match="live reader"):
        p.release_cached(0)           # eviction refused under readers
    p.free(s1)
    assert p.refcount == {}
    p.check_balance()
    p.release_cached(0)
    p.release_cached(1)
    assert p.stats["blocks_freed"] == 2 and p.blocks_cached() == 0
    p.check_balance()
    with pytest.raises(ValueError, match="not cache-registered"):
        s2 = p.allocate(4)
        p.attach_blocks(s2, [0])      # uncached pages cannot be shared


def test_cow_copy_moves_one_block_between_rows():
    import jax.numpy as jnp
    p = _pool(num_slots=2, block_len=4, n_blocks=2)
    s0 = p.allocate(8)
    k, v = p.slabs[0]
    # fill slot 0's SECOND block (cols 4..8) with a recognizable value
    p.slabs[0] = (k.at[s0, :, 4:8].set(7.0), v.at[s0, :, 4:8].set(3.0))
    s1 = p.allocate(4)
    p.cow_copy(s0 * 2 + 1, s1)        # page 1 = slot0/block1
    k, v = p.slabs[0]
    assert float(jnp.abs(k[s1, :, 4:8] - 7.0).max()) == 0.0
    assert float(jnp.abs(v[s1, :, 4:8] - 3.0).max()) == 0.0
    assert float(jnp.abs(k[s1, :, :4]).max()) == 0.0   # only that block
    assert p.stats["cow_copies"] == 1


def test_allocate_skips_pinned_rows_and_calls_pressure_hook():
    from paddle_tpu.serving.llm import SlotsExhaustedError
    p = _pool(num_slots=1, block_len=4, n_blocks=2)
    s = p.allocate(4)
    p.set_length(s, 4)
    p.register_cached(0)
    p.free(s)
    with pytest.raises(SlotsExhaustedError, match="pinned"):
        p.allocate(4)                 # only row is pinned, no hook
    calls = []

    def pressure():
        calls.append(1)
        p.release_cached(0)
        return 1

    p.on_pressure = pressure
    assert p.allocate(4) == 0         # hook evicted, row reusable
    assert calls == [1]
    p.check_balance()


# ---- radix index (fake pool, no model) ----

def test_radix_lookup_insert_tail_and_tenant_namespacing():
    from paddle_tpu.serving.llm import PrefixCache
    p = _pool(num_slots=4, block_len=4, n_blocks=4)
    cache = PrefixCache(p)
    s = p.allocate(10)
    toks = np.arange(100, 110, dtype=np.int32)    # 2 full blocks + 2 tail
    p.set_length(s, 10)
    cache.insert("a", toks, s, [])
    assert cache.cached_blocks("a") == 3          # 2 nodes + 1 tail page
    plan = cache.acquire("a", toks, max_tokens=9)
    assert plan.pages == [s * 4, s * 4 + 1]
    assert plan.tail_page == s * 4 + 2 and plan.tail_len == 1   # cap 9
    assert plan.attach_len == 9
    assert p.refcount[s * 4] == 1 and p.refcount[s * 4 + 2] == 1
    cache.release_tail(plan)
    assert s * 4 + 2 not in p.refcount            # tail ref was transient
    for pg in plan.pages:
        p.release_block(pg)
    # divergent suffix: only the common full blocks match
    other = np.concatenate([toks[:8], [1, 2, 3, 4]]).astype(np.int32)
    plan2 = cache.acquire("a", other, max_tokens=11)
    assert plan2.attach_len == 8 and plan2.tail_page is None
    for pg in plan2.pages:
        p.release_block(pg)
    # tenants never share KV: same tokens, different namespace -> miss
    plan3 = cache.acquire("b", toks, max_tokens=9)
    assert plan3.attach_len == 0 and not plan3.pages
    assert cache.hit_rate("b") == 0.0 and cache.hit_rate("a") > 0.0


def test_lru_eviction_under_pressure_frees_coldest_first():
    from paddle_tpu.serving.llm import PrefixCache
    p = _pool(num_slots=2, block_len=4, n_blocks=2)
    cache = PrefixCache(p)            # wires itself as on_pressure
    s0 = p.allocate(8)
    old = np.arange(0, 8, dtype=np.int32)
    p.set_length(s0, 8)
    cache.insert("t", old, s0, [])
    p.free(s0)
    s1 = p.allocate(8)
    assert s1 == 1
    new = np.arange(100, 108, dtype=np.int32)
    p.set_length(s1, 8)
    cache.insert("t", new, s1, [])
    p.free(s1)
    # every row pinned; allocation pressure must evict the LRU ('old')
    # entry's pages and leave the recently-touched 'new' chain alone
    s2 = p.allocate(8)
    assert s2 == 0                    # old chain lived in row 0
    assert cache.stats["evictions"] == 2
    assert cache.acquire("t", old, max_tokens=7).attach_len == 0
    plan = cache.acquire("t", new, max_tokens=7)
    assert plan.attach_len == 7       # survivor chain intact (1 block+tail)
    p.check_balance()


# ---- the LRU order against the tree scan it replaced (ISSUE 40) ----

def _scan_lru_victim(cache):
    """The pressure path's victim as it was found until PR 39, kept here as
    the oracle: a depth-first walk of every tenant's tree for the smallest
    tick among refcount-0 tails and refcount-0 leaves (no children, no
    tail), each with the block's full token path (the host tier's key)."""
    best = None   # (tick, kind, tenant, node_or_parent, key, path)
    for tenant, root in cache._roots.items():
        stack = [(root, None, None, ())]
        while stack:
            node, parent, key, path = stack.pop()
            if (node.tail_page is not None
                    and cache.pool.refcount.get(node.tail_page, 0) == 0):
                cand = (node.tail_tick, "tail", tenant, node, None, path)
                if best is None or cand[0] < best[0]:
                    best = cand
            if (parent is not None and not node.children
                    and node.tail_page is None
                    and cache.pool.refcount.get(node.page, 0) == 0):
                cand = (node.tick, "node", tenant, parent, key, path)
                if best is None or cand[0] < best[0]:
                    best = cand
            for k, c in node.children.items():
                stack.append((c, node, k, path + k))
    return best


def _scan_candidates(cache):
    """(tick, page) of every tail and every leaf, readers or none: what
    the order has to hold."""
    out = set()
    for root in cache._roots.values():
        stack = [(root, None)]
        while stack:
            node, parent = stack.pop()
            if node.tail_page is not None:
                out.add((node.tail_tick, node.tail_page))
            elif parent is not None and not node.children:
                out.add((node.tick, node.page))
            stack.extend((c, node) for c in node.children.values())
    return out


class _History:
    """A seeded history of what an engine does to its cache (admit: probe
    the row, allocate under pressure, acquire, attach, release; index the
    finished prefill; free the slot) and what its operators do (a reader
    held across admissions, `evict_row`, `clear(only=)`), every pressure
    call checked against the scan, drop by drop."""

    BL, NB, SLOTS = 4, 6, 5

    def __init__(self, seed, tenants, host):
        from paddle_tpu.serving.llm import HostKVPool, PrefixCache
        self.rng = np.random.default_rng(seed)
        self.tenants = tenants
        self.pool = _pool(self.SLOTS, self.BL, self.NB)
        self.puts = []
        host_pool = None
        if host:
            class Recorder(HostKVPool):
                def put(_, tenant, path, layers):
                    self.puts.append((tenant, tuple(path)))
                    return super().put(tenant, path, layers)
            host_pool = Recorder(1 << 20, self.BL)
        self.cache = PrefixCache(self.pool, host_pool=host_pool)
        self.live = []        # [tenant, prompt, slot, indexed]
        self.plans = []       # (tenant, plan): readers held across calls
        self.prompts = []     # (tenant, prompt) seen so far
        self.victims = 0      # pressure-path drops, each checked
        self.set_aside = 0    # pressure calls that met a candidate with a reader
        self._want = []       # the scan's keys for the puts still to come
        self._wrap()

    def _wrap(self):
        cache, pool = self.cache, self.pool
        drop, pressure = cache._drop, cache.evict_for_pressure

        def checked_drop(tenant, holder, key, spill=False):
            if spill:
                assert not pool.has_allocatable_row()
                want = _scan_lru_victim(cache)
                assert want is not None
                _, _, w_tenant, w_holder, w_key, w_path = want
                assert (w_tenant, w_key) == (tenant, key)
                assert w_holder is holder
                if key is not None and cache.host_pool is not None:
                    self._want.append((tenant, w_path))
                self.victims += 1
            drop(tenant, holder, key, spill)

        def checked_pressure(keep_below=0, rows=None):
            if rows is None and any(
                    pool.refcount.get(page, 0) > 0
                    for _, page in _scan_candidates(cache)):
                self.set_aside += 1
            n = pressure(keep_below, rows)
            if rows is None:
                # the stop rule: a row came free, or the scan is out of
                # victims as well
                assert (pool.has_allocatable_row()
                        or _scan_lru_victim(cache) is None)
            assert self.puts == self._want
            return n

        cache._drop = checked_drop
        cache.evict_for_pressure = pool.on_pressure = checked_pressure

    def _prompt(self):
        rng, cap = self.rng, self.BL * self.NB - 2
        tenant = self.tenants[int(rng.integers(len(self.tenants)))]
        mine = [p for t, p in self.prompts if t == tenant]
        kind = rng.random()
        if mine and kind < 0.25:                       # an exact duplicate
            tokens = mine[int(rng.integers(len(mine)))]
        elif mine and kind < 0.7:     # a shared prefix, another remainder
            base = mine[int(rng.integers(len(mine)))]
            keep = int(rng.integers(1, len(base) + 1))
            more = int(rng.integers(0, cap - keep + 1))
            tokens = np.concatenate(
                [base[:keep], rng.integers(1, 5, (more,))]).astype(np.int32)
        else:
            tokens = rng.integers(1, 5, (int(rng.integers(1, cap + 1)),)
                                  ).astype(np.int32)
        self.prompts.append((tenant, tokens))
        return tenant, tokens

    def admit(self):
        from paddle_tpu.serving.llm import SlotsExhaustedError
        cache, pool = self.cache, self.pool
        tenant, tokens = self._prompt()
        keep_below, prefer = cache.probe_row(tenant, tokens, len(tokens) - 1)
        try:
            slot = pool.allocate(len(tokens) + 1, keep_below, prefer)
        except SlotsExhaustedError:
            return
        plan = cache.acquire(tenant, tokens, len(tokens) - 1)
        assert len(plan.pages) >= keep_below
        if plan.pages:
            pool.attach_blocks(slot, plan.pages)
        cache.release(plan)
        pool.set_length(slot, len(tokens))
        self.live.append([tenant, tokens, slot, False])

    def index(self):
        todo = [r for r in self.live if not r[3]]
        if todo:
            req = todo[int(self.rng.integers(len(todo)))]
            tenant, tokens, slot, _ = req
            self.cache.insert(tenant, tokens, slot,
                              self.pool._attached.get(slot, []))
            req[3] = True

    def free(self):
        if self.live:
            req = self.live.pop(int(self.rng.integers(len(self.live))))
            if not req[3] and self.rng.random() < 0.8:
                self.live.append(req)
                return self.index()
            self.pool.free(req[2])

    def hold(self):
        if self.prompts and len(self.plans) < 3:
            tenant, tokens = self.prompts[
                int(self.rng.integers(len(self.prompts)))]
            self.plans.append(
                (tenant, self.cache.acquire(tenant, tokens, len(tokens) - 1)))

    def unhold(self):
        if self.plans:
            _, plan = self.plans.pop(int(self.rng.integers(len(self.plans))))
            self.cache.release(plan)

    def evict_row(self):
        free = np.flatnonzero(~self.pool.active)
        if free.size:
            self.cache.evict_row(int(free[self.rng.integers(free.size)]),
                                 int(self.rng.integers(self.NB)))

    def clear_only(self):
        # the caller holds that namespace idle (a hot swap's contract)
        gone = self.tenants[-1]
        if all(r[0] != gone for r in self.live) \
                and all(t != gone for t, _ in self.plans):
            self.cache.clear(only=lambda t: t == gone)

    def pressure(self):
        self.cache.evict_for_pressure()

    def run(self, steps):
        ops = [self.admit] * 8 + [self.index] * 4 + [self.free] * 6 + [
            self.hold, self.hold, self.unhold, self.unhold, self.evict_row,
            self.pressure, self.clear_only]
        cache = self.cache
        for step in range(steps):
            ops[int(self.rng.integers(len(ops) - (step % 40 != 39)))]()
            self.pool.check_balance()
            # no entry is ever lost to the lazy structure, and the order
            # stays the size of what is cached
            assert _scan_candidates(cache) <= set(cache._order)
            assert len(cache._order) <= 2 * cache.stats["cached_blocks"] + 64
        for _, plan in self.plans:
            cache.release(plan)
        for _, _, slot, _ in self.live:
            self.pool.free(slot)
        # drain: every row handed to a fresh sequence at once, so what is
        # left goes, in the scan's order, to the last page
        self.plans, self.live = [], []
        for slot in [self.pool.allocate(1) for _ in range(self.SLOTS)]:
            self.pool.free(slot)
        self.pool.check_balance()
        assert not cache._where and not self.pool.cached


@pytest.mark.parametrize("seed,tenants,host", [
    (0, ("a",), False), (1, ("a",), True), (2, ("a", "b"), False),
    (3, ("a", "b"), True), (4, ("a",), False), (5, ("a", "b"), True),
    (6, ("a", "b"), False), (7, ("a",), True)])
def test_pressure_victims_are_the_tree_scans(seed, tenants, host):
    """The pressure path's victims, their order and the host tier's keys
    are the old scan's at every drop of a seeded history, and the order
    holds every candidate the scan can find."""
    h = _History(seed, tenants, host)
    h.run(600)
    stats = h.cache.stats
    assert h.victims > 100 and h.set_aside > 0
    assert stats["evict_pops"] >= h.victims
    assert stats["evict_pops"] > stats["evict_stale"] > 0
    assert h.cache.snapshot()["evict_pops"] == stats["evict_pops"]
    if host:
        assert len(h.puts) > 20 and h.cache.spilled_pages == len(h.puts)


def _counting_nodes(monkeypatch):
    """Every read of a node's `children` counted: what a walk cannot do
    without."""
    from paddle_tpu.serving.llm import prefix_cache
    plain = prefix_cache._Node

    class Counted(plain):
        __slots__ = ()
        reads = 0

        @property
        def children(self):
            Counted.reads += 1
            return plain.children.__get__(self)

        @children.setter
        def children(self, value):
            plain.children.__set__(self, value)

    monkeypatch.setattr(prefix_cache, "_Node", Counted)
    return Counted


def test_a_freed_page_costs_pops_not_a_walk(monkeypatch):
    """`mistral-7b.serve-prefill-cached`'s shape: 32 rows of 65 pages of
    16, a 256-1,024-token prompt pinning every row, nothing shared. An
    admission frees some thirty pages; each is a pop or two from the order
    and a constant number of nodes, never the trees (≈1,000 nodes here).
    Counters, no clock."""
    from paddle_tpu.serving.llm import PrefixCache
    nodes = _counting_nodes(monkeypatch)
    pool = _pool(num_slots=32, block_len=16, n_blocks=65)
    cache = PrefixCache(pool)
    rng = np.random.default_rng(40)
    in_pressure = [0]
    pressure = cache.evict_for_pressure

    def counted(*a, **kw):
        before = nodes.reads
        n = pressure(*a, **kw)
        in_pressure[0] += nodes.reads - before
        return n

    pool.on_pressure = counted

    def admission():
        tokens = rng.integers(1, 30000, (int(np.exp(rng.uniform(
            np.log(256), np.log(1024)))),)).astype(np.int32)
        slot = pool.allocate(len(tokens) + 16)
        cache.release(cache.acquire("t", tokens, len(tokens) - 1))
        pool.set_length(slot, len(tokens))
        cache.insert("t", tokens, slot, [])
        pool.free(slot)

    for _ in range(32):
        admission()
    assert cache.stats["evictions"] == 0 and not pool.has_allocatable_row()
    assert cache.stats["cached_blocks"] > 900
    for _ in range(40):
        admission()
    evictions, pops = cache.stats["evictions"], cache.stats["evict_pops"]
    assert evictions > 40 * 16            # a prompt's pages an admission
    assert pops <= 2 * evictions + 64
    # a pop reads its holder's children and its own, a drop unlinks and
    # looks at what it left: four reads a pop bound it, a walk would read
    # a thousand nodes a page
    assert in_pressure[0] <= 4 * pops
    pool.check_balance()


def test_a_lookup_over_a_long_chain_offers_one_entry(monkeypatch):
    """A session's history is a chain of interior nodes: `acquire` re-ticks
    2,000 of them and offers the order the one that is a candidate."""
    from paddle_tpu.serving.llm import PrefixCache
    pool = _pool(num_slots=2, block_len=4, n_blocks=2002)
    cache = PrefixCache(pool)
    offers = []
    offer = cache._offer
    monkeypatch.setattr(cache, "_offer",
                        lambda tick, page: (offers.append(page),
                                            offer(tick, page)))
    tokens = np.arange(1, 8003, dtype=np.int32)      # 2,000 blocks + 2
    slot = pool.allocate(8008)
    pool.set_length(slot, len(tokens))
    cache.insert("t", tokens, slot, [])
    assert offers == [slot * 2002 + 2000]            # the tail alone
    pool.free(slot)
    plan = cache.acquire("t", tokens, len(tokens) - 1)
    assert len(plan.pages) == 2000 and plan.tail_len == 1
    assert offers == [slot * 2002 + 2000] * 2
    cache.release(plan)
    plan = cache.acquire("t", tokens[:8000], 7999)   # ends inside the chain
    assert len(offers) == 2 and len(cache._order) == 2
    cache.release(plan)
    pool.check_balance()


# ---- SimClock acceptance: N shared-prefix requests ~ 1 prefill ----

def test_shared_prefix_requests_cost_one_prefill_bit_identically(gpt_tiny):
    """8 requests sharing a 32-token prefix (4 blocks) with unique 8-token
    suffixes: the first pays a full 40-token prefill, every later one
    attaches the cached blocks and prefills exactly its 8-token suffix —
    total prefilled tokens 40 + 7*8 = 96 vs 320 cold — and every stream
    is bit-identical to cold-path batch-locked greedy generate()."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate

    rng = np.random.RandomState(7)
    shared = rng.randint(1, 500, size=32).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.randint(1, 500, size=8).astype(np.int32)])
        for _ in range(8)]
    NEW = 4
    ref = np.asarray(generate(gpt_tiny, np.stack(prompts),
                              max_new_tokens=NEW).numpy())[:, 40:]

    clock = serving.SimClock()
    eng = _engine(gpt_tiny, clock)
    h0 = eng.submit(prompts[0], max_new_tokens=NEW)
    _drain_all(eng)                   # donor completes -> prefix cached
    assert eng.prefill_tokens == 40
    handles = [eng.submit(pr, max_new_tokens=NEW) for pr in prompts[1:]]
    _drain_all(eng)
    # ~1 prefill total: donor's 40 tokens + 7 x 8-token suffixes
    assert eng.prefill_tokens == 40 + 7 * 8
    assert eng.prefill_tokens <= 0.35 * sum(len(p) for p in prompts)
    for h, r in zip([h0] + handles, ref):
        assert np.array_equal(h.result(timeout=0), r)
    snap = eng.metrics.snapshot()
    assert snap["prefix_hits"] == 7 and snap["prefix_misses"] == 1
    assert snap["prefix_hit_tokens"] == 7 * 32
    assert snap["prefix_hit_rate"] == pytest.approx(224 / 320)
    assert snap["cached_blocks"] >= 5
    eng.pool.check_balance()
    eng.stop()


def test_full_hit_duplicate_prompt_prefills_one_token(gpt_tiny):
    """An exact-duplicate prompt can't skip ALL prefill (the last token's
    step produces the first output logits): the cache attaches 4 full
    blocks, COWs 7 tokens of the 5th into the slot's own page, and the
    engine prefills exactly 1 token — TTFT = one chunk-wide step — with
    the warm stream bit-identical to the cold one."""
    from paddle_tpu import serving

    rng = np.random.RandomState(11)
    prompt = rng.randint(1, 500, size=40).astype(np.int32)
    eng = _engine(gpt_tiny, serving.SimClock())
    cold = eng.submit(prompt, max_new_tokens=4)
    _drain_all(eng)
    base = eng.prefill_tokens
    warm = eng.submit(prompt, max_new_tokens=4)
    _drain_all(eng)
    assert eng.prefill_tokens - base == 1
    assert eng.pool.stats["cow_copies"] == 1
    assert np.array_equal(warm.result(timeout=0), cold.result(timeout=0))
    eng.pool.check_balance()
    eng.stop()


# ---- fault matrix (ISSUE 8 scenarios) ----

@pytest.mark.fault_matrix
def test_poisoned_sibling_quarantined_without_corrupting_shared_blocks(
        gpt_tiny):
    """Two requests attach the same cached prefix; one is poisoned
    mid-decode. The poisoned request is quarantined, the sibling's FULL
    stream through the shared blocks stays bit-identical to a fault-free
    run, the shared pages survive (no eviction, donor KV intact — a
    LATER request still attaches them bit-identically), and both the
    slot and block ledgers balance."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate
    from paddle_tpu.utils.fault_injection import FaultPlan

    rng = np.random.RandomState(3)
    shared = rng.randint(1, 500, size=32).astype(np.int32)
    mk = lambda: np.concatenate(  # noqa: E731
        [shared, rng.randint(1, 500, size=8).astype(np.int32)])
    donor_p, surv_p, pois_p, late_p = mk(), mk(), mk(), mk()
    ref_surv = np.asarray(generate(gpt_tiny, surv_p[None, :],
                                   max_new_tokens=6).numpy())[0, 40:]
    ref_late = np.asarray(generate(gpt_tiny, late_p[None, :],
                                   max_new_tokens=6).numpy())[0, 40:]

    plan = FaultPlan.from_spec("poison_request@2:decode")
    eng = _engine(gpt_tiny, serving.SimClock(), plan=plan)
    eng.submit(donor_p, max_new_tokens=2)          # idx 0: seeds the cache
    _drain_all(eng)
    survivor = eng.submit(surv_p, max_new_tokens=6)   # idx 1, attaches
    poisoned = eng.submit(pois_p, max_new_tokens=6)   # idx 2, attaches
    _drain_all(eng)
    with pytest.raises(serving.DispatchFailedError) as exc:
        poisoned.result(timeout=0)
    assert exc.value.reason == "poisoned"
    assert np.array_equal(survivor.result(timeout=0), ref_surv)
    # quarantine freed the poisoned slot's refcounts but evicted nothing
    assert eng.prefix_cache.stats["evictions"] == 0
    assert not eng.pool.refcount                   # all readers released
    late = eng.submit(late_p, max_new_tokens=6)    # idx 3: cache still hot
    _drain_all(eng)
    assert np.array_equal(late.result(timeout=0), ref_late)
    snap = eng.metrics.snapshot()
    assert snap["quarantined"] == 1 and snap["prefix_hits"] == 3
    assert snap["submitted"] == (snap["completed"] + snap["rejected"]
                                 + snap["expired"] + snap["failed"])
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


@pytest.mark.fault_matrix
def test_eviction_under_pressure_never_reclaims_live_readers(gpt_tiny):
    """Slot pressure with every free row pinned: eviction may reclaim
    refcount-0 cached pages, but a page a live stream attached must
    survive — the reader's stream stays bit-identical — and once readers
    drain, pressure eviction proceeds and the block ledger balances."""
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate

    rng = np.random.RandomState(5)
    pA = rng.randint(1, 500, size=16).astype(np.int32)    # 2 blocks
    pC = rng.randint(1, 500, size=16).astype(np.int32)
    ref_b = np.asarray(generate(gpt_tiny, pA[None, :],
                                max_new_tokens=8).numpy())[0, 16:]

    eng = _engine(gpt_tiny, serving.SimClock(), num_slots=2, n_blocks=4)
    eng.submit(pA, max_new_tokens=2)        # donor: caches pA's 2 blocks
    _drain_all(eng)
    rb = eng.submit(pA, max_new_tokens=8)   # attaches block 0 (+COW tail)
    eng.pump()                              # admit + prefill: refcount live
    shared_page = rb_attached = None
    with eng._cond:
        (slot_b, req_b), = eng._active.items()
        rb_attached = list(req_b.attached_pages)
    assert len(rb_attached) == 1
    shared_page = rb_attached[0]
    assert eng.pool.refcount[shared_page] == 1
    # pressure: rC needs a row; row0 pinned, row1 is rb's. Eviction may
    # only take refcount-0 pages — the attached page must survive.
    rc = eng.submit(pC, max_new_tokens=2)
    eng.pump()
    assert shared_page in eng.pool.cached           # live reader: kept
    assert eng.pool.refcount.get(shared_page) == 1
    _drain_all(eng)                                 # rb finishes, rc runs
    assert np.array_equal(rb.result(timeout=0), ref_b)
    assert np.array_equal(rc.result(timeout=0)[:2],
                          np.asarray(generate(
                              gpt_tiny, pC[None, :],
                              max_new_tokens=2).numpy())[0, 16:])
    assert eng.prefix_cache.stats["evictions"] >= 1
    # the order's counters reach the engine's metrics: the reader's page
    # was popped, set aside and handed back, so pops outnumber evictions
    snap = eng.metrics.snapshot()
    assert snap["cache_evict_pops"] == \
        eng.prefix_cache.stats["evict_pops"] > snap["cache_evictions"]
    assert snap["cache_evict_stale"] == \
        eng.prefix_cache.stats["evict_stale"]
    eng.pool.check_balance()
    assert eng.pool.active_slots() == 0
    eng.stop()


# ---- multi-tenant scheduling ----

def test_tenant_namespacing_isolates_kv_but_not_correctness(gpt_tiny):
    from paddle_tpu import serving

    rng = np.random.RandomState(9)
    prompt = rng.randint(1, 500, size=16).astype(np.int32)
    eng = _engine(gpt_tiny, serving.SimClock())
    ha = eng.submit(prompt, max_new_tokens=3, tenant="acme")
    _drain_all(eng)
    hb = eng.submit(prompt, max_new_tokens=3, tenant="bravo")
    _drain_all(eng)
    # same prompt, same greedy output — but bravo MISSED the cache:
    # tenants never share KV, so it paid its own full prefill
    assert np.array_equal(ha.result(timeout=0), hb.result(timeout=0))
    assert eng.prefill_tokens == 2 * len(prompt)
    snap = eng.metrics.snapshot()
    assert snap["tenants"]["acme"]["prefix_misses"] == 1
    assert snap["tenants"]["bravo"]["prefix_misses"] == 1
    assert snap["tenants"]["bravo"]["prefix_hit_tokens"] == 0
    assert eng.prefix_cache.cached_blocks("acme") == 2
    assert eng.prefix_cache.cached_blocks("bravo") == 2
    eng.pool.check_balance()
    eng.stop()


def test_tenant_quota_rejects_typed_without_starving_others(gpt_tiny):
    from paddle_tpu import serving

    rng = np.random.RandomState(13)
    prompt = rng.randint(1, 500, size=16).astype(np.int32)   # cost 20
    eng = _engine(gpt_tiny, serving.SimClock(),
                  tenant_max_inflight_tokens=50)
    eng.submit(prompt, max_new_tokens=4, tenant="hog")
    eng.submit(prompt, max_new_tokens=4, tenant="hog")
    with pytest.raises(serving.RejectedError) as exc:
        eng.submit(prompt, max_new_tokens=4, tenant="hog")
    assert exc.value.reason == "tenant_quota"
    assert exc.value.retry_after_s is not None
    # another tenant is unaffected by hog's quota exhaustion
    eng.submit(prompt, max_new_tokens=4, tenant="polite")
    assert eng.metrics.reject_reasons.get("tenant_quota") == 1
    assert eng.metrics.tenants["hog"]["rejected"] == 1
    _drain_all(eng)
    eng.pool.check_balance()
    eng.stop()


def test_tenant_fair_dequeue_within_slo_class(gpt_tiny):
    """Two slots held by tenant A, another A request queued FIRST and a
    B request queued last: when a slot frees while A still occupies the
    other, fair dequeue picks B (zero active usage), not FIFO's next A."""
    from paddle_tpu import serving

    rng = np.random.RandomState(17)
    mk = lambda: rng.randint(1, 500, size=8).astype(np.int32)  # noqa: E731
    eng = _engine(gpt_tiny, serving.SimClock(), num_slots=2,
                  enable_prefix_cache=False)
    a1 = eng.submit(mk(), max_new_tokens=2, tenant="a")
    eng.submit(mk(), max_new_tokens=8, tenant="a")
    eng.pump()                        # a1 + a2 take both slots
    with eng._cond:
        assert sorted(r.tenant for r in eng._active.values()) == ["a", "a"]
    eng.submit(mk(), max_new_tokens=6, tenant="a")    # FIFO-next
    hb = eng.submit(mk(), max_new_tokens=6, tenant="b")
    while not a1.future.done():
        eng.pump()
    eng.pump()                        # a1's slot refills here
    with eng._cond:
        active = sorted(r.tenant for r in eng._active.values())
        queued = [r.tenant for q in eng._queues.values() for r in q]
    assert active == ["a", "b"]       # fairness beat FIFO
    assert queued == ["a"]            # a3 still waits its turn
    _drain_all(eng)
    assert hb.future.done()
    eng.pool.check_balance()
    eng.stop()


# ---- HTTP surface (X-Tenant-Id, per-tenant observability) ----

def _post(url, payload, headers=None, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_http_tenant_header_and_per_tenant_observability(gpt_tiny):
    from paddle_tpu import serving
    from paddle_tpu.serving.server import _RETRYABLE_REJECTS

    assert "tenant_quota" in _RETRYABLE_REJECTS   # 429 + Retry-After
    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=2, block_len=8,
                                          n_blocks=4, prefill_chunk=16))
    srv = serving.ServingServer(llm_engine=eng, port=0).start()
    base = f"http://{srv.host}:{srv.port}"
    try:
        prompt = list(range(1, 13))
        code, out = _post(f"{base}/generate",
                          {"input_ids": prompt, "max_new_tokens": 2},
                          headers={"X-Tenant-Id": "alpha"})
        assert code == 200 and len(out["tokens"]) == 2
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(f"{base}/generate",
                  {"input_ids": prompt, "max_new_tokens": 2},
                  headers={"X-Tenant-Id": "bad tenant!"})
        assert exc.value.code == 400
        assert "X-Tenant-Id" in json.loads(exc.value.read())["error"]
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert ('pdtpu_llm_tenant_requests_total{tenant="alpha",'
                'outcome="submitted"} 1') in text
        assert 'pdtpu_llm_tenant_cache_hit_rate{tenant="alpha"}' in text
        assert "pdtpu_llm_prefix_misses_total 1" in text
        assert "pdtpu_llm_cached_blocks" in text
        assert "pdtpu_llm_cache_evict_pops_total 0" in text
        assert "pdtpu_llm_cache_evict_stale_total 0" in text
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert "alpha" in health["llm_tenants"]
        t = health["llm_tenants"]["alpha"]
        assert {"cache_hit_rate", "cached_blocks",
                "inflight_tokens"} <= set(t)
    finally:
        srv.stop()
    eng.pool.check_balance()
