"""Radix prefix cache over shared KV blocks (ISSUE 8 tentpole).

A per-tenant radix/trie index over token prefixes, one level per full
`block_len`-token chunk, each node naming the global KV page that holds
that chunk's keys/values. On admission the engine looks the prompt up:
every matched full block is ATTACHED (the new slot's block table points
at the donor's physical pages, refcounted for the reader's lifetime) and
a matched *partial* block — a trie tail, or a full block truncated by
the always-prefill-one-token cap — is COPY-ON-WRITten into the slot's
own page so the divergent suffix can append in place. The engine then
chunk-prefills only the uncovered suffix: at a full hit TTFT collapses
to one chunk-wide step, and N requests sharing a prefix cost ~1
prefill's worth of prefill work in total.

Correctness lever: chunked prefill is bit-invariant to chunking (PR 7),
and a row's KV depends only on that row's own tokens/positions, so KV
attached from a donor row — or COW-copied out of one — is bitwise the KV
the request would have computed itself. Warm streams are therefore
bit-identical to cold-path greedy `generate()`.

Lifecycle and safety:

- Pages enter the cache only when their prefill COMPLETED (the blocks
  provably hold the full chunk's KV); insertion registers them with the
  pool (`register_cached`), pinning the owning row against reallocation.
- Readers take a refcount per attached page (`SlotPagedKVPool.refcount`)
  held until the reader's slot frees. Eviction refuses refcount>0 pages
  structurally — `release_cached` raises — so cache pressure can never
  reclaim a block out from under a live stream.
- Eviction is LRU over refcount-0 leaves and tails (a deterministic
  monotonic tick, no wall clock), driven by the pool's `on_pressure`
  hook from inside `allocate()`: evict just enough to unpin one row.
  The victims come from `_order`, a min-heap of `(tick, page)` that the
  cache keeps as it goes: an entry is pushed when it becomes a candidate
  (an insert's terminal leaf or tail; a parent whose last child or tail
  `_drop` just took) or is touched while one (an `acquire` that ends on
  a leaf or matches a tail), and is checked when it is popped (still
  indexed, still childless or still the tail, tick unchanged). A freed
  page costs a few heap operations, whatever the trees hold.
- A request that attaches its leading blocks needs a row only from the
  block behind them (`SlotPagedKVPool.allocate(keep_below=)`): pressure
  then clears *one free row* from that block on (`evict_row`: the pages
  of that row at or above the block, deepest first, found through
  `_where`, no walk of the trie): the row the attached pages live in if it
  can be cleared (asked for first), else the row that costs the fewest
  pages. A session that starts over from its history so drops its own
  stale turns and nobody's history.
- Tenant namespacing is structural: each tenant gets its own root, so
  one tenant's prompts can never attach another tenant's KV.

The index is host-side pure-python bookkeeping — dict hops per block, no
device work — sized by cached blocks, not tokens.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...profiler import SPAN_SERVE_EVICT, RecordEvent
from .kv_pool import SlotPagedKVPool


class _Node:
    """One radix node = one full cached block. `children` is keyed by the
    next block's token tuple; `page` is the global KV page holding THIS
    node's block (None only at roots). A node may also carry one cached
    partial-block `tail` — the sub-block remainder of some inserted
    prompt — usable by COW up to its longest common prefix with a new
    prompt's remainder."""

    __slots__ = ("children", "page", "tick",
                 "tail_tokens", "tail_page", "tail_tick")

    def __init__(self, page: Optional[int] = None):
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.page = page
        self.tick = 0
        self.tail_tokens: Optional[Tuple[int, ...]] = None
        self.tail_page: Optional[int] = None
        self.tail_tick = 0


class AttachPlan:
    """Result of a cache lookup, increfs already taken.

    `pages` back the prompt's leading full blocks (held until the
    reader's slot frees — `SlotPagedKVPool.free` drops them). `tail_page`
    holds `tail_len` further tokens to COW into the slot's own page; its
    refcount is transient — release via `PrefixCache.release_tail` right
    after the copy. `attach_len = len(pages) * block_len + tail_len` is
    the number of prompt tokens the engine may skip prefilling."""

    __slots__ = ("pages", "attach_len", "tail_page", "tail_len")

    def __init__(self, pages: List[int], attach_len: int,
                 tail_page: Optional[int], tail_len: int):
        self.pages = pages
        self.attach_len = attach_len
        self.tail_page = tail_page
        self.tail_len = tail_len


def _tenant_stats() -> dict:
    return {"hits": 0, "misses": 0, "hit_tokens": 0, "lookup_tokens": 0,
            "insertions": 0, "evictions": 0, "cached_blocks": 0}


class PrefixCache:
    """Per-tenant radix index over cached KV pages in a SlotPagedKVPool.

    Constructing the cache wires itself as the pool's `on_pressure` hook
    so allocation pressure transparently evicts cold entries.

    `name` labels which pool this cache fronts (ISSUE 17: the engine runs
    a "target" cache and, with a draft model attached, a parallel "draft"
    cache over the draft pool — both tries are keyed by the same prompt
    tokens and the same page-aligned block_len, so a prompt that warm-hits
    on the target side attaches the congruent draft pages too and the
    draft skips re-prefilling the shared prefix)."""

    def __init__(self, pool: SlotPagedKVPool, name: str = "target",
                 host_pool=None, clock=None):
        self.pool = pool
        self.name = name
        self.block_len = pool.block_len
        self._roots: Dict[str, _Node] = {}
        # page -> (tenant, the node that names it, the key it hangs under
        # in that node, or None for the node's tail)
        self._where: Dict[int, Tuple[str, _Node, Optional[tuple]]] = {}
        self._tick = 0
        # the LRU order: (tick, page) of every entry that is evictable but
        # for its readers, and of entries that were (checked on pop)
        self._order: List[Tuple[int, int]] = []
        # `evict_pops`: entries the pressure path took from the order;
        # `evict_stale`: those of them that were no longer candidates
        self.stats = {**_tenant_stats(), "evict_pops": 0, "evict_stale": 0}
        self.tenant_stats: Dict[str, dict] = {}
        # ISSUE 19 spill tier: when a HostKVPool is attached, pressure
        # eviction of a refcount-0 FULL block serializes its page to host
        # RAM (keyed by tenant + full token path) before releasing it, so
        # a later admission can re-onboard it instead of re-prefilling.
        # Tails (partial blocks) are dropped as before — see host_kv.py.
        self.host_pool = host_pool
        # optional clock (engine passes clock.now) so spill copy time is
        # attributable: the engine books the delta into the ledger's
        # `kv_spill` phase each pump
        self.clock = clock
        self.spill_seconds = 0.0
        self.spilled_pages = 0
        pool.on_pressure = self.evict_for_pressure

    def _ts(self, tenant: str) -> dict:
        return self.tenant_stats.setdefault(tenant, _tenant_stats())

    # ---- lookup ----
    def acquire(self, tenant: str, tokens, max_tokens: int) -> AttachPlan:
        """Match `tokens` against the tenant's trie and take refcounts on
        every matched page. `max_tokens` caps the covered length — the
        engine passes len(prompt)-1 so at least one prompt token is
        always prefilled (the step that produces the first output
        token's logits). A full matched block pushed over the cap
        becomes a partially-used COW tail, which is what makes an
        exact-duplicate prompt still cost only a one-token prefill."""
        self._tick += 1
        ts = self._ts(tenant)
        n = len(tokens)
        ts["lookup_tokens"] += n
        self.stats["lookup_tokens"] += n
        bl = self.block_len
        node = self._roots.get(tenant)
        chain: List[int] = []
        i = 0
        if node is not None:
            while i + bl <= n:
                child = node.children.get(
                    tuple(int(t) for t in tokens[i:i + bl]))
                if child is None:
                    break
                child.tick = self._tick
                chain.append(child.page)
                node = child
                i += bl
        n_full = min(len(chain), max(0, int(max_tokens)) // bl)
        pages = chain[:n_full]
        attach_len = n_full * bl
        tail_page: Optional[int] = None
        tail_len = 0
        if n_full < len(chain):
            # next matched block exists but the cap truncates it
            u = int(max_tokens) - attach_len
            if u > 0:
                tail_page = chain[n_full]
                tail_len = u
        elif node is not None and node.tail_tokens is not None:
            rem = [int(t) for t in tokens[attach_len:]]
            m = 0
            for a, b in zip(node.tail_tokens, rem):
                if a != b:
                    break
                m += 1
            u = min(m, int(max_tokens) - attach_len)
            if u > 0:
                tail_page = node.tail_page
                tail_len = u
                node.tail_tick = self._tick
                self._offer(self._tick, tail_page)
        if chain:
            # of the nodes this lookup re-ticked only the last can be a
            # leaf: the others have it, or its ancestors, below them
            self._offer_leaf(node)
        hit_tokens = attach_len + tail_len
        if hit_tokens > 0:
            ts["hits"] += 1
            self.stats["hits"] += 1
            ts["hit_tokens"] += hit_tokens
            self.stats["hit_tokens"] += hit_tokens
        else:
            ts["misses"] += 1
            self.stats["misses"] += 1
        for p in pages:
            self.pool.refcount[p] = self.pool.refcount.get(p, 0) + 1
        if tail_page is not None:
            self.pool.refcount[tail_page] = \
                self.pool.refcount.get(tail_page, 0) + 1
        return AttachPlan(pages, attach_len + tail_len, tail_page, tail_len)

    def _match(self, tenant: str, tokens, blocks: Optional[int] = None):
        """Read-only walk: (the full blocks of `tokens` the tenant's trie
        holds, at most `blocks`; the node of the last of them, or None)."""
        node = self._roots.get(tenant)
        bl = self.block_len
        limit = len(tokens) // bl
        if blocks is not None:
            limit = min(limit, blocks)
        i, last = 0, None
        while node is not None and i < limit:
            node = node.children.get(
                tuple(int(t) for t in tokens[i * bl:(i + 1) * bl]))
            if node is not None:
                i, last = i + 1, node
        return i, last

    def probe(self, tenant: str, tokens) -> int:
        """Read-only lookup: the longest block-aligned cached prefix of
        `tokens` in the tenant's trie, in tokens. Unlike `acquire` it
        takes no refcounts and touches no ticks or stats — the router
        probes every candidate replica per admission, and a probe must
        never distort LRU order or hit-rate accounting, let alone pin
        pages on replicas that lose the election."""
        return self._match(tenant, tokens)[0] * self.block_len

    def probe_row(self, tenant: str, tokens, max_tokens: int):
        """Read-only, before a row is chosen: (the full blocks `acquire`
        will attach under the same cap, the pool row the last of them
        lives in or None). What `SlotPagedKVPool.allocate` needs to hand
        out a row that keeps its cached pages below those blocks."""
        i, last = self._match(tenant, tokens,
                              max(0, int(max_tokens)) // self.block_len)
        return i, None if last is None else last.page // self.pool.n_blocks

    def release_tail(self, plan: AttachPlan):
        """Drop the transient tail refcount once its KV has been COW'd
        into the reader's own page."""
        if plan.tail_page is not None:
            self.pool.release_block(plan.tail_page)
            plan.tail_page = None

    def release(self, plan: AttachPlan):
        """Drop ALL of acquire()'s transient refcounts: call after the
        reader holds its own protection — attach_blocks() took per-slot
        refs on the full pages and the tail was COW'd into the slot's
        own page. Idempotent (the plan is cleared as it is released)."""
        for p in plan.pages:
            self.pool.release_block(p)
        plan.pages = []
        self.release_tail(plan)

    # ---- insertion ----
    def insert(self, tenant: str, tokens, slot: int,
               attached_pages: List[int]):
        """Index a completed prefill. Called by the engine the moment the
        final prefill chunk commits (slot still active, full prompt KV
        provably in place). Path nodes the prompt attached from already
        exist (their refcounts kept them alive); every NEW node claims
        the slot's own page for that block index and pins it via
        `register_cached`. The sub-block remainder becomes the terminal
        node's tail, replacing a shorter refcount-0 tail only."""
        self._tick += 1
        ts = self._ts(tenant)
        bl = self.block_len
        nb_pool = self.pool.n_blocks
        node = self._roots.setdefault(tenant, _Node())
        n_full = len(tokens) // bl
        for j in range(n_full):
            key = tuple(int(t) for t in tokens[j * bl:(j + 1) * bl])
            child = node.children.get(key)
            if child is None:
                page = (attached_pages[j] if j < len(attached_pages)
                        else slot * nb_pool + j)
                if page in self.pool.cached:
                    # defensive: never double-register (an attached page
                    # is only reachable through an existing node)
                    node = node.children.setdefault(key, _Node(page))
                    continue
                self.pool.register_cached(page)
                child = _Node(page)
                node.children[key] = child
                self._where[page] = (tenant, node, key)
                ts["insertions"] += 1
                self.stats["insertions"] += 1
                ts["cached_blocks"] += 1
                self.stats["cached_blocks"] += 1
            child.tick = self._tick
            node = child
        rem = tuple(int(t) for t in tokens[n_full * bl:])
        page = slot * nb_pool + n_full
        if rem and (node.tail_tokens is None or (
                len(rem) > len(node.tail_tokens)
                and self.pool.refcount.get(node.tail_page, 0) == 0)) \
                and page not in self.pool.cached:
            if node.tail_page is not None:
                self.pool.release_cached(node.tail_page)
                self._where.pop(node.tail_page, None)
                ts["cached_blocks"] -= 1
                self.stats["cached_blocks"] -= 1
            self.pool.register_cached(page)
            self._where[page] = (tenant, node, None)
            node.tail_tokens = rem
            node.tail_page = page
            node.tail_tick = self._tick
            self._offer(self._tick, page)
            ts["insertions"] += 1
            self.stats["insertions"] += 1
            ts["cached_blocks"] += 1
            self.stats["cached_blocks"] += 1
        # the path's last node is the one this insert may have left a leaf
        self._offer_leaf(node)

    # ---- eviction ----
    def _offer(self, tick: int, page: int):
        """Put one entry into the LRU order. The order may hold entries
        that are no longer candidates (`_candidate` tells on pop); it is
        swept once they outnumber the cached blocks."""
        heapq.heappush(self._order, (tick, page))
        if len(self._order) > 2 * self.stats["cached_blocks"] + 64:
            self._sweep()

    def _sweep(self):
        """Keep the order's standing entries, once each (a sorted list is
        a heap). That leaves at most one entry a cached page, so the next
        sweep is `cached_blocks + 64` offers away or more: amortised, an
        offer pays O(1) of it."""
        self._order = sorted({e for e in self._order
                              if self._candidate(*e) is not None})

    def _offer_leaf(self, node: _Node):
        """Offer `node` if it is a leaf: no children and no tail (interior
        and tailed nodes are structurally pinned until their descendants
        go first; a root names no page)."""
        if (node.page is not None and not node.children
                and node.tail_page is None):
            self._offer(node.tick, node.page)

    def _candidate(self, tick: int, page: int):
        """`_where`'s (tenant, holder, key) for an entry of the order if
        it still stands as it was offered — a tail that is still that
        node's tail, or a node still without children and tail, and not
        touched since — else None. Readers are the caller's to check."""
        where = self._where.get(page)
        if where is None:
            return None
        _, holder, key = where
        if key is None:
            if holder.tail_page != page or holder.tail_tick != tick:
                return None
        else:
            node = holder.children[key]
            if (node.tick != tick or node.children
                    or node.tail_page is not None):
                return None
        return where

    def _pop_victim(self, held: list):
        """Take the coldest evictable entry from the order: `_where`'s
        (tenant, holder, key) for it, or None when the order is out.
        Entries that no longer stand are dropped on the way, those with a
        reader put on `held` (the caller hands them back)."""
        while self._order:
            entry = heapq.heappop(self._order)
            self.stats["evict_pops"] += 1
            where = self._candidate(*entry)
            if where is None:
                self.stats["evict_stale"] += 1
            elif self.pool.refcount.get(entry[1], 0) > 0:
                held.append(entry)
            else:
                return where
        return None

    def _path(self, holder: _Node, key) -> Tuple[int, ...]:
        """The FULL token path, from the prefix start, of the block under
        `key` in `holder` — the content address the host spill tier is
        keyed by (ISSUE 19) — read upwards through `_where`."""
        keys = [key]
        while holder.page is not None:
            _, holder, key = self._where[holder.page]
            keys.append(key)
        return tuple(t for k in reversed(keys) for t in k)

    def _drop(self, tenant: str, holder: _Node, key, spill: bool = False):
        """Unlink one refcount-0 entry (`holder`'s tail where `key` is
        None, else its childless child under `key`) and release its
        page; `spill`: a full block goes to the host tier first, where
        there is one."""
        ts = self._ts(tenant)
        if key is None:
            page = holder.tail_page
            holder.tail_tokens = None
            holder.tail_page = None
            holder.tail_tick = 0
        else:
            page = holder.children.pop(key).page
            if spill and self.host_pool is not None:
                # spill the full block to the host tier before the page is
                # released (refcount is provably 0 here, so the device copy
                # is quiescent — the export is the exact KV the trie
                # indexed)
                t0 = self.clock() if self.clock is not None else None
                self.host_pool.put(tenant, self._path(holder, key),
                                   self.pool.export_page(page))
                self.spilled_pages += 1
                if t0 is not None:
                    self.spill_seconds += self.clock() - t0
        self.pool.release_cached(page)
        self._where.pop(page, None)
        ts["evictions"] += 1
        self.stats["evictions"] += 1
        ts["cached_blocks"] -= 1
        self.stats["cached_blocks"] -= 1
        # what held `holder` in place may just have gone
        self._offer_leaf(holder)

    def _row_victims(self, row: int, keep_below: int):
        """The cached pages of `row` at block `keep_below` or above,
        deepest first, if dropping them in that order is possible (each a
        tail, or a node whose children and tail went before it, none with
        a reader); else None."""
        base = row * self.pool.n_blocks
        blocks = np.flatnonzero(self.pool._cached_at[row, keep_below:])
        pages = [base + keep_below + int(j) for j in blocks[::-1]]
        going = set(pages)
        for page in pages:
            if self.pool.refcount.get(page, 0) > 0:
                return None
            _, holder, key = self._where[page]
            if key is None:
                continue
            node = holder.children[key]
            if any(c.page not in going for c in node.children.values()) \
                    or (node.tail_page is not None
                        and node.tail_page not in going):
                return None
        # a node's tail and children sit a block deeper: they went first
        return pages

    def evict_row(self, row: int, keep_below: int, victims=None) -> int:
        """Clear free row `row` of cached pages from block `keep_below` on
        (`victims`: `_row_victims`' answer, where the caller has it).
        Returns pages released (0, and nothing touched, where a page there
        has a reader or holds up an entry in another row)."""
        if victims is None:
            victims = self._row_victims(row, keep_below) or ()
        for page in victims:
            tenant, holder, key = self._where[page]
            self._drop(tenant, holder, key)
        return len(victims)

    def evict_for_pressure(self, keep_below: int = 0, rows=None) -> int:
        """Pool pressure hook. For a fresh sequence (`keep_below` 0, no
        `rows`): evict LRU refcount-0 entries until the pool has an
        allocatable row (or nothing evictable remains). For one that
        attaches its leading `keep_below` blocks: clear one of the free
        rows `rows` from that block on: the one that costs the fewest
        pages (the caller asks for its preferred row alone first). Returns
        pages released. Pages with live readers never qualify, so eviction
        under slot pressure cannot reclaim a block a stream is still
        reading — the fault matrix proves this."""
        with RecordEvent(SPAN_SERVE_EVICT):
            if rows is not None:
                plans = [(len(v), i, r, v) for i, r in enumerate(rows)
                         for v in [self._row_victims(r, keep_below)]
                         if v is not None]
                if not plans:
                    return 0
                _, _, row, victims = min(plans, key=lambda p: p[:2])
                return self.evict_row(row, keep_below, victims)
            released = 0
            held = []    # candidates with a reader: back into the order
            try:
                while not self.pool.has_allocatable_row():
                    where = self._pop_victim(held)
                    if where is None:
                        break
                    self._drop(*where, spill=True)
                    released += 1
            finally:
                for entry in held:
                    heapq.heappush(self._order, entry)
        return released

    def clear(self, only=None) -> int:
        """Release cached pages and drop their trie(s), keeping the pool
        ledger balanced. Used on an in-place weight swap (ISSUE 16):
        cached KV was computed under the old weights, and attaching it to
        a new-version prompt would stitch two weight sets inside one
        attention window. Caller must hold the engine idle (acquire-plan
        refcounts all released); cached pins are dropped here.

        `only` (ISSUE 20) is an optional namespace predicate: an adapter
        hot-swap invalidates exactly that adapter's `(tenant, adapter)`
        namespaces, leaving base/other-adapter tries warm. None keeps
        the original flush-everything contract. Returns pages
        released."""
        released = 0
        victims = [t for t in self._roots
                   if only is None or only(t)]
        for tenant in victims:
            root = self._roots[tenant]
            ts = self._ts(tenant)
            stack: List[Tuple[_Node, bool]] = [(root, True)]
            while stack:
                node, is_root = stack.pop()
                if node.tail_page is not None:
                    self.pool.release_cached(node.tail_page)
                    node.tail_tokens = None
                    node.tail_page = None
                    released += 1
                    ts["evictions"] += 1
                    self.stats["evictions"] += 1
                if not is_root and node.page is not None:
                    self.pool.release_cached(node.page)
                    released += 1
                    ts["evictions"] += 1
                    self.stats["evictions"] += 1
                for c in node.children.values():
                    stack.append((c, False))
            self.stats["cached_blocks"] -= ts["cached_blocks"]
            ts["cached_blocks"] = 0
            del self._roots[tenant]
            self._where = {p: w for p, w in self._where.items()
                           if w[0] != tenant}
        if only is None:
            self._roots.clear()
            self.stats["cached_blocks"] = 0
        # what went left `_where`, so its entries in the order no longer
        # stand: the trees were just walked, the order can be as well
        self._sweep()
        if self.host_pool is not None:
            # spilled KV is a function of the weights that computed it —
            # a weight swap poisons the host tier the same way it poisons
            # the device trie (adapter-scoped when `only` is)
            self.host_pool.clear(only=only)
        return released

    # ---- views ----
    def cached_blocks(self, tenant: Optional[str] = None) -> int:
        if tenant is None:
            return self.stats["cached_blocks"]
        return self._ts(tenant)["cached_blocks"]

    def hit_rate(self, tenant: Optional[str] = None) -> float:
        s = self.stats if tenant is None else self._ts(tenant)
        if s["lookup_tokens"] == 0:
            return 0.0
        return s["hit_tokens"] / s["lookup_tokens"]

    def snapshot(self) -> dict:
        return {
            "name": self.name,
            **self.stats,
            "hit_rate": self.hit_rate(),
            "tenants": {t: {**s, "hit_rate":
                            (s["hit_tokens"] / s["lookup_tokens"]
                             if s["lookup_tokens"] else 0.0)}
                        for t, s in self.tenant_stats.items()},
        }
