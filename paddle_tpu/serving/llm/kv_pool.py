"""Slot-paged static KV cache pool (ISSUE 5 tentpole; ISSUE 8 shared
block pool).

A fixed pool of `num_slots` cache slots backed by one static slab per
layer: `[num_slots, Hkv, block_len * n_blocks (+ pad), D]` (exactly the
model's `init_cache(num_slots, capacity)` layout, so the pool, one-shot
`generate()` and the training-side cached forward share one cache
format). Slots are the unit of admission — a sequence owns one slot from
prefill to eviction — and blocks are the unit of *accounting and
sharing*: the per-slot block table tracks which `block_len`-sized pages
of the slabs back a sequence's KV.

All device writes stay static-shape: rows are filled via
`dynamic_update_slice` (per-row vmapped in the decode hot path), never a
dynamic-extent scatter, so ONE mixed prefill+decode executable serves
every request mix. The pool is host-side bookkeeping (numpy tables +
stats); the slabs it owns are jax arrays threaded through the engine's
jitted calls.

ISSUE 7: the block tables are additionally exposed as padded DEVICE
arrays — `device_block_table() [num_slots, n_blocks]` and
`device_seq_lens() [num_slots]` — consumed directly by the ragged paged
attention kernel. Uploads are version-gated and incremental. `pad_tokens`
extends each slab past the addressable capacity so chunked prefill's
fixed-width writes near the capacity edge land in scratch columns; block
tables never address the pad region.

ISSUE 8 — the shared block pool under the prefix cache. The KV write
path (`ops/attention.update_kv_cache`) always lands a dispatch row's new
KV in that row's own slab stripe at its logical column offset, so a
slot's OWN page for logical block j is invariably the physical page
`slot * n_blocks + j`; only the READ side (the ragged kernel's block
table) redirects. Prefix sharing is therefore expressed as:

- `attach_blocks(slot, pages)` points a slot's leading logical blocks at
  pages physically living in OTHER rows (the row of the slot that
  originally prefilled them), refcounting every shared page;
- `cow_copy(src_page, dst_slot)` copies one shared *partial* block into
  the slot's own page so the suffix can diverge in place (copy-on-write);
- a prefix cache pins pages via `register_cached`/`release_cached`; rows
  holding pinned pages are never handed out by `allocate` (a fresh
  prefill would overwrite the cached KV) — under pressure `allocate`
  invokes the `on_pressure` hook so the cache can evict refcount-0
  entries LRU-first, and pages with live readers are structurally
  un-evictable;
- the ledger extends from slots to blocks: every page ever claimed is
  freed, active, or cached — `check_balance()` proves both ledgers.

Ownership: a page claimed by a slot counts as *active* while the slot
lives. When the slot frees, each own page either transfers to the cache
(it was registered: now *cached*) or is *freed*. Evicting a cache-owned
page frees it. `blocks_allocated == blocks_freed + blocks_active +
blocks_cached` at every quiescent point.

What a layer keeps per slot. `models.generation.CACHE_KINDS` declares each
kind once, by the type of the layer's `init_cache` entry: its name
(`layer_kinds`), the label each of its arrays' bytes are counted under
(`kv_bytes()`, `recurrent_state_bytes`), what it refuses and the sentence
that says why. The pool reads the row; `refusal(feature, what)` is the one
place a refusal is worded, for the pool's own operations (which raise the
row's error) and for the engine's features (which quote it). An entry of
`slabs` is a tuple of the entry's arrays, at its layer's index, and rides
the step as operand and result whatever its kind. The kinds:

- `paged`: `(k, v)` slabs `[slots, Hkv, slab_len, D]`. Nothing is refused.
- `recurrent` (`RecurrentState`, a state-space mixer): one fixed block per
  slot, `(conv [slots, K - 1, channels], ssm [slots, N, H * P])`: not paged,
  never shared, valid only at the row's committed length, zeroed inside the
  step when a row starts at position 0. Whatever re-reads, copies or trims
  pages raises `RecurrentStateError`: `rewind_length`, `attach_blocks`,
  `register_cached`, `cow_copy`, `export_rows`, `export_page` /
  `import_page`.
- `window` (`WindowKV`, answered to `init_cache(window_slab=)`): a ring of
  `ring_len` = window + `pad_tokens` columns a slot, rounded up to whole
  pages and to whole chunks, not `capacity`: position p lives at column
  `p mod ring_len` of the slot's own row, and the step's chunk-wide stripe
  overwrites only keys that have left every live query's window (that is
  what the `pad_tokens` of slack are for). `lengths`, the block table and
  the ledger stay logical: they count the row's positions, as the full
  layers hold them. The same operations raise `WindowRingError`, but a
  `rewind_length` of no more than the slack is served.
- `latent` (`LatentKV`, multi-head latent attention): per token one
  compressed latent and one rotary key shared by every head, `(c [slots,
  1, slab_len, kv_lora_rank], r [slots, 1, slab_len, qk_rope_head_dim])`:
  a pair of unequal widths with one "head", addressed by position exactly
  as `(k, v)` is, so every page operation works on it as it is.
- `indexed` (`IndexedLatentKV`, a latent layer with an indexer): a third
  slab beside `c` and `r`, `k_index [slots, 1, slab_len, index_head_dim]`.
  Its pages are numbered as the latent pages are (one block table, one
  ledger), so whatever moves, shares, pins or exports a page does it to
  all three slabs of such a layer.

Which row a request gets (PR 39). A request that attaches its leading `n`
blocks writes only from block `n` on, so a free row is fit for it when none
of the row's cached pages sits at block `n` or above: `allocate(need,
keep_below=n, prefer=row)`. A session's next turn, which attaches what its
last turn left cached in a row, so goes back into that row (`prefer`),
cleared behind block `n` if need be (the tail of its last prompt, a stale
continuation) where no row is free of cached pages; `keep_below=0` is the
old rule (a row with any cached page is not handed out).
"""
from __future__ import annotations

import inspect
import threading
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.generation import (CACHE_KINDS, REREAD, REWIND,  # noqa: F401
                                  RecurrentStateError, WindowRingError,
                                  kind_of)
from ...ops.attention import PagedView

# the names `layer_kinds` holds (`models.generation.CACHE_KINDS`)
PAGED, RECURRENT, WINDOW, LATENT = "paged", "recurrent", "window", "latent"
INDEXED = "indexed"      # latent pages and, beside them, index-key pages


class SlotsExhaustedError(RuntimeError):
    """allocate() found no usable free slot — every row is decoding or
    pinned by cached blocks with live readers. The engine maps this to
    queueing (and ultimately RejectedError admission control), never to a
    dynamic reallocation: pool size is a compile-time shape."""


class SlotPagedKVPool:
    """Fixed pool of KV cache slots with block/length accounting and a
    shared, refcounted block pool for prefix sharing.

    init_cache_fn(batch, max_len) must return the model's cache pytree — a
    list with, per layer, (k, v) arrays shaped [batch, Hkv, max_len, D] or
    a `RecurrentState` (fixed size, `layer_kinds`) — and is called
    once with batch=num_slots, max_len=block_len*n_blocks (+pad). Models
    enforce their own limits here (GPT refuses capacity beyond its
    learned position table).
    """

    def __init__(self, init_cache_fn: Callable, num_slots: int,
                 block_len: int, n_blocks: int, dtype=None,
                 pad_tokens: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if block_len < 1 or n_blocks < 1:
            raise ValueError(
                f"block_len/n_blocks must be >= 1, got "
                f"{block_len}/{n_blocks}")
        if pad_tokens < 0:
            raise ValueError(f"pad_tokens must be >= 0, got {pad_tokens}")
        self.num_slots = int(num_slots)
        self.block_len = int(block_len)
        self.n_blocks = int(n_blocks)
        self.capacity = self.block_len * self.n_blocks  # tokens per slot
        # slab columns past `capacity` are write-scratch for fixed-width
        # chunked-prefill stripes; never addressed by any block table
        self.pad_tokens = int(pad_tokens)
        self.slab_len = self.capacity + self.pad_tokens
        kwargs = {} if dtype is None else {"dtype": dtype}
        self.window: Optional[int] = None   # the window layers', if any
        # a model with window layers is told how long their slabs are
        if "window_slab" in inspect.signature(init_cache_fn).parameters:
            kwargs["window_slab"] = self._window_slab
        entries = list(init_cache_fn(self.num_slots, self.slab_len,
                                     **kwargs))
        # what each layer keeps per slot, told by its entry's type
        self._kinds = [kind_of(e) for e in entries]
        self.layer_kinds: List[str] = [kind.name for kind in self._kinds]
        self.recurrent = RECURRENT in self.layer_kinds
        self.windowed = WINDOW in self.layer_kinds
        self._latent = bool({LATENT, INDEXED} & set(self.layer_kinds))
        # the window layers' ring, in columns and pages (None: no such
        # layer); every window layer of a model has the one window
        rings = {int(e[0].shape[2]) - self.pad_tokens
                 for e, kind in zip(entries, self.layer_kinds)
                 if kind == WINDOW}
        if len(rings) > 1:
            raise ValueError(f"window layers of different rings: {rings}")
        self.ring_len: Optional[int] = rings.pop() if rings else None
        self.ring_pages: Optional[int] = (
            None if self.ring_len is None
            else self.ring_len // self.block_len)
        # the engine's step is donated `slabs` and its result, in the same
        # buffers, is assigned back: a reference kept across a step is a
        # deleted array. Read the attribute when you work, on the thread
        # that launches steps, or under `slabs_lock` on another
        self.slabs: List[Tuple[jnp.ndarray, ...]] = [
            tuple(e) for e in entries]
        self.slabs_lock = threading.Lock()
        self.lengths = np.zeros((self.num_slots,), np.int32)
        self.active = np.zeros((self.num_slots,), bool)
        # rows freed and not handed out again since: their pages hold the
        # last sequence's KV until the next one's prefill overwrites it
        # (`allocate` counts such a row as a reuse)
        self.dirty = np.zeros((self.num_slots,), bool)
        # slot -> global page ids backing its current length: leading
        # entries may be attached (shared) pages in other rows, the rest
        # are the slot's own identity pages (slot*n_blocks + j)
        self.block_table: Dict[int, List[int]] = {}
        # ---- shared-block state (ISSUE 8) ----
        self._attached: Dict[int, List[int]] = {}   # slot -> shared pages
        self._own_claimed: Dict[int, int] = {}      # slot -> own pages
        self.refcount: Dict[int, int] = {}          # page -> live readers
        self.cached: Set[int] = set()               # pages pinned by cache
        # the same by row and block, and counted by row, for `_fit_rows`
        self._cached_at = np.zeros((self.num_slots, self.n_blocks), bool)
        self._cached_in = np.zeros((self.num_slots,), np.int32)
        self._cache_owned: Set[int] = set()         # cached, owner freed
        # cache-pressure hook: called by allocate() when free rows exist
        # but every one is pinned; the prefix cache wires its eviction
        # here (`on_pressure()` for a fresh sequence, else
        # `on_pressure(keep_below, free rows)`) and returns pages released
        self.on_pressure: Optional[Callable[..., int]] = None
        self.stats = {"allocs": 0, "frees": 0, "reuses": 0,
                      "alloc_failures": 0, "peak_active": 0,
                      "blocks_allocated": 0, "blocks_freed": 0,
                      "cow_copies": 0}
        self._cow = None     # lazily-jitted copy-on-write block copy
        # device-array mirrors for the ragged kernel: identity stripes
        # (slot s owns global pages s*n_blocks..s*n_blocks+n_blocks-1)
        # until attach_blocks redirects a row; version counters gate
        # re-upload so the hot loop pays a transfer only on change
        self._host_table = self._identity_table()
        self._table_version = 1
        self._table_uploaded = 0
        self._dev_table: Optional[jnp.ndarray] = None
        self._lens_version = 1
        self._lens_uploaded = 0
        self._dev_lens: Optional[jnp.ndarray] = None

    def _window_slab(self, window: int) -> int:
        """Columns of a window layer's slab: a ring of window + the
        chunk's slack, whole pages and whole chunks (an aligned chunk then
        never straddles the ring's end), and the write pad behind it."""
        self.window = int(window)
        unit = int(np.lcm(self.block_len, max(self.pad_tokens, 1)))
        return -(-(int(window) + self.pad_tokens) // unit) * unit \
            + self.pad_tokens

    def kv_bytes(self) -> Dict[str, int]:
        """Bytes of the K/V slabs by what they are: "full" (a slot's whole
        context), "window" (a ring) and, on a pool that holds one,
        "latent" (a slot's whole context as a latent and a rotary key) and
        "index" (as an index key), all slots and layers: each array under
        the label its kind's row gives it."""
        out = {"full": 0, "window": 0}
        for entry, kind in zip(self.slabs, self._kinds):
            for a, label in zip(entry, kind.bytes_as):
                if label is not None:
                    out[label] = out.get(label, 0) + int(a.nbytes)
        return out

    @property
    def recurrent_state_bytes(self) -> int:
        """Bytes of the per-slot state that is no page (a recurrent layer's:
        the arrays whose row gives them no label), all slots."""
        return sum(int(a.nbytes) for entry, kind
                   in zip(self.slabs, self._kinds)
                   for a, label in zip(entry, kind.bytes_as)
                   if label is None)

    def consumed(self) -> bool:
        """Whether a dispatch that was donated the slabs took them: asked
        after one failed, never on the way of a step that did not."""
        return any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(self.slabs))

    def reset_slabs(self):
        """A zeroed pool of the same shapes, after the slabs were lost to
        a dispatch that consumed them and failed. The caller has freed
        every row and dropped what a prefix cache pinned."""
        self.slabs = [tuple(jnp.zeros(a.shape, a.dtype) for a in entry)
                      for entry in self.slabs]

    def refusal(self, feature: str, what: str) -> Optional[Exception]:
        """The error that refuses `what`, a use of `feature` (`REREAD`,
        `HOST_TIER`, `REWIND`), on this pool: the sentence of the first
        kind, in the table's order, that some layer here is of and that
        refuses the feature. None where no layer does."""
        for kind in CACHE_KINDS.values():
            n = self.layer_kinds.count(kind.name)
            if n and feature in kind.refuses:
                ring = "" if self.ring_len is None else \
                    f" (a ring of {self.ring_len} columns a slot)"
                return kind.error(
                    f"{what} of which {n} of {len(self.layer_kinds)} layers "
                    f"are {kind.name} layers{ring}: {kind.why}")
        return None

    def _refuse_reread(self, what: str, feature: str = REREAD):
        """Refuse `what`, which re-reads a row's pages, on a pool some of
        whose layers do not keep them."""
        err = self.refusal(feature, f"{what} on a pool")
        if err is not None:
            raise err

    def view(self, table, seq_lens) -> PagedView:
        """The page operand of a step over this pool: `table` and the
        rows' lengths after the step with the pool's own geometry."""
        return PagedView(table, seq_lens, self.block_len, self.n_blocks,
                         self.ring_pages)

    def step_counts(self, pos: np.ndarray, adv: np.ndarray
                    ) -> Tuple[dict, int, Tuple[int, int]]:
        """What a step over rows at `pos` with `adv` live columns means to
        each kind of layer here: the kinds' arguments of the dispatch span,
        the rows whose recurrent state the step starts from zero, and the
        keys its attention calls must read, (one window layer's call, one
        full or latent layer's): a row's length after the step and, on a
        pool with a ring, the part of it inside the window."""
        live = adv > 0
        after = (pos + adv)[live]
        args, started, in_window = {}, 0, 0
        if self.recurrent:
            args["recurrent_rows"] = int(after.size)
            started = int(np.count_nonzero(live & (pos == 0)))
        if self.windowed:
            in_window = int(np.minimum(after, self.window).sum())
            args["window_rows"] = int(after.size)
            # rows whose ring has begun to overwrite its oldest keys
            args["wrapped_rows"] = int(np.count_nonzero(
                after > self.ring_len))
        if self._latent:
            args["latent_rows"] = int(after.size)
        return args, started, (in_window, int(after.sum()))

    def _identity_table(self) -> np.ndarray:
        return (np.arange(self.num_slots, dtype=np.int32)[:, None]
                * self.n_blocks
                + np.arange(self.n_blocks, dtype=np.int32)[None, :])

    def _identity_row(self, slot: int) -> List[int]:
        return [slot * self.n_blocks + j for j in range(self.n_blocks)]

    def _fit_rows(self, keep_below: int = 0) -> np.ndarray:
        """[num_slots] bool: the free rows that can be handed to a sequence
        which writes from block `keep_below` on. A row holding a cached
        page at that block or above cannot: the prefill would overwrite
        shared KV in place (0: a fresh sequence, any cached page pins the
        row). The pressure hook asks once a page it frees, between two
        steps and beside the clients' threads: a fresh sequence's answer
        reads the rows' counts alone (a reduction over the whole ledger
        lets go of the interpreter lock each time, and the other threads'
        work then lands inside the admission)."""
        if keep_below == 0:
            return ~self.active & (self._cached_in == 0)
        return ~self.active & ~self._cached_at[:, keep_below:].any(axis=1)

    def has_allocatable_row(self, keep_below: int = 0) -> bool:
        return bool(self._fit_rows(keep_below).any())

    # ---- allocation ----
    def allocate(self, need_tokens: int, keep_below: int = 0,
                 prefer: Optional[int] = None) -> int:
        """Claim a free, unpinned slot for a sequence that will grow to
        `need_tokens` (prompt + max_new_tokens). Raises ValueError when
        the request can never fit and SlotsExhaustedError when the pool
        is momentarily full. When every free row is pinned by cached
        blocks, the `on_pressure` hook (the prefix cache's eviction)
        gets one chance to release refcount-0 entries before the
        exhaustion verdict — pages with live readers are never touched.

        `keep_below`: the sequence attaches its leading `keep_below`
        blocks and writes only behind them, so cached pages below that
        block do not pin a row against it; `prefer`: the row to take if it
        is fit (the row its attached pages live in)."""
        if need_tokens > self.capacity:
            raise ValueError(
                f"sequence needs {need_tokens} tokens but slot capacity is "
                f"{self.capacity} (block_len={self.block_len} x "
                f"n_blocks={self.n_blocks})")
        free = np.flatnonzero(~self.active)
        if free.size == 0:
            self.stats["alloc_failures"] += 1
            raise SlotsExhaustedError(
                f"all {self.num_slots} slots active")
        order = [int(r) for r in free]
        mine = [int(prefer)] if prefer in order else []

        def fit(rows, below=keep_below):
            ok = self._fit_rows(below)
            return next((r for r in rows if ok[r]), None)

        # in order: the preferred row as it is; a row with no cached page
        # at all (the rule for a fresh sequence); the preferred row cleared
        # behind `keep_below` (what goes is this prefix's own stale
        # continuation: a session's pages stay in one row); another row
        # whose cached pages sit below `keep_below`; under pressure, the
        # row that costs the fewest pages
        slot = fit(mine) if mine else None
        if slot is None:
            slot = fit(order, 0)
        if slot is None and mine and self.on_pressure is not None:
            self.on_pressure(keep_below, mine)
            slot = fit(mine)
        if slot is None and keep_below:
            slot = fit(order)
        if slot is None and self.on_pressure is not None:
            if keep_below == 0 and prefer is None:
                self.on_pressure()
            else:
                self.on_pressure(keep_below, order)
            slot = fit(order)
        if slot is None:
            self.stats["alloc_failures"] += 1
            raise SlotsExhaustedError(
                f"every free slot is pinned by cached blocks with live "
                f"readers ({free.size} free of {self.num_slots})")
        self.active[slot] = True
        if self.dirty[slot]:
            self.stats["reuses"] += 1
            self.dirty[slot] = False
        if self.lengths[slot] != 0:
            self._lens_version += 1
        self.lengths[slot] = 0
        self.block_table[slot] = []
        self._attached[slot] = []
        self._own_claimed[slot] = 0
        self.set_block_row(slot, self._identity_row(slot))
        self.stats["allocs"] += 1
        self.stats["peak_active"] = max(self.stats["peak_active"],
                                        int(self.active.sum()))
        return slot

    def free(self, slot: int):
        """Release a slot: drop the refcount it held on every attached
        (shared) page, and settle its OWN pages' ledger — pages the cache
        registered transfer ownership to the cache, the rest are freed."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        for p in self._attached.get(slot, ()):
            self.release_block(p)
        n_att = len(self._attached.get(slot, ()))
        for j in range(n_att, n_att + self._own_claimed.get(slot, 0)):
            p = slot * self.n_blocks + j
            if p in self.cached:
                self._cache_owned.add(p)
            else:
                self.stats["blocks_freed"] += 1
        self._attached.pop(slot, None)
        self._own_claimed.pop(slot, None)
        self.active[slot] = False
        self.dirty[slot] = True
        if self.lengths[slot] != 0:
            self._lens_version += 1
        self.lengths[slot] = 0
        self.block_table.pop(slot, None)
        self.stats["frees"] += 1

    def set_length(self, slot: int, length: int):
        """Record `length` valid tokens in `slot`, growing its block
        table to ceil(length / block_len) pages: the attached shared
        prefix first, then the slot's own identity pages. Newly-claimed
        own pages charge the block ledger."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if length > self.capacity:
            raise ValueError(
                f"length {length} exceeds slot capacity {self.capacity}")
        if int(self.lengths[slot]) != int(length):
            self._lens_version += 1
        self.lengths[slot] = length
        blocks = -(-int(length) // self.block_len)
        attached = self._attached.get(slot, [])
        own_needed = max(0, blocks - len(attached))
        claimed = self._own_claimed.get(slot, 0)
        if own_needed > claimed:
            self.stats["blocks_allocated"] += own_needed - claimed
            self._own_claimed[slot] = own_needed
        self.block_table[slot] = (
            attached[:blocks]
            + [slot * self.n_blocks + j
               for j in range(len(attached), blocks)])

    def rewind_length(self, slot: int, length: int):
        """Shrink `slot`'s committed length to `length`, returning own
        pages past the new block count to the ledger (ISSUE 17
        speculative decoding: a draft window commits K tokens of KV
        optimistically; rejected positions must give their pages back so
        `check_balance()` keeps holding). Cache-registered own pages stay
        claimed — the prefix cache owns their lifetime, and `_own_claimed`
        is a contiguous count, so the scan un-claims from the top down and
        stops at the first cached page. Attached (shared) pages are never
        touched: they back the prefix below any rewind point. Growing is
        `set_length`'s job; a larger `length` raises."""
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        length = int(length)
        cur = int(self.lengths[slot])
        # inside the write pad it is a rejected draft window: a ring gives
        # that back (the stripe overwrote nothing a query still sees), a
        # recurrence cannot; past the pad it re-reads pages (on a pool of
        # paged layers alone nothing is refused)
        if length < cur:
            self._refuse_reread(
                f"rewind_length by {cur - length}",
                REWIND if cur - length <= self.pad_tokens else REREAD)
        if length > cur:
            raise ValueError(
                f"rewind_length can only shrink: {length} > committed "
                f"{cur} (use set_length to grow)")
        if length < 0:
            raise ValueError(f"length must be >= 0, got {length}")
        if length == cur:
            return
        self._lens_version += 1
        self.lengths[slot] = length
        blocks = -(-length // self.block_len)
        attached = self._attached.get(slot, [])
        own_needed = max(0, blocks - len(attached))
        claimed = self._own_claimed.get(slot, 0)
        new_claimed = claimed
        for j in range(len(attached) + claimed - 1,
                       len(attached) + own_needed - 1, -1):
            if slot * self.n_blocks + j in self.cached:
                break
            new_claimed -= 1
        if new_claimed != claimed:
            self.stats["blocks_freed"] += claimed - new_claimed
            self._own_claimed[slot] = new_claimed
        self.block_table[slot] = (
            attached[:blocks]
            + [slot * self.n_blocks + j
               for j in range(len(attached), blocks)])

    # ---- prefix sharing (ISSUE 8) ----
    def attach_blocks(self, slot: int, pages: List[int]):
        """Point `slot`'s leading logical blocks at shared pages computed
        by other slots, taking a refcount on each for this slot's
        lifetime. Every shared page must be cache-registered and must sit
        at its logical block offset (`page % n_blocks == j` — the write
        path guarantees a slot's block j is physically at column j of its
        own row, so cached pages always satisfy this)."""
        if pages:
            self._refuse_reread("attach_blocks")
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if len(pages) > self.n_blocks:
            raise ValueError(
                f"cannot attach {len(pages)} pages to a "
                f"{self.n_blocks}-block slot")
        for j, p in enumerate(pages):
            if p not in self.cached:
                raise ValueError(
                    f"page {p} is not cache-registered; only cached "
                    "blocks can be shared")
            if p % self.n_blocks != j:
                raise ValueError(
                    f"page {p} lives at block offset {p % self.n_blocks}, "
                    f"cannot back logical block {j}")
        for p in pages:
            self.refcount[p] = self.refcount.get(p, 0) + 1
        self._attached[slot] = list(pages)
        self.set_block_row(
            slot, list(pages) + [slot * self.n_blocks + j
                                 for j in range(len(pages), self.n_blocks)])

    def release_block(self, page: int):
        """Drop one reader's refcount on a shared page."""
        n = self.refcount.get(page, 0)
        if n <= 1:
            self.refcount.pop(page, None)
        else:
            self.refcount[page] = n - 1

    def cow_copy(self, src_page: int, dst_slot: int):
        """Copy-on-write: copy one shared (partial) block's KV into
        `dst_slot`'s own page at the same logical offset, so the slot can
        append divergent tokens into it. One jitted two-op copy
        (dynamic_slice + dynamic_update_slice) per slab; traced row/col
        offsets keep it a single executable per slab shape."""
        self._refuse_reread("cow_copy")
        if not self.active[dst_slot]:
            raise ValueError(f"slot {dst_slot} is not active")
        block_idx = src_page % self.n_blocks
        src_row = src_page // self.n_blocks
        if src_row == dst_slot:
            return
        if self._cow is None:
            blk_len = self.block_len

            def _cow(slab, src_r, dst_r, c0):
                blk = jax.lax.dynamic_slice(
                    slab, (src_r, 0, c0, 0),
                    (1, slab.shape[1], blk_len, slab.shape[3]))
                return jax.lax.dynamic_update_slice(
                    slab, blk, (dst_r, 0, c0, 0))

            self._cow = jax.jit(_cow)
        sr = jnp.int32(src_row)
        dr = jnp.int32(dst_slot)
        c0 = jnp.int32(block_idx * self.block_len)
        self.slabs = [tuple(self._cow(a, sr, dr, c0) for a in entry)
                      for entry in self.slabs]
        self.stats["cow_copies"] += 1

    def register_cached(self, page: int):
        """Pin a page on behalf of the prefix cache: its row leaves the
        allocatable set."""
        self._refuse_reread("register_cached")
        if not (0 <= page < self.num_slots * self.n_blocks):
            raise ValueError(f"page {page} out of range")
        if page in self.cached:
            raise ValueError(f"page {page} already cache-registered")
        self.cached.add(page)
        row, block = divmod(page, self.n_blocks)
        self._cached_at[row, block] = True
        self._cached_in[row] += 1

    def release_cached(self, page: int):
        """Cache eviction: unpin a page. Refuses while readers hold it.
        A cache-owned page (its slot freed) settles to the freed side of
        the block ledger; a free row it sat in is `dirty` again."""
        if page not in self.cached:
            raise ValueError(f"page {page} is not cache-registered")
        if self.refcount.get(page, 0) > 0:
            raise ValueError(
                f"page {page} has {self.refcount[page]} live reader(s); "
                "evicting it would corrupt active streams")
        self.cached.discard(page)
        row, block = divmod(page, self.n_blocks)
        self._cached_at[row, block] = False
        self._cached_in[row] -= 1
        if page in self._cache_owned:
            self._cache_owned.discard(page)
            self.stats["blocks_freed"] += 1
        row = page // self.n_blocks
        if not self.active[row]:
            self.dirty[row] = True

    def set_block_row(self, slot: int, blocks: List[int]):
        """Point `slot`'s device-table row at an explicit page list
        (incremental update — only this row changes; padding pages past
        len(blocks) are don't-cares masked by seq_lens). The mechanism
        under attach_blocks, and the escape hatch for non-identity
        layouts in tests."""
        if len(blocks) > self.n_blocks:
            raise ValueError(
                f"slot row holds at most {self.n_blocks} pages, got "
                f"{len(blocks)}")
        row = np.zeros((self.n_blocks,), np.int32)
        row[:len(blocks)] = np.asarray(blocks, np.int32)
        if not np.array_equal(self._host_table[slot], row):
            self._host_table[slot] = row
            self._table_version += 1

    # ---- device mirrors (ragged paged attention inputs) ----
    def device_block_table(self) -> jnp.ndarray:
        """[num_slots, n_blocks] int32 page ids, uploaded lazily on
        version change (identity stripes → effectively uploaded once for
        cold traffic; attach/restore bump the version per changed row)."""
        if self._dev_table is None \
                or self._table_uploaded != self._table_version:
            self._dev_table = jnp.asarray(self._host_table)
            self._table_uploaded = self._table_version
        return self._dev_table

    def device_seq_lens(self) -> jnp.ndarray:
        """[num_slots] int32 committed lengths, uploaded lazily only when
        some set_length() actually changed a value."""
        if self._dev_lens is None \
                or self._lens_uploaded != self._lens_version:
            self._dev_lens = jnp.asarray(self.lengths)
            self._lens_uploaded = self._lens_version
        return self._dev_lens

    # ---- views ----
    def free_slots(self) -> int:
        return int((~self.active).sum())

    def active_slots(self) -> int:
        return int(self.active.sum())

    def occupancy(self) -> float:
        return self.active_slots() / self.num_slots

    def used_blocks(self) -> int:
        return sum(len(b) for b in self.block_table.values())

    def blocks_active(self) -> int:
        """Own pages claimed by currently-active slots (shared attached
        pages are accounted by their owner or the cache, never twice)."""
        return sum(n for s, n in self._own_claimed.items()
                   if self.active[s])

    def blocks_cached(self) -> int:
        """Pages whose owning slot freed while the cache held them: the
        cache is now the owner of record."""
        return len(self._cache_owned)

    def cached_blocks(self) -> int:
        """Every page currently pinned by the prefix cache (owner active
        or not)."""
        return len(self.cached)

    def dirty_blocks(self) -> int:
        """Pages that hold a finished sequence's KV and nobody's claim:
        those of `dirty` rows that the cache does not pin."""
        total = 0
        for r in np.flatnonzero(self.dirty):
            base = int(r) * self.n_blocks
            total += sum(1 for j in range(self.n_blocks)
                         if (base + j) not in self.cached)
        return total

    def lengths_array(self) -> jnp.ndarray:
        return jnp.asarray(self.lengths)

    def fragmentation_ratio(self) -> float:
        """Fraction of allocated block tokens not holding valid KV:
        1 - sum(lengths) / (used_blocks * block_len). 0.0 when idle —
        exported as the LLMMetrics fragmentation gauge."""
        used = self.used_blocks()
        if used == 0:
            return 0.0
        return 1.0 - float(self.lengths.sum()) / (used * self.block_len)

    def snapshot(self) -> dict:
        return {
            **self.stats,
            "num_slots": self.num_slots,
            "active_slots": self.active_slots(),
            "capacity_tokens": self.capacity,
            "used_blocks": self.used_blocks(),
            "dirty_blocks": self.dirty_blocks(),
            "total_blocks": self.num_slots * self.n_blocks,
            "blocks_active": self.blocks_active(),
            "blocks_cached": self.blocks_cached(),
            "cached_pages": self.cached_blocks(),
        }

    def check_balance(self) -> bool:
        """The two accounting invariants the fault matrix proves after
        every scenario. Slots: every slot ever allocated was freed or is
        still active (`allocs == frees + active_slots`). Blocks: every
        page ever claimed is freed, active in a living slot, or owned by
        the cache (`blocks_allocated == blocks_freed + blocks_active +
        blocks_cached`) — i.e. no failure path leaked a slot OR a page.
        Raises AssertionError with the offending ledger on violation."""
        allocs = self.stats["allocs"]
        frees = self.stats["frees"]
        active = self.active_slots()
        if allocs != frees + active:
            raise AssertionError(
                f"KV pool slot ledger out of balance: allocs={allocs} != "
                f"frees={frees} + active={active} "
                f"(leaked {allocs - frees - active})")
        b_alloc = self.stats["blocks_allocated"]
        b_freed = self.stats["blocks_freed"]
        b_active = self.blocks_active()
        b_cached = self.blocks_cached()
        if b_alloc != b_freed + b_active + b_cached:
            raise AssertionError(
                f"KV pool block ledger out of balance: "
                f"blocks_allocated={b_alloc} != blocks_freed={b_freed} + "
                f"blocks_active={b_active} + blocks_cached={b_cached} "
                f"(leaked {b_alloc - b_freed - b_active - b_cached})")
        # the pinned pages as `_fit_rows` reads them: by row and block, and
        # counted by row
        at = np.flatnonzero(self._cached_at)
        if set(at.tolist()) != self.cached or not np.array_equal(
                self._cached_in, self._cached_at.sum(axis=1)):
            raise AssertionError(
                f"KV pool cached-page ledgers disagree: {len(self.cached)} "
                f"pinned pages, {at.size} by row and block, "
                f"{int(self._cached_in.sum())} counted by row")
        return True

    # ---- row serialization (ISSUE 14: KV handoff groundwork) ----
    def export_rows(self, slots: List[int]) -> dict:
        """Serialize the committed KV of active `slots` to host numpy:
        per slot, its valid length and per-layer [Hkv, length, D] K/V
        arrays assembled page-by-page through the block table (attached
        shared pages read from their physical row, exactly as the ragged
        kernel would). The payload is self-describing enough for
        ANOTHER pool with the same slab geometry to land it page by page
        (`import_page`, the engine's `kv_row`): prefill/decode-
        disaggregated KV handoff. KV alone
        is not enough to resume a SAMPLED stream bit-identically: pair
        this payload with `LLMEngine.export_sampling_lanes` (ISSUE 18),
        which carries each slot's RNG-lane index and grammar DFA state."""
        self._refuse_reread("export_rows")
        rows: Dict[int, dict] = {}
        # the one reader that may run beside the thread that launches
        # steps (a router handing a stream off): no step is dispatched,
        # and no slab consumed, while it reads
        with self.slabs_lock:
            for slot in slots:
                slot = int(slot)
                if not self.active[slot]:
                    raise ValueError(f"slot {slot} is not active")
                length = int(self.lengths[slot])
                pages = list(self.block_table.get(slot, []))
                layers = []
                for entry in self.slabs:
                    # ISSUE 19: length-trimmed fetch — slice each occupied
                    # page's columns on DEVICE and fetch only those, instead
                    # of materializing the whole [num_slots, Hkv, slab_len, D]
                    # slab on the host per layer. Spill/handoff copies scale
                    # with the row's committed length, not the pool size; the
                    # payload is bit-identical to the untrimmed path (pinned
                    # in tests/test_router.py).
                    parts = [[] for _ in entry]
                    for j, p in enumerate(pages):
                        prow = p // self.n_blocks
                        c0 = (p % self.n_blocks) * self.block_len
                        w = min(self.block_len, length - j * self.block_len)
                        for got, a in zip(parts, entry):
                            got.append(np.asarray(a[prow, :, c0:c0 + w, :]))
                    if pages:
                        layers.append(tuple(np.concatenate(got, axis=1)
                                            for got in parts))
                    else:
                        layers.append(tuple(
                            np.zeros((a.shape[1], 0, a.shape[3]), a.dtype)
                            for a in entry))
                rows[slot] = {"length": length, "layers": layers}
        return {"block_len": self.block_len, "capacity": self.capacity,
                "rows": rows}

    def export_page(self, page: int,
                    width: Optional[int] = None) -> List[Tuple[np.ndarray,
                                                               ...]]:
        """Fetch ONE page's occupied KV columns to host numpy: per layer
        an owned ([Hkv, width, D] K, same-shape V) pair, sliced on device
        so the transfer is exactly `width` tokens. This is the spill unit
        the host tier (HostKVPool, ISSUE 19) stores; `width` defaults to
        the full block."""
        self._refuse_reread("export_page")
        if not (0 <= page < self.num_slots * self.n_blocks):
            raise ValueError(f"page {page} out of range")
        w = self.block_len if width is None else int(width)
        if not (0 < w <= self.block_len):
            raise ValueError(
                f"width must be in 1..{self.block_len}, got {w}")
        prow = page // self.n_blocks
        c0 = (page % self.n_blocks) * self.block_len
        return [tuple(np.asarray(a[prow, :, c0:c0 + w, :]) for a in entry)
                for entry in self.slabs]

    def import_page(self, slot: int, block_idx: int,
                    layers: List[Tuple[np.ndarray, np.ndarray]]):
        """Land one spilled page's KV into `slot`'s OWN identity page at
        logical block `block_idx` (the write-path invariant: a slot's
        block j is physically at column j of its own row, so the identity
        block table already covers it). Inverse of `export_page`, bitwise.
        Ledger accounting rides the normal path: the engine's next
        `set_length` past this block claims the own page."""
        self._refuse_reread("import_page")
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        if not (0 <= block_idx < self.n_blocks):
            raise ValueError(f"block_idx {block_idx} out of range "
                             f"0..{self.n_blocks - 1}")
        if len(layers) != len(self.slabs):
            raise ValueError(
                f"payload has {len(layers)} layers, pool has "
                f"{len(self.slabs)}")
        c0 = block_idx * self.block_len
        new_slabs = []
        for entry, payload in zip(self.slabs, layers):
            if payload[0].shape[1] > self.block_len:
                raise ValueError(
                    f"page payload holds {payload[0].shape[1]} tokens, "
                    f"block_len is {self.block_len}")
            new_slabs.append(self._land(entry, payload, slot, c0))
        self.slabs = new_slabs

    @staticmethod
    def _land(entry, payload, slot: int, c0: int):
        """`entry`'s slabs with `payload`'s `[Hkv, w, D]` arrays written at
        column `c0` of row `slot`."""
        if len(entry) != len(payload):
            raise ValueError(f"a layer of {len(entry)} slabs given "
                             f"{len(payload)} arrays")
        return tuple(jax.lax.dynamic_update_slice(
            a, jnp.asarray(e, dtype=a.dtype)[None], (slot, 0, c0, 0))
            for a, e in zip(entry, payload))
