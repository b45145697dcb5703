"""Serving metrics: request counters, latency quantiles, batch-size
histogram, queue-depth gauge — collected by the BatchingEngine on every
admission/dispatch and exposed two ways:

- `render()` — Prometheus text exposition for the HTTP `/metrics` endpoint;
- `paddle_tpu.profiler.record_instant` — a `serving/dispatch` instant per
  engine dispatch, so serving activity lands on the same chrome trace
  timeline as training step spans when profiling is enabled.

Latency quantiles come from a bounded reservoir of recent completions
(exact over the window, not an approximation sketch); totals are lifetime
counters so a drain snapshot reconciles against a replayed trace:
submitted == completed + rejected + expired + failed.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Dict, Optional

# text-exposition plumbing lives in obs.prom (ISSUE 9) so training-side
# exporters render the same way; parse_exposition is re-exported from
# here for existing callers
from ..obs.prom import PromBuilder, parse_exposition  # noqa: F401

# cumulative histogram upper bounds for dispatched batch rows
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# SLO classes in strict priority order (ISSUE 6 overload control): the
# scheduler admits interactive before batch before best_effort, and load
# shedding walks the same list from the BOTTOM up.
SLO_CLASSES = ("interactive", "batch", "best_effort")
# the phases a request's time to its first token is the sum of, in order
# (`LLMEngine`: the stamps of `_GenRequest`)
TTFT_PHASES = ("queued", "bound", "prefill", "first_fetch")


class ServingMetrics:
    # metric family prefix — subclasses (LLMMetrics) override it so two
    # engines behind one server scrape without name collisions
    _PREFIX = "pdtpu_serving"

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self.window = int(window)
        self._latencies_ms: deque = deque(maxlen=self.window)
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "rejected": 0,
            "expired": 0, "failed": 0, "dispatches": 0,
        }
        self.reject_reasons: Dict[str, int] = {}
        self.batch_hist: Dict[int, int] = {}   # exact dispatched rows -> n
        self.queue_depth = 0
        self.dispatched_rows = 0
        self.padded_rows = 0
        # supervision (ISSUE 6): dispatch failures by kind ("raise"/"hang"/
        # "poisoned"/"engine") and the engine circuit-breaker gauge
        self.dispatch_failures: Dict[str, int] = {}
        self.circuit_open = False
        # economics providers (ISSUE 11), attached by the engine when
        # built with economics=True and sampled only at snapshot/render
        # time (scrape-rate cost, never pump-rate cost)
        self.ledger = None   # obs.serving_ledger.ServingLedger
        self.burn = None     # obs.serving_ledger.SLOBurnMonitor

    # ---- engine callbacks ----
    def on_submit(self, queue_depth: int):
        with self._lock:
            self.counters["submitted"] += 1
            self.queue_depth = queue_depth

    def on_reject(self, reason: str):
        with self._lock:
            self.counters["rejected"] += 1
            self.reject_reasons[reason] = self.reject_reasons.get(reason, 0) + 1

    def on_expire(self, n: int = 1):
        with self._lock:
            self.counters["expired"] += n

    def on_complete(self, latency_ms: float):
        with self._lock:
            self.counters["completed"] += 1
            self._latencies_ms.append(float(latency_ms))

    def on_fail(self, n: int = 1):
        with self._lock:
            self.counters["failed"] += n

    def on_dispatch(self, rows: int, n_requests: int, padded_rows: int,
                    dispatch_ms: float, queue_depth: int):
        with self._lock:
            self.counters["dispatches"] += 1
            self.batch_hist[rows] = self.batch_hist.get(rows, 0) + 1
            self.dispatched_rows += rows
            self.padded_rows += padded_rows - rows
            self.queue_depth = queue_depth
        from ..profiler import record_instant
        record_instant("serving/dispatch", {
            "rows": rows, "requests": n_requests,
            "padded_rows": padded_rows, "dispatch_ms": dispatch_ms,
            "queue_depth": queue_depth,
        })

    def set_queue_depth(self, depth: int):
        with self._lock:
            self.queue_depth = depth

    def on_dispatch_failure(self, kind: str):
        with self._lock:
            self.dispatch_failures[kind] = \
                self.dispatch_failures.get(kind, 0) + 1

    def set_circuit_open(self, open_: bool):
        with self._lock:
            self.circuit_open = bool(open_)

    # ---- views ----
    def quantile_ms(self, q: float) -> Optional[float]:
        with self._lock:
            lat = sorted(self._latencies_ms)
        if not lat:
            return None
        idx = min(len(lat) - 1, max(0, int(round(q * (len(lat) - 1)))))
        return lat[idx]

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self.counters)
            hist = dict(self.batch_hist)
            depth = self.queue_depth
            rows, padded = self.dispatched_rows, self.padded_rows
            dfail = dict(self.dispatch_failures)
            circuit = self.circuit_open
        mean_batch = rows / counters["dispatches"] if counters["dispatches"] \
            else 0.0
        return {
            **counters,
            "queue_depth": depth,
            "batch_hist": hist,
            "mean_batch_rows": mean_batch,
            "pad_overhead_rows": padded,
            "dispatch_failures": dfail,
            "circuit_open": circuit,
            "p50_ms": self.quantile_ms(0.50),
            "p95_ms": self.quantile_ms(0.95),
            "p99_ms": self.quantile_ms(0.99),
            **({"economics": self.ledger.snapshot()}
               if self.ledger is not None else {}),
            **({"slo_burn": self.burn.snapshot()}
               if self.burn is not None else {}),
        }

    def render(self) -> str:
        """Prometheus text exposition (served at /metrics)."""
        b = PromBuilder()
        self._render_into(b)
        return b.render()

    def _render_into(self, b: PromBuilder):
        s = self.snapshot()
        px = self._PREFIX
        b.family(f"{px}_requests_total", "counter")
        for outcome in ("submitted", "completed", "rejected", "expired",
                        "failed"):
            b.sample(f"{px}_requests_total", s[outcome],
                     {"outcome": outcome})
        b.family(f"{px}_dispatches_total", "counter")
        b.sample(f"{px}_dispatches_total", s["dispatches"])
        b.family(f"{px}_queue_depth", "gauge")
        b.sample(f"{px}_queue_depth", s["queue_depth"])
        b.family(f"{px}_latency_ms", "summary")
        for q, key in ((0.5, "p50_ms"), (0.95, "p95_ms"), (0.99, "p99_ms")):
            b.sample(f"{px}_latency_ms", s[key], {"quantile": q}, round_to=3)
        b.family(f"{px}_batch_rows", "histogram")
        hist = s["batch_hist"]
        for le in BATCH_BUCKETS:
            cum = sum(n for rows, n in hist.items() if rows <= le)
            b.sample(f"{px}_batch_rows_bucket", cum, {"le": le})
        b.sample(f"{px}_batch_rows_bucket", sum(hist.values()),
                 {"le": "+Inf"})
        b.sample(f"{px}_batch_rows_count", sum(hist.values()))
        b.sample(f"{px}_batch_rows_sum",
                 sum(r * n for r, n in hist.items()))
        b.family(f"{px}_dispatch_failures_total", "counter")
        for kind in sorted(s["dispatch_failures"]):
            b.sample(f"{px}_dispatch_failures_total",
                     s["dispatch_failures"][kind], {"kind": kind})
        b.family(f"{px}_circuit_open", "gauge")
        b.sample(f"{px}_circuit_open", int(s["circuit_open"]))
        self._render_economics_into(b, s)

    def _render_economics_into(self, b: PromBuilder, s: dict):
        """Serving-economics families (ISSUE 11): phase tiling, token
        efficiency, decode MFU, per-tenant/per-class device-seconds and
        SLO burn rates — rendered only when the engine attached the
        providers, under this metrics object's own prefix (pdtpu_serving
        for the predictor engine, pdtpu_llm for the LLM engine)."""
        px = self._PREFIX
        if self.ledger is not None:
            e = s["economics"]
            b.family(f"{px}_phase_seconds_total", "counter")
            for phase, secs in sorted(e["phase_seconds"].items()):
                b.sample(f"{px}_phase_seconds_total", secs,
                         labels={"phase": phase}, round_to=4)
            b.family(f"{px}_wall_seconds", "gauge")
            b.sample(f"{px}_wall_seconds", e["wall_seconds"], round_to=4)
            b.family(f"{px}_token_efficiency", "gauge")
            b.sample(f"{px}_token_efficiency", e["token_efficiency"],
                     round_to=4)
            b.family(f"{px}_host_fraction", "gauge")
            b.sample(f"{px}_host_fraction", e["host_fraction"], round_to=4)
            b.family(f"{px}_decode_mfu", "gauge")
            b.sample(f"{px}_decode_mfu", e["decode_mfu"], round_to=6)
            if e["tenants"]:
                b.family(f"{px}_tenant_device_seconds_total", "counter")
                for tenant in sorted(e["tenants"]):
                    b.sample(f"{px}_tenant_device_seconds_total",
                             e["tenants"][tenant]["device_seconds"],
                             {"tenant": tenant}, round_to=6)
                b.family(f"{px}_tenant_device_tokens_total", "counter")
                for tenant in sorted(e["tenants"]):
                    b.sample(f"{px}_tenant_device_tokens_total",
                             e["tenants"][tenant]["tokens"],
                             {"tenant": tenant})
            if e["classes"]:
                b.family(f"{px}_class_device_seconds_total", "counter")
                for cls in sorted(e["classes"]):
                    b.sample(f"{px}_class_device_seconds_total",
                             e["classes"][cls]["device_seconds"],
                             {"slo": cls}, round_to=6)
                b.family(f"{px}_class_device_tokens_total", "counter")
                for cls in sorted(e["classes"]):
                    b.sample(f"{px}_class_device_tokens_total",
                             e["classes"][cls]["tokens"], {"slo": cls})
        if self.burn is not None:
            burn = s["slo_burn"]
            b.family(f"{px}_slo_burn_rate", "gauge")
            b.family(f"{px}_slo_burn_fired", "gauge")
            for cls in sorted(burn["classes"]):
                v = burn["classes"][cls]
                for window in ("fast", "slow"):
                    b.sample(f"{px}_slo_burn_rate", v[f"burn_{window}"],
                             {"slo": cls, "window": window}, round_to=3)
                b.sample(f"{px}_slo_burn_fired", int(v["fired"]),
                         {"slo": cls})


def _quantile(sorted_vals, q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


# The per-slot recurrent state the newest LLM engine's pool holds, in bytes
# (`LLMMetrics.set_recurrent_state`; 0: no engine with recurrent layers
# yet): for a reader that comes after the engine is gone, as
# `nn.layer.moe.EXPERT_TOKENS` is for the expert counts.
RECURRENT_STATE_BYTES = 0
# Likewise the K/V slabs of the newest LLM engine whose pool keeps window
# layers in a ring or latent pages, by kind: {"full": bytes, "window":
# bytes[, "latent": bytes[, "index": bytes]]}
# (`LLMMetrics.set_kv_pool_bytes`; empty: no such engine yet).
KV_POOL_BYTES: dict = {}


class LLMMetrics(ServingMetrics):
    """ServingMetrics extended for the continuous-batching LLM engine
    (ISSUE 5): TTFT and inter-token latency summaries, decode-throughput
    (tokens/sec) and slot-occupancy gauges, prefill/decode-step/token
    counters. Rendered under the `pdtpu_llm` family prefix so an LLM
    engine can share a /metrics endpoint with a predictor BatchingEngine
    without name collisions. The inherited batch-rows histogram counts
    ACTIVE rows per decode iteration — i.e. how well continuous batching
    keeps the fixed-width decode full."""

    _PREFIX = "pdtpu_llm"

    def __init__(self, window: int = 4096):
        super().__init__(window)
        self._ttft_ms: deque = deque(maxlen=self.window)
        # where a first token's time went, a window a phase: in a class
        # queue, bound to a slot that no launched step carried yet, in
        # the chunks but the last, in the last chunk's step
        self._ttft_phase_ms: Dict[str, deque] = {
            p: deque(maxlen=self.window) for p in TTFT_PHASES}
        self._intertoken_ms: deque = deque(maxlen=self.window)
        # (active_rows, step_ms) pairs: tokens/sec over the recent window
        self._decode_window: deque = deque(maxlen=self.window)
        self.counters.update({"prefills": 0, "decode_steps": 0,
                              "unified_steps": 0,
                              "sampler_filter_steps": 0,
                              "step_tokens_live": 0,
                              "step_tokens_computed": 0,
                              "attn_query_positions": 0,
                              "head_positions": 0,
                              "attn_query_heads_full": 0,
                              "attn_query_heads_window": 0,
                              "prefill_rows_deferred": 0,
                              "slot_steps_vacant_queued": 0,
                              "first_tokens": 0, "ttft_steps": 0,
                              "paged_rows_one_column": 0,
                              "paged_rows_wide": 0,
                              "steps_overlapped": 0,
                              "rows_discarded": 0,
                              "pool_copies": 0, "pool_lost": 0,
                              "moe_assignments": 0,
                              "recurrent_rows_started": 0,
                              "recurrent_rows_matrix": 0,
                              "recurrent_rows_loop": 0,
                              "window_kv_tokens": 0,
                              "full_kv_tokens": 0,
                              "sparse_keys_selected": 0,
                              "sparse_keys_resident": 0,
                              "index_layers_full": 0,
                              "index_layers_shared": 0,
                              "tokens_out": 0, "shed": 0, "quarantined": 0,
                              "brownout_entries": 0,
                              "prefix_hits": 0, "prefix_misses": 0,
                              "prefix_hit_tokens": 0,
                              "prefix_lookup_tokens": 0,
                              "spec_windows": 0, "spec_drafted": 0,
                              "spec_accepted": 0,
                              "spec_draft_quarantines": 0,
                              "sampled_tokens": 0,
                              "constrained_tokens": 0,
                              "adapter_swaps": 0,
                              "adapter_rollbacks": 0})
        self.slots_active = 0
        self.slots_total = 0
        # per-SLO-class accounting (ISSUE 6 overload control): aggregate
        # counters above stay authoritative for the drain reconciliation
        # invariant; these break the same events down by class so the
        # overload gates can pin e.g. interactive-only TTFT ceilings
        self.class_counters: Dict[str, Dict[str, int]] = {
            c: {"submitted": 0, "completed": 0, "shed": 0}
            for c in SLO_CLASSES}
        self._class_ttft: Dict[str, deque] = {
            c: deque(maxlen=self.window) for c in SLO_CLASSES}
        self.brownout = False
        self.inflight_tokens = 0
        # KV-pool block fragmentation (ISSUE 7): fraction of allocated
        # block tokens not holding valid KV, from
        # SlotPagedKVPool.fragmentation_ratio()
        self.fragmentation = 0.0
        # prefix cache + multi-tenancy (ISSUE 8): aggregate cache gauges
        # plus a per-tenant breakdown (lazily created per tenant id) —
        # aggregate counters above stay authoritative for the drain
        # reconciliation invariant
        self.cached_blocks = 0
        self.cache_evictions = 0
        # the eviction order's work (ISSUE 40): entries the pressure path
        # popped, and those of them that no longer stood
        self.cache_evict_pops = 0
        self.cache_evict_stale = 0
        self.tenants: Dict[str, Dict[str, int]] = {}
        # time-weighted slot occupancy (ISSUE 11 satellite): ∫occupancy·dt
        # integrated at pump granularity, so the average weighs each
        # occupancy level by how long it actually held — a snapshot-only
        # gauge read at scrape time sees whatever instant the scrape hit
        self._occ_integral = 0.0    # ∫ occupancy dt
        self._occ_wall = 0.0        # observed seconds
        self._occ_last_t: Optional[float] = None
        self._occ_prev = 0.0        # occupancy held since the last observe
        # per-slot sampling modes (ISSUE 18): slot occupancy broken down
        # by decode mode, plus the host-side cost of assembling the
        # per-step sampling operands (params, RNG lanes, grammar masks)
        self.sample_slots: Dict[str, int] = {
            "greedy": 0, "sampled": 0, "constrained": 0}
        self._mask_overhead_ms: deque = deque(maxlen=self.window)
        self.grammars_compiled = 0
        # host-RAM KV spill tier (ISSUE 19): the engine pushes the
        # HostKVPool's snapshot() each pump; None until a tiered engine
        # reports, so a device-only engine renders no host families
        self.host_kv: Optional[Dict[str, int]] = None
        # sparse experts: a sparse engine sets this to its
        # `moe_expert_tokens` (the `[L, E]` totals live on the device and
        # are fetched when /metrics is rendered, never in a step); None,
        # and no expert family, for a dense model
        self.moe_source = None
        # bytes of per-slot recurrent state (state-space layers) the pool
        # holds beside the paged K/V; None, and no family, for a model
        # without recurrent layers
        self.recurrent_state_bytes: Optional[int] = None
        # bytes of the K/V slabs by kind ("full", "window") of a pool that
        # keeps window layers in a ring; None, and no family, otherwise
        self.kv_pool_bytes: Optional[dict] = None
        # multi-LoRA serving (ISSUE 18/20): emitted tokens per adapter id
        # ("base" for row-0 streams) — on an armed engine every emission
        # lands in exactly one bucket, so these sum to tokens_out
        self.adapter_tokens: Dict[str, int] = {}

    def _class(self, slo) -> Optional[Dict[str, int]]:
        return self.class_counters.get(slo) if slo else None

    def _tenant(self, tenant) -> Optional[Dict[str, int]]:
        if not tenant:
            return None
        return self.tenants.setdefault(tenant, {
            "submitted": 0, "completed": 0, "rejected": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_hit_tokens": 0,
            "prefix_lookup_tokens": 0, "inflight_tokens": 0,
            "cached_blocks": 0})

    # ---- engine callbacks ----
    def on_submit(self, queue_depth: int, slo: Optional[str] = None,
                  tenant: Optional[str] = None):
        super().on_submit(queue_depth)
        with self._lock:
            c = self._class(slo)
            if c is not None:
                c["submitted"] += 1
            t = self._tenant(tenant)
            if t is not None:
                t["submitted"] += 1

    def on_complete(self, latency_ms: float, slo: Optional[str] = None,
                    tenant: Optional[str] = None):
        super().on_complete(latency_ms)
        with self._lock:
            c = self._class(slo)
            if c is not None:
                c["completed"] += 1
            t = self._tenant(tenant)
            if t is not None:
                t["completed"] += 1

    def on_reject(self, reason: str, tenant: Optional[str] = None):
        super().on_reject(reason)
        with self._lock:
            t = self._tenant(tenant)
            if t is not None:
                t["rejected"] += 1

    def on_prefix_lookup(self, tenant: Optional[str], hit_tokens: int,
                         prompt_tokens: int):
        """One admission-time prefix-cache lookup: `hit_tokens` prompt
        tokens were served from cached KV (attach + COW) out of
        `prompt_tokens` looked up. The token-weighted ratio of these two
        counters is the cache hit rate."""
        with self._lock:
            hit = hit_tokens > 0
            self.counters["prefix_hits" if hit else "prefix_misses"] += 1
            self.counters["prefix_hit_tokens"] += int(hit_tokens)
            self.counters["prefix_lookup_tokens"] += int(prompt_tokens)
            t = self._tenant(tenant)
            if t is not None:
                t["prefix_hits" if hit else "prefix_misses"] += 1
                t["prefix_hit_tokens"] += int(hit_tokens)
                t["prefix_lookup_tokens"] += int(prompt_tokens)

    def set_tenant_inflight(self, per_tenant: Dict[str, int]):
        """Refresh per-tenant in-flight token gauges; tenants absent from
        the map (fully drained) read 0."""
        with self._lock:
            for t in self.tenants.values():
                t["inflight_tokens"] = 0
            for tenant, tokens in per_tenant.items():
                t = self._tenant(tenant)
                if t is not None:
                    t["inflight_tokens"] = int(tokens)

    def set_prefix_cache(self, cached_blocks: int, evictions: int,
                         per_tenant_cached: Optional[Dict[str, int]] = None,
                         evict_pops: int = 0, evict_stale: int = 0):
        with self._lock:
            self.cached_blocks = int(cached_blocks)
            self.cache_evictions = int(evictions)
            self.cache_evict_pops = int(evict_pops)
            self.cache_evict_stale = int(evict_stale)
            for tenant, n in (per_tenant_cached or {}).items():
                t = self._tenant(tenant)
                if t is not None:
                    t["cached_blocks"] = int(n)

    def on_shed(self, slo: Optional[str] = None):
        """A queued request was load-shed to make room for higher-priority
        work. Also counted as rejected (reason "shed") by the engine, so
        submitted == completed + rejected + expired + failed still holds."""
        with self._lock:
            self.counters["shed"] += 1
            c = self._class(slo)
            if c is not None:
                c["shed"] += 1

    def on_quarantine(self):
        with self._lock:
            self.counters["quarantined"] += 1

    def set_brownout(self, active: bool):
        with self._lock:
            entered = active and not self.brownout
            self.brownout = bool(active)
            if entered:
                self.counters["brownout_entries"] += 1

    def set_inflight_tokens(self, tokens: int):
        with self._lock:
            self.inflight_tokens = int(tokens)

    def set_fragmentation(self, ratio: float):
        with self._lock:
            self.fragmentation = float(ratio)

    def on_prefill(self, ttft_ms: float, slo: Optional[str] = None):
        with self._lock:
            self.counters["prefills"] += 1
            self._ttft_ms.append(float(ttft_ms))
            if slo in self._class_ttft:
                self._class_ttft[slo].append(float(ttft_ms))

    def on_decode_step(self, active_rows: int, step_ms: float,
                       tokens: Optional[int] = None):
        """One committed decode iteration over `active_rows` rows.
        `tokens` is how many tokens the iteration actually emitted —
        under speculative decoding (ISSUE 17) an accepted draft window
        commits several tokens per row, so throughput counters take the
        real emission while the batch-rows histogram keeps counting HOW
        FULL the fixed-width step was (its documented meaning)."""
        tokens = int(active_rows) if tokens is None else int(tokens)
        with self._lock:
            self.counters["decode_steps"] += 1
            self.counters["tokens_out"] += tokens
            self.batch_hist[active_rows] = \
                self.batch_hist.get(active_rows, 0) + 1
            self.dispatched_rows += int(active_rows)
            self.counters["dispatches"] += 1
            self.counters["unified_steps"] += 1
            self._intertoken_ms.append(float(step_ms))
            self._decode_window.append((tokens, float(step_ms)))

    def on_prefill_step(self):
        """One committed unified step that carried only prefill rows:
        with `on_decode_step` it makes `unified_steps` every committed
        step (`dispatches` keeps meaning steps with a decode row)."""
        with self._lock:
            self.counters["unified_steps"] += 1

    def on_sampler_filter_step(self):
        """One committed unified step in which at least one active row
        sampled, so the step's sampler ran its draw for every row (and
        one vocabulary sort if a sampling row set top-k or top-p).
        Over `unified_steps`: the share of steps that paid for it."""
        with self._lock:
            self.counters["sampler_filter_steps"] += 1

    def on_step_tokens(self, live: int, computed: int, deferred: int,
                       vacant_queued: int = 0, attn_positions: int = 0,
                       attn_heads_full: int = 0, attn_heads_window: int = 0,
                       head_positions: int = 0):
        """One committed unified step: `live` of the `computed` positions
        it ran held a token (`computed` is the engine's `step_tokens`:
        the packed width, or slots x chunk where nothing is packed), and
        `deferred` prefill rows waited for a later step because their
        chunk did not fit. `step_tokens_live / step_tokens_computed` is
        the share of the step's arithmetic that somebody reads.
        `vacant_queued` of its slots carried no row while as many
        requests were queued: over `unified_steps` x slots, the share of
        the step's rows lost to slot turnover. `attn_positions`: the query
        positions its attention computed, summed over the layers that
        attend (`step_tokens` for a layer whose queries stay on the packed
        block, slots x chunk for one that unpacks them); `step_tokens_live`
        x those layers over it is the share somebody reads there.
        `attn_heads_full` / `attn_heads_window`: the query-head rows the
        full and the windowed walk computed, positions x the layer's own
        query heads over the layers of that kind (the two differ where a
        model's head count does by layer type). `head_positions`: the
        positions the step's tail computed (the vocabulary head, the
        selection, the log-softmax): slots x (1 + draft window) emission
        rows, or the block's positions where nothing is gathered; over
        `step_tokens_computed` it is the share of the step the tail still
        works on."""
        with self._lock:
            self.counters["step_tokens_live"] += int(live)
            self.counters["step_tokens_computed"] += int(computed)
            self.counters["attn_query_positions"] += int(attn_positions)
            self.counters["head_positions"] += int(head_positions)
            self.counters["attn_query_heads_full"] += int(attn_heads_full)
            self.counters["attn_query_heads_window"] += \
                int(attn_heads_window)
            self.counters["prefill_rows_deferred"] += int(deferred)
            self.counters["slot_steps_vacant_queued"] += int(vacant_queued)

    def on_first_token(self, queued_ms: float, bound_ms: float,
                       prefill_ms: float, first_fetch_ms: float,
                       steps: int):
        """One request's first token: the four phases its TTFT is the sum
        of (`TTFT_PHASES`), and the unified steps committed from its
        admission to it. `ttft_steps / first_tokens` is the mean number
        of steps a first token costs."""
        with self._lock:
            for phase, ms in zip(TTFT_PHASES, (queued_ms, bound_ms,
                                               prefill_ms, first_fetch_ms)):
                self._ttft_phase_ms[phase].append(float(ms))
            self.counters["first_tokens"] += 1
            self.counters["ttft_steps"] += int(steps)

    def on_step_overlapped(self):
        """One unified step launched while its predecessor was still
        unretired: the chip had it queued before the host fetched and
        committed the step before it. Over `unified_steps`: the share of
        steps that paid no host gap."""
        with self._lock:
            self.counters["steps_overlapped"] += 1

    def on_rows_discarded(self, n: int):
        """`n` rows of a retired step belonged to requests that had ended
        after the step was launched (EOS, a deadline, a grammar's end, an
        evacuation): their tokens were dropped, not emitted."""
        with self._lock:
            self.counters["rows_discarded"] += int(n)

    def on_pool_copy(self):
        """One copy of the whole pool, for a blame probe to be donated
        in the pool's place: the failure path alone."""
        with self._lock:
            self.counters["pool_copies"] += 1

    def on_pool_lost(self):
        """One dispatch after which the pool was found consumed: its
        active rows failed and the pool was zeroed."""
        with self._lock:
            self.counters["pool_lost"] += 1

    def set_recurrent_state(self, nbytes: int):
        with self._lock:
            self.recurrent_state_bytes = int(nbytes)
        global RECURRENT_STATE_BYTES
        RECURRENT_STATE_BYTES = int(nbytes)

    def set_kv_pool_bytes(self, by_kind: dict):
        with self._lock:
            self.kv_pool_bytes = dict(by_kind)
        global KV_POOL_BYTES
        KV_POOL_BYTES = dict(by_kind)

    def on_kv_tokens(self, window: int, full: int):
        """One committed unified step: the keys one window layer's call
        had to read (sum over the active rows of min(length, window); 0
        on an engine without window layers) and one full or latent
        layer's (sum of the lengths; every engine)."""
        with self._lock:
            self.counters["window_kv_tokens"] += int(window)
            self.counters["full_kv_tokens"] += int(full)

    def on_sparse_keys(self, selected: int, resident: int, full: int,
                       shared: int):
        """One committed unified step of a model with learned sparse
        attention: summed over the step's live query positions, the keys
        one sparse layer attends to (`min(position + 1, index_topk)`) and
        the keys it could see (`position + 1`: what the indexer scored);
        and the layers that made a selection in the step (`full`) and that
        reused one (`shared`)."""
        with self._lock:
            self.counters["sparse_keys_selected"] += int(selected)
            self.counters["sparse_keys_resident"] += int(resident)
            self.counters["index_layers_full"] += int(full)
            self.counters["index_layers_shared"] += int(shared)

    def on_paged_rows(self, one_column: int, wide: int):
        """Of a committed step's rows, by their live columns: `one_column`
        have one (a decode row, a prompt's one-token tail), and the paged
        kernels run their groups over that column alone where their trace
        holds the one-column body (`ops/paged_attention.py`;
        `pallas_mode.KERNEL_TILINGS`' `one_column_rows` says where);
        `wide` have two or more (a prefill chunk, a verify window). Free
        and deferred rows are in neither."""
        with self._lock:
            self.counters["paged_rows_one_column"] += int(one_column)
            self.counters["paged_rows_wide"] += int(wide)

    def on_recurrent_rows_started(self, n: int):
        """`n` rows of a committed step began at position 0: the step
        started them from a zero recurrent state."""
        with self._lock:
            self.counters["recurrent_rows_started"] += int(n)

    def on_recurrent_rows(self, matrix: int, loop: int):
        """Of the rows whose recurrent state a committed step advanced (a
        recurrent layer's call; every such layer sees the same rows):
        `matrix` had enough live columns for the recurrence's kernel to
        advance them at once, in matrix form (`ops/ssm.py`:
        `MATRIX_COLUMNS`), `loop` were walked a column at a time."""
        with self._lock:
            self.counters["recurrent_rows_matrix"] += int(matrix)
            self.counters["recurrent_rows_loop"] += int(loop)

    def on_moe_assignments(self, n: int):
        """A fetch of the device's per-expert totals
        (`LLMEngine.moe_expert_tokens()`: on /metrics, at `stop()`, on
        demand) found `n` (position, expert) pairs routed to experts this
        engine holds since the last one. The counter is the table's sum as
        of the newest fetch: live tokens x experts per token x expert
        layers where every expert is held (nothing is dropped), the held
        experts' part of that where the layers hold a share."""
        with self._lock:
            self.counters["moe_assignments"] += int(n)

    def on_spec_window(self, drafted: int, accepted: int):
        """One verified speculative window (ISSUE 17): `drafted` tokens
        proposed, `accepted` of them kept (the corrective token is not
        counted either way — it is ordinary decode output)."""
        with self._lock:
            self.counters["spec_windows"] += 1
            self.counters["spec_drafted"] += int(drafted)
            self.counters["spec_accepted"] += int(accepted)

    def on_sample_token(self, mode: str):
        """One emitted token from a non-greedy slot (ISSUE 18): `mode` is
        "sampled" (temperature/top-k/top-p RNG lane) or "constrained"
        (grammar-masked lane). Greedy emissions stay in `tokens_out`
        alone, so the two counters partition the non-greedy traffic."""
        with self._lock:
            self.counters[f"{mode}_tokens"] += 1

    def set_sample_slots(self, counts: Dict[str, int]):
        """Refresh the per-mode slot occupancy gauge from the engine's
        sampling table (greedy / sampled / constrained active slots)."""
        with self._lock:
            self.sample_slots = {
                m: int(counts.get(m, 0))
                for m in ("greedy", "sampled", "constrained")}

    def on_mask_overhead(self, ms: float):
        """Host-side sampling-operand assembly time for one unified step
        (params + RNG-lane counters + DFA states + grammar bank)."""
        with self._lock:
            self._mask_overhead_ms.append(float(ms))

    def set_grammars(self, compiled: int):
        with self._lock:
            self.grammars_compiled = int(compiled)

    def set_host_kv(self, snap: Dict[str, int]):
        """Refresh the host spill tier's gauges/counters from
        `HostKVPool.snapshot()` (pages, bytes, spills, onboards, hits,
        misses, evictions, rejected)."""
        with self._lock:
            self.host_kv = dict(snap)

    def mask_overhead_quantile_ms(self, q: float) -> Optional[float]:
        with self._lock:
            vals = sorted(self._mask_overhead_ms)
        return _quantile(vals, q)

    def on_draft_quarantine(self):
        """A request's draft was quarantined (spec_off) after a poisoned
        draft dispatch; its target stream continues as plain decode."""
        with self._lock:
            self.counters["spec_draft_quarantines"] += 1

    # ---- multi-LoRA serving (ISSUE 20) ----
    def on_adapter_token(self, adapter: str):
        """One emitted token attributed to a LoRA adapter: `adapter` is
        the bank id, or "base" for a row-0 (no-adapter) stream. The
        per-adapter counters partition `tokens_out` exactly — the token
        analogue of the ledger's adapter-seconds partitioning tenant
        device-seconds."""
        with self._lock:
            self.adapter_tokens[adapter] = \
                self.adapter_tokens.get(adapter, 0) + 1

    def on_adapter_swap(self):
        with self._lock:
            self.counters["adapter_swaps"] += 1

    def on_adapter_rollback(self):
        with self._lock:
            self.counters["adapter_rollbacks"] += 1

    def set_slots(self, active: int, total: int):
        with self._lock:
            self.slots_active = int(active)
            self.slots_total = int(total)

    def observe_occupancy(self, now: float):
        """Advance the occupancy·dt integral to `now` (called once per
        pump iteration): the occupancy the LAST observation left behind
        is credited for the elapsed interval, then the current gauge
        becomes the new level. The averaged value is the utilization the
        ledger's `token_efficiency` is bounded by (a padded-but-occupied
        slot still advances positions; an empty one cannot)."""
        with self._lock:
            if self._occ_last_t is not None:
                dt = now - self._occ_last_t
                if dt > 0:
                    self._occ_integral += self._occ_prev * dt
                    self._occ_wall += dt
            self._occ_last_t = now
            self._occ_prev = (self.slots_active / self.slots_total
                              if self.slots_total else 0.0)

    # ---- views ----
    def ttft_quantile_ms(self, q: float,
                         slo: Optional[str] = None) -> Optional[float]:
        with self._lock:
            src = self._class_ttft[slo] if slo else self._ttft_ms
            vals = sorted(src)
        return _quantile(vals, q)

    def ttft_phase_quantiles_ms(self,
                                phase: str) -> Dict[str, Optional[float]]:
        """{"p50", "p99"} of one of `TTFT_PHASES` over the recent window."""
        with self._lock:
            vals = sorted(self._ttft_phase_ms[phase])
        return {"p50": _quantile(vals, 0.5), "p99": _quantile(vals, 0.99)}

    def intertoken_quantile_ms(self, q: float) -> Optional[float]:
        with self._lock:
            vals = sorted(self._intertoken_ms)
        return _quantile(vals, q)

    def tokens_per_s(self) -> float:
        """Decode throughput over the recent window: generated tokens per
        second of decode-step wall time (idle gaps excluded, so the gauge
        means 'how fast the decode loop moves when it moves')."""
        with self._lock:
            pairs = list(self._decode_window)
        total_ms = sum(ms for _, ms in pairs)
        if total_ms <= 0:
            return 0.0
        return sum(rows for rows, _ in pairs) / (total_ms / 1e3)

    def snapshot(self) -> dict:
        s = super().snapshot()
        with self._lock:
            s["slots_active"] = self.slots_active
            s["slots_total"] = self.slots_total
            s["classes"] = {c: dict(v)
                            for c, v in self.class_counters.items()}
            s["brownout"] = self.brownout
            s["inflight_tokens"] = self.inflight_tokens
            s["kv_fragmentation"] = self.fragmentation
            s["cached_blocks"] = self.cached_blocks
            s["cache_evictions"] = self.cache_evictions
            s["cache_evict_pops"] = self.cache_evict_pops
            s["cache_evict_stale"] = self.cache_evict_stale
            s["tenants"] = {t: dict(v) for t, v in self.tenants.items()}
            s["slot_occupancy_avg"] = (
                self._occ_integral / self._occ_wall
                if self._occ_wall > 0 else None)
        for t in s["tenants"].values():
            t["cache_hit_rate"] = (
                t["prefix_hit_tokens"] / t["prefix_lookup_tokens"]
                if t["prefix_lookup_tokens"] else 0.0)
        s["prefix_hit_rate"] = (
            s["prefix_hit_tokens"] / s["prefix_lookup_tokens"]
            if s["prefix_lookup_tokens"] else 0.0)
        s["slot_occupancy"] = (self.slots_active / self.slots_total
                               if self.slots_total else 0.0)
        s["tokens_per_s"] = self.tokens_per_s()
        s["spec_accept_rate"] = (s["spec_accepted"] / s["spec_drafted"]
                                 if s["spec_drafted"] else None)
        with self._lock:
            s["sample_slots"] = dict(self.sample_slots)
            s["grammars_compiled"] = self.grammars_compiled
            s["host_kv"] = (dict(self.host_kv)
                            if self.host_kv is not None else None)
            s["adapter_tokens"] = dict(self.adapter_tokens)
            s["recurrent_state_bytes"] = self.recurrent_state_bytes
            s["kv_pool_bytes"] = (None if self.kv_pool_bytes is None
                                  else dict(self.kv_pool_bytes))
        s["mask_overhead_p99_ms"] = self.mask_overhead_quantile_ms(0.99)
        s["shed_rate"] = (s["shed"] / s["submitted"] if s["submitted"]
                          else 0.0)
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            s[f"ttft_{key}_ms"] = self.ttft_quantile_ms(q)
            s[f"intertoken_{key}_ms"] = self.intertoken_quantile_ms(q)
        for c in SLO_CLASSES:
            s[f"ttft_p99_ms_{c}"] = self.ttft_quantile_ms(0.99, slo=c)
        s["ttft_phase_ms"] = {p: self.ttft_phase_quantiles_ms(p)
                              for p in TTFT_PHASES}
        return s

    def _render_into(self, b: PromBuilder):
        super()._render_into(b)
        # fetched before the snapshot: the fetch is what brings
        # `moe_assignments` up to the device's totals
        moe_table = None if self.moe_source is None else self.moe_source()
        s = self.snapshot()
        px = self._PREFIX
        for fam, prefix in ((f"{px}_ttft_ms", "ttft"),
                            (f"{px}_intertoken_ms", "intertoken")):
            b.family(fam, "summary")
            for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                b.sample(fam, s[f"{prefix}_{key}_ms"], {"quantile": q},
                         round_to=3)
        b.family(f"{px}_ttft_phase_ms", "summary")
        for phase, qs in s["ttft_phase_ms"].items():
            for q, key in ((0.5, "p50"), (0.99, "p99")):
                b.sample(f"{px}_ttft_phase_ms", qs[key],
                         {"phase": phase, "quantile": q}, round_to=3)
        b.family(f"{px}_tokens_per_s", "gauge")
        b.sample(f"{px}_tokens_per_s", s["tokens_per_s"], round_to=3)
        b.family(f"{px}_slots_active", "gauge")
        b.sample(f"{px}_slots_active", s["slots_active"])
        b.family(f"{px}_slots_total", "gauge")
        b.sample(f"{px}_slots_total", s["slots_total"])
        b.family(f"{px}_slot_occupancy", "gauge")
        b.sample(f"{px}_slot_occupancy", s["slot_occupancy"], round_to=4)
        b.family(f"{px}_slot_occupancy_avg", "gauge")
        b.sample(f"{px}_slot_occupancy_avg", s["slot_occupancy_avg"],
                 round_to=4)
        b.family(f"{px}_tokens_total", "counter")
        b.sample(f"{px}_tokens_total", s["tokens_out"])
        b.family(f"{px}_decode_steps_total", "counter")
        b.sample(f"{px}_decode_steps_total", s["decode_steps"])
        b.family(f"{px}_unified_steps_total", "counter")
        b.sample(f"{px}_unified_steps_total", s["unified_steps"])
        b.family(f"{px}_sampler_filter_steps_total", "counter")
        b.sample(f"{px}_sampler_filter_steps_total",
                 s["sampler_filter_steps"])
        for name in ("step_tokens_live", "step_tokens_computed",
                     "attn_query_positions", "head_positions",
                     "attn_query_heads_full",
                     "attn_query_heads_window", "prefill_rows_deferred",
                     "slot_steps_vacant_queued",
                     "first_tokens", "ttft_steps", "paged_rows_one_column",
                     "paged_rows_wide", "steps_overlapped",
                     "rows_discarded", "pool_copies", "pool_lost"):
            b.family(f"{px}_{name}_total", "counter")
            b.sample(f"{px}_{name}_total", s[name])
        b.family(f"{px}_prefills_total", "counter")
        b.sample(f"{px}_prefills_total", s["prefills"])
        if s["recurrent_state_bytes"] is not None:
            b.family(f"{px}_recurrent_state_bytes", "gauge")
            b.sample(f"{px}_recurrent_state_bytes",
                     s["recurrent_state_bytes"])
            for name in ("recurrent_rows_started", "recurrent_rows_matrix",
                         "recurrent_rows_loop"):
                b.family(f"{px}_{name}_total", "counter")
                b.sample(f"{px}_{name}_total", s[name])
        if s["kv_pool_bytes"] is not None:
            b.family(f"{px}_kv_pool_bytes", "gauge")
            for kind, nbytes in sorted(s["kv_pool_bytes"].items()):
                b.sample(f"{px}_kv_pool_bytes", nbytes, {"kind": kind})
            b.family(f"{px}_window_kv_tokens_total", "counter")
            b.sample(f"{px}_window_kv_tokens_total", s["window_kv_tokens"])
        b.family(f"{px}_full_kv_tokens_total", "counter")
        b.sample(f"{px}_full_kv_tokens_total", s["full_kv_tokens"])
        if s["index_layers_full"]:
            for name in ("sparse_keys_selected", "sparse_keys_resident",
                         "index_layers_full", "index_layers_shared"):
                b.family(f"{px}_{name}_total", "counter")
                b.sample(f"{px}_{name}_total", s[name])
        if self.moe_source is not None:
            b.family(f"{px}_moe_assignments_total", "counter")
            b.sample(f"{px}_moe_assignments_total", s["moe_assignments"])
            b.family(f"{px}_moe_expert_tokens_total", "counter")
            for layer, row in enumerate(moe_table):
                for expert, n in enumerate(row):
                    b.sample(f"{px}_moe_expert_tokens_total", int(n),
                             {"layer": layer, "expert": expert})
        # ---- speculative decoding families (ISSUE 17) ----
        b.family(f"{px}_spec_windows_total", "counter")
        b.sample(f"{px}_spec_windows_total", s["spec_windows"])
        b.family(f"{px}_spec_drafted_total", "counter")
        b.sample(f"{px}_spec_drafted_total", s["spec_drafted"])
        b.family(f"{px}_spec_accepted_total", "counter")
        b.sample(f"{px}_spec_accepted_total", s["spec_accepted"])
        b.family(f"{px}_spec_accept_rate", "gauge")
        b.sample(f"{px}_spec_accept_rate", s["spec_accept_rate"],
                 round_to=4)
        b.family(f"{px}_spec_draft_quarantines_total", "counter")
        b.sample(f"{px}_spec_draft_quarantines_total",
                 s["spec_draft_quarantines"])
        # ---- sampling + constrained decoding families (ISSUE 18) ----
        b.family(f"{px}_sample_slots", "gauge")
        for mode in ("greedy", "sampled", "constrained"):
            b.sample(f"{px}_sample_slots", s["sample_slots"].get(mode, 0),
                     {"mode": mode})
        b.family(f"{px}_sample_tokens_total", "counter")
        for mode in ("sampled", "constrained"):
            b.sample(f"{px}_sample_tokens_total", s[f"{mode}_tokens"],
                     {"mode": mode})
        b.family(f"{px}_sample_mask_overhead_ms", "summary")
        b.sample(f"{px}_sample_mask_overhead_ms", s["mask_overhead_p99_ms"],
                 {"quantile": "0.99"}, round_to=3)
        b.family(f"{px}_sample_grammars_compiled", "gauge")
        b.sample(f"{px}_sample_grammars_compiled", s["grammars_compiled"])
        # ---- multi-LoRA serving families (ISSUE 20) ----
        if s["adapter_tokens"]:
            b.family(f"{px}_adapter_tokens_total", "counter")
            for aid in sorted(s["adapter_tokens"]):
                b.sample(f"{px}_adapter_tokens_total",
                         s["adapter_tokens"][aid], {"adapter": aid})
            b.family(f"{px}_adapter_swaps_total", "counter")
            b.sample(f"{px}_adapter_swaps_total", s["adapter_swaps"])
            b.family(f"{px}_adapter_rollbacks_total", "counter")
            b.sample(f"{px}_adapter_rollbacks_total",
                     s["adapter_rollbacks"])
        # ---- tiered KV cache families (ISSUE 19) ----
        if s["host_kv"] is not None:
            hk = s["host_kv"]
            b.family(f"{px}_kv_host_pages_total", "gauge")
            b.sample(f"{px}_kv_host_pages_total", hk["pages"])
            b.family(f"{px}_kv_host_bytes_total", "gauge")
            b.sample(f"{px}_kv_host_bytes_total", hk["bytes"])
            b.family(f"{px}_kv_host_spills_total", "counter")
            b.sample(f"{px}_kv_host_spills_total", hk["spills"])
            b.family(f"{px}_kv_host_onboards_total", "counter")
            b.sample(f"{px}_kv_host_onboards_total", hk["onboards"])
            b.family(f"{px}_kv_host_evictions_total", "counter")
            b.sample(f"{px}_kv_host_evictions_total", hk["evictions"])
        # ---- overload control + supervision families (ISSUE 6) ----
        b.family(f"{px}_class_requests_total", "counter")
        for c in SLO_CLASSES:
            for outcome in ("submitted", "completed", "shed"):
                b.sample(f"{px}_class_requests_total",
                         s["classes"][c][outcome],
                         {"slo": c, "outcome": outcome})
        b.family(f"{px}_class_ttft_ms", "summary")
        for c in SLO_CLASSES:
            b.sample(f"{px}_class_ttft_ms", s[f"ttft_p99_ms_{c}"],
                     {"slo": c, "quantile": "0.99"}, round_to=3)
        b.family(f"{px}_shed_total", "counter")
        b.sample(f"{px}_shed_total", s["shed"])
        b.family(f"{px}_quarantined_total", "counter")
        b.sample(f"{px}_quarantined_total", s["quarantined"])
        b.family(f"{px}_brownout", "gauge")
        b.sample(f"{px}_brownout", int(s["brownout"]))
        b.family(f"{px}_brownout_entries_total", "counter")
        b.sample(f"{px}_brownout_entries_total", s["brownout_entries"])
        b.family(f"{px}_inflight_tokens", "gauge")
        b.sample(f"{px}_inflight_tokens", s["inflight_tokens"])
        b.family(f"{px}_kv_fragmentation", "gauge")
        b.sample(f"{px}_kv_fragmentation", s["kv_fragmentation"], round_to=4)
        # ---- prefix cache + multi-tenancy families (ISSUE 8) ----
        b.family(f"{px}_prefix_hits_total", "counter")
        b.sample(f"{px}_prefix_hits_total", s["prefix_hits"])
        b.family(f"{px}_prefix_misses_total", "counter")
        b.sample(f"{px}_prefix_misses_total", s["prefix_misses"])
        b.family(f"{px}_prefix_hit_tokens_total", "counter")
        b.sample(f"{px}_prefix_hit_tokens_total", s["prefix_hit_tokens"])
        b.family(f"{px}_prefix_hit_rate", "gauge")
        b.sample(f"{px}_prefix_hit_rate", s["prefix_hit_rate"], round_to=4)
        b.family(f"{px}_cached_blocks", "gauge")
        b.sample(f"{px}_cached_blocks", s["cached_blocks"])
        b.family(f"{px}_cache_evictions_total", "counter")
        b.sample(f"{px}_cache_evictions_total", s["cache_evictions"])
        b.family(f"{px}_cache_evict_pops_total", "counter")
        b.sample(f"{px}_cache_evict_pops_total", s["cache_evict_pops"])
        b.family(f"{px}_cache_evict_stale_total", "counter")
        b.sample(f"{px}_cache_evict_stale_total", s["cache_evict_stale"])
        if s["tenants"]:
            b.family(f"{px}_tenant_requests_total", "counter")
            for tenant in sorted(s["tenants"]):
                tv = s["tenants"][tenant]
                for outcome in ("submitted", "completed", "rejected"):
                    b.sample(f"{px}_tenant_requests_total", tv[outcome],
                             {"tenant": tenant, "outcome": outcome})
            for fam, key, typ, rnd in (
                    ("tenant_cache_hit_rate", "cache_hit_rate", "gauge", 4),
                    ("tenant_cached_blocks", "cached_blocks", "gauge", None),
                    ("tenant_inflight_tokens", "inflight_tokens", "gauge",
                     None)):
                b.family(f"{px}_{fam}", typ)
                for tenant in sorted(s["tenants"]):
                    b.sample(f"{px}_{fam}", s["tenants"][tenant][key],
                             {"tenant": tenant}, round_to=rnd)


class RouterMetrics:
    """Front-of-fleet router counters (ISSUE 14): routing decisions per
    replica, prefix-affinity hit rate, per-replica health/quarantine
    state, failovers with resumed-stream totals, and router-level
    rejects. Rendered under the `pdtpu_router_*` prefix so the router's
    /metrics can concatenate the replicas' `pdtpu_llm_*` families
    without a name collision."""

    _PREFIX = "pdtpu_router"

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "rejected": 0, "failed": 0,
        }
        self.reject_reasons: Dict[str, int] = {}
        self.routed: Dict[str, int] = {}           # replica -> decisions
        self.replica_state: Dict[str, str] = {}    # replica -> health word
        self.quarantines: Dict[str, int] = {}      # replica -> times down
        self.failovers: Dict[str, int] = {}        # dead replica -> events
        self.resumed_streams = 0
        self.readmissions: Dict[str, int] = {}
        self.affinity_hits = 0                     # routed to a prefix match
        self.affinity_decisions = 0
        self.replica_inflight: Dict[str, int] = {}
        self.replica_weight_version: Dict[str, str] = {}   # ISSUE 16
        # prefill/decode disaggregation (ISSUE 19)
        self.replica_role: Dict[str, str] = {}     # replica -> role tag
        self.handoffs = 0                          # prefill→decode moves
        self.handoffs_failed = 0                   # export succeeded but no
        #                                            decode home re-admitted
        #                                            the stream in time
        self._handoff_ms: deque = deque(maxlen=4096)

    # ---- router callbacks ----
    def on_submit(self):
        with self._lock:
            self.counters["submitted"] += 1

    def on_route(self, replica: str, prefix_hit: bool):
        with self._lock:
            self.routed[replica] = self.routed.get(replica, 0) + 1
            self.affinity_decisions += 1
            if prefix_hit:
                self.affinity_hits += 1

    def on_reject(self, reason: str):
        with self._lock:
            self.counters["rejected"] += 1
            self.reject_reasons[reason] = \
                self.reject_reasons.get(reason, 0) + 1

    def on_complete(self):
        with self._lock:
            self.counters["completed"] += 1

    def on_fail(self):
        with self._lock:
            self.counters["failed"] += 1

    def set_replica(self, replica: str, state: str, inflight_tokens: int,
                    weight_version: Optional[str] = None,
                    role: Optional[str] = None):
        with self._lock:
            self.replica_state[replica] = state
            self.replica_inflight[replica] = int(inflight_tokens)
            if weight_version is not None:
                self.replica_weight_version[replica] = str(weight_version)
            if role is not None:
                self.replica_role[replica] = str(role)

    def on_handoff(self, src: str, dst: str, ms: float):
        """One completed prefill→decode stream handoff (ISSUE 19): KV
        exported from `src`, stream re-admitted on `dst` after `ms`
        milliseconds of export-to-accepted-submit wall time."""
        with self._lock:
            self.handoffs += 1
            self._handoff_ms.append(float(ms))

    def on_handoff_failed(self):
        """A handoff export could not be re-admitted anywhere (the stream
        falls back to failover re-prefill, never dropped)."""
        with self._lock:
            self.handoffs_failed += 1

    def handoff_quantile_ms(self, q: float) -> Optional[float]:
        with self._lock:
            vals = sorted(self._handoff_ms)
        return _quantile(vals, q)

    def on_quarantine(self, replica: str):
        with self._lock:
            self.quarantines[replica] = self.quarantines.get(replica, 0) + 1

    def on_readmit(self, replica: str):
        with self._lock:
            self.readmissions[replica] = \
                self.readmissions.get(replica, 0) + 1

    def on_failover(self, replica: str, resumed: int):
        with self._lock:
            self.failovers[replica] = self.failovers.get(replica, 0) + 1
            self.resumed_streams += resumed

    # ---- views ----
    def affinity_hit_rate(self) -> float:
        with self._lock:
            if self.affinity_decisions == 0:
                return 0.0
            return self.affinity_hits / self.affinity_decisions

    def snapshot(self) -> dict:
        with self._lock:
            return {
                **self.counters,
                "reject_reasons": dict(self.reject_reasons),
                "routed": dict(self.routed),
                "replica_state": dict(self.replica_state),
                "replica_inflight": dict(self.replica_inflight),
                "quarantines": dict(self.quarantines),
                "readmissions": dict(self.readmissions),
                "failovers": dict(self.failovers),
                "replica_weight_version": dict(self.replica_weight_version),
                "replica_role": dict(self.replica_role),
                "resumed_streams": self.resumed_streams,
                "handoffs": self.handoffs,
                "handoffs_failed": self.handoffs_failed,
                "affinity_hit_rate": (
                    self.affinity_hits / self.affinity_decisions
                    if self.affinity_decisions else 0.0),
            }

    def render(self) -> str:
        b = PromBuilder()
        self._render_into(b)
        return b.render()

    def _render_into(self, b: PromBuilder):
        s = self.snapshot()
        px = self._PREFIX
        b.family(f"{px}_requests_total", "counter")
        for outcome in ("submitted", "completed", "rejected", "failed"):
            b.sample(f"{px}_requests_total", s[outcome],
                     {"outcome": outcome})
        b.family(f"{px}_rejects_total", "counter")
        for reason in sorted(s["reject_reasons"]):
            b.sample(f"{px}_rejects_total", s["reject_reasons"][reason],
                     {"reason": reason})
        b.family(f"{px}_routed_total", "counter")
        for replica in sorted(s["routed"]):
            b.sample(f"{px}_routed_total", s["routed"][replica],
                     {"replica": replica})
        b.family(f"{px}_replica_up", "gauge")
        for replica in sorted(s["replica_state"]):
            up = int(s["replica_state"][replica] == "ok")
            b.sample(f"{px}_replica_up", up, {"replica": replica})
        b.family(f"{px}_replica_inflight_tokens", "gauge")
        for replica in sorted(s["replica_inflight"]):
            b.sample(f"{px}_replica_inflight_tokens",
                     s["replica_inflight"][replica], {"replica": replica})
        b.family(f"{px}_quarantines_total", "counter")
        for replica in sorted(s["quarantines"]):
            b.sample(f"{px}_quarantines_total", s["quarantines"][replica],
                     {"replica": replica})
        b.family(f"{px}_readmissions_total", "counter")
        for replica in sorted(s["readmissions"]):
            b.sample(f"{px}_readmissions_total",
                     s["readmissions"][replica], {"replica": replica})
        b.family(f"{px}_failovers_total", "counter")
        for replica in sorted(s["failovers"]):
            b.sample(f"{px}_failovers_total", s["failovers"][replica],
                     {"replica": replica})
        b.family(f"{px}_replica_weight_info", "gauge")
        for replica in sorted(s["replica_weight_version"]):
            # info-style gauge: constant 1, the version rides the label
            b.sample(f"{px}_replica_weight_info", 1,
                     {"replica": replica,
                      "version": s["replica_weight_version"][replica]})
        b.family(f"{px}_resumed_streams_total", "counter")
        b.sample(f"{px}_resumed_streams_total", s["resumed_streams"])
        b.family(f"{px}_prefix_affinity_hit_rate", "gauge")
        b.sample(f"{px}_prefix_affinity_hit_rate", s["affinity_hit_rate"],
                 round_to=4)
        # ---- prefill/decode disaggregation families (ISSUE 19) ----
        if s["replica_role"]:
            b.family(f"{px}_replica_role_info", "gauge")
            for replica in sorted(s["replica_role"]):
                b.sample(f"{px}_replica_role_info", 1,
                         {"replica": replica,
                          "role": s["replica_role"][replica]})
        b.family(f"{px}_handoffs_total", "counter")
        b.sample(f"{px}_handoffs_total", s["handoffs"])
        b.family(f"{px}_handoffs_failed_total", "counter")
        b.sample(f"{px}_handoffs_failed_total", s["handoffs_failed"])
        hq = self.handoff_quantile_ms(0.99)
        if hq is not None:
            b.family(f"{px}_handoff_ms", "summary")
            b.sample(f"{px}_handoff_ms", hq, {"quantile": "0.99"},
                     round_to=3)
