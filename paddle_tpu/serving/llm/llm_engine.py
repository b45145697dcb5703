"""Continuous-batching LLM decode engine over the slot-paged KV pool
(ISSUE 5 tentpole; ISSUE 6 supervision + overload control; ISSUE 7
ragged paged attention + chunked prefill; ISSUE 8 prefix-sharing radix
KV cache + multi-tenant scheduling; ISSUE 17 speculative decoding).

Prefix sharing (ISSUE 8): admission consults a per-tenant radix
`PrefixCache` — a prompt hitting a cached prefix attaches the donor's
refcounted KV pages (partial blocks copy-on-write into the slot's own
page) and chunk-prefills only the suffix, so N requests sharing a prefix
pay ~one prefill total and a full hit's TTFT is one chunk-wide step.
Chunk-invariance (PR 7) makes warm streams bit-identical to cold ones.
Multi-tenancy: requests carry a tenant id; dequeue is tenant-fair within
each SLO class, an optional per-tenant in-flight token quota rejects
with reason "tenant_quota", and tenants never share cached KV.

The batch-locked `models.generation.generate()` loop makes every sequence
enter together, share one prompt length and pay the batch's full
`max_new_tokens` — one long request holds the whole batch's KV slabs
hostage. This engine schedules the same numeric path (the
`make_decoder_fns` prefill builder routed through the ragged
paged-attention kernel, so outputs are bit-identical per row) as a
continuously-batched service:

- ONE unified mixed-row dispatch per pump iteration (`_launch`): every
  slot contributes a fixed-width `[prefill_chunk]` row — a prompt chunk
  for prefilling requests, `[last_tok, 0, ...]` for decoding requests,
  zeros for free slots — and the single jitted executable writes all KV
  stripes, runs ragged paged attention over the pool's block tables +
  per-row target lengths, and emits each row's next greedy token. No
  per-pow2-bucket prefill executable zoo, no bucket padding FLOPs: the
  engine compiles exactly one step program for its lifetime;
- **token packing**: the `[slots, prefill_chunk]` rows are the step's
  operands and results, not what it computes. An engine wider than
  `MIN_STEP_TOKENS` positions packs the columns that hold a token into one
  `[step_tokens, 1]` block inside the executable (`ops.attention.
  token_pack`, from `adv` and `pos` alone) and runs every token-wise
  operation of the body — embedding, norms, projections, MLP or experts,
  LoRA deltas — on that; attention alone unpacks to the slots' layout.
  `step_tokens = min(slots * chunk, max(slots * (1 + draft window) +
  chunk, MIN_STEP_TOKENS))` follows from the engine's shapes, so a decode
  row costs one position and not `prefill_chunk`. The step's tail — the
  vocabulary head, the grammar mask, the selection, the log-softmax —
  runs on the columns the host reads, `slots x (1 + draft window)`
  emission rows gathered behind the body (`head_positions` a step), packed
  or not. The scheduler keeps a step's live tokens within it:
  decode rows always fit, prefill rows ride oldest first with their whole
  chunk or wait a step (`prefill_rows_deferred`);
- **one step in flight**: a pump pass launches step k+1 (`_launch`) before
  it fetches and commits step k (`_retire`), so the chip runs while the
  host admits, builds rows, uploads and commits. Step k+1's rows are
  built from what step k WILL commit — known at launch for a plain row:
  positions advance by `adv`, a row at its `max_new_tokens` is not
  scheduled again — and the one unknown, the token step k selects, is fed
  back on the device (`feed`, `prev_sel`). A request that ends unseen by
  the launch (EOS, a deadline) rides one step more and that row is
  discarded at `_retire`, which matches rows to requests by identity, not
  by slot. Draft windows, grammar rows and engines that book device time
  per step keep launch, retire (`_can_launch_ahead`); a dispatch that
  fails ahead of its predecessor is retried after that one is retired.
  The step is donated the pool it reads: the K/V writes and a
  state-space layer's state land in the buffers they were read from, so
  the pool exists once, a step queued behind a running one included, and
  `pool.slabs` as held before a launch is a deleted array after it. A
  blame probe is donated a copy (`pool_copies`, the failure path alone);
  a dispatch that took the pool and failed leaves the active rows
  without their K/V: they fail and the pool starts again from zeros
  (`pool_lost`);
- **chunked prefill**: prompts longer than `prefill_chunk` are admitted
  as fixed-size chunks interleaved with the decode loop, so a short
  prompt's TTFT is bounded by a couple of chunk-width steps instead of a
  long neighbor's whole-prompt prefill. A row's first token is emitted by
  the step that lands its final chunk (TTFT ends there);
- between iterations the scheduler admits queued requests into freed
  slots and evicts finished rows (EOS / per-request max-tokens /
  deadline — queued, mid-prefill and mid-decode alike), so a short
  request never waits for a long one;
- admission control reuses the serving vocabulary: bounded queue →
  `RejectedError`, absolute deadlines → `DeadlineExceededError`.

Supervision (ISSUE 6, chunk-granular under ISSUE 7): every jitted
dispatch runs through an `EngineSupervisor` — failures arrive as typed
`DispatchFailedError`s, a hung dispatch trips the watchdog
(`DispatchHungError`). The failure protocol keeps faults request-scoped
at CHUNK granularity: a failing step retries whole, then blame-probes
each active row in isolation (prefilling rows probe as "prefill" kind at
their current chunk offset, decoding rows as "decode") and quarantines
the implicated requests — a request poisoned in chunk k>0 is evicted
without touching co-scheduled decode rows, whose streams stay
bit-identical to a fault-free run because probe results are never
committed. Non-attributable failures fail the active rows and count
toward the engine circuit breaker, which opens after `breaker_threshold`
consecutive engine-level failures (admissions reject with reason
"circuit_open", /healthz flips to 503, the server drains).

Overload control (ISSUE 6): requests carry an SLO class —
`interactive` > `batch` > `best_effort` — admitted in strict priority
order from per-class queues. A full queue or an exceeded token budget
(`max_inflight_tokens`, estimated cost = prompt_len + max_new_tokens over
queued + active) sheds the NEWEST queued request of the lowest class
below the submitter (reason "shed") before rejecting; sustained queue
pressure enters brownout, capping newly-admitted `max_new_tokens` so the
backlog drains at interactive-friendly latency.

Speculative decoding (ISSUE 17): a `draft_model` (same vocab, own
`SlotPagedKVPool` + page-congruent "draft" `PrefixCache`) turns each
decode pump into draft-propose + single-dispatch verify. A chunk-wide
draft catch-up replays committed tokens the draft hasn't seen, a jitted
width-1 `lax.scan` proposes `spec_k` tokens per eligible slot (and
pre-writes the draft KV for the all-accept case), and the target scores
all `spec_k + 1` positions in the ONE existing unified dispatch
(`[last_tok, d1..dK]`, adv = K+1). Greedy acceptance takes the longest
draft prefix matching the target's per-position argmax plus the
target's corrective token — bit-identical to plain decode by
construction. Commit is `set_length(L + accepted + 1)`; rejected
columns need no KV scrub (garbage past the committed length IS the
rollback invariant) and the draft pool rewinds via `rewind_length`.
Draft dispatches are supervision-EXEMPT: a failed one triggers
draft-scoped solo probes, a blamed request loses only its draft
(spec_off, stream continues plain), unattributable failures walk a
failstreak to engine-wide `_spec_disabled` — the target breaker is
never charged.

Per-slot sampling + grammar-constrained decoding (ISSUE 18): every
request carries `SamplingParams` (temperature / top-k / top-p / seed /
JSON-schema grammar) that ride the ONE unified step as batched per-slot
ARRAYS — the engine still compiles exactly one step program for its
lifetime, whatever mix of greedy, sampled and constrained rows it
carries. A seeded request's token `i` is drawn on a per-request
threefry lane keyed by `(seed, i)` alone (`sampling.lane_key`), so
sampled streams are bit-identical across batch composition, engine
restart, and router failover re-prefill (the survivor resumes the lane
at `sample_offset = tokens already emitted`). Grammars compile to
token-level DFAs interned in a fixed-shape bank; the step applies the
per-slot state's legal-token mask on device and returns each row's
advanced DFA state. Speculative decoding composes by seeded replay:
the verify pass samples every window position on the same lanes, so
the longest-matching-prefix acceptance yields streams literally
identical to plain sampled decode (see sampling.py). Constrained slots
do not speculate.

Determinism: every decision is a pure function of `clock.now()` and the
queue/pool tables. Under a `SimClock` the engine runs threadless and a
test harness calls `pump()` directly — slot churn and decode-iteration
counts are provable facts, not timing accidents. Under the default
`MonotonicClock`, `start()` runs the same `pump()` from a scheduler
thread. Default decoding is greedy (argmax), bit-reproducible against
one-shot generate() for free; seeded sampling extends the same
guarantee to `(seed, params)`-keyed streams.
"""
from __future__ import annotations

import logging
import threading
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.generation import HOST_TIER, REREAD, REWIND
from ...nn.layer import moe
from ...obs.flight_recorder import flight_recorder
from ...obs.trace import RequestTrace, TimelineStore, new_request_id
from ...ops.attention import token_pack
from ...profiler import (SPAN_REQUEST_ADMIT, SPAN_REQUEST_FIRST_LAUNCH,
                         SPAN_REQUEST_FIRST_TOKEN, SPAN_REQUEST_SUBMIT,
                         SPAN_SERVE_ADMIT, SPAN_SERVE_BUILD_ROWS,
                         SPAN_SERVE_COMMIT, SPAN_SERVE_DISPATCH,
                         SPAN_SERVE_DRAFT, SPAN_SERVE_FETCH,
                         SPAN_SERVE_PUBLISH, SPAN_SERVE_PUMP,
                         SPAN_SETUP_ENGINE_INIT, SPAN_SETUP_FIRST_STEP,
                         RecordEvent, SetupSpan)
from ..clock import Clock, MonotonicClock, SimClock
from ..engine import DeadlineExceededError, RejectedError
from ..metrics import LLMMetrics, SLO_CLASSES
from ..supervisor import (DispatchFailedError, DispatchHungError,  # noqa: F401
                          EngineSupervisor)
from .host_kv import HostKVPool
from .kv_pool import (INDEXED, LATENT, PAGED, RECURRENT, WINDOW,
                      SlotPagedKVPool,
                      SlotsExhaustedError)
from .lora import AdapterBank, AdapterError
from .prefix_cache import PrefixCache
from .sampling import (GREEDY, SamplingParams, SlotSamplingTable,
                       compile_grammar, select_next, select_tokens)

_log = logging.getLogger("paddle_tpu.serving.llm")

# The unified step's inner function name. XLA names the executable
# `jit_<this>`, and that name is how the benchmark finds the step in a
# profiler trace (benchmark/jobs/serve_closed_loop.py: "main_module":
# "jit_step"; `step_gap_ms_p50`, `unified_step_ms_p50` and PERF_LEDGER's
# `breakdown` are keyed on it). Pinned by tests/test_trace_spans.py: do not
# rename.
UNIFIED_STEP_NAME = "step"

# The fewest packed positions a step computes (`LLMEngine.step_tokens`). A
# bf16 matmul on a TPU v5e is bound by reading its weights while it has
# fewer than 197e12 FLOP/s / 819e9 B/s = 240 rows, and costs the same
# whatever it has below that; 512 rows cost about twice that floor, and
# beside 128 decode rows they leave the prefill 24 whole 16-token chunks a
# step. An engine whose `num_slots * prefill_chunk` is no more than this
# computes every column, as it always did.
MIN_STEP_TOKENS = 512


class WeightSwapError(ValueError):
    """`replace_params` refused a hot swap: the engine still holds work,
    or the new tree's abstract signature (structure / leaf shapes /
    dtypes) differs from the serving params — a mismatched signature
    would recompile the unified step mid-fleet, which is exactly what a
    rolling deploy must never do."""


@dataclass
class LLMEngineConfig:
    num_slots: int = 4             # decode width == KV pool size
    block_len: int = 16            # tokens per accounting block
    n_blocks: int = 8              # blocks per slot (capacity = 128 tokens)
    max_queue_depth: int = 64      # pending-request cap (admission control)
    max_new_tokens: int = 32       # default per-request generation cap
    eos_token_id: Optional[int] = None   # per-request override wins
    default_deadline_ms: Optional[float] = None
    prefill_chunk: int = 16        # prompt tokens prefilled per step; also
    #                                the unified step's fixed row width, so
    #                                it bounds how long a long prompt can
    #                                stall its neighbors (TTFT knob)
    drain_timeout_s: float = 60.0
    cache_dtype: Optional[object] = None  # pool slab dtype override
    # ---- overload control (ISSUE 6) ----
    default_slo: str = "batch"     # SLO class when submit() names none
    max_inflight_tokens: Optional[int] = None  # token-budget admission:
    #                                  sum of (prompt + max_new_tokens) over
    #                                  queued + active requests (None: off)
    brownout_queue_depth: Optional[int] = None  # queued requests at/above
    #                                  this enter brownout (None: off);
    #                                  exits at half the threshold
    brownout_max_new_tokens: int = 8  # admission-time cap while browned out
    retry_after_s: float = 1.0     # backpressure hint on overload rejects
    # ---- prefix cache + multi-tenancy (ISSUE 8) ----
    enable_prefix_cache: bool = True   # radix KV prefix sharing on admission
    default_tenant: str = "default"    # tenant when submit() names none
    tenant_max_inflight_tokens: Optional[int] = None  # per-tenant quota:
    #                                  sum of (prompt + max_new_tokens) over
    #                                  one tenant's queued + active requests
    #                                  (None: off); exceeding it is a typed
    #                                  "tenant_quota" reject — shedding other
    #                                  tenants can never help, so it is
    #                                  checked before shed logic runs
    # ---- supervision (ISSUE 6) ----
    dispatch_timeout_s: Optional[float] = None  # hung-dispatch watchdog
    dispatch_retries: int = 2      # whole-step retries before blame/fail
    breaker_threshold: int = 3     # consecutive engine-level failures that
    #                                open the circuit breaker
    # ---- serving economics (ISSUE 11) ----
    economics: bool = False        # arm the ServingLedger + SLOBurnMonitor;
    #                                off = one predicate per hook, no clock
    #                                reads, no extra device syncs
    slo_burn_budget: float = 0.05       # error budget (bad-outcome fraction)
    slo_burn_threshold: float = 14.4    # page when burn >= this multiple
    slo_burn_fast_window_s: float = 60.0
    slo_burn_slow_window_s: float = 300.0
    slo_burn_min_events: int = 10       # cold-start floor per window
    slo_burn_capture_s: float = 0.0     # >0: bounded profiler capture on fire
    slo_ttft_target_ms: Optional[Dict[str, float]] = None  # per-class TTFT
    #                                targets feeding the burn monitor; a
    #                                class absent from the dict counts every
    #                                prefill as a good outcome
    # ---- compile observatory (ISSUE 12) ----
    observatory: bool = False      # register every unified-step executable
    #                                (signature fingerprint + AOT cost/memory
    #                                analyses) with the process-global
    #                                CompileObservatory; off = one predicate
    # ---- rolling weight deployment (ISSUE 16) ----
    weight_version: str = "v0"     # version id of the params the engine
    #                                starts on; replace_params() advances it
    # ---- speculative decoding (ISSUE 17) ----
    spec_k: int = 4                # draft tokens proposed per verify window
    #                                (only meaningful when the engine is
    #                                built with a draft_model); the verify
    #                                window spans spec_k + 1 of the unified
    #                                step's prefill_chunk columns, so
    #                                spec_k + 1 <= prefill_chunk is enforced
    #                                at engine construction when a draft
    #                                model is present
    # ---- per-slot sampling + constrained decoding (ISSUE 18) ----
    max_grammars: int = 8          # distinct compiled grammars the fixed-
    #                                shape DFA bank holds; the bank's shape
    #                                is part of the unified step's traced
    #                                signature, so it is pre-allocated — a
    #                                request needing a 9th grammar rejects
    #                                instead of recompiling the step
    # ---- tiered KV cache (ISSUE 19) ----
    host_kv_bytes: int = 0         # host-RAM spill tier byte budget: > 0
    #                                arms a bounded LRU HostKVPool that
    #                                captures refcount-0 prefix pages on
    #                                pressure eviction and re-onboards them
    #                                at admission instead of re-prefilling;
    #                                0 = device-only caching (prior behavior)
    # ---- multi-LoRA serving (ISSUE 20) ----
    max_adapters: int = 0          # > 0 arms the AdapterBank: that many
    #                                hot-swappable LoRA adapter rows ride
    #                                the ONE unified step through a
    #                                per-slot adapter_idx lane (bank row 0
    #                                is the all-zero base pass-through, so
    #                                adapter=None streams stay
    #                                bit-identical); 0 = no bank and the
    #                                step's operands/executable are
    #                                byte-identical to the pre-LoRA engine
    lora_rank: int = 8             # bank row rank — part of the step's
    #                                traced operand shapes, so fixed at
    #                                construction; loading an adapter of
    #                                any other rank is a typed refusal,
    #                                never a recompile
    lora_alpha: Optional[float] = None  # default scaling numerator for
    #                                rows loaded without an explicit
    #                                alpha (None = 2 * lora_rank)

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.default_slo not in SLO_CLASSES:
            raise ValueError(
                f"default_slo must be one of {SLO_CLASSES}, got "
                f"{self.default_slo!r}")
        if self.brownout_max_new_tokens < 1:
            raise ValueError(
                f"brownout_max_new_tokens must be >= 1, got "
                f"{self.brownout_max_new_tokens}")
        if self.dispatch_retries < 0:
            raise ValueError("retry counts must be >= 0")
        if not self.default_tenant:
            raise ValueError("default_tenant must be a non-empty string")
        if (self.tenant_max_inflight_tokens is not None
                and self.tenant_max_inflight_tokens < 1):
            raise ValueError(
                f"tenant_max_inflight_tokens must be >= 1, got "
                f"{self.tenant_max_inflight_tokens}")
        if self.breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got "
                f"{self.breaker_threshold}")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.max_grammars < 1:
            raise ValueError(
                f"max_grammars must be >= 1, got {self.max_grammars}")
        if self.host_kv_bytes < 0:
            raise ValueError(
                f"host_kv_bytes must be >= 0, got {self.host_kv_bytes}")
        if self.max_adapters < 0:
            raise ValueError(
                f"max_adapters must be >= 0, got {self.max_adapters}")
        if self.lora_rank < 1:
            raise ValueError(
                f"lora_rank must be >= 1, got {self.lora_rank}")
        if self.lora_alpha is not None and self.lora_alpha <= 0:
            raise ValueError(
                f"lora_alpha must be > 0, got {self.lora_alpha}")
        if not 0.0 < self.slo_burn_budget <= 1.0:
            raise ValueError(
                f"slo_burn_budget must be in (0, 1], got "
                f"{self.slo_burn_budget}")
        if self.slo_burn_threshold <= 0:
            raise ValueError(
                f"slo_burn_threshold must be > 0, got "
                f"{self.slo_burn_threshold}")
        if not (0.0 < self.slo_burn_fast_window_s
                <= self.slo_burn_slow_window_s):
            raise ValueError(
                "slo_burn windows must satisfy 0 < fast <= slow, got "
                f"fast={self.slo_burn_fast_window_s} "
                f"slow={self.slo_burn_slow_window_s}")
        if self.slo_burn_min_events < 1:
            raise ValueError(
                f"slo_burn_min_events must be >= 1, got "
                f"{self.slo_burn_min_events}")
        if self.slo_ttft_target_ms is not None:
            for cls, target in self.slo_ttft_target_ms.items():
                if cls not in SLO_CLASSES:
                    raise ValueError(
                        f"slo_ttft_target_ms keys must be SLO classes "
                        f"{SLO_CLASSES}, got {cls!r}")
                if target <= 0:
                    raise ValueError(
                        f"slo_ttft_target_ms[{cls!r}] must be > 0, got "
                        f"{target}")


class GenerationHandle:
    """Per-request streaming view + completion future.

    Tokens stream into `tokens_so_far()` as decode iterations retire them;
    `future` resolves with the full np.int32 array on EOS/max-tokens, or
    with DeadlineExceededError / RejectedError / DispatchFailedError on
    eviction (partial tokens stay readable off the handle either way)."""

    def __init__(self, prompt_len: int, max_new_tokens: int,
                 slo: str = "batch"):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.slo = slo
        self.future: Future = Future()
        self.ttft_ms: Optional[float] = None
        # with the first token: where `ttft_ms` went ({"queued_ms",
        # "bound_ms", "prefill_ms", "first_fetch_ms"}: they add up to it)
        # and the unified steps committed since a slot was bound
        self.ttft_phases_ms: Optional[Dict[str, float]] = None
        self.steps_to_first_token: Optional[int] = None
        self.rid: Optional[str] = None       # request id (always assigned)
        self.trace: Optional[RequestTrace] = None   # when tracing opted in
        self._lock = threading.Lock()
        self._tokens: List[int] = []
        self._logprobs: List[Optional[float]] = []

    def _append(self, tok: int, lp: Optional[float] = None):
        with self._lock:
            self._tokens.append(int(tok))
            self._logprobs.append(None if lp is None else float(lp))

    def tokens_so_far(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    def logprobs_so_far(self) -> List[Optional[float]]:
        """Per-emitted-token log-probabilities (ISSUE 19): the model's raw
        (pre-temperature) log-softmax at each selected token, streamed in
        lockstep with `tokens_so_far()`. Entries are None when the request
        did not opt in via submit(logprobs=True)."""
        with self._lock:
            return list(self._logprobs)

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        return self.future.result(timeout)

    def timeline(self) -> Optional[dict]:
        """Structured timeline dict when the request was traced (complete
        once the future has resolved), else None."""
        return self.trace.to_dict() if self.trace is not None else None


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "eos_token_id", "arrival",
                 "deadline", "handle", "slot", "emitted", "last_tok",
                 "slo", "submit_idx", "cost", "chunk_off", "tenant",
                 "attached_pages", "rid", "trace", "draft_slot",
                 "spec_off", "draft_attached", "sampling",
                 "sample_offset", "gid", "dfa_state0",
                 "want_logprobs", "kv_row", "adapter", "admitted",
                 "first_launch", "final_launch", "first_token",
                 "admit_step", "chunks")

    def __init__(self, prompt, max_new_tokens, eos_token_id, arrival,
                 deadline, slo, submit_idx, tenant="default"):
        self.prompt = prompt              # np.int32 [S]
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.arrival = arrival            # clock seconds
        # the stamps of its way to the first token, every request's, each
        # set once on the engine's clock; with `arrival` they cut
        # `handle.ttft_ms` into queued + bound + prefill + first_fetch
        # (`_ttft_phases_ms`)
        self.admitted: Optional[float] = None      # `_admit` bound a slot
        self.first_launch: Optional[float] = None  # rows of a launched
        #                                   step first held a chunk of it
        self.final_launch: Optional[float] = None  # ... the chunk that
        #                                   reaches the prompt's end
        self.first_token: Optional[float] = None   # that step committed
        self.admit_step: int = 0          # `unified_steps` at `admitted`
        self.chunks: int = 0              # prefill chunks committed
        self.deadline = deadline          # absolute clock seconds or None
        self.slo = slo                    # SLO class name
        self.submit_idx = submit_idx      # lifetime admission index (fault
        #                                   injection keys poison on this)
        self.cost = len(prompt) + max_new_tokens  # token-budget estimate
        self.handle = GenerationHandle(len(prompt), max_new_tokens, slo)
        self.slot: Optional[int] = None
        self.emitted: List[int] = []
        self.last_tok: int = 0
        self.chunk_off: int = 0           # prompt tokens already prefilled;
        #                                   < len(prompt) means the request
        #                                   is still in chunked prefill —
        #                                   starts at attach_len on a prefix
        #                                   cache hit (those tokens' KV is
        #                                   attached/COW'd, never recomputed)
        self.tenant = tenant
        self.attached_pages: List[int] = []   # shared pages this request
        #                                       reads (refcounted in pool)
        self.rid: Optional[str] = None        # request id (always assigned)
        self.trace: Optional[RequestTrace] = None   # None unless the
        #                                       request opted into tracing —
        #                                       every hot-path hook guards on
        #                                       this ONE predicate
        # speculative decoding (ISSUE 17)
        self.draft_slot: Optional[int] = None  # row in the DRAFT pool; None
        #                                       when spec is off or the draft
        #                                       pool had no row to give
        self.spec_off: bool = False           # draft quarantined for THIS
        #                                       request (poisoned draft
        #                                       dispatch): stream continues
        #                                       as plain decode
        self.draft_attached: List[int] = []   # shared draft-pool pages this
        #                                       request attached (for the
        #                                       draft cache insert)
        # per-slot sampling + constrained decoding (ISSUE 18)
        self.sampling: Optional[SamplingParams] = None  # None == GREEDY
        self.sample_offset: int = 0           # stream index of this
        #                                       request's FIRST emitted
        #                                       token — 0 normally, the
        #                                       already-emitted count on a
        #                                       failover re-prefill (the
        #                                       RNG-lane counter restore)
        self.gid: int = 0                     # interned grammar id in the
        #                                       engine's DFA bank; 0 = the
        #                                       pass-through row
        self.dfa_state0: int = 0              # DFA state at first emission
        #                                       (walked over the resumed
        #                                       prompt tail on failover)
        # tiered KV + disaggregation (ISSUE 19)
        self.want_logprobs: bool = False      # surface per-token logprobs
        #                                       on the handle
        self.kv_row: Optional[dict] = None    # pre-computed KV for the
        #                                       prompt's first `length`
        #                                       tokens (a prefill→decode
        #                                       handoff import); admission
        #                                       uploads it instead of
        #                                       re-prefilling
        # multi-LoRA serving (ISSUE 20)
        self.adapter: Optional[str] = None    # AdapterBank id whose
        #                                       low-rank delta this stream
        #                                       decodes under; None = base
        #                                       model (bank row 0)


@dataclass
class _StepInFlight:
    """One unified step between `_launch` and `_retire`: the device results
    nobody has fetched yet (`nxt`, `lps`, `new_dstate`), the host rows it
    was built from, and each row's request OBJECT (`reqs`: slot ->
    request). A slot alone does not name a row's owner: while the step is
    in flight `_retire` of its predecessor may free the slot and `_admit`
    bind another request to it."""
    nxt: jax.Array                  # [N, C] selected tokens, on the device
    lps: jax.Array                  # [N, C] their log-probabilities
    new_dstate: jax.Array           # [N] advanced grammar-DFA states
    pos: np.ndarray                 # [N] the rows' write offsets
    adv: np.ndarray                 # [N] live columns a row
    prefill_slots: List[int]
    decode_slots: List[int]
    reqs: Dict[int, _GenRequest]
    spec_drafts: Dict[int, List[int]]
    sampled_rows: int
    t0: float                       # clock at the start of `dispatch`
    tc0: Optional[float]            # start of the device span, if booked


class LLMEngine:
    """submit() a prompt, get a GenerationHandle streaming greedy tokens.

    The model must implement the cached-decode contract
    (`init_cache` / `forward_with_cache`, e.g. GPTForCausalLM /
    LlamaForCausalLM); it is switched to eval mode and its functional
    state captured once at construction.

    `fault_plan` (None → the PDTPU_FAULTS-driven global plan) injects
    deterministic dispatch faults for the fault-matrix tests; `on_break`
    fires once when the circuit breaker opens (the server wires it to a
    drain on its own thread).
    """

    def __init__(self, model, config: Optional[LLMEngineConfig] = None,
                 clock: Optional[Clock] = None,
                 metrics: Optional[LLMMetrics] = None,
                 fault_plan=None,
                 on_break: Optional[Callable[[], None]] = None,
                 draft_model=None):
        with SetupSpan(SPAN_SETUP_ENGINE_INIT):
            self._init(model, config, clock, metrics, fault_plan, on_break,
                       draft_model)

    def _init(self, model, config, clock, metrics, fault_plan, on_break,
              draft_model):
        from ...models.generation import make_decoder_fns, make_verify_fn
        self.model = model
        model.eval()
        self.config = config or LLMEngineConfig()
        self.clock = clock or MonotonicClock()
        self.metrics = metrics or LLMMetrics()
        self.params, self._prefill_fn, self._decode_fn = \
            make_decoder_fns(model)
        # per-slot sampling + grammar bank (ISSUE 18): sized off the
        # model's vocab — the DFA bank's last axis is a legal-token mask
        vocab_size = int(getattr(getattr(model, "config", None),
                                 "vocab_size", 0))
        if vocab_size < 1:
            raise ValueError(
                "model must expose config.vocab_size for the sampling "
                "subsystem's grammar mask")
        self.sampling_table = SlotSamplingTable(
            self.config.num_slots, vocab_size,
            max_grammars=self.config.max_grammars)
        # multi-LoRA bank (ISSUE 20): K stacked adapter trees + a per-slot
        # adapter_idx lane appended to the unified step's operands. None
        # unless armed, so an unarmed engine's step signature — and its
        # compiled executable — stays byte-identical to the pre-LoRA one.
        self.adapter_bank: Optional[AdapterBank] = None
        if self.config.max_adapters > 0:
            self.adapter_bank = AdapterBank(
                model, self.config.max_adapters, self.config.lora_rank,
                self.config.num_slots,
                default_alpha=self.config.lora_alpha)
        if not self.config.weight_version:
            raise ValueError("weight_version must be a non-empty string")
        self.weight_version = self.config.weight_version
        # pad_tokens=prefill_chunk: the fixed-width KV stripe written at a
        # row's position needs chunk-width scratch past the last
        # addressable block so near-capacity writes never clamp back onto
        # valid KV (block tables never point into the pad region)
        self.pool = SlotPagedKVPool(
            model.init_cache, self.config.num_slots, self.config.block_len,
            self.config.n_blocks, dtype=self.config.cache_dtype,
            pad_tokens=self.config.prefill_chunk)
        # what the layers keep per slot decides what can be asked of the
        # engine (`models.generation.CACHE_KINDS`, through the pool): the
        # host tier and a draft model are refused in the kind's own words;
        # prefix sharing is switched off (`enable_prefix_cache` is the
        # effective setting); `kv_row` imports and stream exports are
        # refused where they are asked for
        self.enable_prefix_cache = self.config.enable_prefix_cache
        if self.config.host_kv_bytes > 0:
            self._refuse(self.pool, HOST_TIER,
                         "host_kv_bytes > 0 with a model")
        if draft_model is not None:
            self._refuse(self.pool, REWIND, "draft_model with a target")
        no_sharing = self.pool.refusal(REREAD, "a shared prefix in a model")
        if self.enable_prefix_cache and no_sharing is not None:
            _log.warning("enable_prefix_cache is switched off: %s",
                         no_sharing)
            self.enable_prefix_cache = False
        # the pool's bytes by what they are: per-slot state that is no
        # page, and the slabs by kind where the pool holds a second kind
        # of page beside (or in place of) full-length K/V
        state_bytes = self.pool.recurrent_state_bytes
        if state_bytes:
            self.metrics.set_recurrent_state(state_bytes)
        # live columns from which the recurrent layers' kernel advances a
        # row in matrix form (`Mamba2Mixer.matrix_columns`); a model whose
        # layers walk every row a column at a time names none
        self._matrix_columns = next(
            (m.matrix_columns for m in model.sublayers()
             if getattr(m, "matrix_columns", None)), None)
        by_kind = self.pool.kv_bytes()
        if any(n for label, n in by_kind.items() if label != "full"):
            self.metrics.set_kv_pool_bytes(by_kind)
        # learned sparse attention: what the step's sparse layers select
        # from is the model's to say (top-k, layers with an indexer and
        # layers that share one's selection)
        self._sparse = None
        if INDEXED in self.pool.layer_kinds:
            kinds = list(model.config.indexer_types)
            self._sparse = (int(model.config.index_topk),
                            kinds.count("full"), kinds.count("shared"))
        # host-RAM spill tier (ISSUE 19): a byte-budgeted LRU the prefix
        # cache spills refcount-0 pages into on pressure eviction; the
        # admission path re-onboards covered blocks instead of
        # re-prefilling them
        self.host_kv: Optional[HostKVPool] = (
            HostKVPool(self.config.host_kv_bytes, self.config.block_len)
            if self.config.host_kv_bytes > 0 else None)
        self._spill_booked = 0.0     # spill_seconds already booked to the
        #                              ledger's kv_spill phase (delta
        #                              accounting per pump)
        # radix prefix cache (ISSUE 8): wires itself as the pool's
        # on_pressure hook so pinned rows free up under allocation pressure
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.pool, host_pool=self.host_kv,
                        clock=self.clock.now)
            if self.enable_prefix_cache
            else None)
        # ---- speculative decoding (ISSUE 17) ----
        # a draft model arms spec mode: per decode pump a SINGLE draft
        # dispatch (an on-device lax.scan of spec_k+1 width-1 steps over
        # the draft's OWN slot-paged pool) proposes K tokens per eligible
        # row, and the target's unified step verifies all K+1 positions in
        # one dispatch; greedy acceptance = longest matching prefix + the
        # target's corrective token, so output is bit-identical to plain
        # decode. Rejected target columns need no rollback (committing
        # only the accepted length leaves them as the garbage-past-adv the
        # pool invariant already covers); the DRAFT pool rolls back via
        # rewind_length.
        self.draft_model = draft_model
        self.draft_pool: Optional[SlotPagedKVPool] = None
        self.draft_prefix_cache: Optional[PrefixCache] = None
        self._draft_params = None
        self._draft_verify_fn = None
        self._draft_prefill_fn = None
        self._draft_step_jit = None     # chunk-wide draft catch-up
        self._draft_propose_jit = None  # the single-dispatch K-token scan
        self._spec_disabled = False     # engine-wide draft kill switch
        self._draft_failstreak = 0      # consecutive unattributed draft
        #                                 dispatch failures (exempt from the
        #                                 engine breaker by design)
        self.spec_windows = 0           # lifetime verify windows committed
        self.spec_drafted = 0           # lifetime draft tokens verified
        self.spec_accepted = 0          # lifetime draft tokens accepted
        # tiered KV + disaggregation (ISSUE 19): lifetime counters the
        # tests read directly
        self.host_onboard_tokens = 0    # prompt tokens onboarded from the
        #                                 host spill tier (skipped prefill)
        self.kv_import_tokens = 0       # prompt tokens imported via a
        #                                 prefill→decode handoff kv_row
        if draft_model is not None:
            if self.config.spec_k + 1 > self.config.prefill_chunk:
                raise ValueError(
                    f"spec_k + 1 ({self.config.spec_k + 1}) exceeds the "
                    f"unified step width prefill_chunk "
                    f"({self.config.prefill_chunk}): the verify window "
                    "must fit one dispatch")
            draft_model.eval()
            self._draft_params, self._draft_verify_fn = \
                make_verify_fn(draft_model)
            # the propose scan samples its proposals on the SAME per-
            # request lanes as the target verify (seeded-replay
            # acceptance), so it needs raw draft logits, not argmaxes
            _, self._draft_prefill_fn, _ = make_decoder_fns(draft_model)
            self.draft_pool = SlotPagedKVPool(
                draft_model.init_cache, self.config.num_slots,
                self.config.block_len, self.config.n_blocks,
                dtype=self.config.cache_dtype,
                pad_tokens=self.config.prefill_chunk)
            # the draft pool rewinds to the verified stream after every
            # window
            self._refuse(self.draft_pool, REWIND, "draft_model with a draft")
            if self.enable_prefix_cache:
                self.draft_prefix_cache = PrefixCache(self.draft_pool,
                                                      name="draft")
        self.metrics.set_slots(0, self.pool.num_slots)
        self._queues: Dict[str, deque] = {c: deque() for c in SLO_CLASSES}
        self._active: Dict[int, _GenRequest] = {}   # slot -> request
        self._cond = threading.Condition()
        self._draining = False
        self._stopped = False
        self._brownout = False
        self._thread: Optional[threading.Thread] = None
        self._step_jit = None        # the ONE unified step executable
        self._dispatch_step = self._first_dispatch
        self._first_step_span: Optional[SetupSpan] = None
        # the step that is launched and not yet retired (`_StepInFlight`),
        # or None: owned by whoever runs `pump()`. `_no_sel` stands in for
        # a predecessor's selections on a step that feeds nothing back
        self._inflight: Optional[_StepInFlight] = None
        self._no_sel = None
        self._retired_at = float("-inf")   # clock at the last `_retire`
        # packed positions the step computes: every decode row with its
        # draft window always fits beside one whole prefill chunk, and the
        # step is never narrower than MIN_STEP_TOKENS nor wider than the
        # `[slots, chunk]` block, at which width nothing is packed
        window = 1 + (self.config.spec_k if draft_model is not None else 0)
        self.step_tokens = min(
            self.config.num_slots * self.config.prefill_chunk,
            max(self.config.num_slots * window + self.config.prefill_chunk,
                MIN_STEP_TOKENS))
        # columns of a row the host reads (its emission column, or a draft
        # window's), and the positions the step's tail computes for them:
        # slots x window, or the whole block where that is no fewer
        self._window = window
        self._head_positions = min(self.config.num_slots * window,
                                   self.step_tokens)
        # query positions a step's attention computes, summed over the
        # layers that attend: a latent layer that attends to every key
        # keeps its queries on the packed block, every other kind unpacks
        # them to `[slots, chunk]`
        self._attn_positions = sum(
            self.step_tokens if kind == LATENT
            else self.config.num_slots * self.config.prefill_chunk
            for kind in self.pool.layer_kinds if kind != RECURRENT)
        # query-head rows a step's full and windowed walks compute: those
        # positions x the layer's own query heads, over the layers of each
        # kind (0: the model has no layer of that kind)
        heads = model.query_heads_by_layer()
        self._attn_heads = tuple(
            self.config.num_slots * self.config.prefill_chunk * sum(
                h for h, k in zip(heads, self.pool.layer_kinds) if k == kind)
            for kind in (PAGED, WINDOW))
        # sparse experts: the model's dropless expert layers, found by
        # their type (nothing here knows which model holds them). Their
        # per-layer per-expert totals of live assignments `[L, E]` live on
        # the device and ride the step as one more operand and result; a
        # dense model has neither and its step is the step it always was
        experts = [layer for layer in model.sublayers()
                   if isinstance(layer, moe.DroplessMoE)]
        self._moe_totals = None
        if experts:
            if len({m.num_held for m in experts}) != 1:
                raise ValueError("expert layers of different sizes cannot "
                                 "share one [layers, experts] table")
            self._moe_totals = jnp.zeros(
                (len(experts), experts[0].num_held), jnp.int32)
            # live positions the committed steps routed (each through
            # every expert layer), and how many of them the last fetch
            # had published
            self._moe_routed = self._moe_routed_published = 0
            self._moe_published = np.zeros(self._moe_totals.shape, np.int64)
            self._moe_publish_lock = threading.Lock()
            self.metrics.moe_source = self.moe_expert_tokens
        self.decode_iterations = 0   # lifetime steps carrying >=1 decode row
        self.prefill_dispatches = 0  # lifetime steps carrying ONLY prefill
        #                              rows — near-zero under mixed load,
        #                              which is what proves the per-bucket
        #                              prefill executable zoo is gone
        self.prefill_tokens = 0      # lifetime prompt tokens actually
        #                              prefilled (sum of committed chunk
        #                              widths) — the prefix-cache acceptance
        #                              observable: N shared-prefix requests
        #                              should pay ~1 prompt's worth
        self._submit_idx = 0         # lifetime admissions (poison keying)
        self._dispatch_idx = 0       # lifetime dispatch attempts (fault
        #                              clauses key on this index)
        # finished request timelines for /debug/requests/<rid> (ISSUE 9)
        self.timelines = TimelineStore()
        # serving economics (ISSUE 11): both None unless armed, so every
        # hot-path hook costs exactly one predicate when disabled
        self.ledger = None
        self.burn = None
        if self.config.economics:
            from ...obs.serving_ledger import ServingLedger, SLOBurnMonitor
            self.ledger = ServingLedger(clock=self.clock.now)
            self.burn = SLOBurnMonitor(
                clock=self.clock.now,
                budget=self.config.slo_burn_budget,
                threshold=self.config.slo_burn_threshold,
                fast_window_s=self.config.slo_burn_fast_window_s,
                slow_window_s=self.config.slo_burn_slow_window_s,
                min_events=self.config.slo_burn_min_events,
                capture_s=self.config.slo_burn_capture_s)
        self.metrics.ledger = self.ledger
        self.metrics.burn = self.burn
        # compile observatory (ISSUE 12): None unless armed
        self.observatory = None
        if self.config.observatory:
            from ...obs.compile_observatory import compile_observatory
            self.observatory = compile_observatory().enable()
        if fault_plan is None:
            from ...utils.fault_injection import global_plan
            fault_plan = global_plan()
        self._fault_plan = fault_plan
        self.on_break = on_break
        self.supervisor = EngineSupervisor(
            dispatch_timeout_s=self.config.dispatch_timeout_s,
            breaker_threshold=self.config.breaker_threshold,
            on_trip=self._on_breaker_trip, name="llm")

    # ---- observability (ISSUE 9) ----
    def _conclude(self, req: _GenRequest, outcome: str,
                  now: Optional[float] = None):
        """Finalize a traced request's timeline on ANY terminal path
        (complete / evict / quarantine / shed / shutdown): close the
        phase spans, store the timeline for /debug/requests/<rid>, and
        emit the request's spans onto the chrome trace. One predicate
        when the request was not traced."""
        if req.trace is None:
            return
        tr = req.trace
        tr.finish(self.clock.now() if now is None else now, outcome)
        self.timelines.put(tr.rid, tr.to_dict())
        tr.emit_chrome()

    def _record_reject(self, reason: str, rid: Optional[str] = None,
                       tenant: Optional[str] = None):
        flight_recorder().record("reject", engine="llm", reason=reason,
                                 rid=rid, tenant=tenant)

    # ---- the one jitted executable ----
    def _step(self):
        """Unified mixed-row step: `toks [N, C]` carries each slot's chunk
        (prompt tokens for prefilling rows, [last_tok, d1..dk, 0...] for
        decoding rows — k > 0 when a draft window rides the row, ISSUE
        17 — zeros for free slots), `pos [N]` the row's committed length
        (= write offset), `adv [N]` how many of the C columns are real
        (chunk size / 1+k / 0). KV stripes are written at `pos` (garbage
        columns past `adv` land in cols the row's validity never reaches
        or in the slab's pad region, and are overwritten before any
        seq_len admits them — which is also what makes rejected draft
        positions rollback-free: only the accepted length is ever
        committed); ragged paged attention masks every row to
        `col <= pos+t` and `col < pos+adv`. The step returns the
        PER-POSITION selected tokens `[N, C]` plus each row's advanced
        grammar-DFA state `[N]` (ISSUE 18): selection is the vectorized
        per-row `_select_token` path — masked argmax for greedy rows
        (bit-identical to the old make_verify_fn step on unconstrained
        rows), seeded temperature/top-k/top-p draws on per-request
        `(seed, stream_index)` threefry lanes for sampling rows, with
        the grammar bank's legal-token mask applied BEFORE the filters.
        Column `adv-1` is the classic next token for prefill /
        plain-decode rows; columns 0..k score a spec row's whole verify
        window in this one dispatch (free rows emit harmless selections
        of fully-masked rows). All sampling inputs are traced [N]
        arrays + the fixed-shape DFA bank, so the mix of request params
        never changes the executable.

        `slabs` holds, per layer, what that layer's kind keeps per slot
        (`pool.layer_kinds`): the paged K/V slabs, or a state-space
        layer's `(conv, ssm)` state, which the model advances by each
        row's `adv` live columns and starts from zero for a row at `pos`
        0; both ride the step as operand and result.

        Operands and results keep that `[N, C]` layout whatever happens
        inside. Where `step_tokens < N * C` the executable packs the live
        columns (`sum(adv) <= step_tokens`, the scheduler's budget) into
        `step_tokens` rows of width one (`ops.attention.token_pack`, from
        `adv` and `pos` alone) and runs the model's body on those,
        attention alone in the slots' layout: a decode row costs one
        position, not C.

        The step's tail (the vocabulary head, the grammar mask, the
        selection and the log-softmax) works for the columns somebody
        reads: a row's last `window` live columns, `window` the draft
        window's 1 + k columns where a draft model is armed and 1
        otherwise. Their hidden states are gathered behind the body
        (`emit`: `pack.dst[n, c]` packed, `n * C + c` unpacked, `c =
        max(adv - window, 0) + j`), the tail runs on `[N, window, V]` in
        slot order with the slots' own sampling operands, and `sel` / `lp`
        are laid out `[N, C]` again on the device, the tail's columns at
        their own places and zeros elsewhere. Where `N * window` is not
        smaller than the positions the step computes (an unpacked engine
        whose draft window is as wide as its chunk) nothing is gathered
        and the tail runs on the block."""
        if self._step_jit is None:
            view = self.pool.view
            prefill = self._prefill_fn
            chunk = self.config.prefill_chunk
            step_tokens = self.step_tokens
            slots, window = self.pool.num_slots, self._window
            # Python branches on static shapes: at the block's own width
            # the pack is not traced, and where the tail's rows are no
            # fewer than the block's positions neither is the gather. A
            # packed step always gathers (`step_tokens` holds every slot's
            # window and a chunk besides)
            packed = step_tokens < slots * chunk
            narrow = self._head_positions < step_tokens

            def step(params, toks, pos, adv, table, slabs, temp, topk,
                     topp, samp, seed, ctr, dstate, gid, bank, feed,
                     prev_sel, adapters=None, moe_totals=None):
                # `slabs` is donated: XLA aliases it to `new_slabs`, so the
                # K/V writes and the recurrent state land in place and no
                # step copies the pool. The caller's reference is a deleted
                # array once the executable is enqueued (`_launch`).
                # `adapters` (ISSUE 20) is the AdapterBank's stacked LoRA
                # operand — (per-layer A/B banks, per-slot adapter_idx,
                # per-row scale). An unarmed engine never passes it, so
                # its traced signature is unchanged; an armed engine
                # passes a fixed-structure pytree whose leaf VALUES churn
                # as adapters load/swap — zero recompiles either way.
                # token feedback: a row launched before its predecessor
                # step was fetched takes its input token from that step's
                # selections, still on the device (`feed` names the
                # column; -1 keeps the host's `toks[:, 0]`)
                fed = jnp.take_along_axis(
                    prev_sel, jnp.maximum(feed, 0)[:, None], axis=1)[:, 0]
                toks = toks.at[:, 0].set(
                    jnp.where(feed >= 0, fed.astype(toks.dtype), toks[:, 0]))
                paged = view(table, (pos + adv).astype(jnp.int32))
                pack = emit = None
                if packed:
                    # the live tokens as `step_tokens` rows of width one,
                    # each at its own position: the body reads them as it
                    # reads an unpacked step's rows
                    pack = token_pack(adv, pos, chunk, step_tokens)
                    toks, pos = pack.pack(toks), pack.pos
                    if adapters is not None:
                        banks, adapter_idx, scale = adapters
                        adapters = (banks, adapter_idx[pack.slot], scale)
                if narrow:
                    # a row's last `window` live columns, as flat positions
                    # of the body's block (a dead column names a position
                    # that holds finite values nobody reads)
                    first = jnp.maximum(adv - window, 0)
                    cols = first[:, None] + jnp.arange(window,
                                                       dtype=jnp.int32)
                    emit = (jnp.take_along_axis(pack.dst, cols, axis=1)
                            if packed else cols + chunk * jnp.arange(
                                slots, dtype=jnp.int32)[:, None]
                            ).reshape(-1)
                with moe.collect_expert_counts() as expert_counts:
                    logits, new_slabs = prefill(params, toks, slabs, pos,
                                                paged=paged,
                                                adapters=adapters,
                                                pack=pack, emit=emit)
                if narrow:
                    # tail column j is the row's column `first + j`: its
                    # stream index follows, and `window` columns at most
                    # are live
                    logits = logits.reshape(slots, window, -1)
                    adv, ctr = jnp.minimum(adv, window), ctr + first
                sel, new_state = select_tokens(
                    logits, adv, temp, topk, topp, samp, seed, ctr,
                    dstate, gid, bank)
                # per-token logprobs (ISSUE 19): the RAW model
                # distribution's log-softmax at each selected token —
                # pre-temperature/top-k/top-p, so it is a property of the
                # stream, not of the sampling filters. Computed
                # unconditionally (selection above is untouched, so token
                # streams stay bit-identical whether or not a request
                # reads them); float32 keeps the reduction stable under
                # low-precision cache dtypes.
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                    sel[..., None].astype(jnp.int32), axis=-1)[..., 0]
                if narrow:
                    # back to the `[N, C]` the host and the next step's
                    # `feed` read: column c holds tail column `c - first`
                    j = jnp.arange(chunk, dtype=jnp.int32) - first[:, None]
                    read = (j >= 0) & (j < adv[:, None])
                    j = jnp.clip(j, 0, window - 1)
                    sel, lp = (
                        jnp.where(read, jnp.take_along_axis(a, j, axis=1), 0)
                        for a in (sel, lp))
                if moe_totals is None:
                    return sel, lp, new_state, new_slabs
                # a sparse model (the only kind that is handed totals):
                # this step's live assignments per layer and expert
                return (sel, lp, new_state, new_slabs,
                        moe_totals + jnp.stack(expert_counts))

            step.__name__ = step.__qualname__ = UNIFIED_STEP_NAME
            self._step_jit = jax.jit(step, donate_argnames=("slabs",))
        return self._step_jit

    @staticmethod
    def _refuse(pool, feature: str, what: str):
        """Raise the pool's refusal of `what`, a use of `feature`, if some
        layer's kind refuses it, as the engine's `ValueError`."""
        err = pool.refusal(feature, what)
        if err is not None:
            raise ValueError(str(err))

    def _sampling_args_locked(self, ctr):
        """The unified step's per-slot sampling operands: the live table
        rows plus this dispatch's stream-index base `ctr [N]` and the
        cached device DFA bank. Table arrays ride the device-args cache
        (invalidated on bind/clear/DFA commit) so the steady-state cost
        here is one [N] ctr upload."""
        tab = self.sampling_table
        temp, topk, topp, samp, seed, dstate, gid = tab.device_args()
        return (temp, topk, topp, samp, seed, jnp.asarray(ctr),
                dstate, gid, tab.device_bank())

    def _feedback_args(self, feed=None, ahead_of=None):
        """The unified step's token-feedback operands `(feed [N],
        prev_sel [N, C])`: for a step launched ahead of `ahead_of`, that
        step's selections as they lie on the device and, per row, the
        column that is its next input; for every other step -1 in every
        row and a block of zeros nobody reads, so that both are one
        executable."""
        if ahead_of is not None:
            return jnp.asarray(feed), ahead_of.nxt
        if self._no_sel is None:
            shape = (self.pool.num_slots, self.config.prefill_chunk)
            self._no_sel = (jnp.full(shape[:1], -1, jnp.int32),
                            jnp.zeros(shape, jnp.int32))
        return self._no_sel

    def _adapter_args_locked(self):
        """The unified step's adapter operand as a (possibly empty) args
        tail (ISSUE 20): () when no bank is armed — the step is then
        called with its pre-LoRA 15-arg signature — else the bank's
        cached device views, rebuilt only after a row load/swap or a
        slot bind (same invalidation idiom as the sampling table)."""
        if self.adapter_bank is None:
            return ()
        return (self.adapter_bank.device_args(),)

    def _tail_args_locked(self):
        """The step's operands after the sampling ones: the adapter
        operand of an armed engine, then, for a sparse model, the running
        `[L, E]` totals (behind an `adapters` of None where no bank is
        armed: None is no operand). () for a dense, unarmed engine."""
        tail = self._adapter_args_locked()
        if self._moe_totals is not None:
            tail = (tail or (None,)) + (self._moe_totals,)
        return tail

    def _draft_step(self):
        """Draft-pool analogue of `_step` (ISSUE 17): the chunk-wide
        catch-up executable that replays already-committed target tokens
        (prompt suffixes and corrective tokens) into the draft pool so
        its KV tracks the true stream. Output tokens are discarded — only
        the written KV stripes matter."""
        if self._draft_step_jit is None:
            view = self.draft_pool.view
            vfy = self._draft_verify_fn

            def step(params, toks, pos, adv, table, slabs):
                paged = view(table, (pos + adv).astype(jnp.int32))
                return vfy(params, toks, slabs, pos, paged=paged)

            self._draft_step_jit = jax.jit(step)
        return self._draft_step_jit

    def _draft_propose(self):
        """The single-dispatch draft proposal (ISSUE 17): an on-device
        `lax.scan` of spec_k+1 sequential width-1 draft steps. Step 0
        feeds each proposing row's last committed token at `pos`; each
        later step feeds the previous step's argmax, so the scan emits
        d1..dK autoregressively — ONE dispatch, not K. The final (K+1th)
        iteration feeds dK purely for its KV write: after an all-accept
        window the draft pool is then already caught up to the target's
        new committed length, so steady-state spec pays exactly two
        dispatches (propose + verify) per K+1 emitted tokens — that
        dispatch-count collapse is the batch-1 latency win. Rows with
        act=0 park at the slab pad position (same convention as free rows
        in `_build_rows_locked`) and advance nothing.

        Sampled rows (ISSUE 18): scan step j selects its proposal with
        `select_next` on the SAME per-request lane the target verify
        will use for stream index `ctr + j` — when draft and target
        logits agree the proposal IS the target's coin-fixed draw, so
        seeded-replay acceptance keeps the spec speedup for sampled
        requests. Greedy rows still argmax. Grammar-constrained rows
        never reach this scan (spec-ineligible)."""
        if self._draft_propose_jit is None:
            view = self.draft_pool.view
            K = self.config.spec_k
            dprefill = self._draft_prefill_fn

            def propose(params, tok0, pos, act, table, slabs, temp,
                        topk, topp, samp, seed, ctr):
                def body(carry, j):
                    tok, off, slabs_c = carry
                    paged = view(table,
                                 (pos + off + act).astype(jnp.int32))
                    lg, slabs_c = dprefill(params, tok[:, None], slabs_c,
                                           pos + off, paged=paged)
                    nxt = select_next(lg[:, 0], temp, topk, topp, samp,
                                      seed, ctr + j)
                    return (nxt, off + act, slabs_c), nxt

                (_, _, slabs), drafts = jax.lax.scan(
                    body, (tok0, jnp.zeros_like(pos), slabs),
                    jnp.arange(K + 1, dtype=jnp.int32))
                # drafts [K+1, N]: rows 0..K-1 are d1..dK; row K is the
                # throwaway catch-up step (KV write only)
                return jnp.transpose(drafts[:K]), slabs

            self._draft_propose_jit = jax.jit(propose)
        return self._draft_propose_jit

    # ---- supervised dispatch ----
    def _run_dispatch(self, kinds, fn, args, exempt: bool = False):
        """One supervised jitted dispatch attempt. Every attempt — retries
        and blame probes included — consumes a dispatch index, which is
        what deterministic fault clauses key on. `kinds` is the ordered
        (kind, request_ids) pairs riding this dispatch — prefill rows
        announce first, then decode rows, both at the SAME index (a
        dispatch_raise clause fires once, at the first announcement;
        poison_request clauses match their kind; draft dispatches
        announce kind "draft", which is what lets a fault plan poison
        ONLY the draft). `exempt=True` marks a breaker-exempt dispatch
        (ISSUE 17: draft proposals are an optimization, so their failures
        must never charge the target engine's circuit breaker or
        dispatch-failure stats)."""
        idx = self._dispatch_idx
        self._dispatch_idx += 1
        plan = self._fault_plan
        label = "+".join(k for k, _ in kinds) or "step"

        def guarded():
            if plan is not None:
                for kind, rids in kinds:
                    plan.maybe_dispatch_fault(idx, kind=kind,
                                              request_ids=rids)
            return fn(*args)

        return self.supervisor.run(guarded, label=label, exempt=exempt)

    def _first_dispatch(self, kinds, fn, args):
        """The unified step's first call. Its trace, lower, compile or
        load and first run are the set-up ledger's `first_step` phase:
        the span begins here and `_retire` ends it once that step's
        result is on the host. Every later step goes to `_run_dispatch`
        directly."""
        span = SetupSpan(SPAN_SETUP_FIRST_STEP,
                         program=getattr(fn, "__name__", None)).__enter__()
        try:
            out = self._run_dispatch(kinds, fn, args)
        except BaseException:
            span.end()
            raise
        self._first_step_span = span
        self._dispatch_step = self._run_dispatch
        return out

    def _free_row_locked(self, req: "_GenRequest", slot: int):
        """Free a request's target-pool row AND its draft-pool row (ISSUE
        17) — every terminal path (finish, evict, quarantine, evacuate,
        shutdown) must release both or the draft pool's slot ledger
        diverges from the target's."""
        self.pool.free(slot)
        self.sampling_table.clear(slot)
        if self.adapter_bank is not None:
            self.adapter_bank.clear_slot(slot)
        if self.draft_pool is not None and req.draft_slot is not None:
            if self.draft_pool.active[req.draft_slot]:
                self.draft_pool.free(req.draft_slot)
            req.draft_slot = None

    # ---- lifecycle ----
    def start(self) -> "LLMEngine":
        """Run the scheduler on a background thread (production mode). Not
        needed under a SimClock — the harness calls pump() itself."""
        if isinstance(self.clock, SimClock):
            raise RuntimeError(
                "LLMEngine.start() with a SimClock would busy-spin: drive "
                "pump() from the simulation harness instead")
        with self._cond:
            if self._stopped:
                raise RuntimeError("engine already stopped")
            if self._thread is not None:
                return self
            self._thread = threading.Thread(
                target=self._scheduler_main, daemon=True,
                name="pdtpu-llm-scheduler")
            self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Graceful drain: stop admissions (submit -> RejectedError), then
        finish EVERY admitted sequence — queued requests still get
        prefilled and decoded to completion — before stopping the
        scheduler. With drain=False, queued and decoding requests fail
        with RejectedError instead. A drain that cannot finish inside
        `timeout` (default config.drain_timeout_s) fails the stragglers
        with RejectedError(reason="drain_timeout") rather than joining
        forever on futures that can never resolve."""
        with self._cond:
            if self._stopped:
                return
            self._draining = True
            flight_recorder().record(
                "drain_begin", engine="llm", drain=bool(drain),
                queued=self._queue_len_locked(), active=len(self._active))
            if not drain:
                for q in self._queues.values():
                    while q:
                        req = q.popleft()
                        self._conclude(req, "rejected:shutdown")
                        req.handle.future.set_exception(
                            RejectedError("engine shut down before prefill",
                                          reason="shutdown"))
                        self.metrics.on_reject("shutdown")
                for slot, req in list(self._active.items()):
                    self._conclude(req, "rejected:shutdown")
                    req.handle.future.set_exception(
                        RejectedError("engine shut down mid-decode",
                                      reason="shutdown"))
                    self.metrics.on_reject("shutdown")
                    self._free_row_locked(req, slot)
                self._active.clear()
                self.metrics.set_queue_depth(0)
                self.metrics.set_slots(0, self.pool.num_slots)
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            join_s = (timeout if timeout is not None
                      else self.config.drain_timeout_s)
            thread.join(join_s)
            if thread.is_alive():
                _log.warning(
                    "llm drain did not complete within %.1fs; failing "
                    "sequences still in flight", join_s)
        else:
            # threadless (sim) mode: run the scheduler inline to
            # completion, with a no-progress guard so a pump that can no
            # longer advance anything (e.g. breaker open mid-drain) falls
            # through to the stranded-future cleanup instead of spinning
            prev = None
            while self.has_work():
                self.pump()
                state = (self._queue_len_locked(), len(self._active),
                         self._dispatch_idx, self._inflight is None)
                if state == prev:
                    break
                prev = state
        if thread is None or not thread.is_alive():
            # a step still in flight (the scheduler left on `_stopped` or
            # an open breaker, or the loop above gave up): its rows'
            # requests are over or about to be failed below
            self._retire_in_flight()
        with self._cond:
            stranded = 0
            for q in self._queues.values():
                while q:
                    req = q.popleft()
                    self._conclude(req, "rejected:drain_timeout")
                    req.handle.future.set_exception(RejectedError(
                        "engine drain timed out before prefill",
                        reason="drain_timeout"))
                    self.metrics.on_reject("drain_timeout")
                    stranded += 1
            for slot, req in list(self._active.items()):
                self._conclude(req, "rejected:drain_timeout")
                req.handle.future.set_exception(RejectedError(
                    "engine drain timed out mid-decode",
                    reason="drain_timeout"))
                self.metrics.on_reject("drain_timeout")
                self._free_row_locked(req, slot)
                stranded += 1
            self._active.clear()
            if stranded:
                self.metrics.set_queue_depth(0)
                self.metrics.set_slots(0, self.pool.num_slots)
            self._stopped = True
            self._cond.notify_all()
        self.moe_expert_tokens()    # leave the totals where they outlive us
        flight_recorder().record("drain_end", engine="llm",
                                 stranded=stranded)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def broken(self) -> bool:
        """Circuit breaker open: repeated engine-level dispatch failures;
        admissions reject and /healthz reports 503."""
        return self.supervisor.open

    def _on_breaker_trip(self):
        """Repeated engine-level failures: admissions stop (submit ->
        "circuit_open"), queued requests fail now — their dispatches would
        only fail again — and the front end is notified so it can flip
        /healthz and drain on its own thread."""
        flushed = 0
        with self._cond:
            for q in self._queues.values():
                while q:
                    req = q.popleft()
                    self._conclude(req, "rejected:circuit_open")
                    req.handle.future.set_exception(RejectedError(
                        "engine circuit breaker open after repeated "
                        "dispatch failures", reason="circuit_open"))
                    self.metrics.on_reject("circuit_open")
                    flushed += 1
            self.metrics.set_queue_depth(0)
            self._cond.notify_all()
        flight_recorder().record("queue_flushed", engine="llm",
                                 reason="circuit_open", n=flushed)
        self.metrics.set_circuit_open(True)
        if self.on_break is not None:
            try:
                self.on_break()
            except Exception:
                _log.exception("llm on_break callback failed")

    # ---- rolling weight deployment (ISSUE 16) ----
    def evacuate(self, reason: str = "deploy_drain") -> int:
        """Deploy-drain eviction: fail every queued AND active request
        with a typed RejectedError(reason=...) and free their KV rows,
        WITHOUT entering the terminal stop() path — the engine keeps
        serving afterwards. The DeploymentController calls this only
        after the router has already re-queued the same streams for
        failover re-prefill on a survivor, so nothing observable is
        dropped: these engine-side rows are orphans whose handles are
        detached. Returns rows+requests evicted."""
        n = 0
        with self._cond:
            for q in self._queues.values():
                while q:
                    req = q.popleft()
                    self._conclude(req, f"rejected:{reason}")
                    if not req.handle.future.done():
                        req.handle.future.set_exception(RejectedError(
                            f"engine evacuated ({reason}) before prefill",
                            reason=reason))
                    self.metrics.on_reject(reason)
                    n += 1
            for slot, req in list(self._active.items()):
                self._conclude(req, f"rejected:{reason}")
                if not req.handle.future.done():
                    req.handle.future.set_exception(RejectedError(
                        f"engine evacuated ({reason}) mid-decode",
                        reason=reason))
                self.metrics.on_reject(reason)
                self._free_row_locked(req, slot)
                n += 1
            self._active.clear()
            self.metrics.set_queue_depth(0)
            self.metrics.set_slots(self.pool.active_slots(),
                                   self.pool.num_slots)
            self._cond.notify_all()
        if self._thread is None:
            # the step in flight now carries only orphans: discard it here
            # (a scheduler thread does so on its next pass, for which
            # `has_work()` stays true)
            self._retire_in_flight()
        if n:
            flight_recorder().record("deploy_evacuate", engine="llm",
                                     reason=reason, n=n)
        return n

    def export_sampling_lanes(self, slots) -> dict:
        """Serialize the sampling-lane state of active `slots` — the
        companion payload to `kv_pool.export_rows` (ISSUE 18): per slot,
        the request seed, the NEXT RNG stream index, the sampling params,
        and (for constrained rows) the grammar key plus current DFA
        state. A peer that imports the KV rows and rebinds these lanes
        (seed → `SamplingParams`, next_index → `sample_offset`,
        grammar_key → recompile + DFA fast-forward) continues the stream
        bit-identically to the uninterrupted run — the same contract the
        router's failover re-prefill exercises without KV transfer."""
        out: Dict[int, dict] = {}
        with self._cond:
            tab = self.sampling_table
            for slot in slots:
                slot = int(slot)
                req = self._active.get(slot)
                if req is None:
                    raise ValueError(f"slot {slot} has no active request")
                sp = req.sampling or GREEDY
                out[slot] = {
                    "seed": None if sp.seed is None else int(sp.seed),
                    "next_index": req.sample_offset + len(req.emitted),
                    "temperature": float(sp.temperature),
                    "top_k": int(sp.top_k),
                    "top_p": float(sp.top_p),
                    "grammar_key": (sp.grammar_key()
                                    if sp.constrained else None),
                    "dfa_state": int(tab.dfa_state[slot]),
                }
        return out

    def export_stream(self, rid: str) -> dict:
        """Export ONE active stream for a prefill→decode handoff (ISSUE
        19) and release its row — atomically, under a single lock
        acquisition, so no decode step can advance the stream between the
        snapshot and the release (the payload's emitted/KV/lane views are
        mutually consistent by construction).

        Requires the stream to have completed prefill (it has emitted at
        least one token): at that point the row's KV covers exactly
        ``len(prompt) + len(emitted) - 1`` tokens — the last emitted
        token's KV is written by the step that consumes it — so a peer
        that resubmits ``prompt + emitted`` with this payload's `kv_row`
        pays a ONE-token prefill and continues bit-identically
        (chunk-invariance + the bitwise export/import round trip).

        The engine-side handle is detached: its future is left unresolved
        (the receiving replica's handle carries the stream forward — the
        same convention as failover-abandoned handles) and the row is
        freed for new work. Raises ValueError when the rid is not active
        or still mid-prefill.

        A unified step in flight is left there: the export reads the
        row's columns below its committed length, which that step does
        not write (the host read waits for it by data dependence), and
        the row's token in it is discarded when the step retires, the
        request no longer holding the slot."""
        with self._cond:
            found = None
            for slot, req in self._active.items():
                if req.rid == rid:
                    found = (slot, req)
                    break
            if found is None:
                raise ValueError(f"no active stream with rid {rid!r}")
            slot, req = found
            if req.chunk_off < len(req.prompt) or not req.emitted:
                raise ValueError(
                    f"stream {rid!r} has not completed prefill: a handoff "
                    "exports post-prefill KV only")
            row = self.pool.export_rows([slot])["rows"][slot]
            # inline the lane dict (export_sampling_lanes takes _cond,
            # which is non-reentrant)
            sp = req.sampling or GREEDY
            lane = {
                "seed": None if sp.seed is None else int(sp.seed),
                "next_index": req.sample_offset + len(req.emitted),
                "temperature": float(sp.temperature),
                "top_k": int(sp.top_k),
                "top_p": float(sp.top_p),
                "grammar_key": (sp.grammar_key()
                                if sp.constrained else None),
                "dfa_state": int(self.sampling_table.dfa_state[slot]),
            }
            payload = {
                "rid": rid,
                "tenant": req.tenant,
                "prompt": np.asarray(req.prompt, np.int32).copy(),
                "emitted": list(req.emitted),
                "logprobs": (req.handle.logprobs_so_far()
                             if req.want_logprobs else None),
                "kv_row": {
                    "block_len": self.pool.block_len,
                    "length": int(row["length"]),
                    "layers": row["layers"],
                },
                "lane": lane,
                "weight_version": self.weight_version,
                "adapter": req.adapter,
            }
            self._conclude(req, "handoff")
            self._free_row_locked(req, slot)
            del self._active[slot]
            self.metrics.set_slots(self.pool.active_slots(),
                                   self.pool.num_slots)
            self._cond.notify_all()
        flight_recorder().record(
            "kv_export", engine="llm", rid=rid,
            tokens=int(payload["kv_row"]["length"]),
            emitted=len(payload["emitted"]))
        return payload

    def replace_params(self, new_params, version: str):
        """Hot in-place weight swap between pump iterations — NO
        recompile. The unified step executable keys on its arguments'
        abstract signature (shape/dtype tree), and `_launch` reads
        `self.params` fresh on every dispatch, so rebinding the attribute
        with a signature-identical tree reuses the warm `_step_jit` —
        verified end to end by the compile observatory (no
        `compile_recompile` events for `llm/unified_step` across a
        deploy). Refuses (typed `WeightSwapError`) if the engine still
        holds queued/active work or if the new tree's structure, any leaf
        shape, or any leaf dtype differs. Also flushes the prefix cache:
        cached KV was computed under the OLD weights, and attaching it to
        a new-version prompt would stitch two weight sets inside one
        attention window."""
        if not version:
            raise ValueError("version must be a non-empty string")
        converted = jax.tree_util.tree_map(jnp.asarray, new_params)
        old_s = jax.tree_util.tree_structure(self.params)
        new_s = jax.tree_util.tree_structure(converted)
        if old_s != new_s:
            raise WeightSwapError(
                f"weight set {version!r} has a different tree structure "
                f"than the serving params ({new_s} vs {old_s})")
        old_leaves = jax.tree_util.tree_leaves_with_path(self.params)
        new_leaves = jax.tree_util.tree_leaves(converted)
        for (path, old), new in zip(old_leaves, new_leaves):
            if tuple(old.shape) != tuple(new.shape) \
                    or old.dtype != new.dtype:
                raise WeightSwapError(
                    f"weight set {version!r} leaf "
                    f"{jax.tree_util.keystr(path)} is "
                    f"{tuple(new.shape)}/{new.dtype}, serving params have "
                    f"{tuple(old.shape)}/{old.dtype} — abstract signature "
                    "must match exactly (swap without recompile)")
        if self._thread is None:
            self._retire_in_flight()
        with self._cond:
            if self._has_work_locked():
                raise WeightSwapError(
                    f"cannot swap to {version!r} with work in flight "
                    f"(queued={self._queue_len_locked()}, "
                    f"active={len(self._active)}, unretired step="
                    f"{self._inflight is not None}): drain first")
            flushed = 0
            if self.prefix_cache is not None:
                flushed = self.prefix_cache.clear()
            if self.draft_prefix_cache is not None:
                # the draft's weights did not change, but keeping both
                # caches' lifecycles aligned across deploys is cheap and
                # removes a whole class of "stale draft prefix after
                # rollback" questions (draft KV is an optimization, never
                # a correctness input — acceptance re-verifies everything)
                flushed += self.draft_prefix_cache.clear()
            prior = self.weight_version
            self.params = converted
            self.weight_version = str(version)
            self._cond.notify_all()
        flight_recorder().record(
            "weight_swap", engine="llm", version=str(version),
            prior=prior, leaves=len(new_leaves), flushed_blocks=flushed)

    # ---- multi-LoRA adapter lifecycle (ISSUE 20) ----
    def _flush_adapter_kv(self, adapter_id: str):
        """Drop ONE adapter's `(tenant, adapter)` KV namespaces from both
        cache tiers: its cached KV was computed under the delta being
        replaced. Base and other-adapter namespaces stay warm."""
        suffix = f"\x00adapter:{adapter_id}"
        if self.prefix_cache is not None:
            # clears the matching host-tier namespaces too
            self.prefix_cache.clear(only=lambda ns: ns.endswith(suffix))
        elif self.host_kv is not None:
            self.host_kv.clear(only=lambda ns: ns.endswith(suffix))

    def _require_bank(self) -> AdapterBank:
        if self.adapter_bank is None:
            raise AdapterError(
                "engine built without an adapter bank "
                "(config.max_adapters=0)", reason="adapter_unavailable")
        return self.adapter_bank

    def register_adapter(self, adapter_id: str, tree,
                         alpha: Optional[float] = None):
        """Load — or hot-swap, when the id is already resident — one
        adapter into a bank row. Unlike `replace_params` this needs NO
        drain: the swap rewrites bank-row values between pump
        iterations while the step executable and every other row's
        streams are untouched (base weights included), which is what
        makes adapter rollout zero-downtime by construction. The tree
        is validated against the base-model signature first (typed
        AdapterError on rank/target/shape mismatch — never a
        recompile).

        Returns the PRIOR row snapshot (None for a fresh load) — the
        rollback token `rollback_adapter` restores when a post-swap
        canary fails."""
        bank = self._require_bank()
        prior = bank.snapshot_row(adapter_id)
        row = bank.load(adapter_id, tree, alpha=alpha)
        # flush the adapter's KV namespaces: cached pages were computed
        # under the OLD delta (same reasoning as replace_params, scoped
        # to one adapter's namespaces instead of the whole cache)
        if prior is not None:
            self._flush_adapter_kv(adapter_id)
        flight_recorder().record(
            "adapter_swap", engine="llm", adapter=str(adapter_id),
            row=row, update=prior is not None,
            bank_version=bank.version)
        self.metrics.on_adapter_swap()
        return prior

    def rollback_adapter(self, adapter_id: str, snapshot):
        """Restore a bank row to a `register_adapter` rollback token
        (None = the adapter was fresh: unload it). The canary-failed
        delta stops serving the instant the row is rewritten; in-flight
        streams on the row continue on the restored values — no drop,
        no drain."""
        bank = self._require_bank()
        bank.restore(adapter_id, snapshot)
        self._flush_adapter_kv(adapter_id)
        flight_recorder().record(
            "adapter_rollback", engine="llm", adapter=str(adapter_id),
            restored=snapshot is not None, bank_version=bank.version)
        self.metrics.on_adapter_rollback()

    def unregister_adapter(self, adapter_id: str):
        """Unload an adapter and zero its row. Typed refusal while any
        queued/active stream still decodes under it — unloading would
        silently flip those streams to a zero delta mid-sequence."""
        bank = self._require_bank()
        with self._cond:
            users = [r.rid for r in self._active.values()
                     if r.adapter == adapter_id]
            users += [r.rid for q in self._queues.values()
                      for r in q if r.adapter == adapter_id]
            if users:
                raise AdapterError(
                    f"adapter {adapter_id!r} still has {len(users)} "
                    f"in-flight stream(s) ({users[:4]}...): drain or "
                    "finish them first", reason="adapter_in_use")
            bank.unload(adapter_id)
        flight_recorder().record(
            "adapter_unload", engine="llm", adapter=str(adapter_id),
            bank_version=bank.version)

    def canary_probe(self, prompt, max_new_tokens: int = 4,
                     adapter: Optional[str] = None):
        """Golden-prompt canary: greedy-decode `max_new_tokens` tokens
        directly through the prefill/decode functions on the CONTIGUOUS
        cache path (paged=None — same kernel as the paged path at shared
        block size, so bit-identity across replicas is meaningful),
        checking every logits tensor for finiteness along the way.
        Runs outside the scheduler on purpose: the gate must work on a
        drained, placement-excluded replica before any traffic lands on
        the new weights. `adapter` (ISSUE 20) probes through that bank
        row's LoRA delta — the gate an adapter hot-swap must clear
        before its rows keep serving — and raises a typed AdapterError
        when the id is not loaded. Returns (tokens np.int32
        [max_new_tokens], logits_finite bool)."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("canary prompt must be non-empty")
        adapters = None
        if adapter is not None:
            if self.adapter_bank is None:
                raise AdapterError(
                    "engine built without an adapter bank "
                    "(config.max_adapters=0)", reason="adapter_unavailable")
            row = self.adapter_bank.row_of(adapter)
            if row is None:
                raise AdapterError(f"unknown adapter {adapter!r}",
                                   reason="unknown_adapter")
            adapters = self.adapter_bank.args_for_rows([row])
        total = int(prompt.size) + int(max_new_tokens)
        caches = self.model.init_cache(1, total)
        logits, caches = self._prefill_fn(
            self.params, jnp.asarray(prompt[None, :]), caches, 0,
            adapters=adapters)
        lg = np.asarray(logits)
        finite = bool(np.isfinite(lg).all())
        last = int(np.argmax(lg[0, -1]))
        toks = [last]
        pos = int(prompt.size)
        for _ in range(int(max_new_tokens) - 1):
            logits, caches = self._decode_fn(
                self.params, jnp.asarray([last], dtype=jnp.int32),
                pos, caches, adapters=adapters)
            lg = np.asarray(logits)
            finite = finite and bool(np.isfinite(lg).all())
            last = int(np.argmax(lg[0]))
            toks.append(last)
            pos += 1
        return np.asarray(toks, dtype=np.int32), finite

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop(drain=True)
        return False

    # ---- admission ----
    def _queue_len_locked(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _pop_next_locked(self) -> Optional[_GenRequest]:
        """Strict SLO-class priority, tenant-fair WITHIN a class: among
        the highest non-empty class's queue, dequeue the oldest request
        of the tenant with the least active token usage (sum of cost over
        its slot-holding requests), so one tenant's burst cannot starve
        another at equal priority. With a single tenant queued this
        degenerates to exact FIFO."""
        for cls in SLO_CLASSES:     # strict priority order
            q = self._queues[cls]
            if not q:
                continue
            if len({r.tenant for r in q}) <= 1:
                return q.popleft()
            usage: Dict[str, int] = {}
            for r in self._active.values():
                usage[r.tenant] = usage.get(r.tenant, 0) + r.cost
            best_i = 0
            best_u = None
            for i, r in enumerate(q):           # FIFO tie-break
                u = usage.get(r.tenant, 0)
                if best_u is None or u < best_u:
                    best_i, best_u = i, u
            req = q[best_i]
            del q[best_i]
            return req
        return None

    def _tenant_inflight_locked(self, tenant: str) -> int:
        return (sum(r.cost for q in self._queues.values()
                    for r in q if r.tenant == tenant)
                + sum(r.cost for r in self._active.values()
                      if r.tenant == tenant))

    def _inflight_tokens_locked(self) -> int:
        """Estimated token cost of everything admitted: queued + active.
        Recomputed from the tables (never incrementally maintained), so a
        failure path can never leak budget."""
        return (sum(r.cost for q in self._queues.values() for r in q)
                + sum(r.cost for r in self._active.values()))

    def _update_brownout_locked(self):
        if self.config.brownout_queue_depth is None:
            return
        depth = self._queue_len_locked()
        if not self._brownout and depth >= self.config.brownout_queue_depth:
            self._brownout = True
            self.metrics.set_brownout(True)
            _log.warning(
                "llm engine entering brownout at queue depth %d: capping "
                "admitted max_new_tokens to %d", depth,
                self.config.brownout_max_new_tokens)
        elif self._brownout and depth <= self.config.brownout_queue_depth // 2:
            self._brownout = False
            self.metrics.set_brownout(False)
            _log.info("llm engine exiting brownout at queue depth %d", depth)

    def _make_room_locked(self, slo: str, cost: int) -> Optional[str]:
        """Shed-lowest-first: while the queue or token budget blocks this
        admission, fail the NEWEST queued request of the lowest class
        strictly below `slo` (reason "shed"). Returns None when the
        request can be admitted, else the reject reason."""
        pri = SLO_CLASSES.index(slo)
        while True:
            depth_full = (self._queue_len_locked()
                          >= self.config.max_queue_depth)
            budget = self.config.max_inflight_tokens
            over_budget = (budget is not None
                           and self._inflight_tokens_locked() + cost > budget)
            if not depth_full and not over_budget:
                return None
            victim = None
            for cls in reversed(SLO_CLASSES):   # lowest class first
                if SLO_CLASSES.index(cls) <= pri:
                    break
                if self._queues[cls]:
                    victim = self._queues[cls].pop()   # newest of its class
                    break
            if victim is None:
                return "queue_full" if depth_full else "token_budget"
            self._conclude(victim, "shed")
            victim.handle.future.set_exception(RejectedError(
                f"shed ({victim.slo}) to admit {slo} traffic under "
                "overload", reason="shed",
                retry_after_s=self.config.retry_after_s))
            self.metrics.on_reject("shed", tenant=victim.tenant)
            self.metrics.on_shed(victim.slo)
            if self.burn is not None:
                self.burn.observe(victim.slo, False, outcome="shed")
            self._record_reject("shed", rid=victim.rid,
                                tenant=victim.tenant)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               slo: Optional[str] = None,
               tenant: Optional[str] = None,
               rid: Optional[str] = None,
               trace: bool = False,
               sampling: Optional[SamplingParams] = None,
               sample_offset: int = 0,
               logprobs: bool = False,
               kv_row: Optional[dict] = None,
               lane: Optional[dict] = None,
               adapter: Optional[str] = None) -> GenerationHandle:
        """Admit one prompt (1-D int token ids). `slo` names the request's
        SLO class (config.default_slo when None); `tenant` its isolation
        domain (config.default_tenant when None) — tenants get fair
        dequeue within a class, an optional in-flight token quota, and a
        private prefix-cache namespace. `rid` is the request id (ingested
        from a traceparent header by the server, generated when None);
        `trace=True` accumulates a per-request timeline on the handle and
        in the engine's timeline store.

        `sampling` (ISSUE 18) carries the per-request sampling contract;
        None is greedy. `sample_offset` restores the request's RNG lane
        on a failover re-prefill: it is the stream index of the first
        token THIS admission will emit (= tokens already emitted on the
        dead replica, re-prefilled as the prompt's tail), so draw i of
        the logical stream stays keyed by `(seed, i)` across the
        failover. For a constrained request the same tail is walked
        through the grammar DFA host-side to restore the mask state.

        ISSUE 19: `logprobs=True` streams each emitted token's raw
        log-probability onto the handle (`logprobs_so_far()`). `kv_row`
        imports pre-computed KV for the prompt's first `kv_row["length"]`
        tokens at admission (a prefill→decode handoff: the exporting
        replica's `export_stream` payload), skipping their re-prefill.
        `lane` is the exported sampling-lane dict riding the same
        payload; when it matches this admission's `sample_offset`, a
        constrained request restores its DFA state directly from the
        lane instead of re-walking the resumed tail.

        ISSUE 20: `adapter` names a loaded AdapterBank row — the stream
        then decodes under that adapter's LoRA delta on the SAME unified
        step as its base/other-adapter neighbors. None rides bank row 0
        (all-zero delta) and is bit-identical to a pre-LoRA engine.
        Naming an adapter on an engine without a bank, or one that is
        not loaded, is a typed reject ("adapter_unavailable" /
        "unknown_adapter"), never a recompile.

        Raises RejectedError when the sequence can never fit a slot, the
        queue/token budget/tenant quota is exhausted and nothing
        lower-priority can be shed, the grammar bank is full, the engine
        is draining, or the circuit breaker is open."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        rid = rid or new_request_id()
        # on the caller's thread: validation and the wait for the
        # engine's lock, which no other span covers
        with RecordEvent(SPAN_REQUEST_SUBMIT, rid=rid,
                         prompt_tokens=int(prompt.size)):
            return self._submit(prompt, max_new_tokens, eos_token_id,
                                deadline_ms, slo, tenant, rid, trace,
                                sampling, sample_offset, logprobs, kv_row,
                                lane, adapter)

    def _submit(self, prompt, max_new_tokens, eos_token_id, deadline_ms,
                slo, tenant, rid, trace, sampling, sample_offset, logprobs,
                kv_row, lane, adapter) -> GenerationHandle:
        """`submit`'s body, inside its span: `prompt` is the int32 row,
        `rid` is set."""
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        sample_offset = int(sample_offset)
        if sample_offset < 0:
            raise ValueError(
                f"sample_offset must be >= 0, got {sample_offset}")
        mnt = (self.config.max_new_tokens if max_new_tokens is None
               else int(max_new_tokens))
        if mnt < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {mnt}")
        slo = self.config.default_slo if slo is None else slo
        if slo not in SLO_CLASSES:
            raise ValueError(
                f"slo must be one of {SLO_CLASSES}, got {slo!r}")
        tenant = self.config.default_tenant if tenant is None else tenant
        if not isinstance(tenant, str) or not tenant:
            raise ValueError("tenant must be a non-empty string")
        if adapter is not None:
            if self.adapter_bank is None:
                self.metrics.on_reject("adapter_unavailable", tenant=tenant)
                self._record_reject("adapter_unavailable", rid=rid,
                                    tenant=tenant)
                raise RejectedError(
                    f"request names adapter {adapter!r} but the engine "
                    "was built without an adapter bank "
                    "(config.max_adapters=0)",
                    reason="adapter_unavailable")
            if self.adapter_bank.row_of(adapter) is None:
                self.metrics.on_reject("unknown_adapter", tenant=tenant)
                self._record_reject("unknown_adapter", rid=rid,
                                    tenant=tenant)
                raise RejectedError(
                    f"adapter {adapter!r} is not loaded "
                    f"(loaded: {self.adapter_bank.adapter_ids})",
                    reason="unknown_adapter")
        eos = (self.config.eos_token_id if eos_token_id is None
               else eos_token_id)
        gid, dstate0 = 0, 0
        if sampling is not None:
            sampling.validate()
            if sampling.grammar is not None:
                gkey = sampling.grammar_key()
                gid = self.sampling_table.lookup(gkey)
                if gid is None:
                    tg0 = self.clock.now()
                    dfa = compile_grammar(
                        sampling.grammar, self.sampling_table.vocab_size,
                        eos)
                    try:
                        gid = self.sampling_table.intern(gkey, dfa)
                    except ValueError as e:
                        # bank capacity is an admission-control condition,
                        # not a caller bug: typed reject, not ValueError
                        self.metrics.on_reject("grammar_capacity")
                        self._record_reject("grammar_capacity", rid=rid,
                                            tenant=tenant)
                        raise RejectedError(str(e),
                                            reason="grammar_capacity")
                    if self.ledger is not None:
                        self.ledger.book("sample_mask",
                                         self.clock.now() - tg0)
                    self.metrics.set_grammars(
                        self.sampling_table.grammars_compiled)
                if sample_offset and lane is not None \
                        and lane.get("grammar_key") == gkey \
                        and int(lane.get("next_index", -1)) == sample_offset:
                    # prefill→decode handoff (ISSUE 19): the exported lane
                    # carries the DFA state at exactly this admission's
                    # resume index — restore it directly, no re-walk
                    dstate0 = int(lane["dfa_state"])
                elif sample_offset:
                    # failover re-prefill: the prompt's tail IS the
                    # emitted-so-far constrained stream — walk it through
                    # the DFA so the mask resumes mid-grammar exactly
                    bank = self.sampling_table.bank[gid]
                    q = 0
                    for t in prompt[-min(sample_offset, prompt.size):]:
                        nq = int(bank[q, int(t)])
                        if nq < 0:
                            raise ValueError(
                                "failover resume tail violates the "
                                f"request grammar at token {int(t)}")
                        q = nq
                    dstate0 = q
        if kv_row is not None:
            self._refuse(self.pool, REREAD, "kv_row with a model")
            if int(kv_row.get("block_len", -1)) != self.pool.block_len:
                raise ValueError(
                    f"kv_row block_len {kv_row.get('block_len')!r} does "
                    f"not match the pool's ({self.pool.block_len}): KV "
                    "pages are not portable across block geometries")
            klen = int(kv_row["length"])
            if not 0 < klen <= prompt.size - 1:
                raise ValueError(
                    f"kv_row length {klen} must cover 1..{prompt.size - 1} "
                    "prompt tokens (at least one token always prefills — "
                    "that step emits the first token's logits)")
        if prompt.size + mnt > self.pool.capacity:
            self.metrics.on_reject("prompt_too_long")
            self._record_reject("prompt_too_long", rid=rid, tenant=tenant)
            raise RejectedError(
                f"prompt ({prompt.size}) + max_new_tokens ({mnt}) exceeds "
                f"slot capacity ({self.pool.capacity} tokens)",
                reason="prompt_too_long")
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        now = self.clock.now()
        deadline = None if deadline_ms is None else now + deadline_ms / 1e3
        with self._cond:
            if self.supervisor.open:
                self.metrics.on_reject("circuit_open")
                self._record_reject("circuit_open", rid=rid, tenant=tenant)
                raise RejectedError(
                    "engine circuit breaker open after repeated dispatch "
                    "failures; request rejected", reason="circuit_open")
            if self._draining or self._stopped:
                self.metrics.on_reject("draining")
                self._record_reject("draining", rid=rid, tenant=tenant)
                raise RejectedError("engine is draining; request rejected",
                                    reason="draining")
            self._update_brownout_locked()
            if self._brownout and mnt > self.config.brownout_max_new_tokens:
                mnt = self.config.brownout_max_new_tokens
            quota = self.config.tenant_max_inflight_tokens
            if quota is not None and (
                    self._tenant_inflight_locked(tenant)
                    + prompt.size + mnt > quota):
                # checked BEFORE shed logic: shedding OTHER tenants'
                # requests cannot relieve this tenant's own quota
                self.metrics.on_reject("tenant_quota", tenant=tenant)
                self._record_reject("tenant_quota", rid=rid, tenant=tenant)
                raise RejectedError(
                    f"tenant {tenant!r} in-flight token quota exhausted "
                    f"({quota} tokens)", reason="tenant_quota",
                    retry_after_s=self.config.retry_after_s)
            reason = self._make_room_locked(slo, prompt.size + mnt)
            if reason is not None:
                self.metrics.on_reject(reason)
                self._record_reject(reason, rid=rid, tenant=tenant)
                detail = (f"queue at capacity ({self.config.max_queue_depth} "
                          "pending requests)" if reason == "queue_full" else
                          f"token budget exhausted "
                          f"({self.config.max_inflight_tokens} in-flight "
                          "tokens)")
                raise RejectedError(
                    f"{detail}; nothing below class {slo!r} to shed",
                    reason=reason,
                    retry_after_s=self.config.retry_after_s)
            req = _GenRequest(prompt, mnt, eos, now, deadline, slo,
                              self._submit_idx, tenant=tenant)
            req.rid = rid
            req.handle.rid = rid
            req.sampling = sampling
            req.sample_offset = sample_offset
            req.gid = gid
            req.dfa_state0 = dstate0
            req.want_logprobs = bool(logprobs)
            req.kv_row = kv_row
            req.adapter = adapter
            if trace:
                req.trace = RequestTrace(rid, now, slo=slo, tenant=tenant)
                req.trace.event("submitted", now, prompt_len=int(prompt.size),
                                max_new_tokens=mnt,
                                submit_idx=self._submit_idx)
                req.handle.trace = req.trace
            self._submit_idx += 1
            self._queues[slo].append(req)
            self.metrics.on_submit(self._queue_len_locked(), slo=slo,
                                   tenant=tenant)
            self.metrics.set_inflight_tokens(self._inflight_tokens_locked())
            self._cond.notify_all()
        return req.handle

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 timeout: Optional[float] = None,
                 slo: Optional[str] = None,
                 tenant: Optional[str] = None,
                 sampling: Optional[SamplingParams] = None) -> np.ndarray:
        """Synchronous convenience: submit + wait for the full sequence."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_token_id=eos_token_id,
                           deadline_ms=deadline_ms, slo=slo,
                           tenant=tenant, sampling=sampling).result(timeout)

    @staticmethod
    def _kv_ns(tenant: str, adapter: Optional[str]) -> str:
        """Prefix-cache/host-KV namespace for a stream (ISSUE 20): KV
        computed under an adapter's LoRA delta diverges from base KV
        after the first adapted layer, so each `(tenant, adapter)` pair
        gets its own radix namespace — adapter streams never attach base
        pages and vice versa. The composed key rides the existing
        string-tenant cache machinery unchanged (NUL cannot appear in a
        tenant id, so the composition is injective)."""
        return tenant if not adapter else f"{tenant}\x00adapter:{adapter}"

    def prefix_probe(self, prompt, tenant: Optional[str] = None,
                     adapter: Optional[str] = None) -> int:
        """Longest block-aligned cached-prefix match for `prompt` in this
        engine's radix cache, in tokens — 0 with the cache disabled.
        Read-only (no refcounts, ticks, or stats move): the replica
        router calls this on every candidate per admission to steer a
        request to the replica already holding its prefix KV, and a
        probe on a losing candidate must leave that replica's cache
        untouched. Surfaced over HTTP via /healthz `llm_prefix_probe`.

        ISSUE 19: the probe consults BOTH tiers — a replica whose device
        cache evicted a prefix into its host pool can still onboard it
        without re-prefilling, so for placement scoring it is exactly as
        warm as one still holding the pages in HBM.

        ISSUE 20: `adapter` probes that adapter's own `(tenant, adapter)`
        namespace — router placement is then warmth-aware per adapter,
        not just per tenant."""
        tenant = self.config.default_tenant if tenant is None else tenant
        ns = self._kv_ns(tenant, adapter)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        host = (self.host_kv.probe(ns, prompt)
                if self.host_kv is not None else 0)
        if self.prefix_cache is None:
            return host
        return max(self.prefix_cache.probe(ns, prompt), host)

    def inflight_tokens(self) -> int:
        """Current admitted token cost (queued + active): the router's
        load tie-breaker."""
        with self._cond:
            return self._inflight_tokens_locked()

    # ---- scheduling ----
    def _has_work_locked(self) -> bool:
        """Anything queued, decoding, or launched and not yet retired."""
        return bool(self._queue_len_locked() or self._active
                    or self._inflight is not None)

    def has_work(self) -> bool:
        with self._cond:
            return self._has_work_locked()

    def next_event_time(self) -> Optional[float]:
        """Clock instant of the next scheduler action — `now` whenever any
        sequence is queued or decoding (decode/admission work is always
        immediately due), None when idle. The sim harness advances its
        clock here between scripted arrivals."""
        with self._cond:
            if self._has_work_locked():
                return self.clock.now()
            return None

    @property
    def unified_steps(self) -> int:
        """Lifetime committed unified steps of either kind (mirrored as
        `metrics.counters["unified_steps"]`; `counters["dispatches"]`
        counts only those with a decode row)."""
        return self.decode_iterations + self.prefill_dispatches

    def moe_expert_tokens(self) -> Optional[np.ndarray]:
        """`[layers, experts]` lifetime totals of live (position, expert)
        assignments, fetched from the device now (it waits for the step in
        flight, so: on /metrics, at `stop()` and on demand, never inside a
        step); None for a dense model. Every row sums to live tokens x
        experts per token (to the assignments that fell on the held
        experts, where a layer holds a share: `[layers, held]`). The
        fetch brings `counters["moe_assignments"]` up to the table's sum,
        and adds what is new since the last one to the process-wide
        `nn.layer.moe.EXPERT_TOKENS` and, per layer, the live positions
        routed since then to `nn.layer.moe.ROUTED_TOKENS`."""
        if self._moe_totals is None:
            return None
        with self._moe_publish_lock:
            routed = self._moe_routed
            totals = np.asarray(self._moe_totals, np.int64)
            fresh = totals - self._moe_published
            self._moe_published = totals
            self.metrics.on_moe_assignments(int(fresh.sum()))
            for layer, expert in zip(*np.nonzero(fresh)):
                moe.EXPERT_TOKENS[(int(layer), int(expert))] += \
                    int(fresh[layer, expert])
            for layer in range(totals.shape[0]):
                moe.ROUTED_TOKENS[layer] += \
                    routed - self._moe_routed_published
            self._moe_routed_published = routed
        return totals

    def pump(self) -> int:
        """One scheduler pass: drop expired queued requests, admit queued
        requests into free slots (bookkeeping only — no dispatch), then
        retire ONE unified mixed prefill+decode step — its successor
        launched first where its rows can be projected (`_step_pass`), so
        a pass may return with a step in flight; the next pass, `stop()`
        or `evacuate()` retires it, and `has_work()` stays true until
        then. Returns the number of decode iterations
        retired (0 or 1; a step carrying only prefill chunks returns 0) —
        the quantity the continuous-batching tests count. This is THE
        scheduler: the background thread and the sim harness both call
        it.

        With economics armed (ISSUE 11) the whole pass runs inside the
        serving ledger's ``measure("host")`` frame; the successful
        dispatch's device span is booked out of it by `_commit_step`, so
        host/compute/idle tile the pump's wall clock by construction."""
        led = self.ledger
        if led is None:
            return self._pump_inner()
        with led.measure("host"):
            return self._pump_inner()

    def _pump_inner(self) -> int:
        # the spans of one pass (names: profiler.SERVE_SPANS): the children
        # tile `pump` but for a few clock reads, so a jax.profiler trace
        # says what the host did in the gap between two unified steps
        with RecordEvent(SPAN_SERVE_PUMP, step=self.unified_steps):
            now = self.clock.now()
            # time-weighted slot occupancy (ISSUE 11 satellite): integrate
            # the level held since the previous pump pass, at pump
            # granularity
            self.metrics.observe_occupancy(now)
            with RecordEvent(SPAN_SERVE_ADMIT):
                self._drop_expired_queued(now)
                self._admit()
            n = self._step_pass()
            with RecordEvent(SPAN_SERVE_PUBLISH):
                self._publish_gauges()
        return n

    def _step_pass(self) -> int:
        """Retire one unified step, with its successor launched first
        where that can be: `_launch(k+1)` then `_retire(k)`, so the chip
        runs step k+1 while the host fetches and commits step k, admits
        and builds. One step in flight at most. Where the successor's rows
        do not follow from the step's own (`_can_launch_ahead`), or
        nothing is in flight, this is launch, retire: the same two
        functions in today's order. A dispatch that fails ahead of its
        predecessor commits nothing; the predecessor is retired, exactly
        once, and the failed step is taken up synchronously."""
        rec, self._inflight = self._inflight, None
        if rec is None:
            rec = self._launch()
            if rec is None:
                return 0
        ahead = failed = None
        if self._can_launch_ahead(rec):
            try:
                ahead = self._launch(ahead_of=rec)
            except DispatchFailedError as e:
                failed = e
        n = self._retire(rec)
        if failed is not None:
            ahead = self._launch(failed=failed)
        self._inflight = ahead
        return n

    def _publish_gauges(self):
        """The gauges one pump pass refreshes after its step."""
        with self._cond:
            self.metrics.set_inflight_tokens(self._inflight_tokens_locked())
            per_tenant: Dict[str, int] = {}
            for q in self._queues.values():
                for r in q:
                    per_tenant[r.tenant] = \
                        per_tenant.get(r.tenant, 0) + r.cost
            for r in self._active.values():
                per_tenant[r.tenant] = per_tenant.get(r.tenant, 0) + r.cost
            self.metrics.set_tenant_inflight(per_tenant)
            self.metrics.set_sample_slots(
                self.sampling_table.mode_counts(self._active.keys()))
        if self.prefix_cache is not None:
            self.metrics.set_prefix_cache(
                self.prefix_cache.stats["cached_blocks"],
                self.prefix_cache.stats["evictions"],
                {t: s["cached_blocks"]
                 for t, s in self.prefix_cache.tenant_stats.items()},
                self.prefix_cache.stats["evict_pops"],
                self.prefix_cache.stats["evict_stale"])
        if self.host_kv is not None:
            self.metrics.set_host_kv(self.host_kv.snapshot())
            if self.ledger is not None and self.prefix_cache is not None:
                # spill work happens inside pool.allocate's pressure hook
                # (mid-_admit), so the cache accumulates its wall time and
                # the pump books the delta into the kv_spill phase here
                spill = self.prefix_cache.spill_seconds
                if spill > self._spill_booked:
                    self.ledger.book("kv_spill", spill - self._spill_booked)
                    self._spill_booked = spill
        self.metrics.set_fragmentation(self.pool.fragmentation_ratio())

    def _drop_expired_queued(self, now: float):
        with self._cond:
            expired = 0
            for cls, q in self._queues.items():
                if not q:
                    continue
                alive = deque()
                for r in q:
                    if r.deadline is not None and now >= r.deadline:
                        self._conclude(r, "expired:queued", now)
                        r.handle.future.set_exception(DeadlineExceededError(
                            f"deadline expired after "
                            f"{(now - r.arrival) * 1e3:.1f}ms in queue "
                            "(dropped before prefill)"))
                        if self.burn is not None:
                            self.burn.observe(r.slo, False,
                                              outcome="expired_queued")
                        expired += 1
                    else:
                        alive.append(r)
                if len(alive) != len(q):
                    self._queues[cls] = alive
            if expired:
                self.metrics.on_expire(expired)
                self.metrics.set_queue_depth(self._queue_len_locked())

    def _bind_row_locked(self, req: _GenRequest,
                         span: RecordEvent) -> Optional[int]:
        """Give `req` a row of the pool and what the caches hold of its
        prompt: `probe_row`, `allocate`, then a `kv_row` import, or the
        prefix cache's attach and copy and the host tier's onboard.
        Returns the slot, or None where every free row is pinned (the
        request is back at the head of its queue). Stamps `admitted`;
        `span` is the request's `admit` span, which it gives the slot, the
        wait and the tokens that will not be prefilled."""
        # a prompt that the prefix cache covers writes only behind
        # the blocks it attaches: a row is fit for it whose cached
        # pages all sit below them, first of all the row they are
        # in (a session's next turn goes back where its last one
        # left its pages, and evicts nothing)
        keep_below, prefer = 0, None
        if self.prefix_cache is not None and req.kv_row is None:
            keep_below, prefer = self.prefix_cache.probe_row(
                self._kv_ns(req.tenant, req.adapter), req.prompt,
                max_tokens=len(req.prompt) - 1)
        try:
            slot = self.pool.allocate(req.cost, keep_below, prefer)
        except SlotsExhaustedError:
            # every free row is pinned by cached blocks with live
            # readers (pressure eviction couldn't help); requeue
            # at the front and retry once readers drain
            self._queues[req.slo].appendleft(req)
            self.metrics.set_queue_depth(self._queue_len_locked())
            span.set(requeued=1)
            return None
        req.slot = slot
        req.chunk_off = 0
        req.attached_pages = []
        # `queued` ends and `bound` begins
        req.admitted = self.clock.now()
        req.admit_step = self.unified_steps
        queued_ms = (req.admitted - req.arrival) * 1e3
        if req.trace is not None:
            req.trace.mark("admitted", req.admitted)
            req.trace.event("admitted", req.admitted, slot=slot,
                            queue_wait_ms=queued_ms)
        if req.kv_row is not None:
            # prefill→decode handoff import (ISSUE 19): upload the
            # exported row into this slot's own identity pages and
            # start chunked prefill past the covered span. No
            # set_length here — the next chunk commit's
            # set_length claims the own pages exactly as a cold
            # prefill would, so check_balance holds without a
            # special ledger path.
            t0 = self.clock.now()
            bl = self.pool.block_len
            klen = int(req.kv_row["length"])
            layers = req.kv_row["layers"]
            for j in range(0, klen, bl):
                w = min(bl, klen - j)
                blk = [tuple(a[:, j:j + w, :] for a in layer)
                       for layer in layers]
                self.pool.import_page(slot, j // bl, blk)
            req.chunk_off = klen
            self.kv_import_tokens += klen
            if self.ledger is not None:
                self.ledger.book("kv_onboard", self.clock.now() - t0)
            flight_recorder().record("kv_import", engine="llm", rid=req.rid,
                                     tokens=klen)
            if req.trace is not None:
                req.trace.event("kv_import", self.clock.now(), tokens=klen)
        elif self.prefix_cache is not None:
            # cap at plen-1 so at least one prompt token always
            # prefills (that step produces the first output
            # token's logits); an over-cap full block degrades to
            # a COW tail, so an exact-duplicate prompt still
            # costs only a one-token prefill
            plan = self.prefix_cache.acquire(
                self._kv_ns(req.tenant, req.adapter), req.prompt,
                max_tokens=len(req.prompt) - 1)
            if len(plan.pages) < keep_below:
                # cannot be: nothing between the probe and here
                # evicts below `keep_below`
                raise RuntimeError(
                    f"prefix plan of {len(plan.pages)} pages for a "
                    f"row chosen to keep {keep_below}")
            if plan.pages:
                self.pool.attach_blocks(slot, plan.pages)
                req.attached_pages = list(plan.pages)
            if plan.tail_page is not None:
                self.pool.cow_copy(plan.tail_page, slot)
            req.chunk_off = plan.attach_len
            # the slot now holds its own refs (attach_blocks) and
            # its own copy of the tail — drop acquire's transient
            # refcounts so eviction sees the true reader count
            self.prefix_cache.release(plan)
            self.metrics.on_prefix_lookup(
                req.tenant, plan.attach_len, len(req.prompt))
            if req.trace is not None:
                req.trace.event("prefix_lookup", self.clock.now(),
                                attach_len=plan.attach_len,
                                prompt_len=len(req.prompt))
        # host-tier onboard (ISSUE 19): where the device radix
        # cache's coverage ends on a block boundary, keep walking
        # block-by-block through the host spill pool and upload
        # covered pages into the slot's own identity pages —
        # chunked prefill then starts past everything either tier
        # held. A COW tail (non-aligned chunk_off) ends the walk:
        # that block is already mid-copy. Onboarded blocks are
        # re-indexed into the device trie for free when the
        # completed prefill runs `prefix_cache.insert`.
        if (self.host_kv is not None and req.kv_row is None
                and req.chunk_off % self.pool.block_len == 0):
            bl = self.pool.block_len
            t0 = self.clock.now()
            j = req.chunk_off // bl
            onboarded = 0
            # same cap as the device acquire: at least one prompt
            # token always prefills
            while (j + 1) * bl <= len(req.prompt) - 1:
                layers = self.host_kv.get(
                    self._kv_ns(req.tenant, req.adapter),
                    req.prompt[:(j + 1) * bl])
                if layers is None:
                    break
                self.pool.import_page(slot, j, layers)
                j += 1
                onboarded += 1
            if onboarded:
                req.chunk_off = j * bl
                self.host_onboard_tokens += onboarded * bl
                if self.ledger is not None:
                    self.ledger.book("kv_onboard", self.clock.now() - t0)
                flight_recorder().record(
                    "kv_onboard", engine="llm", rid=req.rid,
                    blocks=onboarded, tokens=onboarded * bl)
                if req.trace is not None:
                    req.trace.event("kv_onboard", self.clock.now(),
                                    blocks=onboarded, tokens=onboarded * bl)
        span.set(slot=slot, queued_ms=queued_ms,
                 cached_tokens=int(req.chunk_off))
        return slot

    def _admit(self):
        """Move queued requests into free slots, highest SLO class first —
        pure bookkeeping (slot allocation + chunk_off=0); their prompt
        chunks ride the next unified step alongside everyone else's
        decode rows."""
        with self._cond:
            while True:
                self._update_brownout_locked()
                if self.supervisor.open or self.pool.free_slots() == 0:
                    return
                req = self._pop_next_locked()
                if req is None:
                    return
                self.metrics.set_queue_depth(self._queue_len_locked())
                with RecordEvent(SPAN_REQUEST_ADMIT, rid=req.rid) as span:
                    slot = self._bind_row_locked(req, span)
                if slot is None:
                    return
                # per-slot sampling state (ISSUE 18): bind the request's
                # params + grammar/DFA row for the slot's lifetime
                self.sampling_table.bind(slot, req.sampling or GREEDY,
                                         gid=req.gid,
                                         dfa_state=req.dfa_state0)
                # multi-LoRA lane (ISSUE 20): point the slot's
                # adapter_idx at the request's bank row. The adapter may
                # have been unloaded between submit and admit — that is
                # a typed reject here, never a wrong-delta decode.
                if self.adapter_bank is not None:
                    try:
                        self.adapter_bank.bind_slot(slot, req.adapter)
                    except AdapterError as e:
                        self._conclude(req, "rejected:unknown_adapter")
                        req.handle.future.set_exception(RejectedError(
                            f"adapter {req.adapter!r} was unloaded before "
                            f"admission ({e})", reason="unknown_adapter"))
                        self.metrics.on_reject("unknown_adapter",
                                               tenant=req.tenant)
                        self._record_reject("unknown_adapter", rid=req.rid,
                                            tenant=req.tenant)
                        self._free_row_locked(req, slot)
                        continue
                # speculative decoding (ISSUE 17): give the request a row
                # in the draft pool. Exhaustion is not an error — the
                # request simply runs spec-off (plain decode is always
                # available and always correct). Grammar-constrained
                # requests (ISSUE 18) never speculate — their one
                # emission column per step is masked by a DFA state the
                # draft cannot see ahead of — so they skip the draft row
                # instead of pinning one idle.
                if self.draft_pool is not None and not self._spec_disabled \
                        and req.gid == 0:
                    try:
                        dslot = self.draft_pool.allocate(req.cost)
                    except SlotsExhaustedError:
                        dslot = None
                    if dslot is not None:
                        req.draft_slot = dslot
                        if self.draft_prefix_cache is not None:
                            # same max_tokens cap as the target acquire:
                            # both pools share block_len, so draft and
                            # target attach page-congruent prefixes and a
                            # warm hit skips the SAME token span on both
                            # sides
                            dplan = self.draft_prefix_cache.acquire(
                                req.tenant, req.prompt,
                                max_tokens=len(req.prompt) - 1)
                            if dplan.pages:
                                self.draft_pool.attach_blocks(
                                    dslot, dplan.pages)
                                req.draft_attached = list(dplan.pages)
                            if dplan.tail_page is not None:
                                self.draft_pool.cow_copy(dplan.tail_page,
                                                         dslot)
                            if dplan.attach_len:
                                # attached/COW'd draft KV is immediately
                                # valid: the draft starts its catch-up
                                # from here, not from token 0
                                self.draft_pool.set_length(
                                    dslot, dplan.attach_len)
                            self.draft_prefix_cache.release(dplan)
                self._active[slot] = req
                self.metrics.set_slots(self.pool.active_slots(),
                                       self.pool.num_slots)

    # ---- speculative decoding (ISSUE 17) ----
    def _stream_token(self, req: _GenRequest, i: int) -> int:
        """Token i of the request's true committed stream
        (prompt + emitted) — what draft catch-up replays."""
        plen = len(req.prompt)
        return int(req.prompt[i]) if i < plen else int(req.emitted[i - plen])

    def _draft_phase(self) -> Dict[int, List[int]]:
        """The pump's draft work, run BEFORE the target's unified step:
        one chunk-wide catch-up dispatch for rows whose draft KV trails
        the target's committed stream (prompt suffixes after admission /
        failover re-prefill, gap tokens after partial windows), then ONE
        proposal dispatch — the spec_k+1-step on-device scan — over every
        caught-up decode-ready row. Returns {target_slot: [d1..dK]}, the
        verify windows `_build_rows_locked` stitches into the unified
        step. Both dispatches announce kind "draft" and run
        breaker-exempt: any failure degrades this pump to plain decode
        (and quarantines the implicated request's DRAFT on attribution),
        never the streams."""
        if self.draft_pool is None or self._spec_disabled:
            return {}
        C = self.config.prefill_chunk
        K = self.config.spec_k
        dpool = self.draft_pool
        pad_pos = dpool.n_blocks * dpool.block_len
        N = dpool.num_slots

        # -- catch-up: replay committed stream tokens into the draft pool
        with self._cond:
            toks = np.zeros((N, C), np.int32)
            pos = np.full((N,), pad_pos, np.int32)
            adv = np.zeros((N,), np.int32)
            catchup: List[Tuple[int, _GenRequest, int, int, int]] = []
            for slot, req in self._active.items():
                ds = req.draft_slot
                if ds is None or req.spec_off:
                    continue
                tlen = int(self.pool.lengths[slot])
                dlen = int(dpool.lengths[ds])
                if dlen >= tlen:
                    continue
                n = min(C, tlen - dlen)
                for j in range(n):
                    toks[ds, j] = self._stream_token(req, dlen + j)
                pos[ds] = dlen
                adv[ds] = n
                catchup.append((slot, req, ds, dlen, n))
        if catchup:
            rids = tuple(sorted(r.submit_idx for _, r, _, _, _ in catchup))
            fn = self._draft_step()
            args = (self._draft_params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(adv), dpool.device_block_table(),
                    dpool.slabs)
            tdc0 = self.clock.now() if self.ledger is not None else None
            try:
                out, new_slabs = self._run_dispatch(
                    (("draft", rids),), fn, args, exempt=True)
            except DispatchFailedError as e:
                self._draft_failure(
                    [(s, r) for s, r, _, _, _ in catchup], e, "catchup")
                return {}
            if self.ledger is not None:
                # the launch span only: nothing waits for the catch-up's
                # result, so its execution is inside the next device span
                # the host does wait for (the proposal's fetch below, or
                # the unified step's)
                self.ledger.book_dispatch(
                    self.clock.now() - tdc0, prefill_positions=0,
                    decode_positions=0, total_positions=0,
                    owners=[(r.tenant, r.slo, n)
                            for _, r, _, _, n in catchup],
                    draft_positions=int(sum(n for *_, n in catchup)))
            dpool.slabs = new_slabs
            with self._cond:
                for slot, req, ds, dlen, n in catchup:
                    if self._active.get(slot) is not req \
                            or not dpool.active[ds]:
                        continue
                    dpool.set_length(ds, dlen + n)
                    plen = len(req.prompt)
                    if (self.draft_prefix_cache is not None
                            and dlen < plen <= dlen + n):
                        # the draft's prompt KV just completed: index it
                        # so shared-prefix siblings attach on the draft
                        # side too (page-congruent with the target cache)
                        self.draft_prefix_cache.insert(
                            req.tenant, req.prompt, ds, req.draft_attached)
            self._draft_failstreak = 0

        # -- proposal: ONE scan dispatch over caught-up decode-ready rows
        with self._cond:
            tok0 = np.zeros((N,), np.int32)
            ppos = np.full((N,), pad_pos, np.int32)
            act = np.zeros((N,), np.int32)
            # per-lane sampling operands, indexed by DRAFT slot (ISSUE
            # 18): the scan proposes on the same (seed, stream index)
            # lanes the target verify will draw on
            dtemp = np.ones((N,), np.float32)
            dtopk = np.zeros((N,), np.int32)
            dtopp = np.ones((N,), np.float32)
            dsamp = np.zeros((N,), bool)
            dseed = np.zeros((N,), np.int32)
            dctr = np.zeros((N,), np.int32)
            tab = self.sampling_table
            eligible: List[Tuple[int, _GenRequest, int, int]] = []
            for slot, req in self._active.items():
                ds = req.draft_slot
                if ds is None or req.spec_off or req.gid > 0:
                    continue
                if req.chunk_off < len(req.prompt):
                    continue            # still in chunked prefill
                L = int(self.pool.lengths[slot])
                if int(dpool.lengths[ds]) != L:
                    continue            # draft KV still catching up
                if req.max_new_tokens - len(req.emitted) < 2:
                    continue            # a window cannot beat one step
                if L + K + 1 > self.pool.capacity:
                    continue            # window would overrun the slot
                tok0[ds] = req.last_tok
                ppos[ds] = L
                act[ds] = 1
                dtemp[ds] = tab.temperature[slot]
                dtopk[ds] = tab.top_k[slot]
                dtopp[ds] = tab.top_p[slot]
                dsamp[ds] = tab.do_sample[slot]
                dseed[ds] = tab.seed[slot]
                dctr[ds] = req.sample_offset + len(req.emitted)
                eligible.append((slot, req, ds, L))
        if not eligible:
            return {}
        rids = tuple(sorted(r.submit_idx for _, r, _, _ in eligible))
        fn = self._draft_propose()
        args = (self._draft_params, jnp.asarray(tok0), jnp.asarray(ppos),
                jnp.asarray(act), dpool.device_block_table(), dpool.slabs,
                jnp.asarray(dtemp), jnp.asarray(dtopk), jnp.asarray(dtopp),
                jnp.asarray(dsamp), jnp.asarray(dseed), jnp.asarray(dctr))
        tdc0 = self.clock.now() if self.ledger is not None else None
        try:
            drafts_dev, new_slabs = self._run_dispatch(
                (("draft", rids),), fn, args, exempt=True)
        except DispatchFailedError as e:
            self._draft_failure([(s, r) for s, r, _, _ in eligible], e,
                                "propose")
            return {}
        dpool.slabs = new_slabs
        drafts = np.asarray(drafts_dev)     # the host waits here, armed or not
        if self.ledger is not None:
            self.ledger.book_dispatch(
                self.clock.now() - tdc0, prefill_positions=0,
                decode_positions=0, total_positions=0,
                owners=[(r.tenant, r.slo, K + 1)
                        for _, r, _, _ in eligible],
                draft_positions=(K + 1) * len(eligible))
        spec: Dict[int, List[int]] = {}
        with self._cond:
            for slot, req, ds, L in eligible:
                if self._active.get(slot) is not req \
                        or not dpool.active[ds]:
                    continue
                # the scan wrote K+1 stripes: last_tok @ L and d1..dK at
                # L+1..L+K (the final iteration feeds dK for exactly this
                # write), so after an all-accept window (commit L+K+1)
                # the draft needs NO catch-up dispatch
                dpool.set_length(ds, L + K + 1)
                spec[slot] = [int(t) for t in drafts[ds]]
        self._draft_failstreak = 0
        return spec

    def _draft_failure(self, rows, err, stage: str):
        """A draft dispatch failed after supervision (retries are not
        worth a latency optimization — one failure degrades the pump to
        plain decode). Attribution mirrors `_blame_and_quarantine` at
        draft scope: solo-probe each riding request with a width-1
        draft-kind dispatch; a blamed request's DRAFT is quarantined
        (spec_off + draft row freed) while its target stream continues
        bit-identically. Probes commit nothing — slabs are immutable and
        never assigned here. Unattributable failures count an
        engine-wide failstreak that disables spec at breaker_threshold;
        the target breaker is NEVER charged on any draft path."""
        dpool = self.draft_pool
        fn = self._draft_propose()
        N = dpool.num_slots
        blamed = []
        for slot, req in rows:
            ds = req.draft_slot
            if ds is None:
                continue
            tok0 = np.zeros((N,), np.int32)
            act = np.zeros((N,), np.int32)
            tok0[ds] = req.last_tok
            act[ds] = 1
            # probe at pos=0: the result is discarded and never
            # committed, so clobber-free addressing is all that matters
            # — neutral greedy lanes keep the probe deterministic
            args = (self._draft_params, jnp.asarray(tok0),
                    jnp.asarray(np.zeros((N,), np.int32)),
                    jnp.asarray(act), dpool.device_block_table(),
                    dpool.slabs,
                    jnp.asarray(np.ones((N,), np.float32)),
                    jnp.asarray(np.zeros((N,), np.int32)),
                    jnp.asarray(np.ones((N,), np.float32)),
                    jnp.asarray(np.zeros((N,), bool)),
                    jnp.asarray(np.zeros((N,), np.int32)),
                    jnp.asarray(np.zeros((N,), np.int32)))
            try:
                self._run_dispatch((("draft", (req.submit_idx,)),), fn,
                                   args, exempt=True)
            except DispatchFailedError as probe_err:
                blamed.append((slot, req, probe_err))
                flight_recorder().record(
                    "solo_probe", engine="llm", rid=req.rid,
                    submit_idx=req.submit_idx, stage="draft",
                    outcome="failed")
            else:
                flight_recorder().record(
                    "solo_probe", engine="llm", rid=req.rid,
                    submit_idx=req.submit_idx, stage="draft", outcome="ok")
        if blamed and (len(blamed) < len(rows) or len(rows) == 1):
            with self._cond:
                for slot, req, probe_err in blamed:
                    if self._active.get(slot) is not req:
                        continue
                    req.spec_off = True
                    ds = req.draft_slot
                    if ds is not None and dpool.active[ds]:
                        dpool.free(ds)
                    req.draft_slot = None
                    self.metrics.on_draft_quarantine()
                    flight_recorder().record(
                        "draft_quarantine", engine="llm", rid=req.rid,
                        submit_idx=req.submit_idx, stage=stage,
                        reason="poisoned_draft", error=str(probe_err))
            _log.warning(
                "quarantined the DRAFT of %d request(s) after a poisoned "
                "%s dispatch; their streams continue as plain decode",
                len(blamed), stage)
            return
        self._draft_failstreak += 1
        flight_recorder().record(
            "draft_failure", engine="llm", stage=stage,
            failstreak=self._draft_failstreak, error=str(err))
        if self._draft_failstreak >= self.config.breaker_threshold:
            self._spec_disabled = True
            flight_recorder().record(
                "draft_disabled", engine="llm",
                failstreak=self._draft_failstreak)
            _log.error(
                "disabling speculative decoding after %d consecutive "
                "unattributable draft dispatch failures; the engine "
                "continues on plain decode", self._draft_failstreak)

    def _acceptance_locked(self, decode_rows, spec_drafts,
                           nxt) -> Dict[int, Tuple[List[int], int, int]]:
        """Greedy verification over the step's per-position tokens:
        for each decode row, walk the longest prefix of its draft window
        matching the target's own argmaxes, then take the target's one
        corrective token — truncated by the request's EOS / max-tokens
        caps exactly where sequential decode would stop. Returns
        {slot: (emit_tokens, accepted_draft_count, drafted_count)}; a
        plain decode row (no drafts) degenerates to ([next_token], 0, 0),
        which is precisely the pre-spec commit. `decode_rows` maps each
        decode slot to the request its row belongs to, None where that
        request no longer holds the slot."""
        accept: Dict[int, Tuple[List[int], int, int]] = {}
        for slot, req in decode_rows.items():
            if req is None:
                continue
            drafts = spec_drafts.get(slot, ())
            row = nxt[slot]
            k = len(drafts)
            a = 0
            while a < k and int(row[a]) == int(drafts[a]):
                a += 1
            emit_toks: List[int] = []
            for j in range(a + 1):
                tok = int(row[j])
                emit_toks.append(tok)
                if len(req.emitted) + len(emit_toks) >= req.max_new_tokens:
                    break
                if req.eos_token_id is not None \
                        and tok == req.eos_token_id:
                    break
            accept[slot] = (emit_toks, min(len(emit_toks), a), k)
        return accept

    def _build_rows_locked(self, spec_drafts=None, ahead_of=None):
        """Assemble the unified step's host-side row set from the active
        table: (toks [N, C], pos [N], adv [N], ctr [N], prefill_slots,
        decode_slots, deferred, feed [N]). Free slots stay all-zero
        (adv=0 → fully masked). A decode row with a draft window (ISSUE
        17) carries [last_tok, d1..dk] at adv=1+k — the verify chunk;
        plain decode rows stay [last_tok] at adv=1.

        The step computes `step_tokens` positions, so `sum(adv)` stays
        within them: a prefill row whose chunk no longer fits waits this
        step as a free slot does (adv=0, stripe in the pad region; not
        in `prefill_slots`, counted in `deferred`) and rides a later one
        with the same chunk, so a request's chunk boundaries, and with
        them its arithmetic, do not depend on its neighbours. The oldest
        prefill row always fits: nobody starves.

        `ctr` (ISSUE 18) is each row's RNG-lane stream index for column
        0: decode rows sit at `sample_offset + emitted` (column t draws
        stream token index ctr+t); prefill rows back the base off by
        adv-1 so the emission column adv-1 lands exactly on the first
        emitted token's index — the earlier columns' draws are discarded
        with their logits, negative intermediate indices fold_in as
        harmless uint32 bit-casts.

        `ahead_of` is the step in flight when this one is built before
        that one is retired. A request that rides it is read as that step
        WILL commit it, all of which is known for a plain row: its
        position and prefilled offset advance by the row's `adv`, and a
        decode row, or a prefill row whose last chunk rode, has emitted
        one token more. Which token is the one thing the host does not
        know: `feed[slot]` names the column of that step's selections
        the device takes it from (-1 everywhere else: the host's
        `toks[:, 0]` stands). A request whose cap that token reaches is
        not scheduled again. The committed fields stay what
        `_commit_step` wrote."""
        N = self.pool.num_slots
        C = self.config.prefill_chunk
        toks = np.zeros((N, C), np.int32)
        ctr = np.zeros((N,), np.int32)
        feed = np.full((N,), -1, np.int32)
        # free rows still get a (discarded) C-wide KV stripe written at
        # their pos by the unified step; park it in the slab's pad region
        # (block tables never address cols >= n_blocks*block_len) so it
        # cannot clobber cached prefix pages living in freed rows
        pos = np.full((N,), self.pool.n_blocks * self.pool.block_len,
                      np.int32)
        adv = np.zeros((N,), np.int32)
        prefill_slots: List[int] = []
        decode_slots: List[int] = []
        waiting: List[Tuple[int, int, int]] = []
        for slot, req in self._active.items():
            off, emitted = req.chunk_off, len(req.emitted)
            length = int(self.pool.lengths[slot])
            col = -1
            if ahead_of is not None and ahead_of.reqs.get(slot) is req:
                was_prefill = off < len(req.prompt)
                off = length = int(ahead_of.pos[slot] + ahead_of.adv[slot])
                if not was_prefill or off >= len(req.prompt):
                    emitted += 1
                    col = int(ahead_of.adv[slot]) - 1
                    if emitted >= req.max_new_tokens:
                        continue    # its last token is in flight
            if off < len(req.prompt):
                waiting.append((slot, off, emitted))
                continue
            drafts = (spec_drafts.get(slot, ())
                      if spec_drafts else ())
            if col < 0:
                toks[slot, 0] = req.last_tok
            feed[slot] = col
            for j, d in enumerate(drafts):
                toks[slot, 1 + j] = d
            pos[slot] = length
            adv[slot] = 1 + len(drafts)
            ctr[slot] = req.sample_offset + emitted
            decode_slots.append(slot)
        # the decode rows always fit (`step_tokens`' first term); the
        # prefill rows take what is left, oldest admitted first (`_active`
        # keeps admission order), each its whole chunk or none of it
        budget = self.step_tokens - int(adv.sum())
        deferred = 0
        now = None
        for slot, off, emitted in waiting:
            req = self._active[slot]
            n = min(C, len(req.prompt) - off)
            if n > budget:
                deferred += 1
                continue
            budget -= n
            toks[slot, :n] = req.prompt[off:off + n]
            pos[slot] = off
            adv[slot] = n
            ctr[slot] = req.sample_offset + emitted - (n - 1)
            prefill_slots.append(slot)
            # the request's stamps: `bound` ends with the first step that
            # carries a chunk of it, `prefill` with the one that carries
            # its last (the same step for a prompt of one chunk)
            if req.first_launch is None:
                now = self.clock.now() if now is None else now
                req.first_launch = now
                bound_ms = (now - req.admitted) * 1e3
                if req.trace is not None:
                    req.trace.mark("first_launch", now)
                with RecordEvent(
                        SPAN_REQUEST_FIRST_LAUNCH, rid=req.rid,
                        step=self.unified_steps + (ahead_of is not None),
                        bound_ms=bound_ms):
                    pass
            if req.final_launch is None and off + n >= len(req.prompt):
                now = self.clock.now() if now is None else now
                req.final_launch = now
                if req.trace is not None:
                    req.trace.mark("final_launch", now)
        return (toks, pos, adv, ctr, prefill_slots, decode_slots, deferred,
                feed)

    def _kinds_of(self, prefill_slots, decode_slots) -> Tuple:
        """(kind, request_ids) announcement order for fault injection:
        prefill rows first, then decode rows, both at one dispatch idx.
        Rows riding an adapter (ISSUE 20) additionally announce kind
        "adapter" at the SAME index, so a `poison_request@rid:adapter`
        clause scopes a fault to one adapter's streams without touching
        co-scheduled base/other-adapter rows."""
        kinds = []
        if prefill_slots:
            kinds.append(("prefill", tuple(sorted(
                self._active[s].submit_idx for s in prefill_slots))))
        if decode_slots:
            kinds.append(("decode", tuple(sorted(
                self._active[s].submit_idx for s in decode_slots))))
        adapter_rows = [s for s in list(prefill_slots) + list(decode_slots)
                        if self._active[s].adapter]
        if adapter_rows:
            kinds.append(("adapter", tuple(sorted(
                self._active[s].submit_idx for s in adapter_rows))))
        return tuple(kinds)

    def _can_launch_ahead(self, rec: _StepInFlight) -> bool:
        """Whether the step after `rec` can be built before `rec` is
        fetched: every row of `rec` must commit what `_build_rows_locked`
        projects for it. Not so where a draft model proposes (the draft
        phase replays committed streams, and a window's accepted length
        is the step's result), nor for a grammar row (its DFA state is
        committed through the sampling table, whose device operands the
        commit invalidates). An engine that books device time per step
        (`ledger`, `observatory`: launch to the end of the fetch) keeps
        one step at a time, so that what it books stays one step's."""
        if self.ledger is not None or self.observatory is not None:
            return False
        if self.draft_pool is not None and not self._spec_disabled:
            return False
        return not any(req.gid for req in rec.reqs.values())

    def _launch(self, ahead_of: Optional[_StepInFlight] = None,
                failed: Optional[DispatchFailedError] = None
                ) -> Optional[_StepInFlight]:
        """Build and dispatch ONE unified mixed prefill+decode step over
        every slot, and return it in flight (None when no row rides).
        Nothing here waits for the device.

        `ahead_of` is the unretired predecessor when this step is
        launched before that one is fetched: rows come from its
        projection (`_build_rows_locked`), each row's input token from
        its selections on the device, and a failed dispatch raises
        `DispatchFailedError` at once — the caller retires the
        predecessor and calls again with `failed` set, which takes this
        step's retry and blame protocol up synchronously at its second
        attempt. Without it this is the synchronous launch: rows from the
        committed state, retries, then blame and quarantine.

        The step is donated `pool.slabs`: a failed dispatch that left
        them whole is retried on the same operands, one that took them is
        never retried (`_pool_lost`).

        With a draft model attached (ISSUE 17) the draft phase runs
        first: decode rows carry verify windows [last_tok, d1..dK]
        instead of a lone token, and the commit takes the longest
        target-matching draft prefix plus the corrective token — up to
        K+1 tokens per row from the SAME single dispatch, bit-identical
        to plain greedy decode. Quarantine retries reuse this pump's
        windows: a failed dispatch commits nothing, so the surviving
        rows' positions — and therefore their drafts — are unchanged."""
        if failed is not None and self._pool_gone(failed):
            return self._pool_lost(1, failed)
        spec_drafts = {}
        if self.draft_pool is not None and not self._spec_disabled:
            with RecordEvent(SPAN_SERVE_DRAFT):
                spec_drafts = self._draft_phase()
        first_attempt = 0 if failed is None else 1
        while True:
            with RecordEvent(SPAN_SERVE_BUILD_ROWS), self._cond:
                if not self._active:
                    return None
                toks, pos, adv, ctr, prefill_slots, decode_slots, \
                    deferred, feed = self._build_rows_locked(spec_drafts,
                                                             ahead_of)
                if not (prefill_slots or decode_slots):
                    return None     # every row ends with the step in flight
                reqs = {s: self._active[s]
                        for s in prefill_slots + decode_slots}
                # what the step leaves on the table while somebody waits:
                # slots that carry no row in it (free, or their request's
                # last token is in flight; a deferred row's slot is taken),
                # as far as requests are queued for them. A slot freed at
                # `_retire(k)`, after `_launch(k+1)`, counts one
                vacant_queued = min(
                    self._queue_len_locked(),
                    self.pool.num_slots - len(reqs) - deferred)
                kinds = self._kinds_of(prefill_slots, decode_slots)
                # rows of this step that draw: what the step's sampler
                # branches on (a freed slot is cleared to greedy, so the
                # active rows are all that can sample)
                sampled_rows = int(np.count_nonzero(
                    self.sampling_table.do_sample[
                        prefill_slots + decode_slots]))
                # sampling-operand assembly (ISSUE 18) — per-slot params,
                # RNG-lane counters, DFA states and the grammar bank —
                # is the host-side cost of constrained/sampled decoding,
                # metered as pdtpu_llm_sample_mask_overhead_ms
                ts0 = self.clock.now()
                sargs = self._sampling_args_locked(ctr)
                mask_dt = self.clock.now() - ts0
                aargs = self._tail_args_locked()
            self.metrics.on_mask_overhead(mask_dt * 1e3)
            if self.ledger is not None:
                self.ledger.book("sample_mask", mask_dt)
            # positions of this step that hold a real token, of the
            # `step_tokens` it computes: all a dense model wastes on the
            # rest is arithmetic, a sparse one must keep them out of its
            # experts
            live_tokens = int(adv.sum())
            # rows with one live column: the paged kernels run their groups
            # over that column alone (`ops/paged_attention.py`)
            one_column = int(np.count_nonzero(adv == 1))
            span_args = dict(prefill_rows=len(prefill_slots),
                             decode_rows=len(decode_slots),
                             sampled_rows=sampled_rows,
                             live_tokens=live_tokens,
                             one_column_rows=one_column,
                             step_tokens=self.step_tokens,
                             deferred_rows=deferred,
                             in_flight=int(ahead_of is not None),
                             slots_vacant_queued=vacant_queued)
            kind_args, started, kv_tokens = self.pool.step_counts(pos, adv)
            span_args.update(kind_args)
            sparse_keys = None
            if self._sparse is not None:
                # per live query position p of a row: the keys it can see
                # (p + 1) and of them the keys a sparse layer attends to
                topk, n_full, n_shared = self._sparse
                cols = np.arange(adv.max(initial=0))
                seen = (pos[:, None] + cols + 1)[cols < adv[:, None]]
                sparse_keys = (int(np.minimum(seen, topk).sum()),
                               int(seen.sum()), n_full, n_shared)
                span_args["sparse_rows"] = int(np.count_nonzero(adv))
            with RecordEvent(SPAN_SERVE_DISPATCH, **span_args):
                t0 = self.clock.now()
                fn = self._step()
                args = (self.params, jnp.asarray(toks), jnp.asarray(pos),
                        jnp.asarray(adv), self.pool.device_block_table(),
                        self.pool.slabs) + sargs \
                    + self._feedback_args(feed, ahead_of) + aargs
                if self.observatory is not None:
                    self.observatory.observe_call("llm/unified_step", fn,
                                                  args)
                attempts = self.config.dispatch_retries + 1
                last_err = failed
                nxt = None
                tc0 = None
                for attempt in range(first_attempt, attempts):
                    if self.ledger is not None or self.observatory is not None:
                        # the start of the dispatch's device span, after the
                        # operand uploads and `observe_call`: launch to the
                        # end of `fetch` in `_retire`, where np.asarray has
                        # already waited for the device (such an engine never
                        # launches ahead). Nothing synchronises for
                        # the ledger's or the observatory's sake, so an
                        # armed engine runs the default engine's host
                        # sequence. Re-armed per attempt: a failed round's
                        # wall time stays in the host phase.
                        tc0 = self.clock.now()
                    try:
                        # the pool's buffers go in and come back as the
                        # result: no reader sees them half way
                        with self.pool.slabs_lock:
                            nxt, lps, new_dstate, self.pool.slabs, \
                                *moe_out = self._dispatch_step(kinds, fn,
                                                               args)
                    except DispatchFailedError as e:
                        last_err = e
                        self.metrics.on_dispatch_failure(e.reason)
                        flight_recorder().record(
                            "dispatch_retry", engine="llm",
                            attempt=attempt + 1, attempts=attempts,
                            reason=e.reason,
                            prefill_rows=len(prefill_slots),
                            decode_rows=len(decode_slots))
                        _log.warning(
                            "unified step dispatch failed over %d prefill "
                            "+ %d decode row(s) (attempt %d/%d): %s",
                            len(prefill_slots), len(decode_slots),
                            attempt + 1, attempts, e)
                        if ahead_of is not None:
                            raise   # retire the predecessor first
                        if self._pool_gone(e):
                            return self._pool_lost(attempt + 1, e)
                        continue    # on the same `args`: the pool is whole
                    if moe_out:
                        # committed with the step, like the slabs: a failed
                        # attempt or a blame probe counts nowhere
                        self._moe_totals, = moe_out
                        self._moe_routed += live_tokens
                    self.metrics.on_step_tokens(live_tokens,
                                                self.step_tokens, deferred,
                                                vacant_queued,
                                                self._attn_positions,
                                                *self._attn_heads,
                                                self._head_positions)
                    self.metrics.on_paged_rows(
                        one_column, int(np.count_nonzero(adv > 1)))
                    if started:
                        self.metrics.on_recurrent_rows_started(started)
                    if self.pool.recurrent:
                        matrix = 0 if self._matrix_columns is None else int(
                            np.count_nonzero(adv >= self._matrix_columns))
                        self.metrics.on_recurrent_rows(
                            matrix, kind_args["recurrent_rows"] - matrix)
                    self.metrics.on_kv_tokens(*kv_tokens)
                    if sparse_keys is not None:
                        self.metrics.on_sparse_keys(*sparse_keys)
                    if ahead_of is not None:
                        self.metrics.on_step_overlapped()
                    if decode_slots:
                        # the breaker tracks ENGINE-level (decode-protocol)
                        # failures; prefill-only successes must not launder
                        # a failure streak between decode attempts
                        self.supervisor.record_success()
                    break
                else:
                    first_attempt = 0
                    if self._blame_and_quarantine(fn, toks, pos, adv, ctr,
                                                  last_err):
                        continue    # survivors retry on a rebuilt row set
                    self._fail_all_active(attempts, last_err)
                    self.supervisor.record_failure()
                    return None
            return _StepInFlight(
                nxt=nxt, lps=lps, new_dstate=new_dstate, pos=pos, adv=adv,
                prefill_slots=prefill_slots,
                decode_slots=decode_slots, reqs=reqs,
                spec_drafts=spec_drafts, sampled_rows=sampled_rows, t0=t0,
                tc0=tc0)

    def _retire(self, rec: _StepInFlight) -> int:
        """Fetch one launched step's results (the only place the host
        waits for the device) and commit them. Returns 1 when the step
        carried at least one decode row (the decode-iteration count the
        continuous-batching invariants pin), else 0."""
        with RecordEvent(SPAN_SERVE_FETCH):
            # jit dispatch is async: these conversions are where the
            # host waits for the device
            nxt = np.asarray(rec.nxt)   # [N, C] per-position tokens
            lps = np.asarray(rec.lps)   # [N, C] per-position logprobs
            new_dstate = np.asarray(rec.new_dstate)  # [N] DFA states
        if self._first_step_span is not None:
            self._first_step_span.end()
            self._first_step_span = None
        now = self.clock.now()
        with RecordEvent(SPAN_SERVE_COMMIT):
            if rec.sampled_rows:
                self.metrics.on_sampler_filter_step()
            return self._commit_step(rec, nxt, lps, new_dstate, now)

    def _retire_in_flight(self):
        """Retire the unretired step, if any, from the caller's thread:
        for callers that end or empty the engine from outside a pump pass,
        where no scheduler thread can be inside one."""
        rec, self._inflight = self._inflight, None
        if rec is not None:
            self._retire(rec)

    def _commit_step(self, rec: _StepInFlight, nxt, lps, new_dstate,
                     now: float) -> int:
        """Commit one fetched unified step: draft acceptance, the
        ledger's/observatory's booking of the device span `now - tc0`
        (launch to fetch end; `tc0` is None on an engine that arms
        neither), emission, retire, finish. `now` is the engine clock at
        the end of the fetch.

        A row is committed to the request that rode it, and only while
        that request still holds the slot (`bound`): a request that ended
        after the step was launched (EOS, a deadline, a grammar's end,
        `evacuate()`) has its row discarded, whoever holds the slot by
        now (`rows_discarded`). What the row wrote lies past every
        committed length of its slot."""
        pos, adv = rec.pos, rec.adv
        prefill_slots, decode_slots = rec.prefill_slots, rec.decode_slots
        tc0 = rec.tc0

        def bound(slot):
            req = rec.reqs[slot]
            return req if self._active.get(slot) is req else None

        with self._cond:
            accept = self._acceptance_locked(
                {s: bound(s) for s in decode_slots}, rec.spec_drafts, nxt)
        if self.ledger is not None or self.observatory is not None:
            if self.ledger is not None:
                with self._cond:
                    riding = [(bound(s), int(adv[s])) for s in prefill_slots]
                    owners = [(req.tenant, req.slo, n)
                              for req, n in riding if req is not None]
                    adapter_owners = [(req.adapter or "base", n)
                                      for req, n in riding
                                      if req is not None]
                    decode_useful = drafted = accepted = 0
                    for s in decode_slots:
                        req = bound(s)
                        if req is None or s not in accept:
                            continue
                        emit_toks, acc, k = accept[s]
                        owners.append((req.tenant, req.slo,
                                       len(emit_toks)))
                        adapter_owners.append((req.adapter or "base",
                                               len(emit_toks)))
                        decode_useful += len(emit_toks)
                        drafted += k
                        accepted += acc
                # a verify row's rejected columns stay inside
                # total_positions but out of the useful decode count:
                # wasted draft positions surface as pad-waste in
                # token_efficiency, exactly like prefill padding.
                # adapter_owners (ISSUE 20) re-buckets the SAME
                # per-row shares by adapter id, so per-adapter
                # device-seconds sum exactly to the tenant total.
                self.ledger.book_dispatch(
                    now - tc0,
                    prefill_positions=int(sum(adv[s]
                                              for s in prefill_slots)),
                    decode_positions=decode_useful,
                    total_positions=self.step_tokens,
                    owners=owners,
                    drafted=drafted, draft_accepted=accepted,
                    adapter_owners=(adapter_owners
                                    if self.adapter_bank is not None
                                    else None))
            if self.observatory is not None:
                # the fetch already waited for the result, so the span is
                # launch + execution — attribute it to this call site's
                # latest executable (ISSUE 12)
                self.observatory.note_device_seconds(
                    "llm/unified_step", now - tc0)
        with self._cond:
            n_decode = len(decode_slots)
            if n_decode:
                self.decode_iterations += 1
            elif prefill_slots:
                self.prefill_dispatches += 1
            discarded = 0
            for slot in prefill_slots:
                # the request may have ended, and the slot gone to another,
                # since the row was built
                req = bound(slot)
                if req is None:
                    discarded += 1
                    continue
                n = int(adv[slot])
                off = req.chunk_off
                self.pool.set_length(slot, off + n)
                req.chunk_off = off + n
                self.prefill_tokens += n
                req.chunks += 1
                if req.trace is not None:
                    req.trace.event("prefill_chunk", now, off=off, n=n)
                if req.chunk_off >= len(req.prompt):
                    # final chunk landed: first token emitted, TTFT
                    # ends here
                    req.first_token = now
                    req.handle.ttft_ms = (now - req.arrival) * 1e3
                    if req.trace is not None:
                        # same instant as ttft_ms, so the trace's TTFT
                        # boundary reconciles with the handle exactly
                        req.trace.mark("first_token", now)
                    self.metrics.on_prefill(req.handle.ttft_ms,
                                            slo=req.slo)
                    # where that time went: the stamps' four phases,
                    # and the steps committed since a slot was bound
                    # (this one counted above)
                    phases = req.handle.ttft_phases_ms = {
                        "queued_ms": (req.admitted - req.arrival) * 1e3,
                        "bound_ms":
                            (req.first_launch - req.admitted) * 1e3,
                        "prefill_ms":
                            (req.final_launch - req.first_launch) * 1e3,
                        "first_fetch_ms": (now - req.final_launch) * 1e3}
                    steps = req.handle.steps_to_first_token = \
                        self.unified_steps - req.admit_step
                    self.metrics.on_first_token(*phases.values(), steps)
                    if self.burn is not None:
                        target = (self.config.slo_ttft_target_ms
                                  or {}).get(req.slo)
                        self.burn.observe(
                            req.slo,
                            target is None
                            or req.handle.ttft_ms <= target,
                            outcome="ttft")
                    if self.prefix_cache is not None:
                        # index the completed prefill while the slot
                        # is still active: siblings queued behind it
                        # attach without waiting for it to finish
                        self.prefix_cache.insert(
                            self._kv_ns(req.tenant, req.adapter),
                            req.prompt, slot, req.attached_pages)
                    with RecordEvent(SPAN_REQUEST_FIRST_TOKEN, rid=req.rid,
                                     step=self.unified_steps - 1,
                                     ttft_ms=req.handle.ttft_ms, **phases,
                                     chunks=req.chunks,
                                     steps_to_first_token=steps):
                        self._emit(req, int(nxt[slot, int(adv[slot]) - 1]),
                                   float(lps[slot, int(adv[slot]) - 1]))
                    if req.gid:
                        # first constrained emission: commit the DFA
                        # state advanced in-step past that token
                        self.sampling_table.set_dfa_state(
                            slot, int(new_dstate[slot]))
                    if self._finish_if_done(req, now):
                        del self._active[slot]
                    elif req.deadline is not None and now >= req.deadline:
                        self._evict_expired_locked(req, slot, now)
                elif req.deadline is not None and now >= req.deadline:
                    # mid-prefill eviction: no tokens yet, but the slot
                    # must not keep absorbing chunk work
                    self._evict_expired_locked(req, slot, now)
            total_emitted = 0
            for slot in decode_slots:
                req = bound(slot)
                if req is None or slot not in accept:
                    discarded += 1  # ended after launch, or evacuated
                    continue
                emit_toks, acc, k = accept[slot]
                L = int(pos[slot])
                # the verify wrote KV for every consumed column, but
                # only the accepted prefix + corrective token is
                # committed: lengths/block tables never cover the
                # rejected tail, so the pool's garbage-past-length
                # invariant IS the rollback
                self.pool.set_length(slot, L + len(emit_toks))
                if self.draft_pool is not None \
                        and req.draft_slot is not None \
                        and self.draft_pool.active[req.draft_slot]:
                    # the draft ran ahead on its own proposals; rewind
                    # its tables to the verified stream so the next
                    # window extends truth, not rejected speculation
                    dlen = int(self.draft_pool.lengths[req.draft_slot])
                    self.draft_pool.rewind_length(
                        req.draft_slot,
                        min(dlen, L + len(emit_toks)))
                if req.trace is not None:
                    ev = dict(tok=int(emit_toks[-1]),
                              n_active=len(decode_slots))
                    if k:
                        ev.update(drafted=k, accepted=acc)
                    req.trace.event("decode_step", now, **ev)
                for j, tok in enumerate(emit_toks):
                    self._emit(req, tok, float(lps[slot, j]))
                if req.gid:
                    # constrained rows never speculate (one emission
                    # per step), so the in-step advanced state is
                    # exactly the post-emission state
                    self.sampling_table.set_dfa_state(
                        slot, int(new_dstate[slot]))
                total_emitted += len(emit_toks)
                if k:
                    self.spec_windows += 1
                    self.spec_drafted += k
                    self.spec_accepted += acc
                    self.metrics.on_spec_window(k, acc)
                if self._finish_if_done(req, now):
                    del self._active[slot]
                elif req.deadline is not None and now >= req.deadline:
                    self._evict_expired_locked(req, slot, now)
            self.metrics.set_slots(self.pool.active_slots(),
                                   self.pool.num_slots)
        if discarded:
            self.metrics.on_rows_discarded(discarded)
        # the time this step added: from its launch, or from the previous
        # step's retire where it was launched before that (then the wait
        # since launch covers its predecessor's run too)
        step_ms = (now - max(rec.t0, self._retired_at)) * 1e3
        self._retired_at = now
        if n_decode:
            self.metrics.on_decode_step(n_decode, step_ms,
                                        tokens=total_emitted)
            return 1
        if prefill_slots:
            self.metrics.on_prefill_step()
        return 0

    def _evict_expired_locked(self, req: _GenRequest, slot: int,
                              now: float):
        """Deadline eviction of an active row (mid-prefill or mid-decode):
        partial tokens stay readable on the handle; the future fails with
        the deadline error."""
        stage = ("mid-prefill" if req.chunk_off < len(req.prompt)
                 else "mid-decode")
        self._conclude(req, f"expired:{stage}", now)
        req.handle.future.set_exception(DeadlineExceededError(
            f"deadline expired after {len(req.emitted)} of "
            f"{req.max_new_tokens} tokens (evicted {stage})"))
        self.metrics.on_expire()
        if self.burn is not None:
            self.burn.observe(req.slo, False, outcome="deadline")
        self._free_row_locked(req, slot)
        del self._active[slot]

    def _blame_and_quarantine(self, fn, toks, pos, adv, ctr,
                              last_err) -> bool:
        """Step retries exhausted: probe each active request in ISOLATION
        — the same fixed-width dispatch with every other row masked to
        (toks=0, pos=0, adv=0), announced as that single request's kind
        ("prefill" for a row still in chunked prefill, "decode"
        otherwise) — and quarantine the rows whose solo presence
        reproduces the failure. Probe results are never committed (a
        probe is donated a COPY of the pool, `pool_copies`, and its result
        is dropped; only a successful full step assigns pool.slabs), so
        survivors' streams stay bit-identical to a fault-free run —
        including decode rows co-scheduled with a request poisoned in
        prefill chunk k>0, which lose nothing but the failed step's wall
        time.

        When EVERY probe of a multi-row batch fails, the failure is not
        attributable to any one request — that is an engine-level fault
        and the breaker, not quarantine, must own it. A single-row batch
        whose probe fails is quarantined: the dispatch contained exactly
        that request, which is as exact as attribution gets."""
        with self._cond:
            suspects = list(self._active.items())
        blamed = []
        for slot, req in suspects:
            solo_toks = np.zeros_like(toks)
            solo_pos = np.zeros_like(pos)
            solo_adv = np.zeros_like(adv)
            solo_ctr = np.zeros_like(ctr)
            solo_toks[slot] = toks[slot]
            solo_pos[slot] = pos[slot]
            solo_adv[slot] = adv[slot]
            solo_ctr[slot] = ctr[slot]
            kind = ("prefill" if req.chunk_off < len(req.prompt)
                    else "decode")
            with self._cond:
                # probe with the REAL sampling operands: a poisoning that
                # only reproduces under the row's grammar mask or sampled
                # lane must still be attributable — and (ISSUE 20) with
                # the REAL adapter operands, so an adapter-scoped fault
                # reproduces in isolation too
                sargs = self._sampling_args_locked(solo_ctr)
                aargs = self._tail_args_locked()
            self.metrics.on_pool_copy()
            args = (self.params, jnp.asarray(solo_toks),
                    jnp.asarray(solo_pos), jnp.asarray(solo_adv),
                    self.pool.device_block_table(),
                    jax.tree.map(jnp.copy, self.pool.slabs)) + sargs \
                + self._feedback_args() + aargs
            probe_kinds = [(kind, (req.submit_idx,))]
            if req.adapter:
                # the solo probe must announce the same adapter kind the
                # full step did, or an adapter-keyed clause could not
                # reproduce and the fault would look unattributable
                probe_kinds.append(("adapter", (req.submit_idx,)))
            try:
                self._run_dispatch(tuple(probe_kinds), fn, args)
            except DispatchFailedError as e:
                blamed.append((slot, req, e))
                flight_recorder().record(
                    "solo_probe", engine="llm", rid=req.rid,
                    submit_idx=req.submit_idx, stage=kind,
                    outcome="failed")
            else:
                flight_recorder().record(
                    "solo_probe", engine="llm", rid=req.rid,
                    submit_idx=req.submit_idx, stage=kind, outcome="ok")
        if not blamed or (len(blamed) == len(suspects) and len(suspects) > 1):
            return False
        with self._cond:
            for slot, req, e in blamed:
                if slot not in self._active:
                    continue
                self._conclude(req, "quarantined")
                req.handle.future.set_exception(DispatchFailedError(
                    f"request {req.submit_idx} quarantined: its rows "
                    f"reproduce the decode failure in isolation ({e})",
                    reason="poisoned"))
                self.metrics.on_fail()
                self.metrics.on_quarantine()
                flight_recorder().record(
                    "quarantine", engine="llm", rid=req.rid,
                    submit_idx=req.submit_idx, reason="poisoned",
                    tokens_emitted=len(req.emitted))
                self._free_row_locked(req, slot)
                del self._active[slot]
            self.metrics.set_slots(self.pool.active_slots(),
                                   self.pool.num_slots)
        self.supervisor.absolve()
        _log.warning("quarantined %d poisoned request(s); retrying the "
                     "unified step with %d survivor(s)", len(blamed),
                     len(suspects) - len(blamed))
        return True

    def _pool_gone(self, err: DispatchFailedError) -> bool:
        """Whether the dispatch that failed with `err` took the pool, or
        (a call the watchdog abandoned) may take it yet."""
        return err.abandoned or self.pool.consumed()

    def _pool_lost(self, attempts: int, err) -> None:
        """A dispatch consumed the pool and failed: the active rows' K/V
        and state are gone with it. They fail, what the prefix cache
        pinned is dropped, the pool starts again from zeros of the same
        shapes and the breaker is charged; queued requests are served
        from the fresh pool."""
        self.metrics.on_pool_lost()
        _log.error("a unified step took the K/V pool and failed (%s): "
                   "failing the active requests, zeroing the pool", err)
        self._fail_all_active(attempts, err)
        with self._cond:
            if self.prefix_cache is not None:
                self.prefix_cache.clear()
        self.pool.reset_slabs()
        self.supervisor.record_failure()

    def _fail_all_active(self, attempts: int, last_err):
        """Non-attributable step failure: fail every active request with
        a typed error (partial tokens stay readable), free their slots,
        and let the caller charge the circuit breaker."""
        with self._cond:
            n_failed = len(self._active)
            for slot, req in list(self._active.items()):
                self._conclude(req, "failed:engine")
                req.handle.future.set_exception(DispatchFailedError(
                    f"decode dispatch failed {attempts} consecutive times; "
                    f"{len(req.emitted)} of {req.max_new_tokens} tokens "
                    f"emitted ({last_err})", reason="engine"))
                self.metrics.on_fail()
                # observed BEFORE the caller charges the breaker, so a
                # burn-rate crossing lands in the flight ring ahead of
                # the breaker_open event it predicts
                if self.burn is not None:
                    self.burn.observe(req.slo, False,
                                      outcome="engine_failure")
                self._free_row_locked(req, slot)
            self._active.clear()
            self.metrics.set_slots(self.pool.active_slots(),
                                   self.pool.num_slots)
        flight_recorder().record(
            "engine_failure", engine="llm", failed=n_failed,
            attempts=attempts, error=str(last_err))

    def _emit(self, req: _GenRequest, tok: int,
              lp: Optional[float] = None):
        req.emitted.append(tok)
        req.last_tok = tok
        req.handle._append(tok, lp if req.want_logprobs else None)
        if req.gid > 0:
            self.metrics.on_sample_token("constrained")
        elif req.sampling is not None and req.sampling.do_sample:
            self.metrics.on_sample_token("sampled")
        if self.adapter_bank is not None:
            self.metrics.on_adapter_token(req.adapter or "base")

    def _finish_if_done(self, req: _GenRequest, now: float) -> bool:
        """Retire a request whose last emitted token ended it (EOS,
        max-tokens, or — for a grammar-constrained request — a terminal
        DFA state: accepting with no legal continuation, where the only
        in-grammar move left is stopping). Frees its slot when it held
        one."""
        done = (len(req.emitted) >= req.max_new_tokens
                or (req.eos_token_id is not None
                    and req.emitted[-1] == req.eos_token_id)
                or (req.gid > 0 and req.slot is not None
                    and self.sampling_table.is_terminal(
                        req.gid,
                        int(self.sampling_table.dfa_state[req.slot]))))
        if not done:
            return False
        # finalize the timeline BEFORE resolving the future: a waiter that
        # wakes on result() must see the completed trace
        self._conclude(req, "completed", now)
        req.handle.future.set_result(np.asarray(req.emitted, np.int32))
        self.metrics.on_complete((now - req.arrival) * 1e3, slo=req.slo,
                                 tenant=req.tenant)
        if req.slot is not None and self.pool.active[req.slot]:
            self._free_row_locked(req, req.slot)
        return True

    # ---- scheduler thread (production mode) ----
    def _scheduler_main(self):
        while True:
            with self._cond:
                while True:
                    if self._stopped or self.supervisor.open:
                        return
                    if self._has_work_locked():
                        break
                    if self._draining:
                        return          # drained: stop() joins us
                    self.clock.wait(self._cond, None)
            try:
                self.pump()
            except Exception as e:
                # an unhandled pump exception is exactly what the black box
                # exists for: record + dump before carrying on
                fr = flight_recorder()
                fr.record("pump_exception", engine="llm",
                          error=f"{type(e).__name__}: {e}")
                fr.try_dump(reason="pump_exception:llm")
                _log.exception("llm scheduler pump failed; continuing")
