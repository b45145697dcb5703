"""Training goodput ledger (ISSUE 10): where did the wall clock go?

Every second of trainer wall time is attributed to exactly ONE phase:

- ``compute``        — productive device work (the fused chunk dispatch
                       plus the blocking loss read);
- ``rollback_waste`` — device work re-running steps a rollback already
                       completed once, and retry-backoff sleeps;
- ``data_wait``      — the consumer blocked on ChunkPrefetcher starvation
                       (the producer thread's decode/stage work is NOT
                       booked: overlapping it with compute is the point);
- ``h2d``            — synchronous host→device staging on the caller
                       thread (ScanTrainStep.__call__ without a
                       prefetcher);
- ``compile``        — XLA compilation, reported by the recompile
                       sentinel and subtracted from the enclosing phase;
- ``checkpoint``     — CheckpointManager save/restore;
- ``idle``           — the residual: wall minus everything booked.

The invariant — phase seconds tile measured wall clock — holds by
construction: `measure()` frames nest on a per-thread stack and each
books only its SELF time (span minus inner frames and inner `book()`
charges), and `idle` is defined as the unbooked residual, clamped at
zero. Tests reconcile the sum against wall clock within 1%
(tests/test_goodput.py), mirroring ISSUE 9's span-tiling discipline.

On top of the ledger:

- **live MFU** — `flops_per_step x productive_steps / wall / peak`,
  with the FLOPs arithmetic taken from obs.flops (the benchmark's
  `train_mfu_pct` counts attention too: benchmark/kernel_costs.py);
- **RecompileSentinel** — counts XLA compilations (jax.monitoring's
  ``/jax/core/compile/backend_compile_duration`` where available,
  JitLRUCache miss hooks otherwise), books compile time as
  non-productive, and treats any compilation after ``mark_warm()`` as a
  recompile: each drops a ``train_recompile`` flight-recorder event and
  a storm (>= storm_threshold recompiles) logs a warning;
- **HBMTelemetry** — ``device.memory_stats()`` watermark gauges with
  params/opt-state/KV-slab attribution, and ``oom_forensics`` which
  turns a RESOURCE_EXHAUSTED failure into a ``train_oom`` flight event
  plus an atomic black-box dump.

Cost discipline (the PR 9 contract): a trainer built without the ledger
pays exactly one predicate per hook (`if ledger is not None:`) — no
clock read, no allocation, no lock.

Module import stays stdlib-only; jax and paddle_tpu.utils are imported
lazily inside ``RecompileSentinel.install`` / the default HBM stats fn.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Callable, Dict, List, Optional

from .flight_recorder import flight_recorder

_log = logging.getLogger("paddle_tpu.goodput")

# attribution order is the chrome-trace lane order
PHASES = ("compute", "rollback_waste", "data_wait", "h2d", "compile",
          "checkpoint", "idle")

# the jax.monitoring event that fires once per XLA backend compile
# (cache hits do not fire it)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class PhaseLedger:
    """Exclusive phase attribution over wall clock — the shared frame
    bookkeeping under both the training `GoodputLedger` and the serving
    `obs.serving_ledger.ServingLedger` (ISSUE 11).

    `measure(phase)` frames nest on a per-thread stack; a frame books
    its span MINUS the time inner frames (and inner `book()` charges)
    already claimed, so nested hooks never double-count. `book(phase,
    secs)` attributes time reported from callbacks (compile durations,
    per-dispatch splits) and charges it against the enclosing frame the
    same way. The clock is injectable for deterministic tests.

    Subclasses set `phases` (must end with "idle", the unbooked
    residual) and `lane_prefix` (the chrome-trace lane family, e.g.
    `goodput/<phase>` / `serving/<phase>`).
    """

    phases: tuple = ("busy", "idle")
    lane_prefix: str = "phase"

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._t0: Optional[float] = None
        self._phase_seconds: Dict[str, float] = {
            p: 0.0 for p in self.phases if p != "idle"}
        self._tls = threading.local()

    # ---- lifecycle ----
    def start(self):
        """Arm the wall clock; idempotent (first measure/book auto-arms)."""
        with self._lock:
            if self._t0 is None:
                self._t0 = self._clock()

    def reset(self):
        """Zero the booked phases and re-arm the wall clock at `now` (when
        already armed) — excludes warmup from a measurement window."""
        with self._lock:
            for p in self._phase_seconds:
                self._phase_seconds[p] = 0.0
            if self._t0 is not None:
                self._t0 = self._clock()
            self._reset_extra_locked()

    def _reset_extra_locked(self):
        """Subclass hook: zero per-subclass counters under the lock."""

    # ---- attribution ----
    def _stack(self) -> List[list]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    @contextlib.contextmanager
    def measure(self, phase: str):
        """Attribute the enclosed span's SELF time to `phase`."""
        self.start()
        stack = self._stack()
        frame = [phase, self._clock(), 0.0]  # [phase, t_in, inner_seconds]
        stack.append(frame)
        try:
            yield self
        finally:
            stack.pop()
            t_out = self._clock()
            span = t_out - frame[1]
            with self._lock:
                self._phase_seconds[phase] += max(span - frame[2], 0.0)
            if stack:  # the whole span is inner time for the parent
                stack[-1][2] += span
            _emit_chrome_span(f"{self.lane_prefix}/{phase}",
                              frame[1], t_out)

    def book(self, phase: str, seconds: float):
        """Attribute externally-measured seconds (e.g. a compile duration
        reported by jax.monitoring while a compute measure is open); the
        enclosing frame's self time shrinks by the same amount."""
        seconds = max(float(seconds), 0.0)
        self.start()
        with self._lock:
            self._phase_seconds[phase] += seconds
        stack = getattr(self._tls, "stack", None)
        if stack:
            stack[-1][2] += seconds

    # ---- reporting ----
    def wall_and_phases(self) -> tuple:
        """(wall_seconds, {phase: seconds}) with idle = the clamped
        unbooked residual — the tiling invariant both subclasses build
        their snapshots on."""
        now = self._clock()
        with self._lock:
            phases = dict(self._phase_seconds)
            t0 = self._t0
        wall = (now - t0) if t0 is not None else 0.0
        booked = sum(phases.values())
        phases["idle"] = max(wall - booked, 0.0)
        return wall, phases


class GoodputLedger(PhaseLedger):
    """Training-phase attribution over trainer wall clock, plus the
    step/FLOPs accounting that turns it into goodput and live MFU."""

    phases = PHASES
    lane_prefix = "goodput"

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        super().__init__(clock=clock)
        self.productive_steps = 0
        self.wasted_steps = 0
        self.flops_per_step: Optional[float] = None
        self.peak_flops_total: Optional[float] = None
        # ISSUE 15: seconds the AsyncCheckpointManager writer thread spent
        # persisting snapshots. Deliberately NOT a phase — the writer runs
        # concurrently with the step loop on its own thread, so booking it
        # into phase_seconds would break the phases-tile-wall invariant.
        # The `checkpoint` PHASE is therefore the BLOCKING cost only
        # (host-fetch snapshot + sync saves/restores), and blocking vs
        # async-background is directly comparable in snapshot().
        self.checkpoint_async_seconds = 0.0

    def set_flops(self, flops_per_step: float, peak_flops_total: float):
        """Register the analytic FLOPs (obs.flops helpers) and the mesh's
        total peak so snapshot() can report live MFU."""
        self.flops_per_step = float(flops_per_step)
        self.peak_flops_total = float(peak_flops_total)

    def add_steps(self, k: int, productive: bool = True):
        """Count optimizer steps; re-run steps after a rollback are waste."""
        with self._lock:
            if productive:
                self.productive_steps += int(k)
            else:
                self.wasted_steps += int(k)

    def book_async_checkpoint(self, seconds: float):
        """Background-writer persist seconds (AsyncCheckpointManager):
        overlapped work, counted beside — never inside — the phases."""
        with self._lock:
            self.checkpoint_async_seconds += max(float(seconds), 0.0)

    def _reset_extra_locked(self):
        self.productive_steps = 0
        self.wasted_steps = 0
        self.checkpoint_async_seconds = 0.0

    def snapshot(self) -> dict:
        """Point-in-time view: wall, per-phase seconds (idle = residual),
        goodput = compute/wall, and live MFU when FLOPs are registered."""
        wall, phases = self.wall_and_phases()
        with self._lock:
            productive = self.productive_steps
            wasted = self.wasted_steps
            ckpt_async = self.checkpoint_async_seconds
        goodput = phases["compute"] / wall if wall > 0 else 0.0
        mfu = None
        if (self.flops_per_step and self.peak_flops_total and wall > 0
                and productive):
            mfu = (self.flops_per_step * productive
                   / wall / self.peak_flops_total)
        return {
            "wall_seconds": wall,
            "phase_seconds": phases,
            "goodput": goodput,
            "mfu": mfu,
            "productive_steps": productive,
            "wasted_steps": wasted,
            # the checkpoint blocking/background split (ISSUE 15):
            # blocking is the ledger phase (it spends wall time on the
            # step thread), async is the overlapped writer-thread work
            "checkpoint_blocking_seconds": phases["checkpoint"],
            "checkpoint_async_seconds": ckpt_async,
        }


def _emit_chrome_span(lane: str, t_in: float, t_out: float):
    """Drop a `<lane_prefix>/<phase>` span onto the profiler sink so
    phase lanes interleave with RecordEvent spans and `throughput`
    instants in the chrome export. No-op (one predicate after the cached
    import) unless the profiler is running; both clocks are
    CLOCK_MONOTONIC."""
    try:
        from ..profiler import emit_events, profiler_enabled
    except Exception:  # obs stays usable without the jax-backed profiler
        return
    if not profiler_enabled():
        return
    emit_events([{
        "name": lane, "ph": "X", "pid": 0,
        "tid": threading.get_ident() % 10000,
        "ts": t_in * 1e6, "dur": (t_out - t_in) * 1e6,
    }])


# ---- recompile sentinel ----
#
# jax.monitoring listeners cannot be unregistered through public API, so
# ONE module-level dispatcher is registered (at most once per process)
# and fans out to whichever sentinels are currently installed. The
# jit-cache fallback mirrors the same shape: one module-level miss
# listener fanning out, never a per-sentinel registration. Each
# dispatcher only feeds sentinels installed on ITS source, and "auto"
# resolution is pinned process-wide on first use — a JitLRUCache build
# that also fires jax's backend_compile event can therefore never reach
# the same sentinel through both paths (ISSUE 12 satellite: the
# double-counting fix).
_DISPATCH_LOCK = threading.Lock()
_ACTIVE_SENTINELS: set = set()
_MONITORING_REGISTERED = False
_JIT_CACHE_REGISTERED = False
_PROCESS_SOURCE: Optional[str] = None   # pinned by the first "auto" install


def _monitoring_dispatch(event: str, duration: float, **_kw):
    if event != COMPILE_EVENT:
        return
    with _DISPATCH_LOCK:
        active = [s for s in _ACTIVE_SENTINELS
                  if s.installed == "monitoring"]
    for s in active:
        s.on_compile(duration)


def _jit_cache_dispatch(name, key, seconds):
    with _DISPATCH_LOCK:
        active = [s for s in _ACTIVE_SENTINELS
                  if s.installed == "jit_cache"]
    for s in active:
        s.on_compile(seconds)


class RecompileSentinel:
    """Counts XLA compilations and alarms on post-warmup recompiles.

    Compilations during warmup (before `mark_warm()`) are expected; any
    compile after it means the step function's static shapes churned —
    each one drops a `train_recompile` flight-recorder event, and
    reaching `storm_threshold` recompiles logs a warning naming the
    count (shape churn is fixed at the call site, not hidden). Compile
    seconds are booked to the ledger's `compile` phase so they are
    subtracted from productive compute.
    """

    def __init__(self, ledger: Optional[GoodputLedger] = None,
                 storm_threshold: int = 3):
        if storm_threshold < 1:
            raise ValueError(
                f"storm_threshold must be >= 1, got {storm_threshold}")
        self.ledger = ledger
        self.storm_threshold = int(storm_threshold)
        self.compiles = 0
        self.compile_seconds = 0.0
        self.recompiles = 0
        self.installed: Optional[str] = None  # "monitoring" | "jit_cache"
        self._warm = False
        self._storm_warned = False
        self._lock = threading.Lock()

    def mark_warm(self):
        """Baseline: compilations so far were warmup, later ones are not."""
        with self._lock:
            self._warm = True

    def on_compile(self, seconds: float = 0.0):
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self.compiles += 1
            self.compile_seconds += seconds
            is_recompile = self._warm
            if is_recompile:
                self.recompiles += 1
            count = self.recompiles
            storm = (is_recompile and count >= self.storm_threshold
                     and not self._storm_warned)
            if storm:
                self._storm_warned = True
        if self.ledger is not None:
            self.ledger.book("compile", seconds)
        if is_recompile:
            flight_recorder().record(
                "train_recompile", recompiles=count,
                seconds=round(seconds, 6), storm=storm)
            if storm:
                # the compile observatory (when armed) knows WHICH leaf
                # churned; grouping by culprit turns "3 recompiles" into
                # an actionable shape to bucket (ISSUE 12)
                from .compile_observatory import culprit_summary
                grouped = culprit_summary()
                _log.warning(
                    "recompile storm: %d XLA compilations after warmup "
                    "(threshold %d) — the step fn's static shapes are "
                    "churning; bucket the shapes at the call site%s",
                    count, self.storm_threshold,
                    f" (recompiles by culprit: {grouped})" if grouped
                    else "")

    # jit-cache fallback: JitLRUCache miss listeners carry (name, key,
    # build_seconds). Kept for back-compat with callers that registered
    # the bound method directly; the install() path now routes through
    # the module-level _jit_cache_dispatch instead.
    def _on_cache_miss(self, name, key, seconds):
        self.on_compile(seconds)

    def install(self, source: str = "auto") -> "RecompileSentinel":
        """Start observing compilations. `source`: "monitoring" (jax's
        per-compile event), "jit_cache" (JitLRUCache miss hooks), or
        "auto" (monitoring where available, cache hooks otherwise —
        resolved ONCE per process so both sources can never observe the
        same build)."""
        global _MONITORING_REGISTERED, _JIT_CACHE_REGISTERED
        global _PROCESS_SOURCE
        if self.installed is not None:
            return self
        if source == "auto":
            with _DISPATCH_LOCK:
                if _PROCESS_SOURCE is not None:
                    source = _PROCESS_SOURCE
        if source in ("auto", "monitoring"):
            try:
                import jax.monitoring
                with _DISPATCH_LOCK:
                    if not _MONITORING_REGISTERED:
                        jax.monitoring \
                            .register_event_duration_secs_listener(
                                _monitoring_dispatch)
                        _MONITORING_REGISTERED = True
                    # installed is tagged before the sentinel joins the
                    # set: the dispatchers filter on it, and an untagged
                    # member would be invisible to both
                    self.installed = "monitoring"
                    _ACTIVE_SENTINELS.add(self)
                    if _PROCESS_SOURCE is None:
                        _PROCESS_SOURCE = "monitoring"
                return self
            except Exception:
                if source == "monitoring":
                    raise
        from ..utils import jit_cache
        with _DISPATCH_LOCK:
            if not _JIT_CACHE_REGISTERED:
                jit_cache.add_miss_listener(_jit_cache_dispatch)
                _JIT_CACHE_REGISTERED = True
            self.installed = "jit_cache"
            _ACTIVE_SENTINELS.add(self)
            if _PROCESS_SOURCE is None and source == "auto":
                _PROCESS_SOURCE = "jit_cache"
        return self

    def uninstall(self):
        global _JIT_CACHE_REGISTERED
        with _DISPATCH_LOCK:
            was = self.installed
            self.installed = None
            _ACTIVE_SENTINELS.discard(self)
            # the monitoring listener cannot be unregistered (jax has no
            # API for it); the jit-cache one can, so drop it when the
            # last jit_cache sentinel leaves
            drop = (was == "jit_cache" and _JIT_CACHE_REGISTERED
                    and not any(s.installed == "jit_cache"
                                for s in _ACTIVE_SENTINELS))
            if drop:
                _JIT_CACHE_REGISTERED = False
        if drop:
            from ..utils import jit_cache
            jit_cache.remove_miss_listener(_jit_cache_dispatch)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "recompiles": self.recompiles,
                    "compile_seconds": self.compile_seconds}


# ---- HBM telemetry ----

class HBMTelemetry:
    """`device.memory_stats()` watermark gauges with static attribution.

    `sample()` reads the live allocator stats (None/absent on backends
    without them — CPU jax returns None); `attribute()` records the
    byte sizes of the big static residents (params, optimizer state, KV
    slab) so an OOM forensics dump can say what the HBM was holding.
    `stats_fn` is injectable for tests and custom backends.
    """

    GAUGES = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")

    def __init__(self, device=None, stats_fn: Optional[Callable] = None):
        if stats_fn is None:
            def stats_fn(_device=device):
                try:
                    import jax
                    d = _device if _device is not None else jax.devices()[0]
                    return d.memory_stats()
                except Exception:
                    return None
        self._stats_fn = stats_fn
        self._lock = threading.Lock()
        self._attributed: Dict[str, int] = {}

    def attribute(self, component: str, nbytes: int):
        with self._lock:
            self._attributed[str(component)] = int(nbytes)

    @staticmethod
    def tree_nbytes(tree) -> int:
        """Total nbytes over a nested dict/list/tuple of arrays (works on
        jax arrays, numpy arrays, and core.Tensor wrappers)."""
        total = 0
        stack = [tree]
        while stack:
            x = stack.pop()
            if isinstance(x, dict):
                stack.extend(x.values())
            elif isinstance(x, (list, tuple)):
                stack.extend(x)
            else:
                n = getattr(x, "nbytes", None)
                if n is None:
                    n = getattr(getattr(x, "data", None), "nbytes", None)
                if n is not None:
                    total += int(n)
        return total

    def sample(self) -> dict:
        try:
            stats = self._stats_fn()
        except Exception:
            stats = None
        out = {"available": bool(stats)}
        if stats:
            for k in self.GAUGES:
                if k in stats:
                    out[k] = int(stats[k])
        return out

    def snapshot(self) -> dict:
        s = self.sample()
        with self._lock:
            s["attributed"] = dict(self._attributed)
        return s


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted")


def oom_forensics(exc: BaseException,
                  hbm: Optional[HBMTelemetry] = None) -> Optional[str]:
    """If `exc` is an XLA out-of-memory failure, record a `train_oom`
    flight event carrying the HBM watermarks + attribution and dump the
    black-box ring (reason="oom"). Returns the dump path, or None when
    the exception is not an OOM. Never raises."""
    try:
        msg = f"{type(exc).__name__}: {exc}"
    except Exception:
        msg = type(exc).__name__
    if not any(m in msg for m in _OOM_MARKERS):
        return None
    info = {"error": msg[:400]}
    if hbm is not None:
        snap = hbm.snapshot()
        for k in HBMTelemetry.GAUGES:
            if k in snap:
                info[f"hbm_{k}"] = snap[k]
        for comp, n in sorted(snap.get("attributed", {}).items()):
            info[f"attr_{comp}_bytes"] = n
    flight_recorder().record("train_oom", **info)
    return flight_recorder().try_dump(reason="oom")
