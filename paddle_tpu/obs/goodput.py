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
- **CompileLedger** — process-wide and always on: every program's
  trace, lower and compile-or-load seconds by name (jax.monitoring's
  three ``/jax/core/compile/*_duration`` events), cache hits and
  misses, the program's own start-up phases, and all of it as it stood
  when the process turned warm (``at_warm``);
- **RecompileSentinel** — counts backend builds (compiles, and loads
  from the persistent cache beside them), books their time as
  non-productive, and treats any build after ``mark_warm()`` as a
  recompile: each drops a ``train_recompile`` flight-recorder event
  naming the program, and a storm (>= storm_threshold recompiles) logs
  a warning;
- **HBMTelemetry** — ``device.memory_stats()`` watermark gauges with
  params/opt-state/KV-slab attribution, and ``oom_forensics`` which
  turns a RESOURCE_EXHAUSTED failure into a ``train_oom`` flight event
  plus an atomic black-box dump.

Cost discipline (the PR 9 contract): a trainer built without the ledger
pays exactly one predicate per hook (`if ledger is not None:`) — no
clock read, no allocation, no lock.

Module import stays stdlib-only; jax and paddle_tpu.utils are imported
lazily inside ``register_listeners`` / the default HBM stats fn.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from .flight_recorder import flight_recorder

_log = logging.getLogger("paddle_tpu.goodput")

# attribution order is the chrome-trace lane order
PHASES = ("compute", "rollback_waste", "data_wait", "h2d", "compile",
          "checkpoint", "idle")



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


# ---- the set-up ledger and the recompile sentinel ----
#
# JAX (pinned at a version that has them) emits for every program three
# duration events that carry `fun_name` — the trace to a jaxpr, the
# lowering to an MLIR module, and the backend's compile *or load*: the
# third wraps `compiler.compile_or_get_cached`, so a hit in the persistent
# cache fires it too, with `cache_hits` and `cache_retrieval_time_sec`
# fired inside it on the same thread. ONE dispatcher per listener kind is
# registered, once per process (`register_listeners`, called by the last
# line of `paddle_tpu/__init__.py`, so that no program escapes); it feeds
# the process-wide `CompileLedger` and fans a backend event out, with its
# program's name, to whichever sentinels are installed. The listeners run
# only when something is traced, lowered or compiled: never in a warm step.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# fires once per backend compile AND once per load of a cached executable
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_PHASE_OF = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
             COMPILE_EVENT: "backend"}


def program_key(fun_name) -> str:
    """The ledger's key for a program: the traced function's name. The
    trace event carries it bare (`step`), the lower and backend events as
    the module's name (`jit(step)`), the device trace as `jit_step`."""
    name = str(fun_name) if fun_name else "<unnamed>"
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    if name.startswith("jit_"):
        return name[4:]
    return name


class _Program:
    """One row of the ledger. `*_s` are inclusive seconds, `*_self_s` the
    same less what events nested inside them (a `jax.jit` traced inside
    another, an eager op compiled while tracing) reported themselves."""

    __slots__ = ("traces", "trace_s", "lower_s", "backend_s",
                 "trace_self_s", "lower_self_s", "backend_self_s",
                 "cache_hits", "cache_misses", "retrieval_s",
                 "first_seen", "last_seen", "first_call_s",
                 "pending_trace_s", "pending_lower_s")

    def __init__(self, now: float):
        self.traces = self.cache_hits = self.cache_misses = 0
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.trace_self_s = self.lower_self_s = self.backend_self_s = 0.0
        self.retrieval_s = 0.0
        self.first_seen = self.last_seen = now
        self.first_call_s: Optional[float] = None
        # trace and lower seconds since the row's last backend event:
        # what the next build of this program paid before its compile
        self.pending_trace_s = self.pending_lower_s = 0.0

    def to_dict(self) -> dict:
        out = {k: getattr(self, k) for k in self.__slots__
               if not k.startswith("pending_")}
        for k, v in out.items():
            if isinstance(v, float):
                out[k] = round(v, 6)
        return out


class CompileLedger:
    """Where set-up went, by program: every executable's trace, lower and
    compile-or-load seconds by name, the program's own start-up phases,
    and what both were when the process turned warm.

    Rows are keyed by `program_key(fun_name)`, at most `MAX_ROWS` of them
    (a set-up has hundreds of small eager-op programs, and every `jax.jit`
    traced inside another reports its own trace): later names fold into
    `<other>` with their count. Totals are sums of SELF time, so nothing
    nested is counted twice: the time-span listener sees a thread's spans
    in the order they end, a child before its parent, and a span's self
    time is its length less the spans that ended inside it.

    `freeze()` (first `RecompileSentinel.mark_warm()` of the process)
    copies totals, phases and rows to `at_warm`; whatever is traced or
    compiled afterwards is a recompile with a name."""

    MAX_ROWS = 512
    OTHER = "<other>"
    # a thread's finished spans that a span still open may contain
    _MAX_PENDING_SPANS = 4096
    _MAX_FOLDED = 8192    # names counted exactly beyond the rows' cap

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.reset()

    def reset(self):
        """Forget everything, `at_warm` too (tests; a process has one
        set-up)."""
        with self._lock:
            self._rows: Dict[str, _Program] = {}
            self._folded: set = set()   # names that share the OTHER row
            self.totals = {"trace_s": 0.0, "lower_s": 0.0,
                           "backend_s": 0.0, "cache_hits": 0,
                           "cache_misses": 0}
            self.phases: Dict[str, float] = {}
            self.at_warm: Optional[dict] = None

    # ---- fed by the dispatchers ----
    def _row_locked(self, key: str, now: float) -> _Program:
        row = self._rows.get(key)
        if row is None:
            if len(self._rows) < self.MAX_ROWS - 1:
                row = self._rows[key] = _Program(now)
            else:
                if len(self._folded) < self._MAX_FOLDED:
                    self._folded.add(key)
                row = self._rows.get(self.OTHER)
                if row is None:
                    row = self._rows[self.OTHER] = _Program(now)
        return row

    def on_duration(self, event: str, seconds: float, fun_name=None,
                    **_kw) -> Optional[dict]:
        """One duration event. Returns, for a backend event, what that
        build paid (`fun_name`, `loaded`, the trace and lower seconds
        before it): the sentinels' feed."""
        phase = _PHASE_OF.get(event)
        if phase is None:
            if event == CACHE_RETRIEVAL_EVENT:
                self._tls.retrieval_s = seconds
            return None
        seconds = max(float(seconds), 0.0)
        key = program_key(fun_name)
        now = time.time()
        with self._lock:
            row = self._row_locked(key, now)
            row.last_seen = now
            if phase == "trace":
                row.traces += 1
                row.trace_s += seconds
                row.pending_trace_s += seconds
                return None
            if phase == "lower":
                row.lower_s += seconds
                row.pending_lower_s += seconds
                return None
            tls = self._tls
            loaded = bool(getattr(tls, "hit", False))
            tls.hit = False
            row.backend_s += seconds
            if loaded:
                row.cache_hits += 1
                row.retrieval_s += getattr(tls, "retrieval_s", 0.0)
                self.totals["cache_hits"] += 1
            else:
                row.cache_misses += 1
                self.totals["cache_misses"] += 1
            tls.retrieval_s = 0.0
            build = {"fun_name": key, "loaded": loaded,
                     "trace_s": row.pending_trace_s,
                     "lower_s": row.pending_lower_s}
            row.pending_trace_s = row.pending_lower_s = 0.0
        return build

    def on_span(self, event: str, start: float, end: float, fun_name=None,
                **_kw):
        """One time span: the event's self time, into its row and the
        totals."""
        phase = _PHASE_OF.get(event)
        if phase is None:
            return
        done = getattr(self._tls, "done", None)
        if done is None:
            done = self._tls.done = deque(maxlen=self._MAX_PENDING_SPANS)
        inner = 0.0
        while done and done[-1][0] >= start:
            s, e = done.pop()
            inner += e - s
        done.append((start, end))
        self_s = max(end - start - inner, 0.0)
        key = program_key(fun_name)
        with self._lock:
            row = self._row_locked(key, start)
            if phase == "trace":
                row.trace_self_s += self_s
            elif phase == "lower":
                row.lower_self_s += self_s
            else:
                row.backend_self_s += self_s
            self.totals[phase + "_s"] += self_s

    def on_event(self, event: str, **_kw):
        """A plain event: a hit in the persistent cache, fired inside the
        backend event it belongs to, on its thread."""
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True

    def add_phase(self, name: str, seconds: float,
                  program: Optional[str] = None):
        """A start-up phase of the program's own (`profiler.SetupSpan`):
        `phases[name]` grows by `seconds`; with `program`, that row's
        `first_call_s` is set if it was not."""
        seconds = max(float(seconds), 0.0)
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + seconds
            if program is not None:
                row = self._row_locked(program_key(program), time.time())
                if row.first_call_s is None:
                    row.first_call_s = seconds

    # ---- read ----
    def freeze(self) -> dict:
        """The set-up as of the first call; later calls leave it."""
        with self._lock:
            if self.at_warm is None:
                self.at_warm = self._snapshot_locked()
            return self.at_warm

    def _snapshot_locked(self, rows: bool = True) -> dict:
        totals = {k: round(v, 6) if isinstance(v, float) else v
                  for k, v in self.totals.items()}
        totals["programs"] = len(self._rows) - (self.OTHER in self._rows) \
            + len(self._folded)
        out = {"totals": totals, "time": time.time(),
               "phases": {k: round(v, 6) for k, v in self.phases.items()}}
        if rows:
            out["rows"] = {k: r.to_dict() for k, r in self._rows.items()}
            if self.OTHER in out["rows"]:
                out["rows"][self.OTHER]["programs"] = len(self._folded)
        return out

    def snapshot(self, rows: bool = True) -> dict:
        """Totals, phases and (unless `rows` is False: a scrape) rows as
        they stand."""
        with self._lock:
            return self._snapshot_locked(rows)

    def row(self, fun_name) -> Optional[dict]:
        """The row of one program as it stands, or None."""
        with self._lock:
            row = self._rows.get(program_key(fun_name))
            return row.to_dict() if row is not None else None

    @staticmethod
    def slowest(rows: Dict[str, dict], top: Optional[int] = None) -> list:
        """`rows` as a list, each with its `program`, slowest first by
        self time."""
        out = [{"program": k, **r} for k, r in rows.items()]
        out.sort(key=lambda r: -(r["trace_self_s"] + r["lower_self_s"]
                                 + r["backend_self_s"]))
        return out if top is None else out[:top]


_LEDGER = CompileLedger()
_DISPATCH_LOCK = threading.Lock()
_ACTIVE_SENTINELS: set = set()
_LISTENERS_REGISTERED = False


def compile_ledger() -> CompileLedger:
    """The process-wide set-up ledger, always on."""
    return _LEDGER


# the dispatchers look `_LEDGER` up when called: a test may stand a fresh
# ledger in its place for its own length
def _span_dispatch(event: str, start: float, end: float, **kw):
    _LEDGER.on_span(event, start, end, **kw)


def _event_dispatch(event: str, **kw):
    _LEDGER.on_event(event, **kw)


def _duration_dispatch(event: str, duration: float, **kw):
    build = _LEDGER.on_duration(event, duration, **kw)
    if build is None:
        return
    with _DISPATCH_LOCK:
        active = list(_ACTIVE_SENTINELS)
    for s in active:
        s.on_compile(duration, **build)


def register_listeners():
    """Register the three dispatchers with `jax.monitoring`, once, for
    the life of the process."""
    global _LISTENERS_REGISTERED
    with _DISPATCH_LOCK:
        if _LISTENERS_REGISTERED:
            return
        _LISTENERS_REGISTERED = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_duration_dispatch)
    jax.monitoring.register_event_time_span_listener(_span_dispatch)
    jax.monitoring.register_event_listener(_event_dispatch)


class RecompileSentinel:
    """Counts backend builds and alarms on post-warmup recompiles.

    Builds during warmup (before `mark_warm()`) are expected; any build
    after it, compiled or loaded from the persistent cache, means the
    step function's static shapes churned — each one counts in
    `recompiles`, drops a `train_recompile` flight-recorder event naming
    the program (`fun_name`), which of trace / lower / backend it paid
    and whether it was `loaded` or `compiled`, and is kept by name in
    `recompiled` (newest last); reaching `storm_threshold` recompiles
    logs a warning naming them (shape churn is fixed at the call site,
    not hidden). `compiles` counts real compiles, `loads` the builds the
    persistent cache answered; both book their seconds to the ledger's
    `compile` phase so they are subtracted from productive compute.
    """

    KEEP_RECOMPILED = 32

    def __init__(self, ledger: Optional[GoodputLedger] = None,
                 storm_threshold: int = 3):
        if storm_threshold < 1:
            raise ValueError(
                f"storm_threshold must be >= 1, got {storm_threshold}")
        self.ledger = ledger
        self.storm_threshold = int(storm_threshold)
        self.compiles = 0
        self.loads = 0
        self.compile_seconds = 0.0
        self.recompiles = 0
        self.recompiled: List[dict] = []
        self.installed = False
        self._warm = False
        self._storm_warned = False
        self._lock = threading.Lock()

    def mark_warm(self):
        """Baseline: builds so far were warmup, later ones are not. The
        first call of a process also freezes the set-up ledger
        (`compile_ledger().at_warm`)."""
        with self._lock:
            self._warm = True
        _LEDGER.freeze()

    def on_compile(self, seconds: float = 0.0, fun_name: str = "<unnamed>",
                   loaded: bool = False, trace_s: float = 0.0,
                   lower_s: float = 0.0):
        """One backend event: `seconds` of compile, or of load where
        `loaded`; `trace_s` / `lower_s` are what the program paid before
        it."""
        seconds = max(float(seconds), 0.0)
        how = "loaded" if loaded else "compiled"
        paid = "+".join(p for p, s in (("trace", trace_s), ("lower", lower_s),
                                       ("backend", seconds)) if s > 0)
        with self._lock:
            if loaded:
                self.loads += 1
            else:
                self.compiles += 1
            self.compile_seconds += seconds
            is_recompile = self._warm
            if is_recompile:
                self.recompiles += 1
                self.recompiled.append(
                    {"fun_name": fun_name, "how": how, "paid": paid,
                     "seconds": round(seconds + trace_s + lower_s, 6)})
                del self.recompiled[:-self.KEEP_RECOMPILED]
            count = self.recompiles
            storm = (is_recompile and count >= self.storm_threshold
                     and not self._storm_warned)
            if storm:
                self._storm_warned = True
                names = ", ".join(f"{r['fun_name']} ({r['how']})"
                                  for r in self.recompiled)
        if self.ledger is not None:
            self.ledger.book("compile", seconds)
        if is_recompile:
            flight_recorder().record(
                "train_recompile", recompiles=count, fun_name=fun_name,
                how=how, paid=paid, seconds=round(seconds, 6),
                trace_seconds=round(trace_s, 6),
                lower_seconds=round(lower_s, 6), storm=storm)
            if storm:
                # the compile observatory (when armed) knows WHICH leaf
                # churned; grouping by culprit turns "3 recompiles" into
                # an actionable shape to bucket (ISSUE 12)
                from .compile_observatory import culprit_summary
                grouped = culprit_summary()
                _log.warning(
                    "recompile storm: %d builds after warmup (threshold "
                    "%d): %s — the step fn's static shapes are churning; "
                    "bucket the shapes at the call site%s",
                    count, self.storm_threshold, names,
                    f" (recompiles by culprit: {grouped})" if grouped
                    else "")

    def install(self) -> "RecompileSentinel":
        """Start observing backend events (idempotent)."""
        with _DISPATCH_LOCK:
            self.installed = True
            _ACTIVE_SENTINELS.add(self)
        return self

    def uninstall(self):
        with _DISPATCH_LOCK:
            self.installed = False
            _ACTIVE_SENTINELS.discard(self)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "loads": self.loads,
                    "recompiles": self.recompiles,
                    "compile_seconds": self.compile_seconds,
                    "recompiled": list(self.recompiled)}


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
