"""Profiler (reference: paddle/fluid/platform/profiler.h RecordEvent/EnableProfiler,
python/paddle/fluid/profiler.py).

TPU-native: host spans are recorded in-process (RecordEvent parity) and device
profiling delegates to jax.profiler (xprof) which captures XLA/TPU timelines —
replacing the CUPTI device tracer (platform/device_tracer.cc:131).

The event sink is PROCESS-GLOBAL: serving pump threads, HTTP handler
threads, and the training loop all append to one shared buffer under a
lock, so whichever thread calls `export_chrome_tracing` sees every span.
Only the span *stack* (nesting context) stays per-thread. The disabled
hot path is a single predicate — no lock is taken unless profiling is on.

One span type, two recorders. Entering a `RecordEvent` also enters a
`jax.profiler.TraceAnnotation` of the same name, so whenever ANY
`jax.profiler` session runs (`start_profiler(trace_dir=...)`, an
operator's `jax.profiler.start_server`, the benchmark's traced window)
the program's spans are events of the trace's `/host:CPU` plane, on the
clock the device planes are on, with their keyword arguments as stats.
The in-memory sink (perf_counter clock, chrome export) is what
`start_profiler()` without a trace directory gives.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from typing import Dict, List, Optional

import jax

# ---- the program's span names (the ONE table; docs/observability.md and
# PERF.md §3 copy it). Every span is a RecordEvent under these names; who
# reads each is in PERF.md's inventory. The serve children tile `pump`
# but for a few clock reads.
SPAN_SERVE_PUMP = "pdtpu/serve/pump"              # _pump_inner; step=
SPAN_SERVE_ADMIT = "pdtpu/serve/admit"            # expiry drop + _admit
SPAN_SERVE_EVICT = "pdtpu/serve/evict"            # one evict_for_pressure
SPAN_SERVE_DRAFT = "pdtpu/serve/draft"            # _draft_phase (if armed)
SPAN_SERVE_BUILD_ROWS = "pdtpu/serve/build_rows"  # rows, kinds, operands
SPAN_SERVE_DISPATCH = "pdtpu/serve/dispatch"      # upload + launch;
#                                                   prefill_rows=, decode_rows=,
#                                                   sampled_rows=, live_tokens=,
#                                                   step_tokens=, deferred_rows=,
#                                                   slots_vacant_queued=
SPAN_SERVE_FETCH = "pdtpu/serve/fetch"            # host waits for the device
SPAN_SERVE_COMMIT = "pdtpu/serve/commit"          # acceptance .. retire
SPAN_SERVE_PUBLISH = "pdtpu/serve/publish"        # gauges after the step
# one request's way to its first token (`REQUEST_SPANS`): four events a
# request, none a step or a token, each with the request's `rid`. What
# they say is read off the stamps every request carries (`_GenRequest`:
# arrival, admitted, first_launch, final_launch, first_token), which cut
# its TTFT into queued + bound + prefill + first_fetch
SPAN_REQUEST_SUBMIT = "pdtpu/serve/request/submit"  # submit()'s body, on
#                                                   the caller's thread;
#                                                   rid=, prompt_tokens=
SPAN_REQUEST_ADMIT = "pdtpu/serve/request/admit"  # in `admit`: probe_row +
#                                                   allocate + attach; rid=,
#                                                   slot=, queued_ms=,
#                                                   cached_tokens=
SPAN_REQUEST_FIRST_LAUNCH = "pdtpu/serve/request/first_launch"  # in
#                                                   `build_rows`: the first
#                                                   step that carries a chunk
#                                                   of it; rid=, step=,
#                                                   bound_ms=
SPAN_REQUEST_FIRST_TOKEN = "pdtpu/serve/request/first_token"  # in `commit`,
#                                                   round the first _emit;
#                                                   rid=, step=, ttft_ms=,
#                                                   queued_ms=, bound_ms=,
#                                                   prefill_ms=,
#                                                   first_fetch_ms=, chunks=,
#                                                   steps_to_first_token=
SPAN_TRAIN_BATCH_WAIT = "pdtpu/train/batch_wait"  # ChunkPrefetcher get
SPAN_TRAIN_CHUNK_DISPATCH = "pdtpu/train/chunk_dispatch"  # ScanTrainStep call
# start-up phases (`SetupSpan`): each also adds its seconds to
# `obs.goodput.compile_ledger().phases[<last word>]`, because a profiler
# session rarely covers a start-up
SPAN_SETUP_IMPORT = "pdtpu/setup/import"          # `import paddle_tpu`
SPAN_SETUP_ENGINE_INIT = "pdtpu/setup/engine_init"  # LLMEngine.__init__
SPAN_SETUP_PARALLELIZE = "pdtpu/setup/parallelize"  # parallelize() -> step
SPAN_SETUP_FIRST_STEP = "pdtpu/setup/first_step"  # the step's first call,
#                                                   launch to result; program=
SERVE_SPANS = (SPAN_SERVE_PUMP, SPAN_SERVE_ADMIT, SPAN_SERVE_EVICT,
               SPAN_SERVE_DRAFT, SPAN_SERVE_BUILD_ROWS, SPAN_SERVE_DISPATCH,
               SPAN_SERVE_FETCH, SPAN_SERVE_COMMIT, SPAN_SERVE_PUBLISH)
REQUEST_SPANS = (SPAN_REQUEST_SUBMIT, SPAN_REQUEST_ADMIT,
                 SPAN_REQUEST_FIRST_LAUNCH, SPAN_REQUEST_FIRST_TOKEN)
TRAIN_SPANS = (SPAN_TRAIN_BATCH_WAIT, SPAN_TRAIN_CHUNK_DISPATCH)
SETUP_SPANS = (SPAN_SETUP_IMPORT, SPAN_SETUP_ENGINE_INIT,
               SPAN_SETUP_PARALLELIZE, SPAN_SETUP_FIRST_STEP)


class _ProfSink:
    """Shared event buffer. `enabled` is read without the lock (a stale
    read drops or records one extra event, never corrupts the buffer);
    all appends/reads of `events` and `trace_dir` hold `lock`."""

    __slots__ = ("lock", "enabled", "events", "trace_dir")

    def __init__(self):
        self.lock = threading.Lock()
        self.enabled = False
        self.events: List[dict] = []
        self.trace_dir: Optional[str] = None


_SINK = _ProfSink()


_LANES = itertools.count(1)      # chrome-export lane per thread
_SPAN_IDS = itertools.count(1)   # sink-side span ids (parent links)


class _ThreadState(threading.local):
    def __init__(self):
        self.stack: List[int] = []      # ids of this thread's open spans
        # a process-wide counter, not get_ident(): a joined thread's ident
        # is reused, which put two threads on one lane
        self.lane = next(_LANES)


_T = _ThreadState()


class RecordEvent:
    """RAII host span (platform/profiler.h:127 analog). Keyword arguments
    are the ids the span was given (`step=`, `rid=`, row counts): stats of
    the trace event, `args` of the sink event. The sink event also records
    `id` and `parent` (the id of the enclosing span on this thread, 0 at
    top level). With no `jax.profiler` session and the sink off, a span
    costs the annotation's own inactive check plus one predicate."""

    __slots__ = ("name", "args", "begin", "_ann", "_open", "_id", "_parent")

    def __init__(self, name: str, event_type: str = "UserDefined", **args):
        self.name = name
        self.args = args
        self.begin = None
        self._open = False
        self._ann = jax.profiler.TraceAnnotation(name, **args)

    # False: the span takes a parent but is nobody's, so it may end after
    # spans that began inside it (SetupSpan)
    _nests = True

    def __enter__(self):
        self._ann.__enter__()
        self._open = True
        if _SINK.enabled:
            stack = _T.stack
            self._parent = stack[-1] if stack else 0
            self._id = next(_SPAN_IDS)
            if self._nests:
                stack.append(self._id)
            self.begin = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def set(self, **args):
        """Arguments that are known only once the span's work is done (the
        slot an admission was given): call before the span ends."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def end(self):
        if not self._open:
            return
        self._open = False
        self._ann.__exit__(None, None, None)
        if self.begin is None:
            return
        end = time.perf_counter_ns()
        stack = _T.stack
        if stack and stack[-1] == self._id:
            stack.pop()
        begin, self.begin = self.begin, None
        if not _SINK.enabled:
            return
        evt = {
            "name": self.name, "ts": begin / 1e3, "dur": (end - begin) / 1e3,
            "ph": "X", "pid": 0, "tid": _T.lane,
            "args": {"id": self._id, "parent": self._parent, **self.args},
        }
        with _SINK.lock:
            _SINK.events.append(evt)


class SetupSpan(RecordEvent):
    """A span of `SETUP_SPANS`: a `RecordEvent` that also adds its seconds
    to the set-up ledger's phase of the same last word, and, given
    `program` (the name of the jitted function whose first call it times),
    to that program's row. `t0` is the `perf_counter` reading the phase
    began at, where that was before a span could be made (`import`). It
    is no span's parent, so it need not end in the order it began: the
    engine's `first_step` begins in a `dispatch` and ends after the
    `fetch` that follows. A set-up path: never entered in a warm step."""

    __slots__ = ("_t0", "_program")
    _nests = False

    def __init__(self, name: str, program: Optional[str] = None,
                 t0: Optional[float] = None):
        super().__init__(name, **({"program": program} if program else {}))
        self._program = program
        self._t0 = t0

    def __enter__(self):
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return super().__enter__()

    def end(self):
        if not self._open:
            return
        super().end()
        from ..obs.goodput import compile_ledger
        compile_ledger().add_phase(self.name.rsplit("/", 1)[-1],
                                   time.perf_counter() - self._t0,
                                   self._program)


def record_instant(name: str, args: Optional[dict] = None):
    """Zero-duration instant event (chrome 'i' phase) — used for fault /
    recovery markers (resilient runtime) so they land on the same timeline
    as the step spans."""
    if not _SINK.enabled:
        return
    evt = {
        "name": name, "ts": time.perf_counter_ns() / 1e3,
        "ph": "i", "s": "p", "pid": 0,
        "tid": _T.lane,
        "args": args or {},
    }
    with _SINK.lock:
        _SINK.events.append(evt)


def emit_events(events: List[dict]):
    """Append pre-built chrome events (e.g. a finished request's phase
    spans from paddle_tpu.obs.trace) onto the shared timeline."""
    if not _SINK.enabled or not events:
        return
    with _SINK.lock:
        _SINK.events.extend(events)


def profiler_enabled() -> bool:
    return _SINK.enabled


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    with _SINK.lock:
        _SINK.events.clear()
        # module-global, NOT thread-local: stop_profiler() from any thread
        # must see the trace_dir that start_profiler() armed
        _SINK.trace_dir = trace_dir or None
    _SINK.enabled = True
    if trace_dir:
        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _SINK.enabled = False
    with _SINK.lock:
        trace_dir, _SINK.trace_dir = _SINK.trace_dir, None
    if trace_dir:
        jax.profiler.stop_trace()
    export_chrome_tracing(profile_path)


def export_chrome_tracing(path: str):
    with _SINK.lock:
        events = list(_SINK.events)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class Profiler:
    """paddle.profiler.Profiler-style API over jax.profiler."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, trace_dir="/tmp/paddle_tpu_trace"):
        self.trace_dir = trace_dir
        self.timer_only = timer_only
        self._active = False

    def start(self):
        with _SINK.lock:
            _SINK.events.clear()
        _SINK.enabled = True
        if not self.timer_only:
            try:
                jax.profiler.start_trace(self.trace_dir)
                self._active = True
            except Exception:
                self._active = False

    def stop(self):
        _SINK.enabled = False
        if self._active:
            jax.profiler.stop_trace()
            self._active = False

    def step(self, num_samples=None):
        pass

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        by_name: Dict[str, List[float]] = {}
        # only complete ("X") spans carry a duration; instants ("i") from
        # record_instant share the buffer and must not crash the summary
        for e in get_events():
            if e.get("ph") != "X":
                continue
            by_name.setdefault(e["name"], []).append(e["dur"])
        lines = [f"{'Event':40s} {'Calls':>8s} {'Total(us)':>12s} {'Avg(us)':>12s}"]
        for name, durs in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
            lines.append(f"{name:40s} {len(durs):8d} {sum(durs):12.1f} "
                         f"{sum(durs)/len(durs):12.1f}")
        return "\n".join(lines)


class ThroughputTracker:
    """Per-chunk wall-time → steps/sec and tokens/sec.

    The chunk run loop (trainer.DeviceWorker over a parallel.ScanTrainStep)
    calls `update(steps=K, seconds=dt, tokens=K*B*S)` once per fused
    dispatch, so utilization is reported from the production path. Rates
    are computed over a sliding window of recent chunks (warmup/compile
    chunks age out) alongside lifetime totals; each
    update also drops a `throughput` instant on the profiler timeline when
    profiling is enabled.
    """

    def __init__(self, window: int = 32):
        from collections import deque
        self.window = int(window)
        self._chunks = deque(maxlen=self.window)  # (steps, tokens, seconds)
        self.total_steps = 0
        self.total_tokens = 0
        self.total_seconds = 0.0
        # duration of the most recent chunk — the watchdog and the goodput
        # ledger read the same step-duration signal the rates use
        self.last_chunk_seconds = 0.0
        self._flops_per_step: Optional[float] = None
        self._peak_flops: Optional[float] = None

    def register_flops(self, flops_per_step: float, peak_flops: float):
        """Arm the windowed MFU gauge: analytic FLOPs per step (see
        obs.flops) and the mesh's TOTAL peak FLOP/s."""
        self._flops_per_step = float(flops_per_step)
        self._peak_flops = float(peak_flops)

    def update(self, steps: int, seconds: float, tokens: int = 0):
        steps, tokens, seconds = int(steps), int(tokens), float(seconds)
        self.last_chunk_seconds = seconds
        # a zero/negative-duration chunk flood (mocked clocks, duplicate
        # timestamps) must not age real measurements out of the rate
        # window; totals still count the work
        if seconds > 0.0:
            self._chunks.append((steps, tokens, seconds))
        self.total_steps += steps
        self.total_tokens += tokens
        self.total_seconds += seconds
        record_instant("throughput", {
            "steps": steps, "tokens": tokens, "seconds": seconds,
            "steps_per_sec": self.steps_per_sec,
            "tokens_per_sec": self.tokens_per_sec,
        })

    def _windowed(self, idx: int) -> float:
        secs = sum(c[2] for c in self._chunks)
        if secs <= 0.0:
            return 0.0
        return sum(c[idx] for c in self._chunks) / secs

    @property
    def steps_per_sec(self) -> float:
        return self._windowed(0)

    @property
    def tokens_per_sec(self) -> float:
        return self._windowed(1)

    @property
    def mfu(self) -> Optional[float]:
        """Windowed model-FLOPs utilization, or None until
        register_flops() arms the gauge."""
        if self._flops_per_step is None or not self._peak_flops:
            return None
        return self.steps_per_sec * self._flops_per_step / self._peak_flops

    def summary(self) -> dict:
        out = {
            "steps_per_sec": self.steps_per_sec,
            "tokens_per_sec": self.tokens_per_sec,
            "total_steps": self.total_steps,
            "total_tokens": self.total_tokens,
            "total_seconds": self.total_seconds,
            "last_chunk_seconds": self.last_chunk_seconds,
        }
        if self._flops_per_step is not None:
            out["mfu"] = self.mfu
        return out


def get_events():
    with _SINK.lock:
        return list(_SINK.events)
