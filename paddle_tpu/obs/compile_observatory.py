"""Compile observatory (ISSUE 12): explain every recompile, cost- and
memory-profile every executable.

PRs 9-11 made runtime *time* attributable; the compiled-program layer
stayed a black box: the recompile sentinel (obs.goodput) can count XLA
compiles and warn on storms, but cannot say WHICH argument changed
shape, what each executable costs in FLOPs/bytes, or how much HBM XLA
reserved. This module closes that gap:

- **Registry** — every jitted executable the runtime builds is keyed by
  a stable fingerprint of its abstract signature (the pytree of
  shape/dtype/sharding per leaf plus a static-arg hash) and records its
  compile duration, ``cost_analysis()`` FLOPs / bytes-accessed,
  ``memory_analysis()`` temp/argument/output bytes, and cumulative
  dispatch count + device-seconds (device time is fed by the goodput /
  serving-ledger dispatch hooks, which already block on the result).
- **Culprit diffs** — a post-warmup build for an already-registered
  call site is a recompile: the new signature is diffed against the
  previous one and a ``compile_recompile`` flight event names the
  culprit leaf (``batch['x'].shape[0]: 32→48``). Recompiles are counted
  per culprit; a per-culprit storm (>= storm_threshold) logs a grouped
  warning, records a ``compile_storm`` event, and dumps the black box.
- **Hooks** — explicit ``observe_call()`` wrappers in ``DeviceWorker``,
  ``ScanTrainStep``, ``ShardedTrainStep``, the LLM engine's unified
  step, and ``BatchingEngine`` predict — each costing exactly one
  ``is not None`` predicate when disabled (the PR 9 cost contract).
- **Exposition** — ``GET /debug/compiles`` on both HTTP servers,
  ``pdtpu_compile_*`` Prometheus families, chrome ``compile/<callsite>``
  lanes, and a predicted-vs-measured HBM row reconciling
  ``memory_analysis()`` totals against the PR 10 HBMTelemetry watermark
  (a ratio far from 1 means XLA's plan and the allocator disagree). The
  same payload carries the always-on set-up ledger
  (``obs.goodput.CompileLedger``): ``programs``, every program the
  process traced, lowered, compiled or loaded, by name, and ``setup``,
  its totals and the start-up phases as of the first ``mark_warm()``.

Analyses come from JAX's AOT path (``jit(f).lower(*args).compile()``
then ``cost_analysis()`` / ``memory_analysis()``). The AOT compile is
issued once per NEW fingerprint only, and only while the observatory is
enabled; backends that share the XLA compilation cache pay nothing
extra, others pay one bounded duplicate compile per distinct signature
— the price of knowing what the program costs. What it took is read
from the ledger's row for the function, not from a clock of its own.
Module import stays stdlib-only; jax is only touched inside the AOT
helper.
"""
from __future__ import annotations

import hashlib
import logging
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .flight_recorder import flight_recorder
from .goodput import CompileLedger, _emit_chrome_span, compile_ledger

_log = logging.getLogger("paddle_tpu.compile_observatory")

# repr of a static (non-array) leaf is bounded so a pathological object
# cannot bloat signatures, events, or /debug/compiles payloads
_STATIC_REPR_LIMIT = 64


# ---- abstract-signature capture ----

def _leaf_entry(path: str, leaf) -> Tuple[str, str, str, str]:
    """(path, shape, dtype, sharding) for one pytree leaf. Array-likes
    (jax arrays, numpy arrays, core.Tensor wrappers) contribute their
    abstract value; anything else is a static leaf whose bounded repr
    rides in the dtype slot (a changed static arg must show up in the
    culprit diff exactly like a changed shape)."""
    data = leaf
    if not hasattr(data, "shape") and hasattr(data, "data") \
            and hasattr(getattr(data, "data"), "shape"):
        data = data.data                       # core.Tensor wrapper
    shape = getattr(data, "shape", None)
    dtype = getattr(data, "dtype", None)
    if shape is not None and dtype is not None:
        sharding = getattr(data, "sharding", None)
        sh = ""
        if sharding is not None:
            try:
                sh = str(sharding)
            except Exception:
                sh = type(sharding).__name__
        return (path, str(tuple(shape)), str(dtype), sh)
    r = repr(leaf)
    if len(r) > _STATIC_REPR_LIMIT:
        r = r[:_STATIC_REPR_LIMIT] + "..."
    return (path, "static", r, "")


def signature_of(tree, prefix: str = "args") -> Tuple[tuple, ...]:
    """Flatten an argument pytree (dicts/lists/tuples of array-likes)
    into a stable, ordered tuple of (path, shape, dtype, sharding)
    leaf entries. Dict keys are sorted so insertion order can never
    masquerade as a signature change."""
    out: List[tuple] = []
    stack: List[Tuple[str, Any]] = [(prefix, tree)]
    while stack:
        path, node = stack.pop()
        if isinstance(node, dict):
            for k in sorted(node, key=repr, reverse=True):
                stack.append((f"{path}[{k!r}]", node[k]))
        elif isinstance(node, (list, tuple)) and not hasattr(node, "shape"):
            for i in range(len(node) - 1, -1, -1):
                stack.append((f"{path}[{i}]", node[i]))
        else:
            out.append(_leaf_entry(path, node))
    return tuple(out)


def fingerprint_of(signature: Tuple[tuple, ...],
                   static_hash: Optional[str] = None) -> str:
    """Stable 12-hex-digit fingerprint of a signature (+ optional
    static-arg hash) — the registry key and the /debug/compiles id."""
    h = hashlib.sha1(repr(signature).encode())
    if static_hash:
        h.update(str(static_hash).encode())
    return h.hexdigest()[:12]


def diff_signatures(old: Tuple[tuple, ...],
                    new: Tuple[tuple, ...]) -> List[str]:
    """Human-readable leaf-level diff between two signatures, most
    specific field first: `path.shape: (32, 8)→(48, 8)`, then dtype,
    then sharding; leaves present on only one side report added/removed.
    The FIRST entry is the named culprit."""
    old_by = {e[0]: e for e in old}
    new_by = {e[0]: e for e in new}
    changes: List[str] = []
    for path, (_, n_shape, n_dtype, n_shard) in \
            ((e[0], e) for e in new):
        o = old_by.get(path)
        if o is None:
            changes.append(f"{path}: added {n_shape} {n_dtype}".rstrip())
            continue
        _, o_shape, o_dtype, o_shard = o
        if o_shape != n_shape:
            changes.append(f"{path}.shape: {o_shape}→{n_shape}")
        elif o_dtype != n_dtype:
            field = "static" if n_shape == "static" else "dtype"
            changes.append(f"{path}.{field}: {o_dtype}→{n_dtype}")
        elif o_shard != n_shard:
            changes.append(f"{path}.sharding: {o_shard}→{n_shard}")
    for path in old_by:
        if path not in new_by:
            changes.append(f"{path}: removed")
    return changes


# ---- AOT analysis ----

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WORD = re.compile(r"[\w.\-]+")


def pallas_kernel_census(hlo_text: str) -> Dict[str, int]:
    """{kernel name: count} over the Mosaic custom calls of compiled HLO
    text. The name is the `name=` its pallas_call was given: the op_name
    scope right before `/pallas_call`, inside any `jvp(...)`/
    `transpose(...)` wrapper. Empty on the CPU, where kernels
    run interpreted and leave no custom call — which is how a caller
    tells a step that contains the kernel from one that took an XLA
    stand-in."""
    census: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _OP_NAME.search(line)
        scopes = m.group(1).split("/") if m else []
        words = (_WORD.findall(scopes[-2]) if len(scopes) >= 2
                 and scopes[-1] == "pallas_call" else [])
        name = words[-1] if words else "unnamed"
        census[name] = census.get(name, 0) + 1
    return census


def _aot_analyses(fn, args) -> Tuple[float, dict]:
    """lower()+compile() `fn` for `args` and pull cost/memory analyses.
    Returns (compile_seconds, analyses-dict); tolerant of callables
    without an AOT path (plain predictors) and of backends whose
    analyses are unavailable — missing numbers stay None, never raise."""
    out: Dict[str, Any] = {
        "flops": None, "bytes_accessed": None, "temp_bytes": None,
        "argument_bytes": None, "output_bytes": None,
        "generated_code_bytes": None, "pallas_kernels": None,
        "phase_seconds": None,
    }
    lower = getattr(fn, "lower", None)
    if lower is None:
        return 0.0, out
    # what the build takes is what the set-up ledger books to the
    # function's row while it runs: trace and lower (paid in every
    # process before the compile cache can be asked) and the backend's
    # compile or load, apart; the wall clock where jax names no such row
    name = getattr(fn, "__name__", None)
    before = compile_ledger().row(name) if name else None
    t0 = time.monotonic()
    try:
        trace = getattr(fn, "trace", None)
        traced = trace(*args) if trace is not None else None
        t1 = time.monotonic()
        lowered = traced.lower() if traced is not None else lower(*args)
        t2 = time.monotonic()
        compiled = lowered.compile()
    except Exception:
        _log.debug("AOT lower/compile failed", exc_info=True)
        return time.monotonic() - t0, out
    paid = {"trace": t1 - t0, "lower": t2 - t1,
            "compile": time.monotonic() - t2}
    after = compile_ledger().row(name) if name else None
    if after is not None:
        booked = {ph: after[key] - (before[key] if before else 0.0)
                  for ph, key in (("trace", "trace_s"), ("lower", "lower_s"),
                                  ("compile", "backend_s"))}
        if booked["compile"] > 0:
            paid = booked
    out["phase_seconds"] = paid
    seconds = sum(paid.values())
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):   # per-device on older jax
            cost = cost[0] if cost else {}
        if cost:
            if cost.get("flops") is not None:
                out["flops"] = float(cost["flops"])
            if cost.get("bytes accessed") is not None:
                out["bytes_accessed"] = float(cost["bytes accessed"])
    except Exception:
        _log.debug("cost_analysis unavailable", exc_info=True)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["temp_bytes"] = int(mem.temp_size_in_bytes)
            out["argument_bytes"] = int(mem.argument_size_in_bytes)
            out["output_bytes"] = int(mem.output_size_in_bytes)
            out["generated_code_bytes"] = int(
                mem.generated_code_size_in_bytes)
    except Exception:
        _log.debug("memory_analysis unavailable", exc_info=True)
    try:
        out["pallas_kernels"] = pallas_kernel_census(compiled.as_text())
    except Exception:
        _log.debug("compiled text unavailable", exc_info=True)
    return seconds, out


class ExecutableRecord:
    """One registered executable: the signature behind a fingerprint and
    everything measured about it."""

    __slots__ = ("callsite", "fingerprint", "signature", "compile_seconds",
                 "flops", "bytes_accessed", "temp_bytes", "argument_bytes",
                 "output_bytes", "generated_code_bytes", "pallas_kernels",
                 "phase_seconds", "dispatches", "device_seconds",
                 "built_seq")

    def __init__(self, callsite: str, fingerprint: str,
                 signature: Tuple[tuple, ...], compile_seconds: float,
                 analyses: dict, built_seq: int):
        self.callsite = callsite
        self.fingerprint = fingerprint
        self.signature = signature
        self.compile_seconds = float(compile_seconds)
        self.flops = analyses.get("flops")
        self.bytes_accessed = analyses.get("bytes_accessed")
        self.temp_bytes = analyses.get("temp_bytes")
        self.argument_bytes = analyses.get("argument_bytes")
        self.output_bytes = analyses.get("output_bytes")
        self.generated_code_bytes = analyses.get("generated_code_bytes")
        self.pallas_kernels = analyses.get("pallas_kernels")
        self.phase_seconds = analyses.get("phase_seconds")
        self.dispatches = 0
        self.device_seconds = 0.0
        self.built_seq = built_seq

    def to_dict(self, leaves: int = 8) -> dict:
        return {
            "callsite": self.callsite,
            "fingerprint": self.fingerprint,
            "compile_seconds": round(self.compile_seconds, 6),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "pallas_kernels": self.pallas_kernels,
            "phase_seconds": self.phase_seconds,
            "dispatches": self.dispatches,
            "device_seconds": round(self.device_seconds, 6),
            "built_seq": self.built_seq,
            "signature_leaves": len(self.signature),
            "signature": [" ".join(x for x in e if x)
                          for e in self.signature[:leaves]],
        }


class CompileObservatory:
    """Process-global registry of every jitted executable the runtime
    builds, plus the recompile explainer. Disabled by default; armed
    via engine/trainer ``observatory`` config flags or ``enable()``.
    Every hot-path hook is ``if self.observatory is not None:`` — one
    predicate, no clock read, no hashing, when off."""

    def __init__(self, storm_threshold: int = 3,
                 clock: Callable[[], float] = time.monotonic):
        if storm_threshold < 1:
            raise ValueError(
                f"storm_threshold must be >= 1, got {storm_threshold}")
        self.storm_threshold = int(storm_threshold)
        self._clock = clock
        self._lock = threading.Lock()
        self._enabled = False
        self._warm = False
        self._build_seq = 0
        self._records: Dict[Tuple[str, str], ExecutableRecord] = {}
        self._latest: Dict[str, str] = {}   # callsite -> latest fingerprint
        self.recompiles = 0
        self.recompiles_by_culprit: Dict[str, int] = {}
        self._storm_warned: set = set()

    # ---- lifecycle ----
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> "CompileObservatory":
        """Arm signature capture. Idempotent."""
        with self._lock:
            self._enabled = True
        return self

    def disable(self):
        with self._lock:
            self._enabled = False

    def mark_warm(self):
        """Baseline: builds so far were warmup; any later build for an
        already-registered call site is a recompile with a culprit."""
        with self._lock:
            self._warm = True

    def reset(self):
        with self._lock:
            self._records.clear()
            self._latest.clear()
            self.recompiles = 0
            self.recompiles_by_culprit.clear()
            self._storm_warned.clear()
            self._warm = False
            self._build_seq = 0

    # ---- the observe() hook ----
    def observe_call(self, callsite: str, fn, args: tuple,
                     static_hash: Optional[str] = None) -> str:
        """Per-dispatch wrapper the hook sites call just before their
        jitted dispatch: fingerprints the args, registers a new
        executable (AOT analyses + recompile diff) on first sighting,
        and counts the dispatch. Returns the fingerprint. Never raises
        into the dispatch path."""
        try:
            sig = signature_of(args)
            fp = fingerprint_of(sig, static_hash)
            with self._lock:
                rec = self._records.get((callsite, fp))
            if rec is None:
                seconds, analyses = _aot_analyses(fn, args)
                t1 = self._clock()
                rec = self._register(callsite, fp, sig, seconds, analyses)
                _emit_chrome_span(f"compile/{callsite}", t1 - seconds, t1)
            with self._lock:
                rec.dispatches += 1
            return fp
        except Exception:
            _log.debug("observe_call failed for %s", callsite,
                       exc_info=True)
            return ""

    def record_build(self, callsite: str, signature: Tuple[tuple, ...],
                     seconds: float = 0.0,
                     static_hash: Optional[str] = None,
                     analyses: Optional[dict] = None) -> str:
        """Register a build observed externally, already timed. Returns
        the fingerprint; re-registering a known fingerprint is a no-op."""
        fp = fingerprint_of(signature, static_hash)
        with self._lock:
            if (callsite, fp) in self._records:
                return fp
        self._register(callsite, fp, signature, seconds, analyses or {})
        return fp

    def _register(self, callsite: str, fp: str,
                  sig: Tuple[tuple, ...], seconds: float,
                  analyses: dict) -> ExecutableRecord:
        with self._lock:
            rec = self._records.get((callsite, fp))
            if rec is not None:            # raced with another thread
                return rec
            self._build_seq += 1
            rec = ExecutableRecord(callsite, fp, sig, seconds, analyses,
                                   self._build_seq)
            self._records[(callsite, fp)] = rec
            prev_fp = self._latest.get(callsite)
            self._latest[callsite] = fp
            is_recompile = self._warm and prev_fp is not None \
                and prev_fp != fp
            prev = self._records.get((callsite, prev_fp)) \
                if is_recompile else None
        if not is_recompile:
            return rec
        changes = diff_signatures(prev.signature if prev else (), sig)
        culprit = changes[0] if changes else "unknown"
        # group by the culprit's leaf path (before the ": old→new" part)
        # so successive churns of the same leaf share one bucket
        key = f"{callsite}: {culprit.split(': ')[0]}"
        with self._lock:
            self.recompiles += 1
            count = self.recompiles_by_culprit[key] = \
                self.recompiles_by_culprit.get(key, 0) + 1
            storm = (count >= self.storm_threshold
                     and key not in self._storm_warned)
            if storm:
                self._storm_warned.add(key)
        flight_recorder().record(
            "compile_recompile", callsite=callsite, culprit=culprit,
            changes="; ".join(changes[:4]), old_fingerprint=prev_fp,
            new_fingerprint=fp, seconds=round(seconds, 6), storm=storm)
        if storm:
            _log.warning(
                "recompile storm at %s: %d recompiles share one culprit "
                "(%s) — bucket that leaf's shapes at the call site; "
                "grouped counts: %s", callsite, count, culprit,
                self.culprit_summary())
            flight_recorder().record(
                "compile_storm", callsite=callsite, culprit=culprit,
                count=count)
            flight_recorder().try_dump(reason="recompile_storm")
        return rec

    # ---- dispatch accounting ----
    def note_device_seconds(self, callsite: str, seconds: float):
        """Attribute a dispatch's device span (launch to the end of the
        caller's own wait for the result: the serve engine's fetch, the
        goodput hook's block) to the call site's latest executable."""
        with self._lock:
            fp = self._latest.get(callsite)
            rec = self._records.get((callsite, fp)) if fp else None
            if rec is not None:
                rec.device_seconds += max(float(seconds), 0.0)

    # ---- reporting ----
    def culprit_summary(self, limit: int = 3) -> str:
        """`'batch['x'].shape[0]' x3, ...` — the grouped view the storm
        warnings (here and in the recompile sentinel) embed."""
        with self._lock:
            items = sorted(self.recompiles_by_culprit.items(),
                           key=lambda kv: -kv[1])[:limit]
        return ", ".join(f"{k} x{v}" for k, v in items)

    def snapshot(self, top: Optional[int] = None,
                 hbm=None) -> dict:
        """The /debug/compiles payload: per-executable rows (sorted by
        compile seconds, then dispatches), totals, recompiles grouped by
        culprit, the set-up ledger (`programs`: its rows, slowest first;
        `setup`: totals, phases and rows as of the first `mark_warm()`,
        None before it), and — when an HBMTelemetry is supplied — the
        predicted-vs-measured HBM reconciliation row."""
        with self._lock:
            records = list(self._records.values())
            latest = dict(self._latest)
            by_culprit = dict(self.recompiles_by_culprit)
            recompiles = self.recompiles
            warm = self._warm
            enabled = self._enabled
        records.sort(key=lambda r: (-r.compile_seconds, -r.dispatches))
        rows = [r.to_dict() for r in
                (records[:top] if top is not None else records)]
        out = {
            "enabled": enabled,
            "warm": warm,
            "executables": len(records),
            "compile_seconds_total": round(
                sum(r.compile_seconds for r in records), 6),
            "dispatches_total": sum(r.dispatches for r in records),
            "device_seconds_total": round(
                sum(r.device_seconds for r in records), 6),
            "recompiles": recompiles,
            "recompiles_by_culprit": by_culprit,
            "rows": rows,
        }
        ledger = compile_ledger()
        now, at_warm = ledger.snapshot(), ledger.at_warm
        out["programs"] = CompileLedger.slowest(now["rows"], top)
        out["program_totals"] = {**now["totals"], "phases": now["phases"]}
        out["setup"] = None if at_warm is None else {
            "totals": at_warm["totals"], "phases": at_warm["phases"],
            "programs": CompileLedger.slowest(at_warm["rows"], top)}
        if hbm is not None:
            out["hbm"] = self.reconcile_hbm(hbm, latest=latest)
        return out

    def reconcile_hbm(self, hbm, latest: Optional[dict] = None) -> dict:
        """Predicted-vs-measured HBM: sum memory_analysis() totals over
        each call site's LATEST executable (the resident set a steady
        process keeps live) against the PR 10 watermark gauge. A ratio
        far from 1 means XLA's plan and the allocator disagree."""
        with self._lock:
            if latest is None:
                latest = dict(self._latest)
            live = [self._records[(cs, fp)] for cs, fp in latest.items()
                    if (cs, fp) in self._records]
        temp = sum(r.temp_bytes or 0 for r in live)
        args_b = sum(r.argument_bytes or 0 for r in live)
        outs = sum(r.output_bytes or 0 for r in live)
        predicted = temp + args_b + outs
        row = {"predicted_temp_bytes": temp,
               "predicted_argument_bytes": args_b,
               "predicted_output_bytes": outs,
               "predicted_bytes": predicted,
               "measured_peak_bytes": None, "ratio": None}
        try:
            sample = hbm.sample()
        except Exception:
            sample = {}
        peak = sample.get("peak_bytes_in_use")
        if peak:
            row["measured_peak_bytes"] = int(peak)
            if predicted:
                row["ratio"] = round(predicted / peak, 4)
        return row

    def render_prom(self) -> str:
        """`pdtpu_compile_*` families of the registry; empty when nothing
        is registered (the ledger's families are `render_prom()`'s, with
        or without an observatory)."""
        snap = self.snapshot()
        if not snap["rows"] and not snap["recompiles_by_culprit"]:
            return ""
        from .prom import PromBuilder
        b = PromBuilder()
        b.family("pdtpu_compile_executables", "gauge")
        b.sample("pdtpu_compile_executables", snap["executables"])
        b.family("pdtpu_compile_recompiles_total", "counter")
        b.sample("pdtpu_compile_recompiles_total", snap["recompiles"])
        per_site: Dict[str, dict] = {}
        # build order, so the per-site temp/flops GAUGES track the most
        # recently built executable while the counters sum across all
        for r in sorted(snap["rows"], key=lambda r: r["built_seq"]):
            s = per_site.setdefault(
                r["callsite"], {"seconds": 0.0, "dispatches": 0,
                                "device": 0.0, "temp": None, "flops": None})
            s["seconds"] += r["compile_seconds"]
            s["dispatches"] += r["dispatches"]
            s["device"] += r["device_seconds"]
            if r["temp_bytes"] is not None:
                s["temp"] = r["temp_bytes"]
            if r["flops"] is not None:
                s["flops"] = r["flops"]
        b.family("pdtpu_compile_seconds_total", "counter")
        for site in sorted(per_site):
            b.sample("pdtpu_compile_seconds_total",
                     per_site[site]["seconds"], labels={"callsite": site},
                     round_to=6)
        b.family("pdtpu_compile_dispatches_total", "counter")
        for site in sorted(per_site):
            b.sample("pdtpu_compile_dispatches_total",
                     per_site[site]["dispatches"],
                     labels={"callsite": site})
        b.family("pdtpu_compile_device_seconds_total", "counter")
        for site in sorted(per_site):
            b.sample("pdtpu_compile_device_seconds_total",
                     per_site[site]["device"], labels={"callsite": site},
                     round_to=6)
        b.family("pdtpu_compile_predicted_temp_hbm_bytes", "gauge")
        for site in sorted(per_site):
            if per_site[site]["temp"] is not None:
                b.sample("pdtpu_compile_predicted_temp_hbm_bytes",
                         per_site[site]["temp"], labels={"callsite": site})
        b.family("pdtpu_compile_flops", "gauge")
        for site in sorted(per_site):
            if per_site[site]["flops"] is not None:
                b.sample("pdtpu_compile_flops", per_site[site]["flops"],
                         labels={"callsite": site})
        b.family("pdtpu_compile_recompiles_by_culprit_total", "counter")
        for culprit in sorted(snap["recompiles_by_culprit"]):
            b.sample("pdtpu_compile_recompiles_by_culprit_total",
                     snap["recompiles_by_culprit"][culprit],
                     labels={"culprit": culprit})
        return b.render()


# ---- the process-global observatory ----

_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[CompileObservatory] = None


def compile_observatory() -> CompileObservatory:
    """The process-global observatory (created disabled on first use) —
    one registry per process, like the flight recorder, so every hook
    site and both HTTP servers see the same executables."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = CompileObservatory()
        return _GLOBAL


def render_prom() -> str:
    """Scrape-time helper for the HTTP servers: the set-up ledger's
    totals (no per-program label: cardinality), then the global
    observatory's `pdtpu_compile_*` exposition where it was armed."""
    from .prom import PromBuilder
    totals = compile_ledger().snapshot(rows=False)["totals"]
    b = PromBuilder()
    for family, key in (
            ("pdtpu_compile_trace_seconds_total", "trace_s"),
            ("pdtpu_compile_lower_seconds_total", "lower_s"),
            ("pdtpu_compile_backend_seconds_total", "backend_s"),
            ("pdtpu_compile_cache_hits_total", "cache_hits"),
            ("pdtpu_compile_cache_misses_total", "cache_misses")):
        b.family(family, "counter")
        b.sample(family, totals[key])
    with _GLOBAL_LOCK:
        inst = _GLOBAL
    return b.render() + (inst.render_prom() if inst is not None else "")


def culprit_summary(limit: int = 3) -> str:
    """Grouped recompiles-by-culprit summary for the sentinel's storm
    warning; "" when the observatory was never created or saw none."""
    with _GLOBAL_LOCK:
        inst = _GLOBAL
    return inst.culprit_summary(limit) if inst is not None else ""
