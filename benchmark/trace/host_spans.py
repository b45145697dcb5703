"""The program's own spans on the device trace's clock: what the serve host
loop did in the gap between two runs of the unified step.

The program writes `jax.profiler.TraceAnnotation`s named `pdtpu/...`
(`paddle_tpu/profiler`: `RecordEvent`; the table of names is there). In a
traced run they are events of the `/host:CPU` plane's lines, one line per
thread, on the clock of the device planes. This module reads, from the raw
`.xplane.pb`: those events, the runs of the job's executable on chip 0
(`XLA Modules`), and the benchmark's own `benchmark_window` annotation, to
which both are clipped. `reduce.py` is not involved and not changed; the
`XLA Ops` lines are never iterated here.

Where the file comes from: a reader is called as `read(trace, counters,
ctx)` and is not handed the trace file. `harness.Window` makes the trace
directory with `tempfile.mkdtemp(prefix="bench_trace_")` and `run.py`
removes it only after the readers ran, so this module looks for
`bench_trace_*/plugins/profile/*/*.xplane.pb` under `tempfile.gettempdir()`
and caches what it read for the life of the process (one run, one trace).
It never guesses: one such directory is this run's (the driver gives each
run a `TMPDIR` of its own, and the tests do the same); among several (a
killed run's leftover, a second traced run in one `TMPDIR`) it takes the
file whose `benchmark_window` is the window of the reduced trace the reader
was handed, and where none matches, or there is no reduced trace to match
(the CPU), it reads nothing and says so. ROADMAP C6 asks for the
`benchmark` PR that passes the path.

A trace with no `pdtpu/` span (the parent commit of PR 23; any program
without them) gives `None`, and every metric over it is left out of the
line; so is a metric none of whose spans is in the trace (a train cell,
which holds `pdtpu/train/` spans only).
A trace with spans and no device plane (the CPU test cells) gives the
per-step times over the program's own `dispatch` spans and no gap.
"""
from __future__ import annotations

import glob
import os
import tempfile
from typing import Optional

from .reduce import (DEVICE_PLANE, _total, _union, _window, module_name)

PREFIX = "pdtpu/"
SERVE = PREFIX + "serve/"
PUMP, ADMIT, EVICT, DRAFT, BUILD_ROWS, DISPATCH, FETCH, COMMIT, PUBLISH = (
    SERVE + n for n in ("pump", "admit", "evict", "draft", "build_rows",
                        "dispatch", "fetch", "commit", "publish"))
# which span encloses which, on the engine's one thread
PARENT = {EVICT: ADMIT, ADMIT: PUMP, DRAFT: PUMP, BUILD_ROWS: PUMP,
          DISPATCH: PUMP, FETCH: PUMP, COMMIT: PUMP, PUBLISH: PUMP}
# a gap second counts as attributed when any of these covers it: every
# serve span but the enclosing `pump` and `fetch`, in which the host only
# waits for the device
ATTRIBUTING = (ADMIT, EVICT, DRAFT, BUILD_ROWS, DISPATCH, COMMIT, PUBLISH)

_CACHE: dict = {}


def find_xplane(trace: Optional[dict] = None) -> Optional[str]:
    """The trace file this run's `harness.Window` wrote: the only
    `bench_trace_*` directory's, or among several the one whose
    `benchmark_window` is the reduced `trace`'s window. None, with a line
    that says why, where that cannot be told."""
    paths = sorted(glob.glob(os.path.join(
        tempfile.gettempdir(), "bench_trace_*", "plugins", "profile", "*",
        "*.xplane.pb")))
    if len(paths) <= 1:
        return paths[0] if paths else None
    want = None
    if trace and trace.get("annotated"):
        want = tuple(trace["devices"][0]["window_ns"])
    if want is not None:
        from jax.profiler import ProfileData
        for path in paths:
            if _window(ProfileData.from_file(path)) == want:
                return path
    print(f"host spans: {len(paths)} traces under {tempfile.gettempdir()} "
          "and none that is provably this run's: nothing read", flush=True)
    return None


def _intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint [start, end)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def read_xplane(path: str, main_module: str) -> Optional[dict]:
    """{"window_ns", "spans": {name: [[s, e], ...]}, "runs": [[s, e], ...]
    or None}: the program's spans (every `/host:` line; clipped to the
    window) and the runs of `main_module` on chip 0 that lie whole inside
    the window, as `reduce.py` counts them. None where the trace holds no
    `pdtpu/` span."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    window = _window(profile)
    spans: dict = {}
    runs = None
    chip0 = None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = int(e.start_ns)
                        spans.setdefault(e.name, []).append(
                            [s, s + int(e.duration_ns)])
        elif DEVICE_PLANE.match(plane.name) and (
                chip0 is None or plane.name < chip0.name):
            chip0 = plane
    if not spans:
        return None
    if chip0 is not None:
        for line in chip0.lines:
            if line.name == "XLA Modules":
                runs = sorted(
                    [int(e.start_ns), int(e.start_ns + e.duration_ns)]
                    for e in line.events
                    if module_name(e.name) == main_module)
    if window is None:
        edges = [x for iv in spans.values() for s_e in iv for x in s_e]
        window = (min(edges), max(edges))
    t0, t1 = window
    clipped = {}
    for name, ivs in spans.items():
        ivs = [[max(s, t0), min(e, t1)] for s, e in ivs
               if min(e, t1) > max(s, t0)]
        if ivs:
            clipped[name] = sorted(ivs)
    if runs is not None:
        runs = [[s, e] for s, e in runs if s >= t0 and e <= t1] or None
    return {"window_ns": [t0, t1], "spans": clipped, "runs": runs}


def summarize(raw: dict) -> dict:
    """Seconds per span name inside the window, the steps they are divided
    by, and (with a device plane) the idle gaps between consecutive runs,
    split by the span that covers them. Self time: a span's share of a gap
    less its children's, so the rows add up to the gap."""
    spans, runs = raw["spans"], raw["runs"]
    out = {"span_s": {n: _total(ivs) / 1e9 for n, ivs in spans.items()},
           "span_count": {n: len(ivs) for n, ivs in spans.items()}}
    if runs:
        out["steps"], out["steps_from"] = len(runs), "device runs"
    else:
        out["steps"] = len(spans.get(DISPATCH, []))
        out["steps_from"] = "dispatch spans (no device plane)"
    if not runs or len(runs) < 2:
        return out
    gaps = _union([runs[i][1], runs[i + 1][0]]
                  for i in range(len(runs) - 1))
    gap_ns = _total(gaps)
    in_gaps = {n: _intersect(_union(ivs), gaps) for n, ivs in spans.items()}
    by_span = {}
    for name, ivs in in_gaps.items():
        # a span without its children; a child whose parent the profiler
        # never saw whole (a pump open when the session stopped) keeps
        # its own time, and no row goes negative
        kids = _union(iv for child, parent in PARENT.items()
                      if parent == name for iv in in_gaps.get(child, []))
        by_span[name] = _total(ivs) - _total(_intersect(ivs, kids))
    everything = _union(iv for ivs in in_gaps.values() for iv in ivs)
    by_span["(no span)"] = gap_ns - _total(everything)
    cover = _union(iv for n in ATTRIBUTING for iv in spans.get(n, []))
    out.update(gap_s=gap_ns / 1e9, gaps=len(runs) - 1,
               gap_by_span_s={n: ns / 1e9 for n, ns in sorted(
                   by_span.items(), key=lambda kv: -kv[1])},
               attributed_s=_total(_intersect(cover, gaps)) / 1e9)
    return out


def summary(trace: Optional[dict], counters: dict) -> Optional[dict]:
    """The summary of this run's trace (cached: one run, one trace), or
    None where there is no trace or no span in it. `trace` is the reduced
    trace the reader was handed (None on the CPU). Prints the gap by span
    name once, on an earlier line of the run's output."""
    if "summary" in _CACHE:
        return _CACHE["summary"]
    path = find_xplane(trace)
    raw = path and read_xplane(path, counters.get("main_module", ""))
    result = summarize(raw) if raw else None
    _CACHE["summary"] = result
    if result is not None:
        _say(result, counters.get("main_module", ""))
    return result


def _say(s: dict, module: str):
    steps = max(s["steps"], 1)
    print(f"host spans: {s['steps']} step(s) by {s['steps_from']}; per "
          "step, ms: " + ", ".join(
              f"{n[len(PREFIX):]} {sec / steps * 1e3:.3f} "
              f"(x{s['span_count'][n]})"
              for n, sec in sorted(s["span_s"].items())), flush=True)
    if "gap_s" in s:
        print(f"host spans: idle between {module} runs {s['gap_s']:.4f}s "
              f"over {s['gaps']} gap(s), mean "
              f"{s['gap_s'] / s['gaps'] * 1e3:.3f} ms; by span (self "
              "time, s): " + ", ".join(
                  f"{n[len(PREFIX):] if n.startswith(PREFIX) else n} "
                  f"{sec:.4f}" for n, sec in s["gap_by_span_s"].items())
              + f"; attributed {s['attributed_s']:.4f}s", flush=True)


def ms_per_step(trace: Optional[dict], counters: dict,
                *names: str) -> Optional[float]:
    """Mean milliseconds a step spent in the named spans: their time in
    the window over the runs of the step in the window. A mean, not a
    median, so that the per-step metrics add up to the gap. None where the
    trace holds none of the named spans."""
    s = summary(trace, counters)
    if s is None or not s["steps"] \
            or not any(n in s["span_s"] for n in names):
        return None
    return sum(s["span_s"].get(n, 0.0) for n in names) / s["steps"] * 1e3


def gap_attributed_pct(trace: Optional[dict],
                       counters: dict) -> Optional[float]:
    s = summary(trace, counters)
    if s is None or not s.get("gap_s") \
            or not any(n in s["span_s"] for n in ATTRIBUTING):
        return None
    return 100.0 * s["attributed_s"] / s["gap_s"]
