"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: per-device busy intervals, per-name operation time,
collective time, the runs of each executable and the idle gaps between them.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
`/device:TPU:<n>`, with the lines
  `XLA Modules`    one event per run of an executable, named
                   `jit_<function>(<fingerprint>)`;
  `XLA Ops`        one event per HLO instruction run on the core, named by
                   the instruction's whole text (`%name = shape opcode(...)`);
                   a `while` holds its body's events inside its own span;
  `Async XLA Ops`  the in-flight spans of asynchronous copies and
                   collectives (start..done), which overlap the core's work.
Host threads are lines of the plane `/host:CPU`, on the same clock, so a
`jax.profiler.TraceAnnotation` the benchmark puts round its window marks the
window on the trace's clock.

Busy is the union of the `XLA Ops` intervals. An operation's time is its
self time: its span less the spans of the events nested in it, so that a
`while` is charged only its own overhead. The core runs one instruction at
a time, so the self time of a collective instruction (a synchronous
collective, or the `-done` that waits for an asynchronous one) is time in
which no compute ran: that is the exposed collective time.

Pure Python over `jax.profiler.ProfileData`; no TensorFlow, no protobuf
schema. A trace with no device plane (a CPU run) reduces to `None`.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Iterable, Optional

WINDOW_ANNOTATION = "benchmark_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
CONTAINER_OPCODES = ("while", "conditional", "call")
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast", "ragged-all-to-all")
_BRACES = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_TRAILING_ID = re.compile(r"(\.\d+)+$")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")
_KIND = re.compile(r"kind=(k\w+)")


def parse_op(event_name: str) -> tuple:
    """(`label`, `opcode`) of an `XLA Ops` event. The label is the
    instruction's name without its `.N`, e.g. `jvp_flash_fwd_`; a fusion
    that XLA left unnamed (`fusion.778`) is told from the others by its
    kind and result: `fusion kLoop bf16[50304,2048]`. Text that is not an
    HLO instruction comes back as `(text, "")`."""
    text = event_name.strip()
    if not text.startswith("%") or " = " not in text:
        return _TRAILING_ID.sub("", text), ""
    name, rest = text[1:].split(" = ", 1)
    plain = _BRACES.sub("", rest)
    m = _OPCODE.search(plain)
    base, opcode = _TRAILING_ID.sub("", name), (m.group(1) if m else "")
    if base == "fusion" and m:
        kind = _KIND.search(rest)
        base = (f"fusion {kind.group(1) if kind else ''} "
                f"{plain[:m.start(1)].strip()[:48]}")
    return base, opcode


def is_collective(base: str, opcode: str) -> bool:
    return (opcode.startswith(COLLECTIVE_PREFIXES)
            or base.startswith(COLLECTIVE_PREFIXES))


def module_name(event_name: str) -> str:
    """`jit_step(6892548630688767988)` -> `jit_step`."""
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: Iterable) -> list:
    """Sorted disjoint [start, end) covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


def _events(plane, line_name: str) -> list:
    """[(start_ns, end_ns, name)] of one line, as integers."""
    for line in plane.lines:
        if line.name == line_name:
            return [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in line.events]
    return []


def _window(profile) -> Optional[tuple]:
    """The benchmark's own window annotation, on the trace's clock."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_ANNOTATION:
                    return (int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
    return None


def _reduce_device(plane, window) -> dict:
    ops = _events(plane, "XLA Ops")
    modules = sorted(_events(plane, "XLA Modules"))
    asyncs = _events(plane, "Async XLA Ops")
    if window is None:
        spans = ops + modules
        window = (min(s for s, _, _ in spans), max(e for _, e, _ in spans))
    t0, t1 = window

    # ---- self time per instruction, by nesting -------------------------
    parsed = {}
    per_op = {}        # base -> [self_ns, count, opcode]
    top_level = []     # intervals with nothing around them: the busy set
    collective_self = 0
    stack = []         # [end, self_ns, base, opcode]

    def close(item):
        nonlocal collective_self
        _, self_ns, base, opcode = item
        row = per_op.setdefault(base, [0, 0, opcode])
        row[0] += max(self_ns, 0)
        row[1] += 1
        if is_collective(base, opcode) and opcode not in CONTAINER_OPCODES:
            collective_self += max(self_ns, 0)

    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        s, e = _clip(s, e, t0, t1)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if name not in parsed:
            parsed[name] = parse_op(name)
        base, opcode = parsed[name]
        if stack:
            stack[-1][1] -= e - s
        else:
            top_level.append((s, e))
        stack.append([e, e - s, base, opcode])
    while stack:
        close(stack.pop())
    busy = _union(top_level)

    # ---- runs of each executable, and the gaps between them -------------
    runs = {}
    in_window = [(s, e, module_name(n)) for s, e, n in modules
                 if s >= t0 and e <= t1]
    for s, e, n in in_window:
        runs.setdefault(n, []).append((s, e))
    module_rows = {}
    for n, spans in runs.items():
        module_rows[n] = {
            "count": len(spans),
            "durations_ns": [e - s for s, e in spans],
            "gaps_ns": [spans[i + 1][0] - spans[i][1]
                        for i in range(len(spans) - 1)],
            "total_ns": sum(e - s for s, e in spans)}

    # ---- idle gaps, labelled by the executables on either side ----------
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    labelled = {}
    starts = [s for s, _, _ in in_window]
    for gs, ge in gaps:
        i = bisect.bisect_right(starts, gs) - 1     # run that began last
        prev = in_window[i] if i >= 0 else None
        nxt = in_window[i + 1] if i + 1 < len(in_window) else None
        if prev is not None and prev[1] >= ge:
            label = f"inside {prev[2]}"
        elif prev is not None and nxt is not None and prev[2] == nxt[2]:
            label = f"between {prev[2]} runs"
        elif prev is None or nxt is None:
            label = "window edge"
        else:
            label = f"other ({prev[2]} -> {nxt[2]})"
        row = labelled.setdefault(label, [0, 0])
        row[0] += ge - gs
        row[1] += 1

    in_flight = _union(
        _clip(s, e, t0, t1) for s, e, n in asyncs
        if is_collective(*parse_op(n)))
    return {
        "plane": plane.name,
        "window_ns": [t0, t1],
        "busy_ns": _total(busy),
        "ops": {k: {"self_ns": v[0], "count": v[1], "opcode": v[2]}
                for k, v in per_op.items()},
        "modules": module_rows,
        "idle_gaps": {k: {"ns": v[0], "count": v[1]}
                      for k, v in labelled.items()},
        "collective_exposed_ns": collective_self,
        "collective_in_flight_ns": _total(in_flight),
    }


def reduce_xplane(path: str) -> Optional[dict]:
    """The reduced trace, or None where the file holds no device plane."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    window = _window(profile)
    def has_ops(plane):
        return any(line.name == "XLA Ops" and any(True for _ in line.events)
                   for line in plane.lines)

    devices = [_reduce_device(p, window) for p in profile.planes
               if DEVICE_PLANE.match(p.name) and has_ops(p)]
    if not devices:
        return None
    devices.sort(key=lambda d: d["plane"])
    return {"devices": devices, "annotated": window is not None}


# ---- what the metric readers ask of a reduced trace -------------------------

def window_s(trace: dict) -> float:
    t0, t1 = trace["devices"][0]["window_ns"]
    return (t1 - t0) / 1e9


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    devs = trace["devices"]
    return sum(d["busy_ns"] for d in devs) / len(devs) / 1e9


def op_time_s(trace: dict, *needles: str, opcode: Optional[str] = None
              ) -> tuple:
    """(seconds, events) of the instructions whose name holds one of
    `needles`, averaged over the chips. Kernels are found by the `name=`
    their `pallas_call` was given, which JAX carries into the instruction
    name (`jvp_flash_fwd_`, `transpose_jvp_flash_bwd_dq__`,
    `paged_attention`)."""
    ns = n = 0
    for d in trace["devices"]:
        for base, row in d["ops"].items():
            if opcode is not None and row["opcode"] != opcode:
                continue
            if any(x in base for x in needles):
                ns += row["self_ns"]
                n += row["count"]
    k = len(trace["devices"])
    return ns / k / 1e9, n / k


def module_runs(trace: dict, name: str) -> Optional[dict]:
    """The runs of executable `name` on the first chip (every chip of an
    SPMD program runs the same executables)."""
    return trace["devices"][0]["modules"].get(name)


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds]] of the instructions with most self time, averaged
    over the chips, `.N` suffixes merged."""
    total = {}
    for d in trace["devices"]:
        for base, row in d["ops"].items():
            total[base] = total.get(base, 0) + row["self_ns"]
    k = len(trace["devices"])
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in rows]


def top_gaps(trace: dict, n: int = 10) -> list:
    """[[what the gap lies between, seconds]], largest total first,
    averaged over the chips."""
    total = {}
    for d in trace["devices"]:
        for label, row in d["idle_gaps"].items():
            total[label] = total.get(label, 0) + row["ns"]
    k = len(trace["devices"])
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / k / 1e9] for label, ns in rows]


def median(xs: list) -> Optional[float]:
    return float(statistics.median(xs)) if xs else None
