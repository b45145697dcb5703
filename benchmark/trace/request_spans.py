"""A request's way to its first token, read from the program's
`pdtpu/serve/request/*` spans in a raw profiler trace.

The engine stamps every request at four boundaries and, at its first
token, writes one `pdtpu/serve/request/first_token` event whose stats are
what the stamps say: `queued_ms` (in a class queue), `bound_ms` (a slot is
bound, no launched step carries it yet), `prefill_ms` (the chunks but the
last), `first_fetch_ms` (the last chunk's step queued, run, fetched and
committed), which add up to the engine's `ttft_ms`, and
`steps_to_first_token`. Every `pdtpu/serve/dispatch` event carries
`slots_vacant_queued`: the slots that carried no row in the step being
launched while as many requests were queued.

This module reads, from the `.xplane.pb` that `host_spans.find_xplane`
finds: the `first_token` events whose start lies inside
`benchmark_window`, the `dispatch` events inside it, and how many of those
requests have their `submit` event in the trace too (a request submitted
before the profiler started has none: its `queued_ms` still counts, the
engine's clock was running). The per-layer metrics are MEANS over those
requests, so that the four add up, as `host_*_ms_per_step` do.

A trace without such events (the parent commit of PR 49; a train cell)
gives `None`, and every metric over it is left out of the line.
"""
from __future__ import annotations

import statistics
from typing import Optional

from . import host_spans as H
from .reduce import _window

FIRST_TOKEN = H.SERVE + "request/first_token"
SUBMIT = H.SERVE + "request/submit"
PHASES = ("queued_ms", "bound_ms", "prefill_ms", "first_fetch_ms")
STEPS = "steps_to_first_token"
VACANT = "slots_vacant_queued"

_CACHE: dict = {}


def read_xplane(path: str) -> Optional[dict]:
    """{"requests": [{"rid", "ttft_ms", <PHASES>, STEPS, ...}],
    "submits_inside": n, "vacant": [slots_vacant_queued of each dispatch
    in the window that carries it]}; None where the trace holds neither a
    `first_token` event nor a `dispatch` with the stat. With no
    `benchmark_window` in the trace every event counts."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    window = _window(profile)
    t0, t1 = window if window is not None else (float("-inf"), float("inf"))
    requests, submitted, vacant = [], set(), []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == SUBMIT:
                    submitted.add(dict(e.stats).get("rid"))
                elif not t0 <= e.start_ns < t1:
                    continue
                elif name == FIRST_TOKEN:
                    requests.append(dict(e.stats))
                elif name == H.DISPATCH:
                    stats = dict(e.stats)
                    if VACANT in stats:
                        vacant.append(int(stats[VACANT]))
    if not requests and not vacant:
        return None
    return {"requests": requests, "vacant": vacant,
            "submits_inside": sum(1 for r in requests
                                  if r.get("rid") in submitted)}


def summarize(raw: dict) -> dict:
    """Means and medians over the requests read, and the sums that must
    agree: the four means together beside the mean of the engine's own
    `ttft_ms` over the same requests."""
    reqs = raw["requests"]
    out = {"requests": len(reqs), "submits_inside": raw["submits_inside"],
           "dispatches": len(raw["vacant"]),
           "vacant_slot_steps": sum(raw["vacant"])}
    if reqs:
        for key in PHASES + (STEPS, "ttft_ms"):
            values = [float(r[key]) for r in reqs]
            out[key] = {"mean": statistics.fmean(values),
                        "median": statistics.median(values)}
        out["sum_of_means_ms"] = sum(out[k]["mean"] for k in PHASES)
    return out


def summary(trace: Optional[dict]) -> Optional[dict]:
    """The summary of this run's trace (cached: one run, one trace), or
    None where there is no trace or no such event in it. `trace` is the
    reduced trace the reader was handed (None on the CPU). Says what it
    read once, on an earlier line of the run's output."""
    if "summary" not in _CACHE:
        path = H.find_xplane(trace)
        raw = path and read_xplane(path)
        _CACHE["summary"] = summarize(raw) if raw else None
        if _CACHE["summary"] is not None:
            _say(_CACHE["summary"])
    return _CACHE["summary"]


def _say(s: dict):
    said = (f"request spans: {s['requests']} first token(s) in the window "
            f"({s['submits_inside']} with their submit in the trace too)")
    if s["requests"]:
        said += "; mean / median, ms: " + ", ".join(
            f"{k[:-3]} {s[k]['mean']:.3f} / {s[k]['median']:.3f}"
            for k in PHASES) \
            + (f"; the four means add up to {s['sum_of_means_ms']:.3f}, the "
               f"engine's ttft_ms has the mean {s['ttft_ms']['mean']:.3f} "
               f"(median {s['ttft_ms']['median']:.3f}); steps to the first "
               f"token {s[STEPS]['mean']:.3f} / {s[STEPS]['median']:g}")
    print(said + f"; {s['vacant_slot_steps']} slot(s) vacant while queued "
          f"over {s['dispatches']} dispatch(es)", flush=True)


def mean_of(trace: Optional[dict], key: str) -> Optional[float]:
    """Mean of one stat of the `first_token` events in the window."""
    s = summary(trace)
    return s[key]["mean"] if s is not None and s["requests"] else None


def vacant_queued_pct(trace: Optional[dict], slots) -> Optional[float]:
    """Slots vacant while somebody was queued, summed over the window's
    launches, as a share of launches x slots."""
    s = summary(trace)
    if s is None or not s["dispatches"] or not slots:
        return None
    return 100.0 * s["vacant_slot_steps"] / (s["dispatches"] * int(slots))
