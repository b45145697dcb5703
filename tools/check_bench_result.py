"""Benchmark regression gate (reference: tools/check_op_benchmark_result.py:1,
which diffs develop-vs-PR op benchmark logs and fails the CI on speed
regressions). TPU analog: measured chip rows (a BENCH_SWEEP.json-style list,
or a {"results": [...]} document) are checked against pinned per-preset floors in
tools/bench_thresholds.json; an MFU drop beyond --max-regress fails the gate
(exit 2) instead of relying on judge-side JSON diffing.

Serving rows (`bench.py --serve`, ISSUE 3) gate through the same floors
file with direction-aware keys: `serve_qps` is a floor (throughput must not
drop) and `serve_p99_ms` is a CEILING (tail latency must not grow) —
`--update` only ever tightens in the favorable direction for each.

Provenance (ISSUE 9): bench rows embed `extra.provenance` (platform,
device kind, git sha, timestamp). `--update` pins the platform/device
kind alongside the floors (underscore keys, ignored by gating math); a
later run on a DIFFERENT platform refuses to compare those presets — a
CPU fallback number must never silently gate against a TPU pin. The
refusal is a warning by default and a failure (exit 3) under --strict.

    python tools/check_bench_result.py                 # gate current sweep
    python tools/check_bench_result.py --update        # raise floors to best
    python tools/check_bench_result.py --new f.json --max-regress 0.05
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_NEW = os.path.join(REPO, "BENCH_SWEEP.json")
THRESHOLDS = os.path.join(REPO, "tools", "bench_thresholds.json")


def _rows(path):
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):  # {"results": [...]} document
        data = data.get("results", [])
    return data


def _preset_of(row):
    metric = row.get("metric", "")
    parts = metric.split()
    # "tokens/sec/chip <preset> bs8 seq1024 ..." — the preset token
    if len(parts) >= 2 and "/" in parts[0]:
        p = parts[1]
        p = p[4:-1] if p.startswith("GPT(") else p
        # scan-fused rows ("... chunked32") key separately so a dedicated
        # floor can be pinned; absent one they gate against the base
        # preset's floor (resolved in main)
        if any(t.startswith("chunked") for t in parts[2:]):
            return f"{p}-chunked"
        return p
    return row.get("tag")


# gate-able metric keys and which direction is "better": a "higher" key
# pins a floor (regression = measured below it), a "lower" key pins a
# ceiling (regression = measured above it). comm_* keys come from
# `bench.py --comm` (ISSUE 4): bytes-on-wire and quantized-allreduce
# latency must never grow past their pinned ceilings. llm_* keys come from
# `bench.py --llm` (ISSUE 5): generated tokens/sec is a floor, p95
# time-to-first-token a ceiling.
GATE_KEYS = {"mfu": "higher", "serve_qps": "higher", "serve_p99_ms": "lower",
             "comm_bytes_per_step": "lower", "allreduce_ms": "lower",
             "llm_tok_s": "higher", "llm_ttft_ms": "lower",
             # ISSUE 6 overload-control gates: under the bench's 2x
             # overload phase, interactive-class p99 TTFT is a CEILING
             # (shedding must protect the premium tail) and the shed rate
             # itself is a ceiling (overload control, not overload panic)
             "llm_interactive_ttft_p99_ms": "lower",
             "llm_shed_rate": "lower",
             # ISSUE 7 chunked-prefill gates: short-prompt p99 TTFT under
             # the mixed long/short trace is a CEILING (chunk folding must
             # keep shorts from queueing behind long prefills), and so is
             # the count of prefill-ONLY dispatches (prefill chunks should
             # ride decode steps, not spend dispatches of their own)
             "llm_mixed_ttft_p99_ms": "lower",
             "llm_prefill_dispatches": "lower",
             # ISSUE 8 prefix-cache gates: under the 90%-shared-prefix
             # trace the token-weighted cache hit rate is a FLOOR (radix
             # matching must keep attaching cached blocks) and so is the
             # effective prompt-token service rate (prefix sharing is the
             # point: serving a prompt must not require recomputing it)
             "llm_prefix_hit_rate": "higher",
             "llm_shared_prefill_tok_s": "higher",
             # ISSUE 10 goodput-ledger gates: the live goodput ratio
             # (compute seconds / wall) and the ledger's live MFU are
             # FLOORS — telemetry overhead or a phase-accounting bug that
             # eats productive time must fail the gate. TPU-only by the
             # provenance platform pinning above (a CPU row never gates
             # against a TPU pin).
             "train_goodput": "higher",
             "train_mfu_live": "higher",
             # ISSUE 15 continuous-checkpointing gate (`bench.py --ckpt`):
             # the worst step-thread stall at any async save boundary is a
             # CEILING — the blocking cost of a snapshot is one host fetch,
             # and anything that drags persist work back onto the step
             # thread (lock contention, a sync fallback, CRC on the hot
             # path) must fail the gate. The async run's train_goodput
             # floor above gates the same row.
             "train_ckpt_stall_ms": "lower",
             # ISSUE 11 serving-economics gates: the unified mixed step's
             # token efficiency (useful / total fixed-width positions) and
             # the ledger's effective decode MFU are FLOORS; the pump's
             # host fraction (host seconds / wall) is a CEILING — host
             # bloat or a pad-waste regression must fail the gate. Same
             # provenance platform pinning as the train_* gates.
             "llm_token_efficiency": "higher",
             "llm_decode_mfu": "higher",
             "llm_host_fraction": "lower",
             # ISSUE 12 compile-observatory gates: the number of distinct
             # executables the fused train step builds and the total XLA
             # compile seconds it pays are CEILINGs — a change that
             # sprouts extra program variants (shape churn, lost cache
             # hits) or slower compiles must fail the gate
             "compile_executables": "lower",
             "compile_seconds_total": "lower",
             # ISSUE 13 numerics-observatory gate: the armed in-step
             # telemetry's step-time overhead (percent vs the unarmed
             # fused step) is a CEILING — the observatory must stay
             # effectively free, and growth past the pin fails the gate
             "train_numerics_overhead_pct": "lower",
             # ISSUE 14 fleet gates (`bench.py --fleet`): replayed-trace
             # qps scaling vs one replica is a FLOOR (adding replicas
             # must keep buying near-linear throughput; routing overhead
             # or accidental serialization fails the gate), and the
             # crash-to-all-streams-resumed failover time is a CEILING
             # (the zero-dropped-streams dance must stay fast)
             "fleet_qps_scaling": "higher",
             "fleet_failover_resume_ms": "lower",
             # ISSUE 16 rolling-deploy gates (`bench.py --deploy`): p99
             # TTFT measured across a full rolling weight swap of the
             # fleet is a CEILING (drain/swap/canary churn must not
             # starve admissions), and the count of streams dropped by
             # the rollout MUST stay 0 — the gate pins the zero-downtime
             # contract itself
             "deploy_ttft_p99_ms": "lower",
             "deploy_dropped_streams": "lower",
             # ISSUE 17 speculative-decoding gates (`bench.py --llm` spec
             # phase): batch-1 closed-loop tok/s with the draft model
             # attached is a FLOOR — pin it ABOVE the spec-off baseline
             # (llm_spec_base_tok_s, which rides along ungated) so the
             # dispatch-collapse win itself is regression-proof — and the
             # greedy acceptance rate is a FLOOR (a draft/target
             # divergence or a rollback bug craters the accept rate long
             # before it shows up in tok/s)
             "llm_spec_tok_s": "higher",
             "llm_spec_accept_rate": "higher",
             # ISSUE 18 sampling gates (`bench.py --llm` sampled phase):
             # per-slot seeded sampling rides the SAME fixed-width
             # unified step as greedy — only the select differs — so its
             # closed-loop tok/s is a FLOOR pinned within ~10% of the
             # greedy baseline (llm_sampled_base_tok_s rides along
             # ungated), and the host-side sampling-operand/grammar-mask
             # assembly cost, as a percent of pump wall time from the
             # ledger's sample_mask phase, is a CEILING
             "llm_sampled_tok_s": "higher",
             "llm_mask_overhead_pct": "lower",
             # ISSUE 19 tiered-KV / disaggregation gates (`bench.py --llm`
             # tiered phase): the warm-replay host-tier hit rate (fraction
             # of onboardable full-block prompt tokens actually served
             # from host RAM instead of re-prefilled) and the host→HBM
             # onboard token rate are FLOORS — a change that stops
             # spilling under pressure or re-prefills what the host tier
             # holds must fail the gate — and the p99 prefill→decode
             # handoff latency (export to re-place, router summary) is a
             # CEILING: staged-KV handoff must never degenerate into a
             # queued re-prefill
             "llm_tiered_hit_rate": "higher",
             "llm_onboard_tok_s": "higher",
             "llm_handoff_ms": "lower",
             # ISSUE 20 multi-LoRA gates (`bench.py --llm` lora phase):
             # one seeded Poisson trace replayed through an UNARMED
             # engine (base-only) then through an adapter-armed engine
             # with 8 concurrent adapters round-robined across the
             # slots. The armed tok/s is a FLOOR, and the armed-vs-base
             # throughput overhead percent is a CEILING (≤15% at pin
             # time): the gathered low-rank delta must stay a marginal
             # cost of the ONE unified step, never a per-adapter
             # dispatch (llm_lora_base_tok_s rides along ungated)
             "llm_lora_tok_s": "higher",
             "llm_lora_overhead_pct": "lower"}


def _metrics_of(row):
    """Every gate-able metric a row carries: {key: value}."""
    extra = row.get("extra") or {}
    out = {}
    v = extra.get("mfu", row.get("mfu_6nd"))
    if v is not None:
        out["mfu"] = float(v)
    for k in ("serve_qps", "serve_p99_ms", "comm_bytes_per_step",
              "allreduce_ms", "llm_tok_s", "llm_ttft_ms",
              "llm_interactive_ttft_p99_ms", "llm_shed_rate",
              "llm_mixed_ttft_p99_ms", "llm_prefill_dispatches",
              "llm_prefix_hit_rate", "llm_shared_prefill_tok_s",
              "train_goodput", "train_mfu_live", "train_ckpt_stall_ms",
              "llm_token_efficiency", "llm_decode_mfu",
              "llm_host_fraction",
              "compile_executables", "compile_seconds_total",
              "train_numerics_overhead_pct",
              "fleet_qps_scaling", "fleet_failover_resume_ms",
              "deploy_ttft_p99_ms", "deploy_dropped_streams",
              "llm_spec_tok_s", "llm_spec_accept_rate",
              "llm_sampled_tok_s", "llm_mask_overhead_pct",
              "llm_tiered_hit_rate", "llm_onboard_tok_s",
              "llm_handoff_ms",
              "llm_lora_tok_s", "llm_lora_overhead_pct"):
        if extra.get(k) is not None:
            out[k] = float(extra[k])
    return out


def _better(key, a, b):
    """True when measured value `a` beats `b` for this key's direction."""
    return a > b if GATE_KEYS[key] == "higher" else a < b


def _is_chip_row(row):
    if "error" in row:
        return False
    extra = row.get("extra") or {}
    backend = extra.get("backend", "tpu" if "mfu_6nd" in row else None)
    return backend == "tpu"


def best_by_preset(rows):
    """{preset: {key: best value}} — best per key in its own direction.
    Rows carrying `extra.provenance` contribute `_platform` /
    `_device_kind` underscore keys (provenance metadata, never gated as
    metrics)."""
    best = {}
    for r in rows:
        if not _is_chip_row(r):
            continue
        p = _preset_of(r)
        if not p:
            continue
        mets = _metrics_of(r)
        if not mets:
            continue
        cur = best.setdefault(p, {})
        for k, v in mets.items():
            if k not in cur or _better(k, v, cur[k]):
                cur[k] = v
        prov = (r.get("extra") or {}).get("provenance") or {}
        if prov.get("platform"):
            cur.setdefault("_platform", prov["platform"])
        if prov.get("device_kind"):
            cur.setdefault("_device_kind", prov["device_kind"])
    return {p: vals for p, vals in best.items() if vals}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--new", default=DEFAULT_NEW,
                    help="sweep/measured JSON with fresh chip rows")
    ap.add_argument("--thresholds", default=THRESHOLDS)
    ap.add_argument("--max-regress", type=float, default=0.05,
                    help="tolerated fractional MFU drop vs the pinned floor")
    ap.add_argument("--update", action="store_true",
                    help="raise floors to the best measured values")
    ap.add_argument("--strict", action="store_true",
                    help="fail (exit 3) when a measured row resolves to a "
                         "key with no pinned floor while floors exist")
    args = ap.parse_args(argv)

    floors = {}
    if os.path.exists(args.thresholds):
        with open(args.thresholds) as f:
            floors = json.load(f)

    measured = best_by_preset(_rows(args.new))
    if args.update:
        for p, vals in measured.items():
            for k, v in vals.items():
                if k.startswith("_"):  # provenance metadata: pin verbatim
                    floors.setdefault(p, {})[k] = v
                    continue
                cur = floors.get(p, {}).get(k)
                if cur is None or _better(k, v, cur):
                    floors.setdefault(p, {})[k] = round(v, 4)
        with open(args.thresholds, "w") as f:
            json.dump(floors, f, indent=1, sort_keys=True)
        print(f"updated {args.thresholds}: {floors}")
        return 0

    if not measured:
        print("no chip-measured rows in", args.new,
              "- nothing to gate; exit 0")
        return 0

    failures = []
    unmapped = []
    mismatched = []
    for p, vals in sorted(measured.items()):
        # provenance guard: numbers measured on a different platform than
        # the pinned floor are not comparable — refuse rather than gate a
        # CPU-fallback row against a TPU pin (or vice versa)
        pin_plat = floors.get(p, {}).get("_platform")
        meas_plat = vals.get("_platform")
        if pin_plat and meas_plat and pin_plat != meas_plat:
            mismatched.append(p)
            print(f"WARNING: {p!r} was measured on platform "
                  f"{meas_plat!r} but its floors are pinned from "
                  f"{pin_plat!r}; refusing to compare (re-pin with "
                  "--update on the target platform)", file=sys.stderr)
            continue
        gated_any = False
        for k, m in sorted(vals.items()):
            if k.startswith("_"):   # provenance metadata, not a metric
                continue
            floor = floors.get(p, {}).get(k)
            if floor is None and k == "mfu" and p.endswith("-chunked"):
                # scan fusion must never be slower than the eager floor: a
                # chunked row without its own pinned floor gates against the
                # base preset's (keeps --strict meaningful for fused runs)
                floor = floors.get(p[: -len("-chunked")], {}).get("mfu")
            if floor is None:
                continue
            gated_any = True
            if GATE_KEYS[k] == "higher":
                limit = floor * (1.0 - args.max_regress)
                ok = m >= limit
            else:  # ceiling key (serve_p99_ms): growing past it regresses
                limit = floor * (1.0 + args.max_regress)
                ok = m <= limit
            verdict = "OK" if ok else "REGRESSION"
            print(f"  {p:28s} {k} {m:.4f}  pinned {floor:.4f} "
                  f"(limit {limit:.4f})  {verdict}")
            if not ok:
                failures.append((p, k, m, floor))
        if not gated_any:
            if floors:
                # a row that matches no pinned floor silently weakens the
                # gate — shout, so a renamed metric/tag can't make the
                # regression check vacuous without anyone noticing
                unmapped.append(p)
                print(f"WARNING: measured key {p!r} has no pinned floor in "
                      f"{args.thresholds} (known: "
                      f"{', '.join(sorted(floors))}); this row does NOT "
                      "gate — fix the tag mapping or pin a floor",
                      file=sys.stderr)
            else:
                stats = " ".join(f"{k} {m:.4f}" for k, m in sorted(
                    vals.items()) if not k.startswith("_"))
                print(f"  {p:28s} {stats}  (no pinned floor - pass)")
    if failures:
        print(f"FAILED: {len(failures)} metric(s) regressed beyond "
              f"{args.max_regress:.0%}:",
              ", ".join(f"{p}.{k} {m:.4f} vs {f0:.4f}"
                        for p, k, m, f0 in failures))
        return 2
    if args.strict and (unmapped or mismatched):
        parts = []
        if unmapped:
            parts.append(f"{len(unmapped)} measured key(s) gate nothing: "
                         f"{', '.join(unmapped)}")
        if mismatched:
            parts.append(f"{len(mismatched)} preset(s) measured on a "
                         "different platform than their pinned floors: "
                         f"{', '.join(mismatched)}")
        print("FAILED (--strict): " + "; ".join(parts))
        return 3
    print("bench gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
