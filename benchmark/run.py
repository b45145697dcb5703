#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell by name (`benchmark/workloads/<cell>.json`), its
configuration and traffic by the names the cell gives, its job kind as a
module of `benchmark/jobs/` and each per-layer metric as a module of
`benchmark/layer_metrics/`. Builds the system from `--seed`, warms the
cell's shapes (set-up), measures for `--seconds`, prints what it likes on
earlier lines and one JSON object on the last:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` the profiler runs round a shorter window of its own and the
metrics are the cell's per-layer metrics. Exits non-zero, with no result
line, when JAX finds no accelerator or fewer chips than the cell asks for,
and anywhere the program (`paddle_tpu`) is not importable.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cells-root", default=None,
                    help="directory holding workloads/, configs/, traffic/ "
                         "(default: benchmark/; the benchmark's own tests "
                         "point it at benchmark/tests/cells)")
    ap.add_argument("--check-grads", action="store_true",
                    help="train cells, outside the driver's runs: compare "
                         "the program's gradients with the reference's on "
                         "a two-layer model of the cell's widths, and exit")
    return ap.parse_args(argv)


def _traced_metrics(ctx, result, checks):
    """Per-layer metrics, the device's busy time and the breakdown, from the
    traced window. A reader that finds nothing to read returns None and its
    metric is left out of the line."""
    from benchmark import cells
    from benchmark.harness import say
    from benchmark.trace import reduce as R

    window = result["window"]
    trace = R.reduce_xplane(window.xplane) if window.xplane else None
    metrics, extra, breakdown = {}, {}, None
    if trace is not None:
        extra = {"busy_s": R.busy_s(trace), "window_s": R.window_s(trace)}
        breakdown = {"device_ops": R.top_ops(trace),
                     "idle_gaps": R.top_gaps(trace)}
        say(f"trace: {len(trace['devices'])} device plane(s), window "
            f"{extra['window_s']:.3f}s (annotated: {trace['annotated']}), "
            f"busy {extra['busy_s']:.3f}s")
        main = R.module_runs(trace, result["counters"]["main_module"])
        checks.add("the traced window holds the job's executable",
                   bool(main and main["count"] > 0),
                   f"{result['counters']['main_module']}: "
                   f"{main['count'] if main else 0} run(s)")
        for kernel in ctx.cell.get("kernels", []):
            seconds, calls = R.op_time_s(trace, kernel, opcode="custom-call")
            checks.add(f"kernel {kernel} ran on the device", calls > 0,
                       f"{calls:g} call(s), {seconds:.4f}s per chip")
    elif ctx.device["platform"] != "cpu":
        checks.add("the traced run left a device trace", False,
                   f"no device plane in {window.xplane!r}")
    for name in ctx.cell["layer_metrics"]:
        module = cells.metric_module(name)
        value = module.read(trace, result["counters"], ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": module.UNIT}
    return metrics, extra, breakdown


def main(argv=None) -> int:
    args = _parse(argv)
    import faulthandler
    from benchmark import cells, device as D, harness
    # a run still going at the contract's limit says where it is, and dies
    faulthandler.dump_traceback_later(harness.RUN_LIMIT_S - 50, exit=True)
    root = os.path.abspath(args.cells_root) if args.cells_root \
        else cells.BENCH_DIR
    cell = cells.load_cell(args.workload, root)
    try:
        import paddle_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the program is not importable from {REPO}: {e}",
              file=sys.stderr)
        return 4
    try:
        dev = D.require_devices(cell)
        peaks = D.peaks_for(dev)
    except D.DeviceError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    harness.say(f"cell {cell['name']}: config {cell['config']}, traffic "
                f"{cell['traffic']}, job {cell['job']}, {cell['chips']} "
                f"chip(s) of {dev['kind']!r}; seed {args.seed}, "
                f"{args.seconds:g}s, trace {args.trace}; compile cache "
                f"{cache}")
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), device=dev, peaks=peaks,
                          t_start=T_START)
    job = cells.job_module(cell)
    if args.check_grads:
        return 0 if job.check_grads(ctx).correct else 1
    result = job.run(ctx)
    checks, window = result["checks"], result["window"]
    result["counters"]["memory_window_bytes"] = window.memory_bytes
    peak = D.memory_bytes(cell["chips"], "peak_bytes_in_use")
    harness.say(f"memory, fullest chip: {window.memory_bytes / 1e9:.3f} GB "
                "held in the window (largest sample of bytes_in_use); "
                f"the process's peak, set-up included, {peak / 1e9:.3f} GB")
    try:
        if ctx.trace:
            metrics, extra, breakdown = _traced_metrics(ctx, result, checks)
        else:
            metrics = {name: {"value": float(result["end_to_end"][name]),
                              "unit": job.END_TO_END[name]}
                       for name in cell["end_to_end"]
                       if result["end_to_end"].get(name) is not None}
            extra, breakdown = {}, None
    finally:
        window.cleanup()
    harness.say("end to end (information in a traced run): " + ", ".join(
        f"{k} {v:.4f}" for k, v in result["end_to_end"].items()
        if v is not None))
    harness.say("counters: " + json.dumps(result["counters"]))
    line = {"correct": checks.correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": {**dev, "memory_peak_bytes": window.memory_bytes,
                       **extra}}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
