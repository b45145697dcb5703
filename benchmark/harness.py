"""What every job kind shares: the run's context, the measured window (with
the profiler round it in a traced run), the checks that decide `correct`.
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Optional

from .trace.reduce import WINDOW_ANNOTATION, find_xplane


# the contract's limit on a cell's first run in a checkout, compilation
# included; nothing in a run waits longer than this for anything
RUN_LIMIT_S = 1200.0


def say(msg: str):
    """An earlier line of the output; the last line is the result alone."""
    print(msg, flush=True)


@dataclasses.dataclass
class Context:
    cell: dict
    seed: int
    seconds: float
    trace: bool
    device: dict                 # {"platform", "kind", "count"}
    peaks: Optional[dict]        # the device's row of peaks.json
    t_start: float               # perf_counter at process start

    @property
    def config(self) -> dict:
        return self.cell["config_data"]

    @property
    def traffic(self) -> dict:
        return self.cell["traffic_data"]

    @property
    def window_seconds(self) -> float:
        """A traced run measures a shorter window of its own: traces are
        large and tracing slows the host."""
        if self.trace:
            return min(self.seconds,
                       float(self.cell.get("trace_seconds", self.seconds)))
        return self.seconds


class Checks:
    """Named pass/fail checks; `correct` is their conjunction."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, ok: bool, detail: str = ""):
        self.rows.append((name, bool(ok), detail))
        say(f"  [{'ok' if ok else 'FAILED'}] {name}"
            + (f": {detail}" if detail else ""))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(ok for _, ok, _ in self.rows)


class Window:
    """The measured window. Entering it ends set-up: from here on a
    compilation is an error. In a traced run the profiler runs from just
    before it to just after it, and a trace annotation marks the window on
    the trace's clock. `t0`/`t1` are host-clock seconds (perf_counter).
    `memory_bytes` is the most any chip of the cell held at the window's
    two ends and wherever the job called `sample_memory()` in between: what
    the measured work holds, without set-up's transients."""

    def __init__(self, ctx: Context):
        from paddle_tpu.obs.goodput import RecompileSentinel
        self.ctx = ctx
        self.sentinel = RecompileSentinel().install()
        self.t0 = self.t1 = None
        self.memory_bytes = 0
        self.trace_dir = None
        self.xplane = None
        self._ann = None

    def __enter__(self):
        import jax
        if self.ctx.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # no per-call Python events
            opts.host_tracer_level = 1     # annotations only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
            self._ann.__enter__()
        self.sample_memory()
        self.sentinel.mark_warm()
        self.t0 = time.perf_counter()
        return self

    def sample_memory(self):
        from .device import memory_bytes
        self.memory_bytes = max(self.memory_bytes, memory_bytes(
            self.ctx.cell["chips"], "bytes_in_use"))

    def close(self, t1: Optional[float] = None):
        """End the window at `t1` (default: now)."""
        if self.t1 is not None:
            return
        self.t1 = time.perf_counter() if t1 is None else t1
        self.sample_memory()
        self.compilations = self.sentinel.recompiles
        if self._ann is not None:
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.xplane = find_xplane(self.trace_dir)
        self.sentinel.uninstall()

    def __exit__(self, *exc):
        self.close()
        return False

    def cleanup(self):
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def setup_s(self) -> float:
        return self.t0 - self.ctx.t_start


def check_kernel_paths(ctx: Context, checks: Checks):
    """Which path each kernel trace took (`ops/pallas_mode.KERNEL_TRACES`,
    always on). On the chip every kernel the cell names must have been
    traced through Mosaic and nothing may have taken an XLA stand-in; on the
    CPU, where the test cells run, the paths are printed and not judged."""
    from paddle_tpu.ops import pallas_mode
    paths = {f"{k}/{p}": n
             for (k, p), n in sorted(pallas_mode.KERNEL_TRACES.items())}
    if ctx.device["platform"] == "cpu":
        say(f"  kernel trace paths (cpu, not judged): {paths}")
        return
    missing = [k for k in ctx.cell.get("kernels", [])
               if not pallas_mode.KERNEL_TRACES.get((k, "mosaic"))]
    stand_ins = [k for k in paths if not k.endswith("/mosaic")]
    checks.add("every kernel took the Mosaic path",
               not missing and not stand_ins,
               f"{paths}" + (f"; not traced: {missing}" if missing else ""))


def build_model(ctx: Context, recompute: bool = False):
    """The program's model at the cell's configuration, holding the
    benchmark's seeded weights. Returns (model, weights)."""
    from . import cells, weights as W
    family = cells.family_module(ctx.config)
    t = time.perf_counter()
    model = family.build(ctx.config, recompute)
    t_built = time.perf_counter() - t
    shapes = {k: tuple(p.shape) for k, p in model.named_parameters()}
    t = time.perf_counter()
    weights = W.seeded_weights(shapes, ctx.seed, ctx.config["dtype"])
    W.load_into(model, weights)
    import jax
    jax.block_until_ready(weights)
    n = sum(int(v.size) for v in weights.values())
    if n != family.total_params(ctx.config):
        raise ValueError(f"the program's model has {n} parameters, the "
                         f"family's arithmetic says "
                         f"{family.total_params(ctx.config)}")
    say(f"model {ctx.config['name']}: {n / 1e9:.3f} B parameters "
        f"({ctx.config['dtype']}); program constructor {t_built:.1f}s, "
        f"seeded weights {time.perf_counter() - t:.1f}s")
    return model, weights
