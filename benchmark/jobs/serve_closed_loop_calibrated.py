"""Job kind `serve-closed-loop-calibrated`: `serve-closed-loop` for a cell
whose model the benchmark's common rules misfit, so that the comparison
that decides `correct` can see what the cell is there to guard. The run,
the load, the window and every number are `serve_closed_loop.run`'s; two
things come from the cell's own files instead of the harness's constants:

- **Initial values, leaf by leaf** (`"leaf_seeding"` in the configuration
  file: `{leaf-name suffix: ["uniform", lo, hi] | ["normal", mean, std]}`).
  `weights.py`'s rule (matrices N(0, 0.02), 1-D leaves 1, biases 0) is
  GPT-2's and suits a fan-in of thousands. A leaf it misfits (a depthwise
  conv's four taps, a decay rate, a step-size bias) is drawn again from
  `--seed` and the leaf's name, in the program's model and in the dict the
  reference is given alike.
- **The limits of the comparison with the reference** (`"limits"` in the
  workload file: `mean` and `max` |difference of log-probabilities|,
  `margin` of the greedy token below the reference's best logit), where
  `serve_closed_loop.TOLERANCE` has one set per dtype, measured on logits
  of spread ~1.3. A cell whose logits are scaled down passes those with
  anything.

Both are set from two readings, which `controls()` takes (outside the
driver's runs; `python3 -m benchmark.jobs.serve_closed_loop_calibrated
--workload <cell> --seed <n>`): what the sound program gives, and what two
faults give that must come out not `correct` through this job's own
comparison: the recurrent state wiped after every step, and the reference
computed from weights held in the nearest precision below the
configuration's. `PERF.md` holds the readings the limits were set from.

The harness's two constants are module attributes of files that are not
this job's to edit, so `_cell_rules` swaps them for the length of one call
and puts them back; a `benchmark` PR that teaches `harness.build_model`
and `serve_closed_loop` to read the two keys retires this file.
"""
from __future__ import annotations

import contextlib
import zlib
from unittest import mock

import numpy as np

from .. import cells, harness
from ..harness import say
from . import serve_closed_loop as base

END_TO_END = base.END_TO_END
# the nearest precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def reseed_leaves(model, weights: dict, rules: dict, seed: int) -> list:
    """Draw every leaf whose name ends in one of `rules`' keys again, from
    `seed` and the leaf's name (numpy on the host: these leaves are small),
    in `model` and in `weights`. Returns the names redrawn."""
    import jax.numpy as jnp
    named = dict(model.named_parameters())
    done = []
    for name in sorted(weights):
        rule = next((r for suffix, r in rules.items()
                     if name.endswith(suffix)), None)
        if rule is None:
            continue
        kind, a, b = rule
        rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
        if kind not in ("uniform", "normal"):
            raise cells.CellError(f"leaf_seeding {name}: {rule!r}")
        value = rng.uniform(a, b, weights[name].shape) if kind == "uniform" \
            else rng.normal(a, b, weights[name].shape)
        weights[name] = jnp.asarray(value, weights[name].dtype)
        named[name].data = weights[name]
        done.append(name)
    return done


@contextlib.contextmanager
def _cell_rules(ctx):
    """`harness.build_model` followed by the configuration's
    `leaf_seeding`, and the cell's `limits` where `serve_closed_loop` looks
    up its dtype's, for the length of the block."""
    limits = ctx.cell.get("limits")
    if not limits or set(limits) != {"mean", "max", "margin"}:
        raise cells.CellError(
            f"cell {ctx.cell['name']!r}: job serve-closed-loop-calibrated "
            f"needs \"limits\": {{mean, max, margin}}, got {limits!r}")
    rules = ctx.config.get("leaf_seeding", {})
    build = harness.build_model

    def build_model(ctx, recompute=False):
        model, weights = build(ctx, recompute)
        done = reseed_leaves(model, weights, rules, ctx.seed)
        say(f"leaf_seeding {rules}: {len(done)} leaves drawn again; limits "
            f"of the comparison with the reference {limits}")
        return model, weights

    with mock.patch.object(harness, "build_model", build_model), \
            mock.patch.dict(base.TOLERANCE, {ctx.config["dtype"]: limits}):
        yield


def run(ctx: harness.Context) -> dict:
    with _cell_rules(ctx):
        return base.run(ctx)


# ---- the two readings the limits lie between ----

class _Replay:
    """Stands where the engine stands in the job's comparison: passes the
    check requests on to `engine` and keeps its answers; with no engine,
    gives the kept answers again, in the order they were asked for."""

    def __init__(self, engine):
        self.engine, self.answers, self._next = engine, [], 0

    def submit(self, prompt, **kwargs):
        if self.engine is not None:
            self.answers.append(_Answer(self.engine.submit(prompt,
                                                           **kwargs)))
            return self.answers[-1]
        self._next += 1
        return self.answers[self._next - 1]


class _Answer:
    def __init__(self, handle):
        self._handle, self._kept = handle, None

    def result(self, timeout=None):
        if self._kept is None:
            tokens = np.asarray(self._handle.result(timeout=timeout))
            self._kept = (tokens, list(self._handle.logprobs_so_far()))
            self._handle = None
        return self._kept[0]

    def logprobs_so_far(self):
        return self._kept[1]


def _wipe_recurrence(model):
    """From here on the model's cached forward hands back every recurrent
    layer's state as zeros: a row's next step starts from nothing."""
    from paddle_tpu.models.generation import RecurrentState
    recurrent = [isinstance(c, RecurrentState)
                 for c in model.init_cache(1, 1)]
    if not any(recurrent):
        raise cells.CellError("the model has no recurrent layer to wipe")
    plain = model.forward_with_cache

    def wiped(*args, **kwargs):
        logits, caches = plain(*args, **kwargs)
        return logits, [(a, b * 0) if rec else (a, b)
                        for (a, b), rec in zip(caches, recurrent)]

    model.forward_with_cache = wiped


def controls(ctx: harness.Context) -> dict:
    """{reading: Checks}: "sound", then the two faults. The job's own
    comparison throughout (`serve_closed_loop._check_against_reference`
    under the cell's limits)."""
    import jax
    from paddle_tpu import serving

    out = {}

    def compare(name, engine, weights):
        say(f"==== {name}")
        out[name] = harness.Checks()
        base._check_against_reference(ctx, engine, weights, out[name])
        say(f"==== {name}: correct {out[name].correct}")

    with _cell_rules(ctx):
        model, weights = harness.build_model(ctx)
        model.eval()
        config = base._engine_config(ctx.traffic)
        engine = serving.LLMEngine(model, config).start()
        sound = _Replay(engine)
        try:
            compare("sound", sound, weights)
        finally:
            engine.stop(drain=False, timeout=30)
        sound.engine = None

        _wipe_recurrence(model)
        engine = serving.LLMEngine(model, config).start()
        try:
            compare("recurrent state wiped after every step", engine,
                    weights)
        finally:
            engine.stop(drain=False, timeout=30)
        del model.forward_with_cache, engine

        # the sound engine's answers against the reference given the same
        # weights held in the next precision down (the engine and its pool
        # are gone by now: the chip need not hold them beside the copy)
        lower = LOWER[ctx.config["dtype"]]
        low = {k: v.astype(lower) if v.ndim >= 2 else v
               for k, v in weights.items()}
        jax.block_until_ready(low)
        compare(f"reference from matrices held in {lower}", sound, low)
    return out


def main(argv=None) -> int:
    import argparse
    import os
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="the readings a calibrated "
                                 "cell's limits lie between")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells-root", default=None)
    args = ap.parse_args(argv)
    from .. import device as D
    cell = cells.load_cell(args.workload, os.path.abspath(args.cells_root)
                           if args.cells_root else cells.BENCH_DIR)
    dev = D.require_devices(cell)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=1.0,
                          trace=False, device=dev, peaks=D.peaks_for(dev),
                          t_start=t_start)
    results = controls(ctx)
    verdict = {name: checks.correct for name, checks in results.items()}
    want = {name: name == "sound" for name in verdict}
    say(f"controls: {verdict}; as they should be: {verdict == want}")
    return 0 if verdict == want else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
