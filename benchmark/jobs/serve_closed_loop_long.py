"""Job kind `serve-closed-loop-long`: `serve-closed-loop-calibrated` for a
cell whose requests are thousands of tokens long. The run, the load, the
window and every end-to-end number are `serve_closed_loop.run`'s; the
configuration's `leaf_seeding` and the cell's `limits` are read as
`serve_closed_loop_calibrated` reads them. Two things more:

- **The reference's scores in blocks.** `reference/common.py` asks the
  reference for the logits of all four check requests at once, padded to
  the mix's longest request: `[4, 8320, 98304]` float32 is 13 GB here. The
  same two numbers a token (the log-softmax at the next token; the best
  logit minus the next token's) are taken from the reference's final hidden
  states with the head applied to `HEAD_BLOCK` positions at a time
  (`blockwise_scores`; the family's reference gives `hidden_and_head`).
  The formula is `common._logprob_fn`'s; only what is alive at once
  differs. A family whose reference has no `hidden_and_head` keeps
  `common.next_token_scores`.
- **The program's counters at the window's edges.** `LLMMetrics.counters`
  (`window_kv_tokens`, `full_kv_tokens`, `unified_steps`; always on) read
  when the window opens and when it closes, for the per-layer metrics that
  need a step's mean of them (`layer_metrics/_window.py`), and the pool's
  bytes by kind. A program without those counters leaves the keys out.

`serve_closed_loop.run` builds its engine and its window itself, so this
file stands a recorder where `serving.LLMEngine` and `harness.Window` are
looked up, for the length of one call, as `serve_closed_loop_calibrated`
does with the harness's two constants; a `benchmark` PR that lets a job
hand `run` a scorer and a counter hook retires both files.

`controls()` takes the readings the cell's limits lie between (outside
the driver's runs; `python3 -m benchmark.jobs.serve_closed_loop_long
--workload <cell> --seed <n>`): the sound program, and three references
that must come out not `correct` against it through this job's own
comparison: computed without the window (full attention in every layer),
with the plain rotary embedding in the full-attention layers (no YaRN), and
from matrices held in the nearest precision below the configuration's.
"""
from __future__ import annotations

import contextlib
import functools
import json
import sys
from unittest import mock

from .. import cells, harness
from ..harness import say
from ..reference import common as ref_common
from . import serve_closed_loop as base
from . import serve_closed_loop_calibrated as calibrated

END_TO_END = base.END_TO_END
HEAD_BLOCK = 1024
COUNTERS = ("window_kv_tokens", "full_kv_tokens", "unified_steps")


# ---- the reference's scores, the head in blocks ----

@functools.lru_cache(maxsize=None)
def _score_fn(module: str, frozen_config: str):
    import jax
    import jax.numpy as jnp
    ref, config = sys.modules[module], json.loads(frozen_config)

    def score(weights, ids):
        """ids [B, S] -> (log-softmax of the logits at the next token
        [B, S-1], best logit minus next token's logit [B, S-1])."""
        x, head = ref.hidden_and_head(weights, ids, config)
        B, S, hidden = x.shape
        xs, nxt = x[:, :-1].reshape(-1, hidden), ids[:, 1:].reshape(-1)
        n = xs.shape[0]
        pad = -n % HEAD_BLOCK
        xs = jnp.pad(xs, ((0, pad), (0, 0))).reshape(-1, HEAD_BLOCK, hidden)
        nxt = jnp.pad(nxt, (0, pad)).reshape(-1, HEAD_BLOCK, 1)

        def one(block):
            xb, nb = block
            with jax.default_matmul_precision("highest"):
                lg = xb @ head
            at = jnp.take_along_axis(lg, nb, -1)[:, 0]
            lp = jnp.take_along_axis(jax.nn.log_softmax(lg, -1), nb, -1)
            return lp[:, 0], jnp.max(lg, -1) - at

        lp, margin = jax.lax.map(one, (xs, nxt))
        return (lp.reshape(-1)[:n].reshape(B, S - 1),
                margin.reshape(-1)[:n].reshape(B, S - 1))

    return jax.jit(score)


def blockwise_scores(logits_fn, weights, ids, config):
    """`reference.common.next_token_scores`, the head applied in blocks."""
    import jax.numpy as jnp
    ref = sys.modules[logits_fn.__module__]
    if not hasattr(ref, "hidden_and_head"):
        return _scores_whole(logits_fn, weights, ids, config)
    return _score_fn(ref.__name__, json.dumps(config, sort_keys=True))(
        weights, jnp.asarray(ids))


_scores_whole = ref_common.next_token_scores


# ---- the program's counters at the window's edges ----

class _Recorder:
    """Stands where `serving.LLMEngine` and `harness.Window` are looked up:
    keeps the engine the job builds, and reads its counters as the window
    opens and as it closes."""

    def __init__(self):
        self.engine, self.edges = None, []

    def engine_class(self, plain):
        recorder = self

        def build(*args, **kwargs):
            recorder.engine = plain(*args, **kwargs)
            return recorder.engine
        return build

    def window_class(self, plain):
        recorder = self

        class Window(plain):
            def __enter__(self):
                recorder.read()
                return super().__enter__()

            def close(self, t1=None):
                if len(recorder.edges) == 1:
                    recorder.read()
                return super().close(t1)
        return Window

    def read(self):
        snap = self.engine.metrics.snapshot() if self.engine else {}
        self.edges.append({k: snap.get(k) for k in COUNTERS})

    def counters(self) -> dict:
        """Means a step over the window, and the pool's bytes by kind;
        nothing where the program keeps no such counter."""
        out = {}
        pool = getattr(self.engine, "pool", None)
        if hasattr(pool, "kv_bytes"):
            out["kv_pool_bytes"] = pool.kv_bytes()
            out["sliding_window"] = getattr(pool, "window", None)
        if len(self.edges) == 2 and None not in self.edges[0].values():
            d = {k: self.edges[1][k] - self.edges[0][k] for k in COUNTERS}
            if d["unified_steps"] > 0:
                for k in COUNTERS[:2]:
                    out[f"{k}_per_step"] = d[k] / d["unified_steps"]
        return out


@contextlib.contextmanager
def _cell_rules(ctx):
    from paddle_tpu import serving
    recorder = _Recorder()
    with calibrated._cell_rules(ctx), \
            mock.patch.object(ref_common, "next_token_scores",
                              blockwise_scores), \
            mock.patch.object(serving, "LLMEngine",
                              recorder.engine_class(serving.LLMEngine)), \
            mock.patch.object(harness, "Window",
                              recorder.window_class(harness.Window)):
        yield recorder


def run(ctx: harness.Context) -> dict:
    with _cell_rules(ctx) as recorder:
        result = base.run(ctx)
    result["counters"].update(recorder.counters())
    return result


# ---- the readings the limits lie between ----

def faulty_references(config: dict) -> dict:
    """{reading: the configuration as a faulty reference is given it}: the
    mechanisms the cell guards, each taken out of the reference alone."""
    ropes = config["rope_parameters"]
    return {
        "reference without the window (full attention in every layer)":
            {**config, "sliding_window": None},
        "reference with plain RoPE in the full-attention layers (no YaRN)":
            {**config, "rope_parameters": {
                **ropes, "full_attention": ropes["sliding_attention"]}},
    }


def controls(ctx: harness.Context) -> dict:
    """{reading: Checks}: "sound", then the faults. The job's own
    comparison throughout (`serve_closed_loop._check_against_reference`
    under the cell's limits, the reference's scores in blocks)."""
    import dataclasses
    import jax
    from paddle_tpu import serving

    out = {}

    def compare(name, engine, weights, config=None):
        say(f"==== {name}")
        out[name] = harness.Checks()
        c = ctx if config is None else dataclasses.replace(
            ctx, cell={**ctx.cell, "config_data": config})
        base._check_against_reference(c, engine, weights, out[name])
        say(f"==== {name}: correct {out[name].correct}")

    with _cell_rules(ctx):
        model, weights = harness.build_model(ctx)
        model.eval()
        engine = serving.LLMEngine(
            model, base._engine_config(ctx.traffic)).start()
        sound = calibrated._Replay(engine)
        try:
            compare("sound", sound, weights)
        finally:
            engine.stop(drain=False, timeout=30)
        sound.engine = None
        del engine
        for name, config in faulty_references(ctx.config).items():
            sound._next = 0
            compare(name, sound, weights, config)
        lower = calibrated.LOWER[ctx.config["dtype"]]
        low = {k: v.astype(lower) if v.ndim >= 2 else v
               for k, v in weights.items()}
        jax.block_until_ready(low)
        sound._next = 0
        compare(f"reference from matrices held in {lower}", sound, low)
    return out


def main(argv=None) -> int:
    import argparse
    import os
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="the readings a long-context "
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
    sys.exit(main())
