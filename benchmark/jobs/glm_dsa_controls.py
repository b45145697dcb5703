"""The readings the limits of a GLM-5.2 cell lie between (outside the
driver's runs; `python3 -m benchmark.jobs.glm_dsa_controls --workload <cell>
--seed <n>`): the sound program, and references that must each come out not
`correct` against it through the cell's own comparison
(`serve_sessions_long.check_against_reference` under the cell's `limits`).
The mechanisms the cell guards, each taken out of the reference alone
(`reference/glm_moe_dsa.py`'s fault switches):

  attends to every key          `index_topk` past every context: no selection
  no sharing                    `index_share` false: every layer selects for
                                itself from its own input, with the indexer
                                weights of the nearest "full" layer
  selection without the ReLU    `index_relu` false
  without the head weights      `index_head_weights` false: w = 1
  without the selection bias    every router's `select_bias` zeroed
  matrices in the precision     every matrix held in the nearest precision
  below                         below the configuration's

The sound engine fills its cache and answers the check's turns once; every
reading compares those answers (a `serve_closed_loop_calibrated._Replay`)
with another reference, the engine and its pool gone by then. For the last
reading the program's model and the weights as served are let go leaf by
leaf as the lower-precision copy is made. `--sound-only` stops after the
first reading (the sound program over many seeds)."""
from __future__ import annotations

import sys
from unittest import mock

from .. import harness
from ..harness import say
from . import serve_closed_loop as base
from . import serve_closed_loop_calibrated as calibrated
from . import serve_closed_loop_long as long
from . import serve_sessions_long as sessions


def _without_selection_bias(weights: dict, config: dict) -> dict:
    return {k: v * 0 if k.endswith("select_bias") else v
            for k, v in weights.items()}


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    return {
        "reference that attends to every key (no selection)":
            ({**config, "index_topk": 1 << 30}, None),
        "reference in which every layer selects for itself (no sharing)":
            ({**config, "index_share": False}, None),
        "reference whose index scores lack the ReLU":
            ({**config, "index_relu": False}, None),
        "reference whose index scores lack the head weights":
            ({**config, "index_head_weights": False}, None),
        "reference without the router's selection bias":
            (config, _without_selection_bias),
    }


def controls(ctx: harness.Context, faults: dict = None) -> dict:
    """{reading: Checks}: "sound", then `faults` (default: every one of
    `faulty_references`), then the lower-precision reference."""
    import dataclasses
    import gc
    import jax
    from paddle_tpu import serving

    out = {}
    traffic, config = ctx.traffic, ctx.config

    def compare(name, engine, weights, turns, config=None):
        say(f"==== {name}")
        out[name] = harness.Checks()
        c = ctx if config is None else dataclasses.replace(
            ctx, cell={**ctx.cell, "config_data": config})
        sessions.check_against_reference(c, engine, weights, out[name],
                                         turns)
        say(f"==== {name}: correct {out[name].correct}")

    if faults is None:
        faults = faulty_references(config)
    with calibrated._cell_rules(ctx):
        model, weights = harness.build_model(ctx)
        model.eval()
        engine = serving.LLMEngine(
            model, base._engine_config(traffic)).start()
        sound = calibrated._Replay(engine)
        try:
            history = sessions.histories(traffic, config["vocab_size"],
                                         ctx.seed)
            sessions.fill_cache(engine, history)
            turns = sessions.check_turns(traffic, config["vocab_size"],
                                         ctx.seed, history)
            compare("sound", sound, weights, turns)
        finally:
            engine.stop(drain=False, timeout=30)
        sound.engine = None
        del engine
        for name, (faulty, change) in faults.items():
            sound._next = 0
            compare(name, sound,
                    weights if change is None else change(weights, faulty),
                    turns, faulty)
        lower = calibrated.LOWER[config["dtype"]]
        del model           # its parameters are the arrays of `weights`
        gc.collect()
        low = {}
        for k in sorted(weights):
            v = weights.pop(k)
            low[k] = v.astype(lower) if v.ndim >= 2 else v
        jax.block_until_ready(low)
        sound._next = 0
        compare(f"reference from matrices held in {lower}", sound, low,
                turns)
    return out


def main(argv=None) -> int:
    """`serve_closed_loop_long`'s command line over this file's readings;
    `--sound-only` stops after the first."""
    argv = list(sys.argv[1:] if argv is None else argv)
    sound_only = "--sound-only" in argv
    if sound_only:
        argv.remove("--sound-only")

    def readings(ctx):
        out = controls(ctx, {} if sound_only else None)
        return {"sound": out["sound"]} if sound_only else out

    with mock.patch.object(long, "controls", readings):
        return long.main(argv)


if __name__ == "__main__":
    sys.exit(main())
