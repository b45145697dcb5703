"""The readings the limits of an A.X-K1 cell lie between (outside the
driver's runs; `python3 -m benchmark.jobs.axk1_controls --workload <cell>
--seed <n>`): the sound program, and references that must each come out not
`correct` against it through the cell's own comparison
(`serve_closed_loop_long`: the cell's `limits`, the reference's scores in
blocks). `serve_closed_loop_long.controls` has the window family's faults
and holds the weights twice for its last reading, which this configuration
(8.33 GB) cannot; so this file has its own. The mechanisms the cell guards,
each taken out of the reference alone:

  without YaRN                 `rope_scaling` None: plain RoPE at theta, and
                               with it the softmax scale's mscale^2
  softmax for sigmoid          the router scores by softmax over the experts
  without the group limit      every expert eligible (`n_group` 1)
  without the shared expert    `n_shared_experts` 0
  the rotary key out of the    the rotary columns of every W_kva zeroed, so
  scores                       k_r = 0 and s_h = scale q_nope_h . k_nope_h
  matrices in the precision    every matrix held in the nearest precision
  below                        below the configuration's

The sound engine answers the check requests once; every reading compares
those answers (a `serve_closed_loop_calibrated._Replay`) with another
reference, the engine and its pool gone by then. For the last reading the
program's model and the weights as served are let go leaf by leaf as the
lower-precision copy is made."""
from __future__ import annotations

import sys
from unittest import mock

from .. import harness
from ..harness import say
from ..reference import common as ref_common
from . import serve_closed_loop as base
from . import serve_closed_loop_calibrated as calibrated
from . import serve_closed_loop_long as long


def _without_rotary_key(weights: dict, config: dict) -> dict:
    rank = config["kv_lora_rank"]
    return {k: v.at[:, rank:].set(0)
            if k.endswith("kv_a_proj_with_mqa.weight") else v
            for k, v in weights.items()}


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    return {
        "reference without YaRN (plain RoPE, no mscale in the scale)":
            ({**config, "rope_scaling": None}, None),
        "reference with softmax scores in place of sigmoid":
            ({**config, "scoring_func": "softmax"}, None),
        "reference without the group limit (every expert eligible)":
            ({**config, "n_group": 1, "topk_group": 1}, None),
        "reference without the shared expert":
            ({**config, "n_shared_experts": 0}, None),
        "reference with the rotary key left out of the scores":
            (config, _without_rotary_key),
    }


def controls(ctx: harness.Context, faults: dict = None) -> dict:
    """{reading: Checks}: "sound", then `faults` (default: every one of
    `faulty_references`), then the lower-precision reference."""
    import dataclasses
    import gc
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

    if faults is None:
        faults = faulty_references(ctx.config)
    # the cell's seeding and limits and the scores in blocks, without
    # `serve_closed_loop_long`'s recorder: it would keep the engine, its
    # pool and the served weights alive to the end
    with calibrated._cell_rules(ctx), mock.patch.object(
            ref_common, "next_token_scores", long.blockwise_scores):
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
        for name, (config, change) in faults.items():
            sound._next = 0
            compare(name, sound,
                    weights if change is None else change(weights, config),
                    config)
        lower = calibrated.LOWER[ctx.config["dtype"]]
        del model           # its parameters are the arrays of `weights`
        gc.collect()
        low = {}
        for k in sorted(weights):
            v = weights.pop(k)
            low[k] = v.astype(lower) if v.ndim >= 2 else v
        jax.block_until_ready(low)
        sound._next = 0
        compare(f"reference from matrices held in {lower}", sound, low)
    return out


def main(argv=None) -> int:
    """`serve_closed_loop_long`'s command line over this file's readings."""
    with mock.patch.object(long, "controls", controls):
        return long.main(argv)


if __name__ == "__main__":
    sys.exit(main())
