"""The readings the limits of a Xing4.0 cell lie between (outside the
driver's runs; `python3 -m benchmark.jobs.xing4_0_controls --workload <cell>
--seed <n>`): the sound program, and references that must each come out not
`correct` against it through the cell's own comparison
(`serve_closed_loop_long`: the cell's `limits`, the reference's scores in
blocks). The mechanisms the cell guards, each taken out of the reference
alone (`reference/xing4_0.py`'s fault switches):

  one Sinkhorn pass            `hc_sinkhorn_iters` 1: H_res is normalised
                               once, not to a doubly stochastic matrix
  static mixing                `hc_dynamic` false: every gain alpha = 0, the
                               coefficients no longer depend on the token
  H_post without its factor 2  `hc_post_gain` 1
  one stream                   `hc_mixing` false: H_res the identity, H_pre
                               uniform
  without the selection bias   every router's `select_bias` zeroed
  matrices in the precision    every matrix held in the nearest precision
  below                        below the configuration's

`axk1_controls.controls` takes the readings (the sound engine answers the
check requests once, every reading compares those answers with another
reference, the engine and its pool gone by then; the weights are let go
leaf by leaf for the last one); this file gives it this family's faults.
`--sound-only` stops after the first reading (the sound program over many
seeds)."""
from __future__ import annotations

import sys
from unittest import mock

from . import axk1_controls
from . import serve_closed_loop_long as long


def _without_selection_bias(weights: dict, config: dict) -> dict:
    return {k: v * 0 if k.endswith("select_bias") else v
            for k, v in weights.items()}


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    return {
        "reference with one Sinkhorn pass":
            ({**config, "hc_sinkhorn_iters": 1}, None),
        "reference with static mixing (every gain 0)":
            ({**config, "hc_dynamic": False}, None),
        "reference whose H_post lacks its factor 2":
            ({**config, "hc_post_gain": 1.0}, None),
        "reference with one stream (H_res the identity, H_pre uniform)":
            ({**config, "hc_mixing": False}, None),
        "reference without the router's selection bias":
            (config, _without_selection_bias),
    }


def main(argv=None) -> int:
    """`serve_closed_loop_long`'s command line over this family's
    readings."""
    argv = list(sys.argv[1:] if argv is None else argv)
    sound_only = "--sound-only" in argv
    if sound_only:
        argv.remove("--sound-only")

    def readings(ctx):
        faults = {} if sound_only else faulty_references(ctx.config)
        out = axk1_controls.controls(ctx, faults)
        return {"sound": out["sound"]} if sound_only else out

    with mock.patch.object(long, "controls", readings):
        return long.main(argv)


if __name__ == "__main__":
    sys.exit(main())
