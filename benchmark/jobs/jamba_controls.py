"""The readings the limits of a Jamba cell lie between (outside the driver's
runs; `python3 -m benchmark.jobs.jamba_controls --workload <cell> --seed
<n>`): the sound program, and references that must each come out not
`correct` against it through the cell's own comparison
(`serve_closed_loop_long`: the cell's `limits`, the reference's scores in
blocks). The mechanisms the cell guards, each taken out of the reference
alone (`reference/jamba.py`'s fault switches, or the weights it is given):

  state wiped after every step   `mamba_carry` false: h_{t-1} = 0 at every
                                 position
  without the inner norms        `mamba_inner_norms` false: dt_layernorm,
                                 b_layernorm and c_layernorm left out
  a decay a channel              every column of `A_log` is its first:
                                 `A[:, 0]` for every state element, what
                                 Mamba-2 would compute
  matrices in the precision      every matrix held in the nearest precision
  below                          below the configuration's
  state in bfloat16              `mamba_state_dtype`: h rounded to bfloat16
                                 after every position. REPORTED, not
                                 required: the cell's limits are set round
                                 the four faults above, and a state held in
                                 the model's type may lie inside them
                                 (PERF.md section 7 says whether it does)

The readings, the sound engine's answers kept and replayed, and the
letting go of the served weights before the last reading are
`axk1_controls.controls`'s; this file brings the faults and a verdict that
leaves the reported reading out."""
from __future__ import annotations

import sys

from .. import cells, harness
from ..harness import say
from . import axk1_controls

REPORTED = "reference whose state is rounded to bfloat16 between positions"


def _decay_a_channel(weights: dict, config: dict) -> dict:
    return {k: v[:, :1] + 0 * v if k.endswith("mamba.A_log") else v
            for k, v in weights.items()}


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    return {
        "reference whose state is wiped after every position":
            ({**config, "mamba_carry": False}, None),
        "reference without dt_layernorm, b_layernorm and c_layernorm":
            ({**config, "mamba_inner_norms": False}, None),
        "reference with a decay a channel (A[:, 0] for every element)":
            (config, _decay_a_channel),
        REPORTED: ({**config, "mamba_state_dtype": "bfloat16"}, None),
    }


def controls(ctx: harness.Context) -> dict:
    """{reading: Checks}: "sound", the faults above, then the
    lower-precision reference."""
    return axk1_controls.controls(ctx, faulty_references(ctx.config))


def main(argv=None) -> int:
    import argparse
    import os
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
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
    verdict = {name: checks.correct
               for name, checks in controls(ctx).items()}
    required = {k: v for k, v in verdict.items() if k != REPORTED}
    want = {name: name == "sound" for name in required}
    say(f"controls: {verdict}; as they should be: {required == want} "
        f"(reported only: {REPORTED!r} seen by a limit: "
        f"{not verdict[REPORTED]})")
    return 0 if required == want else 1


if __name__ == "__main__":
    sys.exit(main())
