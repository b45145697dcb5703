"""The readings the limits of a Solar Open 2 cell lie between (outside the
driver's runs; `python3 -m benchmark.jobs.solar_open2_controls --workload
<cell> --seed <n>`): the sound program, and references that must each come
out not `correct` against it through the cell's own comparison
(`serve_closed_loop_long`: the cell's `limits`, the reference's scores in
blocks). The mechanisms the cell guards, each taken out of the reference
alone (`reference/solar_open2.py` reads every one from the configuration it
is given):

  beta without the 2           `kda_allow_neg_eigval` false: beta =
                               sigmoid(b), eigenvalues in [0, 1]
  without the delta term       `kda_delta` false: S <- S + beta k v^T, the
                               state is never read before it is written
  a decay a head               `kda_decay_per_head`: a head's 128 key
                               channels all take its first channel's decay
                               (what a gated delta rule with a scalar gate
                               a head would compute)
  q and k not normalised       `kda_qk_l2norm` false
  without the conv             `kda_conv` false: silu of the projections
  without the GQA gate         `use_gqa_gate` false
  without the shared expert    `n_shared_experts` 0
  matrices in the precision    every matrix held in the nearest precision
  below                        below the configuration's
  state in bfloat16            `kda_state_dtype`: S rounded to bfloat16
                               after every position. REPORTED, not
                               required, like every reading `REPORTED`
                               names: the cell's limits are set round the
                               faults above, and the cell's `doc` says
                               which of them the chip's limits do not see
                               and where the CPU guards them

`axk1_controls.controls` takes the readings (the sound engine answers the
check requests once, every reading compares those answers with another
reference, the engine and its pool gone by then; the weights are let go
leaf by leaf for the last one); this file gives it this family's faults
and a verdict that leaves the reported readings out. `--sound-only` stops
after the first reading (the sound program over many seeds)."""
from __future__ import annotations

import sys
from unittest import mock

from . import axk1_controls
from . import serve_closed_loop_long as long

BF16_STATE = "reference whose state is rounded to bfloat16 between positions"
# readings the verdict reports and does not require (the cell's `doc` and
# PERF.md section 6 say what the chip's limits read for each)
REPORTED = (BF16_STATE,)


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    return {
        "reference whose beta lacks its factor 2":
            ({**config, "kda_allow_neg_eigval": False}, None),
        "reference without the delta term (S += beta k v^T)":
            ({**config, "kda_delta": False}, None),
        "reference with a decay a head (its first channel's)":
            ({**config, "kda_decay_per_head": True}, None),
        "reference whose q and k are not normalised":
            ({**config, "kda_qk_l2norm": False}, None),
        "reference without the conv":
            ({**config, "kda_conv": False}, None),
        "reference without the GQA output gate":
            ({**config, "use_gqa_gate": False}, None),
        "reference without the shared expert":
            ({**config, "n_shared_experts": 0}, None),
        BF16_STATE: ({**config, "kda_state_dtype": "bfloat16"}, None),
    }


def main(argv=None) -> int:
    """`serve_closed_loop_long`'s command line over this family's
    readings; the exit code leaves the reported readings out."""
    argv = list(sys.argv[1:] if argv is None else argv)
    sound_only = "--sound-only" in argv
    if sound_only:
        argv.remove("--sound-only")
    seen = {}

    def readings(ctx):
        faults = {} if sound_only else faulty_references(ctx.config)
        out = axk1_controls.controls(ctx, faults)
        if sound_only:
            out = {"sound": out["sound"]}
        seen.update({name: checks.correct for name, checks in out.items()})
        return out

    with mock.patch.object(long, "controls", readings):
        long.main(argv)
    required = {k: v for k, v in seen.items() if k not in REPORTED}
    want = {name: name == "sound" for name in required}
    long.say(f"required readings as they should be: {required == want} "
             f"(reported only, seen by a limit: "
             f"{ {k: not seen[k] for k in REPORTED if k in seen} })")
    return 0 if seen and required == want else 1


if __name__ == "__main__":
    sys.exit(main())
