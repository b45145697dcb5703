"""The readings the limits of a Laguna cell lie between (outside the
driver's runs; `python3 -m benchmark.jobs.laguna_controls --workload <cell>
--seed <n>`): the sound program, and references that must each come out not
`correct` against it through the cell's own comparison
(`serve_closed_loop_long`: the cell's `limits`, the reference's scores in
blocks). The mechanisms the cell guards, each taken out of the reference
alone (`reference/laguna.py` reads every one from the configuration it is
given):

  without the output gate      `gating` false
  full rotary on full layers   `partial_rotary_factor` 1 where it is 0.5
                               (YaRN then over the head's 128 dimensions)
  head groups swapped          `gqa_group`: full layers pair query head i
                               with KV head i // 8, sliding layers i // 6
                               (each the other kind's group)
  without the window           `sliding_window` None
  without the shared expert    `shared_expert_intermediate_size` 0
  without the factor 2.5       `moe_routed_scaling_factor` 1
  softmax scores               `router_scoring` "softmax"
  the router in bfloat16       `router_dtype`: logits from bf16 operands
  matrices in the precision    every matrix held in the nearest precision
  below                        below the configuration's

`axk1_controls.controls` takes the readings (the sound engine answers the
check requests once, every reading compares those answers with another
reference, the engine and its pool gone by then) under `xing4_0_controls`'
command line; this file gives them this family's faults. `--sound-only`
stops after the first reading (the sound program over many seeds)."""
from __future__ import annotations

import sys
from unittest import mock

from ..reference.laguna import _whole
from . import xing4_0_controls

FULL, SLIDING = "full_attention", "sliding_attention"


def faulty_references(config: dict) -> dict:
    """{reading: (the configuration a faulty reference is given, what is
    done to the weights it is given or None)}."""
    config = _whole(config)
    ropes = config["rope_parameters"]
    heads = dict(zip(config["layer_types"],
                     config["num_attention_heads_per_layer"]))
    kv = config["num_key_value_heads"]
    return {
        "reference without the attention output gate":
            ({**config, "gating": False}, None),
        "reference with the whole head turned on full layers":
            ({**config, "rope_parameters": {**ropes, FULL: {
                **ropes[FULL], "partial_rotary_factor": 1.0}}}, None),
        "reference with the head groups of the layer kinds swapped":
            ({**config, "gqa_group": {FULL: heads[SLIDING] // kv,
                                      SLIDING: heads[FULL] // kv}}, None),
        "reference without the window (full attention in every layer)":
            ({**config, "sliding_window": None}, None),
        "reference without the shared expert":
            ({**config, "shared_expert_intermediate_size": 0}, None),
        "reference without the routed scaling factor":
            ({**config, "moe_routed_scaling_factor": 1.0}, None),
        "reference with softmax scores in place of sigmoid":
            ({**config, "router_scoring": "softmax"}, None),
        "reference whose router computes in bfloat16":
            ({**config, "router_dtype": "bfloat16"}, None),
    }


def main(argv=None) -> int:
    """`xing4_0_controls`' command line (`--sound-only` included) over this
    family's readings."""
    with mock.patch.object(xing4_0_controls, "faulty_references",
                           faulty_references):
        return xing4_0_controls.main(argv)


if __name__ == "__main__":
    sys.exit(main())
