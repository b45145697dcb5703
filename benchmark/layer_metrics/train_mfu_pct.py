"""Model FLOP/s utilisation of the training step: operations the forward
and backward passes need per token (recompute not counted) times tokens per
second per chip, over the chip's bf16 peak."""
from .. import cells, kernel_costs

LAYER = "SPMD step"
UNIT = "%"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(trace, counters, ctx):
    if ctx.peaks is None:
        return None
    config = ctx.config
    family = cells.family_module(config)
    per_token = kernel_costs.train_flops_per_token(
        family.matmul_params(config), config["num_hidden_layers"],
        config["hidden_size"], counters["sequence_length"])
    return (100.0 * per_token * counters["tokens_per_s_per_chip"]
            / ctx.peaks["bf16_flops_per_s"])
