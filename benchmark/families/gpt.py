"""GPT-3 family (Brown et al. 2020) through the program's `models/gpt.py`:
how the benchmark builds the program's model from a configuration file, and
the arithmetic of its size. The plain reference is `reference/gpt.py`."""
from __future__ import annotations


def build(config: dict, recompute: bool = False):
    """The program's model at the configuration's sizes, in its dtype.
    Built the way a user builds it (float32 constructor, then the cast);
    the benchmark then loads its own seeded weights over it."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        layer_norm_eps=config["layer_norm_eps"],
        use_recompute=bool(recompute))
    model = GPTForCausalLM(cfg)
    model.to(dtype=config["dtype"])
    return model


def matmul_params(config: dict) -> int:
    """Parameters that multiply every token: the layers' matrices and the
    output head. Embedding tables are looked up, not multiplied."""
    h, f = config["hidden_size"], config["intermediate_size"]
    per_layer = 3 * h * h + h * h + 2 * h * f
    return config["num_hidden_layers"] * per_layer + h * config["vocab_size"]


def total_params(config: dict) -> int:
    h, f = config["hidden_size"], config["intermediate_size"]
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (2 * h * f + f + h) \
        + 4 * h
    return (config["num_hidden_layers"] * per_layer + 2 * h
            + (2 * config["vocab_size"]
               + config["max_position_embeddings"]) * h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_attention_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"]}
