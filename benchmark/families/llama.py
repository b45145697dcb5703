"""Llama-architecture decoders (RMSNorm, RoPE, SwiGLU, grouped-query
attention, untied head) through the program's `models/llama.py`. The first
configuration of this family is Mistral-7B-v0.3, which shares the
architecture; no preset is added to the program. The plain reference is
`reference/llama.py`."""
from __future__ import annotations


def build(config: dict, recompute: bool = False):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        use_recompute=bool(recompute))
    if cfg.hidden_size // cfg.num_attention_heads != config["head_dim"]:
        raise ValueError("models/llama.py derives head_dim as hidden / heads; "
                         f"the configuration says {config['head_dim']}")
    model = LlamaForCausalLM(cfg)
    model.to(dtype=config["dtype"])
    return model


def matmul_params(config: dict) -> int:
    h, f = config["hidden_size"], config["intermediate_size"]
    d = config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * f
    return config["num_hidden_layers"] * per_layer + h * config["vocab_size"]


def total_params(config: dict) -> int:
    h = config["hidden_size"]
    return (matmul_params(config) + config["vocab_size"] * h
            + (2 * config["num_hidden_layers"] + 1) * h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}
