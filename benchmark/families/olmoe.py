"""OLMoE: a Llama-shaped decoder (RMSNorm, RoPE, causal attention, untied
head) whose every FFN is a router over sparse SwiGLU experts, with RMSNorm
on the q and k projections. Through the program's `models/llama.py`
(`LlamaConfig(num_experts=..., qk_norm=True)`); the plain reference is
`reference/olmoe.py`. A token touches `num_experts_per_tok` of the
`num_experts` experts, so the arithmetic a token costs (`matmul_params`)
follows the active parameters, not the parameters held (`total_params`)."""
from __future__ import annotations

import dataclasses


def build(config: dict, recompute: bool = False):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    sparse = {"num_experts", "num_experts_per_tok", "norm_topk_prob",
              "qk_norm"}
    have = {f.name for f in dataclasses.fields(LlamaConfig)}
    if not sparse <= have:
        raise NotImplementedError(
            "this program's models/llama.py has no sparse-expert FFN "
            f"(LlamaConfig lacks {sorted(sparse - have)}): it cannot build "
            f"{config['name']}")
    # built in the serving dtype from the start: this model fills the chip
    # in bf16 and must never exist in float32
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
        use_recompute=bool(recompute), dtype=config["dtype"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"], qk_norm=True)
    if cfg.hidden_size // cfg.num_attention_heads != config["head_dim"]:
        raise ValueError("models/llama.py derives head_dim as hidden / heads; "
                         f"the configuration says {config['head_dim']}")
    return LlamaForCausalLM(cfg)


def _shared_per_layer(config: dict) -> int:
    """Matmul parameters of a layer that every token uses: attention and
    the router."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + h * config["num_experts"]


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against."""
    expert = 3 * config["hidden_size"] * config["intermediate_size"]
    per_layer = _shared_per_layer(config) \
        + config["num_experts_per_tok"] * expert
    return config["num_hidden_layers"] * per_layer \
        + config["hidden_size"] * config["vocab_size"]


def total_params(config: dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    expert = 3 * h * config["intermediate_size"]
    norms = 2 * h + (config["num_attention_heads"]
                     + config["num_key_value_heads"]) * d
    per_layer = _shared_per_layer(config) + config["num_experts"] * expert \
        + norms
    return (config["num_hidden_layers"] * per_layer
            + 2 * config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}
