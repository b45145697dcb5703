"""Mellum 2 (`model_type` "mellum"): a Llama-shaped GQA decoder (RMSNorm,
SwiGLU experts, untied head) whose layers differ by `layer_types`:
"sliding_attention" layers attend `sliding_window` keys back with the plain
rotary embedding, "full_attention" layers attend to everything with YaRN
(`rope_parameters`, one group per layer type); a head width of its own
(`head_dim`, not hidden / heads); every FFN a router over sparse experts of
width `moe_intermediate_size`, renormalised over the chosen
(`norm_topk_prob`). Through the program's `models/llama.py`
(`LlamaConfig(layer_types=..., sliding_window=..., rope_parameters=...,
head_dim=..., experts_held=...)`); the plain reference is
`reference/mellum.py`.

A configuration may hold one chip's share of a deployment that divides
each layer over several chips by expert parallelism: `num_experts` of the
router's `num_experts_published` experts (the first ones). The router
keeps its published width and its experts per token; what the absent
experts would add is left out, in the program and in the reference alike.
`intermediate_size` (a dense MLP's width) is in the published file and
unused: every `mlp_layer_types` entry is "sparse"."""
from __future__ import annotations

import dataclasses


def build(config: dict, recompute: bool = False):
    from .. import cells
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    needs = {"head_dim", "layer_types", "sliding_window", "rope_parameters",
             "experts_held"}
    have = {f.name for f in dataclasses.fields(LlamaConfig)}
    if not needs <= have:
        raise cells.CellError(
            "this program's models/llama.py has no window layers, no "
            "rotary parameters by layer type, no head width of its own or "
            f"no expert share (LlamaConfig lacks {sorted(needs - have)}): "
            f"it cannot build {config['name']}")
    if set(config["mlp_layer_types"]) != {"sparse"} or recompute:
        raise cells.CellError("mellum: every FFN sparse, serving only")
    published = config.get("num_experts_published", config["num_experts"])
    held = config["num_experts"]
    # built in the serving dtype from the start: set-up holds this copy and
    # the seeded one at once
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_parameters"]["sliding_attention"][
            "rope_theta"],
        rope_parameters=config["rope_parameters"],
        layer_types=config["layer_types"],
        sliding_window=config["sliding_window"]
        if config["use_sliding_window"] else None,
        tie_word_embeddings=config["tie_word_embeddings"],
        dtype=config["dtype"], num_experts=published,
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"], qk_norm=False,
        experts_held=None if held == published else (0, held)))


def _shared_per_layer(config: dict) -> int:
    """Matmul parameters of a layer that every token uses: attention and
    the router (as wide as published)."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + h * config.get(
        "num_experts_published", config["num_experts"])


def _expert(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against (its experts per token of
    the published router, wherever they are held)."""
    per_layer = _shared_per_layer(config) \
        + config["num_experts_per_tok"] * _expert(config)
    return config["num_hidden_layers"] * per_layer \
        + config["hidden_size"] * config["vocab_size"]


def total_params(config: dict) -> int:
    """Held on this chip."""
    h = config["hidden_size"]
    per_layer = _shared_per_layer(config) \
        + config["num_experts"] * _expert(config) + 2 * h      # two norms
    return (config["num_hidden_layers"] * per_layer
            + 2 * config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}
