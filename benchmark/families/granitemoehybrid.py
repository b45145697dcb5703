"""Granite 4.0-H (`model_type` "granitemoehybrid"): a decoder whose mixer
differs by layer (`layer_types`: Mamba-2 state-space layers with an
attention layer without rotary embedding every tenth), every FFN a router
over sparse SwiGLU experts plus one shared expert, four scalar multipliers
and a tied head. Through the program's `models/granitemoehybrid.py`; the
plain reference is `reference/granitemoehybrid.py`.

A configuration may hold one chip's share of a deployment that divides
each layer over several chips: `num_local_experts` of the router's
`num_local_experts_published` experts (the first ones) and a slice of the
vocabulary. The router keeps its published width and its experts per
token; what the absent experts would add is left out, in the program and
in the reference alike."""
from __future__ import annotations


def build(config: dict, recompute: bool = False):
    from .. import cells
    try:
        from paddle_tpu.models.granitemoehybrid import (
            GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)
    except ImportError as e:
        raise cells.CellError(
            "this program has no models/granitemoehybrid.py (no state-space "
            f"layer, no expert layer that holds a share): it cannot build "
            f"{config['name']} ({e})") from None
    published = config.get("num_local_experts_published",
                           config["num_local_experts"])
    held = config["num_local_experts"]
    # built in the serving dtype from the start: set-up holds this copy and
    # the seeded one at once
    cfg = GraniteMoeHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        layer_types=config["layer_types"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        num_local_experts=published,
        num_experts_per_tok=config["num_experts_per_tok"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_n_groups=config["mamba_n_groups"],
        attention_multiplier=config["attention_multiplier"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], dtype=config["dtype"],
        experts_held=None if held == published else (0, held))
    if cfg.head_dim != config["head_dim"]:
        raise ValueError("models/granitemoehybrid.py derives head_dim as "
                         f"hidden / heads; the configuration says "
                         f"{config['head_dim']}")
    if recompute or not config["tie_word_embeddings"]:
        raise cells.CellError("granitemoehybrid: serving only, tied head")
    return GraniteMoeHybridForCausalLM(cfg)


def _mamba(config: dict) -> tuple:
    """(matmul parameters, the rest) of one Mamba-2 mixer."""
    h = config["hidden_size"]
    heads, n = config["mamba_n_heads"], config["mamba_d_state"]
    inner = heads * config["mamba_d_head"]
    conv = inner + 2 * config["mamba_n_groups"] * n
    matmul = h * (inner + conv + heads) + inner * h
    rest = conv * config["mamba_d_conv"] + conv + 3 * heads + inner
    return matmul, rest


def _attention(config: dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def _experts(config: dict, routed: int) -> int:
    """Router, `routed` routed experts and the shared one."""
    h = config["hidden_size"]
    return (h * config.get("num_local_experts_published",
                           config["num_local_experts"])
            + routed * 3 * h * config["intermediate_size"]
            + 3 * h * config["shared_intermediate_size"])


def _per_kind(config: dict) -> tuple:
    kinds = config["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against (its experts per token of
    the published router; the tied head's matrix once)."""
    mamba, attention = _per_kind(config)
    ffn = _experts(config, config["num_experts_per_tok"])
    return (mamba * (_mamba(config)[0] + ffn)
            + attention * (_attention(config) + ffn)
            + config["hidden_size"] * config["vocab_size"])


def total_params(config: dict) -> int:
    """Held on this chip."""
    h = config["hidden_size"]
    mamba, attention = _per_kind(config)
    ffn = _experts(config, config["num_local_experts"]) + 2 * h   # norms
    return (mamba * (sum(_mamba(config)) + ffn)
            + attention * (_attention(config) + ffn)
            + config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}
