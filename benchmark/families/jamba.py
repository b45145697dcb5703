"""Jamba (`model_type` "jamba", AI21): a decoder whose mixer differs by
layer (`attn_layer_period` / `attn_layer_offset`: Mamba-1 state-space
layers round multi-query attention layers without rotary embedding), every
FFN the dense SwiGLU, a tied head. Through the program's `models/jamba.py`;
the plain reference is `reference/jamba.py`. Held whole: no share of a
deployment, no slice of the vocabulary.

The model is constructed under `paddle_tpu.LazyGuard`: `harness.build_model`
loads the seeded weights over the constructor's, and two copies of 6.06 GB
beside the pool are more than a chip holds with room."""
from __future__ import annotations

from ..reference.jamba import layer_types


def build(config: dict, recompute: bool = False):
    from .. import cells
    try:
        import paddle_tpu
        from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
        guard = paddle_tpu.LazyGuard
    except (ImportError, AttributeError) as e:
        raise cells.CellError(
            "this program has no models/jamba.py (no Mamba-1 layer, no "
            f"selective_scan): it cannot build {config['name']} ({e})"
        ) from None
    if recompute or not config["tie_word_embeddings"]:
        raise cells.CellError("jamba: serving only, tied head")
    cfg = JambaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        num_experts=config["num_experts"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_dt_rank=config["mamba_dt_rank"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], dtype=config["dtype"])
    if cfg.head_dim != config["head_dim"] \
            or cfg.layer_types != layer_types(config):
        raise ValueError("models/jamba.py derives head_dim as hidden / "
                         f"heads and the layer order from period and "
                         f"offset; the configuration says "
                         f"{config['head_dim']}, {layer_types(config)}")
    with guard():
        return JambaForCausalLM(cfg)


def _mamba(config: dict) -> tuple:
    """(matmul parameters, the rest) of one Mamba-1 mixer."""
    h, n = config["hidden_size"], config["mamba_d_state"]
    inner, rank = config["mamba_expand"] * h, config["mamba_dt_rank"]
    matmul = h * 2 * inner + inner * (rank + 2 * n) + rank * inner \
        + inner * h
    # conv taps and bias, dt_proj's bias, A_log, D, the three norms
    rest = inner * config["mamba_d_conv"] + inner + inner + inner * n \
        + inner + rank + 2 * n
    return matmul, rest


def _attention(config: dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def _ffn(config: dict) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def _per_kind(config: dict) -> tuple:
    kinds = layer_types(config)
    return kinds.count("mamba"), kinds.count("attention")


def matmul_params(config: dict) -> int:
    """What one token multiplies against (the tied head's matrix once)."""
    mamba, attention = _per_kind(config)
    return (mamba * (_mamba(config)[0] + _ffn(config))
            + attention * (_attention(config) + _ffn(config))
            + config["hidden_size"] * config["vocab_size"])


def total_params(config: dict) -> int:
    h = config["hidden_size"]
    mamba, attention = _per_kind(config)
    ffn = _ffn(config) + 2 * h                            # the two norms
    return (mamba * (sum(_mamba(config)) + ffn)
            + attention * (_attention(config) + ffn)
            + config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}
