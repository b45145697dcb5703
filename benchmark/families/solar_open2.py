"""Solar Open 2 (`model_type` "solar_open2", Upstage): a decoder whose mixer
differs by layer (`gqa_layers`: a grouped-query attention layer without
rotary embedding and with a gate on its output, then `gqa_interval` Kimi
Delta Attention layers: a delta-rule linear attention with a decay a key
channel and a conv of width 4), every FFN a softmax router over small
SwiGLU experts plus a shared one, an untied head. Through the program's
`models/solar_open2.py`; the plain reference is `reference/solar_open2.py`.

A configuration holds one chip's share of a deployment that divides each
layer over several chips: `n_routed_experts` of the router's
`n_routed_experts_published` experts (the first ones) and a slice of the
vocabulary. The router keeps its published width and its experts per
token; what the absent experts would add is left out, in the program and
in the reference alike. The model is constructed under
`paddle_tpu.LazyGuard`: `harness.build_model` loads the seeded weights over
the constructor's, and two copies of 6.62 GB beside the pool are more than
a chip holds."""
from __future__ import annotations

from ..reference.solar_open2 import _linear, layer_types


def build(config: dict, recompute: bool = False):
    from .. import cells
    try:
        import paddle_tpu
        from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                                   SolarOpen2ForCausalLM)
        guard = paddle_tpu.LazyGuard
    except (ImportError, AttributeError) as e:
        raise cells.CellError(
            "this program has no models/solar_open2.py (no delta-rule "
            f"linear-attention layer, no kda_update): it cannot build "
            f"{config['name']} ({e})") from None
    if recompute or config["tie_word_embeddings"]:
        raise cells.CellError("solar_open2: serving only, untied head")
    published = config.get("n_routed_experts_published",
                           config["n_routed_experts"])
    held = config["n_routed_experts"]
    heads, head_dim, kernel = _linear(config)
    cfg = SolarOpen2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        gqa_layers=config["gqa_layers"], gqa_interval=config["gqa_interval"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], use_rope=config["use_rope"],
        use_gqa_gate=config["use_gqa_gate"],
        gqa_gate=config.get("gqa_gate", "elementwise"),
        linear_num_heads=heads, linear_head_dim=head_dim,
        short_conv_kernel_size=kernel,
        kda_use_full_proj=config["kda_use_full_proj"],
        kda_allow_neg_eigval=config["kda_allow_neg_eigval"],
        n_routed_experts=published,
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_k_dense_replace=config["first_k_dense_replace"],
        tie_word_embeddings=config["tie_word_embeddings"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], dtype=config["dtype"],
        experts_held=None if held == published else (0, held))
    if cfg.layer_types != layer_types(config):
        raise ValueError(f"models/solar_open2.py orders the layers "
                         f"{cfg.layer_types}; the configuration says "
                         f"{layer_types(config)}")
    with guard():
        return SolarOpen2ForCausalLM(cfg)


def _kda(config: dict) -> tuple:
    """(matmul parameters, the rest) of one KDA mixer."""
    h = config["hidden_size"]
    heads, d, kernel = _linear(config)
    inner = heads * d
    # q, k and v; the decay's and the gate's bottlenecks and beta; their
    # up-projections; o
    matmul = h * 3 * inner + h * (2 * d + heads) + 2 * d * inner + inner * h
    # the conv's taps, the gate's bias, dt_bias, A_log, the output norm
    rest = 3 * inner * kernel + inner + inner + heads + d
    return matmul, rest


def _attention(config: dict) -> int:
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    gate = 0
    if config["use_gqa_gate"]:
        gate = h * (q if config.get("gqa_gate", "elementwise")
                    == "elementwise" else q // d)
    return h * q + 2 * h * kv + q * h + gate


def _expert(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _ffn(config: dict, experts: int) -> int:
    """A layer's FFN with `experts` routed experts counted: the router at
    its published width, the experts, the shared expert."""
    published = config.get("n_routed_experts_published",
                           config["n_routed_experts"])
    return config["hidden_size"] * published \
        + (experts + config["n_shared_experts"]) * _expert(config)


def _per_kind(config: dict) -> tuple:
    kinds = layer_types(config)
    return kinds.count("kda"), kinds.count("attention")


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against."""
    kda, attention = _per_kind(config)
    ffn = _ffn(config, config["num_experts_per_tok"])
    return (kda * (_kda(config)[0] + ffn)
            + attention * (_attention(config) + ffn)
            + config["hidden_size"] * config["vocab_size"])


def total_params(config: dict) -> int:
    """Held on this chip."""
    h = config["hidden_size"]
    kda, attention = _per_kind(config)
    ffn = _ffn(config, config["n_routed_experts"]) + 2 * h   # the two norms
    return (kda * (sum(_kda(config)) + ffn)
            + attention * (_attention(config) + ffn)
            + 2 * config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}


def expert_shape(config: dict) -> dict:
    """What the expert-layer metrics need of a sparse layer: its width,
    the experts held and published, the experts per token, the layers that
    have experts (every one)."""
    return {"hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"],
            "held": config["n_routed_experts"],
            "published": config.get("n_routed_experts_published",
                                    config["n_routed_experts"]),
            "per_token": config["num_experts_per_tok"],
            "layers": config["num_hidden_layers"]}
