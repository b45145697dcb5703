"""Laguna-XS.2 (poolside, `model_type` "laguna"): a pre-norm GQA decoder
(RMSNorm, untied head) whose layers differ by `layer_types` in more than
their mask: a "full_attention" layer has 48 query heads, a
"sliding_attention" layer 64 (`num_attention_heads_per_layer`), over 8 KV
heads of 128 on both; full layers turn half of a head by YaRN computed over
the turned dimensions, sliding layers the whole head by the plain rotary
embedding at another theta (`rope_parameters`, `partial_rotary_factor` by
layer type); every attention output passes a sigmoid gate a head
(`gating`). Layer 0's FFN is a dense SwiGLU MLP, every other layer's a
sigmoid router over `num_experts` small experts, the chosen
`num_experts_per_tok` renormalised and times `moe_routed_scaling_factor`,
beside one shared expert (`mlp_layer_types`,
`shared_expert_intermediate_size`). Through the program's `models/llama.py`
(`LlamaConfig(num_attention_heads_per_layer=..., attn_output_gate=...,
mlp_layer_types=..., shared_expert_intermediate_size=...,
router_scoring=..., routed_scaling_factor=...)`); the plain reference is
`reference/laguna.py`.

Every expert and the whole vocabulary are held; a configuration is a run of
the published layers (`num_attention_heads_per_layer` may stay the
published list: a layer reads its own entry). The model is constructed under
`paddle_tpu.LazyGuard`: 3.87 B parameters the seeded weights replace leaf
by leaf are never drawn by the constructor."""
from __future__ import annotations

import dataclasses

SLIDING = "sliding_attention"


def build(config: dict, recompute: bool = False):
    from .. import cells
    try:
        import paddle_tpu
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        guard = paddle_tpu.LazyGuard
    except (ImportError, AttributeError) as e:
        raise cells.CellError(
            f"this program has no models/llama.py or no LazyGuard ({e}): it "
            f"cannot build {config['name']}") from None
    needs = {"num_attention_heads_per_layer", "attn_output_gate",
             "mlp_layer_types", "moe_intermediate_size",
             "shared_expert_intermediate_size", "router_scoring",
             "routed_scaling_factor"}
    lacks = sorted(needs - {f.name for f in dataclasses.fields(LlamaConfig)})
    if lacks:
        raise cells.CellError(
            f"this program's LlamaConfig has no {lacks}: one query-head "
            "count for every layer, no gate on the attention output, no "
            "dense layer beside sparse ones or no shared expert; it cannot "
            f"build {config['name']}")
    if recompute:
        raise cells.CellError("laguna: serving only")
    if config.get("moe_apply_router_weight_on_input"):
        raise cells.CellError("laguna: the router's weight is on an "
                              "expert's output")
    with guard():
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_expert_intermediate_size=config[
                "shared_expert_intermediate_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            num_attention_heads_per_layer=config[
                "num_attention_heads_per_layer"][
                    :config["num_hidden_layers"]],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            max_position_embeddings=config["max_position_embeddings"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_parameters"][SLIDING]["rope_theta"],
            rope_parameters={k: v for k, v in
                             config["rope_parameters"].items()
                             if isinstance(v, dict)},
            layer_types=config["layer_types"],
            mlp_layer_types=config["mlp_layer_types"],
            sliding_window=config["sliding_window"],
            tie_word_embeddings=config["tie_word_embeddings"],
            dtype=config["dtype"], num_experts=config["num_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            norm_topk_prob=True,
            router_scoring=config["router_scoring"],
            routed_scaling_factor=config["moe_routed_scaling_factor"],
            attn_output_gate=config["gating"], qk_norm=config["qk_norm"]))


def _attention(config: dict, layer: int) -> int:
    """Matmul parameters of layer `layer`'s attention: q, k, v, o and the
    gate."""
    h, d = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads_per_layer"][layer] * d
    kv = config["num_key_value_heads"] * d
    gate = h * (q // d) if config["gating"] else 0
    return h * q + 2 * h * kv + q * h + gate


def _expert(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _ffn(config: dict, layer: int, experts: int) -> int:
    """Layer `layer`'s FFN with `experts` routed experts counted."""
    h = config["hidden_size"]
    if config["mlp_layer_types"][layer] == "dense":
        return 3 * h * config["intermediate_size"]
    return (h * config["num_experts"] + experts * _expert(config)
            + 3 * h * config["shared_expert_intermediate_size"])


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against."""
    return sum(_attention(config, i)
               + _ffn(config, i, config["num_experts_per_tok"])
               for i in range(config["num_hidden_layers"])) \
        + config["hidden_size"] * config["vocab_size"]


def total_params(config: dict) -> int:
    """Held on this chip."""
    h = config["hidden_size"]
    return sum(_attention(config, i) + _ffn(config, i, config["num_experts"])
               + 2 * h for i in range(config["num_hidden_layers"])) \
        + 2 * config["vocab_size"] * h + h


def attention_shape(config: dict) -> dict:
    """The full layers' shape (48 query heads). The windowed walk's reader
    takes the same dict and so counts q and o at 48 heads where the sliding
    layers have 64: under 1% of its bytes (32 KB of q and o a row against
    2.1 MB of K and V)."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"]}


def expert_shape(config: dict) -> dict:
    """What the expert-layer metrics need of a sparse layer: its width,
    the experts held and published, the experts per token, the layers that
    have experts."""
    return {"hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"],
            "held": config["num_experts"],
            "published": config["num_experts"],
            "per_token": config["num_experts_per_tok"],
            "layers": sum(t == "sparse"
                          for t in config["mlp_layer_types"])}
