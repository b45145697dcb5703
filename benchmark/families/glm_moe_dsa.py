"""GLM-5.2 (Z.ai, `model_type` "glm_moe_dsa"): the DeepSeek-V3 family's block
(multi-head latent attention, `first_k_dense_replace` leading dense SwiGLU
layers, then `n_routed_experts` routed experts chosen by sigmoid scores plus
a selection bias beside one shared expert) with DeepSeek-V3.2's learned
sparse attention: in the layers whose `indexer_types` entry is "full" an
indexer of `index_n_heads` x `index_head_dim` scores every earlier token and
the attention's softmax is over the `index_topk` best; the "shared" layers
that follow reuse that selection. Interleaved RoPE at `rope_parameters
.rope_theta`, no scaling; untied head. Through the program's
`models/deepseek.py`; the plain reference is `reference/glm_moe_dsa.py`.

A configuration may hold one chip's share of a deployment, as
`families/axk1.py` describes it (`n_routed_experts` of
`n_routed_experts_published`, `vocab_size` of `vocab_size_published`), and a
run of the published layers: `indexer_types` is then those layers' entries
and `first_k_dense_replace` how many of them are dense. The model is
constructed under `paddle_tpu.LazyGuard`."""
from __future__ import annotations

from .axk1 import (_attention, _expert, _layers, _sparse_shared,  # noqa: F401
                   expert_shape)


def build(config: dict, recompute: bool = False):
    import dataclasses
    from .. import cells
    try:
        import paddle_tpu
        from paddle_tpu.models.deepseek import (DeepseekConfig,
                                                DeepseekForCausalLM)
        guard = paddle_tpu.LazyGuard
        fields = {f.name for f in dataclasses.fields(DeepseekConfig)}
    except (ImportError, AttributeError) as e:
        raise cells.CellError(
            f"this program has no models/deepseek.py or no LazyGuard ({e}): "
            f"it cannot build {config['name']}") from None
    lacks = sorted({"indexer_types", "index_topk", "rope_interleave"}
                   - fields)
    if lacks:
        raise cells.CellError(
            f"this program's DeepseekConfig has no {lacks}: no indexer, no "
            "index-key pages, no selection shared between layers (learned "
            f"sparse attention); it cannot build {config['name']}")
    if recompute:
        raise cells.CellError("glm_moe_dsa: serving only")
    published = config.get("n_routed_experts_published",
                           config["n_routed_experts"])
    held = config["n_routed_experts"]
    with guard():
        return DeepseekForCausalLM(DeepseekConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            n_routed_experts=published,
            n_shared_experts=config["n_shared_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            first_k_dense_replace=config["first_k_dense_replace"],
            n_group=config["n_group"], topk_group=config["topk_group"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            scoring_func=config["scoring_func"],
            select_bias=config["topk_method"] == "noaux_tc",
            max_position_embeddings=config["max_position_embeddings"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_parameters"]["rope_theta"],
            rope_scaling=None,
            rope_interleave=config["rope_interleave"],
            indexer_types=list(config["indexer_types"]),
            index_n_heads=config["index_n_heads"],
            index_head_dim=config["index_head_dim"],
            index_topk=config["index_topk"],
            indexer_rope_interleave=config["indexer_rope_interleave"],
            tie_word_embeddings=config["tie_word_embeddings"],
            dtype=config["dtype"],
            experts_held=None if held == published else (0, held)))


def _indexer(config: dict) -> int:
    """Matmul parameters of one indexer: W^I_q, W^I_k, W^I_w."""
    Hi, Di = config["index_n_heads"], config["index_head_dim"]
    return (config["q_lora_rank"] * Hi * Di + config["hidden_size"] * Di
            + config["hidden_size"] * Hi)


def _full_layers(config: dict) -> int:
    return list(config["indexer_types"]).count("full")


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against (its experts per token of
    the published router, wherever they are held)."""
    dense, sparse = _layers(config)
    h = config["hidden_size"]
    return (dense * (_attention(config) + 3 * h * config["intermediate_size"])
            + sparse * (_attention(config) + _sparse_shared(config)
                        + config["num_experts_per_tok"] * _expert(config))
            + _full_layers(config) * _indexer(config)
            + h * config["vocab_size"])


def total_params(config: dict) -> int:
    """Held on this chip."""
    dense, sparse = _layers(config)
    h = config["hidden_size"]
    # the two low-rank norms and the block's two
    norms = config["q_lora_rank"] + config["kv_lora_rank"] + 2 * h
    published = expert_shape(config)["published"]
    return (dense * (_attention(config) + norms
                     + 3 * h * config["intermediate_size"])
            + sparse * (_attention(config) + norms + _sparse_shared(config)
                        + published       # the selection bias
                        + config["n_routed_experts"] * _expert(config))
            # an indexer, and its key's LayerNorm (weight and bias)
            + _full_layers(config) * (_indexer(config)
                                      + 2 * config["index_head_dim"])
            + 2 * config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    """`families/axk1.py`'s, and the indexer's: its heads and width, the
    keys a query attends to, the layers that carry one."""
    return {"heads": config["num_attention_heads"], "kv_heads": 1,
            "head_dim": config["kv_lora_rank"] + config["qk_rope_head_dim"],
            "latent": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "index_heads": config["index_n_heads"],
            "index_dim": config["index_head_dim"],
            "index_topk": config["index_topk"],
            "index_layers": _full_layers(config)}
