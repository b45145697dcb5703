"""A.X-K1 (SK Telecom, `model_type` "axk1"): the DeepSeek-V3 family's block.
Multi-head latent attention (queries through a rank-`q_lora_rank`
bottleneck, keys and values up-projected from one rank-`kv_lora_rank`
latent a token, one rotary key of `qk_rope_head_dim` shared by all heads,
q/k heads of `qk_nope_head_dim + qk_rope_head_dim` against v heads of
`v_head_dim`), YaRN by that family's `mscale` convention,
`first_k_dense_replace` leading dense SwiGLU layers, then layers of
`n_routed_experts` routed experts (sigmoid scores, `topk_group` of `n_group`
groups eligible, `num_experts_per_tok` chosen, renormalised and scaled by
`routed_scaling_factor`) beside `n_shared_experts` shared ones; untied head.
Through the program's `models/deepseek.py`; the plain reference is
`reference/axk1.py`.

A configuration may hold one chip's share of a deployment that divides each
layer over several chips by expert parallelism: `n_routed_experts` of the
router's `n_routed_experts_published` experts (the first ones), and a slice
of the vocabulary (`vocab_size` of `vocab_size_published` rows: ids, logits
and sampling are over the slice). The router keeps its published width and
its experts per token; what the absent experts would add is left out, in
the program and in the reference alike.

The model is constructed under `paddle_tpu.LazyGuard`: `harness.build_model`
loads the seeded weights over the constructor's, and this configuration's
two copies at once are more than a chip holds."""
from __future__ import annotations


def build(config: dict, recompute: bool = False):
    from .. import cells
    try:
        import paddle_tpu
        from paddle_tpu.models.deepseek import (DeepseekConfig,
                                                DeepseekForCausalLM)
        guard = paddle_tpu.LazyGuard
    except (ImportError, AttributeError) as e:
        raise cells.CellError(
            "this program has no models/deepseek.py (multi-head latent "
            "attention, a latent cache, the group-limited sigmoid router) "
            f"or no LazyGuard ({e}): it cannot build {config['name']}") \
            from None
    if recompute:
        raise cells.CellError("axk1: serving only")
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
            scoring_func=config["scoring_func"], select_bias=False,
            max_position_embeddings=config["max_position_embeddings"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_theta"],
            rope_scaling=config["rope_scaling"],
            tie_word_embeddings=config["tie_word_embeddings"],
            dtype=config["dtype"],
            experts_held=None if held == published else (0, held)))


def _attention(config: dict) -> int:
    """Matmul parameters of one attention layer."""
    h, H = config["hidden_size"], config["num_attention_heads"]
    q, kv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    return (h * q + q * H * (nope + rope) + h * (kv + rope)
            + kv * H * (nope + dv) + H * dv * h)


def expert_shape(config: dict) -> dict:
    """What the expert-layer metrics need of a sparse layer: its width,
    the experts held and published, the experts per token, the layers that
    have experts."""
    return {"hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"],
            "held": config["n_routed_experts"],
            "published": config.get("n_routed_experts_published",
                                    config["n_routed_experts"]),
            "per_token": config["num_experts_per_tok"],
            "layers": config["num_hidden_layers"]
            - config["first_k_dense_replace"]}


def _expert(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _layers(config: dict) -> tuple:
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def _sparse_shared(config: dict) -> int:
    """A sparse layer's FFN outside its routed experts: the router (as
    wide as published) and the shared experts."""
    return config["hidden_size"] * expert_shape(config)["published"] \
        + config["n_shared_experts"] * _expert(config)


def matmul_params(config: dict) -> int:
    """Active: what one token multiplies against (its experts per token of
    the published router, wherever they are held)."""
    dense, sparse = _layers(config)
    h = config["hidden_size"]
    return (dense * (_attention(config) + 3 * h * config["intermediate_size"])
            + sparse * (_attention(config) + _sparse_shared(config)
                        + config["num_experts_per_tok"] * _expert(config))
            + h * config["vocab_size"])


def total_params(config: dict) -> int:
    """Held on this chip."""
    dense, sparse = _layers(config)
    h = config["hidden_size"]
    # the two low-rank norms and the block's two
    norms = config["q_lora_rank"] + config["kv_lora_rank"] + 2 * h
    return (dense * (_attention(config) + norms
                     + 3 * h * config["intermediate_size"])
            + sparse * (_attention(config) + norms + _sparse_shared(config)
                        + config["n_routed_experts"] * _expert(config))
            + 2 * config["vocab_size"] * h + h)


def attention_shape(config: dict) -> dict:
    """One "KV head" (the latent and the rotary key) for every query head;
    `head_dim` is what a key is wide in the cache."""
    return {"heads": config["num_attention_heads"], "kv_heads": 1,
            "head_dim": config["kv_lora_rank"] + config["qk_rope_head_dim"],
            "latent": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"]}
