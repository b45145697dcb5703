"""Xing4.0 (XingChen-AGI, `model_type` "xing4_0"): the DeepSeek-V3 family's
block as `families/axk1.py` describes it (multi-head latent attention,
`first_k_dense_replace` leading dense SwiGLU layers, then `n_routed_experts`
routed experts chosen by sigmoid scores plus a selection bias beside a
shared expert; untied head) inside a residual path of `hc_mult` streams: a
manifold-constrained hyper-connection round each sublayer (`phi [hc_mult x
hidden, hc_mult^2 + 2 hc_mult]`, a bias as long, three gains) reads the
sublayer's input as a learned, input-dependent mixture of the streams and
writes its result back through a doubly stochastic `hc_mult x hc_mult`
matrix made per token by `hc_sinkhorn_iters` Sinkhorn passes. Through the
program's `models/deepseek.py` (`DeepseekConfig.hc_mult`); the plain
reference is `reference/xing4_0.py`.

Every expert and the whole vocabulary are held (the configuration's own
`ep_size` is 1); a configuration is a run of the published layers. The
model is constructed under `paddle_tpu.LazyGuard`."""
from __future__ import annotations

from .axk1 import (_attention, _expert, _layers, _sparse_shared,  # noqa: F401
                   attention_shape, expert_shape, matmul_params)


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
    lacks = sorted({"hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp",
                    "select_bias"} - fields)
    if lacks:
        raise cells.CellError(
            f"this program's DeepseekConfig has no {lacks}: one residual "
            "stream a token, no hyper-connection round a sublayer; it "
            f"cannot build {config['name']}")
    if recompute:
        raise cells.CellError("xing4_0: serving only")
    if config["n_routed_experts"] != config.get(
            "n_routed_experts_published", config["n_routed_experts"]):
        raise cells.CellError("xing4_0: every expert is held (ep_size 1)")
    clamp = config["mhc_h_res_clamp_max"]
    if config["mhc_h_res_clamp_min"] != -clamp:
        raise cells.CellError("xing4_0: the clamp is symmetric")
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
            n_routed_experts=config["n_routed_experts"],
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
            rope_theta=config["rope_theta"],
            rope_scaling=config["rope_scaling"],
            tie_word_embeddings=config["tie_word_embeddings"],
            dtype=config["dtype"],
            hc_mult=config["hc_mult"],
            hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
            hc_eps=config["hc_eps"], hc_res_clamp=clamp))


def connection_params(config: dict) -> int:
    """One hyper-connection: phi, its bias, the three gains."""
    n = config["hc_mult"]
    k = n * n + 2 * n
    return n * config["hidden_size"] * k + k + 3


def total_params(config: dict) -> int:
    """Held on this chip."""
    dense, sparse = _layers(config)
    h = config["hidden_size"]
    # the two low-rank norms, the block's two, and its two connections
    shared = (_attention(config) + config["q_lora_rank"]
              + config["kv_lora_rank"] + 2 * h
              + 2 * connection_params(config))
    bias = config["n_routed_experts"] \
        if config["topk_method"] == "noaux_tc" else 0
    return (dense * (shared + 3 * h * config["intermediate_size"])
            + sparse * (shared + _sparse_shared(config) + bias
                        + config["n_routed_experts"] * _expert(config))
            + 2 * config["vocab_size"] * h + h)
