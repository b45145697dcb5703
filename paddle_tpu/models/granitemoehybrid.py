"""Granite 4.0-H (`model_type` "granitemoehybrid", IBM): a decoder whose
mixer differs by layer. `layer_types[l]` is "mamba" (a Mamba-2 state-space
mixer, `nn/layer/mamba.py`) or "attention" (grouped-query attention with no
rotary embedding: the state-space layers carry the order); every layer's
FFN is a router over sparse SwiGLU experts plus one shared expert that
every token takes; four scalar multipliers and a tied head:

    x = E[ids] * embedding_multiplier
    for l: x = x + residual_multiplier * Mixer_l(RMSNorm(x))
           h = RMSNorm(x)
           x = x + residual_multiplier * (MoE(h) + Shared(h))
    logits = RMSNorm(x) @ E^T / logits_scaling

Attention scores are scaled by `attention_multiplier`, not 1/sqrt(d). The
router's gates are the softmax over the chosen experts' logits (= softmax
over all, renormalised over the top-k). Written from the published
`config.json` and from memory of Hugging Face's
`modeling_granitemoehybrid.py`; composed from the layers the other decoders
use (`models/llama.py`'s attention, norm and SwiGLU, `nn/layer/moe.py`'s
dropless experts) over the stack every hybrid decoder shares
(`models/hybrid.py`: the loop over the layers, the residual path, the
cached-decode contract). Serving only: `forward(labels=...)` raises.

`experts_held=(first, count)` gives every expert layer one chip's share of
the `num_local_experts` (expert parallelism's unit; `DroplessMoE(held=)`):
the router stays as wide as published and the layer returns its share's
part. The shared expert is whole on every share.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from ..nn.layer.mamba import Mamba2Mixer
from ..nn.layer.moe import DroplessMoE
from .hybrid import (ATTENTION, MAMBA, HybridDecoderLayer,
                     HybridForCausalLM, HybridModel, check_kinds)
from .llama import LlamaAttention, LlamaConfig, LlamaMLP


@dataclass
class GraniteMoeHybridConfig:
    """The published keys of `config.json` (defaults: granite-4.0-h-small),
    `dtype`, and the share of the experts held here."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768            # width of one routed expert
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    # one of MAMBA / ATTENTION per layer; None: attention after every nine
    # mamba layers, starting with five (the published pattern)
    layer_types: Optional[Sequence[str]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [ATTENTION if i % 10 == 5 else MAMBA
                                for i in range(self.num_hidden_layers)]
        self.layer_types = check_kinds(
            self.layer_types, self.num_hidden_layers, "layer_types")
        if self.mamba_expand * self.hidden_size \
                != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand x hidden_size = "
                f"{self.mamba_expand * self.hidden_size} is not "
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def _llama(self, intermediate_size: int) -> LlamaConfig:
        """The attention and the shared expert are `models/llama.py`'s."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype, rope=False,
            attention_multiplier=self.attention_multiplier)


class GraniteMoeHybridDecoderLayer(HybridDecoderLayer):
    def __init__(self, config: GraniteMoeHybridConfig, kind: str):
        super().__init__(kind, config.residual_multiplier)
        if kind == MAMBA:
            self.mamba = Mamba2Mixer(
                config.hidden_size, config.mamba_n_heads,
                config.mamba_d_head, config.mamba_d_state,
                config.mamba_d_conv, config.mamba_n_groups,
                config.rms_norm_eps)
        else:
            self.self_attn = LlamaAttention(
                config._llama(config.shared_intermediate_size))
        self.block_sparse_moe = DroplessMoE(
            config.hidden_size, config.intermediate_size,
            config.num_local_experts, config.num_experts_per_tok,
            norm_topk_prob=True, held=config.experts_held)
        self.shared_mlp = LlamaMLP(
            config._llama(config.shared_intermediate_size))
        self._norms(config.hidden_size, config.rms_norm_eps)

    def ffn(self, h, live=None):
        return self.block_sparse_moe(h, live=live) + self.shared_mlp(h)


def _layers(config: GraniteMoeHybridConfig):
    return [GraniteMoeHybridDecoderLayer(config, kind)
            for kind in config.layer_types]


class GraniteMoeHybridModel(HybridModel):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config, _layers(config))


class GraniteMoeHybridForCausalLM(HybridForCausalLM):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config, lambda: _layers(config))
