"""Jamba (`model_type` "jamba", AI21): a decoder whose mixer differs by
layer. Layer l is "attention" iff `l mod attn_layer_period ==
attn_layer_offset` (Hugging Face's `layers_block_type`), else "mamba": a
Mamba-1 state-space mixer (`nn/layer/mamba.py::Mamba1Mixer`: a step size a
channel, a decay a channel and state element, RMSNorms on dt, B and C).
Attention is multi-query or grouped-query without rotary embedding or any
position table (the state-space layers carry the order), scores scaled by
head_dim^-0.5; every FFN is the dense SwiGLU; no multipliers; a tied head:

    x = E[ids]
    for l: x = x + Mixer_l(RMSNorm(x))
           x = x + MLP(RMSNorm(x))
    logits = RMSNorm(x) @ E^T

Written from the published `config.json` and from memory of Hugging Face's
`modeling_jamba.py`; a configuration of the stack every hybrid decoder
shares (`models/hybrid.py`) out of `models/llama.py`'s attention and
SwiGLU. `num_experts` above 1 (Jamba's routed FFNs every
`expert_layer_period` layers) is refused: the dense models of the family
are what is served. Serving only: `forward(labels=...)` raises.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..nn.layer.mamba import Mamba1Mixer
from .hybrid import (ATTENTION, MAMBA, HybridDecoderLayer,
                     HybridForCausalLM)
from .llama import LlamaAttention, LlamaConfig, LlamaMLP


@dataclass
class JambaConfig:
    """The published keys of `config.json` (defaults: AI21-Jamba2-3B) and
    `dtype`."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_experts: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_experts != 1:
            raise NotImplementedError(
                f"Jamba with num_experts = {self.num_experts}: the routed "
                "FFNs of the family's expert models are not wired")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset {self.attn_layer_offset} outside the "
                f"period of {self.attn_layer_period}")

    @property
    def layer_types(self):
        return [ATTENTION if i % self.attn_layer_period
                == self.attn_layer_offset else MAMBA
                for i in range(self.num_hidden_layers)]

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def _llama(self) -> LlamaConfig:
        """The attention and the FFN are `models/llama.py`'s."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype, rope=False)


class JambaDecoderLayer(HybridDecoderLayer):
    def __init__(self, config: JambaConfig, kind: str):
        super().__init__(kind)
        if kind == MAMBA:
            self.mamba = Mamba1Mixer(
                config.hidden_size, config.mamba_expand * config.hidden_size,
                config.mamba_d_state, config.mamba_dt_rank,
                config.mamba_d_conv, config.rms_norm_eps)
        else:
            self.self_attn = LlamaAttention(config._llama())
        self.feed_forward = LlamaMLP(config._llama())
        self._norms(config.hidden_size, config.rms_norm_eps)

    def ffn(self, h, live=None):
        return self.feed_forward(h)


class JambaForCausalLM(HybridForCausalLM):
    def __init__(self, config: JambaConfig):
        super().__init__(config, lambda: [
            JambaDecoderLayer(config, kind) for kind in config.layer_types])
