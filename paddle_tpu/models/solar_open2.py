"""Solar Open 2 (`model_type` "solar_open2", Upstage): a decoder whose mixer
differs by layer. Layer l is "attention" where `gqa_layers` names it (every
fourth, from 0: `gqa_interval` 3 linear layers between two of them), else
"kda": Kimi Delta Attention (`nn/layer/kda.py`: a delta-rule linear
attention with a decay a key channel, `kda_allow_neg_eigval`, a conv of
width 4 in front, rank-d decay and gate projections since
`kda_use_full_proj` is false). Attention is grouped-query without rotary
embedding (`use_rope` false: the linear layers carry the order), scores
scaled by head_dim^-0.5, and its output passes a sigmoid gate a channel
computed from the layer's normed input (`use_gqa_gate`; `gqa_gate` names
another form). Every layer's FFN is a softmax router over `n_routed_experts`
SwiGLU experts of width `moe_intermediate_size`, the chosen
`num_experts_per_tok` renormalised and times `routed_scaling_factor`, plus
`n_shared_experts` shared experts as one SwiGLU that every token takes at
weight 1; no leading dense layer (`first_k_dense_replace` 0), no
multipliers, an untied head:

    x = E[ids]
    for l: x = x + Mixer_l(RMSNorm(x))
           h = RMSNorm(x)
           x = x + MoE(h) + Shared(h)
    logits = RMSNorm(x) @ W_head

Written from the published `config.json` and, for the KDA layer, from
memory of flash-linear-attention's; composed from the layers the other
decoders use (`models/llama.py`'s attention and SwiGLU, `nn/layer/moe.py`'s
dropless experts) over the stack every hybrid decoder shares
(`models/hybrid.py`). Serving only: `forward(labels=...)` raises.

`experts_held=(first, count)` gives every expert layer one chip's share of
the `n_routed_experts` (`DroplessMoE(held=)`): the router stays as wide as
published and the layer returns its share's part. The shared expert is
whole on every share.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple, Union

import jax

from ..nn.layer.kda import KimiDeltaAttention
from ..nn.layer.moe import DroplessMoE
from .hybrid import (ATTENTION, KDA, HybridDecoderLayer, HybridForCausalLM)
from .llama import LlamaAttention, LlamaConfig, LlamaMLP


@dataclass
class SolarOpen2Config:
    """The published keys of `config.json` (defaults: Solar-Open2-250B),
    `dtype`, the share of the experts held here and the form of the
    attention layers' gate."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    # the layers that are attention layers; None: every `gqa_interval + 1`th
    # from 0 (the published list)
    gqa_layers: Optional[Sequence[int]] = None
    gqa_interval: int = 3
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    use_gqa_gate: bool = True
    # the gate's form where `use_gqa_gate`: "elementwise" (`g_proj [hidden,
    # heads * head_dim]`) or "headwise" (`[hidden, heads]`); the config
    # does not say
    gqa_gate: str = "elementwise"
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.gqa_layers is None:
            self.gqa_layers = range(0, self.num_hidden_layers,
                                    self.gqa_interval + 1)
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers
                                if i < self.num_hidden_layers)
        for key, wired in (("use_rope", False), ("kda_use_full_proj", False),
                           ("first_k_dense_replace", 0)):
            if getattr(self, key) != wired:
                raise NotImplementedError(
                    f"SolarOpen2Config: {key} = {getattr(self, key)!r} is "
                    f"not wired (the published model has {wired!r})")
        if self.gqa_gate not in ("elementwise", "headwise"):
            raise ValueError(f"gqa_gate {self.gqa_gate!r}")

    @property
    def layer_types(self):
        return [ATTENTION if i in self.gqa_layers else KDA
                for i in range(self.num_hidden_layers)]

    @property
    def _gate(self) -> Union[bool, str]:
        if not self.use_gqa_gate:
            return False
        return True if self.gqa_gate == "headwise" else "elementwise"

    def _llama(self) -> LlamaConfig:
        """The attention and the shared expert are `models/llama.py`'s."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size
            * max(self.n_shared_experts, 1),
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.head_dim,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype, rope=False,
            attn_output_gate=self._gate)


class SolarOpen2DecoderLayer(HybridDecoderLayer):
    def __init__(self, config: SolarOpen2Config, kind: str):
        super().__init__(kind)
        if kind == KDA:
            self.kda = KimiDeltaAttention(
                config.hidden_size, config.linear_num_heads,
                config.linear_head_dim, config.short_conv_kernel_size,
                config.rms_norm_eps, config.kda_allow_neg_eigval)
        else:
            self.self_attn = LlamaAttention(config._llama())
        self.experts = DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob, held=config.experts_held,
            routed_scale=config.routed_scaling_factor)
        self.shared_experts = LlamaMLP(config._llama()) \
            if config.n_shared_experts else None
        self._norms(config.hidden_size, config.rms_norm_eps)

    def ffn(self, h, live=None):
        out = self.experts(h, live=live)
        if self.shared_experts is None:
            return out
        with jax.named_scope("shared_expert"):
            return out + self.shared_experts(h)


class SolarOpen2ForCausalLM(HybridForCausalLM):
    def __init__(self, config: SolarOpen2Config):
        super().__init__(config, lambda: [
            SolarOpen2DecoderLayer(config, kind)
            for kind in config.layer_types])
