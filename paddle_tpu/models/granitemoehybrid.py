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
dropless experts). Serving only: `forward(labels=...)` raises.

`experts_held=(first, count)` gives every expert layer one chip's share of
the `num_local_experts` (expert parallelism's unit; `DroplessMoE(held=)`):
the router stays as wide as published and the layer returns its share's
part. The shared expert is whole on every share.

The cached-decode contract (`init_cache` / `forward_with_cache`): a mamba
layer's cache is a `models.generation.RecurrentState`, fixed in size,
where an attention layer's is its `(k, v)` slabs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

from ..core.tensor import apply
from ..distributed.meta_parallel.mp_layers import VocabParallelEmbedding
from ..nn.layer.layers import Layer, LayerList, parameter_dtype
from ..nn.layer.mamba import Mamba2Mixer
from ..nn.layer.moe import DroplessMoE
from .generation import RecurrentState
from .llama import LlamaAttention, LlamaConfig, LlamaMLP, RMSNorm

MAMBA, ATTENTION = "mamba", "attention"


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
        self.layer_types = list(self.layer_types)
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {MAMBA!r} or {ATTENTION!r}; got "
                f"{len(self.layer_types)} with {sorted(bad)}")
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


class GraniteMoeHybridDecoderLayer(Layer):
    def __init__(self, config: GraniteMoeHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.residual_multiplier = config.residual_multiplier
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
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, hidden, cache=None, pos=None, paged=None, adv=None,
                live=None, pack=None):
        h = self.input_layernorm(hidden)
        new_cache = None
        if self.kind == MAMBA:
            h = self.mamba(h, cache=cache, pos=pos, adv=adv, pack=pack)
        else:
            h = self.self_attn(h, cache=cache, pos=pos, paged=paged,
                               pack=pack)
        if cache is not None:
            h, new_cache = h
        hidden = self._residual(hidden, h)
        h = self.post_attention_layernorm(hidden)
        h = self.block_sparse_moe(h, live=live) + self.shared_mlp(h)
        hidden = self._residual(hidden, h)
        return hidden if cache is None else (hidden, new_cache)

    def _residual(self, hidden, branch):
        # a Python scalar inside the traced function: the activations keep
        # their type (bfloat16 stays bfloat16)
        rm = self.residual_multiplier
        return apply(lambda x, b: x + b * rm, hidden, branch)


class GraniteMoeHybridModel(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([
            GraniteMoeHybridDecoderLayer(config, kind)
            for kind in config.layer_types])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None, pos=None, paged=None,
                pack=None):
        em = self.config.embedding_multiplier
        hidden = apply(lambda e: e * em, self.embed_tokens(input_ids))
        if caches is None:
            for layer in self.layers:
                hidden = layer(hidden)
            return self.norm(hidden)
        # what the token-wise code and the per-slot state need to know of a
        # serving step: which positions hold a token, and how many of a
        # row's columns do
        adv = live = None
        if paged is not None:
            slot_pos = pack.slot_pos if pack is not None \
                else jnp.reshape(getattr(pos, "data", pos), (-1,))
            adv = paged.advance(slot_pos)
            live = pack.live[:, None] if pack is not None else \
                jnp.arange(input_ids.shape[1], dtype=jnp.int32) \
                < adv[:, None]
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            hidden, new_cache = layer(hidden, cache=cache, pos=pos,
                                      paged=paged, adv=adv, live=live,
                                      pack=pack)
            new_caches.append(new_cache)
        return self.norm(hidden), new_caches


class GraniteMoeHybridForCausalLM(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        with parameter_dtype(config.dtype):
            self.model = GraniteMoeHybridModel(config)

    def _logits(self, hidden):
        """The tied head: the embedding's rows are the output's columns."""
        scale = 1.0 / self.config.logits_scaling
        return apply(lambda h, e: (h @ e.T) * scale, hidden,
                     self.model.embed_tokens.weight)

    def forward(self, input_ids, labels=None):
        if labels is not None:
            raise NotImplementedError(
                "training GraniteMoeHybridForCausalLM is not wired: the "
                "loss would lack the router's auxiliary loss, and the "
                "recurrence (ops/ssm.py) has no backward kernel")
        return self._logits(self.model(input_ids))

    # ---- the cached-decode contract (models/generation.py) ----
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        cfg = self.config
        dt = dtype or self.model.embed_tokens.weight.dtype
        kv = (batch_size, cfg.num_key_value_heads, max_len, cfg.head_dim)
        return [RecurrentState(*layer.mamba.init_state(batch_size, dt))
                if layer.kind == MAMBA
                else (jnp.zeros(kv, dt), jnp.zeros(kv, dt))
                for layer in self.model.layers]

    def forward_with_cache(self, input_ids, caches, pos, paged=None,
                           adapters=None, pack=None):
        if adapters is not None:
            raise NotImplementedError(
                "LoRA adapters are not wired into GraniteMoeHybrid")
        hidden, new_caches = self.model(input_ids, caches=caches, pos=pos,
                                        paged=paged, pack=pack)
        return self._logits(hidden), new_caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, eos_token_id=None, seed=0):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, do_sample,
                        temperature, top_k, eos_token_id=eos_token_id,
                        seed=seed)
