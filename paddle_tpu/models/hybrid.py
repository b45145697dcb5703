"""A decoder whose mixer differs by layer: the stack that the hybrid
families share (`models/granitemoehybrid.py`, `models/jamba.py`,
`models/solar_open2.py`).

    x = E[ids] [* embedding_multiplier]
    for l: x = x + [residual_multiplier *] Mixer_l(RMSNorm(x))
           x = x + [residual_multiplier *] FFN_l(RMSNorm(x))
    logits = RMSNorm(x) @ E^T [/ logits_scaling]           (tied head)
           | RMSNorm(x) @ W_head             (`tie_word_embeddings` false)

`kinds[l]` is "mamba" (a state-space mixer of `nn/layer/mamba.py`), "kda"
(the delta-rule linear attention of `nn/layer/kda.py`) or "attention"
(`models/llama.py`'s, without rotary embedding: the recurrent layers carry
the order). A family is a layer class: a subclass of `HybridDecoderLayer`
that builds its mixer under the attribute its kind names (`self.mamba`,
`self.kda` or `self.self_attn`) and its FFN's layers and says what the FFN
computes (`ffn`); the multipliers are the family's configuration's, and one
that is None is not traced at all. Serving only: `forward(labels=...)`
raises (`ops/ssm.py` and `ops/kda.py` have no backward kernel).

The cached-decode contract (`init_cache` / `forward_with_cache`): a
recurrent layer's cache (mamba, kda) is a `models.generation.RecurrentState`,
fixed in size, each of its two arrays in the type the mixer's `init_state`
gives it; an attention layer's is its `(k, v)` slabs.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from ..core.tensor import apply
from ..distributed.meta_parallel.mp_layers import (ColumnParallelLinear,
                                                   VocabParallelEmbedding)
from ..nn.layer.layers import Layer, LayerList, parameter_dtype
from ..ops.attention import take_positions
from .generation import RecurrentState
from .llama import RMSNorm

MAMBA, KDA, ATTENTION = "mamba", "kda", "attention"
# the kinds whose mixer carries a fixed-size state (`init_state`, the
# `Mamba2Mixer.forward` contract); a layer holds it under its kind's name
RECURRENT = (MAMBA, KDA)


def check_kinds(kinds: Sequence[str], num_layers: int, what: str) -> list:
    kinds = list(kinds)
    bad = set(kinds) - {*RECURRENT, ATTENTION}
    if bad or len(kinds) != num_layers:
        raise ValueError(
            f"{what} must name {num_layers} layers, each {MAMBA!r}, "
            f"{KDA!r} or {ATTENTION!r}; got {len(kinds)} with {sorted(bad)}")
    return kinds


class HybridDecoderLayer(Layer):
    """One block. A subclass's `__init__` builds `self.mamba` (kind MAMBA),
    `self.kda` (KDA) or `self.self_attn` (ATTENTION) and its FFN's layers,
    then calls `_norms`; its `ffn(h, live)` is the FFN of a normed `h`."""

    def __init__(self, kind: str, residual_multiplier=None):
        super().__init__()
        self.kind = kind
        self.residual_multiplier = residual_multiplier

    def _norms(self, hidden_size: int, eps: float):
        self.input_layernorm = RMSNorm(hidden_size, eps)
        self.post_attention_layernorm = RMSNorm(hidden_size, eps)

    def ffn(self, h, live=None):
        raise NotImplementedError

    @property
    def recurrent(self):
        """The layer's recurrent mixer, None for an attention layer."""
        return getattr(self, self.kind) if self.kind in RECURRENT else None

    def forward(self, hidden, cache=None, pos=None, paged=None, adv=None,
                live=None, pack=None):
        h = self.input_layernorm(hidden)
        new_cache = None
        if self.kind in RECURRENT:
            h = self.recurrent(h, cache=cache, pos=pos, adv=adv, pack=pack)
        else:
            h = self.self_attn(h, cache=cache, pos=pos, paged=paged,
                               pack=pack)
        if cache is not None:
            h, new_cache = h
        hidden = self._residual(hidden, h)
        h = self.ffn(self.post_attention_layernorm(hidden), live)
        hidden = self._residual(hidden, h)
        return hidden if cache is None else (hidden, new_cache)

    def _residual(self, hidden, branch):
        # a Python scalar inside the traced function: the activations keep
        # their type (bfloat16 stays bfloat16)
        rm = self.residual_multiplier
        if rm is None:
            return apply(lambda x, b: x + b, hidden, branch)
        return apply(lambda x, b: x + b * rm, hidden, branch)


class HybridModel(Layer):
    """`config`: `vocab_size`, `hidden_size`, `rms_norm_eps`, and
    `embedding_multiplier` where the family has one."""

    def __init__(self, config, layers: Sequence[HybridDecoderLayer]):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList(list(layers))
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None, pos=None, paged=None,
                pack=None):
        hidden = self.embed_tokens(input_ids)
        em = getattr(self.config, "embedding_multiplier", None)
        if em is not None:
            hidden = apply(lambda e: e * em, hidden)
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


class HybridForCausalLM(Layer):
    """`config` as `HybridModel`'s, with `dtype`, `num_key_value_heads`,
    `head_dim`, and `logits_scaling` or `tie_word_embeddings` false where
    the family has them; `layers` is called under the configuration's
    parameter type."""

    def __init__(self, config, layers):
        super().__init__()
        self.config = config
        with parameter_dtype(config.dtype):
            self.model = HybridModel(config, layers())
            if not getattr(config, "tie_word_embeddings", True):
                self.lm_head = ColumnParallelLinear(
                    config.hidden_size, config.vocab_size, has_bias=False,
                    gather_output=False)

    def _logits(self, hidden):
        """The tied head (the embedding's rows are the output's columns),
        or the model's own `lm_head`."""
        if not getattr(self.config, "tie_word_embeddings", True):
            return self.lm_head(hidden)
        scaling = getattr(self.config, "logits_scaling", None)
        if scaling is None:
            return apply(lambda h, e: h @ e.T, hidden,
                         self.model.embed_tokens.weight)
        scale = 1.0 / scaling
        return apply(lambda h, e: (h @ e.T) * scale, hidden,
                     self.model.embed_tokens.weight)

    def forward(self, input_ids, labels=None):
        if labels is not None:
            raise NotImplementedError(
                f"training {type(self).__name__} is not wired: the "
                "recurrence (ops/ssm.py) has no backward kernel, and a "
                "router's auxiliary loss would be missing")
        return self._logits(self.model(input_ids))

    # ---- the cached-decode contract (models/generation.py) ----
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        cfg = self.config
        dt = dtype or self.model.embed_tokens.weight.dtype
        kv = (batch_size, cfg.num_key_value_heads, max_len, cfg.head_dim)
        return [RecurrentState(*layer.recurrent.init_state(batch_size, dt))
                if layer.kind in RECURRENT
                else (jnp.zeros(kv, dt), jnp.zeros(kv, dt))
                for layer in self.model.layers]

    def query_heads_by_layer(self):
        """Each layer's query heads, one entry an `init_cache` entry: a
        recurrent layer has none."""
        return [0 if layer.kind in RECURRENT else layer.self_attn.num_heads
                for layer in self.model.layers]

    def forward_with_cache(self, input_ids, caches, pos, paged=None,
                           adapters=None, pack=None, emit=None):
        """`pack`, `emit`: see `LlamaForCausalLM.forward_with_cache`."""
        if adapters is not None:
            raise NotImplementedError(
                f"LoRA adapters are not wired into {type(self).__name__}")
        hidden, new_caches = self.model(input_ids, caches=caches, pos=pos,
                                        paged=paged, pack=pack)
        if emit is not None:
            hidden = apply(take_positions, hidden, emit)
        return self._logits(hidden), new_caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, eos_token_id=None, seed=0):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, do_sample,
                        temperature, top_k, eos_token_id=eos_token_id,
                        seed=seed)
