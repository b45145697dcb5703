"""GPT model family (BASELINE config 2: GPT-3 1.3B pure DP).

Reference anchor: the GPT-era ops the reference DOES ship —
softmax_mask_fuse_upper_triangle (fused causal softmax, incubate API) and the TP
parallel layers. Architecture: pre-LN GPT with learned positions, GELU MLP.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, apply
from ..distributed.meta_parallel.mp_layers import (ColumnParallelLinear,
                                                   ParallelCrossEntropy,
                                                   RowParallelLinear,
                                                   VocabParallelEmbedding)
from ..nn import Dropout, Embedding, LayerNorm
from ..nn import functional as F
from ..nn.layer.layers import Layer, LayerList
from ..ops.lora import add_lora_delta
from ..ops.attention import decode_attention, flash_attention, \
    take_positions, update_kv_cache


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: int = 8192
    max_position_embeddings: int = 2048
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    layer_norm_eps: float = 1e-5
    use_recompute: bool = False
    # MoE (ERNIE-MoE-style, BASELINE config 5): 0 = dense
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_every_n_layers: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


GPT_PRESETS = {
    "gpt2-tiny": GPTConfig(vocab_size=512, hidden_size=128,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=512,
                           max_position_embeddings=512),
    "gpt3-125m": GPTConfig(hidden_size=768, num_hidden_layers=12,
                           num_attention_heads=12, intermediate_size=3072),
    "gpt3-350m": GPTConfig(hidden_size=1024, num_hidden_layers=24,
                           num_attention_heads=16, intermediate_size=4096),
    "gpt3-1.3b": GPTConfig(hidden_size=2048, num_hidden_layers=24,
                           num_attention_heads=16, intermediate_size=8192),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_hidden_layers=32,
                           num_attention_heads=32, intermediate_size=16384),
    "ernie-moe-tiny": GPTConfig(vocab_size=512, hidden_size=128,
                                num_hidden_layers=4, num_attention_heads=4,
                                intermediate_size=256,
                                max_position_embeddings=512,
                                moe_num_experts=4),
    "ernie-moe-base": GPTConfig(hidden_size=768, num_hidden_layers=12,
                                num_attention_heads=12,
                                intermediate_size=3072, moe_num_experts=8),
}


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = config.head_dim
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, has_bias=True,
                                             gather_output=False)
        self.out_proj = RowParallelLinear(h, h, has_bias=True,
                                          input_is_parallel=True)
        self.dropout_p = config.attention_dropout_prob

    def forward(self, hidden, cache=None, pos=None, paged=None,
                adapters=None, pack=None):
        qkv = self.qkv_proj(hidden)
        hd = self.head_dim
        if cache is not None:
            if adapters is not None:
                # gathered per-row LoRA delta on the fused qkv projection
                # (ISSUE 20); row 0 of the bank is zeros = base pass-through
                amap, aidx, ascale = adapters
                qkv = add_lora_delta(qkv, hidden, amap.get("qkv_proj"),
                                     aidx, ascale)
            k_cache, v_cache = cache

            def attn_dec(a, kc, vc, pos_):
                # pos_ scalar: whole batch at one offset (generate());
                # pos_ [B]: per-row offsets (slot-paged decode, ISSUE 5).
                # `paged` (closed over — constants, not Tensors) routes
                # attention through the slot-pool block tables (ISSUE 7).
                # `pack` (closed over too): `a` holds packed tokens;
                # attention runs in the slots' layout at their offsets
                if pack is not None:
                    a, pos_ = pack.unpack(a), pack.slot_pos
                B, T = a.shape[0], a.shape[1]
                n_local = a.shape[-1] // (3 * hd)
                a4 = a.reshape(B, T, n_local, 3 * hd)
                q, k, v = jnp.split(a4, 3, axis=-1)
                qh = jnp.swapaxes(q, 1, 2)
                kh = jnp.swapaxes(k, 1, 2)
                vh = jnp.swapaxes(v, 1, 2)
                kc, vc = update_kv_cache(kc, vc, kh, vh, pos_)
                out = decode_attention(qh, kc, vc, pos_,
                                       scale=1.0 / (hd ** 0.5),
                                       paged=paged)
                out = jnp.swapaxes(out, 1, 2).reshape(B, T, -1)
                if pack is not None:
                    out = pack.pack(out)
                return out, kc, vc

            ctx, new_k, new_v = apply(attn_dec, qkv, k_cache, v_cache, pos)
            out = self.out_proj(ctx)
            if adapters is not None:
                out = add_lora_delta(out, ctx, amap.get("out_proj"),
                                     aidx, ascale)
            return out, (new_k, new_v)

        def attn(a):
            B, S, _ = a.shape
            # local heads = local width / (3*head_dim)
            n_local = a.shape[-1] // (3 * hd)
            a = a.reshape(B, S, n_local, 3 * hd)
            q, k, v = jnp.split(a, 3, axis=-1)
            q = jnp.swapaxes(q, 1, 2)
            k = jnp.swapaxes(k, 1, 2)
            v = jnp.swapaxes(v, 1, 2)
            out = flash_attention(q, k, v, causal=True)
            out = jnp.swapaxes(out, 1, 2)
            return out.reshape(B, S, -1)

        ctx = apply(attn, qkv)
        return self.out_proj(ctx)


class GPTDecoderLayer(Layer):
    def __init__(self, config: GPTConfig, use_moe: bool = False):
        super().__init__()
        h = config.hidden_size
        self.norm1 = LayerNorm(h, epsilon=config.layer_norm_eps)
        self.self_attn = GPTAttention(config)
        self.norm2 = LayerNorm(h, epsilon=config.layer_norm_eps)
        self.use_moe = use_moe
        if use_moe:
            from ..nn.layer.moe import MoELayer
            self.moe = MoELayer(h, config.intermediate_size,
                                config.moe_num_experts, config.moe_top_k,
                                config.moe_capacity_factor)
        else:
            self.linear1 = ColumnParallelLinear(h, config.intermediate_size,
                                                has_bias=True,
                                                gather_output=False)
            self.linear2 = RowParallelLinear(config.intermediate_size, h,
                                             has_bias=True,
                                             input_is_parallel=True)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self._use_recompute = config.use_recompute

    def _block(self, x):
        """Returns (x, aux_loss): the MoE aux loss must flow through the
        function OUTPUT (not a layer attribute) so it survives recompute /
        jax.checkpoint retracing."""
        x = x + self.dropout(self.self_attn(self.norm1(x)))
        if self.use_moe:
            h = self.moe(self.norm2(x))
            aux = self.moe.aux_loss
        else:
            h = self.linear1(self.norm2(x))
            h = apply(lambda a: jax.nn.gelu(a), h)
            h = self.linear2(h)
            aux = None
        return x + self.dropout(h), aux

    def forward(self, x, cache=None, pos=None, paged=None, adapters=None,
                pack=None):
        if cache is not None:
            if self.use_moe:
                raise NotImplementedError(
                    "KV-cache decode is not wired through this capacity-"
                    "based MoE layer (it drops tokens by batch "
                    "composition); the served sparse path is models/"
                    "llama.py with LlamaConfig(num_experts=...), over "
                    "nn/layer/moe.py::DroplessMoE")
            h, new_cache = self.self_attn(self.norm1(x), cache=cache,
                                          pos=pos, paged=paged,
                                          adapters=adapters, pack=pack)
            # same dropout as the training forward (identity in eval), so
            # forward_with_cache on a training-mode model matches forward()
            x = x + self.dropout(h)
            h_in = self.norm2(x)
            h = self.linear1(h_in)
            if adapters is not None:
                amap, aidx, ascale = adapters
                h = add_lora_delta(h, h_in, amap.get("linear1"),
                                   aidx, ascale)
            h = apply(lambda a: jax.nn.gelu(a), h)
            h2 = self.linear2(h)
            if adapters is not None:
                h2 = add_lora_delta(h2, h, amap.get("linear2"),
                                    aidx, ascale)
            x = x + self.dropout(h2)
            return x, new_cache
        if self._use_recompute and self.training:
            from ..distributed.fleet.utils.recompute import recompute
            if self.use_moe:
                return recompute(self._block, x)
            out = recompute(lambda a: self._block(a)[0], x)
            return out, None
        return self._block(x)


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.word_embeddings = VocabParallelEmbedding(config.vocab_size,
                                                      config.hidden_size)
        self.position_embeddings = Embedding(config.max_position_embeddings,
                                             config.hidden_size)
        self.dropout = Dropout(config.hidden_dropout_prob)

        def _is_moe(i):
            return (config.moe_num_experts > 0
                    and (i + 1) % config.moe_every_n_layers == 0)

        self.layers = LayerList([GPTDecoderLayer(config, use_moe=_is_moe(i))
                                 for i in range(config.num_hidden_layers)])
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_eps)

    def forward(self, input_ids, caches=None, pos=None, paged=None,
                adapters=None, pack=None):
        """Returns (hidden, total_aux_loss) — aux is None for dense models.
        With caches: (hidden, new_caches), positions offset by `pos`.
        `adapters` is the per-slot LoRA indirection operand
        (per_layer_banks, adapter_idx, scale) — see ops/lora.py. `pack`
        (`ops.attention.TokenPack`): the rows are a serving step's packed
        tokens, one each, and only attention needs to know."""
        S = input_ids.shape[1]
        from ..core.tensor import Tensor, apply as _apply
        from ..tensor.creation import arange
        if caches is not None:
            # absolute learned positions for the decoded slice; scalar pos
            # broadcasts one offset, a [B] vector gives per-row offsets
            # ([B, S] position ids) for slot-paged decode
            pos_ids = _apply(
                lambda p: ((p[:, None] if jnp.ndim(p) else p)
                           + jnp.arange(S)).astype(jnp.int32),
                pos if isinstance(pos, Tensor) else Tensor(pos))
            hidden = self.word_embeddings(input_ids) + \
                self.position_embeddings(pos_ids)
            hidden = self.dropout(hidden)  # identity in eval; parity with
            new_caches = []                # the training forward
            for i, (layer, cache) in enumerate(zip(self.layers, caches)):
                layer_ad = None if adapters is None else (
                    adapters[0][i], adapters[1], adapters[2])
                hidden, nc = layer(hidden, cache=cache, pos=pos,
                                   paged=paged, adapters=layer_ad,
                                   pack=pack)
                new_caches.append(nc)
            return self.final_norm(hidden), new_caches
        pos_ids = arange(S, dtype="int64")
        hidden = self.word_embeddings(input_ids) + \
            self.position_embeddings(pos_ids)
        hidden = self.dropout(hidden)
        total_aux = None
        for layer in self.layers:
            hidden, aux = layer(hidden)
            if aux is not None:
                total_aux = aux if total_aux is None else total_aux + aux
        return self.final_norm(hidden), total_aux


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        # gather_output=False pairs the explicit-TP vocab-sharded logits with
        # ParallelCrossEntropy's sharded softmax-CE (mp_layers.py:249); the
        # GSPMD path ignores the flag.
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def forward(self, input_ids, labels=None):
        hidden, total_aux = self.gpt(input_ids)
        if labels is not None and self._can_fuse_lm_ce():
            # chunked lm-head+CE: never materializes the [B,S,V] logits
            # (ops/softmax_ce.py); identical numerics to the dense path
            import jax.numpy as jnp
            from ..core.tensor import apply
            from ..ops.softmax_ce import fused_linear_cross_entropy

            def f(h, w, y):
                hs = h.reshape(-1, h.shape[-1])
                loss = fused_linear_cross_entropy(hs, w, y.reshape(-1))
                return jnp.mean(loss)

            loss = apply(f, hidden, self.lm_head.weight, labels)
            if total_aux is not None:
                loss = loss + total_aux * self.config.moe_aux_loss_weight
            return loss
        logits = self.lm_head(hidden)
        if labels is not None:
            from ..tensor.math import mean
            loss = mean(self.loss_fn(logits, labels))
            if total_aux is not None:
                loss = loss + total_aux * self.config.moe_aux_loss_weight
            return loss
        return logits

    @staticmethod
    def _can_fuse_lm_ce():
        from ..distributed.meta_parallel.mp_layers import (_explicit_tp,
                                                           _mp_degree)
        from ..ops.attention import sequence_sharded_trace
        # vocab-sharded weights keep the ParallelCrossEntropy path; a
        # sequence-sharded trace keeps the dense path (the chunk scan's
        # [B,S]->[N] reshape would force GSPMD to regather the tokens)
        return (not _explicit_tp() and _mp_degree() <= 1
                and not sequence_sharded_trace())

    # ---- KV-cache generation (parity-plus; models/generation.py) ----
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        cfg = self.config
        if max_len > cfg.max_position_embeddings:
            # jnp.take clamps out-of-range position ids, so decoding past
            # the learned position table would silently reuse the last
            # position embedding instead of erroring
            raise ValueError(
                f"init_cache: max_len={max_len} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}; "
                "GPT's learned position table cannot decode past it")
        dt = dtype or self.gpt.word_embeddings.weight.dtype
        shape = (batch_size, cfg.num_attention_heads, max_len, cfg.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.num_hidden_layers)]

    def query_heads_by_layer(self):
        """Each layer's query heads, one entry an `init_cache` entry."""
        return [self.config.num_attention_heads] \
            * self.config.num_hidden_layers

    def forward_with_cache(self, input_ids, caches, pos, paged=None,
                           adapters=None, pack=None, emit=None):
        """`pack`, `emit`: see `LlamaForCausalLM.forward_with_cache`."""
        hidden, new_caches = self.gpt(input_ids, caches=caches, pos=pos,
                                      paged=paged, adapters=adapters,
                                      pack=pack)
        if emit is not None:
            hidden = apply(take_positions, hidden, emit)
        return self.lm_head(hidden), new_caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, eos_token_id=None, seed=0):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, do_sample,
                        temperature, top_k, eos_token_id=eos_token_id,
                        seed=seed)

    # ---- pipeline-parallel segmentation protocol (pp_layers.py:44-76) ----
    def pipe_layer_prefixes(self):
        return [f"gpt.layers.{i}." for i in range(len(self.gpt.layers))]

    def pipe_layers(self):
        return list(self.gpt.layers)

    def pipe_embed(self, input_ids):
        from ..tensor.creation import arange
        pos = arange(input_ids.shape[1], dtype="int64")
        return self.gpt.word_embeddings(input_ids) + \
            self.gpt.position_embeddings(pos)

    def pipe_logits(self, hidden):
        return self.lm_head(self.gpt.final_norm(hidden))

    def pipe_head(self, hidden, labels):
        from ..tensor.math import mean
        return mean(self.loss_fn(self.pipe_logits(hidden), labels))

    @classmethod
    def from_preset(cls, name: str, **overrides):
        import dataclasses
        cfg = dataclasses.replace(GPT_PRESETS[name], **overrides)
        return cls(cfg)
