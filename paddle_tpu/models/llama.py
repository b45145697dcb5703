"""Llama-2 model family — the flagship (BASELINE configs 3 & 4).

Reference capability anchor: the reference has no Llama model in-tree; its GPT-era
parallel layers (fleet/meta_parallel/parallel_layers/mp_layers.py) define the TP
contract this model uses. Architecture follows Llama-2 (RMSNorm, RoPE, SwiGLU,
GQA), built TPU-first:
- attention/MLP projections are the Megatron TP layers carrying PartitionSpecs
  over the `model` mesh axis; under parallelize() GSPMD shards them and inserts
  the TP collectives;
- attention runs through ops.flash_attention (Pallas on long sequences);
- weights default bf16-friendly; norm/softmax math is fp32 inside the ops.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor, apply
from ..distributed.meta_parallel.mp_layers import (ColumnParallelLinear,
                                                   ParallelCrossEntropy,
                                                   RowParallelLinear,
                                                   VocabParallelEmbedding)
from ..nn import functional as F
from ..nn.layer.layers import Layer, LayerList, parameter_dtype
from ..nn.layer.moe import DroplessMoE
from ..ops.attention import decode_attention, flash_attention, \
    take_positions, update_kv_cache
from ..ops.lora import add_lora_delta


FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    # the type the constructor creates every parameter in (no float32 copy
    # is made first: a model that fills the chip in bf16 must never exist
    # in float32)
    dtype: str = "float32"
    # sparse experts (OLMoE): 0 = the dense SwiGLU MLP; otherwise every
    # layer's FFN is `num_experts` experts of width `intermediate_size`,
    # `num_experts_per_tok` of them per position (nn/layer/moe.py)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    # RMSNorm over the whole q and the whole k projection, before RoPE
    qk_norm: bool = False
    # False: no rotary embedding (a model that takes its order from
    # elsewhere, e.g. state-space layers between its attention layers)
    rope: bool = True
    # scores are scaled by this and not by 1/sqrt(head_dim): q is scaled by
    # `attention_multiplier * sqrt(head_dim)` after its projection, so the
    # attention kernels keep their own 1/sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    # width of one head; None: hidden_size // num_attention_heads. A model
    # may state another (q is then heads * head_dim wide, not hidden_size)
    head_dim: Optional[int] = None
    # one entry a layer, FULL or SLIDING; None: every layer attends to the
    # whole cache. A SLIDING layer's query sees the `sliding_window` keys
    # up to itself, and its cache in the serving pool is a ring
    layer_types: Optional[Sequence[str]] = None
    sliding_window: Optional[int] = None
    # rotary parameters by layer type, as Hugging Face's `rope_parameters`:
    # {layer type: {"rope_type": "default" | "yarn", "rope_theta", and for
    # yarn "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor"}}. None, or a type it lacks:
    # `rope_theta`, unscaled
    rope_parameters: Optional[Dict[str, dict]] = None
    # (first, count): every expert layer holds that share of `num_experts`
    # (one chip's under expert parallelism; `DroplessMoE(held=)`)
    experts_held: Optional[Tuple[int, int]] = None
    # one entry a layer: that layer's query heads, where they differ by
    # layer (q, o and the GQA group are then the layer's own; the KV heads
    # and the head width are the model's). None: `num_attention_heads`
    num_attention_heads_per_layer: Optional[Sequence[int]] = None
    # a sigmoid gate on the attention output, computed from the layer's
    # normed input ("Gated Attention for Large Language Models"): True, a
    # gate a query head by `g_proj [hidden, heads]` (the head-wise form);
    # "elementwise", a gate a channel by `g_proj [hidden, heads * head_dim]`
    attn_output_gate: Union[bool, str] = False
    # one entry a layer, DENSE or SPARSE; None: every layer sparse where
    # `num_experts` > 0. A DENSE layer's FFN is the SwiGLU MLP of width
    # `intermediate_size`
    mlp_layer_types: Optional[Sequence[str]] = None
    # width of a routed expert where it is not `intermediate_size`
    moe_intermediate_size: Optional[int] = None
    # > 0: a sparse layer adds one SwiGLU expert of this width that every
    # position takes at weight 1, beside the routed ones
    shared_expert_intermediate_size: int = 0
    # the router's scores ("softmax" | "sigmoid") and the factor on the
    # chosen experts' gates (`nn/layer/moe.py::route`)
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.num_attention_heads_per_layer is not None:
            self.num_attention_heads_per_layer = tuple(
                int(h) for h in self.num_attention_heads_per_layer)
            if len(self.num_attention_heads_per_layer) \
                    != self.num_hidden_layers or any(
                        h <= 0 or h % self.num_key_value_heads
                        for h in self.num_attention_heads_per_layer):
                raise ValueError(
                    f"num_attention_heads_per_layer: "
                    f"{self.num_hidden_layers} multiples of "
                    f"{self.num_key_value_heads} KV heads, got "
                    f"{self.num_attention_heads_per_layer}")
        if self.mlp_layer_types is not None:
            self.mlp_layer_types = tuple(self.mlp_layer_types)
            bad = set(self.mlp_layer_types) - {DENSE, SPARSE}
            if bad or len(self.mlp_layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"mlp_layer_types: {self.num_hidden_layers} entries of "
                    f"{DENSE!r} / {SPARSE!r}, got {self.mlp_layer_types}")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            bad = set(self.layer_types) - {FULL, SLIDING}
            if bad or len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_types: {self.num_hidden_layers} entries of "
                    f"{FULL!r} / {SLIDING!r}, got {self.layer_types}")
            if SLIDING in self.layer_types and not (
                    self.sliding_window and self.sliding_window > 0):
                raise ValueError("a sliding_attention layer needs "
                                 "sliding_window >= 1")

    def window_of(self, layer: Optional[int]) -> Optional[int]:
        """The window of layer `layer`, None where it attends to all."""
        if layer is None or self.layer_types is None \
                or self.layer_types[layer] != SLIDING:
            return None
        return int(self.sliding_window)

    def rope_of(self, layer: Optional[int]) -> dict:
        """The rotary parameters of layer `layer`."""
        kind = FULL if layer is None or self.layer_types is None \
            else self.layer_types[layer]
        return dict((self.rope_parameters or {}).get(
            kind, {"rope_type": "default", "rope_theta": self.rope_theta}))

    def heads_of(self, layer: Optional[int]) -> int:
        """The query heads of layer `layer`."""
        if layer is None or self.num_attention_heads_per_layer is None:
            return self.num_attention_heads
        return self.num_attention_heads_per_layer[layer]

    def sparse_at(self, layer: Optional[int]) -> bool:
        """Whether layer `layer`'s FFN is a router over experts."""
        if not self.num_experts:
            return False
        return layer is None or self.mlp_layer_types is None \
            or self.mlp_layer_types[layer] == SPARSE


LLAMA_PRESETS = {
    "llama2-tiny": LlamaConfig(vocab_size=512, hidden_size=128,
                               intermediate_size=352, num_hidden_layers=2,
                               num_attention_heads=4, num_key_value_heads=4,
                               max_position_embeddings=512),
    "llama2-7b": LlamaConfig(),
    "llama2-13b": LlamaConfig(hidden_size=5120, intermediate_size=13824,
                              num_hidden_layers=40, num_attention_heads=40,
                              num_key_value_heads=40),
    "llama2-70b": LlamaConfig(hidden_size=8192, intermediate_size=28672,
                              num_hidden_layers=80, num_attention_heads=64,
                              num_key_value_heads=8),
}


class RMSNorm(Layer):
    def __init__(self, hidden_size, eps=1e-5):
        super().__init__()
        from ..nn import initializer as I
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=I.Constant(1.0))
        self.weight.partition_spec = P(None)
        self.eps = eps

    def forward(self, x):
        eps = self.eps

        def f(a, w):
            h = a.astype(jnp.float32)
            var = jnp.mean(h * h, axis=-1, keepdims=True)
            h = h * jax.lax.rsqrt(var + eps)
            return (h * w.astype(jnp.float32)).astype(a.dtype)

        return apply(f, x, self.weight)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction for a context stretched `factor` times:
    `0.1 mscale ln(factor) + 1` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(head_dim, rope: dict):
    """(inv_freq [D/2] float32, the factor cos and sin are multiplied by)
    of one layer type's rotary parameters (`LlamaConfig.rope_parameters`).

    "default": `theta^(-2i/D)`, factor 1. "yarn" (Peng et al. 2023, as
    Hugging Face computes it): dimensions that turn more than `beta_fast`
    times over the original context keep their frequency, those that turn
    less than `beta_slow` times take it divided by `factor`, a linear ramp
    between; cos and sin are scaled by `attention_factor`. Where the
    parameters give none it is `yarn_mscale(factor)`, or, with the DeepSeek
    family's `mscale` and `mscale_all_dim`, `yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)`: that family puts the correction
    into the softmax scale (times `yarn_mscale(factor, mscale_all_dim)`
    squared, the attention layer's to apply) and leaves cos and sin the
    ratio, 1 where the two are equal."""
    theta = float(rope["rope_theta"])
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return inv_freq, 1.0
    if kind != "yarn":
        raise NotImplementedError(f"rope_type {kind!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def correction_dim(turns):
        return head_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))),
               head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    attention_factor = rope.get("attention_factor")
    if attention_factor is None and rope.get("mscale") \
            and rope.get("mscale_all_dim"):
        attention_factor = yarn_mscale(factor, rope["mscale"]) \
            / yarn_mscale(factor, rope["mscale_all_dim"])
    elif attention_factor is None:
        attention_factor = yarn_mscale(factor)
    return (inv_freq / factor * ramp + inv_freq * (1.0 - ramp),
            float(attention_factor))


def _rope_cos_sin(seq_len, head_dim, rope: dict):
    """cos, sin [S, D] of positions 0..S-1 under one layer type's rotary
    parameters (`LlamaConfig.rope_of`)."""
    inv_freq, factor = rope_inv_freq(head_dim, rope)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)           # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], -1)  # [S, D]
    if factor != 1.0:
        return jnp.cos(emb) * factor, jnp.sin(emb) * factor
    return jnp.cos(emb), jnp.sin(emb)


def _apply_rope(x, cos, sin):
    # x: [B, H, S, D]; cos/sin [S, R] (shared positions) or [B, S, R]
    # (per-row positions, slot-paged decode). R < D (a partial rotary
    # embedding): the first R dimensions of a head turn, the rest pass
    rotary = cos.shape[-1]
    if rotary < x.shape[-1]:
        return jnp.concatenate(
            [_apply_rope(x[..., :rotary], cos, sin), x[..., rotary:]], -1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], -1)
    if cos.ndim == 3:
        return x * cos[:, None] + rotated * sin[:, None]
    return x * cos[None, None] + rotated * sin[None, None]


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig, layer: Optional[int] = None):
        super().__init__()
        self.config = config
        # by the layer's type: its window (None: the whole cache) and its
        # rotary parameters
        self.window = config.window_of(layer)
        self.rope = config.rope_of(layer)
        self.num_heads = config.heads_of(layer)
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        # the dimensions of a head the rotary embedding turns: the first
        # `head_dim * partial_rotary_factor` of the layer type's rotary
        # parameters, the rest passed through (YaRN's ramp is computed over
        # the turned ones)
        self.rotary_dim = int(self.head_dim * self.rope.get(
            "partial_rotary_factor", 1.0))
        h = config.hidden_size
        self.q_proj = ColumnParallelLinear(h, self.num_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(self.num_heads * self.head_dim, h,
                                        has_bias=False, input_is_parallel=True)
        if config.qk_norm:
            self.q_norm = RMSNorm(self.num_heads * self.head_dim,
                                  config.rms_norm_eps)
            self.k_norm = RMSNorm(self.num_kv_heads * self.head_dim,
                                  config.rms_norm_eps)
        if config.attn_output_gate:
            wide = config.attn_output_gate == "elementwise"
            self.g_proj = ColumnParallelLinear(
                h, self.num_heads * (self.head_dim if wide else 1),
                has_bias=False, gather_output=False)

    def _gated(self, ctx, hidden):
        """`ctx [..., heads * head_dim]` with each head's part times that
        head's gate (each channel times its own in the element-wise form),
        `sigmoid(hidden g_proj)` in float32 (`hidden` the layer's normed
        input, in ctx's layout); ctx itself where the model has no gate."""
        if not self.config.attn_output_gate:
            return ctx
        hd = self.head_dim

        def gate(c, g):
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(g.astype(jnp.float32))
                if g.shape[-1] == c.shape[-1]:
                    return c * g.astype(c.dtype)
                c = c.reshape(*c.shape[:-1], -1, hd)
                return (c * g[..., None].astype(c.dtype)).reshape(
                    *c.shape[:-2], -1)

        return apply(gate, ctx, self.g_proj(hidden))

    def forward(self, hidden, attn_mask=None, cache=None, pos=None,
                paged=None, adapters=None, pack=None):
        if attn_mask is not None:
            raise NotImplementedError(
                "padding masks are not wired into the fused attention yet; "
                "pack sequences or pad-to-multiple instead")
        q = self.q_proj(hidden)
        k = self.k_proj(hidden)
        v = self.v_proj(hidden)
        n_rep = self.num_heads // self.num_kv_heads
        hd = self.head_dim
        rope_params, window = self.rope, self.window
        if cache is not None:
            if adapters is not None:
                # gathered per-row LoRA deltas (ISSUE 20); bank row 0 is
                # zeros so adapter-less rows stay bit-identical to base
                amap, aidx, ascale = adapters
                q = add_lora_delta(q, hidden, amap.get("q_proj"),
                                   aidx, ascale)
                k = add_lora_delta(k, hidden, amap.get("k_proj"),
                                   aidx, ascale)
                v = add_lora_delta(v, hidden, amap.get("v_proj"),
                                   aidx, ascale)
        if self.config.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.config.attention_multiplier is not None:
            q_scale = self.config.attention_multiplier * math.sqrt(hd)
            q = apply(lambda a: a * q_scale, q)
        rope = self.config.rope
        if cache is not None:
            return self._forward_cached(q, k, v, cache, pos, n_rep, hd,
                                        paged=paged, adapters=adapters,
                                        pack=pack, hidden=hidden)

        def attn(qa, ka, va):
            qh = qa.reshape(qa.shape[0], qa.shape[1], -1, hd)
            kh = ka.reshape(ka.shape[0], ka.shape[1], -1, hd)
            vh = va.reshape(va.shape[0], va.shape[1], -1, hd)
            qh = jnp.swapaxes(qh, 1, 2)   # [B, H, S, D]
            kh = jnp.swapaxes(kh, 1, 2)
            vh = jnp.swapaxes(vh, 1, 2)
            if rope:
                cos, sin = _rope_cos_sin(qa.shape[1], self.rotary_dim,
                                         rope_params)
                cos = cos.astype(qh.dtype)[None].squeeze(0)
                sin = sin.astype(qh.dtype)[None].squeeze(0)
                qh = _apply_rope(qh, cos, sin)
                kh = _apply_rope(kh, cos, sin)
            if n_rep > 1:  # GQA: repeat kv heads
                kh = jnp.repeat(kh, n_rep, axis=1)
                vh = jnp.repeat(vh, n_rep, axis=1)
            out = flash_attention(qh, kh, vh, causal=True, window=window)
            out = jnp.swapaxes(out, 1, 2)
            return out.reshape(out.shape[0], out.shape[1], -1)

        ctx = apply(attn, q, k, v)
        return self.o_proj(self._gated(ctx, hidden))

    def _forward_cached(self, q, k, v, cache, pos, n_rep, hd, paged=None,
                        adapters=None, pack=None, hidden=None):
        """Static-shape KV-cache decode/prefill step (jit/scan friendly):
        new k/v are written into the [B, Hkv, Lmax, D] cache at `pos`,
        attention runs over the FULL cache with an absolute-position causal
        mask (cols <= pos + t). No reference analog (Paddle 2.1 core has no
        generation loop) — TPU-first inference parity-plus. With `pack`
        (`ops.attention.TokenPack`) q/k/v arrive as packed tokens and
        attention runs in the slots' own layout, the context packed again
        behind it.

        A window layer (`self.window`): with `paged` (the serving pool's
        step) its cache is a ring of `paged.ring_pages` pages, written at
        `pos mod ring` and read from the window's first block; without, a
        full-length cache read the same way (`generate()`)."""
        k_cache, v_cache = cache
        window, rope_params = self.window, self.rope
        ring = None
        if window is not None and paged is not None:
            # the rotary table reaches the slot's capacity and the
            # chunk-wide stripe a free row writes past it
            ring, positions = paged.ring, paged.positions
            paged = paged.window_view()

        def attn_dec(qa, ka, va, kc, vc, pos_):
            import jax.numpy as jnp
            from jax import lax
            if pack is not None:
                qa, ka, va = pack.unpack(qa), pack.unpack(ka), pack.unpack(va)
                pos_ = pack.slot_pos
            B, T = qa.shape[0], qa.shape[1]
            Lmax = kc.shape[2]
            qh = jnp.swapaxes(qa.reshape(B, T, -1, hd), 1, 2)
            kh = jnp.swapaxes(ka.reshape(B, T, -1, hd), 1, 2)
            vh = jnp.swapaxes(va.reshape(B, T, -1, hd), 1, 2)
            if self.config.rope:
                cos, sin = _rope_cos_sin(
                    Lmax if ring is None else positions + T,
                    self.rotary_dim, rope_params)
                if jnp.ndim(pos_) == 0:
                    cos_t = lax.dynamic_slice_in_dim(cos, pos_, T, 0)
                    sin_t = lax.dynamic_slice_in_dim(sin, pos_, T, 0)
                else:
                    # per-row rotation angles for slot-paged decode: each
                    # row sits at its own absolute position → cos/sin
                    # [B, T, D]
                    row = jax.vmap(
                        lambda tab, p: lax.dynamic_slice_in_dim(tab, p, T,
                                                                0),
                        in_axes=(None, 0))
                    cos_t, sin_t = row(cos, pos_), row(sin, pos_)
                cos_t = cos_t.astype(qh.dtype)
                sin_t = sin_t.astype(qh.dtype)
                qh = _apply_rope(qh, cos_t, sin_t)
                kh = _apply_rope(kh, cos_t, sin_t)
            kc, vc = update_kv_cache(kc, vc, kh, vh, pos_, ring=ring)
            # `paged` closed over (constants): slot-pool block-table
            # routing for the ragged kernel (ISSUE 7)
            out = decode_attention(qh, kc, vc, pos_,
                                   scale=1.0 / (hd ** 0.5), paged=paged,
                                   window=window)
            out = jnp.swapaxes(out, 1, 2).reshape(B, T, -1)
            if pack is not None:
                out = pack.pack(out)
            return out, kc, vc

        ctx, new_k, new_v = apply(attn_dec, q, k, v, k_cache, v_cache, pos)
        ctx = self._gated(ctx, hidden)
        out = self.o_proj(ctx)
        if adapters is not None:
            amap, aidx, ascale = adapters
            out = add_lora_delta(out, ctx, amap.get("o_proj"), aidx, ascale)
        return out, (new_k, new_v)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig, width: Optional[int] = None):
        super().__init__()
        h, i = config.hidden_size, width or config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, i, has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, i, has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(i, h, has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x, adapters=None):
        gate = self.gate_proj(x)
        up = self.up_proj(x)
        if adapters is not None:
            amap, aidx, ascale = adapters
            gate = add_lora_delta(gate, x, amap.get("gate_proj"),
                                  aidx, ascale)
            up = add_lora_delta(up, x, amap.get("up_proj"), aidx, ascale)
        act = apply(lambda g, u: jax.nn.silu(g) * u, gate, up)
        down = self.down_proj(act)
        if adapters is not None:
            down = add_lora_delta(down, act, amap.get("down_proj"),
                                  aidx, ascale)
        return down


class SharedExpertMoE(Layer):
    """A sparse FFN beside a shared expert: the routed experts' part
    (`experts`, a `DroplessMoE`, held here) plus one SwiGLU MLP of width
    `shared_width` that every position takes at weight 1
    (`shared_experts`; None at width 0)."""

    def __init__(self, experts: DroplessMoE, config, shared_width: int):
        super().__init__()
        self.experts = experts
        self.shared_experts = LlamaMLP(config, shared_width) \
            if shared_width else None

    def forward(self, x, live=None):
        out = self.experts(x, live=live)
        if self.shared_experts is None:
            return out
        with jax.named_scope("shared_expert"):
            return out + self.shared_experts(x)


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, layer: Optional[int] = None):
        super().__init__()
        self.self_attn = LlamaAttention(config, layer)
        self.sparse = config.sparse_at(layer)
        if self.sparse:
            shared = config.shared_expert_intermediate_size
            experts = DroplessMoE(
                config.hidden_size,
                config.moe_intermediate_size or config.intermediate_size,
                config.num_experts, config.num_experts_per_tok,
                config.norm_topk_prob, held=config.experts_held,
                scoring=config.router_scoring,
                routed_scale=config.routed_scaling_factor)
            self.mlp = SharedExpertMoE(experts, config, shared) \
                if shared else experts
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self._use_recompute = config.use_recompute

    def _block(self, hidden):
        residual = hidden
        h = self.input_layernorm(hidden)
        h = self.self_attn(h)
        hidden = residual + h
        residual = hidden
        h = self.post_attention_layernorm(hidden)
        h = self.mlp(h)
        return residual + h

    def forward(self, hidden, cache=None, pos=None, paged=None,
                adapters=None, live=None, pack=None):
        if cache is not None:
            residual = hidden
            h, new_cache = self.self_attn(self.input_layernorm(hidden),
                                          cache=cache, pos=pos,
                                          paged=paged, adapters=adapters,
                                          pack=pack)
            hidden = residual + h
            h = self.post_attention_layernorm(hidden)
            # a dense MLP computes padding too and nobody reads it; a
            # router would send it to experts, so it is told what is live
            hidden = hidden + (self.mlp(h, live=live) if self.sparse
                               else self.mlp(h, adapters=adapters))
            return hidden, new_cache
        if self._use_recompute and self.training:
            from ..distributed.fleet.utils.recompute import recompute
            return recompute(self._block, hidden)
        return self._block(hidden)


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None, pos=None, paged=None,
                adapters=None, pack=None):
        hidden = self.embed_tokens(input_ids)
        if caches is not None:
            live = None
            if pack is not None:
                live = pack.live[:, None]
            elif paged is not None and self.config.num_experts:
                live = paged.live(getattr(pos, "data", pos),
                                  input_ids.shape[1])
            new_caches = []
            for i, (layer, cache) in enumerate(zip(self.layers, caches)):
                layer_ad = None if adapters is None else (
                    adapters[0][i], adapters[1], adapters[2])
                hidden, nc = layer(hidden, cache=cache, pos=pos,
                                   paged=paged, adapters=layer_ad,
                                   live=live, pack=pack)
                new_caches.append(nc)
            return self.norm(hidden), new_caches
        for layer in self.layers:
            hidden = layer(hidden)
        return self.norm(hidden)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        with parameter_dtype(config.dtype):
            self.llama = LlamaModel(config)
            # gather_output=False: under explicit TP the vocab-sharded
            # logits feed ParallelCrossEntropy's sharded softmax-CE directly
            # (Megatron pairing; mp_layers.py:249). The GSPMD path ignores
            # the flag.
            self.lm_head = ColumnParallelLinear(config.hidden_size,
                                                config.vocab_size,
                                                has_bias=False,
                                                gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def forward(self, input_ids, labels=None):
        if labels is not None and self.config.num_experts:
            raise NotImplementedError(
                "training a sparse-expert LlamaConfig is not wired yet: the "
                "loss would lack the router's load-balancing and z losses "
                "(ROADMAP B1, the train half: those losses, gradients "
                "against benchmark/reference/olmoe.py, the dropless layer "
                "under `ep`)")
        hidden = self.llama(input_ids)
        logits = self.lm_head(hidden)
        if labels is not None:
            loss = self.loss_fn(logits, labels)
            from ..tensor.math import mean
            return mean(loss)
        return logits

    # ---- KV-cache generation (parity-plus; models/generation.py) ----
    def init_cache(self, batch_size: int, max_len: int, dtype=None,
                   window_slab=None):
        """Per layer `(k, v)` slabs `[batch, Hkv, max_len, D]`. A cache
        manager that keeps a window layer's keys in a ring passes
        `window_slab(window) -> columns`: such a layer's entry is then a
        `generation.WindowKV` of that many columns, by whose type and
        shape the manager knows the layer's kind and its ring. Left out
        (`generate()`), every layer's slab is `max_len` long."""
        from .generation import WindowKV
        cfg = self.config
        dt = dtype or self.llama.embed_tokens.weight.dtype

        def slab(cols):
            shape = (batch_size, cfg.num_key_value_heads, cols, cfg.head_dim)
            return jnp.zeros(shape, dt), jnp.zeros(shape, dt)

        entries = []
        for i in range(cfg.num_hidden_layers):
            window = cfg.window_of(i)
            if window is None or window_slab is None:
                entries.append(slab(max_len))
            else:
                entries.append(WindowKV(*slab(int(window_slab(window)))))
        return entries

    def query_heads_by_layer(self):
        """Each layer's query heads, one entry an `init_cache` entry (part
        of the cached-decode contract: a cache manager knows a layer's kind
        by its cache, never its heads)."""
        return [self.config.heads_of(i)
                for i in range(self.config.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches, pos, paged=None,
                           adapters=None, pack=None, emit=None):
        """`pack` (`ops.attention.TokenPack`, the serving step's): the
        rows of `input_ids [T, 1]` are a step's live tokens, each at its
        own `pos [T]`, and `pack` tells attention which slot and column
        each belongs to. The logits come back packed, `[T, 1, V]`.

        `emit [E]` (the serving step's too): the flat positions of the
        block, viewed `[positions, hidden]`, whose logits somebody reads.
        The head runs on those rows alone and the logits come back
        `[E, V]`; the caches are written for every position as before."""
        hidden, new_caches = self.llama(input_ids, caches=caches, pos=pos,
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

    # ---- pipeline-parallel segmentation protocol ----
    # (the LayerDesc/SharedLayerDesc contract of reference pp_layers.py:44-76,
    # expressed as embed/layers/head callables for the 1F1B stage scan)
    def pipe_layer_prefixes(self):
        return [f"llama.layers.{i}."
                for i in range(len(self.llama.layers))]

    def pipe_layers(self):
        return list(self.llama.layers)

    def pipe_embed(self, input_ids):
        return self.llama.embed_tokens(input_ids)

    def pipe_logits(self, hidden):
        return self.lm_head(self.llama.norm(hidden))

    def pipe_head(self, hidden, labels):
        from ..tensor.math import mean
        return mean(self.loss_fn(self.pipe_logits(hidden), labels))

    @classmethod
    def from_preset(cls, name: str, **overrides):
        import dataclasses
        # a head width the preset derived follows the overridden sizes
        overrides.setdefault("head_dim", None)
        cfg = dataclasses.replace(LLAMA_PRESETS[name], **overrides)
        return cls(cfg)
