"""The DeepSeek-V3 family of decoders (DeepSeek-V3, and models built on its
block such as SK Telecom's A.X-K1, `model_type` "axk1", and, with
DeepSeek-V3.2's learned sparse attention on top, Z.ai's GLM-5.2,
`model_type` "glm_moe_dsa", and, with a hyper-connected residual path of
several streams round the block, XingChen-AGI's Xing4.0, `model_type`
"xing4_0"): multi-head latent attention, a router with sigmoid scores and a
group-limited choice beside a shared expert, dense SwiGLU layers before the
sparse ones.

A file of its own and not `models/llama.py` grown: that file's attention is
"q, k, v of one head width and a `(k, v)` cache", which every other decoder
here shares; this family's attention has none of the three (two low-rank
down-projections with their norms, keys and values up-projected from one
latent, a rotary key shared by all heads, q/k of 192 against v of 128, a
latent cache), so a branch a line would have left `LlamaAttention` two
classes in one. What the families do share is imported: `RMSNorm`,
`LlamaMLP`, the rotary tables (`rope_inv_freq`: YaRN with this family's
`mscale` / `mscale_all_dim`), `DroplessMoE`, the cache ops.

Per layer, pre-norm residual (`x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`),
no biases. Attention, heads h = 1..H:

    c_q = RMSNorm(x W_qa);  [q_nope_h | q_r_h] = c_q W_qb
    [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv); k_r = RoPE(k_r)   one key
    [k_nope_h | v_h] = c_kv W_kvb;  q_r_h = RoPE(q_r_h)
    s_h = scale (q_nope_h . k_nope_h + q_r_h . k_r), causal softmax (f32)
    out = concat_h(sum p v_h) W_o
    scale = (nope + rope)^-0.5 * yarn_mscale(factor, mscale_all_dim)^2

Two forms of the same sum. **Expanded** (the uncached forward): keys and
values are made for every position and `flash_attention` runs over q/k of
`nope + rope` columns, v padded with zeros to that width (one kernel for
every model; the pad costs a third more p.v work on a path that is not
measured). **Absorbed** (the cached forward, every query width: the engine's
chunks and decode rows and `generate()`'s prompt alike, so that the two
stay bit-identical): the cache holds per token `c_kv` (after its norm) and
`k_r` (after RoPE), `kv_lora_rank + qk_rope_head_dim` values; the key
up-projection is folded into the query, the value up-projection applied to
the attention's result:

    qt_h = q_nope_h W_kvb[k part, h]^T          [kv_lora_rank]
    s_h  = scale (qt_h . c_kv + q_r_h . k_r)
    o_h  = (sum p c_kv) W_kvb[v part, h]

which `ops.paged_attention` walks as `paged_latent`: one "KV head" for all H
query heads, the latent page read once for scores and values. The rotary
key's slab is padded to whole lane tiles (`_lanes`: 64 -> 128 columns; a
TPU array's minor dimension is stored in tiles of 128, so a 64-wide slab
takes as much HBM, and the kernel's page copies want whole tiles).

Learned sparse attention (`indexer_types`, one entry a layer; None: every
layer attends to every key). A "full" layer carries an `Indexer` and keeps a
third slab in its cache, one index key of `index_head_dim` a token. With
`h` the block's normed input and `c_q` the query latent above:

    q^I_j = RoPE((c_q W^I_q)[j])          j = 1..index_n_heads, RoPE on the
                                          first qk_rope_head_dim columns
    k^I   = RoPE(LayerNorm(h W^I_k))      one key a token: what is cached
    w     = h W^I_w * index_n_heads^-0.5 * index_head_dim^-0.5
    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])      s <= t, float32
    S_t   = the index_topk positions of largest I[t, .]   (every s <= t
            while t < index_topk), ties to the lower position

and the softmax of that layer, and of every "shared" layer up to the next
"full" one (no indexer weights, no index keys: the selection rides from
layer to layer inside one forward pass, `DeepseekDecoderLayer.forward`'s
`sel`), is over `s in S_t` alone, the same for every head. No option
chooses between a sparse and a dense form: a layer under an indexer is
sparse at every length. It is in this file and not one of its own because
everything but the `Indexer` and one argument of the attention call is the
block above: a branch does not leave `MLAttention` two classes in one, it
leaves it one class with a sublayer on some layers. Through the cache the
selection is `ops.index_select.select` (the `index_score` and `index_topk`
kernels) and the attention `ops.paged_attention.sparse_latent_attention`
(`paged_sparse`); uncached, a dense score matrix and an additive mask.

`rope_interleave`: the rotary columns pair (2i, 2i + 1), not (i, i + D/2).
They are brought into the half-split order once (`_deinterleave`), in q and
k alike, and rotated as ever: every q . k is the interleaved rotation's.

FFN: layers `< first_k_dense_replace` a dense SwiGLU of `intermediate_size`;
the rest `DroplessMoE` (`nn.layer.moe.route`: `scoring_func`, `n_group` /
`topk_group`, `norm_topk_prob`, `routed_scaling_factor`, an optional
selection bias) plus `n_shared_experts` shared experts as one SwiGLU of
`n_shared_experts * moe_intermediate_size`, weight 1. `experts_held` as
`LlamaConfig`'s. Serving only: no load-balancing loss is wired.

The residual path (`hc_mult`; 1: the two adds above). With n = `hc_mult` > 1
a token's residual state is n streams, `X [n, C]`, carried from layer to
layer side by side as `[..., n C]` (stream j the columns j C .. (j + 1) C).
The embedding's row is copied into the n streams and the final RMSNorm
reads their sum. Each sublayer F (attention behind `input_layernorm`, the
FFN behind `post_attention_layernorm`; F itself is unchanged) stands inside
one manifold-constrained hyper-connection (`nn.layer.hyper_connection`,
`ops.hyper_connection`: `phi [n C, n^2 + 2 n]`, `bias`, three gains
`alpha`, all float32 whatever the model's type):

    x'  = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       [n C], float32
    [h_pre | h_post | h_res] = x' phi, split n | n | n^2
    H_pre  = sigmoid(alpha_pre h_pre + b_pre)                [n]
    H_post = 2 sigmoid(alpha_post h_post + b_post)           [n]
    M^0    = exp(clip(alpha_res mat(h_res) + b_res, -hc_res_clamp,
                      hc_res_clamp))                         [n, n]
    M^t    = T_r(T_c(M^(t-1))), t = 1..hc_sinkhorn_iters; T_c: each column
             over (its sum + hc_eps), T_r: each row likewise; H_res = M^last
    u      = sum_j H_pre[j] X[j]               F reads norm(u)      [C]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(norm(u))

so a sublayer reads a learned, input-dependent mixture of the streams and
writes back through a doubly stochastic matrix made per token. Nothing a
slot keeps changes: the cache kinds, `init_cache`, `forward_with_cache` and
`generate()` are the one-stream model's. A one-stream configuration builds
no connection and imports none of it (`DeepseekDecoderLayer.__init__`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import apply
from ..distributed.meta_parallel.mp_layers import (ColumnParallelLinear,
                                                   RowParallelLinear,
                                                   VocabParallelEmbedding)
from ..nn.layer.common import Linear
from ..nn.layer.layers import Layer, LayerList, parameter_dtype
from ..nn.layer.moe import DroplessMoE
from ..nn.layer.norm import LayerNorm
from ..ops.attention import _NEG_INF, decode_attention, \
    decode_attention_packed, flash_attention, take_positions, \
    update_caches, update_kv_cache
from ..ops.index_select import Selection, select, topk_mask
from .llama import LlamaMLP, RMSNorm, SharedExpertMoE, _apply_rope, \
    _rope_cos_sin, yarn_mscale

LANES = 128


def _lanes(width: int) -> int:
    """`width` rounded up to whole lane tiles."""
    return -(-width // LANES) * LANES


def _deinterleave(x):
    """Columns (0, 1, 2, 3, ...) -> (0, 2, ..., 1, 3, ...): interleaved
    rotary pairs brought into the half-split order `_apply_rope` rotates."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)


def _rope_rows(cos, sin, pos, T: int, dtype):
    """The rotary tables' rows of a step: T positions from `pos`, a scalar
    ([T, D]) or one a row ([B, T, D])."""
    if jnp.ndim(pos) == 0:
        cos_t = lax.dynamic_slice_in_dim(cos, pos, T, 0)
        sin_t = lax.dynamic_slice_in_dim(sin, pos, T, 0)
    else:
        row = jax.vmap(lambda tab, p: lax.dynamic_slice_in_dim(tab, p, T, 0),
                       in_axes=(None, 0))
        cos_t, sin_t = row(cos, pos), row(sin, pos)
    return cos_t.astype(dtype), sin_t.astype(dtype)


@dataclass
class DeepseekConfig:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432         # a dense layer's SwiGLU
    moe_intermediate_size: int = 2048      # one expert's
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    # a per-expert bias on the router's choice ("noaux_tc" checkpoints
    # carry one); False: the router has no such parameter
    select_bias: bool = False
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # Hugging Face's `rope_scaling`: None, or {"type": "yarn", "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "mscale", "mscale_all_dim"}
    rope_scaling: Optional[dict] = None
    # rotary pairs (2i, 2i + 1) in place of (i, i + D/2)
    rope_interleave: bool = False
    # learned sparse attention: "full" | "shared" a layer (None: none)
    indexer_types: Optional[Sequence[str]] = None
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_rope_interleave: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # (first, count): every expert layer holds that share of
    # `n_routed_experts` (`DroplessMoE(held=)`)
    experts_held: Optional[Tuple[int, int]] = None
    # residual streams a token (1: `x += F(norm(x))`); the Sinkhorn passes,
    # the epsilon in their denominators and the clamp before their `exp`
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0

    def __post_init__(self):
        if self.tie_word_embeddings:
            raise NotImplementedError("a tied head is not wired")
        if self.hc_mult < 1:
            raise ValueError(f"hc_mult {self.hc_mult}: residual streams")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers")
        kinds = self.indexer_types
        if kinds is not None and (
                len(kinds) != self.num_hidden_layers or kinds[0] != "full"
                or set(kinds) - {"full", "shared"}):
            raise ValueError(
                f"indexer_types {list(kinds)}: one of \"full\" / "
                f"\"shared\" for each of {self.num_hidden_layers} layers, "
                "the first \"full\" (a shared layer reuses the selection "
                "of the full layer above it)")

    @property
    def rope(self) -> dict:
        """The rotary parameters as `llama.rope_inv_freq` reads them."""
        scaling = dict(self.rope_scaling or {})
        kind = scaling.pop("type", scaling.pop("rope_type", "default"))
        return {"rope_type": kind, "rope_theta": self.rope_theta, **scaling}

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rope = self.rope
        if rope["rope_type"] == "yarn" and rope.get("mscale_all_dim"):
            scale *= yarn_mscale(rope["factor"],
                                 rope["mscale_all_dim"]) ** 2
        return scale


class Indexer(Layer):
    """A "full" layer's index queries, index key and head weights (module
    docstring): what the selection is computed from."""

    def __init__(self, config: DeepseekConfig):
        super().__init__()
        self.config = config
        Hi, Di = config.index_n_heads, config.index_head_dim
        self.wq_b = Linear(config.q_lora_rank, Hi * Di, bias_attr=False)
        self.wk = Linear(config.hidden_size, Di, bias_attr=False)
        self.k_norm = LayerNorm(Di, epsilon=1e-6)
        self.weights_proj = Linear(config.hidden_size, Hi, bias_attr=False)

    def forward(self, hidden, c_q):
        """hidden [.., h], c_q [.., q_lora_rank] -> (q [.., Hi * Di],
        k [.., Di], both before RoPE; w [.., Hi] float32)."""
        cfg = self.config
        gain = cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5
        w = apply(lambda a: a.astype(jnp.float32) * gain,
                  self.weights_proj(hidden))
        return self.wq_b(c_q), self.k_norm(self.wk(hidden)), w

    def rotate(self, x, cos, sin):
        """x [B, heads, T, Di]: RoPE on its first `qk_rope_head_dim`
        columns."""
        dr = self.config.qk_rope_head_dim
        rot = x[..., :dr]
        if self.config.indexer_rope_interleave:
            rot = _deinterleave(rot)
        return jnp.concatenate([_apply_rope(rot, cos, sin), x[..., dr:]],
                               -1)


class MLAttention(Layer):
    """Multi-head latent attention (module docstring). `kind`: None (every
    key), or the layer's entry of `indexer_types`."""

    def __init__(self, config: DeepseekConfig, kind: Optional[str] = None):
        super().__init__()
        self.config = config
        self.kind = kind
        self.indexer = Indexer(config) if kind == "full" else None
        h, H = config.hidden_size, config.num_attention_heads
        self.qk_dim = config.qk_nope_head_dim + config.qk_rope_head_dim
        self.q_a_proj = Linear(h, config.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = RMSNorm(config.q_lora_rank, config.rms_norm_eps)
        self.q_b_proj = ColumnParallelLinear(
            config.q_lora_rank, H * self.qk_dim, has_bias=False,
            gather_output=False)
        self.kv_a_proj_with_mqa = Linear(
            h, config.kv_lora_rank + config.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = RMSNorm(config.kv_lora_rank,
                                      config.rms_norm_eps)
        self.kv_b_proj = ColumnParallelLinear(
            config.kv_lora_rank,
            H * (config.qk_nope_head_dim + config.v_head_dim),
            has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(H * config.v_head_dim, h,
                                        has_bias=False,
                                        input_is_parallel=True)

    def _down(self, hidden):
        """(q [.., H * (nope + rope)], c_kv [.., rank] after its norm,
        k_r [.., rope] before RoPE, the indexer's (q, k, w) or ())."""
        rank = self.config.kv_lora_rank
        c_q = self.q_a_layernorm(self.q_a_proj(hidden))
        q = self.q_b_proj(c_q)
        ckv = self.kv_a_proj_with_mqa(hidden)
        c = self.kv_a_layernorm(apply(lambda a: a[..., :rank], ckv))
        index = () if self.indexer is None else self.indexer(hidden, c_q)
        return q, c, apply(lambda a: a[..., rank:], ckv), index

    def _rotary(self, x):
        return _deinterleave(x) if self.config.rope_interleave else x

    def forward(self, hidden, cache=None, pos=None, paged=None, pack=None,
                sel=None):
        """Returns the output, and with a cache the new cache; a layer of
        `indexer_types` also the selection it used (made here if "full",
        else `sel` as given): `out[, cache][, sel]`."""
        q, c, k_r, index = self._down(hidden)
        if cache is not None:
            return self._forward_cached(q, c, k_r, index, cache, pos, paged,
                                        pack, sel)
        cfg = self.config
        H, nope, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
        rope, qk = cfg.rope, self.qk_dim
        # the kernels keep their own 1/sqrt(width): q carries the rest
        q_scale = cfg.softmax_scale * math.sqrt(qk)

        Hi, Di, topk = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk

        def choose(qi, ki, w):
            """The selection as an additive mask [B, 1, S, S]."""
            B, S = qi.shape[:2]
            cos, sin = (t.astype(qi.dtype)
                        for t in _rope_cos_sin(S, dr, rope))
            qi = self.indexer.rotate(
                jnp.swapaxes(qi.reshape(B, S, Hi, Di), 1, 2), cos, sin)
            ki = self.indexer.rotate(ki[:, None], cos, sin)[:, 0]
            s = jnp.einsum("bhtd,bsd->bhts", qi, ki,
                           preferred_element_type=jnp.float32)
            scores = jnp.einsum("bhts,bth->bts", jnp.maximum(s, 0.0), w)
            t = jnp.arange(S, dtype=jnp.int32)
            scores = jnp.where(t[None, :, None] >= t[None, None, :], scores,
                               -jnp.inf)
            keep = topk_mask(scores, topk, impl="reference")
            return jnp.where(keep > 0.5, 0.0, _NEG_INF)[:, None]

        def attn(qa, kv, kr, *mask):
            B, S = qa.shape[:2]
            qh = jnp.swapaxes(qa.reshape(B, S, H, qk), 1, 2)   # [B,H,S,qk]
            kvh = jnp.swapaxes(kv.reshape(B, S, H, nope + dv), 1, 2)
            cos, sin = (t.astype(qa.dtype)
                        for t in _rope_cos_sin(S, dr, rope))
            q_rot = _apply_rope(self._rotary(qh[..., nope:]), cos, sin)
            k_rot = _apply_rope(self._rotary(kr[:, None]), cos, sin)
            qh = jnp.concatenate([qh[..., :nope], q_rot], -1) * q_scale
            kh = jnp.concatenate(
                [kvh[..., :nope], jnp.broadcast_to(k_rot, (B, H, S, dr))],
                -1)
            vh = jnp.pad(kvh[..., nope:],
                         ((0, 0), (0, 0), (0, 0), (0, max(qk - dv, 0))))
            out = flash_attention(qh.astype(qa.dtype), kh, vh, causal=True,
                                  mask=mask[0] if mask else None)[..., :dv]
            return jnp.swapaxes(out, 1, 2).reshape(B, S, H * dv)

        if self.indexer is not None:
            sel = apply(choose, *index)
        more = () if sel is None else (sel,)
        out = self.o_proj(apply(attn, q, self.kv_b_proj(c), k_r, *more))
        return out if self.kind is None else (out, sel)

    def _attend_packed(self, qa, w_kvb, caches, pos, paged, pack, B, T, cos,
                       sin):
        """A layer that attends to every key: the queries stay where the
        step's tokens lie, `[positions, H, .]` (`ops.paged_attention`,
        "Packed queries"). Under a `TokenPack` that is the packed block,
        `qa [step_tokens, 1, H * qk]`, padded so that a row's window of T
        positions from its first fits; else `qa [B, T, H * qk]` as it lies,
        row b's tokens from b * T. Each token is rotated at its own
        position, and the absorption, the walk and the value projection run
        once a position. Returns the context in `qa`'s layout."""
        cfg = self.config
        H, nope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.v_head_dim)
        w = w_kvb.reshape(cfg.kv_lora_rank, H, nope + dv)
        if pack is None:
            qt = qa.reshape(B * T, H, self.qk_dim)
            at = jnp.broadcast_to(
                jnp.reshape(pos, (-1, 1)) + jnp.arange(T, dtype=jnp.int32),
                (B, T)).reshape(-1)
            starts = jnp.arange(B, dtype=jnp.int32) * T
        else:
            qt = jnp.pad(qa[:, 0], ((0, T - 1), (0, 0))).reshape(
                -1, H, self.qk_dim)
            at, starts = jnp.pad(pack.pos, (0, T - 1)), pack.dst[:, 0]
        cos_t, sin_t = (jnp.take(t, at, axis=0, mode="clip").astype(
            qa.dtype)[:, None] for t in (cos, sin))            # [P, 1, dr]
        # to RoPE a position is a sequence of one token
        q_rot = _apply_rope(self._rotary(qt[:, :, None, nope:]), cos_t,
                            sin_t)[:, :, 0]
        q_rot = jnp.pad(q_rot, ((0, 0), (0, 0),
                                (0, caches[1].shape[3] - q_rot.shape[2])))
        q_lat = jnp.einsum("thd,rhd->thr", qt[..., :nope],
                           w[..., :nope]).astype(qa.dtype)
        out = decode_attention_packed(
            q_lat, q_rot, caches[0], caches[1], pos, starts, T,
            scale=cfg.softmax_scale, paged=paged)
        out = jnp.einsum("thr,rhd->thd", out, w[..., nope:])
        out = out.reshape(-1, H * dv).astype(qa.dtype)
        return out.reshape(B, T, H * dv) if pack is None \
            else out[:qa.shape[0], None]

    def _forward_cached(self, q, c, k_r, index, cache, pos, paged, pack,
                        sel):
        """The absorbed form through the latent cache `(c [B, 1, L, rank],
        r [B, 1, L, lanes(rope)])`: the step's latents and rotary keys are
        written at `pos`, then every query attends to the cache
        (`decode_attention(q_rope=)`). `pack` as `LlamaAttention`'s. A
        "full" layer's cache has a third slab, `k_index [B, 1, L, Di]`: the
        step's index keys are written with the other two and the selection
        is made over it; a layer under an indexer attends to the selection
        (`decode_attention(sel=)`)."""
        cfg = self.config
        H, nope, dr, dv, rank = (
            cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
        Hi, Di, topk = cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk
        rope, qk, scale = cfg.rope, self.qk_dim, cfg.softmax_scale
        full, n_cache = self.indexer is not None, len(cache)

        def attn(qa, ca, kr, w_kvb, *rest):
            # behind the caches and the position: a "full" layer's index
            # queries, index key and head weights; a "shared" layer's
            # selection; nothing
            caches, pos_, more = rest[:n_cache], rest[n_cache], \
                rest[n_cache + 1:]
            if pack is not None:
                # the step's latents and keys go to their slots' rows; the
                # queries of a layer that attends to every key stay packed
                ca, kr = (pack.unpack(a) for a in (ca, kr))
                if self.kind is not None:
                    qa = pack.unpack(qa)
                if full:
                    more = tuple(pack.unpack(a) for a in more)
                pos_ = pack.slot_pos
            B, T = ca.shape[:2]
            cos, sin = _rope_cos_sin(caches[0].shape[2], dr, rope)
            cos_t, sin_t = _rope_rows(cos, sin, pos_, T, qa.dtype)
            pad = ((0, 0), (0, 0), (0, 0), (0, caches[1].shape[3] - dr))
            k_rot = jnp.pad(_apply_rope(self._rotary(kr[:, None]), cos_t,
                                        sin_t), pad)
            if self.kind is None:
                caches = update_kv_cache(*caches, ca[:, None], k_rot, pos_)
                return (self._attend_packed(qa, w_kvb, caches, pos_, paged,
                                            pack, B, T, cos, sin),) \
                    + tuple(caches)
            qh = jnp.swapaxes(qa.reshape(B, T, H, qk), 1, 2)   # [B,H,T,qk]
            q_rot = jnp.pad(_apply_rope(self._rotary(qh[..., nope:]), cos_t,
                                        sin_t), pad)
            if full:
                qi, ki, w = more
                qi = self.indexer.rotate(
                    jnp.swapaxes(qi.reshape(B, T, Hi, Di), 1, 2), cos_t,
                    sin_t)
                ki = self.indexer.rotate(ki[:, None], cos_t, sin_t)
                caches = update_caches(caches, (ca[:, None], k_rot, ki),
                                       pos_)
                chosen = select(qi, jnp.swapaxes(w, 1, 2), caches[2], pos_,
                                topk, paged=paged)
            else:
                caches = update_kv_cache(*caches, ca[:, None], k_rot, pos_)
                chosen = Selection(*more) if more else None
            w = w_kvb.reshape(rank, H, nope + dv)
            q_lat = jnp.einsum("bhtd,rhd->bhtr", qh[..., :nope],
                               w[..., :nope]).astype(qa.dtype)
            out = decode_attention(q_lat, caches[0], caches[1], pos_,
                                   scale=scale, paged=paged, q_rope=q_rot,
                                   sel=chosen)
            out = jnp.einsum("bhtr,rhd->bthd", out, w[..., nope:])
            out = out.reshape(B, T, H * dv).astype(qa.dtype)
            if pack is not None:
                out = pack.pack(out)
            return (out,) + tuple(caches) + (tuple(chosen) if full else ())

        given = index if full else tuple(sel or ())
        out = apply(attn, q, c, k_r, self.kv_b_proj.weight, *cache, pos,
                    *given)
        ctx, new_cache = out[0], tuple(out[1:1 + n_cache])
        if self.kind is None:
            return self.o_proj(ctx), new_cache
        # a shared layer hands on the selection it was handed
        return self.o_proj(ctx), new_cache, \
            Selection(*out[1 + n_cache:]) if full else sel


class DeepseekMoE(SharedExpertMoE):
    """The routed experts' part (held here) plus the shared expert's."""

    def __init__(self, config: DeepseekConfig):
        super().__init__(DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            config.norm_topk_prob, held=config.experts_held,
            scoring=config.scoring_func, n_group=config.n_group,
            topk_group=config.topk_group, select_bias=config.select_bias,
            routed_scale=config.routed_scaling_factor),
            config, config.n_shared_experts * config.moe_intermediate_size)


class DeepseekDecoderLayer(Layer):
    def __init__(self, config: DeepseekConfig, layer: int):
        super().__init__()
        kinds = config.indexer_types
        self.self_attn = MLAttention(
            config, None if kinds is None else kinds[layer])
        self.sparse = layer >= config.first_k_dense_replace
        self.mlp = DeepseekMoE(config) if self.sparse else LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.attn_hc = self.mlp_hc = None
        if config.hc_mult > 1:
            # here and nowhere else: a one-stream model loads no line of it
            from ..nn.layer.hyper_connection import HyperConnection
            self.attn_hc, self.mlp_hc = (HyperConnection(
                config.hidden_size, config.hc_mult,
                config.hc_sinkhorn_iters, config.hc_eps,
                config.hc_res_clamp, config.rms_norm_eps) for _ in "am")

    def forward(self, hidden, cache=None, pos=None, paged=None, live=None,
                pack=None, sel=None):
        """`sel`: the selection of the "full" layer above (None above the
        first, and in a model without indexers). Returns `(hidden,
        new_cache or None, the selection this layer used)`. With
        `hc_mult` streams `hidden` is `[..., hc_mult * hidden_size]`."""
        def attend(x):
            nonlocal sel
            h = self.self_attn(self.input_layernorm(x), cache=cache,
                               pos=pos, paged=paged, pack=pack, sel=sel)
            new_cache = None
            if self.self_attn.kind is not None:
                h, sel = h[:-1], h[-1]
                h = h[0] if cache is None else h
            if cache is not None:
                h, new_cache = h
            return h, new_cache

        def ffn(x):
            h = self.post_attention_layernorm(x)
            # a router must not send padding to experts: it is told what is
            # live
            return self.mlp(h, live=live) if self.sparse else self.mlp(h)

        if self.attn_hc is None:
            h, new_cache = attend(hidden)
            hidden = hidden + h
            return hidden + ffn(hidden), new_cache, sel
        # a hyper-connection in each residual add's place: a sublayer reads
        # `pre`'s mixture of the streams, `post` writes its result back into
        # every stream
        u, carry = self.attn_hc.pre(hidden)
        h, new_cache = attend(u)
        hidden = self.attn_hc.post(hidden, h, carry)
        u, carry = self.mlp_hc.pre(hidden)
        return self.mlp_hc.post(hidden, ffn(u), carry), new_cache, sel


class DeepseekModel(Layer):
    def __init__(self, config: DeepseekConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([DeepseekDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def _streams_in(self, hidden):
        """The embedding's row copied into every residual stream."""
        n = self.config.hc_mult
        return hidden if n == 1 else apply(
            lambda a: jnp.concatenate([a] * n, -1), hidden)

    def _streams_out(self, hidden):
        """The residual streams' sum (float32, then the model's type)."""
        n = self.config.hc_mult
        if n == 1:
            return hidden

        def total(a):
            parts = jnp.split(a.astype(jnp.float32), n, -1)
            return sum(parts[1:], parts[0]).astype(a.dtype)

        return apply(total, hidden)

    def forward(self, input_ids, caches=None, pos=None, paged=None,
                pack=None):
        hidden = self._streams_in(self.embed_tokens(input_ids))
        sel = None
        if caches is None:
            for layer in self.layers:
                hidden, _, sel = layer(hidden, sel=sel)
            return self.norm(self._streams_out(hidden))
        live = None
        if pack is not None:
            live = pack.live[:, None]
        elif paged is not None:
            live = paged.live(getattr(pos, "data", pos), input_ids.shape[1])
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            hidden, new_cache, sel = layer(hidden, cache=cache, pos=pos,
                                           paged=paged, live=live,
                                           pack=pack, sel=sel)
            new_caches.append(new_cache)
        return self.norm(self._streams_out(hidden)), new_caches


class DeepseekForCausalLM(Layer):
    def __init__(self, config: DeepseekConfig):
        super().__init__()
        self.config = config
        with parameter_dtype(config.dtype):
            self.model = DeepseekModel(config)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)

    def forward(self, input_ids, labels=None):
        if labels is not None:
            raise NotImplementedError(
                "training DeepseekForCausalLM is not wired: the loss would "
                "lack the router's balancing terms")
        return self.lm_head(self.model(input_ids))

    # ---- the cached-decode contract (models/generation.py) ----
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Per layer a `generation.LatentKV`: `c [batch, 1, max_len,
        kv_lora_rank]` and `r [batch, 1, max_len, lanes(qk_rope_head_dim)]`
        (the rotary key in the first `qk_rope_head_dim` columns, zeros
        behind); for a layer with an indexer a `generation.IndexedLatentKV`:
        those two and `k_index [batch, 1, max_len, index_head_dim]`."""
        from .generation import IndexedLatentKV, LatentKV
        cfg = self.config
        dt = dtype or self.model.embed_tokens.weight.dtype

        def slab(width):
            return jnp.zeros((batch_size, 1, max_len, width), dt)

        kinds = cfg.indexer_types or [None] * cfg.num_hidden_layers
        return [IndexedLatentKV(slab(cfg.kv_lora_rank),
                                slab(_lanes(cfg.qk_rope_head_dim)),
                                slab(cfg.index_head_dim))
                if kind == "full" else
                LatentKV(slab(cfg.kv_lora_rank),
                         slab(_lanes(cfg.qk_rope_head_dim)))
                for kind in kinds]

    def query_heads_by_layer(self):
        """Each layer's query heads, one entry an `init_cache` entry."""
        return [self.config.num_attention_heads] \
            * self.config.num_hidden_layers

    def forward_with_cache(self, input_ids, caches, pos, paged=None,
                           adapters=None, pack=None, emit=None):
        """`pack`, `emit`: see `LlamaForCausalLM.forward_with_cache`."""
        if adapters is not None:
            raise NotImplementedError(
                "LoRA adapters are not wired into DeepseekForCausalLM")
        hidden, new_caches = self.model(input_ids, caches=caches, pos=pos,
                                        paged=paged, pack=pack)
        if emit is not None:
            hidden = apply(take_positions, hidden, emit)
        return self.lm_head(hidden), new_caches

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, eos_token_id=None, seed=0):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, do_sample,
                        temperature, top_k, eos_token_id=eos_token_id,
                        seed=seed)
