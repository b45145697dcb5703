"""Plain reference for OLMoE (allenai/OLMoE-1B-7B-0125-Instruct), from its
`config.json` and Hugging Face's `modeling_olmoe.py` (`OlmoeForCausalLM`;
written from memory, the sandbox has no network; the configuration file
lists what that leaves `assumed`). One decoder block:

    h  = RMSNorm(x);  q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk), v = h Wv
    x  = x + Attn(rope(q), rope(k), v) Wo
    h' = RMSNorm(x);  p = softmax(h' Wr) over the experts
    S  = the `num_experts_per_tok` largest p  (renormalised over S only if
         `norm_topk_prob`; OLMoE's is false)
    x  = x + sum_{e in S} p_e * (silu(h' Wg_e) * (h' Wu_e)) Wd_e

The two q/k norms run over the whole projection (all heads at once), before
the rotary embedding (rotate-half form, angles `pos / theta**(2i/d)`).
Causal softmax attention, grouped-query where the configuration has fewer
KV heads (OLMoE has as many), a final RMSNorm and an untied output head.

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`, on whatever weights it is handed (upcast where they are
used). No kernels, no cache, no sorting, no grouped matmul: every expert is
computed for every position and weighted by the position's top-k mask (zero
for the experts it did not choose), one expert after the other so that only
one expert's weights are alive in float32. Weights are named as
`models/llama.py` names them: matrices [in, out], experts stacked on a
leading axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama import F32, _rms_norm, _rope


def _experts(h, router, w_gate, w_up, w_down, top_k, renormalise):
    """h [T, hidden] float32 -> (the expert layer's output [T, hidden], the
    dense [T, E] weights: p on a position's chosen experts, 0 elsewhere)."""
    p = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(p, top_k)
    if renormalise:
        top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)

    def one(out, expert):
        wg, wu, wd, w_e = expert
        y = (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
            @ wd.astype(F32)
        return out + w_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w_gate, w_up, w_down, weight.T))
    return out, weight


def forward(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (logits [B, S, V] float32, the routing weights
    of every layer [L, B, S, E] float32: for comparing the experts a
    program chose with the reference's)."""
    def w(name):
        return weights[name].astype(F32)

    L = config["num_hidden_layers"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    B, S = ids.shape
    routing = []
    with jax.default_matmul_precision("highest"):
        x = w("llama.embed_tokens.weight")[ids]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for i in range(L):
            p = f"llama.layers.{i}."
            h = _rms_norm(x, w(p + "input_layernorm.weight"), eps)
            q = _rms_norm(h @ w(p + "self_attn.q_proj.weight"),
                          w(p + "self_attn.q_norm.weight"), eps)
            k = _rms_norm(h @ w(p + "self_attn.k_proj.weight"),
                          w(p + "self_attn.k_norm.weight"), eps)
            v = h @ w(p + "self_attn.v_proj.weight")
            q = _rope(q.reshape(B, S, H, hd), theta)
            k = _rope(k.reshape(B, S, Hkv, hd), theta)
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v.reshape(B, S, Hkv, hd), H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, H * hd) @ w(p + "self_attn.o_proj.weight")
            h = _rms_norm(x, w(p + "post_attention_layernorm.weight"), eps)
            y, weight = _experts(
                h.reshape(B * S, -1), weights[p + "mlp.router_weight"],
                weights[p + "mlp.w_gate"], weights[p + "mlp.w_up"],
                weights[p + "mlp.w_down"], config["num_experts_per_tok"],
                config["norm_topk_prob"])
            x = x + y.reshape(x.shape)
            routing.append(weight.reshape(B, S, -1))
        x = _rms_norm(x, w("llama.norm.weight"), eps)
        return x @ w("lm_head.weight"), jnp.stack(routing)


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    return forward(weights, ids, config)[0]
