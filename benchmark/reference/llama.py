"""Plain reference for Llama-architecture decoders, as Mistral-7B-v0.3's
`config.json` and the Hugging Face `MistralForCausalLM` describe them:
RMSNorm, rotary positions in the rotate-half form with angles
`pos / theta**(2i/d)`, grouped-query causal softmax attention (each KV head
serves `heads / kv_heads` query heads), a SwiGLU MLP
(`down(silu(gate(x)) * up(x))`), a final RMSNorm and an untied output head.
No sliding window (v0.3 has none).

Straightforward `jax.numpy`, float32 arithmetic at
`default_matmul_precision("highest")`, on whatever weights it is handed
(upcast where they are used). No kernels, no cache, no batching tricks:
the whole sequence is one full forward pass. Weights are named as
`models/llama.py` names them, matrices stored [in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, D] -> rotated by its position."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]       # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    def w(name):
        return weights[name].astype(F32)

    L = config["num_hidden_layers"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    B, S = ids.shape
    with jax.default_matmul_precision("highest"):
        x = w("llama.embed_tokens.weight")[ids]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for i in range(L):
            p = f"llama.layers.{i}."
            h = _rms_norm(x, w(p + "input_layernorm.weight"), eps)
            q = (h @ w(p + "self_attn.q_proj.weight")).reshape(B, S, H, hd)
            k = (h @ w(p + "self_attn.k_proj.weight")).reshape(B, S, Hkv, hd)
            v = (h @ w(p + "self_attn.v_proj.weight")).reshape(B, S, Hkv, hd)
            q, k = _rope(q, theta), _rope(k, theta)
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, H * hd) @ w(p + "self_attn.o_proj.weight")
            h = _rms_norm(x, w(p + "post_attention_layernorm.weight"), eps)
            g = jax.nn.silu(h @ w(p + "mlp.gate_proj.weight")) \
                * (h @ w(p + "mlp.up_proj.weight"))
            x = x + g @ w(p + "mlp.down_proj.weight")
        x = _rms_norm(x, w("llama.norm.weight"), eps)
        return x @ w("lm_head.weight")
