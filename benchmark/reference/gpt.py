"""Plain reference for the GPT-3 decoder (Brown et al. 2020, which follows
GPT-2): learned absolute positions, pre-LayerNorm blocks, one fused QKV
projection, causal softmax attention, a GELU MLP, a final LayerNorm and an
untied output head.

Straightforward `jax.numpy`, float32 arithmetic at
`default_matmul_precision("highest")`, on whatever weights it is handed
(the program's bf16-rounded seeded weights, upcast where they are used, so
no float32 copy of the model is resident). No kernels, no cache, no
batching tricks. Departures from the paper, to match what the program
computes: GELU in its tanh form (GPT-2's; the program calls
`jax.nn.gelu`, whose default is the tanh form); the fused QKV weight is laid
out head by head as [q | k | v] per head, as `models/gpt.py` splits it.
Weights are named as `models/gpt.py` names them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    def w(name):
        return weights[name].astype(F32)

    L, H = config["num_hidden_layers"], config["num_attention_heads"]
    eps = config["layer_norm_eps"]
    B, S = ids.shape
    with jax.default_matmul_precision("highest"):
        x = w("gpt.word_embeddings.weight")[ids] \
            + w("gpt.position_embeddings.weight")[jnp.arange(S)][None]
        hd = x.shape[-1] // H
        causal = jnp.tril(jnp.ones((S, S), bool))
        for i in range(L):
            p = f"gpt.layers.{i}."
            h = _layer_norm(x, w(p + "norm1.weight"), w(p + "norm1.bias"),
                            eps)
            qkv = h @ w(p + "self_attn.qkv_proj.weight") \
                + w(p + "self_attn.qkv_proj.bias")
            q, k, v = jnp.split(qkv.reshape(B, S, H, 3 * hd), 3, axis=-1)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(B, S, H * hd) \
                @ w(p + "self_attn.out_proj.weight") \
                + w(p + "self_attn.out_proj.bias")
            h = _layer_norm(x, w(p + "norm2.weight"), w(p + "norm2.bias"),
                            eps)
            h = _gelu_tanh(h @ w(p + "linear1.weight")
                           + w(p + "linear1.bias"))
            x = x + h @ w(p + "linear2.weight") + w(p + "linear2.bias")
        x = _layer_norm(x, w("gpt.final_norm.weight"),
                        w("gpt.final_norm.bias"), eps)
        return x @ w("lm_head.weight")
