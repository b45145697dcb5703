"""Plain reference for Granite 4.0-H (ibm-granite/granite-4.0-h-small,
`model_type` "granitemoehybrid"), from its `config.json` and Hugging Face's
`modeling_granitemoehybrid.py` / `modeling_bamba.py` (written from memory,
the sandbox has no network; the configuration file lists what that leaves
`assumed`).

    x = E[ids] * embedding_multiplier
    for each layer l:
        m = Mamba2(RMSNorm(x))  or  Attn(RMSNorm(x))      (its leaves say)
        x = x + residual_multiplier * m
        h = RMSNorm(x)
        x = x + residual_multiplier * (MoE(h) + Shared(h))
    logits = RMSNorm(x) @ E^T / logits_scaling            (tied head)

Attn: q, k, v, o without bias, grouped-query, causal, no rotary embedding,
scores x `attention_multiplier`.
MoE: g = h W_r (float32); S = the `num_experts_per_tok` largest g;
p = softmax(g[S]); sum_{e in S} p_e (silu(h Wg_e) * (h Wu_e)) Wd_e.
Shared: the same SwiGLU, every token, no gate.
Mamba2 (H heads of width P, one group, N state channels, conv width K):
    [z | u | dt] = h W_in
    u'_t = silu(b + sum_j w[:, j] u_{t-(K-1)+j})          (causal, depthwise)
    [x | B | C] = u'_t;  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t    [P, N]
    y_t[h] = S_t[h] C_t + D[h] x_t[h]
    out = RMSNorm(y * silu(z)) W_out      (gate first; norm over all H * P)

A share of a deployment (`num_local_experts` < `num_local_experts_published`):
the router is as wide as published and the experts held are the first
`num_local_experts`; a chosen expert that is not held adds nothing.

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no carried state, no chunked form of
the recurrence (one `lax.scan` step per position over `S [B, H, P, N]`),
no sorting. Leaves are read by the names `models/granitemoehybrid.py`
gives them (matrices [in, out], experts stacked on a leading axis); a
layer is a Mamba-2 layer if it has `mamba.in_proj.weight`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama import F32, _rms_norm


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
        @ wd.astype(F32)


def _experts(h, router, w_gate, w_up, w_down, top_k):
    """h [T, hidden] -> the routed experts' output [T, hidden]: every held
    expert computed for every position, weighted by the position's gate
    for it (zero where it was not chosen)."""
    g = h @ router.astype(F32)                            # [T, E published]
    top, idx = jax.lax.top_k(g, top_k)
    gate = jnp.zeros_like(g).at[
        jnp.arange(h.shape[0])[:, None], idx].set(jax.nn.softmax(top, -1))
    held = w_gate.shape[0]

    def one(out, expert):
        wg, wu, wd, p = expert
        return out + p[:, None] * _swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w_gate, w_up, w_down, gate.T[:held]))
    return out


def _mamba2(h, w, config):
    """h [B, S, hidden] float32; `w(name)` the layer's leaf as float32."""
    B, S, _ = h.shape
    H, P = config["mamba_n_heads"], config["mamba_d_head"]
    N, K = config["mamba_d_state"], config["mamba_d_conv"]
    inner, conv_dim = H * P, H * P + 2 * N
    proj = h @ w("in_proj.weight")
    z, u, dt = (proj[..., :inner], proj[..., inner:inner + conv_dim],
                proj[..., inner + conv_dim:])
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    kernel = w("conv_weight")                             # [conv_dim, K]
    u = jax.nn.silu(w("conv_bias") + sum(
        kernel[:, j] * padded[:, j:j + S] for j in range(K)))
    x = u[..., :inner].reshape(B, S, H, P)
    b, c = u[..., inner:inner + N], u[..., inner + N:]
    dt = jax.nn.softplus(dt + w("dt_bias"))               # [B, S, H]
    a = -jnp.exp(w("A_log"))

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, N), F32),
        tuple(jnp.swapaxes(t, 0, 1) for t in (x, b, c, dt)))
    y = jnp.swapaxes(y, 0, 1) + w("D")[:, None] * x       # [B, S, H, P]
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    y = _rms_norm(y, w("norm_weight"), config["rms_norm_eps"])
    return y @ w("out_proj.weight")


def _attention(h, w, config):
    B, S, _ = h.shape
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = (h @ w("q_proj.weight")).reshape(B, S, H, hd)
    k = (h @ w("k_proj.weight")).reshape(B, S, Hkv, hd)
    v = (h @ w("v_proj.weight")).reshape(B, S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
        * F32(config["attention_multiplier"])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, S, H * hd) @ w("o_proj.weight")


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    eps, rm = config["rms_norm_eps"], F32(config["residual_multiplier"])
    B, S = ids.shape
    with jax.default_matmul_precision("highest"):
        embed = weights["model.embed_tokens.weight"].astype(F32)
        x = embed[ids] * F32(config["embedding_multiplier"])
        for i in range(config["num_hidden_layers"]):
            p = f"model.layers.{i}."

            def leaf(name, p=p):
                return weights[p + name].astype(F32)

            def under(kind):
                return lambda name: leaf(kind + name)

            h = _rms_norm(x, leaf("input_layernorm.weight"), eps)
            if p + "mamba.in_proj.weight" in weights:
                m = _mamba2(h, under("mamba."), config)
            else:
                m = _attention(h, under("self_attn."), config)
            x = x + rm * m
            h = _rms_norm(x, leaf("post_attention_layernorm.weight"),
                          eps).reshape(B * S, -1)
            moe = p + "block_sparse_moe."
            y = _experts(h, weights[moe + "router_weight"],
                         weights[moe + "w_gate"], weights[moe + "w_up"],
                         weights[moe + "w_down"],
                         config["num_experts_per_tok"]) \
                + _swiglu(h, leaf("shared_mlp.gate_proj.weight"),
                          leaf("shared_mlp.up_proj.weight"),
                          leaf("shared_mlp.down_proj.weight"))
            x = x + rm * y.reshape(x.shape)
        x = _rms_norm(x, weights["model.norm.weight"].astype(F32), eps)
        return (x @ embed.T) / F32(config["logits_scaling"])
