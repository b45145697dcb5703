"""Plain reference for Jamba (ai21labs/AI21-Jamba2-3B, `model_type` "jamba"),
from its `config.json` and Hugging Face's `modeling_jamba.py` (written from
memory, the sandbox has no network; the configuration file lists what that
leaves `assumed`). d = hidden_size, d_inner = mamba_expand * d,
N = mamba_d_state, R = mamba_dt_rank, K = mamba_d_conv, eps = rms_norm_eps;
RMSNorm_w(u) = w * u / sqrt(mean(u^2) + eps).

    layer l is "attention" iff l mod attn_layer_period == attn_layer_offset
    x = E[ids]
    for each layer l:
        x = x + Mixer_l(RMSNorm(x))                      (input_layernorm)
        x = x + down(silu(gate(h)) * up(h)), h = RMSNorm(x)
    logits = RMSNorm(x) @ E^T                            (tied head)

Mamba-1 mixer, position t of one sequence, state h [d_inner, N] from zero:
    [u_t | z_t] = in_proj(x_t)
    c_t   = silu(conv_b + sum_j conv_w[:, j] * u_{t-(K-1)+j})   (causal,
                                                       depthwise, u only)
    [r_t | B_t | C_t] = x_proj(c_t)
    r_t, B_t, C_t = RMSNorm(r_t), RMSNorm(B_t), RMSNorm(C_t)
    dt_t  = softplus(dt_proj(r_t))                     (with bias, no clamp)
    A     = -exp(A_log)                                [d_inner, N]
    h_t   = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t
    y_t   = h_t @ C_t + D * c_t
    out_t = out_proj(y_t * silu(z_t))                  (no norm after)
Attention: q, k, v, o without bias, `num_attention_heads` query heads on
`num_key_value_heads` key/value heads, causal, no rotary embedding and no
position table, scores * head_dim^-0.5.

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no carried state, one `lax.scan` step a
position over `h [d_inner, N]`, one sequence after the other. Leaves are
read by the names `models/jamba.py` gives them (matrices [in, out],
`A_log` [d_inner, N]).

Fault switches, for `jobs/jamba_controls.py` alone (each takes one
mechanism out; the cell's configuration has none of the keys):
`mamba_carry` false: h_{t-1} is zero at every position (a state wiped
between steps); `mamba_inner_norms` false: r, B and C are used as x_proj
gives them; `mamba_state_dtype`: h is rounded to that type after every
position (a state held in less than float32 between steps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_types(config: dict) -> list:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(config["num_hidden_layers"])]


def _mamba1(x, w, config):
    """x [S, hidden] float32; `w(name)` the mixer's leaf as float32."""
    S = x.shape[0]
    inner = config["mamba_expand"] * config["hidden_size"]
    N, R = config["mamba_d_state"], config["mamba_dt_rank"]
    K, eps = config["mamba_d_conv"], config["rms_norm_eps"]
    proj = x @ w("in_proj.weight")
    u, z = proj[:, :inner], proj[:, inner:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    taps = w("conv_weight")                               # [d_inner, K]
    c = jax.nn.silu(w("conv_bias") + sum(
        taps[:, j] * padded[j:j + S] for j in range(K)))
    rbc = c @ w("x_proj.weight")
    r, b, cc = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    if config.get("mamba_inner_norms", True):
        r = _rms_norm(r, w("dt_layernorm"), eps)
        b = _rms_norm(b, w("b_layernorm"), eps)
        cc = _rms_norm(cc, w("c_layernorm"), eps)
    dt = jax.nn.softplus(r @ w("dt_proj.weight") + w("dt_proj.bias"))
    a = -jnp.exp(w("A_log"))                              # [d_inner, N]
    carry = config.get("mamba_carry", True)
    held = config.get("mamba_state_dtype", "float32")

    def step(h, t):
        c_t, dt_t, b_t, c_out = t
        if not carry:
            h = jnp.zeros_like(h)
        h = jnp.exp(dt_t[:, None] * a) * h \
            + (dt_t * c_t)[:, None] * b_t[None, :]
        return h.astype(held).astype(F32), h @ c_out

    _, y = jax.lax.scan(step, jnp.zeros((inner, N), F32), (c, dt, b, cc))
    y = y + w("D") * c
    return (y * jax.nn.silu(z)) @ w("out_proj.weight")


def _attention(x, w, config):
    S = x.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = (x @ w("q_proj.weight")).reshape(S, H, hd)
    k = (x @ w("k_proj.weight")).reshape(S, Hkv, hd)
    v = (x @ w("v_proj.weight")).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return a.reshape(S, H * hd) @ w("o_proj.weight")


def _layer(x, leaf, config, kind):
    """One sequence [S, hidden] through one block."""
    eps = config["rms_norm_eps"]

    def under(prefix):
        return lambda name: leaf(prefix + name).astype(F32)

    w = under("")
    h = _rms_norm(x, w("input_layernorm.weight"), eps)
    if kind == "mamba":
        x = x + _mamba1(h, under("mamba."), config)
    else:
        x = x + _attention(h, under("self_attn."), config)
    h = _rms_norm(x, w("post_attention_layernorm.weight"), eps)
    g = jax.nn.silu(h @ w("feed_forward.gate_proj.weight")) \
        * (h @ w("feed_forward.up_proj.weight"))
    return x + g @ w("feed_forward.down_proj.weight")


def hidden_and_head(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (the final norm's output [B, S, hidden] float32,
    the head's matrix [hidden, V] float32): what `logits` multiplies, for a
    caller that cannot hold [B, S, V] and applies the head in blocks. Layer
    by layer, each over one sequence after the other: one layer's matrices
    are alive in float32 at a time."""
    kinds = layer_types(config)
    with jax.default_matmul_precision("highest"):
        embed = weights["model.embed_tokens.weight"].astype(F32)
        x = embed[ids]
        for i, kind in enumerate(kinds):
            def leaf(name, p=f"model.layers.{i}."):
                return weights[p + name]
            x = jax.lax.map(
                lambda row, leaf=leaf, kind=kind: _layer(
                    row, leaf, config, kind), x)
        x = _rms_norm(x, weights["model.norm.weight"].astype(F32),
                      config["rms_norm_eps"])
        return x, embed.T


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x, head = hidden_and_head(weights, ids, config)
        return x @ head
