"""Plain reference for Solar Open 2 (upstage/Solar-Open2-250B, `model_type`
"solar_open2"), from its `config.json` and, for the linear-attention
layers it names (`linear_attn_config`, `kda_use_full_proj`,
`kda_allow_neg_eigval`: Kimi Delta Attention), from the Kimi Linear paper
(arXiv:2510.26692) and flash-linear-attention's KDA layer, written from
memory: the sandbox has no network and the repo holds no modeling file of
it; the configuration file lists what that leaves `assumed`, each reading
with its alternative. Pre-norm decoder, RMSNorm_eps(u) = w * u /
sqrt(mean(u^2) + eps), no position embedding of any kind (`use_rope`
false), untied head. H = 64 heads of d = 128 in both kinds of layer; one
block, `x [S, hidden]`, u = RMSNorm(x):

KDA layer (layer l not in `gqa_layers`; three of every four):
    [q~ | k~ | v] = silu(conv4(u W_qkv))     depthwise, causal, kernel 4
                                             over the 3 H d channels, no
                                             bias, zeros before position 0
    q = q~ / sqrt(|q~|^2 + 1e-6) * d^-1/2    per head
    k = k~ / sqrt(|k~|^2 + 1e-6)
    [f | z | b] = u W_fgb                    widths d, d, H
    g    = -exp(A_log[h]) * softplus(f W_f + dt_bias)    [H, d], <= 0
    beta = 2 sigmoid(b)                      [H]; the 2 is
                                             `kda_allow_neg_eigval`
    per head, position after position, S [d_k, d_v] from zero:
        S <- Diag(exp(g)) S
        S <- S + beta k (v - S^T k)^T
        o  = S^T q
    x = x + [RMSNorm_d(o) * sigmoid(z W_g + b_g)] W_o    a norm a head with
                                             one weight [d]
GQA layer (l in `gqa_layers`: 0, 4, 8, ...):
    q = u Wq [S, H, d], k = u Wk [S, Hkv, d], v = u Wv [S, Hkv, d]
    a = softmax(q k^T / sqrt(d)) v           causal, query head i with KV
                                             head i // (H / Hkv)
    x = x + (a * sigmoid(u Wgate)) Wo        `use_gqa_gate`: a gate a
                                             channel, Wgate [hidden, H d]
FFN of every layer, u' = RMSNorm(x):
    p = softmax(u' Wr) in float32 over all the published experts
    T = the `num_experts_per_tok` largest p
    w_e = p_e / sum_T p * routed_scaling_factor          for e in T
    x = x + sum_{e in T, e held} w_e (silu(u' Wg_e) * (u' Wu_e)) Wd_e
          + (silu(u' Wg_s) * (u' Wu_s)) Wd_s             the shared expert,
                                             weight 1
then a final RMSNorm and the head. `n_routed_experts` is the experts held
here (the first ones: one chip's share under expert parallelism) and the
router is `n_routed_experts_published` wide; what the absent experts would
add is left out, as in the program.

Departures from the published description: none known. The program holds
W_q, W_k, W_v as one matrix `qkv_proj`, the decay's and the gate's
down-projections and beta's as one `fgb_proj`; that is storage, the columns
are the same. Every reading the config leaves open is a key this file
reads, so that a control can compute the other: `kda_allow_neg_eigval`
(false: beta = sigmoid(b)), `kda_delta` (false: S <- S + beta k v^T, no
read before the write), `kda_decay_per_head` (true: a head's channels all
take its first channel's decay), `kda_qk_l2norm` (false: q~ d^-1/2 and k~
as they are), `kda_conv` (false: silu of the projections, no conv),
`kda_state_dtype` (S rounded to that type after every position),
`use_gqa_gate` (false: no gate), `gqa_gate` ("headwise": Wgate [hidden,
H]), `n_shared_experts` (0: none).

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no carried state, no chunks (one
`lax.scan` step a position over `S [H, d, d]`), no sorting, no grouped
matmul (every held expert is computed for every position and weighted by
the position's gate for it, zero where it was not chosen: a scan over the
experts, one expert's matrices in float32 at a time). Layer by layer, each
over one sequence after the other, an attention layer's queries in blocks
of `QUERY_BLOCK` against all the keys. Leaves are named as
`models/solar_open2.py` names them (matrices [in, out], experts stacked on
a leading axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
L2_EPS = 1e-6
QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_types(config: dict) -> list:
    """"attention" where `gqa_layers` names the layer (a copy of the
    configuration that has lost its lists: every `gqa_interval + 1`th from
    0, which is the published list), else "kda"."""
    n = config["num_hidden_layers"]
    gqa = config.get("gqa_layers")
    if gqa is None:
        gqa = range(0, n, config["gqa_interval"] + 1)
    return ["attention" if i in set(gqa) else "kda" for i in range(n)]


def _linear(config: dict) -> tuple:
    """(heads, head width, conv kernel) of the KDA layers."""
    lin = config.get("linear_attn_config")
    if lin is None:                      # a frozen copy: the scalars
        return (config["linear_num_heads"], config["linear_head_dim"],
                config["short_conv_kernel_size"])
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def _kda(u, w, config):
    """u [S, hidden] float32 (normed); `w(name)` the mixer's leaf as
    float32."""
    S = u.shape[0]
    H, d, K = _linear(config)
    inner, eps = H * d, config["rms_norm_eps"]
    proj = u @ w("qkv_proj.weight")
    if config.get("kda_conv", True):
        padded = jnp.pad(proj, ((K - 1, 0), (0, 0)))
        taps = w("conv_weight")                            # [3 H d, K]
        proj = sum(taps[:, j] * padded[j:j + S] for j in range(K))
    c = jax.nn.silu(proj)
    q = c[:, :inner].reshape(S, H, d)
    k = c[:, inner:2 * inner].reshape(S, H, d)
    v = c[:, 2 * inner:].reshape(S, H, d)
    if config.get("kda_qk_l2norm", True):
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * d ** -0.5
    low = u @ w("fgb_proj.weight")
    f = low[:, :d] @ w("f_up_proj.weight") + w("dt_bias")
    g = -jnp.exp(w("A_log"))[None, :, None] \
        * jax.nn.softplus(f).reshape(S, H, d)
    if config.get("kda_decay_per_head", False):
        g = jnp.broadcast_to(g[..., :1], g.shape)
    beta = (2.0 if config.get("kda_allow_neg_eigval", True) else 1.0) \
        * jax.nn.sigmoid(low[:, 2 * d:])                   # [S, H]
    delta_rule = config.get("kda_delta", True)
    held = config.get("kda_state_dtype", "float32")

    def step(s, t):                                        # s [H, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = t
        s = s * jnp.exp(g_t)[:, :, None]
        if delta_rule:
            v_t = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * v_t)
        return s.astype(held).astype(F32), jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32), (q, k, v, g, beta))
    o = _rms_norm(o, w("o_norm_weight"), eps)
    z = low[:, d:2 * d] @ w("g_up_proj.weight") + w("g_up_proj.bias")
    return (o.reshape(S, inner) * jax.nn.sigmoid(z)) @ w("o_proj.weight")


def _attention(u, w, config):
    S = u.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = (u @ w("q_proj.weight")).reshape(S, H, hd)
    k = (u @ w("k_proj.weight")).reshape(S, Hkv, hd)
    v = (u @ w("v_proj.weight")).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    # the queries in blocks against all the keys: the order of the loops,
    # not another formula ([H, 512, S] float32 scores at a time)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, H, hd)
    starts = jnp.arange(qb.shape[0], dtype=jnp.int32) * block
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, :]

    def one(args):
        qs, start = args
        pos = start + jnp.arange(block, dtype=jnp.int32)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qs, k) / jnp.sqrt(F32(hd))
        s = jnp.where((key_pos <= pos)[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(one, (qb, starts)).reshape(-1, H, hd)[:S]
    if config.get("use_gqa_gate", True):
        gate = jax.nn.sigmoid(u @ w("g_proj.weight"))
        if config.get("gqa_gate", "elementwise") == "headwise":
            gate = gate[..., None]                         # [S, H, 1]
        else:
            gate = gate.reshape(S, H, hd)
        a = a * gate
    return a.reshape(S, H * hd) @ w("o_proj.weight")


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _ffn(h, leaf, config):
    """The held experts' weighted sum plus the shared expert."""
    p = jax.nn.softmax(h @ leaf("experts.router_weight").astype(F32), -1)
    top, idx = jax.lax.top_k(p, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * config.get("routed_scaling_factor", 1.0)
    gate = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    held = leaf("experts.w_gate").shape[0]     # the first `held` experts

    def one(out, expert):
        wg, wu, wd, g = expert
        return out + g[:, None] * _swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (leaf("experts.w_gate"), leaf("experts.w_up"),
         leaf("experts.w_down"), gate.T[:held]))
    if config.get("n_shared_experts", 1):
        out = out + _swiglu(h, leaf("shared_experts.gate_proj.weight"),
                            leaf("shared_experts.up_proj.weight"),
                            leaf("shared_experts.down_proj.weight"))
    return out


def _layer(x, leaf, config, kind):
    """One sequence [S, hidden] through one block; `leaf(name)` the
    layer's leaf as stored (upcast where it is used)."""
    eps = config["rms_norm_eps"]

    def under(prefix):
        return lambda name: leaf(prefix + name).astype(F32)

    w = under("")
    u = _rms_norm(x, w("input_layernorm.weight"), eps)
    if kind == "kda":
        x = x + _kda(u, under("kda."), config)
    else:
        x = x + _attention(u, under("self_attn."), config)
    u = _rms_norm(x, w("post_attention_layernorm.weight"), eps)
    return x + _ffn(u, leaf, config)


def hidden_and_head(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (the final norm's output [B, S, hidden] float32,
    the head's matrix [hidden, V] float32): what `logits` multiplies, for a
    caller that cannot hold [B, S, V] and applies the head in blocks.
    Layer by layer, each over one sequence after the other."""
    kinds = layer_types(config)
    with jax.default_matmul_precision("highest"):
        x = weights["model.embed_tokens.weight"][ids].astype(F32)
        for i, kind in enumerate(kinds):
            def leaf(name, p=f"model.layers.{i}."):
                return weights[p + name]
            x = jax.lax.map(
                lambda row, leaf=leaf, kind=kind: _layer(
                    row, leaf, config, kind), x)
        x = _rms_norm(x, weights["model.norm.weight"].astype(F32),
                      config["rms_norm_eps"])
        return x, weights["lm_head.weight"].astype(F32)


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x, head = hidden_and_head(weights, ids, config)
        return x @ head
