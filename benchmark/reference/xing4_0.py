"""Plain reference for XingChen-AGI's Xing4.0-29B-A4B (`model_type` "xing4_0",
`config.json` at huggingface.co/XingChen-AGI/Xing4.0-29B-A4B): the
DeepSeek-V3 family's block (multi-head latent attention; a sigmoid router
with a selection bias beside a shared expert; leading dense layers) inside a
residual path of n = `hc_mult` streams joined by manifold-constrained
hyper-connections, written from the configuration's keys (`hc_mult`,
`hc_sinkhorn_iters`, `hc_eps`, `mhc_h_res_clamp_min/max` and the family's key
set) and the published equations: "mHC: Manifold-Constrained
Hyper-Connections" (DeepSeek-AI, arXiv:2512.24880; n = 4 and 20 passes are
that paper's own settings and this configuration's) on "Hyper-Connections"
(arXiv:2409.19606) for the copy-in and the sum-out. The sandbox has no
network and the repo holds no modeling file of it; the configuration file
lists what that leaves `assumed`.

One sequence, a token's residual state `X [n, C]` (C = `hidden_size`),
float32 throughout. The embedding's row is copied into the n streams. For
each sublayer F of each layer (attention behind `input_layernorm`, the FFN
behind `post_attention_layernorm`; F is `reference/axk1.py`'s attention and
`reference/glm_moe_dsa.py`'s FFN, whose docstrings state them) one
connection with `phi [n C, n^2 + 2 n]`, `bias [n^2 + 2 n]`, `alpha [3]`:

    x'  = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)          [n C]
    [h_pre | h_post | h_res] = x' phi, split n | n | n^2
    H_pre  = sigmoid(alpha_0 h_pre + bias_pre)                  [n]
    H_post = 2 sigmoid(alpha_1 h_post + bias_post)              [n]
    M^0    = exp(clip(alpha_2 mat(h_res) + bias_res, min, max)) [n, n]
    M^t    = T_r(T_c(M^(t-1))), t = 1..hc_sinkhorn_iters;  T_c: each column
             over (its sum + hc_eps), T_r: each row likewise
    H_res  = M^(hc_sinkhorn_iters)
    u      = sum_j H_pre[j] X[j]                   the sublayer's input, [C]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(RMSNorm(u))

`vec` lays the streams side by side (stream j the entries j C .. (j + 1) C),
`mat` is row-major. The final RMSNorm reads the streams' sum; then an untied
head. The router chooses by `s + select_bias` and weighs by `s` ("noaux_tc");
one group, so no group limit.

Departures from the published model, each also under `assumed` in the
configuration file: that reading of the `hc_*` / `mhc_*` keys; `hc_eps` in
Sinkhorn's denominators; the clamp before `exp`; copy-in and sum-out; no
learned scale on the flattened norm (absorbed in `phi`); rotate-half RoPE;
the multi-token-prediction module is not built.

Straightforward `jax.numpy` at `default_matmul_precision("highest")`: no
kernel, no cache, no batching; a matrix is upcast where it is used, the
experts one after the other, so that a request fits beside a live engine.
Leaves are named as `models/deepseek.py` names them (`attn_hc.phi`,
`mlp_hc.alpha`, ...).

Four keys the program knows nothing of switch a mechanism off in the
reference alone, for the cell's controls (`jobs/xing4_0_controls.py`), each
read with the sound value as its default: `hc_sinkhorn_iters` set to 1;
`hc_dynamic` false (alpha = 0: the mixing no longer depends on the input);
`hc_post_gain` 1 in place of 2; `hc_mixing` false (one stream in effect:
H_res the identity, H_pre uniform at 1 / n).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .axk1 import _attention, _dense, _whole
from .glm_moe_dsa import _moe
from .llama import F32, _rms_norm


def coefficients(X, phi, bias, alpha, config: dict):
    """X `[S, n, C]` -> (H_pre `[S, n]`, H_post `[S, n]`, H_res
    `[S, n, n]`)."""
    S, n, _ = X.shape
    x = X.reshape(S, -1)
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                     + config["rms_norm_eps"])
    alpha = alpha.astype(F32) * (1.0 if config.get("hc_dynamic", True)
                                 else 0.0)
    h = x @ phi.astype(F32)
    bias = bias.astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + bias[:n])
    post = config.get("hc_post_gain", 2.0) * jax.nn.sigmoid(
        alpha[1] * h[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(
        alpha[2] * h[:, 2 * n:] + bias[2 * n:],
        config["mhc_h_res_clamp_min"],
        config["mhc_h_res_clamp_max"])).reshape(S, n, n)
    for _ in range(config["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, 1, keepdims=True) + config["hc_eps"])
        m = m / (jnp.sum(m, 2, keepdims=True) + config["hc_eps"])
    if not config.get("hc_mixing", True):
        pre = jnp.full_like(pre, 1.0 / n)
        m = jnp.broadcast_to(jnp.eye(n, dtype=F32), m.shape)
    return pre, post, m


def connect(X, F, leaf, name: str, config: dict):
    """One connection round the sublayer `F [S, C] -> [S, C]`."""
    pre, post, res = coefficients(X, leaf(name + ".phi"),
                                  leaf(name + ".bias"),
                                  leaf(name + ".alpha"), config)
    y = F(jnp.einsum("sj,sjc->sc", pre, X))
    return jnp.einsum("sij,sjc->sic", res, X) + post[:, :, None] * y[:, None]


def _sequence(x, weights, config):
    """Every layer over one sequence, x [S, hidden]."""
    eps = config["rms_norm_eps"]
    X = jnp.repeat(x[:, None], config["hc_mult"], 1)
    for i in range(config["num_hidden_layers"]):
        def leaf(name, p=f"model.layers.{i}."):
            return weights[p + name]

        def norm(name, u, leaf=leaf):
            return _rms_norm(u, leaf(name + ".weight").astype(F32), eps)

        def ffn(u, i=i, leaf=leaf, norm=norm):
            h = norm("post_attention_layernorm", u)
            if i < config["first_k_dense_replace"]:
                return _dense(h, *(leaf(f"mlp.{m}_proj.weight")
                                   for m in ("gate", "up", "down")))
            return _moe(h, leaf, config)

        X = connect(X, lambda u, leaf=leaf, norm=norm: _attention(
            norm("input_layernorm", u), leaf, config), leaf, "attn_hc",
            config)
        X = connect(X, ffn, leaf, "mlp_hc", config)
    return _rms_norm(jnp.sum(X, 1), weights["model.norm.weight"].astype(F32),
                     eps)


def hidden_and_head(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (the final norm's output [B, S, hidden] float32,
    the head's matrix [hidden, V] float32): what `logits` multiplies, for a
    caller that cannot hold [B, S, V] and applies the head in blocks."""
    config = _whole(config)
    with jax.default_matmul_precision("highest"):
        embed = weights["model.embed_tokens.weight"]
        x = jax.lax.map(
            lambda row: _sequence(embed[row].astype(F32), weights, config),
            ids)
        return x, weights["lm_head.weight"].astype(F32)


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x, head = hidden_and_head(weights, ids, config)
        return x @ head
