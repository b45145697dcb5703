"""Plain reference for SK Telecom's A.X-K1 (`model_type` "axk1", `config.json`
at huggingface.co/skt/A.X-K1): the DeepSeek-V3 family's block, written from
the configuration's keys and that family's published equations (the sandbox
has no network and the repo holds no modeling file of it; the configuration
file lists what that leaves `assumed`). One decoder block over one sequence,
`x [S, hidden]`, pre-norm residual, no biases, RMSNorm eps `rms_norm_eps`:

  Attention (multi-head latent attention, heads h = 1..H, EXPANDED form:
  keys and values are made for every position; the program's cached path
  runs the absorbed form, different algebra for the same sum):
    h    = RMSNorm(x)
    c_q  = RMSNorm(h W_qa)                            [S, q_lora_rank]
    [q_nope_h | q_r_h] = c_q W_qb                      128 + 64 a head
    [c_kv | k_r] = h W_kva;  c_kv = RMSNorm(c_kv)      [S, 512], [S, 64]
    [k_nope_h | v_h] = c_kv W_kvb                      128 + 128 a head
    q_r_h = RoPE(q_r_h);  k_r = RoPE(k_r)              one rotary key for all
    s_h  = scale (q_nope_h . k_nope_h + q_r_h . k_r), key j <= query p
    x    = x + concat_h(softmax(s_h) v_h) W_o
    scale = (128 + 64)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1
            (1 without `rope_scaling`)
  RoPE, rotate-half form over the 64 rotary columns, theta `rope_theta`,
  `inv_freq_i = theta^(-2i/64)`; with `rope_scaling` of type yarn (Hugging
  Face's `_compute_yarn_parameters`):
    low = floor(64 ln(L0 / (beta_fast 2 pi)) / (2 ln theta)), high = ceil(the
    same with beta_slow), clipped to [0, 63]; ramp_i = clip((i - low) /
    (high - low), 0, 1); inv_freq'_i = inv_freq_i / factor * ramp_i +
    inv_freq_i (1 - ramp_i); cos and sin times (0.1 mscale ln(factor) + 1)
    / (0.1 mscale_all_dim ln(factor) + 1)  (L0 = the original positions)

  FFN of layer l < `first_k_dense_replace`: a dense SwiGLU,
    x = x + (silu(h' W_g) * (h' W_u)) W_d,   h' = RMSNorm(x)
  of every other layer: routed experts and shared experts,
    s   = sigmoid(h' W_r) over the published experts (`scoring_func`;
          "softmax": softmax), in float32
    the experts in `n_group` equal groups; a group's score is the sum of
    its 2 largest s; the `topk_group` best groups are eligible
    S   = the `num_experts_per_tok` largest s among the eligible experts
    g_e = routed_scaling_factor * s_e / sum_{S} s     (`norm_topk_prob`)
    x   = x + sum_{e in S, e held here} g_e SwiGLU_e(h') + SwiGLU_shared(h')
  (`n_shared_experts` shared experts are one SwiGLU of that many times the
  expert width, weight 1; none where it is 0.)

then a final RMSNorm and an untied head.

A share of a deployment (`n_routed_experts` < `n_routed_experts_published`):
the router is as wide as published, the experts held are the first
`n_routed_experts`, and a chosen expert that is not held adds nothing (the
renormalisation is over the chosen ones, held or not). `vocab_size` is the
slice's: ids and logits are over it.

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no sorting of assignments, no grouped
matmul; the router's choice is a dense 0/1 mask from thresholds (sorts), not
indices. The order of the loops is chosen so that a cell's longest request
fits beside a live engine (about 1 GB at 8,320 positions), and is no other
formula: one sequence after the other through every layer; attention one
head at a time (its rows of W_o applied to its result and the heads'
parts summed), its queries in blocks of `QUERY_BLOCK` against all the
keys; the held experts one after the other, every position through each,
weighted by the position's gate (zero where it was not chosen); the dense
MLP in `MLP_BLOCK` columns. A matrix is upcast where it is used. Leaves are
named as `models/deepseek.py` names them (matrices [in, out], experts
stacked on a leading axis).

`reference/common.py` hands a jitted reference the configuration's scalars
only, so `rope_scaling`, where the dict it is given lacks the key, is read
again from the configuration file its `name` gives (`_whole`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .llama import F32, _rms_norm
from .mellum import _file

QUERY_BLOCK = 1024
MLP_BLOCK = 2048


def _whole(config: dict) -> dict:
    """`config` with the group a frozen copy has lost."""
    if "rope_scaling" in config:
        return config
    return {**config, "rope_scaling": _file(config["name"])["rope_scaling"]}


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(config: dict):
    """(inv_freq [rope / 2], the factor on cos and sin, the softmax
    scale) of the configuration."""
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    scale = (config["qk_nope_head_dim"] + dim) ** -0.5
    base = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    yarn = config.get("rope_scaling")
    if not yarn:
        return base, 1.0, scale
    if yarn["type"] != "yarn":
        raise NotImplementedError(f"rope_scaling {yarn['type']!r}")

    def correction(turns):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction(yarn["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    factor = yarn["factor"]
    return (base / factor * ramp + base * (1.0 - ramp),
            _mscale(factor, yarn["mscale"])
            / _mscale(factor, yarn["mscale_all_dim"]),
            scale * _mscale(factor, yarn["mscale_all_dim"]) ** 2)


def _rope(x, inv, factor):
    """x [S, ..., D] -> rotated by its position (axis 0)."""
    S, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]         # [S, D/2]
    shape = (S,) + (1,) * (x.ndim - 2) + (D,)
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * factor).reshape(shape)
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * factor).reshape(shape)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _head(q, k, v, scale):
    """One head: q, k [S, 192], v [S, 128] -> [S, 128], causal; the
    queries in blocks, each against all S keys."""
    S = q.shape[0]
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    qb = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, block, q.shape[1])
    starts = jnp.arange(qb.shape[0], dtype=jnp.int32) * block
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, :]

    def one(args):
        qs, start = args
        pos = start + jnp.arange(block, dtype=jnp.int32)[:, None]
        s = jnp.where(key_pos <= pos, (qs @ k.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    return jax.lax.map(one, (qb, starts)).reshape(-1, v.shape[1])[:S]


def _attention(h, leaf, config):
    """h [S, hidden] (normed) -> the attention's output [S, hidden]."""
    def w(name):
        return leaf("self_attn." + name).astype(F32)

    H, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    inv, factor, scale = rotary(config)
    c_q = _rms_norm(h @ w("q_a_proj.weight"), w("q_a_layernorm.weight"), eps)
    ckv = h @ w("kv_a_proj_with_mqa.weight")
    c_kv = _rms_norm(ckv[:, :rank], w("kv_a_layernorm.weight"), eps)
    k_r = _rope(ckv[:, rank:], inv, factor)                     # [S, dr]
    # a head's columns of the two up-projections, stored [in, H * width],
    # and its rows of the output projection, stored [H * dv, hidden]
    w_q = leaf("self_attn.q_b_proj.weight").reshape(-1, H, nope + dr)
    w_kv = leaf("self_attn.kv_b_proj.weight").reshape(rank, H, nope + dv)
    w_o = leaf("self_attn.o_proj.weight").reshape(H, dv, -1)

    def head(out, ws):
        wq, wkv, wo = (a.astype(F32) for a in ws)
        q, kv = c_q @ wq, c_kv @ wkv
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], inv, factor)],
                            -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)
        return out + _head(q, k, kv[:, nope:], scale) @ wo, None

    return jax.lax.scan(head, jnp.zeros_like(h),
                        (jnp.swapaxes(w_q, 0, 1), jnp.swapaxes(w_kv, 0, 1),
                         w_o))[0]


def gates(h, router, config):
    """h [S, hidden] -> the gate of every published expert [S, E]: g_e
    where e is among the position's chosen, 0 elsewhere."""
    E, top_k = router.shape[1], config["num_experts_per_tok"]
    logit = h @ router.astype(F32)
    s = jax.nn.sigmoid(logit) if config["scoring_func"] == "sigmoid" \
        else jax.nn.softmax(logit, -1)
    groups, best = config["n_group"], config["topk_group"]
    choice = s
    if groups > 1:
        grouped = s.reshape(-1, groups, E // groups)
        score = jnp.sum(jnp.sort(grouped, -1)[..., -2:], -1)   # [S, groups]
        bar = jnp.sort(score, -1)[:, groups - best, None]
        choice = jnp.where((score >= bar)[..., None], grouped,
                           -jnp.inf).reshape(-1, E)
    bar = jnp.sort(choice, -1)[:, E - top_k, None]
    g = jnp.where(choice >= bar, s, 0.0)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * config["routed_scaling_factor"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
        @ wd.astype(F32)


def _dense(h, wg, wu, wd):
    """A wide SwiGLU in `MLP_BLOCK` columns of its hidden width."""
    hidden, width = wg.shape
    block = min(MLP_BLOCK, width)
    if width % block:
        return _swiglu(h, wg, wu, wd)
    parts = (jnp.moveaxis(wg.reshape(hidden, -1, block), 1, 0),
             jnp.moveaxis(wu.reshape(hidden, -1, block), 1, 0),
             wd.reshape(-1, block, hidden))

    def one(out, part):
        return out + _swiglu(h, *part), None

    return jax.lax.scan(one, jnp.zeros_like(h), parts)[0]


def _moe(h, leaf, config):
    """The held experts' part of the layer's sum plus the shared expert."""
    g = gates(h, leaf("mlp.experts.router_weight"), config)
    w_gate = leaf("mlp.experts.w_gate")
    held = w_gate.shape[0]

    def one(out, expert):
        wg, wu, wd, ge = expert
        return out + ge[:, None] * _swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (w_gate, leaf("mlp.experts.w_up"), leaf("mlp.experts.w_down"),
         g.T[:held]))
    if config["n_shared_experts"]:
        out = out + _dense(h, *(leaf(f"mlp.shared_experts.{m}_proj.weight")
                                for m in ("gate", "up", "down")))
    return out


def _sequence(x, weights, config):
    """Every layer over one sequence, x [S, hidden]."""
    eps = config["rms_norm_eps"]
    for i in range(config["num_hidden_layers"]):
        def leaf(name, p=f"model.layers.{i}."):
            return weights[p + name]
        h = _rms_norm(x, leaf("input_layernorm.weight").astype(F32), eps)
        x = x + _attention(h, leaf, config)
        h = _rms_norm(x, leaf("post_attention_layernorm.weight").astype(F32),
                      eps)
        if i < config["first_k_dense_replace"]:
            x = x + _dense(h, *(leaf(f"mlp.{m}_proj.weight")
                                for m in ("gate", "up", "down")))
        else:
            x = x + _moe(h, leaf, config)
    return _rms_norm(x, weights["model.norm.weight"].astype(F32), eps)


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
