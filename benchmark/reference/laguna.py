"""Plain reference for Laguna-XS.2 (poolside/Laguna-XS.2, `model_type`
"laguna"), from its `config.json` alone (the sandbox has no network and the
repo holds no modeling file of it; the configuration file lists what that
leaves `assumed`, each reading with its alternative). Pre-norm decoder,
RMSNorm, no bias, untied head. Layer `l` has type `t_l` (`layer_types`),
`H_l` query heads (`num_attention_heads_per_layer`: 48 where full, 64 where
sliding), `Hkv` = 8 KV heads, head width D = 128. One block, `x [S, hidden]`:

    u = RMSNorm(x)
    q = u Wq_l [S, H_l, D],  k = u Wk [S, Hkv, D],  v = u Wv [S, Hkv, D]
    q, k = rope_t(q), rope_t(k)       on the first r_t dimensions of a head,
                                      the rest passed through; below
    s = q k^T / sqrt(D), query head i with KV head i // (H_l / Hkv)
    mask: key j visible to query p iff j <= p, and in a
          "sliding_attention" layer also j > p - sliding_window
          (a query sees `sliding_window` keys, itself included)
    a = softmax(s) v                          [S, H_l, D]
    g = sigmoid(u Wg_l)                       [S, H_l]   (`gating`: a gate
                                      a head, from the layer's normed input)
    x = x + (a * g[..., None]) Wo_l
    u' = RMSNorm(x)
    layer of `mlp_layer_types` "dense":
        x = x + (silu(u' Wg) * (u' Wu)) Wd              width intermediate_size
    "sparse":
        p = sigmoid(u' Wr) in float32 over all `num_experts` experts
        S = the `num_experts_per_tok` largest p
        w_e = p_e / sum_{S} p * moe_routed_scaling_factor      for e in S
        x = x + sum_{e in S} w_e (silu(u' Wg_e) * (u' Wu_e)) Wd_e
              + (silu(u' Wg_s) * (u' Wu_s)) Wd_s   the shared expert, weight
                                      1, width shared_expert_intermediate_size

then a final RMSNorm and the head. The router's weight is on an expert's
output (`moe_apply_router_weight_on_input` false).

Rotary embedding, rotate-half form over the first r = D x
`partial_rotary_factor` dimensions of a head (pairs (i, i + r/2)), by layer
type (`rope_parameters`), `inv_freq_i = theta^(-2i/r)`, i < r/2:
  "default"  cos(p inv_freq), sin(p inv_freq)
  "yarn"     over dimension r (64 on the full layers, NOT the head's 128):
             low  = floor(r ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
             high = ceil (r ln(L0 / (beta_slow 2 pi)) / (2 ln theta)),
             clipped to [0, r - 1]; ramp_i = clip((i - low) / (high - low),
             0, 1); inv_freq'_i = inv_freq_i / factor * ramp_i
             + inv_freq_i * (1 - ramp_i); cos and sin times
             `attention_factor` (L0 = original_max_position_embeddings)

Departures from the published description: none known; the three readings
the config leaves open (the gate's form, the router's scoring, no q/k norm)
are the configuration file's `assumed`, and each is a key this file reads,
so that a control can compute the other reading: `gating` (false: no gate),
`router_scoring` ("softmax"), `moe_routed_scaling_factor`,
`shared_expert_intermediate_size` (0: none), `router_dtype` ("bfloat16":
the router's logits from bf16 operands, what a program that did not upcast
would compute), `sliding_window` (None: every layer full), `gqa_group`
({layer type: query heads a KV head}: a program that took the other layer
type's group would pair query head i with KV head i // that).

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no ring, no sorting, no grouped matmul
(every expert is computed for every position and weighted by the position's
gate for it, zero where it was not chosen: 256 experts a layer by a scan).
Layer by layer, a layer over one sequence at a time and its queries in
blocks of `QUERY_BLOCK` against all the keys; that is the order of the
loops, not another formula. Leaves are named as `models/llama.py` names
them (matrices [in, out], experts stacked on a leading axis).

`reference/common.py` hands a jitted reference the configuration's scalars
only, so the lists and groups, where the dict it is given lacks them, are
read again from the configuration file its `name` gives (`_whole`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama import F32, _rms_norm
from .mellum import SLIDING, _attention, _file, inv_freq

LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
         "rope_parameters")


def _whole(config: dict) -> dict:
    """`config` with the lists and groups a frozen copy has lost."""
    lost = [k for k in LISTS if k not in config]
    if not lost:
        return config
    stored = _file(config["name"])
    return {**config, **{k: stored[k] for k in lost}}


def _rope(x, rope: dict, rotary: int):
    """x [S, heads, D] -> its first `rotary` dimensions rotated by
    position, the rest as they are."""
    S = x.shape[0]
    inv, factor = inv_freq(rotary, rope)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]         # [S, r/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    turn, keep = x[..., :rotary], x[..., rotary:]
    x1, x2 = turn[..., :rotary // 2], turn[..., rotary // 2:]
    turned = turn * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, keep], -1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _gates(h, router, config):
    """h [S, hidden] -> [S, experts]: each position's weight on each
    expert, zero where it was not chosen."""
    if config.get("router_dtype", "float32") != "float32":
        low = config["router_dtype"]
        logits = (h.astype(low) @ router.astype(low)).astype(F32)
    else:
        logits = h @ router.astype(F32)
    scoring = config.get("router_scoring", "sigmoid")
    p = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(p, config["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True) \
        * config.get("moe_routed_scaling_factor", 1.0)
    return jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)


def _sparse(h, leaf, config):
    """The routed experts' weighted sum plus the shared expert."""
    gate = _gates(h, leaf("mlp.experts.router_weight"), config)

    def one(out, expert):
        wg, wu, wd, g = expert
        return out + g[:, None] * _swiglu(h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (leaf("mlp.experts.w_gate"), leaf("mlp.experts.w_up"),
         leaf("mlp.experts.w_down"), gate.T))
    if config.get("shared_expert_intermediate_size", 0):
        out = out + _swiglu(h, leaf("mlp.shared_experts.gate_proj.weight"),
                            leaf("mlp.shared_experts.up_proj.weight"),
                            leaf("mlp.shared_experts.down_proj.weight"))
    return out


def _layer(x, leaf, config, i):
    """Block `i` over one sequence, x [S, hidden]; `leaf(name)` the
    layer's leaf as stored (upcast where it is used)."""
    def w(name):
        return leaf(name).astype(F32)

    kind = config["layer_types"][i]
    rope = config["rope_parameters"][kind]
    H = config["num_attention_heads_per_layer"][i]
    Hkv, hd = config["num_key_value_heads"], config["head_dim"]
    eps, S = config["rms_norm_eps"], x.shape[0]
    rotary = int(hd * rope.get("partial_rotary_factor", 1.0))
    u = _rms_norm(x, w("input_layernorm.weight"), eps)
    q = (u @ w("self_attn.q_proj.weight")).reshape(S, H, hd)
    k = (u @ w("self_attn.k_proj.weight")).reshape(S, Hkv, hd)
    v = (u @ w("self_attn.v_proj.weight")).reshape(S, Hkv, hd)
    k = _rope(k, rope, rotary)
    group = config.get("gqa_group", {}).get(kind)
    if group is not None:            # a fault switch: another group size
        kv_of = jnp.minimum(jnp.arange(H) // group, Hkv - 1)
        k, v = k[:, kv_of], v[:, kv_of]
    a = _attention(_rope(q, rope, rotary), k, v,
                   config.get("sliding_window") if kind == SLIDING else None)
    if config.get("gating", True):
        g = jax.nn.sigmoid(u @ w("self_attn.g_proj.weight"))      # [S, H]
        a = (a.reshape(S, H, hd) * g[..., None]).reshape(S, H * hd)
    x = x + a @ w("self_attn.o_proj.weight")
    u = _rms_norm(x, w("post_attention_layernorm.weight"), eps)
    if config["mlp_layer_types"][i] == "dense":
        return x + _swiglu(u, leaf("mlp.gate_proj.weight"),
                           leaf("mlp.up_proj.weight"),
                           leaf("mlp.down_proj.weight"))
    return x + _sparse(u, leaf, config)


def hidden_and_head(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (the final norm's output [B, S, hidden] float32,
    the head's matrix [hidden, V] float32): what `logits` multiplies, for a
    caller that cannot hold [B, S, V] and applies the head in blocks.
    Layer by layer, each over one sequence after the other."""
    config = _whole(config)
    with jax.default_matmul_precision("highest"):
        x = weights["llama.embed_tokens.weight"][ids].astype(F32)
        for i in range(config["num_hidden_layers"]):
            def leaf(name, p=f"llama.layers.{i}."):
                return weights[p + name]
            x = jax.lax.map(
                lambda row, leaf=leaf, i=i: _layer(row, leaf, config, i), x)
        x = _rms_norm(x, weights["llama.norm.weight"].astype(F32),
                      config["rms_norm_eps"])
        return x, weights["lm_head.weight"].astype(F32)


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x, head = hidden_and_head(weights, ids, config)
        return x @ head
