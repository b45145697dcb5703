"""Plain reference for Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct,
`model_type` "mellum"), from its `config.json` alone (the sandbox has no
network and the repo holds no modeling file of it; the configuration file
lists what that leaves `assumed`). One decoder block, `x [S, hidden]`:

    h = RMSNorm(x);  q = h Wq [S, H, D], k = h Wk, v = h Wv [S, Hkv, D]
    q, k = rope_l(q), rope_l(k)              by the layer's type, below
    s = q k^T / sqrt(D), query head i with KV head i // (H / Hkv)
    mask: key j visible to query p iff j <= p, and in a
          "sliding_attention" layer also j > p - sliding_window
          (a query sees `sliding_window` keys, itself included)
    x = x + softmax(s) v Wo
    h' = RMSNorm(x);  p = softmax(h' Wr) over the published experts
    S = the `num_experts_per_tok` largest p, renormalised to sum 1 over S
        (`norm_topk_prob`)
    x = x + sum_{e in S, e held here} p_e (silu(h' Wg_e) * (h' Wu_e)) Wd_e

then a final RMSNorm and an untied head. `head_dim` is the file's (128:
q is 4,096 wide at hidden 2,304), no bias, no q/k norm.

Rotary embedding, rotate-half form, `inv_freq_i = theta^(-2i/D)`, by layer
type (`rope_parameters`):
  "default"  cos(p inv_freq), sin(p inv_freq)
  "yarn"     low  = floor(D ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
             high = ceil (D ln(L0 / (beta_slow 2 pi)) / (2 ln theta)),
             clipped to [0, D - 1]; ramp_i = clip((i - low) / (high - low),
             0, 1); inv_freq'_i = inv_freq_i / factor * ramp_i
             + inv_freq_i * (1 - ramp_i); cos and sin times
             `attention_factor` (L0 = original_max_position_embeddings)

A share of a deployment (`num_experts` < `num_experts_published`): the
router is as wide as published, the experts held are the first
`num_experts`, and a chosen expert that is not held adds nothing (the
renormalisation is over the chosen ones, held or not).

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no ring, no sorting, no grouped matmul
(every held expert is computed for every position and weighted by the
position's gate for it, zero where it was not chosen). Layer by layer, a
layer over one sequence at a time and its queries in blocks of
`QUERY_BLOCK` against all the keys, so that the scores of a cell's longest
request fit beside the program (`[H, 512, S]` float32: 0.55 GB at S =
8,320); that is the order of the loops, not another formula. Leaves are named as `models/llama.py`
names them (matrices [in, out], experts stacked on a leading axis).

`reference/common.py` hands a jitted reference the configuration's
scalars only, so `layer_types` and `rope_parameters`, where the dict it is
given lacks them, are read again from the configuration file its `name`
gives (`_whole`).
"""
from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp

from .llama import F32, _rms_norm

QUERY_BLOCK = 512
SLIDING = "sliding_attention"


@functools.lru_cache(maxsize=None)
def _file(name: str) -> dict:
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for root in (bench, os.path.join(bench, "tests", "cells")):
        path = os.path.join(root, "configs", f"{name}.json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
    raise KeyError(f"no configuration file for {name!r}")


def _whole(config: dict) -> dict:
    """`config` with the lists and groups a frozen copy has lost."""
    lost = [k for k in ("layer_types", "rope_parameters")
            if k not in config]
    if not lost:
        return config
    stored = _file(config["name"])
    return {**config, **{k: stored[k] for k in lost}}


def inv_freq(head_dim: int, rope: dict):
    """(inv_freq [D/2], the factor on cos and sin) of one layer type."""
    theta = float(rope["rope_theta"])
    base = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim)
    if rope.get("rope_type", "default") == "default":
        return base, 1.0

    def correction(turns):
        return head_dim * math.log(
            rope["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=F32) - low)
                    / (high - low), 0.0, 1.0)
    return (base / rope["factor"] * ramp + base * (1.0 - ramp),
            float(rope["attention_factor"]))


def _rope(x, rope: dict):
    """x [S, heads, D] -> rotated by its position."""
    S, D = x.shape[0], x.shape[-1]
    inv, factor = inv_freq(D, rope)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]         # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window):
    """q [S, H, D], k and v [S, Hkv, D] -> [S, H * D]: causal softmax
    attention, `window` keys back where it is not None; the queries in
    blocks, each against all S keys."""
    S, H, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = min(QUERY_BLOCK, S)
    pad = -S % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, H, D)
    starts = jnp.arange(qb.shape[0], dtype=jnp.int32) * block
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, :]

    def one(args):
        qs, start = args
        pos = start + jnp.arange(block, dtype=jnp.int32)[:, None]
        keep = key_pos <= pos
        if window is not None:
            keep &= key_pos > pos - window
        s = jnp.einsum("qhd,khd->hqk", qs, k) / jnp.sqrt(F32(D))
        s = jnp.where(keep[None], s, -jnp.inf)
        # (a row of padding past S may see no key and come out NaN: the
        # rows are independent and it is dropped below)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, (qb, starts))
    return out.reshape(-1, H * D)[:S]


def _experts(h, router, w_gate, w_up, w_down, top_k, renormalise):
    """h [S, hidden] -> the held experts' part of the layer's sum."""
    p = jax.nn.softmax(h @ router.astype(F32), axis=-1)   # [S, published]
    top, idx = jax.lax.top_k(p, top_k)
    if renormalise:
        top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    held = w_gate.shape[0]

    def one(out, expert):
        wg, wu, wd, g = expert
        y = (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
            @ wd.astype(F32)
        return out + g[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w_gate, w_up, w_down, gate.T[:held]))
    return out


def _layer(x, leaf, config, kind, rope):
    """One decoder block over one sequence, x [S, hidden]; `leaf(name)`
    the layer's leaf as stored (upcast where it is used)."""
    def w(name):
        return leaf(name).astype(F32)

    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    S = x.shape[0]
    h = _rms_norm(x, w("input_layernorm.weight"), eps)
    q = (h @ w("self_attn.q_proj.weight")).reshape(S, H, hd)
    k = (h @ w("self_attn.k_proj.weight")).reshape(S, Hkv, hd)
    v = (h @ w("self_attn.v_proj.weight")).reshape(S, Hkv, hd)
    a = _attention(_rope(q, rope), _rope(k, rope), v,
                   config.get("sliding_window") if kind == SLIDING else None)
    x = x + a @ w("self_attn.o_proj.weight")
    h = _rms_norm(x, w("post_attention_layernorm.weight"), eps)
    return x + _experts(h, leaf("mlp.router_weight"), leaf("mlp.w_gate"),
                        leaf("mlp.w_up"), leaf("mlp.w_down"),
                        config["num_experts_per_tok"],
                        config["norm_topk_prob"])


def hidden_and_head(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> (the final norm's output [B, S, hidden] float32,
    the head's matrix [hidden, V] float32): what `logits` multiplies, for a
    caller that cannot hold [B, S, V] and applies the head in blocks.
    Layer by layer, each over one sequence after the other: one layer's
    matrices are alive in float32 at a time."""
    config = _whole(config)
    kinds, ropes = config["layer_types"], config["rope_parameters"]
    with jax.default_matmul_precision("highest"):
        x = weights["llama.embed_tokens.weight"][ids].astype(F32)
        for i in range(config["num_hidden_layers"]):
            def leaf(name, p=f"llama.layers.{i}."):
                return weights[p + name]
            x = jax.lax.map(
                lambda row, leaf=leaf, i=i: _layer(
                    row, leaf, config, kinds[i], ropes[kinds[i]]), x)
        x = _rms_norm(x, weights["llama.norm.weight"].astype(F32),
                      config["rms_norm_eps"])
        return x, weights["lm_head.weight"].astype(F32)


def logits(weights: dict, ids, config: dict):
    """ids [B, S] int32 -> logits [B, S, V] float32."""
    with jax.default_matmul_precision("highest"):
        x, head = hidden_and_head(weights, ids, config)
        return x @ head
