"""Plain reference for Z.ai's GLM-5.2 (`model_type` "glm_moe_dsa",
`config.json` at huggingface.co/zai-org/GLM-5.2): the DeepSeek-V3 family's
block with DeepSeek-V3.2's learned sparse attention on top, written from
the configuration's keys and those two families' published equations (the
sandbox has no network and the repo holds no modeling file of it; the
configuration file lists what that leaves `assumed`). One decoder block over
one sequence, `x [S, hidden]`, pre-norm residual, no biases but the index
key's LayerNorm, RMSNorm eps `rms_norm_eps`:

  Attention (multi-head latent attention, heads h = 1..H, EXPANDED form:
  keys and values are made for every position; the program's cached path
  runs the absorbed form over a gathered or masked latent cache):
    h    = RMSNorm(x)
    c_q  = RMSNorm(h W_qa)                            [S, q_lora_rank]
    [q_nope_h | q_r_h] = c_q W_qb                      192 + 64 a head
    [c_kv | k_r] = h W_kva;  c_kv = RMSNorm(c_kv)      [S, 512], [S, 64]
    [k_nope_h | v_h] = c_kv W_kvb                      192 + 256 a head
    q_r_h = RoPE(q_r_h);  k_r = RoPE(k_r)              one rotary key for all
    s_h  = (192 + 64)^-0.5 (q_nope_h . k_nope_h + q_r_h . k_r)
    x    = x + concat_h(softmax over the keys j in S_p of s_h, times v_h) W_o
  RoPE, INTERLEAVED (`rope_interleave`): columns (2i, 2i + 1) are a pair
  turned by `pos * theta^(-2i/64)`, theta `rope_parameters.rope_theta`, no
  scaling.

  The selection S_p (`indexer_types[layer]`): a "full" layer has an indexer,
    q^I_j = RoPE((c_q W^I_q)[j])          j = 1..32, 128 wide
    k^I   = RoPE(LayerNorm(h W^I_k))      128 wide, weight and bias, eps 1e-6
    w     = h W^I_w * 32^-0.5 * 128^-0.5
    I[p, j'] = sum_j w[p, j] relu(q^I[p, j] . k^I[j'])        j' <= p
    S_p   = the `index_topk` keys j' <= p of largest I[p, .]   (all of them
            while p < index_topk), ties to the lower position
  with RoPE (interleaved, `indexer_rope_interleave`) on the FIRST 64 of the
  128 columns of q^I_j and k^I; a "shared" layer has no indexer and uses the
  S_p of the nearest "full" layer below it in depth (above it in the list).

  FFN of layer l < `first_k_dense_replace`: a dense SwiGLU; of every other
  layer routed experts and a shared expert,
    s   = sigmoid(h' W_r) over the published experts, float32
    S   = the `num_experts_per_tok` experts of largest s + b  (b: the
          selection bias `select_bias`, "noaux_tc"; one group: `n_group` 1)
    g_e = routed_scaling_factor * s_e / sum_{S} s             (s, not s + b)
    x   = x + sum_{e in S, e held here} g_e SwiGLU_e(h') + SwiGLU_shared(h')

then a final RMSNorm and an untied head. A share of a deployment as
`reference/axk1.py` describes it: the router as wide as published, the first
`n_routed_experts` experts held, a slice of the vocabulary.

Departures from the published model, each also under `assumed` in the
configuration file: no Hadamard rotation of q^I and k^I (an orthogonal map of
both leaves every q^I . k^I as it is; it exists for FP8 index keys); index
keys in the configuration's dtype, not FP8; the first 64 index columns
rotate; the index key's LayerNorm has a bias; ties to the lower position;
the multi-token-prediction layer is not built.

Straightforward `jax.numpy`, float32 at `default_matmul_precision
("highest")`: no kernels, no cache, no gather of selected keys. The order of
the loops is chosen so that a 32k-token request fits beside a live engine,
and is no other formula: one sequence after the other through every layer;
the selection as a 0/1 matrix `[S, S]` held one bit a pair (`_pack`: 32 keys
a word, 152 MB at 34,816 where a byte a pair is 1.2 GB), made `QUERY_BLOCK`
query rows at a time from the block's `[rows, S]` scores, which are never
whole; attention one head at a time, its queries in blocks against all the
keys under the selection's rows; the held experts one after the other.
Leaves are named as `models/deepseek.py` names them.

Four keys the program knows nothing of switch a mechanism off in the
reference alone, for the cell's controls (`jobs/glm_dsa_controls.py`), each
read with the sound value as its default: `index_share` (false: every layer
selects for itself, with the indexer weights of the nearest "full" layer),
`index_relu`, `index_head_weights` (false: w = 1), and an `index_topk`
larger than the sequence (every key).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .axk1 import _dense, _swiglu
from .llama import F32, _rms_norm
from .mellum import _file

QUERY_BLOCK = 1024
LISTS = ("indexer_types", "rope_parameters")


def _whole(config: dict) -> dict:
    """`config` with the lists and groups a frozen copy has lost."""
    lost = [k for k in LISTS if k not in config]
    if not lost:
        return config
    on_file = _file(config["name"])
    return {**config, **{k: on_file[k] for k in lost}}


def _rope(x, theta: float):
    """x [S, ..., D] -> columns (2i, 2i + 1) turned by the position (axis
    0) times theta^(-2i/D)."""
    S, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]         # [S, D/2]
    shape = (S,) + (1,) * (x.ndim - 2) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _blocks(S: int) -> tuple:
    block = min(QUERY_BLOCK, S)
    return block, -S % block


def _pack(keep):
    """[rows, S] bool -> [rows, ceil(S / 32)] uint32, key j at bit j % 32 of
    word j // 32."""
    rows, S = keep.shape
    words = jnp.pad(keep, ((0, 0), (0, -S % 32))).reshape(rows, -1, 32)
    return jnp.sum(words.astype(jnp.uint32)
                   << jnp.arange(32, dtype=jnp.uint32), -1, dtype=jnp.uint32)


def _unpack(words, S: int):
    """`_pack`'s inverse: [rows, S] bool."""
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(words.shape[0], -1)[:, :S] == 1


def selection(h, c_q, leaf, config):
    """h [S, hidden] (normed), c_q [S, q_lora_rank] -> the 0/1 matrix [S,
    S] whose row p holds a 1 at the keys S_p, packed (`_pack`). `leaf` names
    the indexer's weights."""
    def w(name):
        return leaf("self_attn.indexer." + name).astype(F32)

    S = h.shape[0]
    Hi, Di = config["index_n_heads"], config["index_head_dim"]
    dr, theta = config["qk_rope_head_dim"], \
        float(config["rope_parameters"]["rope_theta"])
    topk = min(int(config["index_topk"]), S)

    def rotate(x):           # [S, ..., Di]: the first dr columns turn
        return jnp.concatenate([_rope(x[..., :dr], theta), x[..., dr:]], -1)

    k = h @ w("wk.weight")
    mean = jnp.mean(k, -1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(
        jnp.mean((k - mean) ** 2, -1, keepdims=True) + 1e-6)
    k = rotate(k * w("k_norm.weight") + w("k_norm.bias"))        # [S, Di]
    q = rotate((c_q @ w("wq_b.weight")).reshape(S, Hi, Di))
    gain = h @ w("weights_proj.weight") * Hi ** -0.5 * Di ** -0.5
    if not config.get("index_head_weights", True):
        gain = jnp.ones_like(gain)
    block, pad = _blocks(S)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, Hi, Di)
    gb = jnp.pad(gain, ((0, pad), (0, 0))).reshape(-1, block, Hi)
    starts = jnp.arange(qb.shape[0], dtype=jnp.int32) * block
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    relu = config.get("index_relu", True)

    def one(args):
        qs, gs, start = args

        def head(total, part):
            qh, gh = part                        # [block, Di], [block]
            dots = qh @ k.T
            if relu:
                dots = jnp.maximum(dots, 0.0)
            return total + gh[:, None] * dots, None

        scores, _ = jax.lax.scan(
            head, jnp.zeros((block, S), F32),
            (jnp.swapaxes(qs, 0, 1), gs.T))
        pos = start + jnp.arange(block, dtype=jnp.int32)[:, None]
        scores = jnp.where(key_pos <= pos, scores, -jnp.inf)
        kth = jax.lax.top_k(scores, topk)[0][:, -1:]
        above, tie = scores > kth, scores == kth
        room = topk - jnp.sum(above, -1, keepdims=True)
        keep = above | (tie & (jnp.cumsum(tie, -1) <= room))
        return _pack(keep & (key_pos <= pos))

    packed = jax.lax.map(one, (qb, gb, starts))
    return packed.reshape(-1, packed.shape[-1])[:S]


def _head(q, k, v, scale, chosen):
    """One head: q, k [S, 256], v [S, 256] -> [S, 256]; the queries in
    blocks, each against all S keys under its rows of `chosen` (the packed
    [S, S] matrix)."""
    S = q.shape[0]
    block, pad = _blocks(S)
    qb = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, block, q.shape[1])
    starts = jnp.arange(qb.shape[0], dtype=jnp.int32) * block
    rows = jnp.pad(chosen, ((0, pad), (0, 0)))

    def one(args):
        qs, start = args
        keep = _unpack(jax.lax.dynamic_slice_in_dim(rows, start, block, 0),
                       S)
        s = jnp.where(keep, (qs @ k.T) * scale, -jnp.inf)
        # a padded query row sees no key: its softmax is of no use to anyone
        s = jnp.where(jnp.any(keep, -1, keepdims=True), s, 0.0)
        return jax.nn.softmax(s, -1) @ v

    return jax.lax.map(one, (qb, starts)).reshape(-1, v.shape[1])[:S]


def _attention(h, c_q, chosen, leaf, config):
    """h [S, hidden] (normed) -> the attention's output [S, hidden], the
    softmax over the keys `chosen` (the packed [S, S] matrix) names."""
    def w(name):
        return leaf("self_attn." + name).astype(F32)

    H, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    theta = float(config["rope_parameters"]["rope_theta"])
    scale = (nope + dr) ** -0.5
    ckv = h @ w("kv_a_proj_with_mqa.weight")
    c_kv = _rms_norm(ckv[:, :rank], w("kv_a_layernorm.weight"), eps)
    k_r = _rope(ckv[:, rank:], theta)                           # [S, dr]
    w_q = leaf("self_attn.q_b_proj.weight").reshape(-1, H, nope + dr)
    w_kv = leaf("self_attn.kv_b_proj.weight").reshape(rank, H, nope + dv)
    w_o = leaf("self_attn.o_proj.weight").reshape(H, dv, -1)

    def head(out, ws):
        wq, wkv, wo = (a.astype(F32) for a in ws)
        q, kv = c_q @ wq, c_kv @ wkv
        q = jnp.concatenate([q[:, :nope], _rope(q[:, nope:], theta)], -1)
        k = jnp.concatenate([kv[:, :nope], k_r], -1)
        return out + _head(q, k, kv[:, nope:], scale, chosen) @ wo, None

    return jax.lax.scan(head, jnp.zeros_like(h),
                        (jnp.swapaxes(w_q, 0, 1), jnp.swapaxes(w_kv, 0, 1),
                         w_o))[0]


def gates(h, router, bias, config):
    """h [S, hidden] -> the gate of every published expert [S, E]: g_e
    where e is among the position's chosen, 0 elsewhere."""
    E, top_k = router.shape[1], config["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ router.astype(F32))
    if config["n_group"] != 1:
        raise NotImplementedError("glm_moe_dsa routes over one group")
    choice = s + bias.astype(F32)
    bar = jnp.sort(choice, -1)[:, E - top_k, None]
    g = jnp.where(choice >= bar, s, 0.0)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * config["routed_scaling_factor"]


def _moe(h, leaf, config):
    """The held experts' part of the layer's sum plus the shared expert."""
    g = gates(h, leaf("mlp.experts.router_weight"),
              leaf("mlp.experts.select_bias"), config)
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
    kinds = config["indexer_types"]
    chosen, indexer = None, None
    for i in range(config["num_hidden_layers"]):
        def leaf(name, p=f"model.layers.{i}."):
            return weights[p + name]
        h = _rms_norm(x, leaf("input_layernorm.weight").astype(F32), eps)
        c_q = _rms_norm(h @ leaf("self_attn.q_a_proj.weight").astype(F32),
                        leaf("self_attn.q_a_layernorm.weight").astype(F32),
                        eps)
        if kinds[i] == "full":
            indexer = leaf
        if kinds[i] == "full" or not config.get("index_share", True):
            chosen = selection(h, c_q, indexer, config)
        x = x + _attention(h, c_q, chosen, leaf, config)
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
