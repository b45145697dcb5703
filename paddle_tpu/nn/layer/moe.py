"""Mixture-of-Experts layer with expert parallelism.

Reference anchor: the reference ships ONLY the alltoall primitive
(python/paddle/distributed/collective.py:1456) and no MoE layer (SURVEY header) —
this is parity-plus, designed GSPMD-first (Switch/GLaM pattern):

- experts are stacked [E, ...] weight tensors whose leading dim carries
  partition_spec over the `ep` mesh axis;
- routing builds static-shaped dispatch/combine tensors (capacity-based top-k,
  einsum dispatch) so XLA sees fixed shapes and inserts the all_to_all when the
  token→expert einsum crosses the ep sharding;
- the load-balancing auxiliary loss (Switch eq. 4) is returned alongside.

That capacity path (`moe_forward`, `MoELayer`) is what GPT training uses.
Serving uses the dropless path below (`moe_dropless_forward`,
`DroplessMoE`): capacity drops tokens, and which token is dropped depends
on its batchmates, so a request's stream would depend on who shares its
step; and the `[T, E, C]` one-hot tensors do not fit at a serving shape.
The dropless path sorts the assignments of the live positions by expert
and runs the experts as grouped matmuls (`ops/grouped_matmul.py`) over the
same `[E, ...]` stacked weights and `ep` partition spec.
"""
from __future__ import annotations

import collections
import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.tensor import apply
from ...ops.grouped_matmul import grouped_matmul
from .. import initializer as I
from .layers import Layer

EXPERT_AXIS = "ep"

# (layer, expert) -> live assignments routed there, over every dropless
# engine of this process (`LLMEngine.moe_expert_tokens()` adds what is new
# since it last did; so does `stop()`). What `ops.pallas_mode.KERNEL_TRACES`
# is to kernel paths: always on, and what a benchmark reads after the job,
# when the engine is gone.
EXPERT_TOKENS: collections.Counter = collections.Counter()
# layer -> live positions that layer routed, published with EXPERT_TOKENS:
# x experts per token, what the layer's row sums to where every expert is
# held, and what a share's row is a part of.
ROUTED_TOKENS: collections.Counter = collections.Counter()
_collecting = threading.local()


@contextlib.contextmanager
def collect_expert_counts():
    """Inside the block, every `DroplessMoE` traced on this thread appends
    its `[E]` int32 counts of live assignments to the list this yields, in
    call order: how a jitted step gets the counts out of a model whose
    forward returns logits and caches only. Outside such a block the
    counts are dropped."""
    sink = []
    outer = getattr(_collecting, "sink", None)
    _collecting.sink = sink
    try:
        yield sink
    finally:
        _collecting.sink = outer


def _top_k_dispatch(gates, capacity, top_k):
    """gates [T, E] → dispatch [T, E, C] bool-ish, combine [T, E, C] float,
    aux loss. Static shapes; Switch-Transformer routing."""
    T, E = gates.shape
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    remaining = gates
    # aux loss uses the FULL softmax and the top-1 assignment fractions
    mask1_for_aux = None
    fill = jnp.zeros((E,), jnp.float32)  # slots used per expert so far
    for rank in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                 # [T]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [T, E]
        if rank == 0:
            mask1_for_aux = mask
        # position of each token within its expert queue (respecting slots
        # already consumed by earlier ranks)
        pos = jnp.cumsum(mask, axis=0) - 1 + fill[None, :]   # [T, E]
        keep = (pos < capacity).astype(jnp.float32) * mask
        pos_kept = jnp.where(mask > 0, pos, 0).astype(jnp.int32)
        onehot_pos = jax.nn.one_hot(pos_kept, capacity,
                                    dtype=jnp.float32)       # [T, E, C]
        d = keep[..., None] * onehot_pos
        gate_vals = jnp.sum(gates * mask, axis=-1, keepdims=True)  # [T,1]
        dispatch = dispatch + d
        combine = combine + d * gate_vals[..., None]
        fill = fill + jnp.sum(keep, axis=0)
        remaining = remaining * (1.0 - mask)
    # normalize combine weights over selected experts
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    # load-balance loss: E * sum_e f_e * p_e
    density = jnp.mean(mask1_for_aux, axis=0)        # fraction routed
    density_proxy = jnp.mean(gates, axis=0)          # mean gate prob
    aux = E * jnp.sum(density * density_proxy)
    return dispatch, combine, aux


def _moe_core(x, gate_w, w1, b1, w2, b2, top_k, capacity_factor, activation,
              n_experts, exchange_in=None, exchange_out=None):
    """Shared MoE math: routing over `n_experts`, dispatch to [E, C, H]
    buffers, expert FFN, combine. The optional exchange hooks wrap the
    expert compute — identity for the GSPMD path, all_to_all pairs for the
    explicit expert-parallel path — so the routing/capacity math can never
    diverge between the two."""
    B, S, H = x.shape
    T = B * S
    xt = x.reshape(T, H)
    logits = (xt.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    capacity = max(int(capacity_factor * T * top_k / n_experts), top_k)
    dispatch, combine, aux = _top_k_dispatch(gates, capacity, top_k)
    # token → expert buffers [E, C, H]; on the GSPMD path, crossing the ep
    # sharding here makes XLA emit the all_to_all
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    if exchange_in is not None:
        expert_in = exchange_in(expert_in)
    h = activation(jnp.einsum("ech,ehf->ecf", expert_in, w1)
                   + b1[:, None, :].astype(x.dtype))
    expert_out = jnp.einsum("ecf,efh->ech", h, w2) \
        + b2[:, None, :].astype(x.dtype)
    if exchange_out is not None:
        expert_out = exchange_out(expert_out)
    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S, H), aux.astype(jnp.float32)


def moe_forward(x, gate_w, w1, b1, w2, b2, top_k, capacity_factor,
                activation=jax.nn.gelu):
    """Pure MoE math over arrays. x: [B, S, H]; w1: [E, H, F]; w2: [E, F, H]."""
    return _moe_core(x, gate_w, w1, b1, w2, b2, top_k, capacity_factor,
                     activation, n_experts=w1.shape[0])


def moe_forward_ep(x, gate_w, w1, b1, w2, b2, top_k, capacity_factor,
                   activation=jax.nn.gelu, axis=EXPERT_AXIS):
    """Explicit expert-parallel MoE for MAPPED mesh axes (inside shard_map,
    where GSPMD cannot insert the all_to_all): the GShard dispatch done by
    hand. Each ep rank holds its local tokens [B_local, S, H] and its local
    experts w1 [E_local, H, F]; routing runs over the full E, then a tiled
    lax.all_to_all exchanges token buffers so every rank computes exactly
    its own experts over everyone's tokens, and the inverse all_to_all
    brings the results home (all_to_all is a permutation collective — its
    AD transpose is the inverse permutation, so grads are exact; expert-
    weight grads already sum over ALL ranks' tokens locally and need no
    cross-ep reduction; aux is a local-token statistic the caller averages
    over the ep (data) axis).

    Reference anchor: collective.py:1456 alltoall is the one MoE primitive
    the reference ships; this is its production use, Switch/GShard-style.
    """
    ep_n = jax.lax.psum(1, axis)  # static axis size
    E = w1.shape[0] * ep_n

    def exchange_in(expert_in):
        # split E into ep groups, concat on capacity → each rank now holds
        # [E_local, ep_n*C, H]: its experts, everyone's tokens
        return jax.lax.all_to_all(expert_in, axis, split_axis=0,
                                  concat_axis=1, tiled=True)

    def exchange_out(expert_out):
        # inverse: results home to the token-owning ranks [E, C, H]
        return jax.lax.all_to_all(expert_out, axis, split_axis=1,
                                  concat_axis=0, tiled=True)

    return _moe_core(x, gate_w, w1, b1, w2, b2, top_k, capacity_factor,
                     activation, n_experts=E, exchange_in=exchange_in,
                     exchange_out=exchange_out)


class MoELayer(Layer):
    """paddle.incubate-style MoE FFN (gate + stacked experts).

    usage:
        moe = MoELayer(d_model=512, d_hidden=2048, num_experts=8, top_k=2)
        out = moe(x)               # x: [B, S, d_model]
        aux = moe.aux_loss         # add to the training loss (scaled)
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu", gate=None,
                 name=None):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self._act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                     "silu": jax.nn.silu}[activation]
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=I.XavierUniform())
        self.b1 = self.create_parameter([num_experts, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=I.XavierUniform())
        self.b2 = self.create_parameter([num_experts, d_model], is_bias=True)
        for p in (self.w1, self.b1, self.w2, self.b2):
            p.partition_spec = P(EXPERT_AXIS)
        self.aux_loss = None

    def forward(self, x):
        top_k, cf, act = self.top_k, self.capacity_factor, self._act
        # inside a shard_map with the ep axis mapped (pipeline stage fns),
        # GSPMD can't insert the all_to_all — take the explicit path on the
        # rank-local expert shards (mp_layers' axis_context pattern)
        from ...distributed.collective import current_axes, in_axis_context
        explicit_ep = in_axis_context() and EXPERT_AXIS in current_axes()
        fwd = moe_forward_ep if explicit_ep else moe_forward

        def f(xa, gw, w1, b1, w2, b2):
            return fwd(xa, gw, w1, b1, w2, b2, top_k, cf, act)

        out, aux = apply(f, x, self.gate_weight, self.w1, self.b1, self.w2,
                         self.b2)
        self.aux_loss = aux
        return out


def route(logits, top_k, norm_topk_prob=False, scoring="softmax",
          n_group=1, topk_group=1, select_bias=None, routed_scale=1.0):
    """Router logits `[T, E]` float32 -> (gates `[T, top_k]` float32, the
    chosen experts `[T, top_k]` int32).

        s = softmax(logits) | sigmoid(logits)             (`scoring`)
        c = s + select_bias             for choosing only, never in a gate
        groups: E experts in `n_group` equal groups; a group's score is
                the sum of its 2 largest c; the `topk_group` best groups
                are eligible                     (n_group 1: every expert)
        S = the top_k largest c among the eligible experts
        g_e = s_e, over S renormalised to sum 1 if `norm_topk_prob`,
              times `routed_scale`

    The defaults are the plain softmax top-k, on the operations it always
    took (OLMoE's, granite's and Mellum's arithmetic stays bit for bit);
    the rest is the DeepSeek-V3 family's router (Hugging Face's
    `DeepseekV3TopkRouter`; an ineligible expert is out of the choice
    whatever the sign of c, where that code fills in 0)."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
    s = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    if n_group == 1 and select_bias is None:
        w, idx = jax.lax.top_k(s, top_k)
    else:
        T, E = s.shape
        if E % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{topk_group} of {n_group} groups over {E} "
                             "experts")
        c = s if select_bias is None \
            else s + select_bias.astype(jnp.float32)
        if n_group > 1:
            grouped = c.reshape(T, n_group, E // n_group)
            score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, best = jax.lax.top_k(score, topk_group)      # [T, topk_group]
            eligible = jnp.any(
                best[..., None] == jnp.arange(n_group, dtype=best.dtype),
                axis=1)                                      # [T, n_group]
            c = jnp.where(eligible[..., None], grouped,
                          -jnp.inf).reshape(T, E)
        _, idx = jax.lax.top_k(c, top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if routed_scale != 1.0:
        w = w * routed_scale
    return w, idx


def moe_dropless_forward(x, router_w, w_gate, w_up, w_down, top_k,
                         norm_topk_prob=False, live=None, held=None,
                         scoring="softmax", n_group=1, topk_group=1,
                         select_bias=None, routed_scale=1.0):
    """Dropless top-k SwiGLU experts over arrays. x `[..., H]`; router_w
    `[H, E]`; w_gate, w_up `[E, H, F]`; w_down `[E, F, H]`; `live` a bool
    mask over x's leading axes (None: every position is live). Returns
    (out like x, counts `[E]` int32 of live assignments per expert).

    `held=(first, count)`: this layer holds experts `first .. first +
    count - 1` of the router's E (one chip's share under expert
    parallelism): the weights are `[count, ...]`, routing, top-k and gates
    are over all E as published, and an assignment to an expert held
    elsewhere is treated as one of a position that is not live: no row, no
    count, zeros. The result is this share's part of the layer's sum; the
    counts are `[count]`. None: every expert is held.

        p = softmax_float32(x router_w);  S = the top_k largest p
        out = sum_{e in S} p_e (silu(x Wg_e) * (x Wu_e)) Wd_e

    with p renormalised over S if `norm_topk_prob`; `scoring`, `n_group`,
    `topk_group`, `select_bias` `[E]` and `routed_scale` make it another
    router (`route`: sigmoid scores, group-limited choice, a bias on the
    choice alone, scaled gates), over all E as published. No capacity: every
    live position reaches all its `top_k` experts. A position that is not
    live (the padding of a decode row, a free slot) is routed to no expert,
    takes no expert row, counts nowhere, and gets zeros.

    How: the `T * top_k` assignments are sorted by expert (those of
    positions that are not live sort last, past every group), the rows
    gathered in that order, three grouped matmuls run over them, and each
    position's `top_k` results are gathered back and summed in float32 in
    its own top-k order. Nothing a position receives depends on which
    other positions are in the batch: a row of a grouped matmul depends on
    that row and its expert's weights alone, and the sum is over a
    position's own results in an order fixed by its own routing (a
    scatter-add would sum in the order of the sorted batch).
    """
    E = router_w.shape[1]
    lead, H = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, H)
    T = xt.shape[0]
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    w, idx = route(logits, top_k, norm_topk_prob, scoring, n_group,
                   topk_group, select_bias, routed_scale)
    if live is not None:
        idx = jnp.where(live.reshape(T, 1), idx, E)    # E: no expert
    if held is not None:
        first, E = held          # from here on E counts the experts held
        idx = jnp.where((idx >= first) & (idx < first + E), idx - first, E)
    expert = idx.reshape(-1)                             # [T * top_k]
    counts = jnp.sum(expert[:, None] == jnp.arange(E, dtype=expert.dtype),
                     axis=0, dtype=jnp.int32)
    order = jnp.argsort(expert, stable=True)
    xs = xt[order // top_k]
    act = jax.nn.silu(grouped_matmul(xs, w_gate, counts)) \
        * grouped_matmul(xs, w_up, counts)
    ys = grouped_matmul(act, w_down, counts)
    # back to [T, top_k, H]: assignment a sits at sorted row place[a]
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = ys[place].reshape(T, top_k, H).astype(jnp.float32)
    keep = (idx < E)[..., None]        # rows of no group are unspecified
    out = jnp.sum(jnp.where(keep, y * w[..., None], 0.0), axis=1)
    return out.astype(x.dtype).reshape(*lead, H), counts


class DroplessMoE(Layer):
    """A sparse SwiGLU FFN: a router and `num_experts` experts of width
    `d_hidden`, `top_k` per position, no shared expert, no bias
    (`moe_dropless_forward`; `scoring`, `n_group`, `topk_group`,
    `routed_scale` and `select_bias=True`, a `[num_experts]` parameter
    `select_bias`, are `route`'s). `held=(first, count)`: the layer holds that
    share of the experts (its weights are `[count, ...]`, `num_held` =
    count) and returns the share's part of the sum; None: all of them.
    `forward(x, live=None)`; under `collect_expert_counts()` each call
    also hands over its per-expert counts of live assignments (`[num_held]`)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 norm_topk_prob=False, held=None, scoring="softmax",
                 n_group=1, topk_group=1, select_bias=False,
                 routed_scale=1.0):
        super().__init__()
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        if held is not None and not (
                0 <= held[0] and 0 < held[1]
                and held[0] + held[1] <= num_experts):
            raise ValueError(f"held {held} of {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.norm_topk_prob = norm_topk_prob
        self.held = None if held is None else (int(held[0]), int(held[1]))
        self.num_held = num_experts if held is None else self.held[1]
        self.router = dict(scoring=scoring, n_group=int(n_group),
                           topk_group=int(topk_group),
                           routed_scale=float(routed_scale))
        # a per-expert bias on the router's choice (never on a gate): a
        # buffer of the model's, zero until a checkpoint gives it
        self.select_bias = self.create_parameter(
            [num_experts], is_bias=True) if select_bias else None
        if self.select_bias is not None:      # set by balancing, not SGD
            self.select_bias.trainable = False
            self.select_bias.stop_gradient = True
        init = I.Normal(0.0, 0.02)
        self.router_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=init)
        self.w_gate = self.create_parameter(
            [self.num_held, d_model, d_hidden], default_initializer=init)
        self.w_up = self.create_parameter(
            [self.num_held, d_model, d_hidden], default_initializer=init)
        self.w_down = self.create_parameter(
            [self.num_held, d_hidden, d_model], default_initializer=init)
        for p in (self.w_gate, self.w_up, self.w_down):
            p.partition_spec = P(EXPERT_AXIS)

    def forward(self, x, live=None):
        top_k, norm, held = self.top_k, self.norm_topk_prob, self.held
        router = self.router

        def f(xa, rw, wg, wu, wd, *bias):
            out, counts = moe_dropless_forward(
                xa, rw, wg, wu, wd, top_k, norm, live, held,
                select_bias=bias[0] if bias else None, **router)
            sink = getattr(_collecting, "sink", None)
            if sink is not None:
                sink.append(counts)
            return out

        bias = () if self.select_bias is None else (self.select_bias,)
        return apply(f, x, self.router_weight, self.w_gate, self.w_up,
                     self.w_down, *bias)
