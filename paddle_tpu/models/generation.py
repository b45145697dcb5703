"""Autoregressive generation with a static-shape KV cache.

Parity-plus: the reference (Paddle ~2.1 core) ships only the beam-search
decoder primitive (fluid/contrib decoder; here nn/decode.py) — it has no
LLM generation loop. TPU-first design: ONE jitted prefill call fills the
cache for the prompt, then ONE jitted lax.while_loop runs the decode steps
on-device (static [B, H, max_len, D] cache slabs, dynamic_update_slice
writes, absolute-position causal masks), so the host pays two dispatches
total instead of one per token — and the loop exits as soon as every row
has emitted EOS instead of always paying all max_new_tokens steps.

The prefill/decode-step builders are exposed (make_decoder_fns) so the
serving LLM engine (serving/llm/) and one-shot generate() share one cache
layout and one numeric path: continuous-batched decode is bit-identical
per row to batch-locked greedy generate().
"""
from __future__ import annotations

from typing import Dict, FrozenSet, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, no_grad
from ..utils.jit_cache import JitLRUCache

# varied (B, S0, max_new_tokens, ...) shapes each compile their own
# prefill+decode executable; the shared JitLRUCache policy (ISSUE 7)
# bounds the compiled-program count and warns when callers churn shapes
_GENERATE_JIT_CACHE_CAP = 8


def _nucleus_threshold(srt, p):
    """[B, 1] logit threshold of the nucleus of rows sorted descending
    (`srt` [B, V], `p` [B]): the smallest set of tokens whose
    probability mass reaches p, by the standard "cumulative mass before
    this sorted slot is still < p" rule, so at least the most-likely
    token always survives."""
    probs = jax.nn.softmax(srt, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    n_keep = jnp.maximum(jnp.sum(cum_before < p[:, None], axis=-1), 1)
    return jnp.take_along_axis(srt, (n_keep - 1)[:, None], axis=-1)


def _top_p_filter(lg, top_p):
    """Nucleus filter on [B, V] logits; `top_p` is a scalar (the static
    path of `_select_token`; the batched path takes the same threshold
    from `_top_k_top_p_filter`'s one sort).

    Maps the sorted cut back to logit space as a per-row threshold —
    ties at the threshold survive, matching the top-k tie semantics."""
    B, V = lg.shape
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    thr = _nucleus_threshold(jnp.sort(lg, axis=-1)[:, ::-1], p)
    keep = (lg >= thr) | (p[:, None] >= 1.0)
    return jnp.where(keep, lg, -1e30)


def _top_k_top_p_filter(lg, top_k, top_p):
    """Per-row top-k then top-p filter on [B, V] logits with ONE sort;
    `top_k` an i32 [B] and `top_p` an f32 [B] vector (the serving
    engine's batched path). k <= 0 / p >= 1 mean no such filter for
    that row; ties at either threshold survive (every logit >= the
    threshold), as in the static lax.top_k / `_top_p_filter` branches.

    The top-p filter's input is the top-k-filtered row. That filter is
    monotone (`lg >= kth` survives), so the filtered row sorted again
    equals `where(srt >= kth, srt, -1e30)` of the row sorted once,
    element for element, and the nucleus threshold comes out as a
    second sort would give it."""
    V = lg.shape[-1]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        srt, (jnp.clip(top_k, 1, V) - 1)[:, None], axis=-1)
    no_k = top_k[:, None] <= 0
    lg = jnp.where((lg >= kth) | no_k, lg, -1e30)
    thr = _nucleus_threshold(
        jnp.where((srt >= kth) | no_k, srt, -1e30), top_p)
    keep = (lg >= thr) | (top_p[:, None] >= 1.0)
    return jnp.where(keep, lg, -1e30)


def _select_token(logits, do_sample, temperature, top_k, key, top_p=1.0):
    """logits [B, V] -> next token [B] (greedy or temp/top-k/top-p).

    Two calling conventions share this one function:

    * static knobs (one-shot generate()): `do_sample` a python bool,
      `temperature`/`top_k`/`top_p` python scalars, `key` a single PRNG
      key — python-level branches keep the pre-top-p greedy and
      sampled paths bit-identical to earlier releases;
    * batched per-row params (serving sampling subsystem, ISSUE 18):
      `do_sample` a bool [B] array, `temperature`/`top_k`/`top_p`
      [B] arrays, `key` a [B, 2] array of PER-ROW keys — every row
      mixes greedy and sampled freely inside one traced program, so
      per-request params never force a recompile.
    """
    if isinstance(do_sample, bool):
        if not do_sample:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
        if top_k and top_k > 0:
            # kth-largest via lax.top_k (O(V·k-ish)) instead of a full
            # O(V log V) sort; ties at the threshold keep identical
            # semantics (every logit >= kth survives)
            kth = jax.lax.top_k(lg, top_k)[0][:, -1][:, None]
            lg = jnp.where(lg < kth, -1e30, lg)
        if top_p is not None and float(top_p) < 1.0:
            lg = _top_p_filter(lg, float(top_p))
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    # batched per-row path: params and keys are traced arrays. Only the
    # argmax is unconditional. The draw, and the filters' sort before
    # it, sit in branches of one conditional on the operands themselves:
    # a step whose rows are all greedy sorts nothing and draws nothing,
    # and temperature-only sampling draws without a sort. The device
    # runs one branch; the executable is the same whatever the mix.
    # (One three-way switch and not a conditional inside another: its
    # branches share their temporaries, the nested form held one more
    # [B, V] float32 buffer.)
    B = logits.shape[0]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samp = jnp.broadcast_to(jnp.asarray(do_sample, bool), (B,))
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))

    def _draw(filtered):
        temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
        lg = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
        if filtered:
            lg = _top_k_top_p_filter(lg, k, p)
        sampled = jax.vmap(jax.random.categorical)(key, lg)
        return jnp.where(samp, sampled.astype(jnp.int32), greedy)

    # 0: no row samples; 1: some do, none with a filter; 2: some filter
    filters = samp & ((k > 0) | (p < 1.0))
    mode = (jnp.any(samp).astype(jnp.int32)
            + jnp.any(filters).astype(jnp.int32))
    return jax.lax.switch(mode, (lambda: greedy, lambda: _draw(False),
                                 lambda: _draw(True)))


class RecurrentState(NamedTuple):
    """What `init_cache` returns for a layer whose state does not grow
    with the sequence (a state-space mixer), where an attention layer
    returns its `(k, v)` slabs: `conv [batch, K - 1, channels]`, the last
    inputs of the layer's causal convolution, and `ssm [batch, N, H * P]`,
    the recurrence's state (`ops/ssm.py`). A pair like `(k, v)`, so it
    rides wherever a cache rides; by its type a cache manager knows that
    this layer's state is one fixed block per row, valid only at the row's
    committed length: it has no pages to share, trim or re-read."""
    conv: jax.Array
    ssm: jax.Array


class WindowKV(NamedTuple):
    """What `init_cache` returns for an attention layer with a sliding
    window when the caller asked for a ring (`window_slab=`): `(k, v)`
    slabs `[batch, Hkv, ring + pad, D]`, shorter than the other layers'.
    Position p lives at column `p mod ring` and keys older than the window
    are overwritten, so the layer's pages cannot be shared, exported or
    re-read once aged out; by this type a cache manager knows."""
    k: jax.Array
    v: jax.Array


class LatentKV(NamedTuple):
    """What `init_cache` returns for an attention layer that caches a
    latent (multi-head latent attention, `models/deepseek.py`): `c [batch,
    1, max_len, kv_lora_rank]`, the compressed keys-and-values after their
    norm, and `r [batch, 1, max_len, qk_rope_head_dim]`, the one rotary
    key every head shares, after RoPE. A pair like `(k, v)` of unequal
    widths and one "head", addressed by position like `(k, v)`: its pages
    can be shared, copied, exported and re-read, so a cache manager treats
    it as it treats K/V pages and by this type only knows what to call it."""
    c: jax.Array
    r: jax.Array


class IndexedLatentKV(NamedTuple):
    """What `init_cache` returns for a latent layer that carries an indexer
    (learned sparse attention, `models/deepseek.py`): `LatentKV`'s `c` and
    `r` and, beside them, `k_index [batch, 1, max_len, index_head_dim]`,
    the one index key a token that the layer's indexer scores queries
    against. Three slabs addressed by position alike, so a cache manager
    pages, shares, copies and exports all three together; by this type it
    knows that the third is there."""
    c: jax.Array
    r: jax.Array
    k_index: jax.Array


class RecurrentStateError(NotImplementedError):
    """Asked of a cache manager that holds recurrent per-slot state: an
    operation that rebuilds a row from its pages (a rewind, a page copy, an
    export or import). Snapshots of the state are later work (ROADMAP)."""


class WindowRingError(NotImplementedError):
    """Asked of a cache manager that keeps a window layer's keys in a ring:
    an operation that reads, copies or shares pages by their logical block
    (prefix sharing, a page copy, an export or import), or a rewind past
    the ring's slack. Prefix reuse over window layers is later work
    (ROADMAP)."""


# What a cache manager can be asked that a kind may have to refuse:
REREAD = "pages"            # re-reading or restoring a row's pages by their
#                             logical block: prefix sharing, an imported
#                             `kv_row`, page copies, exports and imports, a
#                             rewind past the step's write pad
HOST_TIER = "host_kv_bytes"  # spilling pages to the host and back
REWIND = "draft_model"      # taking back the positions of a rejected draft
#                             window (at most the step's write pad)


class CacheKind(NamedTuple):
    """A row of `CACHE_KINDS`: all that the serving pool and the engine
    know of what a layer keeps per slot."""
    name: str                   # what `SlotPagedKVPool.layer_kinds` says
    bytes_as: Tuple[Optional[str], ...]   # per array of the entry, the
    #                             label its bytes are reported under
    #                             (`kv_bytes()`); None: per-slot state,
    #                             reported apart (`recurrent_state_bytes`)
    refuses: FrozenSet[str] = frozenset()   # of the three above
    why: str = ""               # the one sentence that says why
    error: type = NotImplementedError   # what the pool raises with it


# By the type of a layer's `init_cache` entry; a plain `(k, v)` is "paged".
# A new kind is a NamedTuple above and a row here: the pool and the engine
# read what they refuse, and what they call its bytes, from the row.
CACHE_KINDS: Dict[type, CacheKind] = {
    tuple: CacheKind("paged", ("full", "full")),
    RecurrentState: CacheKind(
        "recurrent", (None, None), frozenset({REREAD, HOST_TIER, REWIND}),
        "a recurrence's state exists only at a row's committed length: it "
        "cannot be rebuilt from pages, and pages carry none of it",
        RecurrentStateError),
    WindowKV: CacheKind(
        "window", ("window", "window"), frozenset({REREAD, HOST_TIER}),
        "a window layer's keys live in a ring, where a page older than the "
        "window has been overwritten", WindowRingError),
    LatentKV: CacheKind("latent", ("latent", "latent")),
    IndexedLatentKV: CacheKind(
        "indexed", ("latent", "latent", "index"), frozenset({HOST_TIER}),
        "index-key pages have no place in the host tier, which holds (k, v) "
        "pairs; sparse reads from it are later work"),
}


def kind_of(entry) -> CacheKind:
    """The row of `CACHE_KINDS` for one layer's `init_cache` entry."""
    return CACHE_KINDS.get(type(entry), CACHE_KINDS[tuple])


def make_decoder_fns(model):
    """Expose the prefill/decode-step builders for a cached-decode model.

    Returns (params, prefill, decode_step) where both functions are pure
    (jit-able) over raw arrays:

      prefill(params, prompt [B, S], caches, pos) -> (logits [B, S, V],
          new_caches) — runs the whole prompt through the cache at offset
          `pos` (normally 0) and returns per-position logits;
      decode_step(params, tok [B], pos, caches) -> (logits [B, V],
          new_caches) — one token per row, written at `pos`.

    `pos` may be a scalar (whole batch at one offset — the batch-locked
    generate() path) or a [B] int32 vector (per-row offsets — the
    slot-paged serving engine, where each cache row sits at its own
    length). `caches` is model.init_cache() layout: a list of
    (k [B, Hkv, L, D], v) slabs, one per layer (a layer that keeps a third
    slab a token hands a triple). The model is captured for
    its buffers/structure; call with the model already in eval mode.

    Both functions accept an optional `paged` (`ops.attention.PagedView`:
    block table `[B, max_blocks]`, `seq_lens [B]`, the page geometry)
    routing attention through the ragged paged kernel against slot-pool
    page tables (ISSUE 7; the engine's chunked-prefill mixed dispatch). Left as None, attention runs
    the trivial contiguous-table path — the same kernel, so streams stay
    bit-identical across the two callers at a shared block size.

    Both also accept `adapters=(per_layer_banks, adapter_idx [B],
    scale [K])` — the per-slot LoRA parameter-indirection operand
    (ISSUE 20). per_layer_banks[i] maps site name -> (A [K, r, in],
    B [K, out, r]) stacked device arrays; each row gathers its own bank
    row inside the step, so K adapters share one executable and bank row
    0 (all-zeros) keeps adapter-less rows bit-identical to base. Left as
    None, the adapted projections are not even traced.

    `prefill` also accepts `pack` (`ops.attention.TokenPack`): `prompt` is
    then `[T, 1]`, the live tokens of a serving step as rows of width one,
    `pos [T]` each token's own position and `adapter_idx [T]` its slot's;
    the logits come back `[T, 1, V]` (the engine's `_step`). Left as None
    it is not traced either.

    And `emit` (`[E]` int32, the engine's `_step` again): flat positions of
    the block viewed `[positions, hidden]`. The vocabulary head runs on
    those rows alone and the logits come back `[E, V]`; every position
    still writes its cache. Left as None every position gets its logits
    and the trace is the one it was.
    """
    params, buffers = model.functional_state()

    def prefill(p, prompt, caches_, pos, paged=None, adapters=None,
                pack=None, emit=None):
        with model._bound_state(p, buffers), no_grad():
            logits, new_caches = model.forward_with_cache(
                Tensor(prompt),
                [tuple(Tensor(a) for a in entry) for entry in caches_], pos,
                paged=paged, adapters=adapters, pack=pack, emit=emit)
        return logits.data, [tuple(a.data for a in entry)
                             for entry in new_caches]

    def decode_step(p, tok, pos, caches_, paged=None, adapters=None):
        with model._bound_state(p, buffers), no_grad():
            logits, new_caches = model.forward_with_cache(
                Tensor(tok[:, None]),
                [tuple(Tensor(a) for a in entry) for entry in caches_], pos,
                paged=paged, adapters=adapters)
        return logits.data[:, 0], [tuple(a.data for a in entry)
                                   for entry in new_caches]

    return params, prefill, decode_step


def make_verify_fn(model):
    """Multi-position greedy verify builder (ISSUE 17 speculative
    decoding): returns (params, verify) where

      verify(params, toks [B, C], caches, pos, paged=None) ->
          (tokens [B, C] int32, new_caches)

    runs the same cached forward as `make_decoder_fns`'s prefill but
    argmaxes EVERY position: tokens[b, t] is the greedy token the model
    emits after consuming toks[b, :t+1] on top of the cache state at
    `pos`. This is what makes draft-token verification one dispatch: a
    verify row carrying [last_tok, d1..dK] scores all K+1 candidate
    continuations at once, and because each position's logits are
    computed under exactly the causal masking a sequential decode would
    see (chunk invariance, PR 7), tokens[b, t] equals what t sequential
    decode_step calls would have produced — so accepting the longest
    matching draft prefix plus the first divergent (corrective) token is
    bit-identical to plain greedy decoding. Reading only column `adv-1`
    degenerates to the pre-spec unified step, which is why one
    executable serves prefill, plain decode, and verification."""
    params, prefill, _ = make_decoder_fns(model)

    def verify(p, toks, caches_, pos, paged=None, adapters=None):
        logits, new_caches = prefill(p, toks, caches_, pos, paged=paged,
                                     adapters=adapters)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches

    return params, verify


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             seed=0):
    """Returns a Tensor [B, S0 + max_new_tokens] of prompt + continuation.
    With eos_token_id, finished rows pad with eos and the decode loop
    stops early once every row has finished. The number of decode-step
    dispatches actually executed is recorded on the model as
    `_last_decode_steps` (prefill's token excluded)."""
    from ..distributed.meta_parallel.mp_layers import _explicit_tp, \
        _mp_degree
    if _explicit_tp() or _mp_degree() > 1:
        raise NotImplementedError(
            "generate() is single-device: the KV cache is sized by GLOBAL "
            "head count and the decode loop issues no TP collectives. Run "
            "generation outside the tensor-parallel context")
    ids = np.asarray(input_ids.data if isinstance(input_ids, Tensor)
                     else input_ids).astype(np.int32)
    B, S0 = ids.shape
    if max_new_tokens <= 0:
        return Tensor(jnp.asarray(ids))
    L = S0 + max_new_tokens
    caches = model.init_cache(B, L)
    was_training = model.training
    model.eval()
    params, prefill, decode_step = make_decoder_fns(model)

    # jit cache keyed by every static knob: a fresh closure per call would
    # recompile prefill + the decode loop on EVERY generate() invocation
    gen_cache = model.__dict__.setdefault(
        "_generate_jit_cache",
        JitLRUCache(_GENERATE_JIT_CACHE_CAP, name="generate"))
    # top_p is part of the key: a distinct nucleus cutoff is a distinct
    # compiled filter, and omitting it would silently reuse the wrong
    # executable (ISSUE 18 satellite — the LRU test pins the churn story)
    cache_key = (B, S0, max_new_tokens, do_sample, float(temperature),
                 int(top_k), float(top_p), eos_token_id)
    # token buffer pre-filled with eos so rows finished before the loop
    # exits keep the documented eos padding
    eos_fill = 0 if eos_token_id is None else int(eos_token_id)

    def run(p, prompt, caches_, key):
        logits, caches_ = prefill(p, prompt, caches_, jnp.int32(0))
        key, sub = jax.random.split(key)
        tok0 = _select_token(logits[:, -1], do_sample, temperature, top_k,
                             sub, top_p)
        done0 = (jnp.zeros((B,), jnp.bool_) if eos_token_id is None
                 else tok0 == eos_token_id)
        buf = jnp.full((B, max_new_tokens), eos_fill, jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, tok0[:, None], (0, 0))

        def cond(carry):
            i, _tok, done, _caches, _key, _buf = carry
            return jnp.logical_and(i < max_new_tokens - 1,
                                   jnp.logical_not(jnp.all(done)))

        def body(carry):
            i, tok, done, caches_c, key_c, buf_c = carry
            step_logits, caches_c = decode_step(p, tok, S0 + i, caches_c)
            key_c, sub_c = jax.random.split(key_c)
            nxt = _select_token(step_logits, do_sample, temperature, top_k,
                                sub_c, top_p)
            if eos_token_id is not None:
                nxt = jnp.where(done, eos_token_id, nxt)
                done = done | (nxt == eos_token_id)
            buf_c = jax.lax.dynamic_update_slice(buf_c, nxt[:, None],
                                                 (0, i + 1))
            return (i + 1, nxt, done, caches_c, key_c, buf_c)

        steps, _, _, _, _, buf = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), tok0, done0, caches_, key, buf))
        return buf, steps

    run_jit = gen_cache.get_or_build(cache_key, lambda: jax.jit(run))
    new_toks, steps = run_jit(params, jnp.asarray(ids), caches,
                              jax.random.PRNGKey(seed))
    model.__dict__["_last_decode_steps"] = int(steps)
    if was_training:
        model.train()
    return Tensor(jnp.concatenate([jnp.asarray(ids), new_toks], axis=1))
