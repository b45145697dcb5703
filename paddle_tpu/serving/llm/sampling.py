"""Per-slot seeded sampling + grammar-constrained decoding (ISSUE 18).

The unified mixed prefill+decode step stays ONE fixed-width jitted
program; everything a request can ask for — temperature, top-k, top-p,
a reproducible seed, a JSON-schema grammar — rides through it as
batched per-slot ARRAYS, never as static knobs, so per-request params
cannot force a recompile (the generate() JitLRUCache churn story,
solved at the engine by construction).

Three pieces:

* `SamplingParams` — the request-level contract. A request samples iff
  `seed is not None`; greedy requests never consume RNG. The seeding
  contract is **per-request threefry lanes indexed by stream
  position**: token `i` of a request's emitted stream is drawn with
  `fold_in(fold_in(PRNGKey(0), seed), i)` — a pure function of
  `(seed, i)` that never sees the slot index, the batch composition,
  or wall clock. That single property is what makes sampled streams
  bit-identical across batch-mate churn, engine restart, AND router
  failover re-prefill (the survivor just resumes the lane at
  `i = tokens_already_emitted` via `sample_offset`).

* A JSON-schema -> token-level DFA compiler. The schema subset
  (objects with properties emitted in declared order, string enums,
  const, integer, boolean, arrays) compiles to a character NFA, is
  determinized, then LIFTED to token level against the request's
  `tokens` table (token id -> text): token `t` is legal in DFA state
  `q` iff running its text through the char DFA from `q` lands in a
  live state. EOS is legal exactly in accepting states (self-loop).
  Dead token-states — no legal token and no EOS — are pruned to a
  fixpoint so a constrained slot can never paint itself into a
  maskless corner mid-stream.

* `select_tokens` — the pure, jit-traceable selection applied to the
  step's [N, C, V] logits: grammar mask first (so top-k/top-p filter
  the LEGAL set, an empty intersection is impossible), then the
  vectorized `_select_token` per-row params path, with per-(row,
  column) fold_in keys. Greedy rows take the masked argmax — for
  unconstrained greedy rows the mask is pass-through and the result
  is bit-identical to the pre-sampling verify argmax.

Speculative decoding composes via *seeded-replay acceptance*: because
the target's draw at stream index `i` is coin-fixed by `(seed, i)`,
the verify pass simply computes the token the target WOULD sample at
every window position; the existing longest-matching-prefix acceptance
then yields output literally identical to plain sampled decode —
strictly stronger than distribution-level unbiasedness (it is the same
token stream), which is the rejection-sampling guarantee with the
residual-resampling machinery collapsed away by determinism. A draft
sharing the lane (same seed, same indices, its own logits) proposes
exactly the target's draws whenever the two models agree, so the
PR 17 speedup survives. Grammar-constrained slots do not speculate.
"""
from __future__ import annotations

import json
import threading
from dataclasses import dataclass
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...models.generation import _select_token

# char-DFA subset-construction blowup guard; schemas in the supported
# subset are tiny (tens of states) — hitting this means a pathological
# enum/nesting, better rejected at admission than OOMing the bank
_MAX_CHAR_STATES = 4096


# ---------------------------------------------------------------------------
# request-level params
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling contract carried from /generate to the slot.

    `seed is None` -> greedy (the default; bit-identical to the
    pre-sampling engine). `grammar`, when set, is a dict
    `{"schema": <json-schema subset>, "tokens": {token_id: text}}`;
    constrained decoding works for greedy and sampled requests alike.
    """
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    grammar: Optional[dict] = None

    @property
    def do_sample(self) -> bool:
        return self.seed is not None

    @property
    def constrained(self) -> bool:
        return self.grammar is not None

    def validate(self):
        if not (float(self.temperature) > 0.0):
            raise ValueError(
                f"temperature must be > 0, got {self.temperature}")
        if int(self.top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not (0.0 < float(self.top_p) <= 1.0):
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")
        if self.seed is not None and not (
                0 <= int(self.seed) < 2 ** 31):
            raise ValueError(f"seed must be a non-negative int31, "
                             f"got {self.seed}")
        if self.grammar is not None:
            if (not isinstance(self.grammar, dict)
                    or "schema" not in self.grammar
                    or "tokens" not in self.grammar):
                raise ValueError(
                    "grammar must be {'schema': ..., 'tokens': "
                    "{token_id: text}}")
        return self

    def grammar_key(self) -> Optional[str]:
        """Canonical intern key for the compiled-DFA bank."""
        if self.grammar is None:
            return None
        return json.dumps(self.grammar, sort_keys=True)

    @classmethod
    def from_payload(cls, body: Mapping) -> Optional["SamplingParams"]:
        """Build from a /generate JSON payload; None when the request
        carries no sampling field at all (pure greedy fast path)."""
        fields = ("temperature", "top_k", "top_p", "seed", "grammar")
        if not any(f in body for f in fields):
            return None
        grammar = body.get("grammar")
        if grammar is not None and isinstance(grammar.get("tokens"), dict):
            # JSON object keys arrive as strings; token ids are ints
            grammar = dict(grammar)
            grammar["tokens"] = {int(k): str(v)
                                 for k, v in grammar["tokens"].items()}
        return cls(
            temperature=float(body.get("temperature", 1.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            seed=(None if body.get("seed") is None
                  else int(body["seed"])),
            grammar=grammar,
        ).validate()


GREEDY = SamplingParams()


# ---------------------------------------------------------------------------
# JSON-schema subset -> char NFA -> char DFA
# ---------------------------------------------------------------------------

class _NFA:
    def __init__(self):
        self.n = 0
        self.eps: Dict[int, set] = {}
        self.edges: Dict[int, Dict[str, set]] = {}

    def state(self) -> int:
        s = self.n
        self.n += 1
        return s

    def add_eps(self, a, b):
        self.eps.setdefault(a, set()).add(b)

    def add_edge(self, a, ch, b):
        self.edges.setdefault(a, {}).setdefault(ch, set()).add(b)

    def literal(self, text: str):
        """Chain of states consuming `text`; returns (start, end)."""
        start = cur = self.state()
        for ch in text:
            nxt = self.state()
            self.add_edge(cur, ch, nxt)
            cur = nxt
        return start, cur


def _json_string_literal(value) -> str:
    return json.dumps(value, ensure_ascii=False)


def _frag(nfa: _NFA, schema: dict):
    """Compile one schema node to an NFA fragment (start, end)."""
    if not isinstance(schema, dict):
        raise ValueError(f"unsupported schema node: {schema!r}")
    if "const" in schema:
        return nfa.literal(_json_string_literal(schema["const"]))
    if "enum" in schema:
        start, end = nfa.state(), nfa.state()
        for v in schema["enum"]:
            s, e = nfa.literal(_json_string_literal(v))
            nfa.add_eps(start, s)
            nfa.add_eps(e, end)
        return start, end
    typ = schema.get("type")
    if typ == "string":
        raise ValueError(
            "free-form strings are not DFA-boundable; constrain with "
            "'enum' or 'const'")
    if typ == "boolean":
        return _frag(nfa, {"enum": [True, False]})
    if typ == "integer" or typ == "number":
        # -?(0|[1-9][0-9]*) — JSON-canonical integers; 'number' shares
        # the integer grammar (fractions are out of the subset)
        start, end = nfa.state(), nfa.state()
        body = nfa.state()
        nfa.add_eps(start, body)
        nfa.add_edge(start, "-", body)
        nfa.add_edge(body, "0", end)
        loop = nfa.state()
        for d in "123456789":
            nfa.add_edge(body, d, loop)
        for d in "0123456789":
            nfa.add_edge(loop, d, loop)
        nfa.add_eps(loop, end)
        return start, end
    if typ == "object":
        props = schema.get("properties", {})
        if not props:
            return nfa.literal("{}")
        start, cur = nfa.literal("{")
        first = True
        # properties are REQUIRED and emitted in declared order — the
        # canonical serialization a constrained emitter produces; free
        # ordering would square the DFA for no modeled benefit
        for name, sub in props.items():
            prefix = ("" if first else ",") + _json_string_literal(
                str(name)) + ":"
            first = False
            ps, pe = nfa.literal(prefix)
            nfa.add_eps(cur, ps)
            vs, ve = _frag(nfa, sub)
            nfa.add_eps(pe, vs)
            cur = ve
        cs, ce = nfa.literal("}")
        nfa.add_eps(cur, cs)
        return start, ce
    if typ == "array":
        items = schema.get("items")
        if items is None:
            raise ValueError("array schema requires 'items'")
        start, cur = nfa.literal("[")
        end = nfa.state()
        min_items = int(schema.get("minItems", 0))
        if min_items == 0:
            nfa.add_eps(cur, end)    # empty array
        s0, e0 = _frag(nfa, items)
        nfa.add_eps(cur, s0)
        sep_s, sep_e = nfa.literal(",")
        nfa.add_eps(e0, sep_s)
        s1, e1 = _frag(nfa, items)
        nfa.add_eps(sep_e, s1)
        nfa.add_eps(e1, sep_s)       # unbounded repetition
        nfa.add_eps(e0, end)
        nfa.add_eps(e1, end)
        cs, ce = nfa.literal("]")
        nfa.add_eps(end, cs)
        return start, ce
    raise ValueError(f"unsupported schema type: {typ!r}")


class _CharDFA:
    """Determinized char automaton: trans[(state, ch)] -> state,
    `accept` the set of accepting states, state 0 the start."""

    def __init__(self, trans, accept, n_states):
        self.trans = trans
        self.accept = accept
        self.n_states = n_states

    def run(self, state: int, text: str) -> int:
        """Advance `state` over `text`; -1 once any char is illegal."""
        for ch in text:
            state = self.trans.get((state, ch), -1)
            if state < 0:
                return -1
        return state


def _determinize(nfa: _NFA, start: int, end: int) -> _CharDFA:
    def closure(states):
        stack, seen = list(states), set(states)
        while stack:
            s = stack.pop()
            for t in nfa.eps.get(s, ()):
                if t not in seen:
                    seen.add(t)
                    stack.append(t)
        return frozenset(seen)

    s0 = closure({start})
    ids = {s0: 0}
    order = [s0]
    trans: Dict[tuple, int] = {}
    i = 0
    while i < len(order):
        cur = order[i]
        i += 1
        chars = set()
        for s in cur:
            chars.update(nfa.edges.get(s, {}))
        for ch in sorted(chars):
            nxt = set()
            for s in cur:
                nxt.update(nfa.edges.get(s, {}).get(ch, ()))
            nc = closure(nxt)
            if nc not in ids:
                if len(ids) >= _MAX_CHAR_STATES:
                    raise ValueError(
                        "grammar too large: char-DFA exceeds "
                        f"{_MAX_CHAR_STATES} states")
                ids[nc] = len(ids)
                order.append(nc)
            trans[(ids[cur], ch)] = ids[nc]
    accept = {ids[s] for s in order if end in s}
    return _CharDFA(trans, accept, len(ids))


# ---------------------------------------------------------------------------
# token lift
# ---------------------------------------------------------------------------

class TokenDFA:
    """Token-level DFA: `trans` [S, V] int32 (-1 = forbidden),
    `accept` [S] bool (EOS legal there, as a self-loop)."""

    __slots__ = ("trans", "accept", "n_states")

    def __init__(self, trans: np.ndarray, accept: np.ndarray):
        self.trans = trans
        self.accept = accept
        self.n_states = trans.shape[0]


def compile_grammar(grammar: dict, vocab_size: int,
                    eos_token_id: Optional[int]) -> TokenDFA:
    """Compile `{"schema":..., "tokens": {id: text}}` into a TokenDFA.

    Raises ValueError when the schema is outside the subset, the token
    table cannot realize it (start state dead after pruning), or EOS is
    required to terminate but the request has none."""
    schema = grammar["schema"]
    token_strs = grammar["tokens"]
    nfa = _NFA()
    start, end = _frag(nfa, schema)
    cdfa = _determinize(nfa, start, end)

    S = cdfa.n_states
    trans = np.full((S, vocab_size), -1, np.int32)
    for tid, text in token_strs.items():
        tid = int(tid)
        if not (0 <= tid < vocab_size):
            raise ValueError(f"grammar token id {tid} outside vocab "
                             f"[0, {vocab_size})")
        if not text:
            continue                  # empty-text tokens never legal
        for q in range(S):
            r = cdfa.run(q, text)
            if r >= 0:
                trans[q, tid] = r
    accept = np.zeros(S, bool)
    accept[list(cdfa.accept)] = True
    if eos_token_id is not None and 0 <= int(eos_token_id) < vocab_size:
        # EOS legal exactly at acceptance — emitting it finishes the
        # request, the self-loop keeps the mask well-formed afterwards
        trans[accept, int(eos_token_id)] = np.nonzero(accept)[0]
    elif not accept.any():
        raise ValueError("grammar has no accepting state")

    # prune dead states to a fixpoint: a state with NO legal token is a
    # trap (if it accepts without EOS the stream merely stops early at
    # max_new_tokens — still only valid prefixes emitted — but a
    # non-accepting trap would force an illegal token, so transitions
    # into it must die too)
    changed = True
    while changed:
        changed = False
        live = (trans >= 0).any(axis=1) | accept
        for q in range(S):
            row = trans[q]
            bad = (row >= 0) & ~live[np.clip(row, 0, S - 1)]
            if bad.any():
                row[bad] = -1
                changed = True
    if not ((trans[0] >= 0).any() or accept[0]):
        raise ValueError(
            "grammar unsatisfiable with the given token table")
    return TokenDFA(trans, accept)


# ---------------------------------------------------------------------------
# per-slot table + stacked grammar bank
# ---------------------------------------------------------------------------

class SlotSamplingTable:
    """Host-side per-slot sampling state, mirrored into the jitted step
    as batched arrays every dispatch.

    The grammar bank is a FIXED-shape [1 + max_grammars, max_states, V]
    int32 tensor (row 0 = pass-through: one state, every token legal,
    self-loop) so interning a new grammar never changes the step's
    traced shapes — the device copy is cached and invalidated only when
    a compile lands a new row."""

    def __init__(self, num_slots: int, vocab_size: int,
                 max_grammars: int = 8, max_dfa_states: int = 128):
        n = int(num_slots)
        self.vocab_size = int(vocab_size)
        self.max_grammars = int(max_grammars)
        self.max_dfa_states = int(max_dfa_states)
        self.temperature = np.ones(n, np.float32)
        self.top_k = np.zeros(n, np.int32)
        self.top_p = np.ones(n, np.float32)
        self.do_sample = np.zeros(n, bool)
        self.seed = np.zeros(n, np.int32)
        self.dfa_state = np.zeros(n, np.int32)
        self.grammar_id = np.zeros(n, np.int32)
        self.bank = np.full(
            (1 + self.max_grammars, self.max_dfa_states, self.vocab_size),
            -1, np.int32)
        self.bank[0, 0, :] = 0
        self._accept = [np.array([True])]   # per-gid accept vectors
        self._interned: Dict[str, int] = {}
        self._dev_bank = None
        self._dev_args = None   # cached device copies of the per-slot arrays
        self._lock = threading.Lock()

    # -- grammar interning --
    def lookup(self, key: str) -> Optional[int]:
        """gid of an already-interned grammar, else None (the caller
        compiles outside the lock and calls intern)."""
        with self._lock:
            return self._interned.get(key)

    def intern(self, key: str, dfa: TokenDFA) -> int:
        with self._lock:
            gid = self._interned.get(key)
            if gid is not None:
                return gid
            if len(self._interned) >= self.max_grammars:
                raise ValueError(
                    f"grammar bank full ({self.max_grammars}); raise "
                    "max_grammars or retire grammars")
            if dfa.n_states > self.max_dfa_states:
                raise ValueError(
                    f"grammar needs {dfa.n_states} DFA states > "
                    f"max_dfa_states={self.max_dfa_states}")
            gid = len(self._interned) + 1
            self.bank[gid, :dfa.n_states, :] = dfa.trans
            # park unused state rows on a harmless self-loop-free -1
            self._interned[key] = gid
            while len(self._accept) <= gid:
                self._accept.append(None)
            self._accept[gid] = dfa.accept
            self._dev_bank = None
            return gid

    @property
    def grammars_compiled(self) -> int:
        return len(self._interned)

    def accept_of(self, gid: int) -> np.ndarray:
        return self._accept[gid]

    def is_terminal(self, gid: int, state: int) -> bool:
        """True when a constrained slot's grammar is fully emitted and
        has NO legal continuation (an accepting trap with no EOS) —
        the engine finishes the request rather than let the mask go
        empty next step."""
        return gid > 0 and not (self.bank[gid, state] >= 0).any()

    def device_bank(self):
        with self._lock:
            if self._dev_bank is None:
                self._dev_bank = jnp.asarray(self.bank)
            return self._dev_bank

    def device_args(self):
        """Device copies of the 7 per-slot operand arrays, rebuilt only
        when a slot binds/clears or a DFA state commits — the per-step
        host cost of sampling is then just the [N] ctr upload."""
        if self._dev_args is None:
            self._dev_args = (
                jnp.asarray(self.temperature), jnp.asarray(self.top_k),
                jnp.asarray(self.top_p), jnp.asarray(self.do_sample),
                jnp.asarray(self.seed), jnp.asarray(self.dfa_state),
                jnp.asarray(self.grammar_id))
        return self._dev_args

    def set_dfa_state(self, slot: int, state: int):
        """Commit a constrained slot's advanced DFA state (the engine's
        post-step writeback). Mutating `dfa_state` directly would leave
        the device-args cache stale — always go through here."""
        self.dfa_state[slot] = int(state)
        self._dev_args = None

    # -- slot lifecycle --
    def bind(self, slot: int, params: SamplingParams, gid: int = 0,
             dfa_state: int = 0):
        p = params or GREEDY
        self.temperature[slot] = float(p.temperature)
        self.top_k[slot] = int(p.top_k)
        self.top_p[slot] = float(p.top_p)
        self.do_sample[slot] = bool(p.do_sample)
        self.seed[slot] = 0 if p.seed is None else int(p.seed)
        self.grammar_id[slot] = int(gid)
        self.dfa_state[slot] = int(dfa_state)
        self._dev_args = None

    def clear(self, slot: int):
        self.bind(slot, GREEDY)

    def mode_counts(self, active_slots) -> Dict[str, int]:
        """Per-mode occupancy over the given active slot ids."""
        out = {"greedy": 0, "sampled": 0, "constrained": 0}
        for s in active_slots:
            if self.grammar_id[s] > 0:
                out["constrained"] += 1
            elif self.do_sample[s]:
                out["sampled"] += 1
            else:
                out["greedy"] += 1
        return out


# ---------------------------------------------------------------------------
# in-step selection (pure; traced inside the engine's one jitted step)
# ---------------------------------------------------------------------------

def lane_key(seed, index):
    """The seeding contract, exposed for tests/oracles: the key that
    draws stream token `index` of a request seeded `seed`. The base key is
    made here, not at import: a module-level `PRNGKey(0)` initialises the
    backend, and a process that merely imports the package (a launcher, a
    DataLoader worker) would then hold the chip its children need."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), seed), index)


def select_tokens(logits, adv, temperature, top_k, top_p, do_sample,
                  seed, ctr, dfa_state, grammar_id, bank):
    """[N, C, V] logits -> ([N, C] tokens, [N] new DFA states).

    `ctr[n]` is the stream index of row n's COLUMN 0 (decode rows:
    sample_offset + emitted; prefill rows: sample_offset - (adv-1), so
    the emission column adv-1 lands exactly on sample_offset — earlier
    columns' draws are discarded with their logits). The grammar mask
    of the CURRENT state applies to every column: constrained rows
    never speculate, so their single emission column is the only one
    consumed; unconstrained rows ride the pass-through row of `bank`.

    The bank is read only by a step that holds a constrained row: the
    `[N, V]` mask sits in the taken branch of one conditional on
    `grammar_id` itself (`_select_token`'s pattern: decided on the device
    from the operands, one executable whatever the mix). Every row on the
    pass-through row 0 is allowed every token, so the other branch is the
    float32 logits, which is what the mask gives bit for bit. Outside a
    conditional the gather runs in every step, and XLA's TPU gather over
    `[G, S, V]` splits a vocabulary wider than 32,768 and copies the WHOLE
    bank, piece by piece, to pick N of its rows: 604 MB read and written a
    step at V = 131,072, for rows that are all on row 0. (Slicing the N
    rows in a loop instead holds no copy, but a trip costs 19 us on a v5e:
    4.8 ms for 256 rows against the copy's 2.5.)
    """
    N, C, V = logits.shape

    def mask(lg):
        allowed = bank[grammar_id, dfa_state] >= 0      # [N, V]
        return jnp.where(allowed[:, None, :], lg, -1e30)

    masked = jax.lax.cond(jnp.any(grammar_id > 0), mask, lambda lg: lg,
                          logits.astype(jnp.float32))

    cols = jnp.arange(C, dtype=jnp.int32)
    keys = jax.vmap(
        lambda s, c0: jax.vmap(lambda t: lane_key(s, c0 + t))(cols)
    )(seed, ctr)                                        # [N, C, 2]

    flat = masked.reshape(N * C, V)
    rep = lambda a: jnp.repeat(a, C)
    toks = _select_token(
        flat, rep(jnp.asarray(do_sample, bool)),
        rep(temperature), rep(top_k), keys.reshape(N * C, 2),
        rep(top_p)).reshape(N, C)

    emit_col = jnp.maximum(adv - 1, 0)
    tok_e = jnp.take_along_axis(toks, emit_col[:, None], axis=1)[:, 0]
    stepped = bank[grammar_id, dfa_state, tok_e]
    new_state = jnp.where((grammar_id > 0) & (adv > 0),
                          jnp.maximum(stepped, 0), dfa_state)
    return toks, new_state


def select_next(logits, temperature, top_k, top_p, do_sample, seed, ctr):
    """Width-1 selection for the draft propose scan: [N, V] logits ->
    [N] tokens drawn on the SAME lanes the target verify will use, so
    a draft that agrees with the target proposes exactly the target's
    coin-fixed draw (seeded-replay acceptance; module docstring)."""
    keys = jax.vmap(lane_key)(seed, ctr)
    return _select_token(logits, jnp.asarray(do_sample, bool),
                         temperature, top_k, keys, top_p)
