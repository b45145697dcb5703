"""Job kind `serve-sessions-long`: `serve-closed-loop` for clients that are
*sessions*: each keeps one conversation of tens of thousands of tokens, and
every request is a turn of it, admitted onto what the prefix cache holds of
the turns before.

The engine is built as `serve_closed_loop` builds it (`_engine_config`:
slots and context from the traffic file, every other field the program's
default, so the prefix cache is on), the load runs on `serve_closed_loop`'s
one client thread (`_Load`, with a `_send` of its own), the window, the
checks and every end-to-end number are that job's. The configuration's
`leaf_seeding` and the cell's `limits` are read as
`serve_closed_loop_calibrated` reads them. What differs:

- **Set-up fills the cache.** Every session's history (`history_tokens`,
  one length a client from one seeded stream) is prefilled through
  `engine.submit` with one output token, all at once, before anything is
  compared or measured: a deployment's replica holds its sessions.
- **A turn** is the session's context so far (history, earlier turns' new
  tokens, earlier answers) plus `prompt_tokens` new ones; the answer is
  `output_tokens` long; the context then grows by both. A session whose
  next turn, with the longest answer, would pass the slot's
  `context_tokens` starts over from its history. Lengths come from one
  seeded stream in the order the turns are sent, ids from another.
- **The check** is the first turn of four sessions (the shortest history,
  the sixth, the eleventh and the longest of the sorted sixteen), its new
  tokens from the check's own stream, `check_output_tokens` answers;
  the reference scores one request at a time, each at the longest its
  session's stratum allows in whole query blocks (so the same four
  compilations whatever the seed), the head applied to the scored positions
  alone. The sessions themselves start from their
  histories afterwards: the check's turns stay behind in the cache as side
  branches, which the first real turn's admission has to clear out of its
  row.
- **The program's counters at the window's edges** (`LLMMetrics.counters`:
  the sparse layers' selected and resident keys, the prefix cache's hit and
  looked-up tokens, `full_kv_tokens`, `unified_steps`; always on) are left
  in the result's `counters` as `<name>_window`, with the pool's bytes by
  kind. A program without a counter leaves the key out.

`python3 -m benchmark.jobs.glm_dsa_controls` takes the readings the cell's
limits lie between.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import numpy as np

from .. import cells, harness, traffic as T
from ..harness import say
from ..trace.reduce import median
from . import serve_closed_loop as base
from . import serve_closed_loop_calibrated as calibrated

END_TO_END = {k: v for k, v in base.END_TO_END.items() if k != "ttft_p50_ms"}
CHECK_SESSIONS = (0, 5, 10, 15)     # of the sessions sorted by history
PAD = 1024                          # the reference's query block
COUNTERS = ("sparse_keys_selected", "sparse_keys_resident",
            "index_layers_full", "index_layers_shared",
            "prefix_hit_tokens", "prefix_lookup_tokens", "full_kv_tokens",
            "unified_steps")


# ---- the sessions ----

def histories(traffic: dict, vocab: int, seed: int) -> list:
    """One history a client: seeded ids, lengths from `history_tokens`."""
    rng = np.random.default_rng([seed, 2000])
    lengths = T.Lengths(traffic["history_tokens"], rng)
    toks = T.Tokens(traffic.get("prompt_ids", {"dist": "uniform"}), vocab,
                    rng, first=1)
    return [toks.draw((lengths.next(),))
            for _ in range(int(traffic["clients"]))]


class _Sessions(base._Load):
    """`serve_closed_loop._Load` whose client c sends the turns of session
    c. A turn is built when it is sent, from the answer of the one
    before."""

    def __init__(self, engine, traffic, vocab, seed, offsets, history):
        super().__init__(engine, None, offsets)
        rng = np.random.default_rng([seed, 1000])
        self.new = T.Lengths(traffic["prompt_tokens"], rng)
        self.out = T.Lengths(traffic["output_tokens"], rng)
        self.toks = T.Tokens(traffic.get("prompt_ids", {"dist": "uniform"}),
                             vocab, rng, first=1)
        self.room = int(traffic["context_tokens"]) \
            - int(traffic["output_tokens"]["hi"])
        self.history = history
        self.context = list(history)
        self.last = [None] * len(history)
        self.restarts = 0

    def _send(self, client: int, due: float) -> base._Request:
        last = self.last[client]
        if last is not None:     # the turn before, with its answer
            self.context[client] = np.concatenate(
                [last.prompt,
                 np.asarray(last.handle.tokens_so_far(), np.int32)])
        new = self.toks.draw((self.new.next(),))
        if len(self.context[client]) + len(new) > self.room:
            self.context[client] = self.history[client]
            self.restarts += 1
        prompt = np.concatenate([self.context[client], new])
        max_new = self.out.next()
        handle = self.engine.submit(prompt, max_new_tokens=max_new,
                                    logprobs=True)
        req = base._Request(client, prompt, max_new, due, handle)
        self.requests.append(req)
        self.last[client] = req
        return req


def fill_cache(engine, history: list):
    """Every session's history through the engine, one output token."""
    t = time.perf_counter()
    handles = [engine.submit(h, max_new_tokens=1) for h in history]
    for h in handles:
        h.result(timeout=harness.RUN_LIMIT_S)
    say(f"histories prefilled: {[len(h) for h in history]} tokens in "
        f"{time.perf_counter() - t:.1f}s, "
        f"{engine.decode_iterations + engine.prefill_dispatches} steps")


# ---- the check ----

def check_turns(traffic: dict, vocab: int, seed: int, history: list) -> list:
    """(prompt, output tokens) of the check: the first turn of the sessions
    `CHECK_SESSIONS` (by sorted history), new tokens at the middle of each
    quantile of `prompt_tokens`, ids from the check's own stream."""
    rng = np.random.default_rng([seed, 999])
    order = np.argsort([len(h) for h in history], kind="stable")
    n = len(CHECK_SESSIONS)
    new = T._quantile(traffic["prompt_tokens"], (np.arange(n) + 0.5) / n)
    toks = T.Tokens(traffic.get("prompt_ids", {"dist": "uniform"}), vocab,
                    rng, first=1)
    cap = int(traffic.get("check_output_tokens",
                          traffic["output_tokens"]["hi"]))
    return [(np.concatenate([history[order[i % len(order)]],
                             toks.draw((int(k),))]), cap)
            for i, k in zip(CHECK_SESSIONS, new)]


@functools.lru_cache(maxsize=None)
def _score_fn(module: str, frozen_config: str):
    import jax
    import jax.numpy as jnp
    ref, config = sys.modules[module], json.loads(frozen_config)

    def score(weights, ids, at, nxt):
        """ids [S], the scored positions `at [n]` and the tokens that
        follow them `nxt [n]` -> (log-softmax of the logits at `at` on
        `nxt` [n], best logit minus `nxt`'s logit [n])."""
        x, head = ref.hidden_and_head(weights, ids[None], config)
        with jax.default_matmul_precision("highest"):
            lg = x[0][at] @ head
        on = jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
        lp = jnp.take_along_axis(jax.nn.log_softmax(lg, -1), nxt[:, None],
                                 -1)[:, 0]
        return lp, jnp.max(lg, -1) - on

    return jax.jit(score)


def padded_lengths(traffic: dict) -> list:
    """The length each check request is scored at: the longest it can be
    whatever the seed (its session's stratum of `history_tokens` ends
    there), in whole query blocks of the reference. One compilation a
    request, the same in every run; the shortest is not paid for at the
    longest's length."""
    strata = int(traffic["history_tokens"].get("strata", 1))
    n = len(CHECK_SESSIONS)
    new = T._quantile(traffic["prompt_tokens"], (np.arange(n) + 0.5) / n)
    cap = int(traffic.get("check_output_tokens",
                          traffic["output_tokens"]["hi"]))
    ends = T._quantile(traffic["history_tokens"],
                       (np.asarray(CHECK_SESSIONS) % strata + 1.0) / strata)
    return [int(-(-(int(e) + int(k) + cap) // PAD) * PAD)
            for e, k in zip(ends, new)]


def check_against_reference(ctx, engine, weights, checks, turns):
    """The check's turns through `engine` (anything with `submit`), then
    teacher-forced through the reference one at a time: `serve_closed_loop
    ._check_against_reference`'s two comparisons under its tolerances."""
    import jax.numpy as jnp
    config, traffic = ctx.config, ctx.traffic
    t = time.perf_counter()
    sent = [(p, engine.submit(p, max_new_tokens=n, logprobs=True))
            for p, n in turns]
    outs = [np.asarray(h.result(timeout=harness.RUN_LIMIT_S))
            for _, h in sent]
    say(f"check turns: prompts {[len(p) for p, _ in sent]}, outputs "
        f"{[len(o) for o in outs]} tokens, {time.perf_counter() - t:.1f}s")
    ref = cells.reference_module(config)
    fn = _score_fn(ref.__name__, json.dumps(config, sort_keys=True))
    widths = padded_lengths(traffic)
    t = time.perf_counter()
    diffs, margins = [], []
    for (prompt, handle), out, width in zip(sent, outs, widths):
        ids = np.zeros((max(width, len(prompt) + len(out)),), np.int32)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(out)] = out
        # logits at position p score token p + 1
        at = len(prompt) - 1 + np.arange(len(out), dtype=np.int32)
        lp, margin = fn(weights, jnp.asarray(ids), jnp.asarray(at),
                        jnp.asarray(out, jnp.int32))
        got = np.asarray(handle.logprobs_so_far(), np.float64)
        diffs.append(np.abs(got - np.asarray(lp)))
        margins.append(np.asarray(margin))
    diffs, margins = np.concatenate(diffs), np.concatenate(margins)
    tol = base.TOLERANCE[config["dtype"]]
    say(f"reference forward over {widths} in "
        f"{time.perf_counter() - t:.1f}s")
    checks.add("engine log-probabilities equal the reference's",
               bool(np.all(np.isfinite(diffs))) and diffs.size > 0
               and float(diffs.mean()) <= tol["mean"]
               and float(diffs.max()) <= tol["max"],
               f"{diffs.size} tokens: mean |diff| {diffs.mean():.2e} "
               f"(tolerance {tol['mean']:g}), max {diffs.max():.2e} "
               f"({tol['max']:g})")
    checks.add("every greedy token is the reference's best, or ties it",
               float(margins.max()) <= tol["margin"],
               f"largest margin below the reference's best logit "
               f"{margins.max():.2e} (tolerance {tol['margin']:g}); "
               f"{int((margins == 0).sum())} of {margins.size} exact")


# ---- the run ----

def _snapshot(engine) -> dict:
    snap = engine.metrics.snapshot()
    return {k: snap[k] for k in COUNTERS if k in snap}


def run(ctx: harness.Context) -> dict:
    with calibrated._cell_rules(ctx):
        return _run(ctx)


def _run(ctx: harness.Context) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu import serving

    traffic, config = ctx.traffic, ctx.config
    if ctx.cell["chips"] != 1:
        raise cells.CellError("one engine drives one chip")
    checks = harness.Checks()
    paddle.seed(ctx.seed)
    model, weights = harness.build_model(ctx)
    model.eval()
    engine = serving.LLMEngine(model, base._engine_config(traffic)).start()
    load = None
    try:
        history = histories(traffic, config["vocab_size"], ctx.seed)
        fill_cache(engine, history)
        check_against_reference(
            ctx, engine, weights, checks,
            check_turns(traffic, config["vocab_size"], ctx.seed, history))
        steps0 = engine.decode_iterations + engine.prefill_dispatches

        rng = np.random.default_rng([ctx.seed, 99])
        offsets = np.sort(rng.random(len(history))) \
            * float(traffic["ramp_seconds"])
        offsets[0] = 0.0
        load = _Sessions(engine, traffic, config["vocab_size"], ctx.seed,
                         offsets, history).start()
        started = load.all_in_flight.wait(timeout=harness.RUN_LIMIT_S)
        if not started or load.error:
            raise RuntimeError(f"clients did not start: {load.error!r}")

        window = harness.Window(ctx)
        with window:
            edge0 = _snapshot(engine)
            before = (engine.decode_iterations + engine.prefill_dispatches,
                      engine.prefill_tokens)
            deadline = window.t0 + ctx.window_seconds
            while load.error is None:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                time.sleep(min(0.5, left))
                window.sample_memory()    # mid-step, as often as not
            after = (engine.decode_iterations + engine.prefill_dispatches,
                     engine.prefill_tokens)
            edge1 = _snapshot(engine)
    finally:
        if load is not None:
            load.stop()
        engine.stop(drain=False, timeout=30)
    if load.error is not None:
        raise load.error

    t0, t1 = window.t0, window.t1
    inside = load.requests
    out_tokens = sum(n for t, n in load.stamps if t0 <= t < t1)
    ttft = [(r.first - r.due) * 1e3 for r in inside
            if r.first is not None and t0 <= r.first < t1]
    tpot = [(r.last - r.first) / (r.seen - 1) * 1e3 for r in inside
            if r.ended is not None and t0 <= r.ended < t1
            and r.error is None and r.seen > 1]
    attempted = sum(1 for r in inside if t0 <= r.due < t1)
    failed = sum(1 for r in inside
                 if r.error is not None and t0 <= r.ended < t1)
    short = sum(1 for r in inside if r.ended is not None
                and r.error is None and r.seen != r.max_new)
    steps, prefilled = after[0] - before[0], after[1] - before[1]
    cfg = engine.config
    kv = [(k, a) for t, n, k, a in load.kv_samples if t0 <= t < t1]
    say(f"window {window.seconds:.3f}s: {attempted} turns due, "
        f"{len(tpot)} finished, {failed} failed; {out_tokens} output tokens,"
        f" {prefilled} prompt tokens prefilled, {steps} unified steps "
        f"({window.seconds / max(steps, 1) * 1e3:.1f} ms per step, "
        f"{steps0} before the clients); first tokens {len(ttft)}; "
        f"{load.restarts} session restarts; ttft p50 "
        f"{median(ttft) or 0:.1f} ms (not a metric of this cell)")
    checks.add("requests finished inside the window, none failed",
               failed == 0 and len(tpot) > 0 and len(ttft) > 0,
               f"{len(tpot)} finished, {len(ttft)} first tokens, "
               f"{failed} failed")
    checks.add("every finished request has the tokens it asked for",
               short == 0, f"{short} short")
    checks.add("no compilation inside the window", window.compilations == 0,
               f"{window.compilations} compilation(s)")
    harness.check_kernel_paths(ctx, checks)
    counters = {
        "output_tokens": out_tokens, "prefill_tokens": prefilled,
        "steps": steps, "slots": cfg.num_slots,
        "prefill_chunk": cfg.prefill_chunk, "block_len": cfg.block_len,
        "n_blocks": cfg.n_blocks,
        "kv_tokens_per_step": (float(np.mean([k for k, _ in kv]))
                               if kv else None),
        "active_rows_per_step": (float(np.mean([a for _, a in kv]))
                                 if kv else None),
        "ttft_samples": len(ttft), "tpot_samples": len(tpot),
        "session_restarts": load.restarts,
        "main_module": "jit_step"}
    counters.update({f"{k}_window": edge1[k] - edge0[k] for k in edge1
                     if k in edge0})
    pool = getattr(engine, "pool", None)
    if hasattr(pool, "kv_bytes"):
        counters["kv_pool_bytes"] = pool.kv_bytes()
    return {
        "checks": checks, "window": window,
        "attempted": attempted, "failed": failed,
        "end_to_end": {
            "serve_out_tokens_per_s": out_tokens / window.seconds,
            "ttft_p50_ms": median(ttft), "tpot_p50_ms": median(tpot),
            "setup_s": window.setup_s},
        "counters": counters,
    }
