"""Job kind `serve-closed-loop`: `LLMEngine` in process under a fixed number
of clients, each of which sends its next request when its last one ends.

The engine is built as a user builds it: `LLMEngineConfig` with the slots
and the context per slot the traffic file gives, the fields the mix's
`engine` object names (say, a prefix cache switched off where nothing is
shared) and every other field at the program's default, read at run time
and printed, so that a PR which improves a default shows. No HTTP, no
`economics`, no `observatory`: arming either changes the engine's step.

One thread drives all clients and is the only clock: every millisecond it
reads each request's `tokens_so_far()` and stamps the tokens that are new,
so every time is the client's. A request is due the moment its client's
last one ended. Requests come from one seeded stream (`benchmark/
traffic.py`) in the order they are sent, so a run's mix of lengths is the
same whatever the seed and however the clients interleave.

Order of a run: build, four seeded requests at once (they compile or load
the step and are then compared with the plain reference), the clients start
at seeded offsets over `ramp_seconds`, the window opens when all are in
flight, and closes after `--seconds`; the engine is then stopped without
draining. A request in flight at either edge counts its tokens inside the
window and nothing else.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

import numpy as np

from .. import cells, harness, traffic as T
from ..harness import say
from ..reference import common as ref_common
from ..trace.reduce import median

END_TO_END = {"serve_out_tokens_per_s": "tokens/s", "ttft_p50_ms": "ms",
              "tpot_p50_ms": "ms", "setup_s": "s"}
POLL_S = 0.001
CHECK_REQUESTS = 4

# Engine against the plain reference, on the check requests: the engine's
# per-token log-probabilities (bf16 weights and activations, float32
# softmax, through the paged cache in chunks and then token by token)
# against the reference's (float32, highest matmul precision, one full
# forward pass over prompt + emitted tokens, same bf16-rounded weights).
# With N(0, 0.02) weights the logits have a spread of ~1.3 and |logit| < 8;
# the head's bf16 output alone is rounded to 8 bits (1.6e-2 at |x| in
# [2, 4), 3.1e-2 in [4, 8)), and bf16 activations add about as much again.
# The greedy token is the largest logit, |x| in [4, 8), where one bf16 step
# is 3.1e-2. Measured on the chip, PR 22 (Mistral-7B-v0.3 at 8 layers, ten
# runs over both cells, 20-300 tokens a run): mean |diff| 2.4e-2 to 3.2e-2,
# largest 1.4e-1; largest margin 1.7e-1, 91-100% of tokens exactly the
# reference's best.
# Mean |diff| is held to 6e-2 and the largest to 3e-1 (twice what was
# seen); bf16 accumulation over 4,096-term dot products (relative error
# ~2^-8 * sqrt(4096) = 25%), an 8-bit type, a wrong position, a dropped KV
# head or a stale page moves the log-probabilities by 0.5 to O(1). `margin`
# is how far, in the reference's logits, the engine's greedy token may lie
# below the reference's best: a near-tie flips when two logits are each
# rounded by up to 3e-2 on top of the activations' error; a wrong token is
# ~4 sigma = 5 away. In float32 (the CPU test cells) everything agrees to
# 1e-4.
TOLERANCE = {
    "bfloat16": {"mean": 6e-2, "max": 3e-1, "margin": 4e-1},
    "float32": {"mean": 1e-4, "max": 1e-3, "margin": 1e-3},
}


@dataclasses.dataclass
class _Request:
    client: int
    prompt: np.ndarray
    max_new: int
    due: float
    handle: object
    seen: int = 0
    first: Optional[float] = None
    last: Optional[float] = None
    ended: Optional[float] = None
    error: Optional[str] = None


class _Load:
    """All clients on one thread. `stamps` holds (time, new tokens) for
    every poll that saw new tokens; `requests` every request ever sent."""

    def __init__(self, engine, stream, offsets):
        self.engine, self.stream, self.offsets = engine, stream, offsets
        self.requests, self.stamps, self.kv_samples = [], [], []
        self.all_in_flight = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="bench-clients")
        self.error: Optional[BaseException] = None

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        """Idempotent; the thread ends within one poll."""
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("load generator did not stop")

    def _send(self, client: int, due: float) -> _Request:
        prompt, max_new = next(self.stream)
        handle = self.engine.submit(prompt, max_new_tokens=max_new,
                                    logprobs=True)
        req = _Request(client, prompt, max_new, due, handle)
        self.requests.append(req)
        return req

    def _steps(self) -> int:
        e = self.engine
        return e.decode_iterations + e.prefill_dispatches

    def _main(self):
        try:
            t_begin = time.perf_counter()
            live = [None] * len(self.offsets)
            steps_seen = self._steps()
            while not self._stop.is_set():
                now = time.perf_counter()
                for c, req in enumerate(live):
                    if req is None:
                        if now - t_begin >= self.offsets[c]:
                            live[c] = self._send(c, now)
                        continue
                    # read `done` first: every token precedes it
                    done = req.handle.future.done()
                    n = len(req.handle.tokens_so_far())
                    if n > req.seen:
                        self.stamps.append((now, n - req.seen))
                        if req.first is None:
                            req.first = now
                        req.last, req.seen = now, n
                    if done:
                        exc = req.handle.future.exception()
                        req.error = None if exc is None else repr(exc)
                        req.ended = now
                        live[c] = self._send(c, now)
                if all(r is not None for r in live):
                    self.all_in_flight.set()
                steps = self._steps()
                if steps != steps_seen:
                    # tokens resident in the cache as of this step: what
                    # the paged kernel had to read (for its roofline)
                    pool = self.engine.pool
                    self.kv_samples.append(
                        (now, steps - steps_seen,
                         int(pool.lengths[pool.active].sum()),
                         int(pool.active.sum())))
                    steps_seen = steps
                time.sleep(POLL_S)
        except BaseException as e:   # reported by the job, never swallowed
            self.error = e
            self.all_in_flight.set()


def _engine_config(traffic: dict):
    """Slots, pages per slot, the output cap and the queue's depth follow
    from the mix; `"engine": {field: value}` in the traffic file sets
    further `LLMEngineConfig` fields for that mix (data, so a later PR's
    cell can differ in one without code); every other field is the
    program's default, read at run time."""
    from paddle_tpu.serving import LLMEngineConfig
    defaults = dataclasses.asdict(LLMEngineConfig())
    overrides = dict(traffic.get("engine", {}))
    block_len = int(overrides.get("block_len", defaults["block_len"]))
    sized = dict(
        num_slots=int(traffic["slots"]),
        n_blocks=int(traffic["context_tokens"]) // block_len,
        max_new_tokens=int(traffic["output_tokens"]["hi"]),
        # every client may have one request waiting for its slot
        max_queue_depth=max(defaults["max_queue_depth"],
                            int(traffic["clients"])))
    bad = sorted(k for k in overrides if k not in defaults or k in sized)
    if bad:
        raise cells.CellError(
            f"traffic's engine fields {bad}: not fields of LLMEngineConfig, "
            f"or fields the mix's sizes already set ({sorted(sized)})")
    cfg = LLMEngineConfig(**sized, **overrides)
    now = dataclasses.asdict(cfg)
    changed = {k: v for k, v in now.items() if defaults[k] != v}
    at_default = {k: now[k] for k in ("block_len", "prefill_chunk",
                                      "enable_prefix_cache", "economics",
                                      "observatory") if k not in overrides}
    say(f"LLMEngineConfig: set by the cell {changed}; program defaults read "
        f"at run time: {at_default}; max_queue_depth {cfg.max_queue_depth} "
        "(the default, or the clients if more)")
    return cfg


def _check_against_reference(ctx, engine, weights, checks):
    """Four seeded requests, all at once (the first dispatch compiles or
    loads the unified step), then teacher-forced through the reference."""
    config, traffic = ctx.config, ctx.traffic
    t = time.perf_counter()
    sent = []
    for prompt, max_new in T.check_requests(traffic, config["vocab_size"],
                                            ctx.seed, CHECK_REQUESTS):
        sent.append((prompt, engine.submit(prompt, max_new_tokens=max_new,
                                           logprobs=True)))
    outs = [np.asarray(h.result(timeout=harness.RUN_LIMIT_S))
            for _, h in sent]
    say(f"check requests: prompts {[len(p) for p, _ in sent]}, outputs "
        f"{[len(o) for o in outs]} tokens, {time.perf_counter() - t:.1f}s "
        "(includes compiling or loading the unified step)")

    hi = int(traffic["prompt_tokens"]["hi"]) \
        + int(traffic["output_tokens"]["hi"])
    width = -(-hi // 128) * 128          # one padded shape per cell
    ids = np.zeros((len(sent), width), np.int32)
    for i, ((prompt, _), out) in enumerate(zip(sent, outs)):
        ids[i, :len(prompt)] = prompt
        ids[i, len(prompt):len(prompt) + len(out)] = out
    t = time.perf_counter()
    lp_ref, margin = ref_common.next_token_scores(
        cells.reference_module(config).logits, weights, ids, config)
    lp_ref, margin = np.asarray(lp_ref), np.asarray(margin)
    diffs, margins = [], []
    for i, ((prompt, handle), out) in enumerate(zip(sent, outs)):
        # logits at position p score token p + 1
        sl = slice(len(prompt) - 1, len(prompt) - 1 + len(out))
        got = np.asarray(handle.logprobs_so_far(), np.float64)
        diffs.append(np.abs(got - lp_ref[i, sl]))
        margins.append(margin[i, sl])
    diffs, margins = np.concatenate(diffs), np.concatenate(margins)
    tol = TOLERANCE[config["dtype"]]
    say(f"reference forward over [{len(sent)}, {width}] in "
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


def run(ctx: harness.Context) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu import serving

    traffic, config = ctx.traffic, ctx.config
    if ctx.cell["chips"] != 1:
        raise cells.CellError("one engine drives one chip")
    checks = harness.Checks()
    paddle.seed(ctx.seed)
    model, weights = harness.build_model(ctx)
    model.eval()
    engine = serving.LLMEngine(model, _engine_config(traffic)).start()
    load = None
    try:
        _check_against_reference(ctx, engine, weights, checks)
        steps0 = engine.decode_iterations + engine.prefill_dispatches

        n_clients = int(traffic["clients"])
        rng = np.random.default_rng([ctx.seed, 99])
        offsets = np.sort(rng.random(n_clients)) \
            * float(traffic["ramp_seconds"])
        offsets[0] = 0.0
        stream = T.request_stream(traffic, config["vocab_size"], ctx.seed)
        load = _Load(engine, stream, offsets).start()
        started = load.all_in_flight.wait(timeout=harness.RUN_LIMIT_S)
        if not started or load.error:
            raise RuntimeError(f"clients did not start: {load.error!r}")

        window = harness.Window(ctx)
        with window:
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
    finally:
        if load is not None:
            load.stop()
        engine.stop(drain=False, timeout=30)
    if load.error is not None:
        raise load.error

    t0, t1 = window.t0, window.t1
    inside = load.requests           # every request ever sent
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
    say(f"window {window.seconds:.3f}s: {attempted} requests due, "
        f"{len(tpot)} finished, {failed} failed; {out_tokens} output tokens,"
        f" {prefilled} prompt tokens prefilled, {steps} unified steps "
        f"({window.seconds / max(steps, 1) * 1e3:.1f} ms per step, "
        f"{steps0} before the clients); first tokens {len(ttft)}")
    checks.add("requests finished inside the window, none failed",
               failed == 0 and len(tpot) > 0 and len(ttft) > 0,
               f"{len(tpot)} finished, {len(ttft)} first tokens, "
               f"{failed} failed")
    checks.add("every finished request has the tokens it asked for",
               short == 0, f"{short} short")
    checks.add("no compilation inside the window", window.compilations == 0,
               f"{window.compilations} compilation(s)")
    harness.check_kernel_paths(ctx, checks)
    return {
        "checks": checks, "window": window,
        "attempted": attempted, "failed": failed,
        "end_to_end": {
            "serve_out_tokens_per_s": out_tokens / window.seconds,
            "ttft_p50_ms": median(ttft), "tpot_p50_ms": median(tpot),
            "setup_s": window.setup_s},
        "counters": {
            "output_tokens": out_tokens, "prefill_tokens": prefilled,
            "steps": steps, "slots": cfg.num_slots,
            "prefill_chunk": cfg.prefill_chunk, "block_len": cfg.block_len,
            "n_blocks": cfg.n_blocks,
            "kv_tokens_per_step": (float(np.mean([k for k, _ in kv]))
                                   if kv else None),
            "active_rows_per_step": (float(np.mean([a for _, a in kv]))
                                     if kv else None),
            "ttft_samples": len(ttft), "tpot_samples": len(tpot),
            "main_module": "jit_step"},
    }
