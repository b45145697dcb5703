"""Threaded stdlib-HTTP serving front end over a BatchingEngine.

Same idiom as the fleet KV server (distributed/fleet/utils/http_server.py —
ThreadingHTTPServer + BaseHTTPRequestHandler, whose hardened
`read_request_body` this module reuses):

    POST /predict   {"inputs": [[...], ...], "deadline_ms": 50}
                    -> 200 {"outputs": [...]}; 503 rejected (queue full /
                    draining); 504 deadline expired before dispatch
    POST /generate  {"input_ids": [...], "max_new_tokens": 32,
                    "eos_token_id": 2, "deadline_ms": 500,
                    "slo": "interactive"|"batch"|"best_effort",
                    "temperature": 0.8, "top_k": 40, "top_p": 0.95,
                    "seed": 1234, "grammar": {"schema": ...,
                    "tokens": {...}}}   # sampling fields optional
                    -> 200 {"tokens": [...], "ttft_ms": ...} from the
                    continuous-batching LLMEngine (serving/llm/); same
                    503/504 admission-control mapping. An optional
                    X-Tenant-Id header (1-64 chars [A-Za-z0-9._-],
                    malformed -> 400) selects the tenant: per-tenant
                    fair scheduling, quota (429 + Retry-After on
                    "tenant_quota"), metrics labels, and a private
                    prefix-cache namespace (ISSUE 8)
    GET  /healthz   -> 200 {"status": "ok"|"draining"};
                       503 {"status": "broken"} once an engine's circuit
                       breaker opens (ISSUE 6)
    GET  /metrics   -> 200 Prometheus text exposition (serving/metrics.py)
    GET  /debug/requests        -> recently finished request ids (the
                                   engines' bounded timeline LRUs)
    GET  /debug/requests/<rid>  -> one finished request's structured
                                   timeline (phases, marks, events)
    GET  /debug/flightrecorder  -> the process-global black-box ring
                                   (paddle_tpu.obs.flight_recorder)
    GET  /debug/costs           -> per-engine serving economics (ISSUE
                                   11): pump phase tiling, token
                                   efficiency, per-tenant / per-SLO-class
                                   device-seconds, SLO burn-rate state
                                   (null for engines without
                                   economics=True)

Request tracing (ISSUE 9): every /predict and /generate request gets a
request id — ingested from a W3C `traceparent` header when present, else
generated — echoed back as "rid" in the response body. Sending
`X-PDTPU-Trace: 1` additionally records a structured timeline (admission
-> queue wait -> prefill chunks -> decode -> finish) returned inline as
"trace" and retrievable later from /debug/requests/<rid>.

Backpressure (ISSUE 6): overload rejections — queue full, token budget
exhausted, or the request itself shed for a higher class — map to HTTP
429 with a Retry-After header, telling well-behaved clients to back off;
503 stays reserved for "this process is going away" (draining, circuit
breaker open). When an engine's circuit breaker trips, the server flips
/healthz to 503 {"status": "broken"} and starts a drain on its own
thread, so an external supervisor observes unhealthy -> drained -> exit
and replaces the process.

Graceful drain mirrors the ResilientTrainer preemption contract
(distributed/resilient.py): SIGTERM/SIGINT → stop admissions (new requests
get 503), flush every in-flight batch through the engine, let the attached
handler threads finish writing their responses, then exit 0 — no accepted
request is ever dropped. A `final_metrics_path` snapshot is written on the
way out so an external supervisor (or the drain test) can reconcile the
served totals against the replayed trace.

    python -m paddle_tpu.serving.server --model /path/prefix --port 8000
"""
from __future__ import annotations

import json
import logging
import os
import re
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from ..distributed.fleet.utils.http_server import read_request_body
from ..obs.flight_recorder import flight_recorder
from ..obs.trace import ingest_traceparent, new_request_id
from .engine import (BatchingEngine, DeadlineExceededError, EngineConfig,
                     RejectedError)
from .llm.sampling import SamplingParams
from .metrics import SLO_CLASSES

# RejectedError reasons that mean "try again later" (HTTP 429 +
# Retry-After) rather than "this process is going away" (503)
_RETRYABLE_REJECTS = frozenset({"queue_full", "token_budget", "shed",
                                "tenant_quota"})

# X-Tenant-Id values the LLM routes accept (ISSUE 8): tenant ids become
# metric labels and prefix-cache namespace keys, so they are restricted
# to a safe charset and bounded length; anything else is a 400
_TENANT_ID_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]{0,63}$")


def _decode_inputs(payload: dict):
    """JSON request body -> list of np arrays (leading batch dim). Each
    entry is either a nested list (float32) or {"data": ..., "dtype": ...}."""
    inputs = payload.get("inputs")
    if inputs is None:
        raise ValueError('request body needs an "inputs" list')
    arrays = []
    for entry in inputs:
        if isinstance(entry, dict):
            arrays.append(np.asarray(entry["data"],
                                     dtype=entry.get("dtype", "float32")))
        else:
            arrays.append(np.asarray(entry, dtype=np.float32))
    return arrays


class ServingServer:
    """HTTP front end + drain orchestration around a BatchingEngine
    (stateless /predict) and/or an LLMEngine (autoregressive /generate,
    ISSUE 5). At least one engine must be attached; each route 404s when
    its engine is absent. Both engines share the SIGTERM drain contract:
    stop admissions, finish every admitted request/sequence, snapshot
    final metrics, exit 0."""

    def __init__(self, engine: Optional[BatchingEngine] = None,
                 host: str = "127.0.0.1",
                 port: int = 0, final_metrics_path: Optional[str] = None,
                 request_timeout_s: float = 60.0, llm_engine=None):
        if engine is None and llm_engine is None:
            raise ValueError(
                "ServingServer needs a BatchingEngine (/predict), an "
                "LLMEngine (/generate), or both")
        self.engine = engine
        self.llm_engine = llm_engine
        self._thread: Optional[threading.Thread] = None
        self.final_metrics_path = final_metrics_path
        self.request_timeout_s = float(request_timeout_s)
        self._draining = False
        self._stop_lock = threading.Lock()
        self._stopped_event = threading.Event()
        self._active = 0                 # handler threads inside /predict
        self._active_lock = threading.Lock()
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _reply(self, code: int, body: bytes,
                       ctype: str = "application/json", headers=None):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _reply_json(self, code: int, obj, headers=None):
                self._reply(code, json.dumps(obj).encode(), headers=headers)

            def _request_rid(self) -> str:
                """Request id: W3C traceparent trace-id when the client
                sent one (propagating an upstream trace), else fresh."""
                return (ingest_traceparent(self.headers.get("traceparent"))
                        or new_request_id())

            def _trace_wanted(self) -> bool:
                return self.headers.get("X-PDTPU-Trace", "").strip() == "1"

            def _reply_rejected(self, e: RejectedError):
                """Overload -> 429 + Retry-After (back off and come back);
                draining/broken/structural -> 503 (find another replica)."""
                reason = getattr(e, "reason", "rejected")
                if reason in _RETRYABLE_REJECTS:
                    retry_s = getattr(e, "retry_after_s", None) or 1.0
                    self._reply_json(
                        429, {"error": str(e), "reason": reason},
                        headers={"Retry-After": f"{retry_s:g}"})
                else:
                    self._reply_json(503,
                                     {"error": str(e), "reason": reason})

            def do_GET(self):
                if self.path == "/healthz":
                    broken = any(getattr(e, "broken", False)
                                 for e in outer._engines())
                    # an engine-initiated drain (stop(), breaker escalation)
                    # leaves outer._draining False while submissions already
                    # 503 "draining" — a router must see the drain HERE,
                    # before it eats rejects (ISSUE 14 fix)
                    draining = outer._draining or any(
                        getattr(e, "draining", False)
                        for e in outer._engines())
                    health = {
                        "status": ("broken" if broken else
                                   "draining" if draining else "ok"),
                    }
                    if outer.engine is not None:
                        health["queue_depth"] = \
                            outer.engine.metrics.queue_depth
                    if outer.llm_engine is not None:
                        m = outer.llm_engine.metrics
                        health["llm_queue_depth"] = m.queue_depth
                        health["llm_weight_version"] = \
                            outer.llm_engine.weight_version
                        health["llm_slots_active"] = m.slots_active
                        health["llm_slots_total"] = m.slots_total
                        health["llm_inflight_tokens"] = \
                            outer.llm_engine.inflight_tokens()
                        health["llm_prefix_probe"] = bool(
                            outer.llm_engine.prefix_cache is not None)
                        snap = m.snapshot()
                        health["llm_prefix_hit_rate"] = round(
                            snap.get("prefix_hit_rate", 0.0), 4)
                        health["llm_cached_blocks"] = \
                            snap.get("cached_blocks", 0)
                        health["llm_tenants"] = {
                            t: {"cache_hit_rate":
                                round(v["cache_hit_rate"], 4),
                                "cached_blocks": v["cached_blocks"],
                                "inflight_tokens": v["inflight_tokens"]}
                            for t, v in snap.get("tenants", {}).items()}
                    self._reply_json(503 if broken else 200, health)
                elif self.path == "/metrics":
                    # both engines scrape from one endpoint; the llm family
                    # renders under pdtpu_llm_* so names never collide
                    text = "".join(e.metrics.render() for e in
                                   (outer.engine, outer.llm_engine)
                                   if e is not None)
                    # pdtpu_compile_* families ride the same scrape: the
                    # set-up ledger's totals always, the observatory's
                    # registry where some engine armed it (ISSUE 12)
                    from ..obs.compile_observatory import \
                        render_prom as _compile_render_prom
                    text += _compile_render_prom()
                    self._reply(200, text.encode(),
                                ctype="text/plain; version=0.0.4")
                elif self.path == "/debug/flightrecorder":
                    self._reply_json(200, flight_recorder().snapshot())
                elif self.path == "/debug/costs":
                    # serving economics (ISSUE 11): per-engine phase
                    # tiling, token efficiency, per-tenant/per-class
                    # device-seconds meters, and SLO burn-rate state;
                    # engines built without economics=True report null
                    costs = {}
                    for name, e in (("predict", outer.engine),
                                    ("llm", outer.llm_engine)):
                        if e is None:
                            continue
                        led = getattr(e, "ledger", None)
                        burn = getattr(e, "burn", None)
                        costs[name] = {
                            "economics": (led.snapshot()
                                          if led is not None else None),
                            "slo_burn": (burn.snapshot()
                                         if burn is not None else None),
                        }
                    self._reply_json(200, costs)
                elif self.path == "/debug/compiles":
                    # compile observatory (ISSUE 12): every registered
                    # executable (fingerprint, compile seconds, AOT
                    # cost/memory analyses, dispatches, device-seconds)
                    # plus recompiles grouped by culprit — the registry is
                    # process-global, so one table covers both engines —
                    # and the always-on set-up ledger: `programs` by name,
                    # `setup` as of the first mark_warm()
                    from ..obs.compile_observatory import compile_observatory
                    self._reply_json(
                        200, compile_observatory().snapshot(top=50))
                elif self.path == "/debug/requests":
                    ids = []
                    for e in outer._engines():
                        ids.extend(e.timelines.ids())
                    self._reply_json(200, {"ids": ids})
                elif self.path.startswith("/debug/requests/"):
                    rid = self.path[len("/debug/requests/"):]
                    for e in outer._engines():
                        tl = e.timelines.get(rid)
                        if tl is not None:
                            self._reply_json(200, tl)
                            return
                    self._reply_json(
                        404, {"error": f"no timeline for request {rid!r} "
                              "(untraced, unfinished, or evicted from the "
                              "bounded timeline buffer)"})
                else:
                    self._reply_json(404, {"error": "not found"})

            def do_POST(self):
                routes = {"/predict": (outer.engine, self._predict),
                          "/generate": (outer.llm_engine, self._generate)}
                route = routes.get(self.path)
                if route is None or route[0] is None:
                    self._reply_json(404, {"error": "not found"})
                    return
                body = read_request_body(self)
                if body is None:
                    return
                with outer._active_lock:
                    outer._active += 1
                try:
                    route[1](body)
                finally:
                    with outer._active_lock:
                        outer._active -= 1

            def _generate(self, body: bytes):
                try:
                    payload = json.loads(body or b"{}")
                    prompt = np.asarray(payload["input_ids"],
                                        dtype=np.int32).reshape(-1)
                    if prompt.size < 1:
                        raise ValueError("input_ids must be non-empty")
                    slo = payload.get("slo")
                    if slo is not None and slo not in SLO_CLASSES:
                        raise ValueError(
                            f"slo must be one of {list(SLO_CLASSES)}, "
                            f"got {slo!r}")
                    tenant = self.headers.get("X-Tenant-Id")
                    if tenant is not None \
                            and not _TENANT_ID_RE.match(tenant):
                        raise ValueError(
                            "malformed X-Tenant-Id (want 1-64 chars of "
                            "[A-Za-z0-9._-], starting alphanumeric), got "
                            f"{tenant!r}")
                    # sampling fields (ISSUE 18): temperature / top_k /
                    # top_p / seed / grammar; absent → greedy (None)
                    sampling = SamplingParams.from_payload(payload)
                    if sampling is not None:
                        sampling.validate()
                    # per-token logprobs (ISSUE 19): strictly boolean —
                    # a truthy 1 / "yes" is a malformed request
                    want_lp = payload.get("logprobs", False)
                    if not isinstance(want_lp, bool):
                        raise ValueError(
                            f"logprobs must be a boolean, got "
                            f"{want_lp!r}")
                except (ValueError, KeyError, TypeError) as e:
                    self._reply_json(400, {"error": f"bad request: {e}"})
                    return
                rid = self._request_rid()
                traced = self._trace_wanted()
                try:
                    handle = outer.llm_engine.submit(
                        prompt,
                        max_new_tokens=payload.get("max_new_tokens"),
                        eos_token_id=payload.get("eos_token_id"),
                        deadline_ms=payload.get("deadline_ms"),
                        slo=slo, tenant=tenant, rid=rid, trace=traced,
                        sampling=sampling, logprobs=want_lp)
                    toks = handle.result(timeout=outer.request_timeout_s)
                except RejectedError as e:
                    self._reply_rejected(e)
                    return
                except DeadlineExceededError as e:
                    self._reply_json(504, {"error": str(e)})
                    return
                except Exception as e:  # model/decode failure
                    self._reply_json(
                        500, {"error": f"{type(e).__name__}: {e}"})
                    return
                resp = {
                    "tokens": np.asarray(toks).tolist(),
                    "ttft_ms": handle.ttft_ms,
                    "rid": rid,
                }
                if want_lp:
                    resp["logprobs"] = handle.logprobs_so_far()
                if traced:
                    resp["trace"] = handle.timeline()
                self._reply_json(200, resp)

            def _predict(self, body: bytes):
                try:
                    payload = json.loads(body or b"{}")
                    arrays = _decode_inputs(payload)
                except (ValueError, KeyError, TypeError) as e:
                    self._reply_json(400, {"error": f"bad request: {e}"})
                    return
                rid = self._request_rid()
                traced = self._trace_wanted()
                try:
                    fut = outer.engine.submit(
                        arrays, deadline_ms=payload.get("deadline_ms"),
                        rid=rid, trace=traced)
                    outs = fut.result(timeout=outer.request_timeout_s)
                except RejectedError as e:
                    self._reply_rejected(e)
                    return
                except DeadlineExceededError as e:
                    self._reply_json(504, {"error": str(e)})
                    return
                except Exception as e:  # model/dispatch failure
                    self._reply_json(
                        500, {"error": f"{type(e).__name__}: {e}"})
                    return
                resp = {
                    "outputs": [np.asarray(o).tolist() for o in outs],
                    "rid": rid,
                }
                if traced:
                    # the engine publishes the timeline before resolving
                    # the future, so it is visible here
                    resp["trace"] = outer.engine.timelines.get(rid)
                self._reply_json(200, resp)

        # socket-level cap so a stalled client can't pin a handler thread
        # past the drain settle window
        _Handler.timeout = self.request_timeout_s + 30.0
        self._server = ThreadingHTTPServer((host, port), _Handler)
        # ThreadingHTTPServer defaults to daemon handler threads, which
        # server_close() does NOT join — a handler rejecting a late request
        # after the final snapshot was written would break the snapshot's
        # client-for-client reconciliation. Non-daemon + block_on_close
        # makes server_close() wait for every in-flight handler, so the
        # snapshot is written strictly after the last response.
        self._server.daemon_threads = False
        self._server.block_on_close = True
        self.host, self.port = self._server.server_address[:2]
        # circuit-breaker escalation: the trip fires on the engine's
        # scheduler thread, which cannot join itself — drain from a fresh
        # thread so /healthz reports "broken" while the drain runs and the
        # process exits for the supervisor to replace (ISSUE 6)
        for e in self._engines():
            if hasattr(e, "on_break") and e.on_break is None:
                e.on_break = self._drain_on_break

    def _drain_on_break(self):
        logging.getLogger("paddle_tpu.serving").error(
            "engine circuit breaker open; draining server")
        threading.Thread(target=self.stop, daemon=True,
                         name="pdtpu-serving-breaker-drain").start()

    # ---- lifecycle ----
    def _engines(self):
        return [e for e in (self.engine, self.llm_engine) if e is not None]

    def start(self) -> "ServingServer":
        """Engine scheduler(s) + HTTP accept loop on background threads."""
        for e in self._engines():
            e.start()
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True,
                                        name="pdtpu-serving-http")
        self._thread.start()
        return self

    def stop(self, drain: bool = True):
        """Stop admissions, flush the engine, stop the HTTP server. Safe to
        call twice (idempotent, same contract as KVServer.stop); the loser
        of a concurrent stop race waits for the winner to finish."""
        with self._stop_lock:
            if self._draining:
                already = True
            else:
                self._draining = True    # /predict now rejects via engine
                already = False
        drain_s = max(e.config.drain_timeout_s for e in self._engines())
        if already:
            self._stopped_event.wait(timeout=drain_s + 15.0)
            return
        for e in self._engines():
            e.stop(drain=drain)
        self._wait_active_settled()
        self._server.shutdown()
        self._server.server_close()
        if self.final_metrics_path:
            tmp = self.final_metrics_path + ".tmp"
            with open(tmp, "w") as f:
                f.write("".join(e.metrics.render()
                                for e in self._engines()))
            os.replace(tmp, self.final_metrics_path)
        self._stopped_event.set()

    def _wait_active_settled(self, timeout: float = 10.0):
        """Let handler threads holding already-resolved futures finish
        writing their responses before the accept loop dies — the 'no
        accepted request is dropped' half of the drain contract."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._active_lock:
                settled = self._active == 0
            if settled:
                # brief double-check window for a just-accepted socket
                # whose handler hasn't registered itself yet
                time.sleep(0.05)
                with self._active_lock:
                    if self._active == 0:
                        return
                continue
            time.sleep(0.01)
        with self._active_lock:
            still = self._active
        logging.getLogger("paddle_tpu.serving").warning(
            "drain settle window (%.1fs) expired with %d /predict "
            "handler(s) still active; their clients may see a connection "
            "reset", timeout, still)

    def serve_forever(self, install_signal_handlers: bool = True):
        """Foreground serve loop with the SIGTERM drain contract: returns
        after a graceful drain (caller exits 0), mirroring ResilientTrainer's
        preemption path."""
        if install_signal_handlers:
            def _on_term(signum, frame):
                # black-box dump FIRST: if the drain wedges and the
                # supervisor escalates to SIGKILL, the postmortem still
                # has everything up to the signal
                fr = flight_recorder()
                fr.record("sigterm", signum=int(signum))
                fr.try_dump(reason="sigterm")
                # drain from a helper thread: shutdown() would deadlock if
                # called on the main thread blocked inside serve_forever
                threading.Thread(target=self.stop, daemon=True,
                                 name="pdtpu-serving-drain").start()
            for sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(sig, _on_term)
        for e in self._engines():
            e.start()
        try:
            if self._thread is not None:
                # start() already owns an accept loop; a SECOND
                # serve_forever on the same socket would survive shutdown()
                # (the first loop's exit resets the shutdown flag) — block
                # until drain instead
                self._stopped_event.wait()
            else:
                self._server.serve_forever(poll_interval=0.05)
        finally:
            # signal case: the drain thread owns stop() — wait for it so the
            # process doesn't exit with the final snapshot half-written.
            # Direct shutdown() callers get the same flush here.
            self.stop()


def serve(model_path: str, host: str = "127.0.0.1", port: int = 8000,
          config: Optional[EngineConfig] = None,
          final_metrics_path: Optional[str] = None) -> ServingServer:
    """Load an exported model (inference.export_model artifacts) and return
    a ready-to-start ServingServer."""
    from ..inference import load_predictor
    predictor = load_predictor(model_path)
    engine = BatchingEngine.from_predictor(predictor, config=config)
    return ServingServer(engine, host=host, port=port,
                         final_metrics_path=final_metrics_path)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", required=True,
                    help="export_model artifact prefix")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-queue-depth", type=int, default=256)
    ap.add_argument("--max-request-rows", type=int, default=None,
                    help="reject single requests larger than this many rows")
    ap.add_argument("--final-metrics", default=None)
    args = ap.parse_args(argv)
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()  # before the first compilation
    server = serve(args.model, host=args.host, port=args.port,
                   config=EngineConfig(max_batch_size=args.max_batch_size,
                                       max_wait_ms=args.max_wait_ms,
                                       max_queue_depth=args.max_queue_depth,
                                       max_request_rows=args.max_request_rows),
                   final_metrics_path=args.final_metrics)
    print(f"serving {args.model} on {server.host}:{server.port}",
          file=sys.stderr)
    server.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
