"""Fault-tolerant multi-replica serving tier (ISSUE 14): prefix/load-aware
routing, quarantine ladder with backoff re-admission, and the zero-dropped-
streams guarantee — on replica crash/hang every in-flight generation is
re-prefilled on a survivor and resumes bit-identical to an uninterrupted
single-engine greedy generate().

Every scheduler test runs the PRODUCTION router (ReplicaRouter.pump) under
a SimClock — scripted instants, no sleeps, no thread flake. The one
subprocess test kills a replica under live HTTP traffic and reconciles the
router's final metrics client-for-client."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


@pytest.fixture(scope="module")
def gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    """Replica-tier clauses key on the GLOBAL plan (so tests can arm a
    loss mid-decode); never leak one into the next test."""
    from paddle_tpu.utils.fault_injection import set_global_plan
    set_global_plan(None)
    yield
    set_global_plan(None)


def _fleet(gpt_tiny, clock, n=2, plan=None, router_cfg=None, num_slots=4):
    from paddle_tpu import serving
    replicas = [
        serving.InProcessReplica(
            serving.LLMEngine(
                gpt_tiny,
                serving.LLMEngineConfig(num_slots=num_slots, block_len=8,
                                        n_blocks=4, max_queue_depth=64),
                clock=clock),
            i, fault_plan=plan)
        for i in range(n)]
    return serving.ReplicaRouter(replicas, router_cfg), replicas


def _drive(router, clock, max_steps=2000, dt=0.01):
    steps = 0
    while router.has_work():
        clock.advance(dt)
        router.pump()
        steps += 1
        assert steps < max_steps, "router failed to converge"
    return steps


def _reference(gpt_tiny, prompts, max_new_tokens):
    """Uninterrupted one-shot greedy generate() — the bit-identity oracle
    (prompts must share one length so they batch)."""
    from paddle_tpu.models.generation import generate
    plen = prompts[0].size
    assert all(p.size == plen for p in prompts)
    out = np.asarray(generate(gpt_tiny, np.stack(prompts),
                              max_new_tokens=max_new_tokens))
    return out[:, plen:]


# ---- routing policy ----

def test_routing_prefix_affinity_then_load(gpt_tiny):
    """First admission of a prefix lands by load/index; the SECOND lands
    on the replica whose radix cache holds it — affinity compounds
    instead of 1/N-ing the fleet hit rate. With no cache signal, ties
    break toward the lighter replica."""
    from paddle_tpu import serving

    clock = serving.SimClock()
    router, reps = _fleet(gpt_tiny, clock)
    rng = np.random.RandomState(1)
    shared = rng.randint(1, 500, size=(16,)).astype(np.int32)  # 2 blocks

    h1 = router.submit(shared, max_new_tokens=4)
    first = h1._replica
    assert first is reps[0]          # all idle: index breaks the tie
    _drive(router, clock)
    np.testing.assert_array_equal(
        h1.result(timeout=0), _reference(gpt_tiny, [shared], 4)[0])

    # the finished stream's blocks stay cached on replica0 — the probe
    # sees them (read-only: no refcounts move), so the same prefix
    # routes back even though both replicas are equally loaded
    assert reps[0].prefix_probe(shared) >= 8
    assert reps[1].prefix_probe(shared) == 0
    h2 = router.submit(shared, max_new_tokens=4)
    assert h2._replica is first
    _drive(router, clock)

    # a cold prompt while replica0 is busier goes to replica1
    cold = rng.randint(1, 500, size=(16,)).astype(np.int32)
    h3 = router.submit(shared, max_new_tokens=4)     # pins load on r0
    h4 = router.submit(cold, max_new_tokens=4)
    assert h4._replica is reps[1]
    _drive(router, clock)

    snap = router.metrics.snapshot()
    assert snap["routed"]["replica0"] == 3
    assert snap["routed"]["replica1"] == 1
    assert snap["affinity_hit_rate"] == pytest.approx(2 / 4)
    assert snap["completed"] == 4


def test_router_healthz_and_metrics_families(gpt_tiny):
    from paddle_tpu import serving

    clock = serving.SimClock()
    router, reps = _fleet(gpt_tiny, clock)
    h = router.submit([1, 2, 3], max_new_tokens=2)
    _drive(router, clock)
    assert h.result(timeout=0).size == 2
    assert router.healthz() == {
        "status": "ok",
        "replicas": {"replica0": "ok", "replica1": "ok"},
        "weight_versions": {"replica0": "v0", "replica1": "v0"},
        "quarantined": []}
    flat = serving.parse_exposition(router.metrics.render())
    assert flat['pdtpu_router_requests_total{outcome="completed"}'] == 1
    assert flat['pdtpu_router_replica_up{replica="replica0"}'] == 1
    assert flat['pdtpu_router_replica_up{replica="replica1"}'] == 1
    assert flat['pdtpu_router_resumed_streams_total'] == 0


# ---- the acceptance proof: zero dropped streams across a replica loss ----

@pytest.mark.fault_matrix
def test_crash_failover_resumes_bit_identical_mid_decode(
        gpt_tiny, tmp_path, monkeypatch):
    """Kill a replica MID-decode (emitted tokens > 0) via the replica
    fault grammar: every stream it owned must resume on the survivor and
    finish bit-identical to an uninterrupted one-shot generate(), with
    `router_failover` flight events naming the dead replica and each
    resumed rid in submit order — and a flight dump on disk."""
    from paddle_tpu import serving
    from paddle_tpu.obs.flight_recorder import flight_recorder
    from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan

    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    flight_recorder().clear()
    clock = serving.SimClock()
    router, reps = _fleet(gpt_tiny, clock)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 500, size=(6,)).astype(np.int32)
               for _ in range(4)]
    handles = [router.submit(p, max_new_tokens=12) for p in prompts]
    # load-aware spread: 2 streams per replica
    assert {h._replica.name for h in handles} == {"replica0", "replica1"}
    victims = [h for h in handles if h._replica is reps[0]]

    for _ in range(6):              # decode far enough that a kill is MID-stream
        clock.advance(0.01)
        router.pump()
    assert all(len(h.tokens_so_far()) > 0 for h in handles)
    emitted_at_kill = {h.rid: len(h.tokens_so_far()) for h in victims}

    set_global_plan(FaultPlan.from_spec("replica_crash@0"))
    _drive(router, clock)

    ref = _reference(gpt_tiny, prompts, 12)
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(h.result(timeout=0), ref[i])
    assert all(h.failovers == 1 for h in victims)
    assert all(h.failovers == 0 for h in handles if h not in victims)

    # flight events: dead replica named, resumed rids in submit order
    events = [e for e in flight_recorder().snapshot()["events"]
              if e["kind"] == "router_failover"]
    assert [e["rid"] for e in events] == \
        [h.rid for h in sorted(victims, key=lambda h: h._seq)]
    assert all(e["replica"] == "replica0" for e in events)
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    # the kill landed mid-decode and the harvest saw at least what the
    # handle had streamed at that instant
    assert all(e["emitted"] >= emitted_at_kill[e["rid"]] > 0
               for e in events)
    # the failover auto-dumped the recorder
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("pdtpu_flight_")]
    assert dumps, "failover must dump the flight recorder"
    doc = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert any(e["kind"] == "router_failover" for e in doc["events"])

    snap = router.metrics.snapshot()
    assert snap["quarantines"] == {"replica0": 1}
    assert snap["failovers"] == {"replica0": 1}
    assert snap["resumed_streams"] == len(victims)
    assert snap["completed"] == 4 and snap["failed"] == 0
    assert router.healthz()["replicas"]["replica0"] == "quarantined"


@pytest.mark.fault_matrix
def test_hang_quarantine_backoff_readmission_ladder(gpt_tiny):
    """A hung replica (frozen forward, health still 'ok') is caught by
    the watchdog after `quarantine_threshold` consecutive strikes, its
    stream fails over and completes bit-identically, re-admission probes
    back off exponentially while the hang persists, and the replica is
    re-admitted once it shows real forward progress again."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan

    clock = serving.SimClock()
    plan = FaultPlan.from_spec("replica_hang@0:3.0")
    cfg = serving.RouterConfig(hung_timeout_s=0.05, quarantine_threshold=2,
                               backoff_base_s=0.2, backoff_max_s=5.0)
    router, reps = _fleet(gpt_tiny, clock, plan=plan, router_cfg=cfg)
    prompt = np.random.RandomState(3).randint(
        1, 500, size=(6,)).astype(np.int32)

    h = router.submit(prompt, max_new_tokens=6)
    assert h._replica is reps[0]
    router.pump()                       # arms the hang: frozen forward
    strikes = 0
    while not router._state["replica0"].quarantined:
        clock.advance(0.1)
        router.pump()
        strikes += 1
        assert strikes <= 4
    assert strikes == cfg.quarantine_threshold
    # the stream failed over and finishes on replica1, bit-identical
    _drive(router, clock, dt=0.05)
    np.testing.assert_array_equal(
        h.result(timeout=0), _reference(gpt_tiny, [prompt], 6)[0])
    assert h.failovers == 1

    # while the hang persists, every re-admission probe fails and the
    # ladder backs off exponentially instead of flapping traffic
    while clock.now() < 2.5:
        clock.advance(0.1)
        router.pump()
    st = router._state["replica0"]
    assert st.quarantined and st.backoff_level >= 2
    assert router.metrics.snapshot()["readmissions"] == {}

    # hang expires at t=3.0: the next probe pump makes real progress
    # (the orphaned queued stream dispatches) and re-admits the replica
    while router._state["replica0"].quarantined:
        clock.advance(0.5)
        router.pump()
        assert clock.now() < 20.0
    snap = router.metrics.snapshot()
    assert snap["quarantines"] == {"replica0": 1}
    assert snap["readmissions"] == {"replica0": 1}
    assert router.healthz()["replicas"]["replica0"] == "ok"
    # re-admitted means routable again
    h2 = router.submit(prompt, max_new_tokens=2, tenant="fresh")
    assert h2._replica is not None
    _drive(router, clock)


@pytest.mark.fault_matrix
def test_fleet_brownout_shed_confined_to_best_effort(gpt_tiny):
    """With half the fleet quarantined the router sheds best_effort at
    its own door (retryable, Retry-After hinted) while interactive work
    still completes bit-identically on the survivors; with the WHOLE
    fleet down every admission is `fleet_unavailable`."""
    from paddle_tpu import serving
    from paddle_tpu.utils.fault_injection import FaultPlan

    clock = serving.SimClock()
    plan = FaultPlan.from_spec("replica_crash@0")
    router, reps = _fleet(gpt_tiny, clock, plan=plan)
    prompt = np.random.RandomState(4).randint(
        1, 500, size=(6,)).astype(np.int32)

    h = router.submit(prompt, max_new_tokens=6, slo="interactive")
    clock.advance(0.01)
    router.pump()                   # crash fires; h fails over to replica1
    assert reps[0].crashed
    with pytest.raises(serving.RejectedError) as exc:
        router.submit(prompt, max_new_tokens=6, slo="best_effort")
    assert exc.value.reason == "shed"
    assert exc.value.retry_after_s is not None

    h2 = router.submit(prompt, max_new_tokens=6, slo="interactive")
    _drive(router, clock)
    ref = _reference(gpt_tiny, [prompt], 6)[0]
    np.testing.assert_array_equal(h.result(timeout=0), ref)
    np.testing.assert_array_equal(h2.result(timeout=0), ref)

    reps[1].crash()
    router.pump()
    assert router.healthz()["status"] == "unavailable"
    with pytest.raises(serving.RejectedError) as exc:
        router.submit(prompt, max_new_tokens=2)
    assert exc.value.reason == "fleet_unavailable"
    snap = router.metrics.snapshot()
    assert snap["reject_reasons"]["shed"] == 1
    assert snap["reject_reasons"]["fleet_unavailable"] == 1
    assert snap["completed"] == 2


# ---- KV row serialization (failover handoff groundwork) ----

def test_export_rows_length_trimmed_bitwise_parity():
    """export_rows ships ONLY the occupied prefix (ISSUE 19: a handoff
    payload must not drag a row's full static capacity across the wire).
    Parity pin: the trimmed per-layer arrays must equal a manual
    host-side slice of the full slabs over the identity page range —
    bitwise, including a non-block-aligned tail — and cost
    length-proportional bytes."""
    import jax.numpy as jnp
    from paddle_tpu.serving.llm import SlotPagedKVPool

    def init_cache(b, max_len):
        return [(jnp.zeros((b, 2, max_len, 3), jnp.float32),
                 jnp.zeros((b, 2, max_len, 3), jnp.float32))
                for _ in range(2)]

    rng = np.random.RandomState(6)
    pool = SlotPagedKVPool(init_cache, 3, 4, 4)     # block_len=4, 4 blocks
    slot = pool.allocate(10)
    pool.set_length(slot, 10)                       # 2 full blocks + tail 2
    for li in range(len(pool.slabs)):
        k, v = pool.slabs[li]
        pool.slabs[li] = (
            jnp.asarray(rng.randn(*k.shape).astype(np.float32)),
            jnp.asarray(rng.randn(*v.shape).astype(np.float32)))

    row = pool.export_rows([slot])["rows"][slot]
    assert row["length"] == 10
    # identity layout: the slot's token t lives at slab column t of its
    # own row — fetch the WHOLE raw slab host-side (the untrimmed path)
    # and demand the trimmed export equals its first `length` columns
    for li, (ke, ve) in enumerate(row["layers"]):
        assert np.asarray(ke).shape == (2, 10, 3)   # trimmed, not 16
        kfull, vfull = (np.asarray(a) for a in pool.slabs[li])
        np.testing.assert_array_equal(np.asarray(ke),
                                      kfull[slot, :, :10, :])
        np.testing.assert_array_equal(np.asarray(ve),
                                      vfull[slot, :, :10, :])
    # export_page (the spill unit) agrees with the same oracle,
    # including a partial-width tail
    tail = pool.export_page(slot * pool.n_blocks + 2, width=2)
    for li, (ke, ve) in enumerate(tail):
        kfull, _ = (np.asarray(a) for a in pool.slabs[li])
        np.testing.assert_array_equal(np.asarray(ke),
                                      kfull[slot, :, 8:10, :])


# ---- /healthz advertises engine-initiated drain (ISSUE 14 fix) ----

def test_healthz_advertises_engine_drain(gpt_tiny):
    """An ENGINE-initiated drain (engine.stop, breaker escalation) must
    flip /healthz to {"status": "draining"} even though the server-level
    drain flag never moved — a router watching /healthz has to see the
    drain before it starts eating 503s."""
    from paddle_tpu import serving

    eng = serving.LLMEngine(
        gpt_tiny, serving.LLMEngineConfig(num_slots=2, block_len=8,
                                          n_blocks=4))
    srv = serving.ServingServer(llm_engine=eng, port=0).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            body = json.loads(r.read())
        assert body["status"] == "ok"
        assert body["llm_prefix_probe"] is True
        assert body["llm_inflight_tokens"] == 0

        eng.stop(drain=True, timeout=30)    # engine-side, not server-side
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "draining"
    finally:
        srv.stop()


# ---- subprocess: live replica kill under HTTP traffic ----

def test_router_server_replica_kill_reconciles_metrics(tmp_path):
    """Live fleet of two in-process replicas behind a RouterServer; the
    fault timer kills replica0 MID-traffic. Every accepted request must
    still return 200 with its full stream (zero dropped), the fleet
    /healthz must degrade, and the final router metrics must reconcile
    client-for-client: completions match 200s, and the resumed-stream
    counter matches the per-response failover counts."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "LLM_SLOTS": "4",
                "LLM_MAX_NEW": "8", "ROUTER_FAULTS": "replica_crash@0",
                "ROUTER_FAULT_DELAY_S": "1.0"})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(FIXTURES, "router_worker.py"),
         str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        port_file = os.path.join(str(tmp_path), "port")
        deadline = time.time() + 300
        while not os.path.exists(port_file):
            assert proc.poll() is None, proc.stdout.read().decode()
            assert time.time() < deadline, "worker never bound its port"
            time.sleep(0.1)
        port = int(open(port_file).read())
        base = f"http://127.0.0.1:{port}"

        results = []
        res_lock = threading.Lock()
        stop = threading.Event()

        def client(seed):
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                prompt = rng.randint(1, 500, size=(5,)).tolist()
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps({"input_ids": prompt}).encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=240) as r:
                    body = json.loads(r.read())
                    with res_lock:
                        results.append((r.status, body))

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        # keep traffic flowing until the fault timer's kill is VISIBLE in
        # fleet health, so the replica loss provably lands mid-traffic.
        # Seen means quarantined: between the crash and the supervision
        # pass that quarantines it the fleet already reads "degraded"
        # with the replica's own word for its state, and a reading taken
        # then is not the one asserted on below
        health = None
        deadline = time.time() + 240
        while time.time() < deadline:
            with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
                health = json.loads(r.read())
            if health["status"] == "degraded" \
                    and health["replicas"]["replica0"] == "quarantined":
                break
            time.sleep(0.2)
        time.sleep(1.0)       # one more round of post-kill traffic
        stop.set()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)

        assert len(results) >= 4
        assert all(code == 200 for code, _ in results)
        assert all(len(body["tokens"]) == 8 for _, body in results)
        client_failovers = sum(body["failovers"] for _, body in results)

        assert health["status"] == "degraded"
        assert health["replicas"]["replica0"] == "quarantined"
        assert health["replicas"]["replica1"] == "ok"
        from paddle_tpu import serving
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            live = serving.parse_exposition(r.read().decode())
        assert live['pdtpu_router_replica_up{replica="replica0"}'] == 0
        assert live['pdtpu_router_replica_up{replica="replica1"}'] == 1

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0

        flat = serving.parse_exposition(
            open(os.path.join(str(tmp_path), "metrics_final.txt")).read())
        assert flat['pdtpu_router_requests_total{outcome="completed"}'] \
            == len(results)
        assert flat['pdtpu_router_requests_total{outcome="failed"}'] == 0
        assert flat['pdtpu_router_quarantines_total{replica="replica0"}'] == 1
        assert flat['pdtpu_router_resumed_streams_total'] == client_failovers
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
