"""The set-up ledger (ISSUE 34): every program's trace, lower and
compile-or-load seconds by name from the one jax.monitoring dispatcher,
self time so that a jit inside a jit is not counted twice, cache hits and
misses, the freeze at the first mark_warm() and a named recompile after
it, and the program's own start-up phases."""
import time

import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu.obs import goodput
from paddle_tpu.obs.goodput import (CACHE_HIT_EVENT, CACHE_RETRIEVAL_EVENT,
                                    COMPILE_EVENT, LOWER_EVENT, TRACE_EVENT,
                                    CompileLedger, RecompileSentinel,
                                    compile_ledger, program_key)

pytestmark = pytest.mark.obs


@pytest.fixture()
def ledger(monkeypatch):
    """A fresh ledger in the process-wide one's place for one test (the
    real one keeps the process's `import` phase and may be frozen)."""
    fresh = CompileLedger()
    monkeypatch.setattr(goodput, "_LEDGER", fresh)
    assert compile_ledger() is fresh
    return fresh


def test_program_key_joins_the_three_events_names():
    assert program_key("step") == "step"
    assert program_key("jit(step)") == "step"      # lower, backend events
    assert program_key("jit_step") == "step"       # the device trace
    assert program_key(None) == "<unnamed>"


def test_jitted_function_leaves_one_row_and_a_second_call_nothing(ledger):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(6.0)
    _ = float(x.sum())                 # eager programs built before

    @jax.jit
    def ledger_row_fn(v):
        return v * 2.0 + 1.0

    ledger.reset()
    ledger_row_fn(x).block_until_ready()
    snap = ledger.snapshot()
    assert "ledger_row_fn" in snap["rows"]
    row = snap["rows"]["ledger_row_fn"]
    assert row["traces"] == 1
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["cache_hits"] + row["cache_misses"] == 1
    assert row["first_seen"] <= row["last_seen"]
    assert snap["totals"]["programs"] == len(snap["rows"])
    ledger_row_fn(x).block_until_ready()           # a warm call
    again = ledger.snapshot()
    assert again["rows"] == snap["rows"] and \
        again["totals"] == snap["totals"]


def test_a_jit_inside_a_jit_is_not_counted_twice(ledger):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    _ = float(x.sum())

    @jax.jit
    def ledger_inner(v):
        return jnp.where(v > 2, v, 0.0) * 2.0

    @jax.jit
    def ledger_outer(v):
        return ledger_inner(v).sum() + ledger_inner(v + 1.0).sum()

    ledger.reset()
    t0 = time.perf_counter()
    ledger_outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    snap = ledger.snapshot()
    outer, inner = snap["rows"]["ledger_outer"], snap["rows"]["ledger_inner"]
    # the inner jit reports its own trace inside the outer's: the outer's
    # row carries both readings, and the totals are over self time
    assert inner["traces"] == 2 and inner["lower_s"] == 0
    assert outer["trace_self_s"] < outer["trace_s"]
    assert outer["trace_self_s"] + inner["trace_s"] <= outer["trace_s"] + 1e-6
    t = snap["totals"]
    assert 0 < t["trace_s"] + t["lower_s"] + t["backend_s"] <= wall
    assert sum(r["trace_s"] for r in snap["rows"].values()) > t["trace_s"]


def test_first_build_misses_the_cache_and_the_second_hits(ledger, tmp_path):
    """With a cache directory of its own the first process-local build is
    a miss; after jax.clear_caches() the same program is loaded: a hit
    with retrieval seconds, and the backend event fired both times."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    x = jnp.arange(5.0)
    _ = float(x.sum())
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    sen = RecompileSentinel().install()
    try:
        @jax.jit
        def ledger_cached_fn(v):
            return jnp.tanh(v) * 3.0

        ledger.reset()
        ledger_cached_fn(x).block_until_ready()
        first = ledger.row("ledger_cached_fn")
        assert (first["cache_hits"], first["cache_misses"]) == (0, 1)
        assert (sen.compiles, sen.loads) == (1, 0)
        wrote = any(tmp_path.iterdir())
        jax.clear_caches()
        if wrote:
            ledger_cached_fn(x).block_until_ready()
        else:
            # a backend that writes no cache entry: the same, through the
            # events' own names, in the order jax fires them
            import jax.monitoring as mon
            mon.record_event_duration_secs(TRACE_EVENT, 0.001,
                                           fun_name="ledger_cached_fn")
            mon.record_event_duration_secs(LOWER_EVENT, 0.001,
                                           fun_name="jit(ledger_cached_fn)")
            mon.record_event(CACHE_HIT_EVENT)
            mon.record_event_duration_secs(CACHE_RETRIEVAL_EVENT, 0.002)
            mon.record_event_duration_secs(COMPILE_EVENT, 0.003,
                                           fun_name="jit(ledger_cached_fn)")
        second = ledger.row("ledger_cached_fn")
        assert (second["cache_hits"], second["cache_misses"]) == (1, 1)
        assert second["retrieval_s"] > 0
        assert second["traces"] == 2          # traced again: no cache helps
        assert second["backend_s"] > first["backend_s"]   # a load fires it
        assert (sen.compiles, sen.loads) == (1, 1)
        totals = ledger.snapshot()["totals"]
        assert (totals["cache_hits"], totals["cache_misses"]) == (1, 1)
    finally:
        sen.uninstall()
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_mark_warm_freezes_the_set_up_and_a_later_build_has_a_name(ledger):
    import jax
    import jax.numpy as jnp

    obs.flight_recorder().clear()

    @jax.jit
    def ledger_warm_fn(v):
        return (v * v).sum()

    sen = RecompileSentinel().install()
    try:
        ledger_warm_fn(jnp.ones((4,))).block_until_ready()
        assert ledger.at_warm is None
        sen.mark_warm()
        frozen = ledger.at_warm
        assert frozen["rows"]["ledger_warm_fn"]["traces"] == 1
        assert frozen["totals"]["cache_misses"] >= 1
        ledger_warm_fn(jnp.ones((4,))).block_until_ready()   # warm call
        assert sen.recompiles == 0
        ledger_warm_fn(jnp.ones((5,))).block_until_ready()   # shape change
        assert sen.recompiles >= 1
        named = [r for r in sen.recompiled
                 if r["fun_name"] == "ledger_warm_fn"]
        assert len(named) == 1
        assert named[0]["how"] in ("compiled", "loaded")
        assert named[0]["paid"] == "trace+lower+backend"
        ev = [e for e in obs.flight_recorder().snapshot()["events"]
              if e["kind"] == "train_recompile"
              and e["fun_name"] == "ledger_warm_fn"]
        assert len(ev) == 1 and ev[0]["how"] == named[0]["how"]
        assert ev[0]["paid"] == "trace+lower+backend"
        # a second sentinel's mark_warm leaves the frozen set-up alone,
        # while the live ledger has moved on
        RecompileSentinel().mark_warm()
        assert ledger.at_warm is frozen
        assert ledger.row("ledger_warm_fn")["traces"] == 2
        assert ledger.at_warm["rows"]["ledger_warm_fn"]["traces"] == 1
    finally:
        sen.uninstall()


def test_a_post_warm_load_counts_as_a_recompile(ledger):
    """`recompiles` is what the benchmark's "no compilation inside the
    window" reads: a load from the persistent cache after warm counts."""
    import jax.monitoring as mon
    sen = RecompileSentinel().install()
    try:
        sen.mark_warm()
        mon.record_event(CACHE_HIT_EVENT)
        mon.record_event_duration_secs(CACHE_RETRIEVAL_EVENT, 0.002)
        mon.record_event_duration_secs(COMPILE_EVENT, 0.004,
                                       fun_name="jit(ledger_load_fn)")
        assert (sen.recompiles, sen.compiles, sen.loads) == (1, 0, 1)
        assert sen.recompiled[-1]["fun_name"] == "ledger_load_fn"
        assert sen.recompiled[-1]["how"] == "loaded"
        assert sen.recompiled[-1]["paid"] == "backend"
    finally:
        sen.uninstall()


def test_rows_past_the_cap_fold_into_other_with_their_count(ledger,
                                                            monkeypatch):
    monkeypatch.setattr(CompileLedger, "MAX_ROWS", 4)
    for i in range(6):
        ledger.on_duration(TRACE_EVENT, 0.5, fun_name=f"ledger_f{i}")
        ledger.on_span(TRACE_EVENT, 10.0 * i, 10.0 * i + 0.5,
                       fun_name=f"ledger_f{i}")
    ledger.on_duration(TRACE_EVENT, 0.5, fun_name="ledger_f5")   # again
    snap = ledger.snapshot()
    assert sorted(snap["rows"]) == ["<other>", "ledger_f0", "ledger_f1",
                                    "ledger_f2"]
    other = snap["rows"]["<other>"]
    assert other["programs"] == 3 and other["traces"] == 4
    assert snap["totals"]["programs"] == 6
    assert snap["totals"]["trace_s"] == pytest.approx(3.0)
    assert CompileLedger.slowest(snap["rows"], 1)[0]["program"] == "<other>"


def test_import_and_engine_phases_after_a_tiny_engine_is_built():
    """The program's own start-up phases, on the process-wide ledger:
    `import` from `paddle_tpu/__init__.py`'s first line to its last,
    `engine_init` round LLMEngine.__init__, `first_step` round the unified
    step's first call, awaited, and on that program's row."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler, serving
    from paddle_tpu.models.gpt import GPTForCausalLM

    led = compile_ledger()
    assert led.phases["import"] > 0
    assert set(profiler.SETUP_SPANS) == {
        "pdtpu/setup/import", "pdtpu/setup/engine_init",
        "pdtpu/setup/parallelize", "pdtpu/setup/first_step"}
    paddle.seed(0)
    model = GPTForCausalLM.from_preset("gpt2-tiny")
    before = dict(led.phases)
    profiler.start_profiler()
    try:
        eng = serving.LLMEngine(
            model, serving.LLMEngineConfig(num_slots=2, block_len=8,
                                           n_blocks=4, max_queue_depth=8),
            clock=serving.SimClock())
        assert led.phases["engine_init"] > before.get("engine_init", 0.0)
        assert "first_step" not in led.phases \
            or led.phases["first_step"] == before.get("first_step")
        h = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        while eng.has_work():
            eng.pump()
        assert len(h.result(timeout=0)) == 3
        eng.stop()
    finally:
        profiler.stop_profiler(profile_path="/dev/null")
    first = led.phases["first_step"] - before.get("first_step", 0.0)
    assert first > 0
    row = led.row("step")
    assert row["first_call_s"] is not None
    # launch to result: the step's own trace, lower and build lie inside
    assert row["trace_s"] > 0 and row["backend_s"] > 0
    # the spans are RecordEvents of the one table too
    names = [e["name"] for e in profiler.get_events()]
    assert names.count("pdtpu/setup/engine_init") == 1
    assert names.count("pdtpu/setup/first_step") == 1
    # only the first call is a set-up phase: a second request adds nothing
    assert eng._dispatch_step == eng._run_dispatch


def test_parallelize_and_the_train_steps_first_call_are_phases(mesh8):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed import DistributedStrategy
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.parallel import parallelize

    led = compile_ledger()
    before = dict(led.phases)
    paddle.seed(0)
    model = GPTForCausalLM.from_preset("gpt2-tiny")
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters())
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 2}
    step = parallelize(model, opt, mesh=mesh8, strategy=strategy)
    assert led.phases["parallelize"] > before.get("parallelize", 0.0)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 100, (8, 16)).astype(np.int32))
    step(ids, ids)
    after_first = led.phases["first_step"]
    assert after_first > before.get("first_step", 0.0)
    name = step._jitted.__name__
    assert led.row(name)["first_call_s"] > 0
    step(ids, ids)
    assert led.phases["first_step"] == after_first
