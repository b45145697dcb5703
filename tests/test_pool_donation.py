"""The unified step is donated the pool it reads: `LLMEngine._step` is
`jax.jit(step, donate_argnames=("slabs",))`, so the K/V writes and a
state-space layer's state land in the buffers they were read from and the
pool exists once. Pinned here, on the CPU under a `SimClock`:

(a) the compiled step aliases every slab of a paged, a windowed and a
    recurrent engine, and JAX finds every donated buffer usable;
(b) with a step in flight the pool's buffers are one set for the engine's
    life and the streams are `generate()`'s;
(c) a failed dispatch that left the pool whole is retried on it, and a
    blame probe is donated a copy: survivors' streams are those of a
    fault-free run, `pool_copies` counts the probes, `pool_lost` stays 0;
(d) a dispatch that took the pool and failed (or hung in a call that was
    abandoned) fails the active rows, zeroes the pool and serves on;
(e) a reference to the slabs kept across a launch is a deleted array, and
    what reads `pool.slabs` when it works is not.
"""
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import generate
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.utils.fault_injection import FaultPlan, set_global_plan


@pytest.fixture(scope="module")
def gpt_tiny():
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    return GPTForCausalLM.from_preset("gpt2-tiny")


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    set_global_plan(None)
    yield
    set_global_plan(None)


def _windowed():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        sliding_window=32))


def _recurrent():
    from paddle_tpu.models.granitemoehybrid import (
        GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)
    paddle.seed(0)
    return GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        shared_intermediate_size=48, num_hidden_layers=3,
        layer_types=["mamba", "attention", "mamba"], num_attention_heads=4,
        num_key_value_heads=2, num_local_experts=8, num_experts_per_tok=2,
        mamba_n_heads=2, mamba_d_head=64, mamba_d_state=16,
        max_position_embeddings=128))


def _engine(model, fault_plan=None, **kw):
    cfg = dict(num_slots=3, block_len=8, n_blocks=8, max_queue_depth=128,
               enable_prefix_cache=False)
    cfg.update(kw)
    return serving.LLMEngine(model, serving.LLMEngineConfig(**cfg),
                             clock=serving.SimClock(), fault_plan=fault_plan)


def _prompts(lengths, vocab=500, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lengths]


def _reference(model, prompt, n):
    out = generate(model, prompt[None, :], max_new_tokens=n)
    return np.asarray(out.numpy())[0, len(prompt):]


def _drain(eng):
    passes = 0
    while eng.has_work():
        eng.pump()
        passes += 1
        assert passes < 2000, "engine failed to converge"


def _leaves(slabs):
    return jax.tree_util.tree_leaves(slabs)


def _buffers(slabs):
    return sorted(a.unsafe_buffer_pointer() for a in _leaves(slabs))


# ---- (a) the executable aliases the pool ------------------------------------

@pytest.mark.parametrize("kind", ["paged", "window", "recurrent"])
def test_the_compiled_step_aliases_every_slab(gpt_tiny, kind):
    """What makes the per-step copy of the pool go is a property of the
    executable: every byte of `slabs` is aliased to `new_slabs`. A buffer
    JAX could not use ("Some donated buffers were not usable") is an
    error here."""
    model = {"paged": lambda: gpt_tiny, "window": _windowed,
             "recurrent": _recurrent}[kind]()
    eng = _engine(model, n_blocks=16)
    assert kind in eng.pool.layer_kinds
    eng.submit(_prompts([11], vocab=100)[0], max_new_tokens=2)
    with eng._cond:
        eng._admit()
        toks, pos, adv, ctr, *_ = eng._build_rows_locked({})
        args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(adv), eng.pool.device_block_table(),
                eng.pool.slabs) + eng._sampling_args_locked(ctr) \
            + eng._feedback_args() + eng._tail_args_locked()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        compiled = eng._step().lower(*args).compile()
    pool_bytes = sum(a.nbytes for a in _leaves(eng.pool.slabs))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    # and the call itself takes them: the operand is gone, the result whole
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = eng._step()(*args)
    assert all(a.is_deleted() for a in _leaves(args[5]))
    assert [a.shape for a in _leaves(out[3])] \
        == [a.shape for a in _leaves(args[5])]
    eng.pool.slabs = out[3]
    eng.stop(drain=False)


def _latent():
    from paddle_tpu.models.deepseek import (DeepseekConfig,
                                            DeepseekForCausalLM)
    paddle.seed(0)
    return DeepseekForCausalLM(DeepseekConfig(
        vocab_size=128, hidden_size=48, intermediate_size=64,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        first_k_dense_replace=1, n_group=2, topk_group=1,
        max_position_embeddings=256))


@pytest.mark.parametrize("kind", ["paged", "window", "latent"])
def test_the_kv_write_kernel_in_the_step_writes_the_donated_pool_in_place(
        monkeypatch, gpt_tiny, kind):
    """On a TPU the step's K/V writes are the `kv_write` kernel
    (`ops/kv_write.py`, PR 37), which aliases both slabs to its results: a
    kernel that writes pool state aliases it, or donation moves the copy
    behind the kernel (`ssm_update`, PR 35). Here the kernel stands in
    for the vmapped write, interpreted: the step still aliases every byte
    of a paged, a window and a latent pool, JAX finds every donated
    buffer usable, the pool's buffers are one set for the engine's life
    and the streams are `generate()`'s bit for bit."""
    from paddle_tpu.ops import attention, kv_write, pallas_mode
    calls = []

    def through_the_kernel(k_cache, v_cache, k_new, v_new, pos, ring=None):
        assert kv_write.kv_write_supported(k_cache, v_cache, k_new, v_new,
                                           ring)
        calls.append(ring)
        return kv_write.kv_write(k_cache, v_cache, k_new, v_new, pos,
                                 ring=ring)
    monkeypatch.setattr(attention, "_row_writes", through_the_kernel)
    model = {"paged": lambda: gpt_tiny, "window": _windowed,
             "latent": _latent}[kind]()
    eng = _engine(model, n_blocks=16)
    assert kind in eng.pool.layer_kinds
    eng.submit(_prompts([11], vocab=100)[0], max_new_tokens=2)
    with eng._cond:
        eng._admit()
        toks, pos, adv, ctr, *_ = eng._build_rows_locked({})
        args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(adv), eng.pool.device_block_table(),
                eng.pool.slabs) + eng._sampling_args_locked(ctr) \
            + eng._feedback_args() + eng._tail_args_locked()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        compiled = eng._step().lower(*args).compile()
    pool_bytes = sum(a.nbytes for a in _leaves(eng.pool.slabs))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    eng.stop(drain=False)

    eng = _engine(model, n_blocks=16)
    prompts = _prompts([6, 43, 19], vocab=100)
    calls.clear()
    pallas_mode.KERNEL_TRACES.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # "donated buffers not usable"
        handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
        first = _buffers(eng.pool.slabs)
        while eng.has_work():
            eng.pump()
            assert _buffers(eng.pool.slabs) == first
    layers = len(eng.pool.layer_kinds)
    assert len(calls) == layers
    assert pallas_mode.KERNEL_TRACES[("kv_write", "interpret")] == layers
    rings = {r for r in calls if r is not None}
    assert rings == ({eng.pool.ring_len} if kind == "window" else set())
    snap = eng.metrics.snapshot()
    assert snap["pool_copies"] == 0 and snap["pool_lost"] == 0
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(h.result(0), _reference(model, p, 5))
    assert eng._step()._cache_size() == 1
    eng.stop()


# ---- (b) one set of buffers for the engine's life ---------------------------

def test_the_pools_buffers_are_one_set_with_a_step_in_flight(gpt_tiny):
    prompts = _prompts([6, 19, 30, 4, 11])
    eng = _engine(gpt_tiny)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    first = _buffers(eng.pool.slabs)
    in_flight = 0
    while eng.has_work():
        eng.pump()
        in_flight += eng._inflight is not None
        assert _buffers(eng.pool.slabs) == first
    assert in_flight > 5
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(h.result(0),
                                      _reference(gpt_tiny, p, 6))
    snap = eng.metrics.snapshot()
    assert snap["steps_overlapped"] == snap["unified_steps"] - 1
    assert snap["pool_copies"] == 0 and snap["pool_lost"] == 0
    text = eng.metrics.render()
    assert "pdtpu_llm_pool_copies_total 0" in text
    assert "pdtpu_llm_pool_lost_total 0" in text
    assert eng._step()._cache_size() == 1
    eng.stop()


# ---- (c) faults that leave the pool whole -----------------------------------

@pytest.mark.parametrize("spec,retries,quarantined", [
    ("dispatch_raise@2", 1, 0),             # launched ahead, retried
    ("dispatch_raise@0;dispatch_hang@3:2.0", 1, 0),   # the first step; a hang
    ("poison_request@1", 0, 1),             # probes, one row blamed
    ("poison_request@1:decode", 1, 1),      # retries, then probes
])
def test_an_injected_fault_leaves_the_donated_pool_whole(
        gpt_tiny, spec, retries, quarantined):
    """A fault plan raises before the executable is enqueued, so the pool
    the failed dispatch was donated is whole: the retry runs on the same
    operands, each blame probe on a copy whose result is dropped.
    Survivors' streams are bit for bit a fault-free run's."""
    prompts = _prompts([6, 19, 30])
    plan = FaultPlan.from_spec(spec)
    eng = _engine(gpt_tiny, fault_plan=plan, dispatch_retries=retries)
    handles = [eng.submit(p, max_new_tokens=7) for p in prompts]
    first = _buffers(eng.pool.slabs)
    while eng.has_work():
        eng.pump()
        assert _buffers(eng.pool.slabs) == first    # never a probe's copy
    snap = eng.metrics.snapshot()
    assert plan.log and snap["quarantined"] == quarantined
    for i, (p, h) in enumerate(zip(prompts, handles)):
        if quarantined and i == 1:
            with pytest.raises(serving.DispatchFailedError) as exc:
                h.result(0)
            assert exc.value.reason == "poisoned"
        else:
            np.testing.assert_array_equal(h.result(0),
                                          _reference(gpt_tiny, p, 7))
    # every dispatch is a committed step, a full step that failed, or a
    # probe; a probe, and nothing else, copies the pool
    probes = eng._dispatch_idx - snap["unified_steps"] \
        - sum(snap["dispatch_failures"].values())
    assert probes == (3 if quarantined else 0)
    assert snap["pool_copies"] == probes and snap["pool_lost"] == 0
    assert not eng.broken
    eng.pool.check_balance()
    eng.stop()


# ---- (d) a dispatch that takes the pool and fails ---------------------------

def _step_with(eng, before=None, after=None):
    """The engine's step with `before(n)` / `after(n)` run round its n-th
    call: `after` runs once the executable has taken its operands."""
    real, calls = eng._step(), [0]

    def step(*args):
        calls[0] += 1
        if before is not None:
            before(calls[0])
        out = real(*args)
        if after is not None:
            after(calls[0])
        return out

    eng._step_jit = step
    return calls


@pytest.mark.parametrize("at", [1, 3], ids=["first_step", "launched_ahead"])
def test_a_dispatch_that_consumes_the_pool_and_fails_loses_it(gpt_tiny, at):
    """The active rows' K/V went with the buffers: they fail (typed, with
    what they had emitted), never a retry on deleted arrays; the breaker
    is charged once; the queued request and a later one are served from a
    zeroed pool of the same shapes, bit-identical to `generate()`."""
    prompts = _prompts([6, 19, 30, 9])
    eng = _engine(gpt_tiny, enable_prefix_cache=True, dispatch_retries=2)
    shapes = [a.shape for a in _leaves(eng.pool.slabs)]

    def device_fault(n):            # as a device fault looks from the host
        if n == at:
            raise RuntimeError("device fault after the step was enqueued")

    calls = _step_with(eng, after=device_fault)
    handles = [eng.submit(p, max_new_tokens=7) for p in prompts]
    while calls[0] < at or not eng.metrics.snapshot()["pool_lost"]:
        eng.pump()
    assert calls[0] == at                   # no retry on a consumed pool
    snap = eng.metrics.snapshot()
    assert snap["pool_lost"] == 1 and snap["pool_copies"] == 0
    assert snap["failed"] == 3 and not eng._active
    assert eng.supervisor.snapshot()["consecutive_failures"] == 1
    for h in handles[:3]:
        with pytest.raises(serving.DispatchFailedError) as exc:
            h.result(0)
        assert exc.value.reason == "engine"
        assert len(h.tokens_so_far()) <= at - 1     # what was committed
    assert not eng.pool.consumed() and eng.prefix_cache.cached_blocks() == 0
    assert [a.shape for a in _leaves(eng.pool.slabs)] == shapes
    assert not any(np.asarray(a).any() for a in _leaves(eng.pool.slabs))
    again = eng.submit(prompts[1], max_new_tokens=5)
    _drain(eng)
    np.testing.assert_array_equal(handles[3].result(0),
                                  _reference(gpt_tiny, prompts[3], 7))
    np.testing.assert_array_equal(again.result(0),
                                  _reference(gpt_tiny, prompts[1], 5))
    assert eng.metrics.snapshot()["pool_lost"] == 1 and not eng.broken
    eng.pool.check_balance()
    eng.stop()


def test_a_hang_whose_call_was_abandoned_counts_as_a_lost_pool(gpt_tiny):
    """The watchdog gives up on a call that may still run, and take the
    pool, later: the engine does not wait to see, nor retry beside it."""
    prompts = _prompts([6, 19])
    eng = _engine(gpt_tiny, dispatch_timeout_s=120.0, dispatch_retries=2)
    first = eng.submit(prompts[0], max_new_tokens=4)
    _drain(eng)                             # compiled, outside the budget
    # the first call outlasts the budget, then runs
    calls = _step_with(eng, before=lambda n: n > 1 or time.sleep(1.0))
    eng.supervisor.dispatch_timeout_s = 0.2
    hung = eng.submit(prompts[1], max_new_tokens=4)
    eng.pump()
    with pytest.raises(serving.DispatchFailedError) as exc:
        hung.result(0)
    assert exc.value.reason == "engine" and calls[0] == 1
    snap = eng.metrics.snapshot()
    assert snap["pool_lost"] == 1 and snap["dispatch_failures"] == {"hang": 1}
    time.sleep(1.2)                         # the abandoned call has run
    eng.supervisor.dispatch_timeout_s = 120.0
    again = eng.submit(prompts[1], max_new_tokens=4)
    _drain(eng)
    for h, p in ((first, prompts[0]), (again, prompts[1])):
        np.testing.assert_array_equal(h.result(0),
                                      _reference(gpt_tiny, p, 4))
    eng.stop()


# ---- (e) the contract readers rely on ---------------------------------------

def test_a_reference_kept_across_a_launch_is_a_deleted_array(gpt_tiny):
    eng = _engine(gpt_tiny)
    handle = eng.submit(_prompts([12])[0], max_new_tokens=4)
    held = eng.pool.slabs
    eng._admit()
    rec = eng._launch()
    assert all(a.is_deleted() for a in _leaves(held))
    assert not eng.pool.consumed()          # the attribute is the result
    held = eng.pool.slabs
    ahead = eng._launch(ahead_of=rec)       # donated a result not yet fetched
    assert all(a.is_deleted() for a in _leaves(held))
    # who reads the attribute when it works reads the newest result
    page = eng.pool.export_page(0, 8)
    assert len(page) == len(eng.pool.slabs) and page[0][0].any()
    assert eng.pool.kv_bytes()["full"] == sum(
        a.nbytes for a in _leaves(eng.pool.slabs))
    eng._retire(rec)
    eng._inflight = ahead
    _drain(eng)
    np.testing.assert_array_equal(
        handle.result(0), _reference(gpt_tiny, _prompts([12])[0], 4))
    eng.stop()
