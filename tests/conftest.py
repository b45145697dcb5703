"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding logic
runs everywhere (SURVEY §4 implication: multi-node logic tested without a cluster).

Tests are the CPU way of running the program (JAX_PLATFORMS=cpu, 8 virtual
devices via XLA_FLAGS); the chip way is `python chip_smoke.py` through the
builder's chip tool."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_state():
    yield
    from paddle_tpu.core.tensor import reset_tape
    reset_tape()


@pytest.fixture()
def mesh8():
    """A 2x1x2x2 (data/pipe/sharding/model) mesh over the 8 CPU devices.
    Tears the global hybrid group down so mp_degree doesn't leak into
    unrelated tests."""
    from paddle_tpu.distributed import DistributedStrategy, fleet
    from paddle_tpu.distributed.topology import _GLOBAL_HCG, _GLOBAL_MESH
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    yield hcg.build_mesh()
    _GLOBAL_HCG[0] = None
    _GLOBAL_MESH[0] = None


# ---- test tiering ----
# Modules auto-marked `slow` stay out of tier-1 (`-m "not slow"`), which the
# driver runs with `-n 6 --dist loadfile` under a 1,470 s limit (342 s at PR
# 26). The files that guard the benchmark's layers are NOT here:
# test_attention (flash kernels), test_parallel (SPMD step), test_generation
# (the engine tests' bit-identity reference). What is left is heavy
# (multi-device shard_map compiles, cross-process fixtures, model zoos) and
# is reviewed against that limit in ROADMAP.md C8.
_SLOW_MODULES = {
    "test_pipeline", "test_pipeline_compose",
    "test_strategy_compiler", "test_sequence_parallel",
    "test_ring_attention", "test_moe",
    "test_multiprocess_dist", "test_metrics_elastic", "test_vision_models",
    "test_amp", "test_softmax_ce",
    "test_cpp_predictor", "test_op_numerics_batch3",
    "test_op_numerics_batch4", "test_op_numerics_batch5",
    "test_highlevel", "test_beam_search",
    "test_interleaved_pipeline", "test_parameter_server",
    "test_strategy_flags",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy multi-device/model tests (excluded from "
        "tier-1 via -m 'not slow')")
    config.addinivalue_line(
        "markers", "fault_matrix: end-to-end fault-injection recovery "
        "scenarios (subprocess-based); run standalone via "
        "tools/check_fault_matrix.py, and in tier-1 as part of "
        "tests/test_resilient.py and tests/test_serving.py")
    config.addinivalue_line(
        "markers", "serving: online-serving runtime tests (batching engine, "
        "HTTP front end, drain); select with -m serving")
    config.addinivalue_line(
        "markers", "comm: communication-compression tests (quantized "
        "gradient collectives, distributed/compression.py); select with "
        "-m comm")
    config.addinivalue_line(
        "markers", "llm: continuous-batching LLM decode-engine tests "
        "(slot-paged KV pool, serving/llm/); select with -m llm")
    config.addinivalue_line(
        "markers", "paged: ragged paged attention + chunked prefill tests "
        "(ops/paged_attention.py parity suite, device block tables, "
        "chunk-granular scheduling); select with -m paged")
    config.addinivalue_line(
        "markers", "prefix: prefix-sharing radix KV cache + multi-tenant "
        "serving tests (serving/llm/prefix_cache.py, shared block pool, "
        "COW, tenant fairness); select with -m prefix")
    config.addinivalue_line(
        "markers", "obs: observability tests (request tracing, flight "
        "recorder, prometheus exposition; paddle_tpu/obs/); select with "
        "-m obs")
    config.addinivalue_line(
        "markers", "router: multi-replica serving tier tests (breaker-aware "
        "router, failover re-prefill, quarantine ladder; serving/router.py); "
        "select with -m router")
    config.addinivalue_line(
        "markers", "deploy: zero-downtime rolling weight deployment tests "
        "(drain/swap/canary/re-admit, fleet auto-rollback; "
        "serving/deploy.py); select with -m deploy")
    config.addinivalue_line(
        "markers", "spec: speculative-decoding tests (draft propose + "
        "single-dispatch verify, greedy accept/rollback, bit-identity; "
        "ISSUE 17); select with -m spec")
    config.addinivalue_line(
        "markers", "sampling: per-slot seeded sampling + grammar-"
        "constrained decoding tests (RNG lanes, token DFA masks, "
        "failover counter restore; ISSUE 18); select with -m sampling")
    config.addinivalue_line(
        "markers", "tiered: tiered KV cache + disaggregation tests "
        "(host-RAM spill/onboard round trips, prefill→decode handoff "
        "bit-identity, per-token logprobs; ISSUE 19); select with "
        "-m tiered")
    config.addinivalue_line(
        "markers", "lora: multi-LoRA fine-tune-and-serve tests (adapter "
        "injection/training, per-slot bank indirection in the unified "
        "step, hot swap/rollback, adapter KV namespaces; ISSUE 20); "
        "select with -m lora")


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__ if item.module else ""
        if mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        if mod == "test_serving":
            item.add_marker(pytest.mark.serving)
        if mod == "test_compression":
            item.add_marker(pytest.mark.comm)
        if mod == "test_llm_engine":
            item.add_marker(pytest.mark.llm)
        if mod == "test_paged_attention":
            item.add_marker(pytest.mark.paged)
        if mod == "test_prefix_cache":
            item.add_marker(pytest.mark.prefix)
            item.add_marker(pytest.mark.llm)
        if mod in ("test_obs", "test_goodput", "test_serving_ledger"):
            item.add_marker(pytest.mark.obs)
        if mod == "test_router":
            item.add_marker(pytest.mark.router)
            item.add_marker(pytest.mark.serving)
        if mod == "test_deploy":
            item.add_marker(pytest.mark.deploy)
            item.add_marker(pytest.mark.serving)
        if mod == "test_spec_decode":
            item.add_marker(pytest.mark.spec)
            item.add_marker(pytest.mark.llm)
            item.add_marker(pytest.mark.serving)
        if mod == "test_sampling":
            item.add_marker(pytest.mark.sampling)
            item.add_marker(pytest.mark.llm)
            item.add_marker(pytest.mark.serving)
        if mod == "test_tiered":
            item.add_marker(pytest.mark.tiered)
            item.add_marker(pytest.mark.llm)
            item.add_marker(pytest.mark.serving)
        if mod == "test_lora":
            item.add_marker(pytest.mark.lora)
            item.add_marker(pytest.mark.llm)
            item.add_marker(pytest.mark.serving)
