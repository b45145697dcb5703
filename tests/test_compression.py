"""Quantized gradient collectives (ISSUE 4): blockwise int8 quantize/dequant
must be unbiased under stochastic rounding, the shard_map reduce-scatter +
all-gather collective must track lax.pmean within quantization tolerance
(and be EXACT at world size 1), and the end-to-end strategy wiring —
DistributedStrategy.quant_allreduce → StrategyCompiler → ShardedTrainStep /
ScanTrainStep / sync_gradients_fn / eager DataParallel buckets — must train
a small model to the same trajectory as the fp32 path within tolerance.
Satellites ride along: dtype-grouped eager grad buckets and the coalesced
DygraphShardingOptimizer broadcast."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer as optim
from paddle_tpu.distributed import DistributedStrategy
from paddle_tpu.distributed import compression as C
from paddle_tpu.distributed.fleet.strategy_compiler import StrategyCompiler
from paddle_tpu.distributed.strategy import QuantAllreduceConfig
from paddle_tpu.parallel import ScanTrainStep, ShardedTrainStep


def _mesh(n=2):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


# ---- quantize / dequantize numerics ----

def test_roundtrip_error_bounded():
    rng = np.random.RandomState(0)
    x = (rng.randn(2048) * 5).astype(np.float32)
    q, s = C.quantize_blockwise(jnp.asarray(x), 256, stochastic=False)
    assert q.dtype == jnp.int8 and s.shape == (8,)
    out = np.asarray(C.dequantize_blockwise(q, s))
    # round-to-nearest error is at most half an int8 step per block (bf16
    # scale storage adds ~0.4% relative slop)
    scale = np.abs(x).reshape(8, 256).max(axis=1) / 127
    bound = np.repeat(scale * 0.51, 256) + 0.005 * np.abs(x)
    assert (np.abs(out - x) <= bound + 1e-7).all()


def test_stochastic_rounding_unbiased():
    rng = np.random.RandomState(1)
    x = (rng.randn(4096) * 3).astype(np.float32)
    trials = 300
    acc = np.zeros_like(x)
    single = []
    for t in range(trials):
        out = np.asarray(C.quant_dequant(
            jnp.asarray(x), QuantAllreduceConfig(), jax.random.PRNGKey(t)))
        acc += out - x
        single.append(np.abs(out - x).mean())
    bias = np.abs(acc / trials).mean()
    # the mean error must average out: well below one trial's rounding noise
    assert bias < np.mean(single) / 5, (bias, np.mean(single))
    assert bias < 0.01


def test_quant_dequant_small_tensor_passthrough():
    x = jnp.arange(12, dtype=jnp.float32)
    out = C.quant_dequant(x, QuantAllreduceConfig(min_quant_numel=1024))
    assert out is x  # below min_quant_numel: untouched, zero noise


def test_zero_block_and_nonmultiple_length():
    # an all-zero block must dequantize to exact zeros (inv-scale 0, not
    # inf), and a length that needs padding must slice back losslessly
    x = np.zeros(300, np.float32)
    x[257] = 4.0
    out = np.asarray(C.quant_dequant(
        jnp.asarray(x), QuantAllreduceConfig(block_size=256,
                                             min_quant_numel=1)))
    assert out.shape == (300,)
    assert (out[:256] == 0).all()
    assert abs(out[257] - 4.0) < 4.0 / 127 + 1e-6


def test_config_validation():
    with pytest.raises(ValueError):
        QuantAllreduceConfig(dtype="int4").validate()
    with pytest.raises(ValueError):
        QuantAllreduceConfig(block_size=0).validate()


# ---- the collective ----

def test_quantized_allreduce_matches_pmean():
    mesh = _mesh(4)
    rng = np.random.RandomState(2)
    g = rng.randn(4, 5000).astype(np.float32)
    cfg = QuantAllreduceConfig(block_size=256)

    def f(x):
        return C.quantized_allreduce(x, "data", cfg, jax.random.PRNGKey(3))

    out = np.asarray(jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(g))
    ref = g.mean(axis=0)
    # every rank holds the same reduced value within quantization noise
    assert np.abs(out - ref[None]).max() < 0.1
    assert np.abs(out - ref[None]).mean() < 0.01


def test_quantized_allreduce_sum_mode():
    mesh = _mesh(4)
    rng = np.random.RandomState(3)
    g = rng.randn(4, 4096).astype(np.float32)
    cfg = QuantAllreduceConfig()

    def f(x):
        return C.quantized_allreduce(x, "data", cfg, jax.random.PRNGKey(0),
                                     average=False)

    out = np.asarray(jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(g))
    assert np.abs(out - g.sum(axis=0)[None]).max() < 0.4


def test_quantized_allreduce_world1_exact_identity():
    mesh = _mesh(1)
    g = np.random.RandomState(4).randn(1, 4096).astype(np.float32)

    def f(x):
        return C.quantized_allreduce(x, "data", QuantAllreduceConfig(),
                                     jax.random.PRNGKey(0))

    out = np.asarray(jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(g))
    assert np.array_equal(out, g)  # bit-exact: no wire, no quantization


def test_quantized_allreduce_small_leaf_full_precision():
    # below min_quant_numel the collective is a plain pmean — exact
    mesh = _mesh(4)
    g = np.random.RandomState(5).randn(4, 64).astype(np.float32)

    def f(x):
        return C.quantized_allreduce(
            x, "data", QuantAllreduceConfig(min_quant_numel=1024),
            jax.random.PRNGKey(0))

    out = np.asarray(jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(g))
    np.testing.assert_allclose(out, np.broadcast_to(g.mean(0), g.shape),
                               rtol=1e-6, atol=1e-6)


def test_sync_gradients_fn_comm_quant():
    from paddle_tpu.distributed.data_parallel import sync_gradients_fn
    mesh = _mesh(4)
    rng = np.random.RandomState(6)
    tree = {"w": rng.randn(4, 2048).astype(np.float32),
            "b": rng.randn(4, 16).astype(np.float32)}
    sync = sync_gradients_fn("data", comm_quant=QuantAllreduceConfig())

    def f(g):
        return sync(g, key=jax.random.PRNGKey(1))

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(tree)
    # large leaf: quantized tolerance; small leaf: exact pmean
    assert np.abs(np.asarray(out["w"]) - tree["w"].mean(0)[None]).max() < 0.1
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.broadcast_to(tree["b"].mean(0), (4, 16)),
                               rtol=1e-6, atol=1e-6)


# ---- wire-byte accounting ----

def test_comm_bytes_at_least_2x_saving():
    for n in (1 << 20, 10_000_000, 125_000_000):
        for w in (2, 4, 8, 256):
            fp32 = C.comm_bytes_per_step(n, w)
            q = C.comm_bytes_per_step(n, w, QuantAllreduceConfig())
            assert fp32 / q >= 2.0, (n, w, fp32 / q)
    # block 256: payload + 2/256 scale sidecar ≈ 3.97x
    assert C.comm_bytes_per_step(1 << 22, 8) / C.comm_bytes_per_step(
        1 << 22, 8, QuantAllreduceConfig()) > 3.9


def test_comm_bytes_world1_is_zero():
    assert C.comm_bytes_per_step(1 << 20, 1) == 0
    assert C.comm_bytes_per_step(1 << 20, 1, QuantAllreduceConfig()) == 0


# ---- strategy / compiler wiring ----

def test_compiler_quant_allreduce_plan():
    s = DistributedStrategy()
    assert s.quant_allreduce is False  # off by default
    plan = StrategyCompiler().compile(s)
    assert plan.comm_quant is None

    s.quant_allreduce = True
    s.quant_allreduce_configs = {"block_size": 128, "error_feedback": True}
    plan = StrategyCompiler().compile(s)
    assert plan.comm_quant is not None
    assert plan.comm_quant.block_size == 128
    assert plan.comm_quant.error_feedback is True
    assert "quant_allreduce" in plan.applied


def test_compiler_quant_flag_fallback():
    from paddle_tpu.flags import get_flags, set_flags
    old = get_flags("FLAGS_quant_allreduce")["FLAGS_quant_allreduce"]
    try:
        set_flags({"FLAGS_quant_allreduce": True})
        plan = StrategyCompiler().compile(DistributedStrategy())
        assert plan.comm_quant is not None
        # explicit strategy default-off is still overridable by the flag,
        # but flag off + strategy on must stay on
        set_flags({"FLAGS_quant_allreduce": False})
        s = DistributedStrategy()
        s.quant_allreduce = True
        assert StrategyCompiler().compile(s).comm_quant is not None
    finally:
        set_flags({"FLAGS_quant_allreduce": old})


def test_compiler_quant_supersedes_fp16_allreduce():
    s = DistributedStrategy()
    s.quant_allreduce = True
    s.fp16_allreduce = True
    with pytest.warns(UserWarning, match="supersedes fp16_allreduce"):
        plan = StrategyCompiler().compile(s)
    assert plan.comm_quant is not None
    assert plan.fp16_allreduce_dtype is None
    assert "fp16_allreduce" not in plan.applied


def test_compiler_localsgd_drops_quant():
    s = DistributedStrategy()
    s.quant_allreduce = True
    s.localsgd = True
    s.localsgd_configs = {"k_steps": 4}
    with pytest.warns(UserWarning, match="quant_allreduce"):
        plan = StrategyCompiler().compile(s)
    assert plan.comm_quant is None
    assert "quant_allreduce" not in plan.applied


# ---- end-to-end training parity ----

def _model_opt(lr=1e-2):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 32))
    opt = optim.AdamW(learning_rate=lr, parameters=model.parameters())
    return model, opt


def _batches(n=8):
    rng = np.random.RandomState(0)
    return [(rng.randn(4, 32).astype(np.float32),
             rng.randn(4, 32).astype(np.float32)) for _ in range(n)]


def _mse(out, y):
    return nn.functional.mse_loss(out, y)


def _quant_strategy(error_feedback=False):
    s = DistributedStrategy()
    s.quant_allreduce = True
    # the toy model's largest grad is 64x64; quantize everything
    s.quant_allreduce_configs = {"block_size": 64, "min_quant_numel": 1,
                                 "error_feedback": error_feedback}
    return s


def _run(mesh_n, strategy, cls=ShardedTrainStep, **kw):
    model, opt = _model_opt()
    mesh = _mesh(mesh_n)
    plan = StrategyCompiler().compile(strategy, opt, mesh)
    step = cls(model, opt, mesh, loss_fn=_mse, plan=plan, **kw)
    losses = [float(np.asarray(step(*b).data).reshape(-1)[-1])
              for b in _batches()]
    return losses, step


def test_e2e_parity_quant_on_vs_off():
    base_losses, base = _run(2, None)
    q_losses, q = _run(2, _quant_strategy())
    # quantization noise must not derail the trajectory
    np.testing.assert_allclose(q_losses, base_losses, rtol=0.05, atol=0.02)
    # What parity of the parameters can mean under AdamW. The bias-corrected
    # update |m1^| / sqrt(m2^) is at most 1.03 over the first 8 steps
    # (Cauchy-Schwarz over the two moving averages), so a step moves an
    # element by about lr whatever the size of its gradient. The quantiser's
    # error is unbiased and below one step (absmax / 127 of the element's
    # block of 64), so it turns an update around only where a gradient lies
    # within a step of zero -- or where, on a batch of 4 rows, the small
    # shift of the weights flips a ReLU unit on one row and with it that
    # unit's whole column of gradients (0.weight[26, 45] in the third step
    # here: +1.1e-2 against -6.6e-3 at weights 2e-5 apart). Such an element
    # then runs the other way at lr a step, 2 * lr * steps = 0.16 at the
    # most. An element-wise atol of 0.02, two steps' worth, failed on 1 of
    # 2,048 elements for three model seeds of six. So an element is held to
    # what a turned direction reaches in half the run, lr * steps = 0.08
    # (largest seen over six seeds 0.041), and a tensor, since turns are
    # rare, to a relative L2 error of 10% (seen: weights 0.9-1.6%, the
    # zero-initialised biases 0.9-5.9%; a trajectory that ignored the
    # gradients would sit near lr * steps / rms(p), 80%).
    lr, steps = 1e-2, len(q_losses)
    for k in base._params:
        got, want = np.asarray(q._params[k]), np.asarray(base._params[k])
        assert np.linalg.norm(got - want) <= 0.1 * np.linalg.norm(want), k
        assert np.abs(got - want).max() <= lr * steps, k
    assert q_losses[-1] < q_losses[0]  # it actually trains


def test_e2e_world1_exact_match():
    base_losses, base = _run(1, None)
    q_losses, q = _run(1, _quant_strategy())
    # no cross-rank reduction exists at world 1: quant must be a bit-exact
    # no-op (acceptance criterion)
    assert q_losses == base_losses
    for k in base._params:
        assert np.array_equal(np.asarray(q._params[k]),
                              np.asarray(base._params[k])), k


def test_e2e_scan_runner_quant_parity_with_eager():
    # ScanTrainStep reuses the parent's step fn: the merged grad quantizes
    # ONCE per apply boundary with the same fold_in(rng, ...) key stream,
    # so scan-fused and eager quantized runs must match exactly
    from paddle_tpu.parallel import stack_batches
    eager_losses, eager = _run(2, _quant_strategy())
    model, opt = _model_opt()
    mesh = _mesh(2)
    plan = StrategyCompiler().compile(_quant_strategy(), opt, mesh)
    step = ScanTrainStep(model, opt, mesh, scan_steps=4, loss_fn=_mse,
                         plan=plan)
    batches = _batches()
    scan_losses = []
    for c in range(2):
        chunk = stack_batches(batches[c * 4:(c + 1) * 4])
        scan_losses.extend(np.asarray(step(*chunk).data).tolist())
    np.testing.assert_allclose(scan_losses, eager_losses,
                               rtol=1e-5, atol=1e-6)
    for k in eager._params:
        np.testing.assert_allclose(
            np.asarray(step._params[k]), np.asarray(eager._params[k]),
            rtol=1e-5, atol=1e-6, err_msg=k)
    assert step.dispatch_count == 2


def test_e2e_error_feedback():
    losses, step = _run(2, _quant_strategy(error_feedback=True))
    assert "quant_ef" in step._extras  # residual rides in optimizer extras
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)
    # residuals are bounded by the quantization step, not exploding
    for k, r in step._extras["quant_ef"].items():
        assert np.isfinite(np.asarray(r)).all(), k
    base_losses, _ = _run(2, None)
    np.testing.assert_allclose(losses, base_losses, rtol=0.05, atol=0.02)


def test_e2e_gradient_merge_quantizes_merged_grad():
    # quant composes with gradient_merge: trajectory stays near fp32
    def with_merge(s):
        s.gradient_merge = True
        s.gradient_merge_configs = {"k_steps": 2}
        return s

    base_losses, _ = _run(2, with_merge(DistributedStrategy()))
    q_losses, _ = _run(2, with_merge(_quant_strategy()))
    np.testing.assert_allclose(q_losses, base_losses, rtol=0.05, atol=0.02)


# ---- satellites: eager bucket path ----

def test_bucket_grads_never_mix_dtypes():
    from paddle_tpu.distributed.data_parallel import _bucket_grads

    class FakeGrad:
        def __init__(self, n, dt):
            self.data = np.zeros(n, dt)

    class FakeParam:
        def __init__(self, n, dt):
            self.grad = FakeGrad(n, dt)

    params = [FakeParam(100, np.float32), FakeParam(100, np.float16),
              FakeParam(200, np.float32), FakeParam(50, np.float16),
              FakeParam(300, np.float32)]
    buckets = _bucket_grads(params, comm_buffer_size_mb=25)
    assert sum(len(b) for b in buckets) == len(params)
    for b in buckets:
        dts = {np.dtype(p.grad.data.dtype) for p in b}
        assert len(dts) == 1, dts  # native-dtype reduce, no fp32 up-cast


def test_bucket_grads_respects_byte_cap_per_dtype():
    from paddle_tpu.distributed.data_parallel import _bucket_grads

    class FakeGrad:
        def __init__(self, n, dt):
            self.data = np.zeros(n, dt)

    class FakeParam:
        def __init__(self, n, dt):
            self.grad = FakeGrad(n, dt)

    # 4 x 1MB fp32 grads with a 2MB cap -> 2 buckets of 2
    params = [FakeParam(256 * 1024, np.float32) for _ in range(4)]
    buckets = _bucket_grads(params, comm_buffer_size_mb=2)
    assert [len(b) for b in buckets] == [2, 2]


def test_bucket_mean_keeps_native_dtype():
    from paddle_tpu.distributed.data_parallel import _bucket_mean
    x = jnp.asarray(np.random.RandomState(7).randn(512), jnp.bfloat16)
    out = _bucket_mean(x)
    assert out.dtype == jnp.bfloat16  # wire moves bf16, not up-cast fp32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(x, np.float32), rtol=1e-2)


def test_quantized_bucket_mean_roundtrip():
    from paddle_tpu.distributed.data_parallel import _quantized_bucket_mean
    x = (np.random.RandomState(8).randn(4096) * 2).astype(np.float32)
    cfg = QuantAllreduceConfig(block_size=256, min_quant_numel=1)
    out = np.asarray(_quantized_bucket_mean(jnp.asarray(x), cfg, 1))
    assert out.shape == x.shape
    assert np.abs(out - x).max() < 0.1  # single process: mean == dequant(q)


def test_dataparallel_quant_config_from_strategy_and_flag():
    from paddle_tpu.distributed import DataParallel
    from paddle_tpu.flags import get_flags, set_flags
    model = nn.Linear(4, 4)
    assert DataParallel(model)._comm_quant is None
    s = DistributedStrategy()
    s.quant_allreduce = True
    s.quant_allreduce_configs = {"block_size": 128}
    dp = DataParallel(model, strategy=s)
    assert dp._comm_quant is not None and dp._comm_quant.block_size == 128
    old = get_flags("FLAGS_quant_allreduce")["FLAGS_quant_allreduce"]
    try:
        set_flags({"FLAGS_quant_allreduce": True})
        assert DataParallel(model)._comm_quant is not None
    finally:
        set_flags({"FLAGS_quant_allreduce": old})


# ---- satellite: coalesced sharding broadcast ----

def test_sharding_sync_coalesces_broadcasts(monkeypatch):
    from jax.experimental import multihost_utils
    from paddle_tpu.distributed.fleet.dygraph_sharding_optimizer import (
        DygraphShardingOptimizer)

    class HCG:
        def get_sharding_parallel_world_size(self):
            return 2

        def get_sharding_parallel_rank(self):
            return 0

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 8), nn.Linear(8, 8))
    opt = optim.SGD(learning_rate=0.1, parameters=model.parameters())
    sharded = DygraphShardingOptimizer(opt, hcg=HCG())

    calls = []

    def fake_broadcast(x, is_source):
        calls.append(np.asarray(x).size)
        return x

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "broadcast_one_to_all",
                        fake_broadcast)
    before = {id(p): np.asarray(p.data).copy()
              for p in sharded._full_parameter_list}
    sharded._sharding_sync_parameters()
    # 6 params (3 weights + 3 biases, all fp32) over 2 owners -> exactly one
    # flattened broadcast per owner, NOT one per param
    assert len(calls) == 2, calls
    assert sum(calls) == sum(arr.size for arr in before.values())
    for p in sharded._full_parameter_list:
        np.testing.assert_array_equal(np.asarray(p.data), before[id(p)])


def test_sharding_sync_groups_by_dtype(monkeypatch):
    from jax.experimental import multihost_utils
    from paddle_tpu.distributed.fleet.dygraph_sharding_optimizer import (
        DygraphShardingOptimizer)

    class HCG:
        def get_sharding_parallel_world_size(self):
            return 2

        def get_sharding_parallel_rank(self):
            return 0

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.Linear(8, 8))
    # force one param per owner to bf16: each owner needs 2 broadcasts
    params = list(model.parameters())
    opt = optim.SGD(learning_rate=0.1, parameters=params)
    sharded = DygraphShardingOptimizer(opt, hcg=HCG())
    for owner_params in sharded._rank2params.values():
        if owner_params:
            owner_params[-1].data = jnp.asarray(
                np.asarray(owner_params[-1].data), jnp.bfloat16)

    calls = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "broadcast_one_to_all",
                        lambda x, is_source: (calls.append(x.dtype), x)[1])
    sharded._sharding_sync_parameters()
    owners_with_params = sum(
        1 for ps in sharded._rank2params.values() if ps)
    assert len(calls) == 2 * owners_with_params  # one per (owner, dtype)
