"""Benchmark entry: prints ONE JSON line with the headline metric.

Runs a GPT-scale causal-LM training step (bf16, jit/SPMD path) on the
default JAX backend and reports tokens/sec/chip + MFU vs the BASELINE north
star. Runs in-process and fails when it fails: no retry, no probe, no CPU
fallback, no preset swapped by backend, and an exception in any mode is a
traceback and a non-zero exit. Every row names the device it ran on
(`extra.provenance`: platform, device_kind, device_count); a CPU row is
asked for by name (`JAX_PLATFORMS=cpu BENCH_PRESET=gpt2-tiny`) and is a
control-flow check, not a device number.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

def _peak_flops(device_kind: str, backend: str) -> float:
    """Per-chip peak bf16 FLOP/s — delegated to the shared accounting in
    paddle_tpu.obs.flops (ISSUE 10) so bench-reported and live MFU use
    one peak table; a device_kind not in it raises."""
    from paddle_tpu.obs.flops import peak_flops
    return peak_flops(device_kind, backend)


def _provenance() -> dict:
    """Measurement provenance embedded in every row (ISSUE 9): platform,
    device kind, git sha, and wall time — so tools/check_bench_result.py
    can refuse to gate a CPU number against a TPU pin (and a stale pinned
    row is traceable back to the commit that produced it)."""
    import os
    import subprocess

    import jax
    devices = jax.devices()
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"  # no git on the machine / not a checkout
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "git_sha": sha,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _default_blocks():
    from paddle_tpu.ops.attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


def run_bench():
    import jax
    import jax.numpy as jnp
    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.models.llama import LlamaForCausalLM

    import os
    # per-preset (batch, seq, remat, moment_dtype) defaults, sized to one
    # v5e chip (16 GB). gpt3-1.3b: fp32 adam moments alone are 10.5 GB, so
    # the preset runs bf16 moments + remat (BASELINE config 2's model at
    # single-chip scale; multi-chip DP is the production config).
    _PRESETS = {
        "gpt3-125m": (8, 1024, False, "float32"),
        "gpt3-350m": (8, 1024, False, "float32"),
        "gpt3-1.3b": (4, 1024, True, "bfloat16"),
        "ernie-moe-base": (8, 1024, False, "float32"),  # BASELINE config 5
        "resnet50": (64, 224, False, "float32"),        # BASELINE config 1
        # control-flow size for a CPU run; only ever picked by name
        "gpt2-tiny": (2, 128, False, "float32"),
    }
    # the preset is what was asked for, whatever the backend
    preset = os.environ.get("BENCH_PRESET", "gpt3-125m")
    if preset.endswith("-decode"):
        return _run_decode_bench(jax, jnp, backend, on_tpu, preset)
    B, S, remat, moment_dtype = _PRESETS.get(
        preset, (8, 1024, False, "float32"))
    B = int(os.environ.get("BENCH_BS", B))
    S = int(os.environ.get("BENCH_SEQ", S))
    remat = os.environ.get("BENCH_REMAT", "1" if remat else "0") == "1"
    moment_dtype = os.environ.get("BENCH_MOMENT_DTYPE", moment_dtype)
    paddle.seed(0)
    rng = np.random.RandomState(0)
    if preset == "resnet50":
        # BASELINE config 1: ResNet-50 fwd+bwd (metric: images/sec/chip).
        # MACs from the hapi flops counter (fwd); x2 MAC->FLOP, x3 fwd+bwd.
        model = paddle.vision.models.resnet50(num_classes=1000)
        fwd_flops = float(paddle.flops(model, input_size=[1, 3, S, S]))
        if on_tpu:
            model.to(dtype="bfloat16")
        ce = paddle.nn.CrossEntropyLoss()

        class _Clf(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.net = model

            def forward(self, x, y):
                return ce(self.net(x), y)

        model = _Clf()
        cfg = None
        ids = paddle.to_tensor(rng.randn(B, 3, S, S).astype(np.float32))
        if on_tpu:  # match the bf16-cast model (no AMP in the bench step)
            ids = ids.astype("bfloat16")
        labels = paddle.to_tensor(rng.randint(0, 1000, (B,)))
    else:
        family = LlamaForCausalLM if preset.startswith("llama") \
            else GPTForCausalLM
        overrides = {"use_recompute": True} if remat else {}
        model = family.from_preset(preset, **overrides)
        if on_tpu:
            model.to(dtype="bfloat16")
        cfg = model.config
        ids = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (B, S)).astype(np.int32))
        labels = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (B, S)).astype(np.int32))
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      moment_dtype=moment_dtype)

    params, _buffers = model.functional_state()  # kept for the MFU count

    # Run the measured loop ON DEVICE through the SHARED scan-fused runner
    # (parallel.ScanTrainStep): one fused chunk of `iters` steps amortizes
    # host dispatch over the chunk — and since this is the same runner the
    # production trainer path uses, the measured number is the number users
    # get (no private bench-only loop).
    from jax.sharding import Mesh

    from paddle_tpu.parallel import ScanTrainStep

    iters = 32
    mesh = Mesh(np.array(jax.devices()), ("data",))
    step = ScanTrainStep(model, opt, mesh, scan_steps=iters, zero_stage=0)

    def chunk(t):
        arr = np.asarray(t.data)
        return np.broadcast_to(arr, (iters,) + arr.shape).copy()

    ids_chunk, labels_chunk = chunk(ids), chunk(labels)
    # compile observatory (ISSUE 12): armed BEFORE warmup so the one-time
    # AOT lower/compile for the chunk executable lands in the warmup
    # region, keeping the timed region unpolluted; the registry rows
    # (executable count, compile seconds) are gated as CEILINGs
    from paddle_tpu.obs.compile_observatory import compile_observatory
    observatory = compile_observatory().enable()
    observatory.reset()
    step.observatory = observatory
    # warmup / compile (one full chunk; scan compiles the body once)
    losses = step(ids_chunk, labels_chunk)
    _ = float(np.asarray(losses.data)[-1])  # host read: the chunk is done
    observatory.mark_warm()

    n_chips = jax.device_count()
    unit_name = "images" if preset == "resnet50" else "tokens"
    tokens_per_step = B if preset == "resnet50" else B * S
    device_kind = jax.devices()[0].device_kind
    peak = _peak_flops(device_kind, backend)

    # MFU: 6 * params * tokens FLOPs (fwd+bwd) vs the chip's actual peak,
    # via the SHARED accounting (paddle_tpu.obs.flops, ISSUE 10) — the
    # same helpers the live MFU gauge uses, so the two cannot diverge by
    # formula. MoE models count ACTIVE params; conv models use measured
    # fwd MACs x2 (MAC->FLOP) x3 (fwd + ~2x bwd) per image.
    from paddle_tpu.obs import flops as flops_acct
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    moe_E = getattr(cfg, "moe_num_experts", 0) if cfg is not None else 0
    if preset == "resnet50":
        flops_per_step = flops_acct.conv_train_flops_per_step(fwd_flops, B)
    elif moe_E:
        top_k = getattr(cfg, "moe_top_k", 2)
        # expert params come from the MoELayer module structure (all its
        # params minus the gate) — not from key substring matching, which a
        # renamed expert/gate param would silently skew
        from paddle_tpu.nn.layer.moe import MoELayer
        expert_keys = set()
        for lname, sub in model.named_sublayers():
            if isinstance(sub, MoELayer):
                for pname, _ in sub.named_parameters(prefix=lname):
                    if not pname.endswith("gate_weight"):
                        expert_keys.add(pname)
        expert = sum(int(np.prod(p.shape)) for k, p in params.items()
                     if k in expert_keys)
        flops_per_step = flops_acct.train_flops_per_step(
            n_params, tokens_per_step, expert_params=expert,
            moe_top_k=top_k, moe_num_experts=moe_E)
    else:
        flops_per_step = flops_acct.train_flops_per_step(
            n_params, tokens_per_step)

    # Goodput ledger over the timed region (ISSUE 10): warmup compiles are
    # behind us (mark_warm), so any further compile counts as a recompile;
    # the ledger's live MFU must agree with the offline number below
    # because both divide the same flops_per_step by the same peak.
    from paddle_tpu.obs.goodput import GoodputLedger, RecompileSentinel
    ledger = GoodputLedger()
    sentinel = RecompileSentinel(ledger).install()
    sentinel.mark_warm()
    step.ledger = ledger  # caller-thread H2D staging books as h2d
    ledger.set_flops(flops_per_step, peak * n_chips)
    ledger.start()

    # the host read of the final loss ends the timed region: dispatch is
    # asynchronous, the value is not there until the chunk has run
    t0 = time.perf_counter()
    with ledger.measure("compute"):
        losses = step(ids_chunk, labels_chunk)
        final_loss = float(np.asarray(losses.data)[-1])
    ledger.add_steps(iters)
    dt = (time.perf_counter() - t0) / iters
    goodput_snap = ledger.snapshot()
    sentinel.uninstall()
    compile_snap = observatory.snapshot()
    observatory.disable()

    tokens_per_sec_chip = tokens_per_step / dt / n_chips
    achieved = flops_per_step / dt / n_chips
    mfu = achieved / peak

    # numerics-observatory overhead ceiling (ISSUE 13): rebuild the SAME
    # chunked runner with in-step telemetry armed (per-group grad/param
    # norms + update ratios computed inside the jitted chunk), warm it,
    # and time one chunk. The delta vs the unarmed timed region above is
    # the price of arming — gated as a CEILING so the telemetry can never
    # silently grow into the step. The unarmed region keeps the existing
    # floors untouched.
    step.sync_to_model()  # the first step donated the model's own buffers
    step_armed = ScanTrainStep(model, opt, mesh, scan_steps=iters,
                               zero_stage=0, numerics=True)
    warm = step_armed(ids_chunk, labels_chunk)
    _ = float(np.asarray(warm.data)[-1])
    t1 = time.perf_counter()
    losses_armed = step_armed(ids_chunk, labels_chunk)
    _ = float(np.asarray(losses_armed.data)[-1])
    dt_armed = (time.perf_counter() - t1) / iters
    numerics_overhead_pct = max(0.0, (dt_armed - dt) / dt * 100.0)
    numerics_sample = step_armed.numerics_host_sample() or {}
    train_grad_norm = numerics_sample.get("grad_norm/_total")

    result = {
        "metric": f"{unit_name}/sec/chip {preset} bs{B} seq{S} "
                  f"{'bf16' if on_tpu else 'fp32-cpu'} fused train step "
                  f"chunked{iters}",
        "value": round(tokens_per_sec_chip, 1),
        "unit": f"{unit_name}/sec/chip",
        "vs_baseline": round(mfu, 4),
        "extra": {
            "loss": final_loss,
            "step_ms": round(dt * 1e3, 2),
            "params_m": round(n_params / 1e6, 1),
            "mfu": round(mfu, 4),
            "backend": backend,
            "device_kind": device_kind,
            "peak_tflops": peak / 1e12,
            "n_chips": n_chips,
            "remat": remat,
            "moment_dtype": moment_dtype,
            "scan_steps": iters,
            "dispatches": step.dispatch_count,
            # ISSUE 10 live-telemetry rows (gated as floors; TPU-only via
            # the provenance platform pinning)
            "train_goodput": round(goodput_snap["goodput"], 4),
            "train_mfu_live": (round(goodput_snap["mfu"], 4)
                               if goodput_snap["mfu"] is not None else None),
            "train_recompiles": sentinel.recompiles,
            # ISSUE 12 compile-observatory rows (gated as ceilings: more
            # executables or compile seconds than the baseline means the
            # bench step sprouted extra program variants)
            "compile_executables": compile_snap["executables"],
            "compile_seconds_total": compile_snap["compile_seconds_total"],
            # ISSUE 13 numerics-observatory rows: the armed-step overhead
            # is gated as a CEILING; the grad norm is a provenance-stamped
            # info row (never gated — it tracks the model, not the code)
            "train_numerics_overhead_pct": round(numerics_overhead_pct, 2),
            "train_grad_norm": (round(train_grad_norm, 4)
                                if train_grad_norm is not None else None),
            "train_phase_seconds": {
                k: round(v, 4)
                for k, v in goodput_snap["phase_seconds"].items()},
            "flash_block_q": os.environ.get(
                "FLAGS_flash_block_q", str(_default_blocks()[0])),
            "flash_block_k": os.environ.get(
                "FLAGS_flash_block_k", str(_default_blocks()[1])),
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def _run_decode_bench(jax, jnp, backend, on_tpu, preset):
    """Serving-path benchmark: KV-cache autoregressive decode tokens/sec
    via models/generation.py (prefill + one on-device decode loop, two
    dispatches). Decode MFU uses 2ND (fwd only)."""
    import os
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.models.llama import LlamaForCausalLM

    # preset -> (model preset, batch, prompt len, new tokens)
    _DECODE = {
        "llama2-tiny-decode": ("llama2-tiny", 4, 32, 32),
        "gpt3-125m-decode": ("gpt3-125m", 8, 128, 128),
        "gpt3-1.3b-decode": ("gpt3-1.3b", 4, 128, 128),
    }
    base, B, S0, new = _DECODE[preset]
    B = int(os.environ.get("BENCH_BS", B))
    S0 = int(os.environ.get("BENCH_SEQ", S0))
    new = int(os.environ.get("BENCH_NEW_TOKENS", new))
    paddle.seed(0)
    family = LlamaForCausalLM if base.startswith("llama") else GPTForCausalLM
    model = family.from_preset(base)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, model.config.vocab_size, (B, S0)).astype(np.int32))
    out = model.generate(ids, max_new_tokens=new)  # warmup/compile
    _ = np.asarray(out.data)  # host read: warm-up has finished
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new)
    _ = np.asarray(out.data)
    dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    params, _b = model.functional_state()
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    toks = B * new
    tok_s = toks / dt / n_chips
    device_kind = jax.devices()[0].device_kind
    peak = _peak_flops(device_kind, backend)
    from paddle_tpu.obs.flops import decode_flops_per_token
    mfu = decode_flops_per_token(n_params) * toks / dt / n_chips / peak
    result = {
        "metric": f"decode tokens/sec/chip {base} bs{B} prompt{S0} "
                  f"new{new} {'bf16' if on_tpu else 'fp32-cpu'} kv-cache",
        "value": round(tok_s, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu, 4),
        "extra": {
            "decode_ms_per_token": round(dt / new * 1e3, 3),
            "params_m": round(n_params / 1e6, 1),
            "mfu_2nd": round(mfu, 4),
            "backend": backend,
            "device_kind": device_kind,
            "peak_tflops": peak / 1e12,
            "n_chips": n_chips,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def run_serve_bench():
    """Serving-runtime benchmark (ISSUE 3): replays a seeded Poisson arrival
    trace through the REAL serving stack — a static-export MLP behind
    BatchingEngine.from_predictor on the threaded wall-clock scheduler — and
    reports sustained req/sec plus tail latency. The row gates through
    tools/check_bench_result.py's direction-aware keys (serve_qps floor,
    serve_p99_ms ceiling)."""
    import os
    import tempfile

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import inference, nn, serving

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "512"))
    rate_hz = float(os.environ.get("BENCH_SERVE_RATE_HZ", "3000"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "16"))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "2.0"))
    backend = jax.default_backend()

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 8))
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "serve_mlp")
        inference.export_model(
            model, [np.ones((max_batch, 16), np.float32)], path)
        pred = inference.load_predictor(path)
        # compile every pow2 bucket the engine can form BEFORE the timed
        # replay — a mid-trace jit compile would show up as a fake p99 spike
        b = 1
        while b <= max_batch:
            pred.run([np.zeros((b, 16), np.float32)])
            b *= 2

        engine = serving.BatchingEngine.from_predictor(
            pred, serving.EngineConfig(
                max_batch_size=max_batch, max_wait_ms=max_wait_ms,
                max_queue_depth=max(4 * max_batch, 64)))
        engine.start()
        rng = np.random.RandomState(0)
        gaps = rng.exponential(1.0 / rate_hz, size=n_req)
        reqs = [rng.rand(1, 16).astype(np.float32) for _ in range(n_req)]

        futs, rejected = [], 0
        t0 = time.perf_counter()
        t_next = t0
        for gap, x in zip(gaps, reqs):
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                futs.append(engine.submit([x]))
            except serving.RejectedError:
                rejected += 1
        for f in futs:
            try:
                f.result(timeout=60)
            except Exception:
                pass
        dt = time.perf_counter() - t0
        engine.stop(drain=True)

    snap = engine.metrics.snapshot()
    qps = snap["completed"] / dt if dt > 0 else 0.0
    result = {
        "metric": f"req/sec serve-mlp maxb{max_batch} wait{max_wait_ms}ms "
                  f"poisson{int(rate_hz)}",
        "value": round(qps, 1),
        "unit": "req/sec",
        "vs_baseline": 0.0,
        "extra": {
            "serve_qps": round(qps, 1),
            "serve_p50_ms": round(snap["p50_ms"] or 0.0, 3),
            "serve_p95_ms": round(snap["p95_ms"] or 0.0, 3),
            "serve_p99_ms": round(snap["p99_ms"] or 0.0, 3),
            "dispatches": snap["dispatches"],
            "mean_batch_rows": round(snap["mean_batch_rows"], 2),
            "completed": snap["completed"],
            "rejected": snap["rejected"] + rejected,
            "expired": snap["expired"],
            "backend": backend,
            "n_requests": n_req,
            "rate_hz": rate_hz,
            "max_batch": max_batch,
            "max_wait_ms": max_wait_ms,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def _poisson_prompt_trace(rng, n, rate_hz, vocab, min_len=3, max_len=13,
                          max_new=None, min_new=None, len_fn=None):
    """ONE seeded Poisson prompt trace (ISSUE 17): every serving bench
    phase that replays an open-loop prompt trace draws it here so two
    replays from equal-seeded states are token-identical — the spec phase
    replays the SAME trace spec-off then spec-on and diffs the streams
    bit-for-bit. `rng` is an int seed (a fresh RandomState is built) or a
    live RandomState to continue. Draw order is lens → gaps → prompt
    bodies → new_lens; changing it changes every trace, so don't.

    Returns (prompts, gaps, new_lens); new_lens is None unless max_new is
    given (then uniform[min_new or max(2, max_new//4), max_new]).
    `len_fn(rng, i) -> int` overrides the uniform[min_len, max_len)
    prompt-length draw per request (the mixed phase's every-4th-long
    shape)."""
    if not isinstance(rng, np.random.RandomState):
        rng = np.random.RandomState(rng)
    if len_fn is None:
        lens = [int(s) for s in rng.randint(min_len, max_len, size=n)]
    else:
        lens = [int(len_fn(rng, i)) for i in range(n)]
    gaps = rng.exponential(1.0 / rate_hz, size=n)
    prompts = [rng.randint(1, vocab, size=s).astype(np.int32) for s in lens]
    new_lens = None
    if max_new is not None:
        lo = max(2, max_new // 4) if min_new is None else min_new
        new_lens = rng.randint(lo, max_new + 1, size=n)
    return prompts, gaps, new_lens


def run_llm_bench():
    """LLM decode-engine benchmark (ISSUE 5): replays a seeded Poisson
    prompt trace through the REAL continuous-batching stack — a tiny
    GPT/LLaMA causal-LM behind serving.llm.LLMEngine on the threaded
    wall-clock scheduler with a slot-paged KV pool — and reports sustained
    generated tokens/sec plus TTFT tail. The row gates through
    tools/check_bench_result.py's direction-aware keys (llm_tok_s floor,
    llm_ttft_ms CEILING)."""
    import os

    import jax

    from paddle_tpu.serving import LLMMetrics, RejectedError
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig

    preset = os.environ.get("BENCH_LLM_PRESET", "gpt2-tiny")
    n_req = int(os.environ.get("BENCH_LLM_REQUESTS", "24"))
    rate_hz = float(os.environ.get("BENCH_LLM_RATE_HZ", "50"))
    num_slots = int(os.environ.get("BENCH_LLM_SLOTS", "4"))
    max_new = int(os.environ.get("BENCH_LLM_MAX_NEW", "16"))
    backend = jax.default_backend()

    if preset.startswith("llama"):
        from paddle_tpu.models.llama import LlamaForCausalLM
        model = LlamaForCausalLM.from_preset(preset)
    else:
        from paddle_tpu.models.gpt import GPTForCausalLM
        model = GPTForCausalLM.from_preset(preset)
    vocab = model.config.vocab_size if hasattr(model, "config") else 512

    engine = LLMEngine(model, LLMEngineConfig(
        num_slots=num_slots, block_len=8,
        # slots must fit the mixed phase's long prompts (<= 64 tokens)
        n_blocks=max(4, -(-(64 + max_new) // 8)),
        max_queue_depth=max(4 * num_slots, 64),
        economics=True))
    # register analytic decode FLOPs so the ledger's effective decode MFU
    # uses the SAME obs.flops arithmetic as run_decode_bench's offline row
    from paddle_tpu.obs.flops import decode_flops_per_token
    params, _b = model.functional_state()
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    device_kind = jax.devices()[0].device_kind
    engine.ledger.set_decode_flops(
        decode_flops_per_token(n_params),
        _peak_flops(device_kind, backend) * jax.device_count())
    engine.start()

    rng = np.random.RandomState(0)
    prompts, gaps, new_lens = _poisson_prompt_trace(
        rng, n_req, rate_hz, vocab, max_new=max_new)

    # ONE warmup request compiles the engine's single unified mixed
    # prefill+decode executable (ISSUE 7: the per-pow2-bucket prefill zoo
    # is gone — prompt length no longer selects an executable), so no
    # mid-trace jit compile can show up as a fake TTFT spike
    engine.generate(prompts[0], max_new_tokens=2, timeout=300)
    engine.metrics = LLMMetrics()   # warmup rows don't count
    engine.metrics.set_slots(engine.pool.active_slots(),
                             engine.pool.num_slots)
    engine.metrics.ledger = engine.ledger   # re-attach economics providers
    engine.metrics.burn = engine.burn       # after the metrics reset
    engine.ledger.reset()   # warmup compile doesn't count as pump economics

    handles, rejected = [], 0
    t0 = time.perf_counter()
    t_next = t0
    for gap, p, m in zip(gaps, prompts, new_lens):
        t_next += gap
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            handles.append(engine.submit(p, max_new_tokens=int(m)))
        except RejectedError:
            rejected += 1
    for h in handles:
        try:
            h.result(timeout=120)
        except Exception:
            pass
    dt = time.perf_counter() - t0

    snap = engine.metrics.snapshot()
    # serving economics (ISSUE 11): the steady-state window's ledger view
    # — token efficiency + decode MFU gate as floors, host fraction as a
    # ceiling, through tools/check_bench_result.py
    led = engine.ledger.snapshot()
    # generated tokens include each sequence's first (prefill) token
    total_tokens = snap["tokens_out"] + snap["prefills"]
    tok_s = total_tokens / dt if dt > 0 else 0.0
    ttft_p95 = snap["ttft_p95_ms"] or 0.0
    result = {
        "metric": f"tok/sec llm-{preset} slots{num_slots} "
                  f"poisson{int(rate_hz)}",
        "value": round(tok_s, 1),
        "unit": "tok/sec",
        "vs_baseline": 0.0,
        "extra": {
            "llm_tok_s": round(tok_s, 1),
            "llm_ttft_ms": round(ttft_p95, 3),
            "llm_ttft_p50_ms": round(snap["ttft_p50_ms"] or 0.0, 3),
            "llm_intertoken_p50_ms": round(
                snap["intertoken_p50_ms"] or 0.0, 3),
            "llm_intertoken_p99_ms": round(
                snap["intertoken_p99_ms"] or 0.0, 3),
            "decode_steps": snap["decode_steps"],
            "mean_active_rows": round(snap["mean_batch_rows"], 2),
            "llm_token_efficiency": round(
                led["token_efficiency"] or 0.0, 4),
            "llm_decode_mfu": round(led["decode_mfu"] or 0.0, 6),
            "llm_host_fraction": round(led["host_fraction"], 4),
            "llm_dispatches": led["dispatches"],
            "llm_compute_seconds": round(led["compute_seconds"], 4),
            "llm_tenant_device_seconds": {
                t: round(v["device_seconds"], 4)
                for t, v in led["tenants"].items()},
            "completed": snap["completed"],
            "rejected": snap["rejected"] + rejected,
            "expired": snap["expired"],
            "backend": backend,
            "n_requests": n_req,
            "rate_hz": rate_hz,
            "num_slots": num_slots,
            "max_new_tokens": max_new,
            "provenance": _provenance(),
        },
    }

    # ---- mixed long/short phase (ISSUE 7): Poisson trace where every 4th
    # prompt is LONG (40-56 tokens) and the rest are short. Chunked prefill
    # admits long prompts as fixed-width chunks folded into the decode
    # dispatch, so a short prompt arriving behind a long one is never
    # head-of-line blocked behind a whole-prompt prefill. Gates (lower is
    # better): llm_mixed_ttft_p99_ms (short-prompt TTFT tail) and
    # llm_prefill_dispatches (steps carrying ONLY prefill rows — chunk
    # folding should keep this near the slot count, not the request count)
    if os.environ.get("BENCH_LLM_MIXED", "1") != "0":
        n_mixed = int(os.environ.get("BENCH_LLM_MIXED_REQUESTS",
                                     str(max(n_req, 16))))
        mixed_hz = float(os.environ.get("BENCH_LLM_MIXED_RATE_HZ",
                                        str(rate_hz)))
        engine.metrics = LLMMetrics()
        engine.metrics.set_slots(engine.pool.active_slots(),
                                 engine.pool.num_slots)
        engine.metrics.ledger = engine.ledger
        engine.metrics.burn = engine.burn
        pd0 = engine.prefill_dispatches
        m_prompts, m_gaps, _ = _poisson_prompt_trace(
            rng, n_mixed, mixed_hz, vocab,
            len_fn=lambda r, i: (r.randint(40, 57) if i % 4 == 0
                                 else r.randint(3, 9)))
        m_handles, m_rejected = [], 0
        m_new = max(2, max_new // 2)
        t_next = time.perf_counter()
        for gap, p in zip(m_gaps, m_prompts):
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                m_handles.append((len(p), engine.submit(
                    p, max_new_tokens=m_new)))
            except RejectedError:
                m_rejected += 1
        for _, h in m_handles:
            try:
                h.result(timeout=120)
            except Exception:
                pass
        short_ttfts = [h.ttft_ms for plen, h in m_handles
                       if plen <= 8 and h.ttft_ms is not None]
        mixed_p99 = (float(np.percentile(short_ttfts, 99))
                     if short_ttfts else 0.0)
        result["extra"].update({
            "llm_mixed_ttft_p99_ms": round(mixed_p99, 3),
            "llm_prefill_dispatches":
                int(engine.prefill_dispatches - pd0),
            "mixed_requests": n_mixed,
            "mixed_rejected": m_rejected,
        })

    # ---- prefix-overlap phase (ISSUE 8): a trace where 90% of prompts
    # share one 32-token prefix (the "same system prompt" serving shape).
    # The radix prefix cache should attach the shared blocks and prefill
    # only each suffix, so the token-weighted hit rate (llm_prefix_hit_rate)
    # and the effective prompt-token service rate (llm_shared_prefill_tok_s
    # = prompt tokens admitted / wall time, cached tokens served for free)
    # both gate as FLOORS through check_bench_result.py
    if os.environ.get("BENCH_LLM_PREFIX", "1") != "0":
        n_pref = int(os.environ.get("BENCH_LLM_PREFIX_REQUESTS",
                                    str(max(n_req, 16))))
        pref_hz = float(os.environ.get("BENCH_LLM_PREFIX_RATE_HZ",
                                       str(rate_hz)))
        shared = rng.randint(1, vocab, size=32).astype(np.int32)
        # seed the cache OUTSIDE the timed window so the steady-state
        # shape (prefix already hot) is what gets measured
        engine.generate(shared, max_new_tokens=2, timeout=120)
        engine.metrics = LLMMetrics()
        engine.metrics.set_slots(engine.pool.active_slots(),
                                 engine.pool.num_slots)
        engine.metrics.ledger = engine.ledger
        engine.metrics.burn = engine.burn
        pt0 = engine.prefill_tokens
        suffixes, p_gaps, _ = _poisson_prompt_trace(
            rng, n_pref, pref_hz, vocab, min_len=3, max_len=7)
        p_handles, p_rejected = [], 0
        p_new = max(2, max_new // 2)
        pt_start = time.perf_counter()
        t_next = pt_start
        for i, (gap, sfx) in enumerate(zip(p_gaps, suffixes)):
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            p = (np.concatenate([shared, sfx]) if i % 10 else sfx)
            try:
                p_handles.append(engine.submit(p, max_new_tokens=p_new))
            except RejectedError:
                p_rejected += 1
        for h in p_handles:
            try:
                h.result(timeout=120)
            except Exception:
                pass
        p_dt = time.perf_counter() - pt_start
        psnap = engine.metrics.snapshot()
        served_prompt_tokens = psnap["prefix_lookup_tokens"]
        result["extra"].update({
            "llm_prefix_hit_rate": round(psnap["prefix_hit_rate"], 4),
            "llm_shared_prefill_tok_s": round(
                served_prompt_tokens / p_dt if p_dt > 0 else 0.0, 1),
            "prefix_requests": n_pref,
            "prefix_rejected": p_rejected,
            "prefix_hits": psnap["prefix_hits"],
            "prefix_prefill_tokens_computed":
                int(engine.prefill_tokens - pt0),
            "prefix_cached_blocks": psnap["cached_blocks"],
            "prefix_cache_evictions": psnap["cache_evictions"],
        })

    # ---- overload phase (ISSUE 6): drive the SAME warm engine at ~2x its
    # measured service rate with a mixed-SLO trace and tight admission
    # limits, proving overload control holds the interactive tail: sheds
    # stay confined to lower classes (llm_shed_rate) while interactive p99
    # TTFT gates as a CEILING through check_bench_result.py
    if os.environ.get("BENCH_LLM_OVERLOAD", "1") != "0":
        served_hz = snap["completed"] / dt if dt > 0 else rate_hz
        over_hz = max(2.0 * served_hz, 2.0 * rate_hz)
        n_over = int(os.environ.get("BENCH_LLM_OVERLOAD_REQUESTS",
                                    str(max(2 * n_req, 32))))
        # tighten admission on the live engine (config is read at each
        # submit): small queue + a binding token budget so shedding and
        # brownout actually engage at 2x load
        engine.config.max_queue_depth = max(2 * num_slots, 8)
        engine.config.max_inflight_tokens = \
            (num_slots + engine.config.max_queue_depth) * (12 + max_new)
        engine.config.brownout_queue_depth = engine.config.max_queue_depth // 2
        from paddle_tpu.serving import LLMMetrics as _LLMMetrics
        engine.metrics = _LLMMetrics()
        engine.metrics.set_slots(engine.pool.active_slots(),
                                 engine.pool.num_slots)
        engine.metrics.ledger = engine.ledger
        engine.metrics.burn = engine.burn
        classes = ["interactive", "batch", "best_effort"]
        cls_trace = [classes[i % 4 % 3] for i in range(n_over)]  # 50% i/25/25
        o_prompts, o_gaps, _ = _poisson_prompt_trace(
            rng, n_over, over_hz, vocab)
        o_handles, o_rejected = [], 0
        t_next = time.perf_counter()
        for gap, p, c in zip(o_gaps, o_prompts, cls_trace):
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                o_handles.append(engine.submit(
                    p, max_new_tokens=max_new, slo=c))
            except RejectedError:
                o_rejected += 1
        for h in o_handles:
            try:
                h.result(timeout=120)
            except Exception:
                pass
        osnap = engine.metrics.snapshot()
        interactive_p99 = osnap["ttft_p99_ms_interactive"]
        result["extra"].update({
            "llm_shed_rate": round(osnap["shed_rate"], 4),
            "llm_interactive_ttft_p99_ms": round(interactive_p99 or 0.0, 3),
            "overload_rate_hz": round(over_hz, 1),
            "overload_requests": n_over,
            "overload_shed_by_class": {
                c: osnap["classes"][c]["shed"] for c in classes},
            "overload_rejected_at_submit": o_rejected,
            "overload_brownout_entries": osnap["brownout_entries"],
        })
    engine.stop(drain=True)

    # ---- speculative-decoding phase (ISSUE 17): replay ONE seeded prompt
    # trace batch-1 and closed-loop through two fresh engines — the plain
    # target, then the same target with a draft model attached (the draft
    # IS the target here, so greedy acceptance is deterministic) — and
    # compare pure decode speed. Greedy spec decode is bit-identical BY
    # CONSTRUCTION; the phase reports it (llm_spec_bitmatch) and gates
    # llm_spec_tok_s and llm_spec_accept_rate as FLOORS through
    # check_bench_result.py: the win is dispatch-count collapse — one
    # draft-scan dispatch + one verify dispatch advance up to spec_k+1
    # positions that plain decode buys with spec_k+1 pump round-trips.
    if os.environ.get("BENCH_LLM_SPEC", "1") != "0":
        n_spec = int(os.environ.get("BENCH_LLM_SPEC_REQUESTS", "6"))
        spec_new = int(os.environ.get("BENCH_LLM_SPEC_MAX_NEW",
                                      str(max(16, max_new))))
        spec_k = int(os.environ.get("BENCH_LLM_SPEC_K", "4"))

        def replay(draft):
            eng = LLMEngine(model, LLMEngineConfig(
                num_slots=1, block_len=8,
                n_blocks=max(4, -(-(16 + spec_new) // 8)),
                max_queue_depth=64, spec_k=spec_k),
                draft_model=draft)
            eng.start()
            # warm long enough that a draft window actually runs: the
            # propose-scan executable compiles on the FIRST proposal (a
            # 2-token warmup never proposes — remaining < 2), and that
            # one-time compile must not land inside the timed replay
            eng.generate([1, 2, 3], max_new_tokens=2 * spec_k, timeout=300)
            eng.metrics = LLMMetrics()   # warmup rows don't count
            eng.metrics.set_slots(eng.pool.active_slots(),
                                  eng.pool.num_slots)
            prompts, _, _ = _poisson_prompt_trace(0, n_spec, rate_hz, vocab)
            t0 = time.perf_counter()
            streams = [eng.generate(p, max_new_tokens=spec_new, timeout=300)
                       for p in prompts]
            s_dt = time.perf_counter() - t0
            s_snap = eng.metrics.snapshot()
            eng.stop(drain=True)
            return streams, s_dt, s_snap

        base_streams, base_dt, _bsnap = replay(None)
        spec_streams, spec_dt, ssnap = replay(model)
        n_tok = int(sum(s.size for s in base_streams))
        bitmatch = (len(base_streams) == len(spec_streams) and all(
            np.array_equal(a, b)
            for a, b in zip(base_streams, spec_streams)))
        spec_tok_s = n_tok / spec_dt if spec_dt > 0 else 0.0
        base_tok_s = n_tok / base_dt if base_dt > 0 else 0.0
        result["extra"].update({
            "llm_spec_tok_s": round(spec_tok_s, 1),
            "llm_spec_base_tok_s": round(base_tok_s, 1),
            "llm_spec_speedup": (round(spec_tok_s / base_tok_s, 4)
                                 if base_tok_s > 0 else None),
            "llm_spec_accept_rate": round(
                ssnap["spec_accept_rate"] or 0.0, 4),
            "llm_spec_bitmatch": bool(bitmatch),
            "spec_windows": ssnap["spec_windows"],
            "spec_drafted": ssnap["spec_drafted"],
            "spec_accepted": ssnap["spec_accepted"],
            "spec_requests": n_spec,
            "spec_k": spec_k,
        })

    # ---- seeded sampling + constrained decoding phase (ISSUE 18): the
    # same closed-loop replay idiom as the spec phase, through three
    # fresh engines — greedy baseline, per-request seeded
    # temperature/top-p sampling, and grammar-constrained JSON decoding.
    # Gates: llm_sampled_tok_s is a FLOOR (the batched on-device
    # sampling lane must stay within ~10% of greedy — same dispatch
    # count, same fixed-width step, only the select differs) and
    # llm_mask_overhead_pct a CEILING (host-side sampling-operand
    # assembly as a fraction of pump wall time, from the ledger's
    # sample_mask phase). llm_sampled_bitmatch reports seeded-replay
    # determinism: the identical trace re-run is token-identical.
    if os.environ.get("BENCH_LLM_SAMPLED", "1") != "0":
        from paddle_tpu.serving.llm import SamplingParams
        n_samp = int(os.environ.get("BENCH_LLM_SAMPLED_REQUESTS", "6"))
        samp_new = int(os.environ.get("BENCH_LLM_SAMPLED_MAX_NEW",
                                      str(max(16, max_new))))

        def sampled_replay(sp_of):
            eng = LLMEngine(model, LLMEngineConfig(
                num_slots=1, block_len=8,
                n_blocks=max(4, -(-(16 + samp_new) // 8)),
                max_queue_depth=64, economics=True))
            eng.start()
            eng.generate([1, 2, 3], max_new_tokens=2, timeout=300,
                         sampling=sp_of(0))   # compile the unified step
            eng.metrics = LLMMetrics()   # warmup rows don't count
            eng.metrics.set_slots(eng.pool.active_slots(),
                                  eng.pool.num_slots)
            eng.ledger.reset()
            prompts, _, _ = _poisson_prompt_trace(0, n_samp, rate_hz,
                                                  vocab)
            t0 = time.perf_counter()
            streams = [eng.generate(p, max_new_tokens=samp_new,
                                    timeout=300, sampling=sp_of(i + 1))
                       for i, p in enumerate(prompts)]
            s_dt = time.perf_counter() - t0
            s_led = eng.ledger.snapshot()
            eng.stop(drain=True)
            return streams, s_dt, s_led

        base_streams, base_dt, _ = sampled_replay(lambda i: None)
        sp_of = lambda i: SamplingParams(temperature=0.8, top_p=0.95,
                                         seed=1000 + i)
        samp_streams, samp_dt, _ = sampled_replay(sp_of)
        replay_streams, _, _ = sampled_replay(sp_of)
        bitmatch = (len(samp_streams) == len(replay_streams) and all(
            np.array_equal(a, b)
            for a, b in zip(samp_streams, replay_streams)))
        # constrained pass: every request decodes a JSON object under the
        # same compiled token-DFA; mask overhead is measured HERE, where
        # the grammar bank actually gates logits
        gtok = {1: "{", 2: "}", 3: '"a"', 4: ":", 5: "1", 6: "23",
                7: ",", 8: '"b"', 9: "true", 10: "false"}
        gschema = {"type": "object",
                   "properties": {"a": {"type": "integer"},
                                  "b": {"type": "boolean"}},
                   "required": ["a", "b"]}
        gsp = lambda i: SamplingParams(
            temperature=1.0, seed=7000 + i,
            grammar={"schema": gschema, "tokens": gtok})
        con_streams, _con_dt, con_led = sampled_replay(gsp)
        # validity = the actual contract: every emitted token legal from
        # the DFA state its predecessors reached (a stream truncated by
        # max_new_tokens mid-number is still grammar-clean)
        from paddle_tpu.serving.llm import compile_grammar
        gdfa = compile_grammar({"schema": gschema, "tokens": gtok},
                               vocab, None)

        def _grammar_clean(s):
            st = 0
            for t in s:
                st = int(gdfa.trans[st, int(t)])
                if st < 0:
                    return False
            return True

        con_valid = all(_grammar_clean(s) for s in con_streams)
        n_tok = int(sum(s.size for s in base_streams))
        n_stok = int(sum(s.size for s in samp_streams))
        base_tok_s = n_tok / base_dt if base_dt > 0 else 0.0
        samp_tok_s = n_stok / samp_dt if samp_dt > 0 else 0.0
        wall = con_led["wall_seconds"]
        mask_pct = (100.0 * con_led["phase_seconds"]["sample_mask"]
                    / wall if wall > 0 else 0.0)
        result["extra"].update({
            "llm_sampled_tok_s": round(samp_tok_s, 1),
            "llm_sampled_base_tok_s": round(base_tok_s, 1),
            "llm_sampled_ratio": (round(samp_tok_s / base_tok_s, 4)
                                  if base_tok_s > 0 else None),
            "llm_mask_overhead_pct": round(mask_pct, 4),
            "llm_sampled_bitmatch": bool(bitmatch),
            "llm_constrained_valid": bool(con_valid),
            "sampled_requests": n_samp,
        })
    # ---- tiered KV + disaggregation phase (ISSUE 19): two sub-phases.
    # (a) Spill/onboard: a deliberately tiny device pool (2 slots) replays
    # a prompt set whose cached working set exceeds it, so pressure
    # eviction spills full-block KV pages into the host-RAM tier; the
    # SAME trace replayed warm then onboards those pages back instead of
    # re-prefilling. llm_tiered_hit_rate is the fraction of the warm
    # pass's onboardable full-block prompt tokens actually served from
    # the host tier (a FLOOR: device-cache hits don't count, so a
    # regression that stops spilling or stops onboarding drops it), and
    # llm_onboard_tok_s is the host→HBM onboard rate over the warm pass
    # (FLOOR). (b) Disaggregation: a prefill-role + decode-role fleet on
    # the wall clock runs a few streams end to end; llm_handoff_ms is the
    # p99 export→re-place latency from the router's handoff summary
    # (CEILING — the whole point of staging KV is that the stream never
    # waits on a re-prefill).
    if os.environ.get("BENCH_LLM_TIERED", "1") != "0":
        n_tier = int(os.environ.get("BENCH_LLM_TIERED_PROMPTS", "6"))
        tier_new = int(os.environ.get("BENCH_LLM_TIERED_MAX_NEW", "4"))
        t_eng = LLMEngine(model, LLMEngineConfig(
            num_slots=2, block_len=8, n_blocks=4,
            host_kv_bytes=int(os.environ.get(
                "BENCH_LLM_HOST_KV_BYTES", str(64 << 20))),
            max_queue_depth=64, economics=True))
        t_eng.start()
        t_eng.generate([1, 2, 3], max_new_tokens=2, timeout=300)  # compile
        t_rng = np.random.RandomState(19)
        # 17 tokens = 2 full blocks + tail; 6 prompts vs 2 cacheable rows
        t_prompts = [t_rng.randint(1, vocab, size=(17,)).astype(np.int32)
                     for _ in range(n_tier)]
        for p in t_prompts:           # cold pass: fill, then spill
            t_eng.generate(p, max_new_tokens=tier_new, timeout=300)
        onboard0 = t_eng.host_onboard_tokens
        t0 = time.perf_counter()
        for p in t_prompts:           # warm pass: onboard from host
            t_eng.generate(p, max_new_tokens=tier_new, timeout=300)
        warm_dt = time.perf_counter() - t0
        onboard_tok = t_eng.host_onboard_tokens - onboard0
        # tokens the onboard walk could have served: full blocks below
        # the one-token-always-prefills cap (17 tokens -> 16)
        bl = t_eng.config.block_len
        onboardable = sum(((p.size - 1) // bl) * bl for p in t_prompts)
        host_snap = t_eng.host_kv.snapshot()
        t_eng.stop(drain=True)

        from paddle_tpu.serving import InProcessReplica, ReplicaRouter
        mk_eng = lambda: LLMEngine(model, LLMEngineConfig(
            num_slots=4, block_len=8, n_blocks=4, max_queue_depth=64))
        reps = [InProcessReplica(mk_eng(), 0, role="prefill"),
                InProcessReplica(mk_eng(), 1, role="decode")]
        router = ReplicaRouter(reps)
        n_hand = int(os.environ.get("BENCH_LLM_HANDOFF_STREAMS", "3"))
        hs = [router.submit(
                  t_rng.randint(1, vocab, size=(9,)).astype(np.int32),
                  max_new_tokens=8)
              for _ in range(n_hand)]
        steps = 0
        while router.has_work():
            router.pump()
            steps += 1
            assert steps < 200000, "disagg fleet failed to drain"
        for h in hs:
            h.result(timeout=0)
        rsnap = router.metrics.snapshot()
        handoff_ms = router.metrics.handoff_quantile_ms(0.99)
        result["extra"].update({
            "llm_tiered_hit_rate": (round(onboard_tok / onboardable, 4)
                                    if onboardable else 0.0),
            "llm_onboard_tok_s": round(
                onboard_tok / warm_dt if warm_dt > 0 else 0.0, 1),
            "llm_handoff_ms": (round(handoff_ms, 3)
                               if handoff_ms is not None else None),
            "llm_host_spills": host_snap["spills"],
            "llm_host_pages": host_snap["pages"],
            "llm_handoffs": rsnap["handoffs"],
            "llm_handoffs_failed": rsnap["handoffs_failed"],
            "tiered_prompts": n_tier,
        })
    # ---- multi-LoRA phase (ISSUE 20): ONE seeded Poisson trace replayed
    # twice — base-only through an UNARMED engine, then through an
    # adapter-armed engine with 8 concurrent adapters round-robined over
    # the requests, so every dispatch mixes rows of several adapters in
    # the one unified step. llm_lora_tok_s (FLOOR) is the armed pass's
    # throughput; llm_lora_overhead_pct (CEILING, ≤15% at pin time) is
    # the armed-vs-base drop — the gathered low-rank delta must stay a
    # marginal cost of the step, never per-adapter dispatches. The
    # analytic per-token adapter FLOPs (obs.flops.
    # lora_decode_flops_per_token) ride along ungated for sizing.
    if os.environ.get("BENCH_LLM_LORA", "1") != "0":
        from paddle_tpu.obs.flops import lora_decode_flops_per_token
        from paddle_tpu.tuning import target_sites
        n_lora = int(os.environ.get("BENCH_LLM_LORA_REQUESTS", "12"))
        lora_hz = float(os.environ.get("BENCH_LLM_LORA_RATE_HZ",
                                       str(rate_hz)))
        lora_new = int(os.environ.get("BENCH_LLM_LORA_MAX_NEW", "8"))
        n_adapters = int(os.environ.get("BENCH_LLM_LORA_ADAPTERS", "8"))
        lora_rank = int(os.environ.get("BENCH_LLM_LORA_RANK", "4"))
        l_rng = np.random.RandomState(20)
        l_prompts, l_gaps, l_new = _poisson_prompt_trace(
            l_rng, n_lora, lora_hz, vocab, max_new=lora_new)

        def _lora_replay(eng, adapter_ids):
            lh = []
            t0 = time.perf_counter()
            t_next = t0
            for i, (gap, p, m) in enumerate(
                    zip(l_gaps, l_prompts, l_new)):
                t_next += gap
                delay = t_next - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                kw = ({"adapter": adapter_ids[i % len(adapter_ids)]}
                      if adapter_ids else {})
                try:
                    lh.append(eng.submit(p, max_new_tokens=int(m), **kw))
                except RejectedError:
                    pass
            toks = 0
            for h in lh:
                try:
                    toks += int(h.result(timeout=120).size)
                except Exception:
                    pass
            return toks, time.perf_counter() - t0

        mk_cfg = lambda **kw: LLMEngineConfig(
            num_slots=num_slots, block_len=8,
            n_blocks=max(4, -(-(64 + max_new) // 8)),
            max_queue_depth=max(4 * num_slots, 64), **kw)
        b_eng = LLMEngine(model, mk_cfg())
        b_eng.start()
        b_eng.generate(l_prompts[0], max_new_tokens=2, timeout=300)
        b_toks, b_dt = _lora_replay(b_eng, None)
        b_eng.stop(drain=True)

        l_eng = LLMEngine(model, mk_cfg(max_adapters=n_adapters,
                                        lora_rank=lora_rank))
        l_eng.start()
        # synthetic adapters in the bank's exact canonical layout: small
        # random deltas (nonzero B so the gathered matmul does real work)
        sites, _arch = target_sites(model)
        aids = []
        for a in range(n_adapters):
            a_rng = np.random.RandomState(100 + a)
            tree = {
                str(i): {
                    name: {"A": (0.01 * a_rng.randn(
                                lora_rank, io[0])).astype(np.float32),
                           "B": (0.01 * a_rng.randn(
                                io[1], lora_rank)).astype(np.float32)}
                    for name, io in layer.items()}
                for i, layer in enumerate(sites)}
            aid = f"bench-ad{a}"
            l_eng.register_adapter(aid, tree)
            aids.append(aid)
        l_eng.generate(l_prompts[0], max_new_tokens=2, timeout=300)
        l_toks, l_dt = _lora_replay(l_eng, aids)
        adapter_tokens = dict(
            l_eng.metrics.snapshot().get("adapter_tokens", {}))
        l_eng.stop(drain=True)
        lora_base_tok_s = b_toks / b_dt if b_dt > 0 else 0.0
        lora_tok_s = l_toks / l_dt if l_dt > 0 else 0.0
        overhead_pct = (100.0 * (lora_base_tok_s - lora_tok_s)
                        / lora_base_tok_s if lora_base_tok_s > 0 else 0.0)
        dims_flat = [io for layer in sites for io in layer.values()]
        result["extra"].update({
            "llm_lora_tok_s": round(lora_tok_s, 1),
            "llm_lora_base_tok_s": round(lora_base_tok_s, 1),
            "llm_lora_overhead_pct": round(overhead_pct, 4),
            "llm_lora_flops_per_token": lora_decode_flops_per_token(
                lora_rank, dims_flat),
            "llm_lora_adapter_tokens": adapter_tokens,
            "lora_adapters": n_adapters,
            "lora_rank": lora_rank,
            "lora_requests": n_lora,
        })
    print(json.dumps(result))


def run_comm_bench():
    """Communication microbenchmark (ISSUE 4): times one grad-sized
    all-reduce over the full device mesh — fp32 pmean vs the blockwise int8
    quantized reduce-scatter/all-gather (distributed/compression.py) — and
    reports the analytic bytes-on-wire for both. The row gates through
    tools/check_bench_result.py's CEILING keys (comm_bytes_per_step,
    allreduce_ms), so the compression ratio is a pinned, regression-proof
    number."""
    import os

    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.compression import (
        QuantAllreduceConfig, comm_bytes_per_step, quantized_allreduce)

    backend = jax.default_backend()
    # ~a gpt3-125m gradient's worth of elements by default
    numel = int(os.environ.get("BENCH_COMM_NUMEL", str(4 * 1024 * 1024)))
    block = int(os.environ.get("BENCH_COMM_BLOCK", "256"))
    iters = int(os.environ.get("BENCH_COMM_ITERS", "20"))
    cfg = QuantAllreduceConfig(block_size=block)
    devs = jax.devices()
    W = len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    rng = np.random.RandomState(0)
    x = rng.randn(W, numel).astype(np.float32)

    def fp32_sync(g):
        return jax.lax.pmean(g, "data")

    def quant_sync(g):
        return quantized_allreduce(g, "data", cfg, jax.random.PRNGKey(0))

    def sm(f):
        return jax.jit(shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))

    xd = jax.device_put(x, NamedSharding(mesh, P("data")))

    def time_fn(fn):
        jax.block_until_ready(fn(xd))  # compile + warmup
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(xd)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    fp32_ms = time_fn(sm(fp32_sync))
    quant_ms = time_fn(sm(quant_sync))
    bytes_fp32 = comm_bytes_per_step(numel, W)
    bytes_q = comm_bytes_per_step(numel, W, cfg)
    ratio = (bytes_fp32 / bytes_q) if bytes_q else 0.0
    result = {
        "metric": f"bytes/step comm-allreduce n{numel} w{W} block{block} "
                  "int8-rs-ag",
        "value": bytes_q,
        "unit": "bytes/step",
        "vs_baseline": round(ratio, 2),
        "tag": "comm-allreduce",
        "extra": {
            "comm_bytes_per_step": bytes_q,
            "comm_bytes_fp32": bytes_fp32,
            "bytes_ratio": round(ratio, 2),
            "allreduce_ms": round(quant_ms, 3),
            "allreduce_fp32_ms": round(fp32_ms, 3),
            "backend": backend,
            "world": W,
            "numel": numel,
            "block_size": block,
            "iters": iters,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def run_fleet_bench():
    """Multi-replica serving-tier benchmark (ISSUE 14): replays ONE seeded
    Poisson prompt trace through a ReplicaRouter over 1, 2, and 4
    in-process LLMEngine replicas (each on its own threaded wall-clock
    scheduler; XLA releases the GIL during dispatch, so replica compute
    overlaps) and reports the throughput scaling vs the single-replica
    run — then kills a replica mid-decode on the largest fleet and times
    the zero-dropped-streams failover: crash to every victim stream
    re-placed on a survivor. Gates through tools/check_bench_result.py:
    fleet_qps_scaling is a FLOOR, fleet_failover_resume_ms a CEILING."""
    import os

    import jax

    from paddle_tpu.serving import (InProcessReplica, LLMMetrics,
                                    RejectedError, ReplicaRouter,
                                    RouterConfig)
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig

    preset = os.environ.get("BENCH_FLEET_PRESET", "gpt2-tiny")
    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", "24"))
    rate_hz = float(os.environ.get("BENCH_FLEET_RATE_HZ", "400"))
    num_slots = int(os.environ.get("BENCH_FLEET_SLOTS", "4"))
    max_new = int(os.environ.get("BENCH_FLEET_MAX_NEW", "8"))
    failover_new = int(os.environ.get("BENCH_FLEET_FAILOVER_NEW", "32"))
    sizes = [int(s) for s in os.environ.get(
        "BENCH_FLEET_SIZES", "1,2,4").split(",")]
    backend = jax.default_backend()

    if preset.startswith("llama"):
        from paddle_tpu.models.llama import LlamaForCausalLM
        model = LlamaForCausalLM.from_preset(preset)
    else:
        from paddle_tpu.models.gpt import GPTForCausalLM
        model = GPTForCausalLM.from_preset(preset)
    vocab = model.config.vocab_size if hasattr(model, "config") else 512

    def mk_replica(i):
        eng = LLMEngine(model, LLMEngineConfig(
            num_slots=num_slots, block_len=8,
            # slots must fit the failover phase's longest stream
            n_blocks=max(4, -(-(16 + max(max_new, failover_new)) // 8)),
            max_queue_depth=max(8 * num_slots, 64)))
        eng.start()
        # warm each replica's unified step executable so no mid-trace jit
        # compile shows up as fake routing latency
        eng.generate([1, 2, 3], max_new_tokens=2, timeout=300)
        eng.metrics = LLMMetrics()
        eng.metrics.set_slots(0, eng.pool.num_slots)
        return InProcessReplica(eng, i)

    # ONE seeded trace replayed identically over every fleet size — the
    # scaling numbers compare fleets, never traces
    prompts, gaps, _ = _poisson_prompt_trace(0, n_req, rate_hz, vocab)

    qps = {}
    rejected_total = 0
    last_router, last_reps = None, None
    for n in sizes:
        reps = [mk_replica(i) for i in range(n)]
        router = ReplicaRouter(
            reps, RouterConfig(poll_interval_s=0.002)).start()
        handles = []
        t0 = time.perf_counter()
        t_next = t0
        for gap, p in zip(gaps, prompts):
            t_next += gap
            delay = t_next - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                handles.append(router.submit(p, max_new_tokens=max_new))
            except RejectedError:
                rejected_total += 1
        for h in handles:
            h.result(timeout=300)
        qps[n] = len(handles) / (time.perf_counter() - t0)
        if n == sizes[-1]:
            last_router, last_reps = router, reps
        else:
            router.stop(drain=True)

    # ---- failover resume timing: kill replica0 mid-decode on the
    # largest fleet; the ceiling is crash -> every victim stream either
    # finished from its harvest or re-placed on a survivor
    resume_ms = None
    n_victims = resumed_delta = 0
    if last_reps is not None and len(last_reps) >= 2:
        fh = [last_router.submit(p, max_new_tokens=failover_new)
              for p in prompts[:2 * len(last_reps)]]
        # wait for first-token emission fleet-wide so the kill provably
        # lands MID-decode (a fixed sleep lets fast backends finish early)
        t_wait = time.perf_counter()
        while (any(len(h.tokens_so_far()) == 0 for h in fh)
               and time.perf_counter() - t_wait < 30):
            time.sleep(0.001)
        dead = last_reps[0]
        victims = [h for h in fh
                   if h._replica is dead and not h.future.done()]
        n_victims = len(victims)
        base_resumed = last_router.metrics.snapshot()["resumed_streams"]
        t0 = time.perf_counter()
        dead.crash()
        while any(not h.future.done()
                  and (h._replica is None or h._replica is dead)
                  for h in victims):
            if time.perf_counter() - t0 > 120:
                break
            time.sleep(0.002)
        resume_ms = (time.perf_counter() - t0) * 1e3
        for h in fh:                # zero dropped: every stream completes
            assert h.result(timeout=300).size == failover_new
        resumed_delta = (last_router.metrics.snapshot()["resumed_streams"]
                         - base_resumed)
    if last_router is not None:
        last_router.stop(drain=True)

    base = qps[sizes[0]]
    scaling = {n: (qps[n] / base if base > 0 else 0.0) for n in sizes}
    result = {
        "metric": f"qps/fleet fleet-{preset} x{sizes[-1]} "
                  f"slots{num_slots}",
        "value": round(scaling[sizes[-1]], 3),
        "unit": "x vs 1 replica",
        "vs_baseline": 0.0,
        "extra": {
            "fleet_qps_scaling": round(scaling[sizes[-1]], 4),
            "fleet_failover_resume_ms": (round(resume_ms, 3)
                                         if resume_ms is not None else None),
            "fleet_qps": {str(n): round(q, 2) for n, q in qps.items()},
            "fleet_scaling": {str(n): round(s, 4)
                              for n, s in scaling.items()},
            "fleet_victims": n_victims,
            "fleet_resumed_streams": resumed_delta,
            "rejected": rejected_total,
            "backend": backend,
            "n_requests": n_req,
            "rate_hz": rate_hz,
            "num_slots": num_slots,
            "max_new_tokens": max_new,
            "fleet_sizes": sizes,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def run_deploy_bench():
    """Rolling-deploy benchmark (ISSUE 16): replays a seeded Poisson
    prompt trace over a live 4-replica ReplicaRouter fleet WHILE a
    DeploymentController rolls a certified WeightSet (numerically
    identical params published as "v2") across every replica —
    drain → swap → canary → re-admit, one replica at a time. Reports the
    p99 TTFT measured across the whole rollout window and the number of
    admitted streams that failed to complete. Gates through
    tools/check_bench_result.py: `deploy_ttft_p99_ms` is a CEILING
    (the drain/swap churn must not starve admissions) and
    `deploy_dropped_streams` MUST stay 0 — the zero-downtime contract
    itself."""
    import os
    import tempfile

    import jax

    from paddle_tpu.checkpoint import WeightSet
    from paddle_tpu.models.generation import make_decoder_fns
    from paddle_tpu.serving import (DeployConfig, DeploymentController,
                                    InProcessReplica, LLMMetrics,
                                    RejectedError, ReplicaRouter,
                                    RouterConfig)
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig

    preset = os.environ.get("BENCH_DEPLOY_PRESET", "gpt2-tiny")
    n_replicas = int(os.environ.get("BENCH_DEPLOY_REPLICAS", "4"))
    num_slots = int(os.environ.get("BENCH_DEPLOY_SLOTS", "4"))
    max_new = int(os.environ.get("BENCH_DEPLOY_MAX_NEW", "8"))
    rate_hz = float(os.environ.get("BENCH_DEPLOY_RATE_HZ", "200"))
    min_req = int(os.environ.get("BENCH_DEPLOY_MIN_REQUESTS", "24"))
    max_req = int(os.environ.get("BENCH_DEPLOY_MAX_REQUESTS", "400"))
    backend = jax.default_backend()

    if preset.startswith("llama"):
        from paddle_tpu.models.llama import LlamaForCausalLM
        model = LlamaForCausalLM.from_preset(preset)
    else:
        from paddle_tpu.models.gpt import GPTForCausalLM
        model = GPTForCausalLM.from_preset(preset)
    vocab = model.config.vocab_size if hasattr(model, "config") else 512

    def mk_replica(i):
        eng = LLMEngine(model, LLMEngineConfig(
            num_slots=num_slots, block_len=8,
            n_blocks=max(4, -(-(16 + max_new) // 8)),
            max_queue_depth=max(8 * num_slots, 64)))
        eng.start()
        eng.generate([1, 2, 3], max_new_tokens=2, timeout=300)  # warm jit
        eng.metrics = LLMMetrics()
        eng.metrics.set_slots(0, eng.pool.num_slots)
        return InProcessReplica(eng, i)

    reps = [mk_replica(i) for i in range(n_replicas)]
    router = ReplicaRouter(
        reps, RouterConfig(poll_interval_s=0.002)).start()

    tmpdir = tempfile.mkdtemp(prefix="pdtpu_deploy_bench_")
    params, _, _ = make_decoder_fns(model)
    ws = WeightSet.publish(tmpdir, "v2", params)
    ctrl = DeploymentController(
        router, DeployConfig(watch_window_s=0.25, settle_timeout_s=300.0))

    # rejects can burn extra trace entries, so over-provision the draw
    d_prompts, d_gaps, _ = _poisson_prompt_trace(
        0, n_replicas + 2 * max_req, rate_hz, vocab)
    idx = 0

    def submit_one(handles, rejected, p):
        try:
            handles.append(router.submit(p, max_new_tokens=max_new))
            return rejected
        except RejectedError:
            return rejected + 1     # admission control, NOT a drop

    handles, rejected = [], 0
    for _ in range(n_replicas):     # pre-roll: swap lands MID-traffic
        rejected = submit_one(handles, rejected, d_prompts[idx])
        idx += 1
    t0 = time.perf_counter()
    ctrl.spawn(ws)
    # Poisson arrivals sustained across the WHOLE rollout window
    while ((ctrl.active() or len(handles) < min_req)
           and len(handles) < max_req and idx < len(d_prompts)):
        time.sleep(d_gaps[idx])
        rejected = submit_one(handles, rejected, d_prompts[idx])
        idx += 1
    while ctrl.active():            # trace capped out before the rollout
        time.sleep(0.01)
    rollout_s = time.perf_counter() - t0

    dropped = 0
    ttfts = []
    for h in handles:
        try:
            toks = h.result(timeout=300)
            assert toks.size > 0
            if h.ttft_ms is not None:
                ttfts.append(float(h.ttft_ms))
        except Exception:
            dropped += 1
    rec = ctrl.status()["history"][-1]
    versions = sorted({r.weight_version for r in reps if not r.crashed})
    router.stop(drain=True)

    p99 = float(np.percentile(ttfts, 99)) if ttfts else 0.0
    result = {
        "metric": f"ttft_p99/deploy deploy-{preset} x{n_replicas} "
                  f"slots{num_slots}",
        "value": round(p99, 3),
        "unit": "ms p99 TTFT across a full rolling weight swap",
        "vs_baseline": 0.0,
        "extra": {
            "deploy_ttft_p99_ms": round(p99, 3),
            "deploy_dropped_streams": dropped,
            "deploy_outcome": rec["outcome"],
            "deploy_rollout_s": round(rollout_s, 3),
            "deploy_swapped": rec["swapped"],
            "deploy_fleet_versions": versions,
            "deploy_requests": len(handles),
            "deploy_failovers": sum(h.failovers for h in handles),
            "rejected": rejected,
            "backend": backend,
            "n_replicas": n_replicas,
            "rate_hz": rate_hz,
            "num_slots": num_slots,
            "max_new_tokens": max_new,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


def run_ckpt_bench():
    """Continuous-checkpointing benchmark (ISSUE 15): the same train fn
    runs twice under ResilientTrainer with the goodput ledger armed —
    once with a synchronous CheckpointManager at interval K, once with
    an AsyncCheckpointManager at K/4 (4x MORE frequent saves). The async
    tier must keep step-thread stalls strictly below the sync baseline's
    even while checkpointing 4x as often: its per-boundary blocking cost
    is only the device→host snapshot fetch, the pickle+fsync+CRC persist
    runs on the background writer. Gates through
    tools/check_bench_result.py: `train_ckpt_stall_ms` (worst blocking
    ms at any async save boundary) is a CEILING, `train_goodput` (async
    run) is a FLOOR; the sync baseline numbers ride along ungated."""
    import os
    import tempfile

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.checkpoint import (AsyncCheckpointManager,
                                       CheckpointManager)
    from paddle_tpu.distributed.resilient import (ResilientConfig,
                                                  ResilientTrainer)
    from paddle_tpu.obs.flight_recorder import flight_recorder
    from paddle_tpu.optimizer import SGD

    backend = jax.default_backend()
    width = int(os.environ.get("BENCH_CKPT_WIDTH", "1024"))
    num_steps = int(os.environ.get("BENCH_CKPT_STEPS", "32"))
    sync_interval = int(os.environ.get("BENCH_CKPT_INTERVAL", "8"))
    async_interval = max(1, sync_interval // 4)

    paddle.seed(0)
    rng = np.random.RandomState(0)

    class MLP(nn.Layer):
        # ~2*width^2 fp32 params (8 MB at width=1024): big enough that a
        # synchronous pickle+fsync+CRC save has a visible step-thread cost
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(width, width)
            self.fc2 = nn.Linear(width, width)

        def forward(self, x):
            return self.fc2(self.fc1(x))

    x = paddle.to_tensor(rng.randn(8, width).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, width).astype(np.float32))

    def run_one(make_ckpt, interval):
        paddle.seed(0)
        model = MLP()
        opt = SGD(learning_rate=0.1, parameters=model.parameters())

        def train_fn(_i):
            loss = nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        with tempfile.TemporaryDirectory() as d:
            ckpt = make_ckpt(d)
            trainer = ResilientTrainer(
                train_fn, ckpt,
                get_state=lambda: {"model": model.state_dict()},
                set_state=lambda s: model.set_state_dict(s["model"]),
                config=ResilientConfig(save_interval=interval),
                goodput=True)
            summary = trainer.run(lambda i: i, num_steps=num_steps)
            stats = summary.get("checkpoint")
            if hasattr(ckpt, "close"):
                ckpt.close()
        return summary["goodput"], stats

    sync_g, _ = run_one(
        lambda d: CheckpointManager(d, max_to_keep=2, use_orbax=False),
        sync_interval)
    flight_recorder().clear()  # scope ckpt_snapshot events to the async run
    async_g, async_stats = run_one(
        lambda d: AsyncCheckpointManager(d, max_to_keep=2), async_interval)
    # worst single-boundary stall the step thread ever saw (the ceiling):
    # per-boundary blocking_ms rides on the ckpt_snapshot flight events
    snap_ms = [e["blocking_ms"] for e in
               flight_recorder().snapshot()["events"]
               if e["kind"] == "ckpt_snapshot"]
    stall_ms = max(snap_ms) if snap_ms else 0.0

    sync_blocking = sync_g["checkpoint_blocking_seconds"]
    async_blocking = async_g["checkpoint_blocking_seconds"]
    result = {
        "metric": f"ckpt_stall/boundary ckpt-async steps{num_steps} "
                  f"sync{sync_interval} async{async_interval} "
                  f"width{width}",
        "value": round(stall_ms, 3),
        "unit": "ms worst blocking per async save boundary",
        # headline comparison: total step-thread blocking seconds, async
        # tier at 4x the save frequency vs the sync baseline
        "vs_baseline": round(async_blocking / sync_blocking, 4)
        if sync_blocking > 0 else None,
        "extra": {
            "backend": backend,
            "device_kind": jax.devices()[0].device_kind,
            "train_ckpt_stall_ms": round(stall_ms, 3),
            "train_goodput": round(async_g["goodput"], 4),
            "ckpt_sync_goodput": round(sync_g["goodput"], 4),
            "ckpt_sync_blocking_s": round(sync_blocking, 4),
            "ckpt_async_blocking_s": round(async_blocking, 4),
            "ckpt_async_background_s": round(
                async_g["checkpoint_async_seconds"], 4),
            "ckpt_snapshots": async_stats["snapshots"],
            "ckpt_persisted": async_stats["persisted"],
            "ckpt_dropped": async_stats["dropped"],
            "ckpt_sync_interval": sync_interval,
            "ckpt_async_interval": async_interval,
            "provenance": _provenance(),
        },
    }
    print(json.dumps(result))


MODES = {
    "--serve": run_serve_bench, "--comm": run_comm_bench,
    "--llm": run_llm_bench, "--fleet": run_fleet_bench,
    "--deploy": run_deploy_bench, "--ckpt": run_ckpt_bench,
}


def main(argv=None):
    """`python bench.py [--serve|--comm|--llm|--fleet|--deploy|--ckpt]`:
    one mode, in this process, on whatever backend JAX comes up on. A
    failure is an uncaught exception — traceback, non-zero exit, no row."""
    argv = sys.argv[1:] if argv is None else argv
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    for flag, run_mode in MODES.items():
        if flag in argv:
            run_mode()
            return 0
    run_bench()
    return 0


if __name__ == "__main__":
    sys.exit(main())
