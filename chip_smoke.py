#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both halves of the main path once, through the entry points a user
calls, at the full width of gpt3-1.3b (depth and weights as the preset; the
weights are random, from a seed):

  train leg   fleet.init + parallel.parallelize -> ScanTrainStep, bf16 params,
              bf16 AdamW moments, per-layer recompute, batch 4 x seq 1024 per
              chip over a mesh of every local device; two scan chunks.
  serve leg   LLMEngine behind ServingServer on port 0; four POST /generate
              requests (12, 200, 700 and 1,500 prompt tokens; the last two
              concurrent), /metrics scraped, zero compilations after the
              first request.

Each leg first checks the Pallas kernel it depends on against the repo's
own reference ON THE DEVICE (flash fwd + dq/dk/dv vs `_attention_reference`;
`ragged_paged_attention(impl="pallas")`, full walk and windowed walk through
a ring, `ssm_update(impl="pallas")` and `kda_update(impl="pallas")` vs
`impl="scan"`, and the K/V write's
`kv_write` bit for bit vs the vmapped `dynamic_update_slice`) and then
requires that kernel's Mosaic custom calls in the compiled step it just ran. Any
failed check raises; nothing is caught to let a leg fail while the run
exits 0.

One process per chip: this parent imports neither jax nor paddle_tpu and
runs the legs as children, one after the other, so each gets the chip (and
its HBM) to itself. The default invocation refuses to run without a TPU.
`--cpu-rehearsal` is the explicit CPU run at gpt2-tiny that the
on-chip-measurement guide advises before spending chip time; it prints
platform=cpu and never prints the ok line.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
LEGS = ("train", "serve")
# the contract allows 1200 s, compilation included; leave room to report
BUDGET_S = 1140.0

# The sizes the issue fixes. gpt3-1.3b: hidden 2048, 24 layers, 16 heads x
# 128, FFN 8192, vocab 50,304 (models/gpt.py GPT_PRESETS).
FULL = dict(
    preset="gpt3-1.3b", batch_per_chip=4, seq=1024, scan_steps=4,
    # the smoke's own training shape, then the train cells' (a grid step
    # there holds K and V of 2,048 keys and loops over four sub-tiles)
    flash_shapes=((2, 16, 1024, 128), (8, 16, 2048, 128)),
    # 127 pages x 16 tokens + the pool's 16-token write pad = the model's
    # 2,048 positions (init_cache refuses a slab longer than the learned
    # position table, so "8 x 2,048" is 2,032 addressable tokens per slot)
    slots=8, n_blocks=127, prompts=(12, 200, 700, 1500), max_new=32,
    # the serve cells' head layouts and slot geometry: Mistral (GQA 32/8)
    # and OLMoE (MHA 16/16), 14 pages of 16 a slot + a chunk of write-padding
    # + the heads-by-layer cell's full layers: GQA 48/8 (fold 6: 6 of a
    # packed sublane tile's 16 rows in the one-column body), 160 pages
    paged=(dict(heads=32, kv_heads=8, head_dim=128, pages=14),
           dict(heads=16, kv_heads=16, head_dim=128, pages=14),
           dict(heads=48, kv_heads=8, head_dim=128, pages=160)),
    # the window/full cell's window layers: GQA 32/4, a window of 1,024 in
    # a ring of 65 pages (window + one chunk); the heads-by-layer cell's:
    # GQA 64/8, a window of 512 in a ring of 33 pages
    paged_window=(dict(heads=32, kv_heads=4, head_dim=128, window=1024,
                       ring_pages=65),
                  dict(heads=64, kv_heads=8, head_dim=128, window=512,
                       ring_pages=33)),
    # the same cell's expert layer: 256 groups of [2048, 512] and back over
    # a step's 512 positions x 8 (16 rows a group, uneven)
    moe_gmm=dict(rows=4096, experts=256, hidden=2048, width=512),
    # the two latent cells' MLA layers, over one 512-wide latent and one
    # 64-wide rotary key (stored in 128 lanes): 64 heads in two tiles of
    # 32, 518 pages a slot; 32 heads in one tile, 160 pages a slot
    paged_latent=(dict(heads=64, latent=512, rope=64, rope_cols=128,
                       pages=518),
                  dict(heads=32, latent=512, rope=64, rope_cols=128,
                       pages=160)),
    # the sessions cell's sparse layers: an indexer of 32 x 128 that keeps
    # 2,048 keys, over the same latent pair, 2,304 pages a slot
    sparse=dict(heads=64, latent=512, rope_cols=128, index_heads=32,
                index_dim=128, topk=2048, pages=2304),
    # granite-4.0-h-small's Mamba-2 state: 128 heads x 64, 128 channels
    ssm=dict(heads=128, head_dim=64, state=128),
    # the hyper-connected cell's residual path: 4 streams of 3,584 over a
    # packed step's 512 positions
    hyper=dict(streams=4, hidden=3584, rows=512),
    # the linear-attention cell's KDA layers: 64 heads of 128 x 128, 256
    # slots in a packed step of 512 tokens
    kda=dict(heads=64, head_dim=128, rows=256, tokens=512),
)
TINY = dict(
    preset="gpt2-tiny", batch_per_chip=2, seq=128, scan_steps=2,
    flash_shapes=((1, 2, 512, 64),),
    slots=4, n_blocks=31, prompts=(12, 40, 100, 200), max_new=8,
    paged=(dict(heads=4, kv_heads=2, head_dim=64, pages=4),
           dict(heads=2, kv_heads=2, head_dim=64, pages=4),
           dict(heads=12, kv_heads=2, head_dim=64, pages=4)),
    paged_window=(dict(heads=4, kv_heads=2, head_dim=64, window=32,
                       ring_pages=3),
                  dict(heads=16, kv_heads=2, head_dim=64, window=32,
                       ring_pages=3)),
    moe_gmm=dict(rows=256, experts=16, hidden=128, width=128),
    paged_latent=(dict(heads=4, latent=32, rope=8, rope_cols=8, pages=20),
                  dict(heads=2, latent=32, rope=8, rope_cols=8, pages=9)),
    sparse=dict(heads=4, latent=32, rope_cols=8, index_heads=2,
                index_dim=128, topk=32, pages=20),
    ssm=dict(heads=4, head_dim=64, state=16),
    hyper=dict(streams=4, hidden=128, rows=40),
    kda=dict(heads=2, head_dim=128, rows=6, tokens=40),
)


# --------------------------------------------------------------------------
# shared by both legs (children only: these import jax / paddle_tpu)
# --------------------------------------------------------------------------

def _say(msg: str):
    print(msg, flush=True)


def _require(cond: bool, what: str):
    if not cond:
        raise AssertionError(f"chip_smoke: check failed: {what}")
    _say(f"  ok: {what}")


def _device_report(rehearsal: bool) -> dict:
    """First thing in a leg: versions, platform, kind, count. Sets no
    platform itself; fails unless JAX came up on a TPU whose device_kind is
    in the peak table."""
    import importlib.metadata as md

    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    _say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu "
         f"{libtpu} python {sys.version.split()[0]} "
         f"platform={dev['platform']} device_kind={dev['kind']!r} "
         f"count={dev['count']}")
    want = "cpu" if rehearsal else "tpu"
    if dev["platform"] != want:
        raise SystemExit(
            f"chip_smoke: JAX came up on platform={dev['platform']!r}, "
            f"this run needs {want!r}"
            + ("" if rehearsal else
               " — no accelerator found; refusing to run (use "
               "--cpu-rehearsal for the gpt2-tiny CPU rehearsal)"))
    from paddle_tpu.obs.flops import peak_flops
    peak = peak_flops(dev["kind"], dev["platform"])  # raises if unknown
    _say(f"peak table: {dev['kind']!r} -> {peak / 1e12:g} TFLOP/s bf16")
    return dev


def _start_leg(rehearsal: bool):
    """Device check first, then the compile cache before any compilation.
    Returns (device dict, cache entries before)."""
    dev = _device_report(rehearsal)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    return dev, _cache_report("before")


def _trace_paths() -> dict:
    """{kernel/path: traces} from ops.pallas_mode, for the log."""
    from paddle_tpu.ops import pallas_mode
    return {f"{k}/{p}": n
            for (k, p), n in sorted(pallas_mode.KERNEL_TRACES.items())}


def _tilings() -> list:
    """["kernel xN {grid, groups, pages, heads, rows}", ...]: the tile each
    kernel call site traced so far chose from its shapes (`kv_write`: grid,
    rows a grid step, heads, the aligned window's columns, ring)."""
    from paddle_tpu.ops import pallas_mode
    return [f"{k} x{n} {dict(t)}"
            for (k, t), n in sorted(pallas_mode.KERNEL_TILINGS.items())]


def _cache_report(when: str) -> int:
    from paddle_tpu.utils import compile_cache
    n = compile_cache.entry_count()
    _say(f"compile cache {when}: dir={compile_cache.cache_dir()} "
         f"entries={n}")
    return n


def _kernel_row(callsite: str) -> dict:
    """The compile observatory's record of the executable built at
    `callsite` (exactly one is expected)."""
    from paddle_tpu.obs.compile_observatory import compile_observatory
    rows = [r for r in compile_observatory().snapshot()["rows"]
            if r["callsite"] == callsite]
    _require(len(rows) == 1,
             f"one executable registered at {callsite} (got {len(rows)})")
    return rows[0]


def _phases(row: dict) -> str:
    """The seconds an executable's AOT build took, by phase: tracing and
    lowering are paid by every process, compiling only where the compile
    cache misses (one figure hid a kernel whose lowering had tripled)."""
    p = row["phase_seconds"]
    return (f"trace {p['trace']:.1f}s + lower {p['lower']:.1f}s + compile "
            f"{p['compile']:.1f}s = {row['compile_seconds']:.1f}s (AOT)")


def _max_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# a paged walk's steps, (query width, mixed): one-token rows, a step whose
# rows hold sixteen live columns each, and a mixed step (PR 48): every
# other row has one live column of the sixteen and takes the kernel's
# one-column body where its trace holds one, beside chunk rows in the wide
PAGED_STEPS = ((1, False), (16, False), (16, True))


def _live_columns(N: int, Tq: int, mixed: bool):
    """adv [N]: each row's live columns in a step of `PAGED_STEPS`."""
    import numpy as np
    return np.where(np.arange(N) % 2 == 0, 1, Tq).astype(np.int32) \
        if mixed else np.full(N, Tq, np.int32)


def _live_err(outs: dict, adv) -> float:
    """`_max_err` of the kernel's and the scan's results over each row's
    `adv` live columns (a one-column row's dead columns are zeros in the
    kernel and a key-less query's values in the scan)."""
    import jax.numpy as jnp
    Tq = outs["scan"].shape[2]
    live = (jnp.arange(Tq)[None, :] < jnp.asarray(adv)[:, None])[
        :, None, :, None]
    return _max_err(*(jnp.where(live, outs[impl].astype(jnp.float32), 0.0)
                      for impl in ("pallas", "scan")))


# --------------------------------------------------------------------------
# train leg
# --------------------------------------------------------------------------

def _flash_parity(size: dict, rehearsal: bool):
    for shape in size["flash_shapes"]:
        _flash_parity_at(shape, rehearsal)


def _flash_parity_at(shape: tuple, rehearsal: bool):
    """Flash fwd + dq/dk/dv against `_attention_reference` on this device,
    bf16 causal. Tolerance: both sides feed bf16 operands to fp32-
    accumulating dots and round the result to bf16, which keeps 8
    significant bits — one ulp is 1.56e-2 for a value in [2, 4) and 3.1e-2
    in [4, 8). Outputs are softmax averages of unit-normal v (the first
    rows see only a few keys, so |o| reaches 4-5); gradients of
    sum(o * w) with unit-normal w have the same scale. The two paths round
    p to bf16 at different points of the accumulation (per sub-tile of
    `_choose_tiles` after the running rescale vs once per row), so results may differ by
    one ulp: 4e-2 absolute admits one ulp anywhere below 8 and is ~25x
    below what a wrong mask, offset or block would produce (O(1)). (First
    chip run, PR 21: o 1.56e-2, dq 1.86e-2, dk 1.56e-2, dv 1.56e-2.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import attention as A
    B, H, S, D = shape
    rng = np.random.RandomState(0)
    q, k, v, w = (jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
                  for _ in range(4))
    scale = 1.0 / float(np.sqrt(D))

    def flash(**kw):
        # on the CPU rehearsal force_pallas runs the same kernels
        # interpreted; on the chip the wrapper picks them by itself
        return lambda q_, k_, v_: A.flash_attention(
            q_, k_, v_, causal=True, force_pallas=rehearsal, **kw)

    def ref(q_, k_, v_):
        return A._attention_reference(q_, k_, v_, True, scale)

    def out_and_grads(fn):
        """jitted (q, k, v) -> (o, dq, dk, dv) of sum(o * w)."""
        def loss(q_, k_, v_):
            o = fn(q_, k_, v_)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                        has_aux=True))

        def run():
            (_, o), grads = vg(q, k, v)
            return (o,) + tuple(grads)
        return run

    got, want = out_and_grads(flash())(), out_and_grads(ref)()
    tol = 4e-2
    errs = dict(zip(("o", "dq", "dk", "dv"), map(_max_err, got, want)))
    _say(f"flash vs reference at {[B, H, S, D]} bf16 causal: max abs err "
         + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
         + f" (tolerance {tol:g})")
    for n, e in errs.items():
        _require(np.isfinite(e) and e <= tol, f"flash {n} within {tol:g}")

    if rehearsal:
        _say("  dropout variant: not covered (pltpu.prng has no CPU "
             "lowering)")
        return
    # the dropout variant (in-kernel TPU PRNG, SMEM seed) is not on the
    # smoke's training path (dropout 0): compile and run it once here
    dropped = out_and_grads(flash(dropout_p=0.1, dropout_seed=7))
    first, again = dropped(), dropped()
    _require(all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                 for x in first),
             "flash dropout_p=0.1 fwd+bwd compiles, finite")
    _require(_max_err(first[0], got[0]) > 1e-3,
             "flash dropout_p=0.1 output differs from dropout 0")
    _require(_max_err(first[0], again[0]) == 0.0,
             "flash dropout is a function of the seed (same seed, same bits)")
    # the three kernels regenerate one keep-mask: with o linear in v,
    # <dv, v> = <w, o> only if the dkv kernel drew the forward's bits, and
    # with the scores bilinear in q and k, <dq, q> = <dk, k> only if dq and
    # dkv drew the same. Both sides are sums of bf16 results, so they agree
    # to ~1e-6 of |grad| |operand| (PR 45's chip run: 1.0e-6 / 1.7e-7 here,
    # 7e-7 with no dropout at all); another seed's bits on one side read
    # 1.5e-4 / 7.0e-4 at [2, 16, 1024, 128] (5e-6 / 2.1e-4 at the cells'
    # shape, where the first sum averages the noise away)
    def f32(x):
        return x.astype(jnp.float32)
    o, dq, dk, dv = map(f32, first)

    def gap(a, b, grad, operand):
        return float(jnp.abs(a - b) / (jnp.linalg.norm(grad)
                                       * jnp.linalg.norm(f32(operand))))
    v_gap = gap(jnp.sum(dv * f32(v)), jnp.sum(f32(w) * o), dv, v)
    qk_gap = gap(jnp.sum(dq * f32(q)), jnp.sum(dk * f32(k)), dq, q)
    _say(f"  dropout adjoint identities: <dv,v>-<w,o> {v_gap:.2e}, "
         f"<dq,q>-<dk,k> {qk_gap:.2e} of |grad||operand| (tolerance 1e-5)")
    _require(v_gap <= 1e-5 and qk_gap <= 1e-5,
             "flash dropout draws the same keep-bits in forward, dq and dkv")


def _train_strategy(n_dev: int, scan_steps: int, layout: str = ""):
    """dp x sharding over every local device, ZeRO stage 2 when the
    sharding axis is real (the shape of README 'Distributed training').
    `layout` ("dp=2,mp=2") names another one for a multi-chip host."""
    from paddle_tpu.distributed import DistributedStrategy
    if layout:
        chosen = {k: int(v) for k, v in
                  (part.split("=") for part in layout.split(","))}
    elif n_dev % 4 == 0:
        chosen = {"dp": n_dev // 2, "sharding": 2}
    else:
        chosen = {"dp": n_dev}
    degrees = {"dp": 1, "mp": 1, "sharding": 1, **chosen}
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": degrees["dp"], "mp_degree": degrees["mp"],
        "pp_degree": 1, "sharding_degree": degrees["sharding"]}
    if degrees["sharding"] > 1:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 2, "offload": False}
    strategy.scan_steps = scan_steps
    return strategy, degrees


def leg_train(size: dict, rehearsal: bool, layout: str = "") -> dict:
    dev, cache_before = _start_leg(rehearsal)

    import jax
    import numpy as np

    _say("[train] kernel parity on this device")
    _flash_parity(size, rehearsal)

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.obs.compile_observatory import compile_observatory
    from paddle_tpu.parallel import ScanTrainStep, parallelize

    n_dev = dev["count"]
    K = size["scan_steps"]
    strategy, degrees = _train_strategy(n_dev, K, layout)
    batch_shards = degrees["dp"] * degrees["sharding"]
    B, S = size["batch_per_chip"] * batch_shards, size["seq"]
    _say(f"[train] {size['preset']} bf16, recompute, AdamW bf16 moments, "
         f"layout {degrees} over {n_dev} device(s), global batch {B} x "
         f"seq {S}, scan_steps {K}")

    t0 = time.perf_counter()
    paddle.seed(0)
    model = GPTForCausalLM.from_preset(size["preset"], use_recompute=True)
    model.to(dtype="bfloat16")
    opt = optim.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                      moment_dtype="bfloat16")
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().build_mesh()
    _require(mesh.devices.size == n_dev,
             f"mesh {dict(mesh.shape)} uses every local device")
    step = parallelize(model, opt, mesh=mesh, strategy=strategy)
    _require(isinstance(step, ScanTrainStep),
             "strategy.scan_steps gave a ScanTrainStep")
    # the observatory AOT-compiles the chunk once (compile seconds, memory
    # analysis, and which Pallas kernels the compiled text holds)
    step.observatory = compile_observatory().enable()
    _say(f"[train] model + sharded state built in "
         f"{time.perf_counter() - t0:.1f}s")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, model.config.vocab_size, (B, S)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)          # next token, fixed batch
    ids_chunk = np.broadcast_to(ids, (K,) + ids.shape).copy()
    labels_chunk = np.broadcast_to(labels, (K,) + labels.shape).copy()

    walls, losses = [], []
    for chunk in range(2):                      # the second is the warm one
        t0 = time.perf_counter()
        out = step(ids_chunk, labels_chunk)
        vals = np.asarray(jax.block_until_ready(out.data), np.float32)
        walls.append(time.perf_counter() - t0)
        losses.extend(float(x) for x in vals)
        _say(f"[train] chunk {chunk}: {walls[-1]:.2f}s wall, losses "
             + " ".join(f"{x:.4f}" for x in vals))
    _require(all(np.isfinite(losses)), "loss finite on every step")
    _require(losses[-1] < losses[0],
             f"loss lower at the end ({losses[-1]:.4f}) than at the start "
             f"({losses[0]:.4f})")
    _require(step.dispatch_count == 2, "two chunk dispatches")

    row = _kernel_row("train/scan_chunk")
    kernels = row["pallas_kernels"] or {}
    _say(f"[train] compiled chunk: {_phases(row)}, temp "
         f"{row['temp_bytes']} B, args {row['argument_bytes']} B, Pallas "
         f"kernels {kernels}")
    _say(f"[train] kernel trace paths: {_trace_paths()}")
    if rehearsal:
        _say("  kernel census not applicable on the CPU (the model takes "
             "the XLA reference there by design)")
    else:
        n_layers = model.config.num_hidden_layers
        # per layer: one forward (the recomputed layer keeps its output
        # and log-sum-exp since PR 41, so the replay holds no second one),
        # one dq and one dkv kernel — in the compiled text, not the trace
        for name, want in (("flash_fwd", n_layers),
                           ("flash_bwd_dq", n_layers),
                           ("flash_bwd_dkv", n_layers)):
            _require(kernels.get(name, 0) == want,
                     f"compiled step holds {want} {name} custom calls "
                     f"(got {kernels.get(name, 0)})")
        _require("flash_attention/reference" not in _trace_paths(),
                 "no flash_attention call took the XLA reference")

    # state and batch really split across the mesh (four-chip run)
    params = step._params
    big = max(params, key=lambda k_: params[k_].size)
    m_big = next(m for m in step._opt_state[big].values()
                 if m.shape == params[big].shape)
    _say(f"[train] largest param {big} {tuple(params[big].shape)}: param "
         f"shard {tuple(params[big].addressable_shards[0].data.shape)}, "
         f"moment shard {tuple(m_big.addressable_shards[0].data.shape)}, "
         f"batch spec {step.data_spec}")
    shard_deg = degrees["sharding"] * degrees["mp"]
    if shard_deg > 1:
        _require(m_big.addressable_shards[0].data.size * shard_deg
                 <= m_big.size, f"moments split {shard_deg} ways")
    in_use, peaks = [], []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        in_use.append(stats.get("bytes_in_use"))
        peaks.append(stats.get("peak_bytes_in_use"))
    _say(f"[train] INFO (one unrepeated run, not a metric): warm chunk "
         f"{walls[1]:.2f}s = {walls[1] / K * 1e3:.0f} ms/step wall after "
         f"block_until_ready; first chunk {walls[0]:.2f}s incl. compile; "
         f"per device bytes_in_use {in_use} peak_bytes_in_use {peaks}")
    if n_dev > 1 and all(x is not None for x in in_use):
        # (the peak is another matter: the model and the whole optimizer
        # state are built on device 0 before they are sharded)
        _require(max(in_use) <= 1.1 * min(in_use),
                 "training state split evenly: per-device bytes_in_use "
                 "within 10% after the run")
    # the step took the model's buffers over and donated them; the model
    # gets the trained weights back by reference
    step.sync_to_model()
    w = next(iter(model.parameters())).data
    _require(not w.is_deleted() and bool(np.isfinite(
        np.asarray(w[:1], np.float32)).all()),
        "sync_to_model() rebinds the model to live, finite weights")
    cache_after = _cache_report("after")
    return {"device": dev, "layout": degrees, "losses": losses,
            "chunk_wall_s": walls, "compile_s": row["compile_seconds"],
            "pallas_kernels": kernels, "bytes_in_use": in_use,
            "peak_bytes_in_use": peaks,
            "cache_entries": [cache_before, cache_after]}


# --------------------------------------------------------------------------
# serve leg
# --------------------------------------------------------------------------

def _paged_parity(size: dict):
    """`ragged_paged_attention(impl="pallas")` against `impl="scan"` on this
    device at each head layout of `size["paged"]`, bf16, ragged seq_lens,
    block_len 16, query widths 1 and 16, slabs with write-padding past the
    page region. The two run the same arithmetic in the same precision on
    the same bf16 inputs, the scan a page a step and the kernel 128 keys a
    step, so the tolerance is tighter than flash-vs-reference: what
    remains is the grouping and order of the fp32 accumulation (one MXU
    dot over a group vs one XLA einsum a page) and one bf16 rounding of
    the output (|o| <~ 4 -> 8e-3). 2e-2. Prints the grid, the pages a
    group and the tile the kernel chose for each shape."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    N, bl, tol = 8, 16, 2e-2
    rng = np.random.RandomState(1)
    for g in size["paged"]:
        H, Hkv, D, nb = g["heads"], g["kv_heads"], g["head_dim"], g["pages"]
        L = nb * bl
        k = jnp.asarray(rng.randn(N, Hkv, L + bl, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(N, Hkv, L + bl, D), jnp.bfloat16)
        # each row's pages live in another row's slab: a real indirection
        table = ((np.arange(N)[:, None] + 1) % N * nb
                 + np.arange(nb)[None, :]).astype(np.int32)
        for Tq, mixed in PAGED_STEPS:
            q = jnp.asarray(rng.randn(N, H, Tq, D), jnp.bfloat16)
            q_pos = np.array([0, L // 7, L // 2 + 3, L - Tq, 1, bl - 1, bl,
                              L // 3], np.int32)
            adv = _live_columns(N, Tq, mixed)
            lens = q_pos + adv
            pallas_mode.KERNEL_TILINGS.clear()
            outs = {impl: ragged_paged_attention(
                q, k, v, table, lens, q_pos, block_len=bl, pages_per_row=nb,
                impl=impl) for impl in ("pallas", "scan")}
            (_, tiling), = pallas_mode.KERNEL_TILINGS
            tiling = dict(tiling)
            err = _live_err(outs, adv)
            _say(f"paged pallas vs scan H={H} Hkv={Hkv} D={D} block_len={bl} "
                 f"Tq={Tq} seq_lens={lens.tolist()} live columns "
                 f"{adv.tolist()} bf16: grid "
                 f"{tiling['grid']} (G={tiling['grid'][1]}), up to "
                 f"{tiling['groups']} groups of pages={tiling['pages']} a "
                 f"row, tile {tiling['heads']} KV heads x {tiling['rows']} "
                 f"rows, one-column body {tiling['one_column_rows']} rows; "
                 f"max abs err {err:.2e} (tolerance {tol:g})")
            _require(np.isfinite(err) and err <= tol,
                     f"paged H={H}/{Hkv} Tq={Tq} within {tol:g}")


def _paged_window_parity(size: dict):
    for layout in size["paged_window"]:
        _paged_window_parity_at(layout)


def _paged_window_parity_at(g: dict):
    """The windowed walk (`paged_window`) `impl="pallas"` against
    `impl="scan"` on this device at one layout of `size["paged_window"]`: bf16, block_len
    16, each row its own ring (position p at column p mod ring, so rows
    past the ring have wrapped), query widths 1 and 16, rows shorter than
    the window, across it, twice and five times round the ring. Tolerance
    as `_paged_parity`'s."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.paged_attention import (WINDOW_KERNEL,
                                                ragged_paged_attention)
    H, Hkv, D = g["heads"], g["kv_heads"], g["head_dim"]
    W, pages = g["window"], g["ring_pages"]
    N, bl, tol = 8, 16, 2e-2
    ring = pages * bl
    rng = np.random.RandomState(2)
    k = jnp.asarray(rng.randn(N, Hkv, ring + bl, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(N, Hkv, ring + bl, D), jnp.bfloat16)
    for Tq, mixed in PAGED_STEPS:
        q = jnp.asarray(rng.randn(N, H, Tq, D), jnp.bfloat16)
        adv = _live_columns(N, Tq, mixed)
        lens = np.maximum(adv, np.array(
            [1, W // 3, W - 1, W + bl + 1, ring, 2 * ring + 5,
             5 * ring + bl - 1, 8 * ring - 3], np.int32))
        q_pos = (lens - adv).astype(np.int32)
        pallas_mode.KERNEL_TILINGS.clear()
        outs = {impl: ragged_paged_attention(
            q, k, v, None, lens, q_pos, block_len=bl, pages_per_row=pages,
            impl=impl, window=W) for impl in ("pallas", "scan")}
        ((kernel, tiling),) = pallas_mode.KERNEL_TILINGS
        tiling = dict(tiling)
        err = _live_err(outs, adv)
        _say(f"{kernel} pallas vs scan H={H} Hkv={Hkv} D={D} window={W} "
             f"ring={pages} pages Tq={Tq} seq_lens={lens.tolist()} live "
             f"columns {adv.tolist()} bf16: "
             f"grid {tiling['grid']}, up to {tiling['groups']} groups of "
             f"pages={tiling['pages']} a row, tile {tiling['heads']} KV "
             f"heads x {tiling['rows']} rows, one-column body "
             f"{tiling['one_column_rows']} rows; max abs err {err:.2e} "
             f"(tolerance {tol:g})")
        _require(kernel == WINDOW_KERNEL and np.isfinite(err) and err <= tol,
                 f"{WINDOW_KERNEL} H={H}/{Hkv} Tq={Tq} within {tol:g}")


def _moe_gmm_parity(size: dict):
    """`grouped_matmul(impl="pallas")` (`moe_gmm`) against a plain XLA form
    on this device at `size["moe_gmm"]`: bf16, both projections of an expert
    layer of many small experts, uneven groups of which some are empty and
    which leave the last rows to no group. The plain form is a scan over
    the groups, each a whole `lhs @ rhs[g]` kept for the group's own rows
    (`jax.lax.ragged_dot`, the CPU's path, is itself a Mosaic kernel on a
    TPU and refuses bf16 operands under the package's "highest"). Both
    accumulate in float32 over the same bf16 operands, so what remains is
    the order of the sum over K and one bf16 rounding of a result of spread
    ~sqrt(K): 0.5% of the largest value, which is a little over one step of
    bf16 there (the chip read half a step at both shapes, 0.23% and 0.21%
    of the largest value, PR 51)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.grouped_matmul import grouped_matmul
    g = size["moe_gmm"]
    rows, experts = g["rows"], g["experts"]
    rng = np.random.RandomState(3)
    sizes = rng.multinomial(rows - rows // 8, np.ones(experts) / experts)
    sizes[rng.choice(experts, experts // 16, replace=False)] = 0
    live = int(sizes.sum())
    ends = np.cumsum(sizes).astype(np.int32)

    @jax.jit
    def plain(lhs, rhs):
        row = jnp.arange(lhs.shape[0])[:, None]

        def one(out, group):
            w, start, end = group
            y = jnp.dot(lhs, w, preferred_element_type=jnp.float32)
            return jnp.where((row >= start) & (row < end), y, out), None

        out, _ = jax.lax.scan(
            one, jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32),
            (rhs, jnp.asarray(ends - sizes), jnp.asarray(ends)))
        return out

    for k, n in ((g["hidden"], g["width"]), (g["width"], g["hidden"])):
        lhs = jnp.asarray(rng.randn(rows, k), jnp.bfloat16)
        rhs = jnp.asarray(rng.randn(experts, k, n), jnp.bfloat16)
        got = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                             impl="pallas")[:live]
        want = plain(lhs, rhs)[:live]
        err, top = _max_err(got, want), float(jnp.max(jnp.abs(want)))
        _say(f"moe_gmm pallas vs plain XLA [{rows},{k}] x [{experts},{k},"
             f"{n}] bf16, groups of {int(sizes.min())}-{int(sizes.max())} "
             f"rows, {live} live: max abs err {err:.2e} of {top:.1f} "
             "(tolerance 0.5%)")
        _require(np.isfinite(err) and err <= 0.005 * top,
                 f"moe_gmm [{experts},{k},{n}] within 0.5%")


def _paged_latent_parity(size: dict):
    """The latent walk (`paged_latent`) in its packed form
    (`packed_latent_attention`: queries and result token-major on a step's
    packed block, a start a row from a `TokenPack`) `impl="pallas"` against
    `impl="scan"` (the rows unpacked, `_scan_impl` as it stands) on this
    device at each head layout of `size["paged_latent"]`: bf16, block_len
    16, the latent `c` and the rotary key `r` (its first `rope` columns;
    zeros behind, as `models/deepseek.py` stores it), every query head over
    the one latent, the steps of `PAGED_STEPS`, ragged lengths from one key
    to the whole slot. Compared over the live positions. Tolerance as
    `_paged_parity`'s."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.attention import token_pack
    from paddle_tpu.ops.paged_attention import (LATENT_KERNEL,
                                                packed_latent_attention)
    N, bl, tol = 8, 16, 2e-2
    rng = np.random.RandomState(3)
    for g in size["paged_latent"]:
        H, R, Dr, cols, pages = (g["heads"], g["latent"], g["rope"],
                                 g["rope_cols"], g["pages"])
        L = pages * bl
        c = jnp.asarray(rng.randn(N, 1, L + bl, R), jnp.bfloat16)
        r = jnp.pad(jnp.asarray(rng.randn(N, 1, L + bl, Dr), jnp.bfloat16),
                    ((0, 0), (0, 0), (0, 0), (0, cols - Dr)))
        table = np.arange(N * pages, dtype=np.int32).reshape(N, pages)
        scale = (R + Dr) ** -0.5
        for Tq, mixed in PAGED_STEPS:
            adv = _live_columns(N, Tq, mixed)
            lens = np.maximum(adv, np.array(
                [1, bl - 1, 127, 129, L // 7, L // 3, L - bl - 1, L],
                np.int32))
            q_pos = (lens - adv).astype(np.int32)
            live = int(adv.sum())
            pack = token_pack(jnp.asarray(adv), jnp.asarray(q_pos), Tq, live)
            # the step's block and the pad a last row's window runs into
            q = jnp.asarray(rng.randn(live + Tq - 1, H, R), jnp.bfloat16)
            qr = jnp.pad(jnp.asarray(rng.randn(live + Tq - 1, H, Dr),
                                     jnp.bfloat16),
                         ((0, 0), (0, 0), (0, cols - Dr)))
            pallas_mode.KERNEL_TILINGS.clear()
            outs = {impl: packed_latent_attention(
                q, qr, c, r, table, lens, q_pos, pack.dst[:, 0], width=Tq,
                block_len=bl, pages_per_row=pages, scale=scale, impl=impl)
                for impl in ("pallas", "scan")}
            ((kernel, tiling),) = pallas_mode.KERNEL_TILINGS
            tiling = dict(tiling)
            err = _max_err(outs["pallas"][:live], outs["scan"][:live])
            _say(f"{kernel} pallas vs scan, packed queries "
                 f"[{tiling['packed_queries']}, {H}, {R} | {cols}] "
                 f"(rope={Dr}) Tq={Tq} seq_lens={lens.tolist()} live "
                 f"columns {adv.tolist()} bf16: grid {tiling['grid']}, up "
                 f"to {tiling['groups']} groups of pages={tiling['pages']} "
                 f"a row, tile {tiling['rows']} rows, one-column body "
                 f"{tiling['one_column_rows']} rows; max abs err {err:.2e} "
                 f"(tolerance {tol:g})")
            _require(kernel == LATENT_KERNEL and np.isfinite(err)
                     and err <= tol,
                     f"{LATENT_KERNEL} H={H} Tq={Tq} within {tol:g}")


def _sparse_parity(size: dict):
    """Learned sparse attention's three kernels on this device at
    `size["sparse"]`, each against its plain-XLA twin: `index_score`
    (float32 scores of bf16 index queries over index-key pages; tolerance
    the accumulation order's), `index_topk` (exact: the same 0/1 mask from
    the same scores, bit for bit) and `paged_sparse` (a decode row's walk
    over its gathered keys beside a chunk row's walk under its masks, one
    selection for both sides; tolerance as `_paged_parity`'s)."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import index_select as IX
    from paddle_tpu.ops.attention import PagedView
    from paddle_tpu.ops.paged_attention import sparse_latent_attention
    g = size["sparse"]
    H, R, cols, Hi, Di, K, pages = (
        g["heads"], g["latent"], g["rope_cols"], g["index_heads"],
        g["index_dim"], g["topk"], g["pages"])
    N, bl, T = 4, 16, 16
    L = pages * bl
    rng = np.random.RandomState(5)

    def bf(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    c, r, ki = bf(N, 1, L + bl, R), bf(N, 1, L + bl, cols), \
        bf(N, 1, L + bl, Di)
    q, qr, qi = bf(N, H, T, R), bf(N, H, T, cols), bf(N, Hi, T, Di)
    w = jnp.asarray(rng.randn(N, Hi, T), jnp.float32)
    table = rng.permutation(N * pages).astype(np.int32).reshape(N, pages)
    adv = np.array([1, T, T, 1], np.int32)      # decode, chunk, chunk, decode
    lens = np.array([L // 3, K // 2, L, L], np.int32)
    q_pos = (lens - adv).astype(np.int32)
    paged = PagedView(table, lens, bl, pages)
    scores = {impl: IX.index_scores(qi, w, ki, table, lens, q_pos,
                                    block_len=bl, pages_per_row=pages,
                                    impl=impl)
              for impl in ("pallas", "reference")}
    live = np.isfinite(np.asarray(scores["reference"]))
    err = float(np.abs(np.where(live, np.asarray(scores["pallas"])
                                - np.asarray(scores["reference"]), 0)).max())
    same = bool((np.isfinite(np.asarray(scores["pallas"])) == live).all())
    _say(f"index_score pallas vs XLA heads={Hi} x {Di} rows={N} x {T} "
         f"seq_lens={lens.tolist()}: max abs err {err:.2e} of scores up to "
         f"{float(np.abs(np.where(live, scores['reference'], 0)).max()):.1f}"
         f"; the same keys visible {same}")
    _require(same and err <= 5e-2, "index_score within 5e-2")
    masks = {impl: np.asarray(IX.topk_mask(scores["reference"], K, impl=impl))
             for impl in ("pallas", "reference")}
    equal = bool((masks["pallas"] == masks["reference"]).all())
    counts = sorted(set(masks["pallas"].sum(-1).astype(int).ravel().tolist()))
    _say(f"index_topk pallas vs XLA k={K} of {L}: masks bit-identical "
         f"{equal}; selected a query {counts[-3:]}")
    _require(equal, "index_topk exact")
    sel = IX.select(qi, w, ki, q_pos, K, paged=paged)
    outs = {impl: sparse_latent_attention(
        q, c, r, table, lens, q_pos, sel=sel, block_len=bl,
        pages_per_row=pages, scale=(R + 64) ** -0.5, q_rope=qr, impl=impl)
        for impl in ("pallas", "scan")}
    cols_live = np.arange(T)[None] < adv[:, None]
    err = float(np.abs(np.where(
        cols_live[:, None, :, None],
        np.asarray(outs["pallas"], np.float32)
        - np.asarray(outs["scan"], np.float32), 0)).max())
    _say(f"paged_sparse pallas vs scan H={H} latent={R} k={K} rows "
         f"adv={adv.tolist()} seq_lens={lens.tolist()} bf16: max abs err "
         f"{err:.2e} (tolerance 2e-02)")
    _require(np.isfinite(err) and err <= 2e-2, "paged_sparse within 2e-2")


def _kv_write_parity(size: dict):
    """The K/V write's kernel (`kv_write`) against the vmapped
    `dynamic_update_slice` / `_ring_write` form on this device, bf16, at
    the serve cells' head layouts and the window cell's ring: a copy, so
    the whole slab must come out bit for bit. Rows at aligned and odd
    columns, at 15 mod 16, at the slab's last stripe and past it (clamped),
    and on the ring a stripe that wraps by 1 and by 15. Prints the
    kernel's grid and rows a grid step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import kv_write as kvw, pallas_mode
    from paddle_tpu.ops.attention import _row_writes
    T, B = 16, 16
    lat = size["paged_latent"][0]
    cases = [(g["kv_heads"], g["pages"] * 16 + T, g["head_dim"],
              g["head_dim"], None) for g in size["paged"]]
    cases += [(w["kv_heads"], w["ring_pages"] * 16 + T, w["head_dim"],
               w["head_dim"], w["ring_pages"] * 16)
              for w in size["paged_window"]]
    cases += [(1, lat["pages"] * 16 + T, lat["latent"], lat["rope_cols"],
               None)]
    rng = np.random.RandomState(4)
    for Hkv, L, Dk, Dv, r in cases:
        kc = jnp.asarray(rng.randn(B, Hkv, L, Dk), jnp.bfloat16)
        vc = jnp.asarray(rng.randn(B, Hkv, L, Dv), jnp.bfloat16)
        kn = jnp.asarray(rng.randn(B, Hkv, T, Dk), jnp.bfloat16)
        vn = jnp.asarray(rng.randn(B, Hkv, T, Dv), jnp.bfloat16)
        edge = [0, 16, 7, 15, L - T, L - 3, L + 9] if r is None else \
            [0, 16, 7, r - T, r - 15, r - 1, 3 * r - 8]
        pos = jnp.asarray(edge + rng.randint(0, L, B - len(edge)).tolist(),
                          jnp.int32)
        if not kvw.kv_write_supported(kc, vc, kn, vn, r):
            _say(f"{kvw.KERNEL}: slab {list(kc.shape)} ring={r} keeps the "
                 "vmapped form (rehearsal shapes)")
            continue
        pallas_mode.KERNEL_TILINGS.clear()
        want = jax.jit(lambda *a: _row_writes(*a, ring=r))(kc, vc, kn, vn,
                                                           pos)
        got = jax.jit(lambda *a: kvw.kv_write(*a, ring=r))(kc, vc, kn, vn,
                                                           pos)
        ((kernel, tiling),) = pallas_mode.KERNEL_TILINGS
        tiling = dict(tiling)
        same = all(bool(jnp.array_equal(a, b)) for a, b in zip(want, got))
        _say(f"{kernel} pallas vs vmapped slab=[{B}, {Hkv}, {L}, {Dk} | "
             f"{Dv}] T={T} ring={r} bf16: grid {tiling['grid']}, "
             f"{tiling['rows']} rows a step, windows of "
             f"{tiling['columns']} columns; whole slabs bit-identical "
             f"{same}")
        _require(kernel == kvw.KERNEL and same,
                 f"{kvw.KERNEL} Hkv={Hkv} ring={r} bit-identical with the "
                 "vmapped write")


def _ssm_parity(size: dict):
    """`ssm_update(impl="pallas")` against `impl="scan"` on this device at
    `size["ssm"]`, bf16 state and inputs: 8 rows of 16, of 1 and of 80
    columns (80: past `MAX_COLUMNS`, the chunked walk `generate()`'s whole
    prompt takes) with ragged `adv` (a dead row, a row that starts from
    zero, a row that ends inside the second chunk), then the prefill
    cell's step (32 rows of 16 live columns: every row in matrix form),
    32 rows that alternate 16 and 1, and the decode cell's (128 rows, six
    of them chunks). Both run the same float32 arithmetic and differ in
    the order of their sums and one bf16 rounding of `y` and of the state:
    2e-2 of the largest value. The two cells' shapes are also timed, a
    call alone, with `MATRIX_COLUMNS` as it is set and with the column
    loop alone (the threshold past T: PR 45's body), which is where the
    threshold's number comes from. Prints the kernel's grid, state tile
    and threshold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_mode, ssm
    g = size["ssm"]
    H, P, N, tol = g["heads"], g["head_dim"], g["state"], 2e-2
    rng = np.random.RandomState(2)
    a = -jnp.exp(jnp.asarray(rng.randn(H), jnp.float32))
    ragged = [16, 1, 0, 16, 3, 70, 16, 1]
    cases = [(T, np.minimum(ragged, T), [0, 0, 0, 1, 0, 1, 0, 0], False)
             for T in (16, 1, 80)]
    cases += [(16, np.full(32, 16), np.arange(32) % 5 == 0, True),
              (16, np.tile([16, 1], 16), np.arange(32) % 5 == 0, False),
              (16, np.r_[np.full(6, 16), np.ones(122, int)],
               np.arange(128) % 40 == 0, True)]

    @jax.jit
    def ten_calls(x, dt, b, c, state, adv, fresh):
        return jax.lax.fori_loop(0, 10, lambda _, s: ssm.ssm_update(
            x, dt, a, b, c, s, adv, fresh, impl="pallas")[1], state)

    for T, adv, fresh, timed in cases:
        rows = len(adv)
        state = jnp.asarray(rng.randn(rows, N, H * P), jnp.bfloat16)
        x = jnp.asarray(rng.randn(rows, T, H * P), jnp.bfloat16)
        dt = jax.nn.softplus(jnp.asarray(rng.randn(rows, T, H), jnp.float32))
        b = jnp.asarray(rng.randn(rows, T, N), jnp.bfloat16)
        c = jnp.asarray(rng.randn(rows, T, N), jnp.bfloat16)
        adv, fresh = jnp.asarray(adv, jnp.int32), jnp.asarray(fresh, jnp.int32)
        pallas_mode.KERNEL_TILINGS.clear()
        outs = {impl: ssm.ssm_update(x, dt, a, b, c, state, adv, fresh,
                                     impl=impl) for impl in ("pallas", "scan")}
        (_, tiling), = pallas_mode.KERNEL_TILINGS
        tiling = dict(tiling)
        live = (np.arange(T)[None, :] < np.asarray(adv)[:, None])[..., None]
        y = {k: np.where(live, np.asarray(v[0], np.float32), 0.0)
             for k, v in outs.items()}
        y_most = max(float(np.abs(y["scan"]).max()), 1.0)
        s_most = max(float(jnp.abs(outs["scan"][1].astype(jnp.float32))
                           .max()), 1.0)
        err_y = _max_err(y["pallas"], y["scan"])
        err_s = _max_err(outs["pallas"][1], outs["scan"][1])
        by_adv = dict(sorted(collections.Counter(adv.tolist()).items()))
        _say(f"ssm_update pallas vs scan H={H} P={P} N={N} T={T} rows by "
             f"live columns {by_adv} bf16: grid {tiling['grid']}, state tile "
             f"{tiling['state_tile']}, matrix form from "
             f"{tiling['matrix_from']} columns; max abs err y {err_y:.3e} "
             f"of {y_most:.1f}, state {err_s:.3e} of {s_most:.1f} "
             f"(tolerance {tol:g} of the largest)")
        _require(np.isfinite(err_y + err_s)
                 and max(err_y / y_most, err_s / s_most) <= tol,
                 f"ssm_update T={T} x {rows} rows within {tol:g}")
        if not timed or pallas_mode.platform() == "cpu":
            continue        # a time is the chip's to give
        took = {}
        for name, threshold in (("as set", ssm.MATRIX_COLUMNS),
                                ("the loop alone", T + 1)):
            set_to, ssm.MATRIX_COLUMNS = ssm.MATRIX_COLUMNS, threshold
            try:
                args = (x, dt, b, c, state, adv, fresh)
                ten_calls.clear_cache()
                jax.block_until_ready(ten_calls(*args))
                t0 = time.perf_counter()
                for _ in range(5):
                    out = ten_calls(*args)
                jax.block_until_ready(out)
                took[name] = (time.perf_counter() - t0) / 50 * 1e3
            finally:
                ssm.MATRIX_COLUMNS = set_to
        _say(f"ssm_update {rows} rows x {T}: " + ", ".join(
            f"{ms:.3f} ms a call {name}" for name, ms in took.items()))


def _kda_parity(size: dict):
    """`kda_update(impl="pallas")` against `impl="scan"` on this device at
    `size["kda"]` (the linear-attention cell's: 256 rows of 64 heads over a
    packed block of 512 token rows, a float32 state): every row one live
    column (a decode step), and rows of sixteen beside rows of one, a dead
    row and fresh rows. Both run the same float32 arithmetic and differ in
    the order of their sums: 1e-4 of the largest value. The two shapes are
    also timed, a call alone, the state carried from call to call: what a
    one-column row and a sixteen-column row cost (`ops/kda.py` has no
    chunked body). Prints the kernel's grid and state tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import kda, pallas_mode
    g = size["kda"]
    H, d, rows, tokens, tol = g["heads"], g["head_dim"], g["rows"], \
        g["tokens"], 1e-4
    rng = np.random.RandomState(3)

    def unit(x):
        x = x.reshape(tokens, H, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            tokens, H * d)

    q = jnp.asarray(unit(rng.randn(tokens, H * d)) * d ** -0.5, jnp.float32)
    k = jnp.asarray(unit(rng.randn(tokens, H * d)), jnp.float32)
    v = jnp.asarray(rng.randn(tokens, H * d), jnp.bfloat16)
    decay = jnp.asarray(-rng.uniform(0.001, 1.5, (tokens, H * d)),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (tokens, H)), jnp.float32)
    state = jnp.asarray(rng.randn(rows, d, H * d), jnp.float32)
    wide = np.ones(rows, int)
    wide[::max(rows // 16, 2)] = 16      # sixteen-column rows among decodes
    wide[1] = 0
    wide = np.minimum(wide, np.maximum(tokens - np.cumsum(wide) + wide, 0))
    ten = jax.jit(lambda s, start, adv, fresh: jax.lax.fori_loop(
        0, 10, lambda _, c: kda.kda_update(
            q, k, v, decay, beta, c, start, adv, fresh, columns=16,
            impl="pallas")[1], s))
    for name, adv in (("one column a row", np.ones(rows, int)),
                      ("sixteen beside one", wide)):
        start = jnp.asarray(np.cumsum(adv) - adv, jnp.int32)
        fresh = jnp.asarray(np.arange(rows) % 7 == 3, jnp.int32)
        adv = jnp.asarray(adv, jnp.int32)
        pallas_mode.KERNEL_TILINGS.clear()
        outs = {impl: kda.kda_update(q, k, v, decay, beta, state, start, adv,
                                     fresh, columns=16, impl=impl)
                for impl in ("pallas", "scan")}
        (_, tiling), = pallas_mode.KERNEL_TILINGS
        tiling = dict(tiling)
        o_most = max(float(jnp.abs(outs["scan"][0]).max()), 1e-3)
        s_most = max(float(jnp.abs(outs["scan"][1]).max()), 1.0)
        err_o = _max_err(outs["pallas"][0], outs["scan"][0])
        err_s = _max_err(outs["pallas"][1], outs["scan"][1])
        by_adv = dict(sorted(collections.Counter(adv.tolist()).items()))
        _say(f"kda_update pallas vs scan H={H} d={d} rows by live columns "
             f"{by_adv} of {tokens} token rows, float32 state: grid "
             f"{tiling['grid']}, state tile {tiling['state_tile']}; max abs "
             f"err o {err_o:.3e} of {o_most:.3f}, state {err_s:.3e} of "
             f"{s_most:.1f} (tolerance {tol:g} of the largest)")
        _require(np.isfinite(err_o + err_s)
                 and max(err_o / o_most, err_s / s_most) <= tol,
                 f"kda_update {name} x {rows} rows within {tol:g}")
        if pallas_mode.platform() == "cpu":
            continue        # a time is the chip's to give
        jax.block_until_ready(ten(state, start, adv, fresh))
        t0 = time.perf_counter()
        for _ in range(3):
            out = ten(state, start, adv, fresh)
        jax.block_until_ready(out)
        _say(f"kda_update {rows} rows, {name}: "
             f"{(time.perf_counter() - t0) / 30 * 1e3:.3f} ms a call "
             f"(the state's {2 * rows * d * H * d * 4 / 819e9 * 1e3:.3f} ms "
             "at 819 GB/s)")


def _hyper_connection_parity(size: dict):
    """The two hyper-connection kernels (`hc_pre`, `hc_post`) against their
    plain `jax.numpy` forms on this device at `size["hyper"]`: bf16 streams
    under float32 coefficients (`phi` N(0, 0.02), gains U(0.5, 1.5), a bias
    N(0, 1): the benchmark's seeding), 20 Sinkhorn passes. The coefficients
    are float32 on both sides and differ by the order of the sums in
    `x phi` (1e-4 of a coefficient at the most); `u` and `X'` are rounded to
    bf16 once on each side: 2e-2 of the largest value. On a chip each
    kernel is also timed, ten calls in one program, beside the least time
    for its bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import hyper_connection as hc
    from paddle_tpu.ops import pallas_mode
    g = size["hyper"]
    n, C, R = g["streams"], g["hidden"], g["rows"]
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(R, n * C), jnp.bfloat16)
    y = jnp.asarray(rng.randn(R, C), jnp.bfloat16)
    phi = jnp.asarray(0.02 * rng.randn(n * C, n * n + 2 * n), jnp.float32)
    bias = jnp.asarray(rng.randn(n * n + 2 * n), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0.5, 1.5, 3), jnp.float32)
    kw = dict(n=n, iters=20, eps=1e-6, clamp=30.0, norm_eps=1e-6)
    pallas_mode.KERNEL_TILINGS.clear()
    u, post, res = hc.pre_kernel(x, phi, bias, alpha, **kw)
    u0, post0, res0 = hc._pre_plain(x, phi, bias, alpha, **kw)
    out, out0 = hc.post_kernel(x, y, post0, res0), \
        hc._post_plain(x, y, post0, res0)
    tilings = {k: dict(t) for k, t in pallas_mode.KERNEL_TILINGS}
    err_c = max(_max_err(post, post0), _max_err(res, res0))
    most_u = max(float(jnp.abs(u0.astype(jnp.float32)).max()), 1.0)
    most_x = max(float(jnp.abs(out0.astype(jnp.float32)).max()), 1.0)
    err_u, err_x = _max_err(u, u0), _max_err(out, out0)
    sums = max(float(jnp.abs(res.sum(1) - 1).max()),
               float(jnp.abs(res.sum(2) - 1).max()))
    _say(f"hc_pre / hc_post pallas vs jax.numpy rows={R} streams={n} x {C} "
         f"bf16: grid {tilings[hc.PRE_KERNEL]['grid']}, tile "
         f"{tilings[hc.PRE_KERNEL]['tile']}; max abs err coefficients "
         f"{err_c:.2e} (tolerance 1e-4), u {err_u:.2e} of {most_u:.1f}, X' "
         f"{err_x:.2e} of {most_x:.1f} (tolerance 2e-2 of the largest); "
         f"H_res rows and columns sum to 1 within {sums:.1e}")
    _require(np.isfinite(err_c + err_u + err_x) and err_c <= 1e-4
             and max(err_u / most_u, err_x / most_x) <= 2e-2,
             "hc_pre / hc_post within tolerance")
    if pallas_mode.platform() == "cpu":
        return              # a time is the chip's to give

    # each pass depends on the one before, or XLA would run the call once
    @jax.jit
    def ten_pre(x):
        return jax.lax.fori_loop(0, 10, lambda _, u: hc.pre_kernel(
            x, phi, bias, alpha + u[0, 0].astype(jnp.float32) * 0, **kw)[0],
            jnp.zeros((R, C), x.dtype))

    @jax.jit
    def ten_post(x):
        return jax.lax.fori_loop(0, 10, lambda _, s: hc.post_kernel(
            s, y, post0, res0), x)

    took = {}
    for name, fn in (("hc_pre", ten_pre), ("hc_post", ten_post)):
        jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        for _ in range(5):
            last = fn(x)
        jax.block_until_ready(last)
        took[name] = (time.perf_counter() - t0) / 50 * 1e6
    item = x.dtype.itemsize
    least = {"hc_pre": R * (n * C + C) * item / 819e9 * 1e6,
             "hc_post": R * (2 * n * C + C) * item / 819e9 * 1e6}
    _say("hc kernels a call alone: " + ", ".join(
        f"{name} {us:.1f} us (its bytes at 819 GB/s: {least[name]:.1f} us)"
        for name, us in took.items()))


def _post(port: int, path: str, payload: dict, timeout: float):
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def leg_serve(size: dict, rehearsal: bool) -> dict:
    dev, cache_before = _start_leg(rehearsal)

    import urllib.request

    import jax
    import numpy as np

    _say("[serve] kernel parity on this device")
    _paged_parity(size)
    _paged_window_parity(size)
    _moe_gmm_parity(size)
    _paged_latent_parity(size)
    _sparse_parity(size)
    _kv_write_parity(size)
    _ssm_parity(size)
    _kda_parity(size)
    _hyper_connection_parity(size)

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.ops import pallas_mode
    pallas_mode.KERNEL_TILINGS.clear()      # the engine's from here on
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.obs.compile_observatory import compile_observatory
    from paddle_tpu.obs.goodput import RecompileSentinel

    paddle.seed(0)
    model = GPTForCausalLM.from_preset(size["preset"])
    model.to(dtype="bfloat16")
    model.eval()
    max_new = size["max_new"]
    cfg = serving.LLMEngineConfig(
        num_slots=size["slots"], block_len=16, n_blocks=size["n_blocks"],
        prefill_chunk=16, max_new_tokens=max_new, observatory=True)
    _say(f"[serve] {size['preset']} bf16 behind LLMEngine + ServingServer: "
         f"{cfg.num_slots} slots x {cfg.n_blocks} pages x {cfg.block_len} "
         f"tokens, prefill_chunk {cfg.prefill_chunk}")
    sentinel = RecompileSentinel().install()
    engine = serving.LLMEngine(model, cfg)
    # the first request compiles the 1.3 B step and the 1,500-token prompt
    # takes ~94 chunked steps: give a request the leg's whole budget, not
    # the front end's 60 s default
    server = serving.ServingServer(llm_engine=engine, port=0,
                                   request_timeout_s=BUDGET_S).start()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, model.config.vocab_size, (n,)).tolist()
               for n in size["prompts"]]
    replies = [None] * len(prompts)

    def ask(i):
        t0 = time.perf_counter()
        status, body = _post(server.port, "/generate", {
            "input_ids": prompts[i], "max_new_tokens": max_new,
            "logprobs": True}, timeout=BUDGET_S)
        replies[i] = (status, body, time.perf_counter() - t0)

    try:
        ask(0)
        # everything the engine needs is compiled now: any later XLA
        # compilation is a recompile
        sentinel.mark_warm()
        compile_observatory().mark_warm()
        ask(1)
        pair = [threading.Thread(target=ask, args=(i,)) for i in (2, 3)]
        for t in pair:
            t.start()
        for t in pair:
            t.join(timeout=BUDGET_S)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=60) as r:
            metrics_status, metrics_text = r.status, r.read().decode()
        mixed_steps = engine.decode_iterations
        prefill_only = engine.prefill_dispatches
    finally:
        server.stop()
        sentinel.uninstall()

    for i, rep in enumerate(replies):
        _require(rep is not None, f"request {i} returned")
        status, body, wall = rep
        toks, lps = body.get("tokens", []), body.get("logprobs", [])
        _say(f"[serve] request {i}: prompt {len(prompts[i])} tokens -> "
             f"HTTP {status}, {len(toks)} tokens, ttft "
             f"{body.get('ttft_ms')} ms, {wall:.2f}s wall")
        _require(status == 200, f"request {i} answered 200")
        _require(len(toks) == max_new, f"request {i} has {max_new} tokens")
        _require(len(lps) == max_new and bool(np.all(np.isfinite(lps))),
                 f"request {i} has {max_new} finite logprobs")
    _require(metrics_status == 200 and "pdtpu_llm_" in metrics_text,
             "/metrics scrapes with the pdtpu_llm_ families")
    _say(f"[serve] steps carrying a decode row {mixed_steps}, prefill-only "
         f"steps {prefill_only}")
    _require(sentinel.recompiles == 0,
             f"zero XLA compilations after the first request "
             f"(sentinel saw {sentinel.recompiles})")
    _require(compile_observatory().recompiles == 0,
             "zero unified-step recompiles (compile observatory)")

    row = _kernel_row("llm/unified_step")
    kernels = row["pallas_kernels"] or {}
    _say(f"[serve] compiled unified step: {_phases(row)}, temp "
         f"{row['temp_bytes']} B, args {row['argument_bytes']} B, Pallas "
         f"kernels {kernels}")
    _say(f"[serve] kernel trace paths: {_trace_paths()}")
    _say(f"[serve] kernel tilings: {_tilings()}")
    if rehearsal:
        _say("  kernel census not applicable on the CPU (impl=None is the "
             "scan path there by design)")
    else:
        n_layers = model.config.num_hidden_layers
        for kernel in ("paged_attention", "kv_write"):
            _require(kernels.get(kernel, 0) == n_layers,
                     f"compiled unified step holds {n_layers} {kernel} "
                     f"custom calls (got {kernels.get(kernel, 0)})")

    # informational: the engine's greedy stream against one-shot generate().
    # Bit-identity is pinned on the CPU in tests (the scan on both sides at
    # one block_len). On the chip the kernel groups 128 keys a step at
    # either block_len, but the engine's rows are chunks of its prompt
    # beside other slots' and generate()'s a whole prompt, then one token:
    # different matmul shapes around the kernel, so the bits may differ.
    p0 = np.asarray(prompts[0], np.int32)
    one_shot = np.asarray(generate(model, p0[None, :],
                                   max_new_tokens=max_new).data)[0, len(p0):]
    eng_toks = np.asarray(replies[0][1]["tokens"])
    agree = int(np.argmax(np.append(one_shot != eng_toks, True)))
    _say(f"[serve] INFO engine greedy stream == one-shot generate(): "
         f"{bool(agree == max_new)} (first {agree} of {max_new} tokens "
         f"agree; not required)")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    _say(f"[serve] INFO (one unrepeated run, not a metric): request wall "
         f"seconds {[round(r[2], 2) for r in replies]} (the first includes "
         f"compilation), peak_bytes_in_use per device {peaks}")
    cache_after = _cache_report("after")
    return {"device": dev, "request_wall_s": [r[2] for r in replies],
            "ttft_ms": [r[1].get("ttft_ms") for r in replies],
            "compile_s": row["compile_seconds"], "pallas_kernels": kernels,
            "generate_agrees": bool(agree == max_new),
            "peak_bytes_in_use": peaks,
            "cache_entries": [cache_before, cache_after]}


# --------------------------------------------------------------------------
# parent: no jax, no paddle_tpu
# --------------------------------------------------------------------------

def _result_path(leg: str) -> str:
    return os.path.join(OUT_DIR, f"chip_smoke_{leg}.json")


def run_leg(leg: str, rehearsal: bool, layout: str):
    size = TINY if rehearsal else FULL
    t0 = time.perf_counter()
    result = (leg_train(size, rehearsal, layout) if leg == "train"
              else leg_serve(size, rehearsal))
    result["leg"] = leg
    result["wall_s"] = round(time.perf_counter() - t0, 1)
    os.makedirs(OUT_DIR, exist_ok=True)
    tmp = _result_path(leg) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, _result_path(leg))
    _say(f"[{leg}] leg passed in {result['wall_s']}s")


def _run_child(leg: str, rehearsal: bool, layout: str,
               deadline: float) -> dict:
    try:
        os.remove(_result_path(leg))
    except FileNotFoundError:
        pass
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg]
    if rehearsal:
        cmd.append("--cpu-rehearsal")
    if layout:
        cmd += ["--layout", layout]
    proc = subprocess.Popen(cmd, cwd=REPO, start_new_session=True)

    def _kill(*_):
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()

    prev = signal.signal(signal.SIGTERM,
                         lambda *_: (_kill(), sys.exit(143)))
    try:
        rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
    except subprocess.TimeoutExpired:
        raise SystemExit(f"chip_smoke: {leg} leg overran the "
                         f"{BUDGET_S:.0f}s budget; killed")
    finally:
        _kill()
        signal.signal(signal.SIGTERM, prev)
    if rc != 0:
        raise SystemExit(f"chip_smoke: {leg} leg failed (exit code {rc})")
    with open(_result_path(leg)) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=LEGS,
                    help="run one leg in this process (what the parent "
                         "starts; also handy for debugging one half)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="gpt2-tiny on the CPU: control flow only, prints "
                         "platform=cpu, never prints the ok line")
    ap.add_argument("--layout", default="",
                    help='train-leg mesh layout on a multi-chip host, e.g. '
                         '"dp=2,mp=2" (default: dp x sharding=2, ZeRO-2, '
                         'over every local device)')
    args = ap.parse_args(argv)
    if args.leg:
        run_leg(args.leg, args.cpu_rehearsal, args.layout)
        return 0

    deadline = time.monotonic() + BUDGET_S
    t0 = time.monotonic()
    results = [_run_child(leg, args.cpu_rehearsal, args.layout, deadline)
               for leg in LEGS]
    devices = [r["device"] for r in results]
    if devices[0] != devices[1]:
        raise SystemExit(f"chip_smoke: legs saw different devices: "
                         f"{devices}")
    for r in results:
        _say(f"{r['leg']} leg: {r['wall_s']}s, step compile "
             f"{r['compile_s']:.1f}s, compile cache entries "
             f"{r['cache_entries'][0]} -> {r['cache_entries'][1]}")
    _say(f"chip_smoke: both legs passed in {time.monotonic() - t0:.0f}s")
    if args.cpu_rehearsal:
        _say(json.dumps({"rehearsal": True, "device": devices[0]}))
    else:
        _say(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
