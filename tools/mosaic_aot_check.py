"""Compile the Pallas kernels for a TPU v5e with no chip attached.

The installed libtpu can describe a v5e topology and run XLA's TPU compiler
(Mosaic included) against it on a CPU-only machine, so a kernel that Mosaic
rejects is found here, in seconds and for no chip time, instead of on the
chip. It cannot run anything: numbers and numerics still need
`chip_smoke.py` on a chip.

    python tools/mosaic_aot_check.py        # every kernel at smoke shapes

Prints one `[OK]`/`[FAIL]` line per case; exit 1 if any case failed, exit 3
if libtpu could not describe the topology (one process at a time may hold
libtpu: /tmp/libtpu_lockfile). `tests/test_mosaic_aot.py` runs it in tier-1.
Other scripts can reuse `v5e_devices()` + `use_tpu_lowering()` to lower
their own jitted step for the chip (pass ShapeDtypeStructs whose sharding
names these devices, then `.lower(...).compile()`).
"""
from __future__ import annotations

import os
import re
import sys
import time

# the process stays on the CPU backend; libtpu is only asked to compile
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def v5e_devices(n: int = 1):
    """`n` (1 or 4) compile-only v5e devices from libtpu's topology."""
    from jax.experimental import topologies
    if n == 1:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    else:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    return topo.devices


def use_tpu_lowering():
    """Make ops.pallas_mode answer for the devices being compiled FOR (the
    default backend here is the CPU, which would pick interpret mode)."""
    from paddle_tpu.ops import pallas_mode
    pallas_mode.platform = lambda: "tpu"


def compile_case(name, fn, *specs, want=None, memory=False,
                 forbid=None) -> bool:
    """Lower + compile `fn` for the specs' devices; `want` is the expected
    {kernel: count} of Mosaic custom calls in the compiled text, `forbid` a
    pattern no line of it may hold."""
    from paddle_tpu.obs.compile_observatory import pallas_kernel_census
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn).lower(*specs).compile()
    except Exception as e:  # the tool's job is to report every case
        print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:1500]}",
              flush=True)
        return False
    text = compiled.as_text()
    census = pallas_kernel_census(text)
    found = re.findall(forbid, text) if forbid else []
    ok = (want is None or census == want) and not found
    held = f"; no {forbid}" if forbid and not found else ""
    if memory:
        m = compiled.memory_analysis()
        held += (f"; {m.argument_size_in_bytes} bytes of arguments, "
                f"{m.output_size_in_bytes} of results, "
                f"{m.temp_size_in_bytes} of temporaries")
    print(f"[{'OK' if ok else 'FAIL'}] {name}: {census}{held} in "
          f"{time.perf_counter() - t0:.1f}s"
          + ("" if ok else f" (wanted {want}; forbidden, found: "
             f"{found[:3]})"), flush=True)
    return ok


def _subjaxprs(eqn):
    """The jaxprs an equation holds (a loop's body, a branch, a call)."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _equations(jaxpr) -> int:
    """Equations of a jaxpr, those of the jaxprs they hold included."""
    return sum(1 + sum(map(_equations, _subjaxprs(eqn)))
               for eqn in jaxpr.eqns)


def kernel_equations(fn, *specs) -> dict:
    """{kernel: [equations of its body, a call in order]} for every
    `pallas_call` that tracing `fn` reaches: what a process traces and
    lowers for a kernel before the compile cache can be asked."""
    found = {}

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.setdefault(eqn.params["name"], []).append(
                    _equations(eqn.params["jaxpr"]))
            else:
                for sub in _subjaxprs(eqn):
                    visit(sub)
    visit(jax.make_jaxpr(fn)(*specs).jaxpr)
    return found


def recompute_grad(B, H, S, D, layers):
    """The gradient of `layers` residual attention blocks, each under
    `recompute()` as `models/gpt.py` wraps its decoder layers, with respect
    to the input `[B, S, H * D]` and the stacked QKV weights."""
    from paddle_tpu.core.tensor import Tensor, apply, no_grad
    from paddle_tpu.distributed.fleet.utils.recompute import recompute
    from paddle_tpu.ops.attention import flash_attention

    def block(a, w):
        qkv = (a @ w).reshape(B, S, 3, H, D).transpose(2, 0, 3, 1, 4)
        o = flash_attention(qkv[0], qkv[1], qkv[2], causal=True)
        return a + o.transpose(0, 2, 1, 3).reshape(B, S, H * D)

    def loss(a, ws):
        x = Tensor(a)
        with no_grad():
            for i in range(layers):
                x = recompute(lambda t, w=ws[i]: apply(block, t, w), x)
        return jnp.sum(x.data.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1))


def serve_step_case(name, model, device, want) -> bool:
    """Trace, lower and compile (timed apart) the unified step of an
    `LLMEngine` over `model` for `device`. `want` is the number of Mosaic
    kernel bodies in the lowered module: one a distinct kernel shape,
    whatever the layers that call it (the compiled text still holds a
    custom call a layer)."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import serving
    from paddle_tpu.obs.compile_observatory import pallas_kernel_census
    eng = serving.LLMEngine(model, serving.LLMEngineConfig(
        num_slots=8, block_len=16, n_blocks=20, max_queue_depth=8,
        enable_prefix_cache=False), clock=serving.SimClock())
    eng.submit(np.arange(1, 40, dtype=np.int32), max_new_tokens=4)
    with eng._cond:                # the operands of a step, as `_launch`'s
        eng._admit()
        toks, pos, adv, ctr, *_ = eng._build_rows_locked({})
        args = (eng.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(adv), eng.pool.device_block_table(),
                eng.pool.slabs) + eng._sampling_args_locked(ctr) \
            + eng._feedback_args() + eng._tail_args_locked()
    sharding = SingleDeviceSharding(device)
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    try:
        t0 = time.perf_counter()
        traced = eng._step().trace(*specs)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
    except Exception as e:  # the tool's job is to report every case
        print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:1500]}",
              flush=True)
        return False
    bodies = lowered.as_text().count("stablehlo.custom_call @tpu_custom_call")
    # the step is donated the pool: every slab's bytes must be aliased to
    # the result, or the executable copies the pool once a step
    slabs = jax.tree_util.tree_leaves(eng.pool.slabs)
    pool = sum(a.nbytes for a in slabs)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    loops, copies = slab_loops_and_copies(compiled.as_text(), slabs)
    ok = bodies == want and aliased >= pool and not loops and not copies
    print(f"[{'OK' if ok else 'FAIL'}] {name}: {bodies} Mosaic "
          f"{'body' if bodies == 1 else 'bodies'} in the lowered step for "
          f"{pallas_kernel_census(compiled.as_text())} in the compiled one; "
          f"{aliased} bytes aliased of a pool of {pool}; {loops} loops over "
          f"a slab, {copies} copies of one; "
          f"trace {t1 - t0:.1f}s + lower {t2 - t1:.1f}s + compile "
          f"{t3 - t2:.1f}s" + ("" if ok else f" (wanted {want})"),
          flush=True)
    return ok


def serve_tail_case(name, rows, hidden, vocab, spec) -> bool:
    """Compile the unified step's tail at a serve cell's widths: the
    emission rows of a 512-position block (`take_positions`), the head's
    product, `select_tokens` with the bank of 8 grammars x 128 states the
    engine holds, the log-softmax at the selections. The product must be
    `[rows, hidden] x [hidden, vocab]`. No instruction of the entry
    computation may make an array of the bank's shape or of a piece of it:
    XLA's TPU gather splits the vocabulary and copies the whole bank to
    pick its rows, in every step before PR 52 and since then inside the
    conditional's branch that only a step with a constrained row runs.
    Beyond that copy the temporaries must stay within two float32 `[rows,
    vocab]` arrays."""
    from paddle_tpu.ops.attention import take_positions
    from paddle_tpu.serving.llm.sampling import select_tokens

    def tail(block, emit, w, adv, temp, topk, topp, samp, seed, ctr, dstate,
             gid, bank):
        logits = (take_positions(block, emit) @ w).reshape(rows, 1, vocab)
        sel, state = select_tokens(logits, adv, temp, topk, topp, samp,
                                   seed, ctr, dstate, gid, bank)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
            sel[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return sel, lp, state

    i32, f32 = spec((rows,), jnp.int32), spec((rows,), jnp.float32)
    bank = (9, 128, vocab)
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(tail).lower(
            spec((512, 1, hidden), jnp.bfloat16), i32,
            spec((hidden, vocab), jnp.bfloat16), i32, f32, i32, f32,
            spec((rows,), jnp.bool_), i32, i32, i32, i32,
            spec(bank, jnp.int32)).compile()
    except Exception as e:  # the tool's job is to report every case
        print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:1500]}",
              flush=True)
        return False
    text = compiled.as_text()
    products = re.findall(r"= bf16\[(\d+),(\d+)\]\S* convolution\(", text)
    # instructions that make (not merely hand on) the bank or a piece
    makes = (r"= \(?s32\[9,128,\d+\][^=]*? (?:fusion|copy|copy-start|gather|"
             r"slice|dynamic-slice)\(")
    entry = len(re.findall(makes, text[text.index("\nENTRY "):]))
    inside = len(re.findall(makes, text)) - entry
    temps = compiled.memory_analysis().temp_size_in_bytes
    bank_bytes, logits_bytes = 9 * 128 * vocab * 4, rows * vocab * 4
    ok = products == [(str(rows), str(vocab))] and not entry \
        and temps < bank_bytes + 2 * logits_bytes
    print(f"[{'OK' if ok else 'FAIL'}] {name}: head product "
          f"{[list(map(int, p)) for p in products]} over [{rows}, {hidden}]; "
          f"{entry} instructions of the entry computation make an array of "
          f"the bank's shape {list(bank)} or a piece of it, {inside} inside "
          f"the conditional; {temps} bytes of temporaries (the bank "
          f"{bank_bytes}, a float32 [rows, vocab] {logits_bytes}) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return ok


def slab_loops_and_copies(hlo: str, slabs) -> tuple:
    """(`while` instructions that carry an array of a K/V slab's shape,
    `copy` instructions that make one) in compiled HLO text. A vmapped
    `dynamic_update_slice` (a scatter) compiles for the v5e to such a loop,
    one trip a row (PR 37); a result that cannot be written in place, to
    such a copy (PR 35)."""
    short = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
    # the K/V slabs `[slots, Hkv, L, D]` (a recurrent layer's small
    # convolution state is rebuilt by a concatenate, not written in place)
    shapes = {f"{short[str(a.dtype)]}[{','.join(map(str, a.shape))}]"
              for a in slabs if len(a.shape) == 4}
    loops = copies = 0
    for line in hlo.splitlines():
        _, _, rest = line.partition(" = ")
        carried, is_loop, _ = rest.partition(" while(")
        loops += bool(is_loop) and any(s in carried for s in shapes)
        made = re.match(r"(\w+\[[\d,]*\])\{[^}]*\} copy\(", rest)
        copies += bool(made) and made[1] in shapes
    return loops, copies


def kv_write_case(name, slab, widths, T, ring, sharding) -> bool:
    """Compile `kv_write` alone, donated its slabs `[B, Hkv, L, widths[i]]`
    (two, or a sparse layer's three): one Mosaic call for all the caches,
    every byte aliased, and no loop or copy of a slab beside it."""
    from paddle_tpu.obs.compile_observatory import pallas_kernel_census
    from paddle_tpu.ops.kv_write import (kv_write_many,
                                         kv_write_many_supported)
    B, Hkv, L = slab

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    caches = [spec((B, Hkv, L, w)) for w in widths]
    stripes = [spec((B, Hkv, T, w)) for w in widths]
    name = (f"kv_write bf16 {name} slab={[B, Hkv, L]} x "
            f"{' | '.join(map(str, widths))} T={T} ring={ring}")
    t0 = time.perf_counter()
    n = len(widths)
    try:
        assert kv_write_many_supported(caches, stripes, ring)
        compiled = jax.jit(
            lambda *a: kv_write_many(a[:n], a[n:2 * n], a[2 * n], ring=ring),
            donate_argnums=tuple(range(n))).lower(
                *caches, *stripes, spec((B,), jnp.int32)).compile()
    except Exception as e:  # the tool's job is to report every case
        print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:1500]}",
              flush=True)
        return False
    hlo = compiled.as_text()
    census = pallas_kernel_census(hlo)
    pool = sum(int(np.prod(c.shape)) * 2 for c in caches)
    aliased = compiled.memory_analysis().alias_size_in_bytes
    loops, copies = slab_loops_and_copies(hlo, caches)
    ok = census == {"kv_write": 1} and aliased == pool \
        and not loops and not copies
    print(f"[{'OK' if ok else 'FAIL'}] {name}: {census}, {aliased} bytes "
          f"aliased of {pool}, {loops} loops over a slab, {copies} copies "
          f"of one, in {time.perf_counter() - t0:.1f}s", flush=True)
    return ok


def main() -> int:
    try:
        dev1, dev4 = v5e_devices(1), v5e_devices(4)
    except Exception as e:
        print(f"mosaic_aot_check: libtpu gave no v5e topology: {e}",
              file=sys.stderr)
        return 3
    use_tpu_lowering()
    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops.paged_attention import ragged_paged_attention

    one = NamedSharding(Mesh(np.array(dev1), ("x",)), P())

    def spec(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    results = []
    # the three flash kernels at the tiles `_choose_tiles` gives (the
    # `tiling flash_*` lines below): chip_smoke's parity shape plain and
    # with dropout, the train cells' shape, the rehearsal's narrow heads,
    # a length 512 does not divide, a rectangle with its diagonal at
    # Sk - Sq, and 32k, whose K and V no longer fit whole
    for label, shape_q, shape_k, kw in (
            ("", (2, 16, 1024, 128), None, {}),
            (" dropout", (2, 16, 1024, 128), None,
             dict(dropout_p=0.1, dropout_seed=7)),
            ("", (8, 16, 2048, 128), None, {}),
            ("", (1, 2, 512, 64), None, {}),
            ("", (2, 4, 768, 128), None, {}),
            (" over 2048 keys", (2, 4, 512, 128), (2, 4, 2048, 128), {}),
            (" in chunks", (1, 2, 32768, 128), None, {})):
        def loss(q, k, v, kw=kw):
            return jnp.sum(A.flash_attention(q, k, v, causal=True, **kw)
                           .astype(jnp.float32))
        results.append(compile_case(
            f"flash fwd+bwd bf16 {list(shape_q)}{label}",
            jax.grad(loss, argnums=(0, 1, 2)),
            *[spec(s, jnp.bfloat16)
              for s in (shape_q, shape_k or shape_q, shape_k or shape_q)],
            want={"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}))

    # per-layer recompute at the train cells' shapes: a layer's replay in
    # the backward pass takes the forward kernel's kept `out` and `lse`, so
    # two layers hold two forward calls (four without the policy: PR 41);
    # `lse` and delta go from kernel to kernel as lane-dense rows, so the
    # compiled gradient holds no `f32[128,2048,1]` (134 MB where the values
    # are 1 MB, relaid out by a `copy` each: four a layer before PR 45)
    results.append(compile_case(
        "recompute of 2 layers, flash fwd+bwd bf16 [8,16,2048,128]",
        recompute_grad(8, 16, 2048, 128, layers=2),
        spec((8, 2048, 2048), jnp.bfloat16),
        spec((2, 2048, 3 * 2048), jnp.bfloat16),
        want={"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2},
        memory=True, forbid=r"f32\[\d+,2048,1\]"))

    # (label, q [B, H, Tq, D], slab [N, Hkv, L_slab, D], block_len, pages a
    # row): MHA at both block sizes the repo runs and both query widths,
    # then the serve cells' own shapes, the one-shot decode loop's GQA row
    # and whole-prompt prefills that must split the heads (G > 1). A step
    # takes a group of 128 keys, 8 pages of 16 or 16 pages of 8 (an 8-row
    # bf16 page is half a packed sublane tile): `pages` in the tilings
    D, L = 128, 2048
    paged_cases = [
        (f"block_len={bl} Tq={Tq}", (8, 16, Tq, D), (8, 16, L + 16, D), bl,
         L // bl) for bl in (16, 8) for Tq in (1, 16)]
    paged_cases += [
        ("mistral decode cell", (128, 32, 16, D), (128, 8, 240, D), 16, 14),
        ("olmoe decode cell", (128, 16, 16, D), (128, 16, 240, D), 16, 14),
        ("mistral prefill cells", (32, 32, 16, D), (32, 8, 1056, D), 16, 65),
        ("GQA block_len=8 Tq=1", (8, 32, 1, D), (8, 8, L + 16, D), 8, L // 8),
        ("GQA block_len=8 Tq=512, G>1", (2, 32, 512, D), (2, 8, 512, D), 8,
         64),
        ("GQA block_len=16 Tq=512, G>1", (2, 32, 512, D), (2, 8, 528, D), 16,
         33),
        # multi-query, 20 query heads on one KV head: the reasoning cell's
        # step (320 folded rows a tile) and a one-token row (20 rows: two
        # and a half sublane tiles, padded to 24)
        ("MQA 20:1 reasoning cell", (256, 20, 16, D), (256, 1, 2576, D), 16,
         160),
        ("MQA 20:1 Tq=1", (8, 20, 1, D), (8, 1, 2576, D), 16, 160),
        # 6 query heads a KV head (48 over 8), the full layers of a model
        # whose head count differs by layer type: 96 folded rows a tile at
        # the step's rows, and the one-column body at fold 6 (6 of a packed
        # sublane tile's 16 rows); a one-token row folds 6
        ("GQA 6:1 reasoning cell, full layers", (128, 48, 16, D),
         (128, 8, 2576, D), 16, 160),
        ("GQA 6:1 Tq=1", (128, 48, 1, D), (128, 8, 2576, D), 16, 160),
    ]
    for label, q_shape, slab, bl, ppr in paged_cases:
        def paged(q, k, v, t, sl, qp, bl=bl, ppr=ppr):
            return ragged_paged_attention(
                q, k, v, t, sl, qp, block_len=bl, pages_per_row=ppr,
                impl="pallas")
        B = q_shape[0]
        results.append(compile_case(
            f"paged bf16 {label} q={list(q_shape)} slab={list(slab)}", paged,
            spec(q_shape, jnp.bfloat16), spec(slab, jnp.bfloat16),
            spec(slab, jnp.bfloat16), spec((B, ppr), jnp.int32),
            spec((B,), jnp.int32), spec((B,), jnp.int32),
            want={"paged_attention": 1}))
        if label.startswith(("block_len=", "mistral decode", "olmoe")):
            # the body a process traces and lowers does not grow with the
            # pages of a group: the copies are issued by a loop. A trace
            # whose tile is smaller with one column than with sixteen
            # (`one_column_rows` in its tiling) holds the group's
            # arithmetic twice, Mistral's GQA tile; OLMoE's MHA tile holds
            # the one body it held before PR 48
            body = kernel_equations(
                paged, spec(q_shape, jnp.bfloat16), spec(slab, jnp.bfloat16),
                spec(slab, jnp.bfloat16), spec((B, ppr), jnp.int32),
                spec((B,), jnp.int32), spec((B,), jnp.int32))
            print(f"body paged_attention {label}: "
                  f"{body['paged_attention'][0]} equations", flush=True)
    # the windowed walk through a ring (`paged_window`) and the full walk at
    # the window/full GQA cell's shapes: 32 slots, 32/4 heads x 128, a ring
    # of 65 pages (window 1,024 + a chunk) beside 518 full-length pages
    # (neither a multiple of a group's 8); the step's 16-wide rows, and a
    # one-token row
    for label, q_shape, slab, ppr, window in (
            ("window ring, chunk rows", (32, 32, 16, D), (32, 4, 1056, D),
             65, 1024),
            ("window ring, Tq=1", (32, 32, 1, D), (32, 4, 1056, D), 65,
             1024),
            ("window/full cell, full layers", (32, 32, 16, D),
             (32, 4, 8304, D), 518, None),
            # the same model's window layers at 64 heads over 8 (fold 8): a
            # ring of 33 pages (window 512 + a chunk), 128 slots
            ("GQA 8:1 window ring of 33 pages, chunk rows",
             (128, 64, 16, D), (128, 8, 544, D), 33, 512),
            ("GQA 8:1 window ring of 33 pages, Tq=1", (128, 64, 1, D),
             (128, 8, 544, D), 33, 512)):
        def windowed(q, k, v, t, sl, qp, ppr=ppr, window=window):
            return ragged_paged_attention(
                q, k, v, t, sl, qp, block_len=16, pages_per_row=ppr,
                impl="pallas", window=window)
        B = q_shape[0]
        results.append(compile_case(
            f"paged bf16 {label} q={list(q_shape)} slab={list(slab)}",
            windowed, spec(q_shape, jnp.bfloat16), spec(slab, jnp.bfloat16),
            spec(slab, jnp.bfloat16), spec((B, ppr), jnp.int32),
            spec((B,), jnp.int32), spec((B,), jnp.int32),
            want={"paged_window" if window else "paged_attention": 1}))
    # the latent walk (`paged_latent`) in its one layout, queries and
    # result token-major `[positions, H, 512 | 128]` with a start a row
    # (`packed_latent_attention`), at the two latent cells' shapes. The
    # latent cell: 32 slots of 518 pages, 64 query heads in two tiles of
    # 32, an engine that does not pack (32 x 16 positions, row n from
    # n x 16), and `generate()`'s one-token rows (one tile). The
    # hyper-connected cell: 256 slots of 160 pages, 32 heads, the packed
    # step's 512 positions and the 15 of pad a last row's window may run
    # into, the rows' starts a `TokenPack`'s
    from paddle_tpu.ops.paged_attention import packed_latent_attention
    for label, n, H, B, L, ppr, Tq, scale in (
            ("chunk rows", 512, 64, 32, 8304, 518, 16, 0.1309),
            ("Tq=1", 32, 64, 32, 8304, 518, 1, 0.1309),
            ("decode-heavy rows", 527, 32, 256, 2576, 160, 16, 0.1544)):
        def latent(q, qr, c, r, t, sl, qp, st, ppr=ppr, Tq=Tq, scale=scale):
            return packed_latent_attention(
                q, qr, c, r, t, sl, qp, st, width=Tq, block_len=16,
                pages_per_row=ppr, scale=scale, impl="pallas")
        results.append(compile_case(
            f"paged latent bf16 {label} q=[{n}, {H}, 512 | 128] rows={B} x "
            f"{Tq} slab=[{B}, 1, {L}, 512 | 128]", latent,
            spec((n, H, 512), jnp.bfloat16), spec((n, H, 128), jnp.bfloat16),
            spec((B, 1, L, 512), jnp.bfloat16),
            spec((B, 1, L, 128), jnp.bfloat16), spec((B, ppr), jnp.int32),
            spec((B,), jnp.int32), spec((B,), jnp.int32),
            spec((B,), jnp.int32), want={"paged_latent": 1}))
    # the two halves of a hyper-connection (`hc_pre`, `hc_post`) at the
    # hyper-connected cell's shapes: a packed step's 512 positions of 4
    # streams x 3,584 in bf16, the parameters as the benchmark holds them;
    # and an unpacked step's 48 rows (brought up to one grid step of 128)
    from paddle_tpu.ops import hyper_connection as HC
    for rows in (512, 48):
        results.append(compile_case(
            f"hc_pre bf16 rows={rows} streams=[4, 3584] phi=[14336, 24]",
            lambda x, phi, b, a: HC.hc_pre(x, phi, b, a, n=4),
            spec((rows, 1, 4 * 3584), jnp.bfloat16),
            spec((4 * 3584, 24), jnp.bfloat16), spec((24,), jnp.bfloat16),
            spec((3,), jnp.bfloat16), want={"hc_pre": 1}))
        results.append(compile_case(
            f"hc_post bf16 rows={rows} streams=[4, 3584]", HC.hc_post,
            spec((rows, 1, 4 * 3584), jnp.bfloat16),
            spec((rows, 1, 3584), jnp.bfloat16),
            spec((rows, 1, 4), jnp.float32),
            spec((rows, 1, 4, 4), jnp.float32), want={"hc_post": 1}))
    # learned sparse attention at the sessions cell's shapes: 16 slots of
    # 2,304 pages, an indexer of 32 x 128 over index-key pages
    # (`index_score`), the exact top-2,048 of a row's 16 score vectors as a
    # mask (`index_topk`), and the latent attention over the selection
    # (`paged_sparse`: a decode row's walk over its gathered keys and a
    # chunk row's walk under its columns' masks: two calls, two bodies)
    from paddle_tpu.ops import index_select as IX
    from paddle_tpu.ops.paged_attention import sparse_latent_attention
    sB, sT, sP = 16, 16, 2304
    sL = sP * 16
    rows3 = (spec((sB, sP), jnp.int32), spec((sB,), jnp.int32),
             spec((sB,), jnp.int32))
    results.append(compile_case(
        f"index_score bf16 q=[{sB}, 32, {sT}, 128] slab=[{sB}, 1, {sL + 16},"
        " 128]",
        lambda q, w, k, t, sl, qp: IX.index_scores(
            q, w, k, t, sl, qp, block_len=16, pages_per_row=sP,
            impl="pallas"),
        spec((sB, 32, sT, 128), jnp.bfloat16),
        spec((sB, 32, sT), jnp.float32),
        spec((sB, 1, sL + 16, 128), jnp.bfloat16), *rows3,
        want={"index_score": 1}))
    results.append(compile_case(
        f"index_topk k=2048 scores=[{sB}, {sT}, {sL}]",
        lambda scores: IX.topk_mask(scores, 2048, impl="pallas"),
        spec((sB, sT, sL), jnp.float32), want={"index_topk": 1}))
    for label, Tq, want in (("chunk rows", sT, 2), ("Tq=1", 1, 1)):
        def sparse(q, qr, c, r, t, sl, qp, mask, idx, count):
            return sparse_latent_attention(
                q, c, r, t, sl, qp, sel=IX.Selection(mask, idx, count),
                block_len=16, pages_per_row=sP, scale=0.0625, q_rope=qr,
                impl="pallas")
        specs = (spec((sB, 64, Tq, 512), jnp.bfloat16),
                 spec((sB, 64, Tq, 128), jnp.bfloat16),
                 spec((sB, 1, sL + 16, 512), jnp.bfloat16),
                 spec((sB, 1, sL + 16, 128), jnp.bfloat16), *rows3,
                 spec((sB, Tq, sL), jnp.float32),
                 spec((sB, 2048), jnp.int32), spec((sB,), jnp.int32))
        results.append(compile_case(
            f"paged sparse bf16 {label} q=[{sB}, 64, {Tq}, 512 | 128] "
            f"slab=[{sB}, 1, {sL + 16}, 512 | 128] k=2048", sparse, *specs,
            want={"paged_sparse": want}))
        if Tq > 1:      # the gathered walk's body, then the masked walk's
            body = kernel_equations(sparse, *specs)
            print(f"body paged_sparse {label}: "
                  f"{body['paged_sparse']} equations", flush=True)
    # the K/V write (`kv_write`) at every serve cell's slabs: Mistral's
    # decode and prefill cells, OLMoE's 16 heads (4 rows a grid step),
    # the window/full cell's full-length and ring slabs, the latent cell's
    # unequal pair, granite's one attention layer; and a 16-bit-odd batch
    for label, slab, widths, ring in (
            ("mistral decode cell", (128, 8, 240), (D, D), None),
            ("mistral prefill cells", (32, 8, 1056), (D, D), None),
            ("olmoe decode cell", (128, 16, 240), (D, D), None),
            ("window/full cell, full layers", (32, 4, 8304), (D, D), None),
            ("window/full cell, ring", (32, 4, 1056), (D, D), 1040),
            ("latent cell", (32, 1, 8304), (512, D), None),
            ("sessions cell, shared layers", (16, 1, 36880), (512, D), None),
            ("sessions cell, full layers", (16, 1, 36880), (512, D, D),
             None),
            ("granite decode cell", (128, 8, 240), (D, D), None),
            ("reasoning cell", (256, 1, 2576), (D, D), None),
            ("hyper-connected cell", (256, 1, 2576), (512, D), None),
            ("heads-by-layer cell, full layers", (128, 8, 2576), (D, D),
             None),
            ("heads-by-layer cell, ring", (128, 8, 544), (D, D), 528),
            ("a batch of one", (1, 8, 2064), (D, D), None)):
        results.append(kv_write_case(label, slab, widths, 16, ring, one))
    body = kernel_equations(
        lambda kc, vc, kn, vn, pos: A.update_kv_cache(kc, vc, kn, vn, pos,
                                                      ring=1040),
        spec((32, 4, 1056, D), jnp.bfloat16),
        spec((32, 4, 1056, D), jnp.bfloat16),
        spec((32, 4, 16, D), jnp.bfloat16), spec((32, 4, 16, D), jnp.bfloat16),
        spec((32,), jnp.int32))
    print(f"body kv_write ring=1040: {body['kv_write'][0]} equations",
          flush=True)
    # the grouped matmul of the dropless expert layer at OLMoE's widths and
    # the decode cell's rows (2,048 positions x 8 experts each)
    from paddle_tpu.ops.grouped_matmul import grouped_matmul
    # and at granite-4.0-h-small's (512 packed positions x 10, 18 held)
    # and at the window/full cell's (512 x 8, 16 of 64 held, width 896)
    # and at the latent cell's (512 x 8, 12 of 192 held, width 2,048)
    # and at 256 small experts, every one held (512 x 8, width 512: 16
    # rows a group, less than a row tile)
    for m, e, k, n in ((16384, 64, 2048, 1024), (16384, 64, 1024, 2048),
                       (5120, 18, 4096, 768), (5120, 18, 768, 4096),
                       (4096, 16, 2304, 896), (4096, 16, 896, 2304),
                       (4096, 12, 7168, 2048), (4096, 12, 2048, 7168),
                       (4096, 256, 2048, 512), (4096, 256, 512, 2048)):
        results.append(compile_case(
            f"moe_gmm bf16 [{m},{k}] x [{e},{k},{n}]",
            lambda lhs, rhs, gs: grouped_matmul(lhs, rhs, gs, impl="pallas"),
            spec((m, k), jnp.bfloat16), spec((e, k, n), jnp.bfloat16),
            spec((e,), jnp.int32), want={"moe_gmm": 1}))

    # the Mamba-2 recurrence at granite-4.0-h-small's widths: the decode
    # and the prefill cell's steps (128 and 32 slots x 16 columns: the loop
    # and the matrix body behind one `pl.when` each), one-shot generate()'s
    # decode step (the loop alone), a call of `MAX_COLUMNS` (the matrix
    # body's eight tiles of columns) and a whole prompt walked in chunks
    from paddle_tpu.ops.ssm import ssm_update
    for rows, T in ((128, 16), (32, 16), (2, 1), (2, 64), (2, 128)):
        results.append(compile_case(
            f"ssm_update bf16 rows={rows} T={T} state=[128,8192]",
            ssm_update,
            spec((rows, T, 8192), jnp.bfloat16),
            spec((rows, T, 128), jnp.float32), spec((128,), jnp.float32),
            spec((rows, T, 128), jnp.bfloat16),
            spec((rows, T, 128), jnp.bfloat16),
            spec((rows, 128, 8192), jnp.bfloat16), spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), want={"ssm_update": 1}))
    # the Mamba-1 recurrence at Jamba2-3B's widths (a float32 state, the
    # columns token rows): the reasoning cell's packed step (256 slots, 512
    # tokens, 32 rows a grid step), one-shot generate()'s decode step and a
    # whole prompt walked in chunks of columns
    import functools
    from paddle_tpu.ops.ssm import selective_scan, selective_scan_rows
    for rows, tokens, columns in ((256, 512, 16), (2, 2, 1)):
        results.append(compile_case(
            f"selective_scan bf16 rows={rows} tokens={tokens} "
            "state=f32[16,5120]",
            functools.partial(selective_scan, columns=columns),
            spec((tokens, 5120), jnp.bfloat16),
            spec((tokens, 5120), jnp.float32), spec((16, 5120), jnp.float32),
            spec((tokens, 16), jnp.float32), spec((tokens, 16), jnp.float32),
            spec((rows, 16, 5120), jnp.float32), spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), spec((rows,), jnp.int32),
            want={"selective_scan": 1}))
    # the conv over the same token rows, the carried columns as the pool
    # holds them
    from paddle_tpu.ops.ssm import causal_conv_tokens
    for rows, tokens in ((256, 512), (2, 2)):
        results.append(compile_case(
            f"conv_tokens bf16 rows={rows} tokens={tokens} "
            "carried=[3,5120]", causal_conv_tokens,
            spec((tokens, 5120), jnp.bfloat16),
            spec((rows, 3, 5120), jnp.bfloat16),
            spec((5120, 4), jnp.bfloat16), spec((5120,), jnp.bfloat16),
            spec((tokens,), jnp.int32), spec((tokens,), jnp.int32),
            spec((rows,), jnp.int32), spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), want={"conv_tokens": 1}))
    results.append(compile_case(
        "selective_scan bf16 rows=2 T=3000 (chunks of 2048 columns) "
        "state=f32[16,5120]", selective_scan_rows,
        spec((2, 3000, 5120), jnp.bfloat16),
        spec((2, 3000, 5120), jnp.float32), spec((16, 5120), jnp.float32),
        spec((2, 3000, 16), jnp.float32), spec((2, 3000, 16), jnp.float32),
        spec((2, 16, 5120), jnp.float32), want={"selective_scan": 1}))
    # the delta-rule recurrence at Solar-Open2's widths (64 heads of 128 x
    # 128, a float32 state, the columns token rows): the reasoning cell's
    # packed step (256 slots, 512 tokens, 4 rows x 8 heads a grid step),
    # one-shot generate()'s decode step, a whole prompt walked in chunks of
    # columns; and the conv in front of it over q, k and v's 24,576 channels
    from paddle_tpu.ops.kda import kda_update, kda_update_rows
    for rows, tokens, columns in ((256, 512, 16), (2, 2, 1)):
        results.append(compile_case(
            f"kda_update rows={rows} tokens={tokens} state=f32[128,8192]",
            functools.partial(kda_update, columns=columns),
            spec((tokens, 8192), jnp.float32),
            spec((tokens, 8192), jnp.float32),
            spec((tokens, 8192), jnp.bfloat16),
            spec((tokens, 8192), jnp.float32),
            spec((tokens, 64), jnp.float32),
            spec((rows, 128, 8192), jnp.float32), spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), spec((rows,), jnp.int32),
            want={"kda_update": 1}))
    results.append(compile_case(
        "kda_update rows=2 T=3000 (chunks of 256 columns) "
        "state=f32[128,8192]", kda_update_rows,
        spec((2, 3000, 8192), jnp.float32), spec((2, 3000, 8192), jnp.float32),
        spec((2, 3000, 8192), jnp.bfloat16),
        spec((2, 3000, 8192), jnp.float32), spec((2, 3000, 64), jnp.float32),
        spec((2, 128, 8192), jnp.float32), want={"kda_update": 1}))
    results.append(compile_case(
        "conv_tokens bf16 rows=256 tokens=512 carried=[3,24576]",
        causal_conv_tokens, spec((512, 24576), jnp.bfloat16),
        spec((256, 3, 24576), jnp.bfloat16), spec((24576, 4), jnp.bfloat16),
        spec((24576,), jnp.float32), spec((512,), jnp.int32),
        spec((512,), jnp.int32), spec((256,), jnp.int32),
        spec((256,), jnp.int32), spec((256,), jnp.int32),
        want={"conv_tokens": 1}))
    # the unified step of an engine, its layers unrolled: a kernel's
    # jitted entry gives the lowered module one Mosaic body a distinct
    # (shapes, window) pair (the paged kernels) or (shapes, ring) pair
    # (`kv_write`), not one a layer (what every process traces and lowers
    # before it can ask the compile cache)
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    for label, want, kw in (
            ("3 full layers", 2, dict(num_hidden_layers=3)),
            ("3 window layers + 1 full", 4, dict(
                num_hidden_layers=4, sliding_window=128,
                layer_types=["sliding_attention"] * 2 + ["full_attention"]
                + ["sliding_attention"]))):
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=512, hidden_size=256, intermediate_size=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=128,
            max_position_embeddings=1024, dtype="bfloat16", **kw))
        model.eval()
        results.append(serve_step_case(f"serve step, {label}", model,
                                       dev1[0], want))
    # recurrent state in the same donated pool (`ssm_update` aliases the
    # state it carries: the step would copy it whole behind every call)
    from paddle_tpu.models.granitemoehybrid import (
        GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)
    model = GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig(
        vocab_size=512, hidden_size=256, intermediate_size=128,
        shared_intermediate_size=128, num_hidden_layers=3,
        layer_types=["mamba", "attention", "mamba"], num_attention_heads=2,
        num_key_value_heads=1, num_local_experts=8, num_experts_per_tok=2,
        mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
        max_position_embeddings=1024, dtype="bfloat16"))
    model.eval()
    results.append(serve_step_case("serve step, 2 recurrent layers + 1 full",
                                   model, dev1[0], 12))
    # Mamba-1 layers round a multi-query layer: the three recurrent layers
    # share one `selective_scan` body (its entry is jitted, as
    # `ssm_update`'s is since PR 46), and the float32 state is aliased
    # beside the bfloat16 conv columns and pages (8 slots x 16 columns are
    # not wider than a packed step: the unpacked form, without `conv_tokens`)
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
    model = JambaForCausalLM(JambaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=1, mamba_dt_rank=16,
        max_position_embeddings=1024, dtype="bfloat16"))
    model.eval()
    results.append(serve_step_case(
        "serve step, 3 Mamba-1 layers + 1 multi-query", model, dev1[0], 3))
    # KDA layers behind a gated NoPE GQA layer: the three recurrent layers
    # share one `kda_update` body, and the float32 state is aliased beside
    # the bfloat16 conv columns and pages (the unpacked form)
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=512, hidden_size=256, moe_intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, linear_num_heads=8, linear_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=1024, dtype="bfloat16",
        experts_held=(0, 4)))
    model.eval()
    results.append(serve_step_case(
        "serve step, 3 KDA layers + 1 gated GQA", model, dev1[0], 15))
    # latent pages in the donated pool: three MLA layers share one
    # `paged_latent` body and one `kv_write` body; two sparse layers'
    # grouped matmuls, 3 each
    from paddle_tpu.models.deepseek import (DeepseekConfig,
                                            DeepseekForCausalLM)
    model = DeepseekForCausalLM(DeepseekConfig(
        vocab_size=512, hidden_size=256, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=3,
        num_attention_heads=2, q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2, n_group=2, topk_group=1,
        first_k_dense_replace=1, max_position_embeddings=1024,
        dtype="bfloat16"))
    model.eval()
    results.append(serve_step_case("serve step, 3 latent layers", model,
                                   dev1[0], 8))
    # an indexer's selection shared by the layers behind it: two "full"
    # layers (three slabs a token) and two "shared" ones in the donated
    # pool: `kv_write` in two bodies (two slabs, three), `paged_sparse` in
    # two (gathered, masked), one `index_score`, one `index_topk`, and
    # three sparse layers' grouped matmuls, 3 each
    model = DeepseekForCausalLM(DeepseekConfig(
        vocab_size=512, hidden_size=256, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=4,
        num_attention_heads=2, q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2, n_group=1, topk_group=1,
        select_bias=True, first_k_dense_replace=1, rope_interleave=True,
        indexer_types=["full", "shared", "full", "shared"], index_n_heads=4,
        index_head_dim=128, index_topk=64, max_position_embeddings=1024,
        dtype="bfloat16"))
    model.eval()
    results.append(serve_step_case(
        "serve step, 2 indexed + 2 shared latent layers", model, dev1[0],
        15))
    # four residual streams round two latent layers: the four connections
    # share one `hc_pre` and one `hc_post` body (their entries are jitted),
    # beside one `kv_write`, one `paged_latent` and the sparse layer's
    # three grouped matmuls; no copy of the streams round a call
    model = DeepseekForCausalLM(DeepseekConfig(
        vocab_size=512, hidden_size=256, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2, n_group=1, topk_group=1,
        select_bias=True, first_k_dense_replace=1,
        max_position_embeddings=1024, dtype="bfloat16", hc_mult=4))
    model.eval()
    results.append(serve_step_case(
        "serve step, 2 latent layers in 4 hyper-connected streams", model,
        dev1[0], 7))
    # the step's tail at the two reasoning cells' widths (PR 52): 256 and
    # 128 emission rows of 512 positions
    for cell, rows, hidden, vocab in (("xing", 256, 3584, 131072),
                                      ("laguna", 128, 2048, 100352)):
        results.append(serve_tail_case(
            f"step tail bf16 {cell} cell: {rows} rows of 512, "
            f"{hidden} -> {vocab}", rows, hidden, vocab, spec))
    from paddle_tpu.ops import pallas_mode
    for (kernel, tiling), n in sorted(pallas_mode.KERNEL_TILINGS.items()):
        print(f"tiling {kernel} x{n}: {dict(tiling)}", flush=True)

    # four chips: a sharded pallas_call is refused by JAX outright; under
    # spmd_mesh the kernels run as a shard_map island and compile
    mesh4 = Mesh(np.array(dev4).reshape(2, 2), ("data", "model"))
    sharded = NamedSharding(mesh4, P("data", "model"))
    qkv4 = [spec((4, 16, 1024, 128), jnp.bfloat16, sharded)] * 3

    def island(q, k, v):
        with A.spmd_mesh(mesh4, "data"):
            return A.flash_attention(q, k, v, causal=True)

    results.append(compile_case("flash island on a 2x2 mesh", island, *qkv4,
                                want={"flash_fwd": 1}))
    try:
        jax.jit(lambda q, k, v: A.flash_attention(q, k, v, causal=True)
                ).lower(*qkv4)
    except NotImplementedError as e:
        print(f"[OK] bare pallas_call on sharded operands is refused: {e}",
              flush=True)
        results.append(True)
    else:
        print("[FAIL] bare pallas_call on sharded operands lowered; the "
              "island may no longer be needed", flush=True)
        results.append(False)
    return 0 if all(results) else 1


if __name__ == "__main__":
    sys.exit(main())
