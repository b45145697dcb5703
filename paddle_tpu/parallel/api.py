"""SPMD parallel runtime: parallelize a model + optimizer over a mesh.

This is the TPU replacement for the reference's entire multi-device execution
stack — ParallelExecutor/SSA graphs (framework/parallel_executor.cc:618), the DDP
Reducer (imperative/reducer.cc:289), the sharding meta-optimizer
(sharding_optimizer.py:43) and TP program rewrites (tensor_parallel_optimizer.py):
one jit-compiled train step over a jax.sharding.Mesh where
- DP   = batch dim sharded over ('data', 'sharding') — grad psum inserted by XLA,
- TP   = weight PartitionSpecs over 'model' (declared by the mp_layers),
- ZeRO = optimizer-state (stage 1), +gradient (stage 2, reduce-scatter) and
         parameter (stage 3) sharding over 'sharding',
and XLA GSPMD materializes exactly the collectives Fleet inserts by hand.

DistributedStrategy flags compose through
distributed/fleet/strategy_compiler.py (the meta-optimizer analog): amp,
recompute, gradient_merge, sharding stage, lars/lamb swaps all transform THIS
step function.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..distributed.fleet.utils.recompute import KEEP_FLASH_RESIDUALS
from ..nn.layer.layers import Layer
from ..profiler import (SPAN_SETUP_FIRST_STEP, SPAN_SETUP_PARALLELIZE,
                        SPAN_TRAIN_CHUNK_DISPATCH, RecordEvent, SetupSpan)

# The scan chunk's inner function name. XLA names the executable
# `jit_<this>`, and that name is how the benchmark finds the train step in a
# profiler trace (benchmark/jobs/train.py: "main_module": "jit_chunk_step";
# PERF_LEDGER's `breakdown` is keyed on it). Pinned by
# tests/test_trace_spans.py: do not rename.
CHUNK_STEP_NAME = "chunk_step"


def _param_spec(param, mesh: Mesh) -> P:
    spec = getattr(param, "partition_spec", None)
    if spec is None:
        return P()
    # drop axes the mesh doesn't have or that don't divide the dim
    cleaned = []
    for dim, ax in enumerate(spec):
        if ax is None or ax not in mesh.axis_names:
            cleaned.append(None)
            continue
        if mesh.shape[ax] == 1:
            cleaned.append(None)
            continue
        cleaned.append(ax)
    return P(*cleaned)


def _zero_spec(base: P, shape, mesh: Mesh, axis="sharding",
               min_numel: int = 1024) -> P:
    """Extend a param spec with the ZeRO `sharding` axis on the first dim that
    is unsharded and divisible (sharding_optimizer.py shard.py analog).

    Tensors below min_numel stay replicated: sharding a 128-element layernorm
    vector saves nothing and forces GSPMD into a full-rematerialization
    reshard of the backward intermediates that feed it (the reference
    similarly segments by size, segment_broadcast_MB)."""
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return base
    if int(np.prod(shape)) < min_numel:
        return base
    spec = list(base) + [None] * (len(shape) - len(base))
    for ax in spec:  # already ZeRO-extended (e.g. stage-3 param spec)
        if ax == axis or (isinstance(ax, tuple) and axis in ax):
            return P(*spec)
    # prefer stacking onto an already-sharded dim (e.g. vocab-parallel
    # embedding ('model', None) -> (('model','sharding'), None)): the grad
    # arrives sharded on that dim already, so the ZeRO reshard is a local
    # slice; a fresh dim (('model','sharding') on dim1) would force GSPMD to
    # fully rematerialize scatter/matmul grads into a transposed layout
    for dim, ax in enumerate(spec):
        if ax is None or ax == axis:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if axis in axes:
            continue
        group = int(np.prod([mesh.shape[a] for a in axes])) * mesh.shape[axis]
        if shape[dim] % group == 0:
            spec[dim] = tuple(axes) + (axis,)
            return P(*spec)
    for dim, ax in enumerate(spec):
        if ax is None and shape[dim] % mesh.shape[axis] == 0 and shape[dim] > 1:
            spec[dim] = axis
            return P(*spec)
    return base


def _batch_axes(mesh: Mesh):
    """Axes the global batch shards over. `ep` counts: expert parallelism is
    data-parallel in the token dim (each ep rank holds different tokens, the
    expert einsum's [E,...] resharding is the GShard all_to_all)."""
    axes = [ax for ax in ("data", "sharding", "ep") if ax in mesh.axis_names
            and mesh.shape[ax] > 1]
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def _tree_where(pred, a_tree, b_tree):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), a_tree, b_tree)


def make_compute_loss(model, loss_fn, amp_ctx=None):
    """Shared (params, buffers, rng, *arrays) -> (f32 loss, new_buffers)
    closure used by every parallel runner. loss_fn=None means the model
    returns its own scalar loss (causal-LM style)."""
    ctx = amp_ctx or contextlib.nullcontext

    def compute_loss(params_, buffers_, rng, *arrays):
        with ctx():
            if loss_fn is None:
                out, new_buffers = model.functional_call_with_state(
                    params_, buffers_, *arrays, rng=rng)
                loss = out
            else:
                out, new_buffers = model.functional_call_with_state(
                    params_, buffers_, arrays[0], rng=rng)
                loss_t = loss_fn(
                    Tensor(out) if not isinstance(out, Tensor) else out,
                    *[Tensor(a) for a in arrays[1:]])
                loss = loss_t.data if isinstance(loss_t, Tensor) else loss_t
        return loss.astype(jnp.float32), new_buffers

    return compute_loss


def apply_selective_remat(model: Layer, checkpoints) -> list:
    """Wrap the named sublayers' forwards in jax.checkpoint (selective
    recompute, recompute_configs.checkpoints analog: the reference names
    segment-anchor variables, the TPU analog names sublayers/prefixes).

    Only the topmost match of each checkpoint entry is wrapped (wrapping a
    child inside an already-rematted parent would remat twice). Returns the
    wrapped sublayer names; empty means nothing matched."""
    wrapped = []
    for name, sub in model.named_sublayers():
        if not any(name == c or name.startswith(c + ".")
                   for c in checkpoints):
            continue
        if any(name.startswith(w + ".") for w in wrapped):
            continue  # ancestor already wrapped
        _wrap_forward_remat(sub)
        wrapped.append(name)
    return wrapped


def _wrap_forward_remat(layer: Layer):
    """layer.forward := jax.checkpoint(forward) at the array level (Tensor is
    not a pytree: unwrap args to arrays, rebuild inside, unwrap outputs).
    Parameters reach the remat region through the closure — new-style remat
    differentiates closed-over tracers correctly."""
    import jax as _jax
    orig = layer.forward
    if getattr(orig, "_is_remat_wrapped", False):
        return

    def forward(*args, **kwargs):
        import numpy as _np
        names = sorted(kwargs)
        flat = list(args) + [kwargs[k] for k in names]
        # only Tensor/array leaves ride through the checkpoint as operands;
        # static values (strings, None, python flags) stay in the closure
        is_tensor = [isinstance(a, Tensor) for a in flat]
        traced = [t or isinstance(a, (jnp.ndarray, _np.ndarray))
                  for a, t in zip(flat, is_tensor)]
        arrs = [a.data if t else a
                for a, t, tr in zip(flat, is_tensor, traced) if tr]
        out_kind = {}

        def inner(*inner_arrs):
            it = iter(inner_arrs)
            rebuilt = [(Tensor(next(it)) if t else next(it)) if tr else a
                       for a, t, tr in zip(flat, is_tensor, traced)]
            a_args = rebuilt[:len(args)]
            a_kwargs = dict(zip(names, rebuilt[len(args):]))
            out = orig(*a_args, **a_kwargs)
            # any output pytree: Tensor leaves unwrap to arrays (Tensor is
            # not a registered pytree node, so flatten with it as a leaf)
            leaves, treedef = _jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            out_kind["treedef"] = treedef
            out_kind["tensor_leaf"] = [isinstance(l, Tensor) for l in leaves]
            return tuple(l.data if isinstance(l, Tensor) else l
                         for l in leaves)

        res = _jax.checkpoint(inner, policy=KEEP_FLASH_RESIDUALS)(*arrs)
        leaves = [Tensor(r) if t else r
                  for r, t in zip(res, out_kind["tensor_leaf"])]
        return _jax.tree_util.tree_unflatten(out_kind["treedef"], leaves)

    forward._is_remat_wrapped = True
    layer.forward = forward


class ShardedTrainStep:
    """One compiled SPMD train step (fwd+bwd+clip+update) over a mesh.

    usage:
        step = ShardedTrainStep(model, optimizer, mesh, loss_fn=None,
                                zero_stage=1)
        loss = step(input_ids, labels)     # global batch; sharded by XLA

    With `plan=` (a strategy_compiler.CompiledStrategy) the step additionally
    executes amp autocast (+ fp16 dynamic loss scaling), rematerialization,
    cond-gated gradient merge, and the stage-2 gradient reduce-scatter.

    Ownership: with `donate=True` the step takes over the model's parameter
    buffers — the first dispatch donates them, so the eager model's arrays
    are deleted until `sync_to_model()` (or `state_dict()`) rebinds it to
    the live state. Build a second step from the same model only after that.
    """

    def __init__(self, model: Layer, optimizer, mesh: Mesh,
                 loss_fn: Optional[Callable] = None, zero_stage: int = 1,
                 donate: bool = True, plan=None, min_shard_numel: int = 1024,
                 numerics: bool = False):
        if plan is not None:
            zero_stage = plan.zero_stage
            optimizer = plan.optimizer or optimizer
            min_shard_numel = plan.zero_min_numel
            numerics = numerics or bool(getattr(plan, "numerics", False))
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.plan = plan
        self._step_count = 0
        self.zero_stage = zero_stage
        # compile observatory (obs.compile_observatory) — None keeps the
        # dispatch hook at one predicate. The observe runs BEFORE the
        # jitted call: donate_argnums consumes params/opt/buffers, so a
        # post-dispatch signature walk would touch deleted buffers
        self.observatory = None
        # numerics observatory (obs.numerics, ISSUE 13): armed, the step
        # traces per-group grad/param norms and update ratios into the
        # extras carry — a DIFFERENT executable, so the disarmed step's
        # outputs stay bit-identical to a never-armed trainer's
        self.numerics_armed = bool(numerics)

        amp_cfg = plan.amp if plan is not None else None
        use_scaler = bool(
            amp_cfg is not None and amp_cfg.dtype == "float16"
            and amp_cfg.use_dynamic_loss_scaling)
        accum_k = plan.accumulate_steps if plan is not None else 1
        merge_avg = plan.gradient_merge_avg if plan is not None else True
        use_remat = bool(plan is not None and plan.remat)
        # selective recompute wraps the named sublayers instead of the whole
        # loss; parallelize() pre-wraps, but a directly-constructed step
        # must apply the wrappers itself — never silently drop remat
        if use_remat and getattr(plan, "recompute_checkpoints", None):
            already = any(getattr(sub.forward, "_is_remat_wrapped", False)
                          for _, sub in model.named_sublayers())
            wrapped = already or bool(
                apply_selective_remat(model, plan.recompute_checkpoints))
            if wrapped:
                use_remat = False
            else:
                import warnings
                warnings.warn(
                    "recompute_configs.checkpoints matched no sublayer of "
                    f"{type(model).__name__}; falling back to whole-loss "
                    "recompute", stacklevel=2)
        fp16_ar = getattr(plan, "fp16_allreduce_dtype", None) \
            if plan is not None else None
        grad_scale = getattr(plan, "grad_scale", "avg") \
            if plan is not None else "avg"
        use_asp = bool(plan is not None and getattr(plan, "asp", False))

        params, buffers = model.functional_state()
        named = dict(model.named_parameters())

        # --- sharding layout ---
        self.param_specs = {}
        for k, arr in params.items():
            base = _param_spec(named[k], mesh)
            pspec = base
            if zero_stage >= 3:
                pspec = _zero_spec(base, arr.shape, mesh,
                                   min_numel=min_shard_numel)
            self.param_specs[k] = pspec
        self.buffer_specs = {k: P() for k in buffers}

        # gradient layout: stage >= 2 shards grads over `sharding` (the
        # reduce-scatter of sharding_optimizer's stage-2), stage <= 1 keeps
        # grads in the param layout
        self.grad_specs = {
            k: (_zero_spec(self.param_specs[k], params[k].shape, mesh,
                           min_numel=min_shard_numel)
                if zero_stage >= 2 else self.param_specs[k])
            for k in params}

        # optimizer slots follow the (ZeRO-extended) param layout
        opt_state = optimizer.init_state(params)
        self.opt_state_specs = {}
        for k, slots in opt_state.items():
            arr = params[k]
            base = self.param_specs[k]
            zspec = (_zero_spec(base, arr.shape, mesh,
                                min_numel=min_shard_numel)
                     if zero_stage >= 1 else base)
            per = {}
            for sname, sarr in slots.items():
                per[sname] = zspec if sarr.shape == arr.shape else P()
            self.opt_state_specs[k] = per

        # --- materialize sharded state on the mesh ---
        # The step takes the model's parameter buffers over: device_put may
        # hand back the model's own buffer (always on a one-device mesh, and
        # as the device-0 shard of a replicated layout), and the jitted step
        # donates its state, so after the first dispatch the model's arrays
        # are deleted and no second copy of the weights is ever resident.
        # `sync_to_model()` rebinds the model to the live state (no copy).
        def put(arr, spec):
            return jax.device_put(arr, NamedSharding(mesh, spec))

        self._params = {k: put(v, self.param_specs[k])
                        for k, v in params.items()}
        self._buffers = {k: put(v, P()) for k, v in buffers.items()}
        self._opt_state = {
            k: {s: put(a, self.opt_state_specs[k][s])
                for s, a in slots.items()}
            for k, slots in opt_state.items()}

        # ZeRO offload (offload_helper.py:347 analog): optimizer state lives
        # in pinned host memory between steps and is staged to device around
        # the update — trades a host<->HBM copy per step for HBM capacity.
        self._offload = bool(plan is not None and plan.zero_offload)
        self._opt_dev_sh = {
            k: {s: NamedSharding(mesh, sp) for s, sp in per.items()}
            for k, per in self.opt_state_specs.items()}
        if self._offload:
            self._opt_host_sh = {
                k: {s: NamedSharding(mesh, sp, memory_kind="pinned_host")
                    for s, sp in per.items()}
                for k, per in self.opt_state_specs.items()}
            self._opt_state = jax.device_put(self._opt_state,
                                             self._opt_host_sh)

        batch_axes = _batch_axes(mesh)
        _ba = (batch_axes if isinstance(batch_axes, tuple)
               else (batch_axes,)) if batch_axes else ()
        dp_total = int(np.prod([mesh.shape[a] for a in _ba])) if _ba else 1
        # quantized grad collective (EQuARX analog, distributed/compression):
        # gate on an actual cross-rank reduction existing — at dp_total == 1
        # there is no wire, so the step stays bit-exact with quant off
        comm_quant = getattr(plan, "comm_quant", None) \
            if plan is not None else None
        use_quant = bool(comm_quant is not None and dp_total > 1)
        use_ef = bool(use_quant and comm_quant.error_feedback)
        if use_quant:
            from ..distributed.compression import quant_dequant

        # extra step state: gradient-merge accumulator + loss-scale state
        extras = {}
        extras_specs = {}
        if accum_k > 1:
            extras["accum"] = {
                k: put(jnp.zeros(v.shape, v.dtype), self.grad_specs[k])
                for k, v in params.items()}
            extras_specs["accum"] = {
                k: NamedSharding(mesh, self.grad_specs[k]) for k in params}
            extras["accum_n"] = put(jnp.asarray(0, jnp.int32), P())
            extras_specs["accum_n"] = NamedSharding(mesh, P())
        if use_asp:
            # N:M sparsity masks ride in extras (not jit constants: same
            # size as the weights, so they follow the param sharding and the
            # donation path instead of doubling executable const memory)
            asp_masks = {
                k: put(jnp.asarray(getattr(named[k], "_asp_mask"),
                                   params[k].dtype), self.param_specs[k])
                for k in params if getattr(named[k], "_asp_mask", None)
                is not None}
            if not asp_masks:
                raise ValueError(
                    "strategy.asp is set but no parameter carries a sparse "
                    "mask; call incubate.asp.prune_model(model) first (or go "
                    "through parallelize(), which does it for you)")
            extras["asp_masks"] = asp_masks
            extras_specs["asp_masks"] = {
                k: NamedSharding(mesh, self.param_specs[k])
                for k in asp_masks}
        if use_scaler:
            extras["loss_scale"] = put(
                jnp.asarray(amp_cfg.init_loss_scaling, jnp.float32), P())
            extras["good_steps"] = put(jnp.asarray(0, jnp.int32), P())
            extras["bad_steps"] = put(jnp.asarray(0, jnp.int32), P())
            for k in ("loss_scale", "good_steps", "bad_steps"):
                extras_specs[k] = NamedSharding(mesh, P())
        if self.numerics_armed:
            from ..obs.numerics import (in_step_telemetry, telemetry_groups,
                                        telemetry_keys)
            num_groups = telemetry_groups(params.keys())
            extras["numerics"] = {
                key: put(jnp.float32(0.0), P())
                for key in telemetry_keys(num_groups)}
            extras_specs["numerics"] = {
                key: NamedSharding(mesh, P())
                for key in extras["numerics"]}
        if use_ef:
            # error-feedback residual: the rounding error of each synced
            # grad, re-injected into the next sync; only tensors large
            # enough to be quantized (min_quant_numel) carry one
            ef_keys = [k for k, v in params.items()
                       if v.size >= comm_quant.min_quant_numel]
            extras["quant_ef"] = {
                k: put(jnp.zeros(params[k].shape, jnp.float32),
                       self.grad_specs[k]) for k in ef_keys}
            extras_specs["quant_ef"] = {
                k: NamedSharding(mesh, self.grad_specs[k]) for k in ef_keys}
        self._extras = extras

        apply_fn = optimizer.apply_gradients_fn()
        clip_fn = optimizer.clip_gradients_fn()
        # parity-plus sequence/context parallelism: token dim sharded over
        # the `sep` axis (ring/Ulysses kernels cover the explicit shard_map
        # mode; under GSPMD the partitioner slices the transformer and
        # gathers k/v inside attention)
        seq_parallel = bool(
            (plan is not None and getattr(plan, "sequence_parallel", False))
            or ("sep" in mesh.axis_names and mesh.shape["sep"] > 1))
        self.sequence_parallel = seq_parallel and \
            "sep" in mesh.axis_names and mesh.shape["sep"] > 1
        if seq_parallel and not self.sequence_parallel:
            import warnings
            warnings.warn(
                "strategy requests sequence_parallel but the mesh has no "
                "`sep` axis (set hybrid_configs.sep_degree > 1); the step "
                "will run WITHOUT sequence parallelism", stacklevel=2)
        self._batch_axes = batch_axes
        if self.sequence_parallel:
            self.data_spec = P(batch_axes, "sep")
        else:
            self.data_spec = P(batch_axes) if batch_axes else P()

        if amp_cfg is not None:
            from ..amp import auto_cast

            def amp_ctx():
                return auto_cast(True,
                                 custom_white_list=amp_cfg.custom_white_list,
                                 custom_black_list=amp_cfg.custom_black_list,
                                 dtype=amp_cfg.dtype)
        else:
            amp_ctx = None

        compute_loss = make_compute_loss(model, loss_fn, amp_ctx)

        # the model is traced inside a context that tells attention how the
        # step is partitioned
        trace_ctx = None
        if self.sequence_parallel:
            # sequence-sharded: attention drops into the ring/Ulysses
            # shard_map island over `sep` (O(S_local^2) memory — no
            # full-sequence k/v all-gather), and the lm-head CE keeps its
            # GSPMD-partitionable path
            from ..ops.attention import sequence_sharded
            sp_impl = (getattr(plan, "sequence_parallel_impl", None)
                       or "ring") if plan is not None else "ring"

            def trace_ctx():
                return sequence_sharded(mesh=mesh, batch_axes=batch_axes,
                                        impl=sp_impl)
        elif mesh.size > 1:
            # a Mosaic kernel cannot be partitioned by GSPMD (JAX refuses
            # to lower it): the flash kernels run in a shard_map island
            # over the batch and head axes
            from ..ops.attention import spmd_mesh

            def trace_ctx():
                return spmd_mesh(mesh, batch_axes)

        if trace_ctx is not None:
            _inner_compute_loss = compute_loss

            def compute_loss(*a, **k):
                with trace_ctx():
                    return _inner_compute_loss(*a, **k)

        if use_remat:
            # coarsest activation checkpointing: save only the step inputs,
            # recompute the forward during backward (recompute meta-optimizer
            # analog; per-layer policies live in the models themselves)
            compute_loss = jax.checkpoint(compute_loss)

        # kept for the non-finite blame probe (nonfinite_blame): the same
        # loss closure — autocast/remat/sequence-parallel wrapping and all
        # — re-differentiated on the poisoned batch, but WITHOUT donation
        # or an update, so the census runs on the exact params that blew up
        self._compute_loss_fn = compute_loss
        self._blame_jitted = None
        self._ran = False      # the step has had its first call
        self._param_sizes = {k: int(np.prod(v.shape)) or 1
                             for k, v in params.items()}

        def scaled_loss_fn(params_, buffers_, rng, scale, *arrays):
            loss, new_buffers = compute_loss(params_, buffers_, rng, *arrays)
            return loss * scale, (loss, new_buffers)

        def train_step(params_, opt_state_, buffers_, extras_, lr, step, rng,
                       arrays):
            scale = extras_.get("loss_scale", jnp.float32(1.0))
            (_, (loss, new_buffers)), grads = jax.value_and_grad(
                scaled_loss_fn, has_aux=True)(
                    params_, buffers_, rng, scale, *arrays)
            if use_scaler:
                # unscale in fp32 (check_finite_and_unscale analog), back to
                # the grad's dtype so the update path keeps param dtypes
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) / scale).astype(g.dtype),
                    grads)
            if fp16_ar is not None:
                # fp16_allreduce (fp16_allreduce_optimizer.py:148): the
                # reference casts fp32 grads to fp16 around the allreduce.
                # GSPMD inserts the reduction itself, so the step applies the
                # same fp16 quantization at the reduction boundary
                _qd = jnp.dtype(fp16_ar)
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(_qd).astype(g.dtype)
                               if g.dtype == jnp.float32 else g), grads)
            if zero_stage >= 2:
                # stage-2: pin grads to the sharded layout so GSPMD lowers the
                # cross-data reduction as reduce-scatter, not all-reduce
                grads = {
                    k: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, self.grad_specs[k]))
                    for k, g in grads.items()}

            new_extras = dict(extras_)
            if use_scaler:
                # shared non-finite census (obs.numerics, ISSUE 13): one
                # implementation with GradScaler and the pipeline psum
                from ..obs.numerics import all_finite as _all_finite
                finite = _all_finite(jax.tree_util.tree_leaves(grads))
                good = jnp.where(finite, extras_["good_steps"] + 1, 0)
                bad = jnp.where(finite, 0, extras_["bad_steps"] + 1)
                grow = good >= amp_cfg.incr_every_n_steps
                shrink = bad >= amp_cfg.decr_every_n_nan_or_inf
                new_scale = jnp.where(
                    shrink, jnp.maximum(scale * amp_cfg.decr_ratio, 1.0),
                    jnp.where(grow, scale * amp_cfg.incr_ratio, scale))
                new_extras["loss_scale"] = new_scale
                new_extras["good_steps"] = jnp.where(grow, 0, good)
                new_extras["bad_steps"] = jnp.where(shrink, 0, bad)
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads)
            else:
                finite = jnp.bool_(True)

            if accum_k > 1:
                # gradient merge: bank k-1 steps, apply on the k-th
                # (gradient_merge_optimizer.py:72 cond-gated optimizer).
                # accum_n counts banked micro-steps so an overflow-carried
                # window averages over the TRUE number of banked grads, not
                # the nominal k
                acc = jax.tree_util.tree_map(
                    lambda a, g: a + g, extras_["accum"], grads)
                acc_n = extras_["accum_n"] + jnp.where(finite, 1, 0)
                do_apply = (step % accum_k) == 0
                denom = (jnp.maximum(acc_n, 1).astype(jnp.float32)
                         if merge_avg else jnp.float32(1))
                eff_grads = jax.tree_util.tree_map(
                    lambda a: a / denom, acc)
            else:
                do_apply = jnp.bool_(True)
                eff_grads = grads

            do_update = jnp.logical_and(do_apply, finite)
            if accum_k > 1:
                # clear only when the update actually applied: an fp16
                # overflow on the k-th step must not discard the k-1 banked
                # micro-gradients (they re-apply at the next boundary)
                new_extras["accum"] = jax.tree_util.tree_map(
                    lambda a: jnp.where(do_update, jnp.zeros_like(a), a), acc)
                new_extras["accum_n"] = jnp.where(do_update, 0, acc_n)
            if use_quant:
                # the wire sync of the MERGED grad: round-trip through the
                # blockwise int8 quantization exactly where GSPMD lands the
                # cross-rank reduce (same boundary treatment as
                # fp16_allreduce above) — once per merge window / scan
                # chunk, never per banked micro-step, since the banked
                # accumulator above stays full precision
                qkey = jax.random.fold_in(rng, 0x71)
                q_grads = {}
                new_ef = {}
                for qi, k in enumerate(sorted(eff_grads)):
                    g = eff_grads[k]
                    lk = jax.random.fold_in(qkey, qi)
                    if use_ef and k in extras_["quant_ef"]:
                        g32 = g.astype(jnp.float32) + extras_["quant_ef"][k]
                        qg = quant_dequant(g32, comm_quant, lk)
                        # residual advances only when this sync applied
                        new_ef[k] = jnp.where(do_update, g32 - qg,
                                              extras_["quant_ef"][k])
                        q_grads[k] = qg.astype(g.dtype)
                    else:
                        q_grads[k] = quant_dequant(g, comm_quant, lk)
                if use_ef:
                    new_extras["quant_ef"] = new_ef
                eff_grads = q_grads
            if grad_scale == "sum":
                # gradient_scale_configs scale_strategy='sum': ranks SUM
                # grads instead of averaging. The mean-loss backward yields
                # the global average, so sum = avg * (number of batch shards)
                eff_grads = jax.tree_util.tree_map(
                    lambda g: g * dp_total, eff_grads)
            eff_grads = clip_fn(eff_grads)
            cand_params, cand_opt = apply_fn(params_, eff_grads, opt_state_,
                                             lr, step)
            if use_asp:
                # re-apply the N:M masks so pruned weights stay zero
                # (asp_optimizer.py / OptimizerWithSparsityGuarantee)
                cand_params = {
                    k: (p * extras_["asp_masks"][k]
                        if k in extras_["asp_masks"] else p)
                    for k, p in cand_params.items()}
            new_params = _tree_where(do_update, cand_params, params_)
            new_opt = _tree_where(do_update, cand_opt, opt_state_)
            if self.numerics_armed:
                # traced INTO this executable: the telemetry scalars ride
                # the extras carry, so sampling them host-side costs a
                # transfer of a few floats, never an extra dispatch.
                # Norms read the unscaled pre-clip grads; update ratios
                # read the actually-applied delta (zero on skipped steps)
                new_extras["numerics"] = in_step_telemetry(
                    num_groups, grads, params_, new_params)
            return loss, new_params, new_opt, new_buffers, new_extras

        self._train_step_fn = train_step  # exposed for jaxpr/HLO assertions

        param_sh = {k: NamedSharding(mesh, s)
                    for k, s in self.param_specs.items()}
        opt_sh = {k: {s: NamedSharding(mesh, sp) for s, sp in per.items()}
                  for k, per in self.opt_state_specs.items()}
        buf_sh = {k: NamedSharding(mesh, P()) for k in buffers}
        scalar_sh = NamedSharding(mesh, P())
        # kept for subclasses (ScanTrainStep) that jit a different driver
        # over the same state layout
        self._state_shardings = (param_sh, opt_sh, buf_sh, extras_specs)
        self._scalar_sh = scalar_sh

        # seed ONCE, fold in the step: rebuilding PRNGKey(step) on the host
        # every step costs a host round-trip per dispatch and pins the key
        # derivation to python ints; fold_in keeps eager and scan-fused
        # paths on the identical per-step key stream
        from ..core.random import get_rng_state
        self._base_rng = jax.random.PRNGKey(int(get_rng_state()[0]))

        self._jitted = jax.jit(
            train_step,
            # data arrays inherit the per-array sharding applied by
            # __call__'s device_put (_spec_for): a uniform prefix spec here
            # would rank-mismatch (B,)-shaped labels under sequence
            # parallelism
            in_shardings=(param_sh, opt_sh, buf_sh, extras_specs, scalar_sh,
                          scalar_sh, scalar_sh, None),
            out_shardings=(scalar_sh, param_sh, opt_sh, buf_sh, extras_specs),
            donate_argnums=(0, 1, 2, 3) if donate else (),
        )

    def __call__(self, *args):
        arrays = []
        for a in args:
            arr = a.data if isinstance(a, Tensor) else jnp.asarray(a)
            arrays.append(jax.device_put(
                arr, NamedSharding(self.mesh, self._spec_for(arr))))
        self._step_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)
        rng = jax.random.fold_in(self._base_rng, self._step_count)
        opt_in = (jax.device_put(self._opt_state, self._opt_dev_sh)
                  if self._offload else self._opt_state)
        if self.observatory is not None:
            self.observatory.observe_call(
                "train/sharded_step", self._jitted,
                (self._params, opt_in, self._buffers, self._extras, lr,
                 step, rng, tuple(arrays)))
        (loss, self._params, opt_out, self._buffers,
         self._extras) = self._run(self._jitted)(
            self._params, opt_in, self._buffers, self._extras, lr,
            step, rng, tuple(arrays))
        self._opt_state = (jax.device_put(opt_out, self._opt_host_sh)
                           if self._offload else opt_out)
        return Tensor(loss)

    def _run(self, jitted):
        """`jitted` itself, but for the step's first call: that one is
        awaited inside the set-up ledger's `first_step` phase (trace,
        lower, compile or load, and the first run)."""
        if self._ran:
            return jitted
        self._ran = True

        def first(*args):
            with SetupSpan(SPAN_SETUP_FIRST_STEP,
                           program=getattr(jitted, "__name__", None)):
                return jax.block_until_ready(jitted(*args))
        return first

    def _spec_for(self, arr):
        """Per-array data sharding: the sep (token) axis only applies to
        arrays that actually have a sep-divisible dim 1 — (B,) labels and
        non-sequence features keep the plain batch sharding."""
        base = self._batch_axes
        if (self.sequence_parallel and arr.ndim >= 2
                and arr.shape[1] % self.mesh.shape["sep"] == 0):
            return P(base, "sep")
        if arr.ndim >= 1 and base is not None:
            return P(base)
        return P()

    @property
    def loss_scale(self):
        s = self._extras.get("loss_scale")
        return None if s is None else float(s)

    # ---- numerics observatory hooks (obs.numerics, ISSUE 13) ----
    def numerics_host_sample(self) -> Optional[Dict[str, float]]:
        """Host view of the in-step telemetry scalars the armed step left
        in the extras carry (plus AMP loss-scale state when present).
        Blocks only on a handful of replicated f32 scalars — the
        downsampled read the trainer issues every numerics_interval
        steps. None when the step was built without numerics."""
        tele = self._extras.get("numerics")
        if tele is None:
            return None
        import jax as _jax
        sample = {k: float(v) for k, v in _jax.device_get(tele).items()}
        for key in ("loss_scale", "good_steps", "bad_steps"):
            if key in self._extras:
                sample[key] = float(self._extras[key])
        return sample

    def nonfinite_blame(self, step: int, *args) -> Dict:
        """Jitted per-leaf non-finite census on the CURRENT device params
        and the given single-step batch: re-differentiates the step's own
        loss closure (no update, no donation) and counts non-finite
        elements per grad and param leaf. Returns ``{"loss": float,
        "sizes": {name: numel}, "grads": {name: count>0}, "params":
        {name: count>0}, "probe_seconds": float}``.

        Compiled lazily on first use — a process that never sees a bad
        loss never pays the probe's compile. ``step`` seeds the same
        fold_in rng derivation the train step uses, so dropout masks
        match when the step counters are aligned (deterministic models
        reproduce exactly either way)."""
        import time as _time
        t0 = _time.perf_counter()
        if self._blame_jitted is None:
            compute_loss = self._compute_loss_fn
            from ..obs.numerics import nonfinite_count

            def probe(params_, buffers_, rng, arrays):
                def loss_only(p):
                    return compute_loss(p, buffers_, rng, *arrays)[0]

                loss, grads = jax.value_and_grad(loss_only)(params_)
                return (loss,
                        {k: nonfinite_count(g) for k, g in grads.items()},
                        {k: nonfinite_count(v)
                         for k, v in params_.items()})

            param_sh, _, buf_sh, _ = self._state_shardings
            self._blame_jitted = jax.jit(
                probe,
                in_shardings=(param_sh, buf_sh, None, None),
                out_shardings=self._scalar_sh)
        arrays = []
        for a in args:
            arr = a.data if isinstance(a, Tensor) else jnp.asarray(a)
            arrays.append(jax.device_put(
                arr, NamedSharding(self.mesh, self._spec_for(arr))))
        rng = jax.random.fold_in(self._base_rng, int(step))
        loss, g, p = self._blame_jitted(
            self._params, self._buffers, rng, tuple(arrays))
        g = jax.device_get(g)
        p = jax.device_get(p)
        return {
            "loss": float(loss),
            "sizes": dict(self._param_sizes),
            "grads": {k: int(v) for k, v in g.items() if int(v)},
            "params": {k: int(v) for k, v in p.items() if int(v)},
            "probe_seconds": round(_time.perf_counter() - t0, 6),
        }

    # ---- state sync back to the eager model (checkpointing etc.) ----
    def sync_to_model(self):
        named = dict(self.model.named_parameters())
        named_b = dict(self.model.named_buffers())
        for k, arr in self._params.items():
            named[k].data = arr
        for k, arr in self._buffers.items():
            if k in named_b:
                named_b[k].data = arr
            elif k in named:
                named[k].data = arr

    def state_dict(self):
        self.sync_to_model()
        return self.model.state_dict()


def stack_batches(batches):
    """Stack K per-step batches (each a tuple/list of arrays, or one array)
    into the [K, ...] chunk layout ScanTrainStep consumes. Host-side numpy:
    the stacked result is what the prefetcher ships in ONE device_put."""
    if not batches:
        raise ValueError("stack_batches needs at least one batch")
    first = batches[0]
    if isinstance(first, (tuple, list)):
        cols = []
        for j in range(len(first)):
            cols.append(np.stack([
                np.asarray(b[j].data if isinstance(b[j], Tensor) else b[j])
                for b in batches]))
        return tuple(cols)
    return (np.stack([
        np.asarray(b.data if isinstance(b, Tensor) else b)
        for b in batches]),)


class ScanTrainStep(ShardedTrainStep):
    """K train steps fused into ONE dispatch via lax.scan over a device-
    resident batch chunk.

    The python-side step loop pays one host→device dispatch per step;
    scanning K steps inside the jitted computation amortizes dispatch to 1/K
    per step and lets XLA pipeline the whole chunk (what that is worth on
    the chip: not measured on current code). The scan body IS the parent's
    train_step, so every strategy
    transform composes unchanged:

    - per-step LR schedule: precomputed as a length-K vector on the host
      (the chunk runner owns scheduler.step() — the host cannot intervene
      mid-chunk, so an attached LRScheduler is advanced once per fused step);
    - gradient merge: boundaries are `step % accum_k` on the global step
      index threaded through the scan, so accum_k does not need to divide K;
    - RNG: per-step keys are fold_in(base_key, global_step) — the identical
      derivation the eager ShardedTrainStep.__call__ uses, so eager and
      scan-fused runs sample the same dropout masks;
    - AMP loss scaling / accumulators / asp masks: extras ride in the scan
      carry with full donation.

    usage:
        step = ScanTrainStep(model, opt, mesh, scan_steps=8)
        losses = step(ids_chunk, labels_chunk)   # [K, ...] stacked inputs
        # losses: Tensor of shape [K] — per-step granularity is preserved
        # for NaN sentinels / logging even though dispatch is chunk-level.
    """

    def __init__(self, model: Layer, optimizer, mesh: Mesh,
                 scan_steps: int = 8, loss_fn: Optional[Callable] = None,
                 zero_stage: int = 1, donate: bool = True, plan=None,
                 min_shard_numel: int = 1024, numerics: bool = False):
        if plan is not None and getattr(plan, "scan_steps", 1) > 1:
            scan_steps = plan.scan_steps
        super().__init__(model, optimizer, mesh, loss_fn=loss_fn,
                         zero_stage=zero_stage, donate=donate, plan=plan,
                         min_shard_numel=min_shard_numel, numerics=numerics)
        self.scan_steps = int(scan_steps)
        if self.scan_steps < 1:
            raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
        self.dispatch_count = 0  # jitted chunk dispatches issued
        # goodput ledger (obs.goodput) — caller-thread H2D staging books
        # to the "h2d" phase; None keeps the hook at one predicate
        self.ledger = None

        train_step = self._train_step_fn
        K = self.scan_steps

        def chunk_step(params_, opt_state_, buffers_, extras_, lr_vec,
                       steps_vec, base_rng, arrays):
            def body(carry, xs):
                p, o, b, e = carry
                lr_i, step_i = xs[0], xs[1]
                rng_i = jax.random.fold_in(base_rng, step_i)
                loss, p, o, b, e = train_step(p, o, b, e, lr_i, step_i,
                                              rng_i, xs[2:])
                return (p, o, b, e), loss

            (params_, opt_state_, buffers_, extras_), losses = jax.lax.scan(
                body, (params_, opt_state_, buffers_, extras_),
                (lr_vec, steps_vec) + tuple(arrays), length=K)
            return losses, params_, opt_state_, buffers_, extras_

        chunk_step.__name__ = chunk_step.__qualname__ = CHUNK_STEP_NAME
        self._chunk_step_fn = chunk_step  # exposed for jaxpr assertions
        param_sh, opt_sh, buf_sh, extras_specs = self._state_shardings
        scalar_sh = self._scalar_sh
        self._chunk_jitted = jax.jit(
            chunk_step,
            in_shardings=(param_sh, opt_sh, buf_sh, extras_specs, scalar_sh,
                          scalar_sh, scalar_sh, None),
            out_shardings=(scalar_sh, param_sh, opt_sh, buf_sh, extras_specs),
            donate_argnums=(0, 1, 2, 3) if donate else (),
        )

    # ---- host→device staging ----
    def _chunk_spec_for(self, arr):
        """Sharding for a stacked [K, ...] array: the scan (K) dim stays
        replicated, the per-step dims keep _spec_for's layout."""
        base = self._batch_axes
        if (self.sequence_parallel and arr.ndim >= 3
                and arr.shape[2] % self.mesh.shape["sep"] == 0):
            return P(None, base, "sep")
        if arr.ndim >= 2 and base is not None:
            return P(None, base)
        return P()

    def device_put_chunk(self, stacked):
        """Start the (async) sharded H2D transfer of one stacked chunk.
        Returns device arrays; used by the prefetcher as its put_fn so the
        next chunk's transfer overlaps the current chunk's compute."""
        out = []
        for a in stacked:
            arr = a.data if isinstance(a, Tensor) else a
            if not isinstance(arr, jax.Array):
                arr = jnp.asarray(arr)
            out.append(jax.device_put(
                arr, NamedSharding(self.mesh, self._chunk_spec_for(arr))))
        return tuple(out)

    def _lr_vector(self, K):
        """Length-K per-step LR schedule. With a plain float lr the vector
        is constant; with an LRScheduler the chunk runner advances it once
        per fused step (get_lr value first, like the eager convention)."""
        sched = self.optimizer._lr_scheduler
        if sched is None:
            return np.full((K,), float(self.optimizer.get_lr()), np.float32)
        vals = []
        for _ in range(K):
            vals.append(float(sched()))
            sched.step()
        return np.asarray(vals, np.float32)

    def _stage_chunk(self, args):
        """Validate + stage stacked [K, ...] inputs (sync sharded
        device_put on the caller thread)."""
        K = self.scan_steps
        arrays = []
        for a in args:
            arr = a.data if isinstance(a, Tensor) else a
            if not isinstance(arr, jax.Array):
                arr = jnp.asarray(arr)
            if arr.ndim < 1 or arr.shape[0] != K:
                raise ValueError(
                    f"ScanTrainStep expects stacked [K={K}, ...] inputs; got "
                    f"shape {arr.shape} (stack per-step batches with "
                    "parallel.stack_batches or io.ChunkPrefetcher)")
            arrays.append(jax.device_put(
                arr, NamedSharding(self.mesh, self._chunk_spec_for(arr))))
        return arrays

    def __call__(self, *args):
        """Run K fused steps over stacked [K, ...] inputs; returns the
        per-step loss vector as a length-K Tensor."""
        with RecordEvent(SPAN_TRAIN_CHUNK_DISPATCH):
            return self._dispatch_chunk(args)

    def _dispatch_chunk(self, args):
        K = self.scan_steps
        if self.ledger is not None:
            with self.ledger.measure("h2d"):
                arrays = self._stage_chunk(args)
        else:
            arrays = self._stage_chunk(args)
        lr_vec = jnp.asarray(self._lr_vector(K))
        steps_vec = jnp.arange(1, K + 1, dtype=jnp.int32) + self._step_count
        self._step_count += K
        opt_in = (jax.device_put(self._opt_state, self._opt_dev_sh)
                  if self._offload else self._opt_state)
        if self.observatory is not None:
            self.observatory.observe_call(
                "train/scan_chunk", self._chunk_jitted,
                (self._params, opt_in, self._buffers, self._extras, lr_vec,
                 steps_vec, self._base_rng, tuple(arrays)))
        (losses, self._params, opt_out, self._buffers,
         self._extras) = self._run(self._chunk_jitted)(
            self._params, opt_in, self._buffers, self._extras, lr_vec,
            steps_vec, self._base_rng, tuple(arrays))
        self.dispatch_count += 1
        self._opt_state = (jax.device_put(opt_out, self._opt_host_sh)
                           if self._offload else opt_out)
        return Tensor(losses)


def parallelize(model: Layer, optimizer=None, mesh: Optional[Mesh] = None,
                strategy=None, loss_fn=None):
    """Fleet-facade entry: build a train step from strategy/topology.

    (fleet.distributed_model + distributed_optimizer + minimize, compiled.)
    DistributedStrategy flags are resolved by StrategyCompiler (the
    meta-optimizer composition analog) and executed by the returned step.
    """
    with SetupSpan(SPAN_SETUP_PARALLELIZE):
        return _parallelize(model, optimizer, mesh, strategy, loss_fn)


def _parallelize(model, optimizer, mesh, strategy, loss_fn):
    from ..distributed.topology import get_mesh
    from ..distributed.fleet.strategy_compiler import StrategyCompiler
    if mesh is None:
        mesh = get_mesh()
    if mesh is None:
        raise ValueError("no mesh: call fleet.init or pass mesh=")
    plan = StrategyCompiler().compile(strategy, optimizer, mesh)
    # model rewrites (the program-rewrite meta-optimizers' analog) happen
    # BEFORE the step traces the model
    if plan.qat:
        from ..quantization import ImperativeQuantAware
        ImperativeQuantAware().quantize(model)
    if plan.sync_batch_norm:
        from ..nn.layer.norm import SyncBatchNorm
        model = SyncBatchNorm.convert_sync_batchnorm(model)
    if plan.asp:
        from ..incubate import asp as _asp
        if not any(getattr(p, "_asp_mask", None) is not None
                   for _, p in model.named_parameters()):
            _asp.prune_model(model)
    if plan.remat and plan.recompute_checkpoints:
        wrapped = apply_selective_remat(model, plan.recompute_checkpoints)
        if not wrapped:
            import warnings
            warnings.warn(
                "recompute_configs.checkpoints matched no sublayer of "
                f"{type(model).__name__}; falling back to whole-loss "
                "recompute", stacklevel=2)
            plan.recompute_checkpoints = []
    if plan.pipeline or ("pipe" in mesh.axis_names
                         and mesh.shape["pipe"] > 1):
        from .pipeline import PipelinedTrainStep, is_pipeline_stackable
        if not is_pipeline_stackable(model):
            raise ValueError(
                "pp_degree > 1 requires a pipeline-stackable model: "
                f"{type(model).__name__} does not implement the pipe_* "
                "segmentation protocol (pipe_layer_prefixes/pipe_layers/"
                "pipe_embed/pipe_head — reference pp_layers.py LayerDesc "
                "analog; Llama/GPT families implement it). Set pp_degree=1 "
                "to train under ShardedTrainStep instead")
        n_micro = 4
        vpp = 1
        if strategy is not None:
            cfg = getattr(strategy, "pipeline_configs", None)
            if cfg is not None and getattr(cfg, "accumulate_steps", 0) >= 1:
                n_micro = cfg.accumulate_steps
            if cfg is not None:
                vpp = int(getattr(cfg, "virtual_pp_degree", 1) or 1)
        return PipelinedTrainStep(
            model, plan.optimizer or optimizer, mesh, n_micro=n_micro,
            zero_stage=plan.zero_stage, min_shard_numel=plan.zero_min_numel,
            amp_cfg=plan.amp, loss_fn=loss_fn, virtual_pp_degree=vpp,
            fp16_allreduce_dtype=getattr(plan, "fp16_allreduce_dtype", None),
            grad_scale=getattr(plan, "grad_scale", "avg"))
    if plan.localsgd_k:
        from .localsgd import LocalSGDTrainStep
        return LocalSGDTrainStep(model, plan.optimizer or optimizer, mesh,
                                 k_steps=plan.localsgd_k,
                                 begin_step=plan.localsgd_begin,
                                 adaptive=plan.localsgd_adaptive,
                                 loss_fn=loss_fn)
    if getattr(plan, "scan_steps", 1) > 1:
        return ScanTrainStep(model, optimizer, mesh, loss_fn=loss_fn,
                             plan=plan)
    return ShardedTrainStep(model, optimizer, mesh, loss_fn=loss_fn,
                            plan=plan)
