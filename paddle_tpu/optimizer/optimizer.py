"""Optimizers (reference: python/paddle/optimizer/*, operators/optimizers/*_op.cu).

Each optimizer's math lives in a pure `_rule(g, p, state, lr, ctx) -> (new_p,
new_state)` function over jax arrays — the eager `step()` applies it per parameter
(one fused XLA computation per param, analogous to the reference's fused adam_op.cu),
and the functional/jit path (`paddle_tpu.jit.TrainStep`, distributed optimizers)
applies the same rule inside a traced train step, so eager and compiled training
share numerics exactly.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp

from ..core.tensor import Parameter, Tensor, no_grad
from ..nn.clip import ClipGradBase
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip: Optional[ClipGradBase] = None, name=None,
                 multi_precision=False):
        self._learning_rate = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._grad_clip = grad_clip
        self._weight_decay = weight_decay or 0.0
        self._multi_precision = multi_precision
        # state: param id -> dict of slot arrays (moment, velocity, ...)
        self._state: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._step_count = 0

    # ---- lr plumbing ----
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = value

    @property
    def _lr_scheduler(self):
        return (self._learning_rate
                if isinstance(self._learning_rate, LRScheduler) else None)

    # ---- the pure update rule: override in subclasses ----
    def _init_slots(self, p: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        return {}

    def _param_lr(self, p, lr):
        """Per-parameter lr hook (AdamW lr_ratio); default: unchanged."""
        return lr

    def _rule(self, g, p, slots, lr, wd):
        raise NotImplementedError

    def _is_low_precision(self, p) -> bool:
        return p.dtype in (jnp.bfloat16, jnp.float16)

    def _init_slots_mp(self, p: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        slots = self._init_slots(p)
        if self._multi_precision and self._is_low_precision(p):
            # fp32 master copy (reference: multi_precision adam_op / O2 AMP
            # master weights) — updates accumulate in fp32, the live param
            # stays bf16/fp16 for compute
            slots["master_weight"] = p.astype(jnp.float32)
        return slots

    def _rule_mp(self, g, p, slots, lr, wd):
        master = slots.pop("master_weight", None)
        if master is None:
            return self._rule(g, p, slots, lr, wd)
        new_master, new_slots = self._rule(g, master, slots, lr, wd)
        new_slots["master_weight"] = new_master
        return new_master.astype(p.dtype), new_slots

    def _wd_for(self, param) -> float:
        from ..regularizer import L1Decay, L2Decay
        wd = self._weight_decay
        # honor per-param no-decay lists used by models (bias/norm exclusion)
        if getattr(param, "no_weight_decay", False):
            return 0.0
        if isinstance(wd, L2Decay):
            return wd.coeff
        if isinstance(wd, L1Decay):
            return 0.0  # folded into the gradient by _reg_grad instead
        if hasattr(wd, "__call__") and not isinstance(wd, (int, float)):
            return 0.0
        return float(wd)

    def _reg_grad(self, g, p, no_decay=False):
        """Fold non-L2 regularizer penalties into the gradient (the static
        reference appends these ops before the optimizer op). Honors the
        same per-param no_weight_decay exclusion as _wd_for."""
        from ..regularizer import L1Decay
        if no_decay:
            return g
        if isinstance(self._weight_decay, L1Decay):
            return g + self._weight_decay.coeff * jnp.sign(
                p.astype(g.dtype))
        return g

    # ---- eager step ----
    @no_grad()
    def step(self):
        from ..core.selected_rows import SelectedRows
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            # clipping needs every gradient dense (global-norm couples them)
            for p, g in params_grads:
                if isinstance(g, SelectedRows):
                    p.grad = Tensor(g.to_dense())
            params_grads = [(p, p.grad) for p, _ in params_grads]
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        for p, g in params_grads:
            if g is None:
                continue
            pid = id(p)
            if pid not in self._state:
                self._state[pid] = self._init_slots_mp(p.data)
            slots = self._state[pid]
            lr = self.get_lr() * getattr(p, "optimize_attr",
                                         {"learning_rate": 1.0})["learning_rate"]
            lr = self._param_lr(p, lr)
            wd = self._wd_for(p)
            if isinstance(g, SelectedRows):
                from ..regularizer import L1Decay
                sparse_rule = getattr(self, "_sparse_rule", None)
                res = None
                if sparse_rule is not None and not wd and \
                        "master_weight" not in slots:
                    # L1Decay maps to wd=0 (its penalty lives in _reg_grad
                    # on the dense path); fold coeff*sign(p[rows]) into the
                    # row values so sparse updates keep the L1 pull without
                    # touching unvisited rows. Merge duplicate rows FIRST so
                    # a token seen k times gets the penalty once, and keep
                    # the original g for the dense fallback below (where
                    # _reg_grad applies L1 — no double-count).
                    g_rule = g
                    if isinstance(self._weight_decay, L1Decay) and \
                            not getattr(p, "no_weight_decay", False):
                        merged = g.merge()  # fp32 accum for low-prec grads
                        g_rule = SelectedRows(
                            merged.rows,
                            merged.values + self._weight_decay.coeff
                            * jnp.sign(p.data[merged.rows]).astype(
                                merged.values.dtype),
                            g.height)
                    res = sparse_rule(g_rule, p.data, slots, lr)
                if res is not None:
                    p.data, self._state[pid] = res
                    continue
                g = Tensor(g.to_dense())  # wd / mp / no row-wise rule
            new_p, new_slots = self._rule_mp(
                self._reg_grad(g.data, p.data,
                               getattr(p, "no_weight_decay", False)),
                p.data, slots, lr, wd)
            p.data = new_p
            self._state[pid] = new_slots

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list or []:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        params_grads = [(p, p.grad) for p in self._parameter_list or []]
        return None, params_grads

    # ---- functional API (used by jit train steps & distributed wrappers) ----
    def init_state(self, params: Dict[str, jnp.ndarray]):
        """Pure: build slot pytree for a named-param dict."""
        return {k: self._init_slots_mp(v) for k, v in params.items()}

    def clip_gradients_fn(self):
        """Pure fn(grads_dict) -> clipped grads, mirroring self._grad_clip so
        the jit path honors the same clipping as the eager step()."""
        from ..nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                               ClipGradByValue)
        clip = self._grad_clip

        def clip_fn(grads):
            if clip is None:
                return grads
            import jax
            if isinstance(clip, ClipGradByValue):
                return jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, clip.min, clip.max), grads)
            if isinstance(clip, ClipGradByNorm):
                def per_leaf(g):
                    n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                    f = jnp.minimum(clip.clip_norm / jnp.maximum(n, 1e-12),
                                    1.0)
                    return (g.astype(jnp.float32) * f).astype(g.dtype)
                return jax.tree_util.tree_map(per_leaf, grads)
            if isinstance(clip, ClipGradByGlobalNorm):
                leaves = jax.tree_util.tree_leaves(grads)
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in leaves)
                gnorm = jnp.sqrt(gsq)
                f = jnp.minimum(
                    clip.clip_norm / jnp.maximum(gnorm, clip.clip_norm), 1.0)
                return jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * f).astype(g.dtype),
                    grads)
            return grads  # custom clips (hybrid) run in their own wrappers

        return clip_fn

    def apply_gradients_fn(self):
        """Returns pure fn(params, grads, state, lr, step) -> (params, state).

        All leaves are jax arrays; safe to jit/pjit. Per-param knobs
        (AdamW's apply_decay_param_fun/lr_ratio, Lamb's
        exclude_from_weight_decay_fn) are honored per leaf: the params
        dict is name-keyed, so the user fn is called at trace time with
        the name (apply_decay_param_fun) or a name-carrying proxy
        (exclude/lr_ratio fns, which receive a param in eager mode — a
        fn reading attributes beyond .name fails loudly here).
        """
        import types

        decay_fun = getattr(self, "_apply_decay_param_fun", None)
        exclude_fn = getattr(self, "_exclude_fn", None)
        lr_ratio = getattr(self, "_lr_ratio", None)

        def _leaf_wd(k, wd):
            if decay_fun is not None and not decay_fun(k):
                return 0.0
            if exclude_fn is not None and \
                    exclude_fn(types.SimpleNamespace(name=k)):
                return 0.0
            return wd

        def _leaf_lr(k, lr):
            if lr_ratio is None:
                return lr
            return lr * float(lr_ratio(types.SimpleNamespace(name=k)))
        from ..regularizer import L2Decay, WeightDecayRegularizer
        if isinstance(self._weight_decay, L2Decay):
            wd = self._weight_decay.coeff
        elif isinstance(self._weight_decay, WeightDecayRegularizer) or \
                callable(self._weight_decay):
            wd = 0.0  # L1 is folded into the gradient by _reg_grad
        else:
            wd = float(self._weight_decay)

        def apply_fn(params, grads, state, lr, step, norm_meta=None):
            new_params, new_state = {}, {}
            for k, p in params.items():
                g = grads.get(k)
                if g is None:
                    new_params[k] = p
                    new_state[k] = state[k]
                    continue
                ctx_slots = dict(state[k])
                ctx_slots["_step"] = step
                if norm_meta is not None and k in norm_meta:
                    # distributed layout hint for norm-based rules
                    # (Lamb/LARS): mesh axes sharding this leaf + leading
                    # stacked-layer batch dims (see _dist_norm)
                    axes, bd = norm_meta[k]
                    ctx_slots["_norm_axes"] = axes
                    ctx_slots["_norm_batch_dims"] = bd
                np_, ns_ = self._rule_mp(self._reg_grad(g, p), p, ctx_slots,
                                         _leaf_lr(k, lr), _leaf_wd(k, wd))
                for extra in ("_step", "_norm_axes", "_norm_batch_dims"):
                    ns_.pop(extra, None)
                new_params[k] = np_
                new_state[k] = ns_
            return new_params, new_state

        return apply_fn

    # ---- checkpointing ----
    def state_dict(self):
        out = {"_step_count": self._step_count}
        if self._parameter_list:
            for i, p in enumerate(self._parameter_list):
                slots = self._state.get(id(p))
                if slots:
                    for sname, arr in slots.items():
                        out[f"{p.name or i}__{sname}"] = Tensor(arr)
        if self._lr_scheduler is not None:
            out["LR_Scheduler"] = self._lr_scheduler.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("_step_count", 0))
        if "LR_Scheduler" in state and self._lr_scheduler is not None:
            self._lr_scheduler.set_state_dict(state["LR_Scheduler"])
        if not self._parameter_list:
            return
        for i, p in enumerate(self._parameter_list):
            key = p.name or i
            slots = {}
            prefix = f"{key}__"
            for k, v in state.items():
                if isinstance(k, str) and k.startswith(str(prefix)):
                    arr = v.data if isinstance(v, Tensor) else jnp.asarray(v)
                    slots[k[len(str(prefix)):]] = arr
            if slots:
                self._state[id(p)] = slots

    set_dict = set_state_dict


class SGD(Optimizer):
    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        if wd:
            g = g + wd * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * g).astype(p.dtype), slots

    def _sparse_rule(self, g, p, slots, lr):
        """Row-wise update for SelectedRows grads (sgd_op.cc sparse
        kernel): only the looked-up rows are touched; duplicate rows
        accumulate, matching the dense scatter-add semantics."""
        vals = g.values.astype(jnp.float32)
        return p.at[g.rows].add((-lr * vals).astype(p.dtype)), slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, rescale_grad=1.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov
        self._rescale_grad = float(rescale_grad)

    def _init_slots(self, p):
        return {"velocity": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        if self._rescale_grad != 1.0:  # momentum_op RescaleGrad attr
            g = g * self._rescale_grad
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        v = self._momentum * slots["velocity"] + g
        if self._nesterov:
            update = g + self._momentum * v
        else:
            update = v
        out = {"velocity": v}
        out.update({k: v2 for k, v2 in slots.items() if k == "_step"})
        return (p32 - lr * update).astype(p.dtype), out


def _adam_math(p32, g, m1, m2, lr, b1p, b2p, wd, *, b1, b2, eps, decoupled):
    """One Adam / AdamW update in float32 (adam_op.h AdamFunctor): returns
    (new_p32, new_m1, new_m2). `b1p` / `b2p` are beta^t after this step;
    `decoupled` applies the decay to the update (AdamW), not the gradient."""
    g = g.astype(jnp.float32)
    if not decoupled:
        g = g + wd * p32
    m1n = b1 * m1 + (1.0 - b1) * g
    m2n = b2 * m2 + (1.0 - b2) * g * g
    update = (m1n / (1.0 - b1p)) / (jnp.sqrt(m2n / (1.0 - b2p)) + eps)
    if decoupled:
        update = update + wd * p32
    return p32 - lr * update, m1n, m2n


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, moment_dtype="float32"):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lazy_mode = lazy_mode
        # moment_dtype="bfloat16" halves optimizer-state HBM (the update
        # math still runs fp32; only storage rounds). A documented deviation
        # from the reference's fp32 adam moments for capacity-bound
        # single-chip fits (gpt3-1.3b on 16 GB); default keeps fp32 parity.
        self._moment_dtype = jnp.dtype(moment_dtype)

    def _init_slots(self, p):
        return {"moment1": jnp.zeros(p.shape, self._moment_dtype),
                "moment2": jnp.zeros(p.shape, self._moment_dtype),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _decoupled(self):
        return False

    def _sparse_rule(self, g, p, slots, lr):
        """lazy_mode adam (adam_op.h SparseAdamFunctor, lazy_mode=True):
        moments and param update only on the rows present in the
        SelectedRows grad. Duplicate rows are merge-added first (the
        reference's scatter::MergeAdd)."""
        if not self._lazy_mode:
            return None
        # merge-add duplicate rows in fp32 (scatter::MergeAdd)
        merged = g.merge(accum_dtype=jnp.float32)
        rows = merged.rows
        vals = merged.values
        b1, b2 = self._beta1, self._beta2
        b1p = slots["beta1_pow"] * b1
        b2p = slots["beta2_pow"] * b2
        # math in fp32 regardless of moment storage dtype (same contract as
        # the dense rule); only the .set rounds back to moment_dtype
        m1r = b1 * slots["moment1"][rows].astype(jnp.float32) \
            + (1 - b1) * vals
        m2r = b2 * slots["moment2"][rows].astype(jnp.float32) \
            + (1 - b2) * vals * vals
        upd = (m1r / (1 - b1p)) / (jnp.sqrt(m2r / (1 - b2p))
                                   + self._epsilon)
        new_p = p.at[rows].add((-lr * upd).astype(p.dtype))
        md = self._moment_dtype
        new_slots = {"moment1": slots["moment1"].at[rows].set(
                         m1r.astype(md)),
                     "moment2": slots["moment2"].at[rows].set(
                         m2r.astype(md)),
                     "beta1_pow": b1p, "beta2_pow": b2p}
        return new_p, new_slots

    def _rule(self, g, p, slots, lr, wd):
        b1, b2 = self._beta1, self._beta2
        b1p = slots["beta1_pow"] * b1
        b2p = slots["beta2_pow"] * b2
        new_p, m1, m2 = _adam_math(
            p.astype(jnp.float32), g,
            slots["moment1"].astype(jnp.float32),
            slots["moment2"].astype(jnp.float32),
            jnp.asarray(lr, jnp.float32), jnp.asarray(b1p, jnp.float32),
            jnp.asarray(b2p, jnp.float32), jnp.asarray(wd or 0.0, jnp.float32),
            b1=b1, b2=b2, eps=self._epsilon, decoupled=self._decoupled())
        md = self._moment_dtype
        return new_p.astype(p.dtype), {
            "moment1": m1.astype(md), "moment2": m2.astype(md),
            "beta1_pow": b1p, "beta2_pow": b2p}


class AdamW(Adam):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=0.01,
                 apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 lr_ratio=None, moment_dtype="float32"):
        # positional prefix matches the reference (no lr_ratio in the
        # snapshot's adamw.py); lr_ratio/moment_dtype are keyword tail.
        # lr_ratio(param) -> float scales this param's lr (layer-wise lr
        # decay); applied on the eager step path via _param_lr
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name, moment_dtype)
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _param_lr(self, p, lr):
        if self._lr_ratio is not None:
            return lr * float(self._lr_ratio(p))
        return lr

    def _decoupled(self):
        return True

    def _wd_for(self, param):
        if (self._apply_decay_param_fun is not None
                and not self._apply_decay_param_fun(param.name)):
            return 0.0
        return super()._wd_for(param)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-08, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"moment": jnp.zeros(p.shape, jnp.float32),
                "inf_norm": jnp.zeros(p.shape, jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        b1p = slots["beta1_pow"] * self._beta1
        m = self._beta1 * slots["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * slots["inf_norm"], jnp.abs(g))
        new_p = (p32 - lr / (1 - b1p) * m / (u + self._epsilon)).astype(p.dtype)
        return new_p, {"moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-06, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0):
        # reference order: name BEFORE initial_accumulator_value
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {"moment": jnp.full(p.shape, self._init_acc, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        acc = slots["moment"] + g * g
        new_p = (p32 - lr * g / (jnp.sqrt(acc) + self._epsilon)).astype(p.dtype)
        return new_p, {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-06, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slots(self, p):
        slots = {"mean_square": jnp.zeros(p.shape, jnp.float32),
                 "momentum": jnp.zeros(p.shape, jnp.float32)}
        if self._centered:
            slots["mean_grad"] = jnp.zeros(p.shape, jnp.float32)
        return slots

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        ms = self._rho * slots["mean_square"] + (1 - self._rho) * g * g
        out = {"mean_square": ms}
        if self._centered:
            mg = self._rho * slots["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - mg * mg + self._epsilon)
            out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * slots["momentum"] + lr * g / denom
        out["momentum"] = mom
        return (p32 - mom).astype(p.dtype), out


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-06, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon, self._rho = epsilon, rho

    def _init_slots(self, p):
        return {"avg_squared_grad": jnp.zeros(p.shape, jnp.float32),
                "avg_squared_update": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        asg = self._rho * slots["avg_squared_grad"] + (1 - self._rho) * g * g
        update = (jnp.sqrt(slots["avg_squared_update"] + self._epsilon)
                  / jnp.sqrt(asg + self._epsilon)) * g
        asu = (self._rho * slots["avg_squared_update"]
               + (1 - self._rho) * update * update)
        return (p32 - lr * update).astype(p.dtype), \
            {"avg_squared_grad": asg, "avg_squared_update": asu}


def _dist_norm(x, batch_dims, axes):
    """L2 norm of a possibly-sharded, possibly layer-stacked tensor.

    `axes`: mesh axis names whose shards this leaf is split over (model/
    sharding/ep) — the squared sum is lax.psum'd over them so trust ratios
    see WHOLE-parameter norms (HybridParallelClipGrad's cross-group
    allreduce, applied to the optimizer rule; reference
    hybrid_parallel_optimizer.py:32). `batch_dims`: leading dims that stack
    independent per-layer params (the pipeline's [pipe, per_stage, ...]
    leaves) — norms are taken per layer row and broadcast, matching eager
    per-parameter semantics."""
    from jax import lax
    if batch_dims:
        sq = jnp.sum(jnp.square(x), axis=tuple(range(batch_dims, x.ndim)),
                     keepdims=True)
    else:
        sq = jnp.sum(jnp.square(x))
    for ax in axes or ():
        sq = lax.psum(sq, ax)
    return jnp.sqrt(sq)


class Lamb(Optimizer):
    """LAMB (reference: operators/optimizers/lamb_op.cu, lamb meta-optimizer)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-06, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slots(self, p):
        return {"moment1": jnp.zeros(p.shape, jnp.float32),
                "moment2": jnp.zeros(p.shape, jnp.float32),
                "beta1_pow": jnp.ones((), jnp.float32),
                "beta2_pow": jnp.ones((), jnp.float32)}

    def _wd_for(self, param):
        if self._exclude_fn is not None and self._exclude_fn(param):
            return 0.0
        return float(self._weight_decay)

    def _rule(self, g, p, slots, lr, wd):
        norm_axes = slots.pop("_norm_axes", ())
        batch_dims = slots.pop("_norm_batch_dims", 0)
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        b1p = slots["beta1_pow"] * b1
        b2p = slots["beta2_pow"] * b2
        m1 = b1 * slots["moment1"] + (1 - b1) * g
        m2 = b2 * slots["moment2"] + (1 - b2) * g * g
        m1h = m1 / (1 - b1p)
        m2h = m2 / (1 - b2p)
        r = m1h / (jnp.sqrt(m2h) + self._epsilon) + wd * p32
        w_norm = _dist_norm(p32, batch_dims, norm_axes)
        r_norm = _dist_norm(r, batch_dims, norm_axes)
        trust = jnp.where(w_norm > 0, jnp.where(r_norm > 0, w_norm / r_norm,
                                                1.0), 1.0)
        new_p = (p32 - lr * trust * r).astype(p.dtype)
        return new_p, {"moment1": m1, "moment2": m2, "beta1_pow": b1p,
                       "beta2_pow": b2p}


class LarsMomentum(Optimizer):
    """LARS (reference: operators/optimizers/lars_momentum_op.cu)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=0, name=None):
        super().__init__(learning_rate, parameters, lars_weight_decay,
                         grad_clip, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._epsilon = epsilon

    def _init_slots(self, p):
        return {"velocity": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        norm_axes = slots.pop("_norm_axes", ())
        batch_dims = slots.pop("_norm_batch_dims", 0)
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        p_norm = _dist_norm(p32, batch_dims, norm_axes)
        g_norm = _dist_norm(g, batch_dims, norm_axes)
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            self._lars_coeff * p_norm / (g_norm + wd * p_norm + self._epsilon),
            1.0)
        v = self._momentum * slots["velocity"] + lr * local_lr * (g + wd * p32)
        return (p32 - v).astype(p.dtype), {"velocity": v}


class DecayedAdagrad(Optimizer):
    """Decayed Adagrad (operators/optimizers/decayed_adagrad_op.h):
    moment = decay * moment + (1 - decay) * g^2."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-06,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._decay, self._epsilon = decay, epsilon

    def _init_slots(self, p):
        return {"moment": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if wd:
            g = g + wd * p32
        acc = self._decay * slots["moment"] + (1.0 - self._decay) * g * g
        new_p = p32 - lr * g / (jnp.sqrt(acc) + self._epsilon)
        return new_p.astype(p.dtype), {"moment": acc}


class Ftrl(Optimizer):
    """FTRL-proximal (operators/optimizers/ftrl_op.h): accumulates squared
    grads and the linear term, then solves the per-coordinate proximal
    step with L1/L2 shrinkage. lr_power=-0.5 is the canonical sqrt
    schedule (the kernel's special case)."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _init_slots(self, p):
        return {"squared": jnp.zeros(p.shape, jnp.float32),
                "linear": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        sq, lin = slots["squared"], slots["linear"]
        new_sq = sq + g * g
        lp = -self._lr_power
        sigma = (new_sq ** lp - sq ** lp) / lr
        new_lin = lin + g - sigma * p32
        x = self._l1 * jnp.sign(new_lin) - new_lin
        y = new_sq ** lp / lr + 2.0 * self._l2
        new_p = jnp.where(jnp.abs(new_lin) > self._l1, x / y, 0.0)
        return new_p.astype(p.dtype), {"squared": new_sq, "linear": new_lin}


class Dpsgd(Optimizer):
    """Differentially-private SGD (operators/optimizers/dpsgd_op.h, CCS16
    "Deep Learning with Differential Privacy"): per-parameter grad L2 clip
    to `clip`, plus one gaussian noise draw scaled by sigma/batch_size.
    The noise rides jax.random (folded per step) instead of the
    reference's host minstd_rand."""

    def __init__(self, learning_rate, clip=10.0, batch_size=16.0,
                 sigma=1.0, seed=0, parameters=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._clip, self._bs, self._sigma = clip, batch_size, sigma
        self._seed = seed
        self._salt_counter = 0

    def _init_slots(self, p):
        # per-param salt: each parameter draws its own noise stream (the
        # reference's per-op-instance engine); folded with the step as a
        # (salt, step) PAIR below, so streams never collide at any step
        # count or parameter count
        self._salt_counter += 1
        return {"noise_salt": jnp.asarray(self._salt_counter, jnp.int32),
                "noise_step": jnp.asarray(0, jnp.int32)}

    def _rule(self, g, p, slots, lr, wd):
        import jax as _jax
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        l2 = jnp.sqrt(jnp.sum(g * g))
        scale = jnp.maximum(l2 / self._clip, 1.0)
        key = _jax.random.fold_in(
            _jax.random.fold_in(_jax.random.PRNGKey(self._seed),
                                slots["noise_salt"]),
            slots["noise_step"])
        # ONE scalar draw per param per step — dpsgd_op.h draws a single
        # Box-Muller gaussian outside its element loop, same shape here
        noise = _jax.random.normal(key, ()) * self._sigma
        new_p = p32 - lr * (g / scale + noise / self._bs)
        return new_p.astype(p.dtype), {
            "noise_salt": slots["noise_salt"],
            "noise_step": slots["noise_step"] + 1}


class ProximalAdagrad(Optimizer):
    """Proximal Adagrad (operators/optimizers/proximal_adagrad_op.h):
    adagrad step followed by L1/L2 soft-threshold shrinkage."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, parameters=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._l1, self._l2 = l1, l2

    def _init_slots(self, p):
        return {"moment": jnp.zeros(p.shape, jnp.float32)}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        acc = slots["moment"] + g * g
        lr_t = lr / jnp.sqrt(acc)
        prox = p32 - lr_t * g
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr_t * self._l1, 0.0) / (1.0 + lr_t * self._l2)
        return new_p.astype(p.dtype), {"moment": acc}


class ProximalGD(Optimizer):
    """Proximal gradient descent (operators/optimizers/proximal_gd_op.h):
    plain SGD step then the same L1/L2 shrinkage (no accumulator)."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, parameters=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._l1, self._l2 = l1, l2

    def _init_slots(self, p):
        return {}

    def _rule(self, g, p, slots, lr, wd):
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        prox = p32 - lr * g
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr * self._l1, 0.0) / (1.0 + lr * self._l2)
        return new_p.astype(p.dtype), {}
