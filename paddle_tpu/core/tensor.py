"""Tensor + eager autograd tape.

The reference implements eager mode with a C++ tracer that records a GradOpNode per op
(/root/reference/paddle/fluid/imperative/tracer.cc:144,231) and a queue-driven backward
engine (imperative/basic_engine.cc:305) with per-leaf gradient accumulators
(imperative/gradient_accumulator.cc).

TPU-native redesign: every eager op is a pure jax function. When gradients are enabled
and an input requires grad, the op is executed through `jax.vjp`, which both runs the
forward on-device and returns a host-side pullback closure holding on-device residuals.
The pullbacks form a linear tape (execution order), so backward is a single reverse
sweep — no op registry, no grad-op makers, no kernel dispatch: XLA differentiates every
primitive. The jit path (`paddle_tpu.jit`, functional training steps) bypasses the tape
entirely and uses jax.grad over a functionalized module call, which is the performance
path on TPU.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .device import Place, get_device


class _TapeState(threading.local):
    def __init__(self):
        self.grad_enabled: bool = True
        self.seq: int = 0  # monotone op counter orders the reverse sweep


_STATE = _TapeState()


class _Node:
    """One recorded eager op: pullback + links to diff inputs and outputs.

    Nodes are owned by their output Tensors (no global tape), so autograd
    graphs are freed by ordinary GC as soon as the activations die — an eval
    loop without no_grad() cannot grow memory unboundedly. backward() walks
    the graph from the loss and sweeps in reverse `seq` order."""

    __slots__ = ("vjp_fn", "inputs", "in_links", "outputs", "out_grads",
                 "single", "seq", "fn_info")

    def __init__(self, vjp_fn, inputs, outputs, single, seq, fn_info=None):
        self.vjp_fn = vjp_fn
        # (fn, raw_args, diff_idx, kwargs): enough to RE-derive the vjp as
        # a taped computation over the primal Tensors — the create_graph
        # (double-grad) path needs the pullback as a function of the
        # primals, which the residual-closed vjp_fn is not
        self.fn_info = fn_info
        self.inputs: List["Tensor"] = inputs
        # (producer node, out index) per input, snapshotted at record time:
        # in-place ops (__setitem__) rebind a Tensor's _node afterwards, and
        # consumers recorded before the write must keep routing cotangents to
        # the pre-write producer.
        self.in_links = [(t._node, t._out_index) for t in inputs]
        self.outputs: List["Tensor"] = outputs
        self.out_grads: List[Optional[jax.Array]] = [None] * len(outputs)
        self.single = single  # forward returned a bare array (not a tuple)
        self.seq = seq

    def seed(self, index: int, grad):
        cur = self.out_grads[index]
        if cur is None:
            self.out_grads[index] = grad
            return
        if isinstance(cur, Tensor) or isinstance(grad, Tensor):
            # create_graph cotangents are Tensors: accumulate on the tape
            a = cur if isinstance(cur, Tensor) else Tensor(cur)
            b = grad if isinstance(grad, Tensor) else Tensor(grad)
            self.out_grads[index] = a + b
        else:
            self.out_grads[index] = cur + grad


def is_grad_enabled() -> bool:
    return _STATE.grad_enabled


def set_grad_enabled(mode: bool):
    """paddle.set_grad_enabled parity: context manager (and direct call)
    flipping tape recording on/off."""

    class _Ctx:
        def __init__(self, m, prev):
            self._m = bool(m)
            self._prev = prev  # captured BEFORE the mode was applied

        def __enter__(self):
            _STATE.grad_enabled = self._m
            return self

        def __exit__(self, *exc):
            _STATE.grad_enabled = self._prev
            return False

    prev = _STATE.grad_enabled
    # takes effect immediately when used as a plain call; as a context
    # manager, exit restores the state from before this call
    _STATE.grad_enabled = bool(mode)
    return _Ctx(mode, prev)


class no_grad:
    """Context manager + decorator disabling tape recording (paddle.no_grad parity)."""

    def __enter__(self):
        self._prev = _STATE.grad_enabled
        _STATE.grad_enabled = False
        return self

    def __exit__(self, *exc):
        _STATE.grad_enabled = self._prev
        return False

    def __call__(self, fn):
        def wrapper(*args, **kwargs):
            with no_grad():
                return fn(*args, **kwargs)

        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = _STATE.grad_enabled
        _STATE.grad_enabled = True
        return self

    def __exit__(self, *exc):
        _STATE.grad_enabled = self._prev
        return False


def reset_tape():
    """Kept for API compatibility; graphs are GC-owned so there is no global
    tape to clear."""
    _STATE.seq = 0


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


class UnassignedParameterError(RuntimeError):
    """A parameter created under `LazyGuard` was used before `p.data` was
    assigned."""


class Unassigned:
    """What a parameter created under `nn.layer.layers.LazyGuard` holds in
    place of an array: its shape and type, nothing on any device. Assigning
    `p.data = array` makes the parameter real; anything that asks this
    object for values raises `UnassignedParameterError` with the
    parameter's name (`label`: the owning layer's class and attribute,
    filled in when the layer takes the parameter)."""

    __slots__ = ("shape", "dtype", "label")

    def __init__(self, shape, dtype, label="a parameter"):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtypes.convert_dtype(dtype))
        self.label = label

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    def _refuse(self, *_args, **_kwargs):
        raise UnassignedParameterError(
            f"{self.label} {list(self.shape)} was created under LazyGuard "
            "and has not been assigned: give it `p.data = array` first")

    def __getattr__(self, name):
        self._refuse()

    __array__ = __getitem__ = __len__ = _refuse

    def __repr__(self):
        return f"Unassigned({self.label}, {list(self.shape)}, {self.dtype})"


def to_array(value, dtype=None) -> jax.Array:
    """Convert arbitrary input to a jax.Array (host numpy path for lists/scalars)."""
    if isinstance(value, Unassigned):
        return value
    if isinstance(value, Tensor):
        arr = value.data
    elif isinstance(value, (jax.Array,)) or _is_tracer(value):
        arr = value
    else:
        arr = jnp.asarray(np.asarray(value))
    if dtype is not None:
        arr = arr.astype(dtypes.convert_dtype(dtype))
    return arr


class Tensor:
    """Eager tensor: a jax.Array plus autograd metadata.

    `stop_gradient` defaults True (paddle semantics); Parameters flip it to False.
    """

    __slots__ = ("data", "stop_gradient", "grad", "name", "_node", "_out_index",
                 "persistable", "_hooks", "__weakref__")

    def __init__(self, data, dtype=None, place: Optional[Place] = None,
                 stop_gradient: bool = True, name: Optional[str] = None):
        self.data = to_array(data, dtype)
        self.stop_gradient = stop_gradient
        self.grad: Optional[Tensor] = None
        self.name = name
        self.persistable = False
        self._node: Optional[_Node] = None
        self._out_index: int = 0
        self._hooks = None  # OrderedDict[int, hook] once register_hook called

    # ---- metadata ----
    @property
    def shape(self):
        return list(self.data.shape)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def size(self):
        return int(np.prod(self.data.shape)) if self.data.shape else 1

    @property
    def dtype(self):
        return np.dtype(self.data.dtype)

    @property
    def place(self):
        return get_device()

    def numel(self):
        return self.size

    def dim(self):
        return self.data.ndim

    # ---- conversion ----
    def numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def item(self):
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.numpy())

    def __len__(self):
        if not self.data.shape:
            raise TypeError("len() of a 0-d tensor")
        return self.data.shape[0]

    def __iter__(self):
        # without this, `for row in tensor` falls back to the __getitem__
        # protocol, which never raises IndexError (jnp indexing clips) and
        # loops forever; shape[0] is static, so iteration also terminates
        # under tracing (an unrolled loop, like the reference's dygraph)
        if not self.data.shape:
            raise TypeError("iteration over a 0-d tensor")
        for i in range(self.data.shape[0]):
            yield self[i]

    def __hash__(self):
        return id(self)

    # ---- autograd ----
    @property
    def is_leaf(self) -> bool:
        return self._node is None

    def detach(self) -> "Tensor":
        t = Tensor(self.data, stop_gradient=True, name=self.name)
        return t

    def clone(self) -> "Tensor":
        return apply(lambda x: x + 0, self)

    def backward(self, grad_tensor: Optional["Tensor"] = None,
                 retain_graph: bool = False):
        backward(self, grad_tensor, retain_graph=retain_graph)

    def register_hook(self, hook):
        """Register a backward hook fired when this tensor's gradient is
        computed (reference imperative/hooks.h; VarBase::AddVariableWrapperHook).
        hook(grad: Tensor) -> Tensor | None; a returned Tensor replaces the
        gradient flowing upstream (non-leaf) / accumulated into .grad (leaf).
        Hooks run in registration order, each seeing the previous result.
        Returns a removable helper (.remove())."""
        if self.stop_gradient:
            raise RuntimeError(
                "cannot register a gradient hook on a tensor with "
                "stop_gradient=True (reference hooks require a grad var)")
        if self._hooks is None:
            from collections import OrderedDict
            self._hooks = OrderedDict()
        hid = next(_HOOK_IDS)  # never reused: a stale remover handle must
        # not be able to delete a later hook that inherited its id
        self._hooks[hid] = hook
        return _TensorHookRemover(self, hid)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):
        self.grad = None

    def _accumulate_grad(self, g):
        from .selected_rows import SelectedRows
        if isinstance(g, Tensor):
            # create_graph gradient: KEEP its tape node so grad-of-grad
            # can differentiate through it
            if self.grad is None:
                self.grad = g
            elif isinstance(self.grad, Tensor):
                self.grad = self.grad + g
            else:
                self.grad = Tensor(self.grad.to_dense()) + g
            return
        if isinstance(g, SelectedRows):
            if self.grad is None:
                self.grad = g
            elif isinstance(self.grad, SelectedRows):
                self.grad = self.grad.merge(g)
            elif self.grad._node is not None:
                # the existing grad carries a tape (create_graph): keep it
                self.grad = self.grad + Tensor(g.to_dense())
            else:
                self.grad = Tensor(self.grad.data + g.to_dense())
            return
        if self.grad is None:
            self.grad = Tensor(g)
        elif isinstance(self.grad, SelectedRows):
            self.grad = Tensor(self.grad.to_dense() + g)
        else:
            self.grad = Tensor(self.grad.data + g)

    # ---- mutation (optimizer updates, state loading) ----
    def set_value(self, value):
        from .errors import InvalidArgumentError
        arr = to_array(value)
        if tuple(arr.shape) != tuple(self.data.shape):
            raise InvalidArgumentError(
                f"set_value shape mismatch: {arr.shape} vs {self.data.shape}")
        self.data = arr.astype(self.data.dtype)

    def copy_(self, other, *_):
        self.set_value(other)
        return self

    # ---- basic ops (full surface lives in paddle_tpu.tensor.*) ----
    def astype(self, dtype) -> "Tensor":
        d = dtypes.convert_dtype(dtype)
        return apply(lambda x: x.astype(d), self)

    def cast(self, dtype) -> "Tensor":
        return self.astype(dtype)

    def __repr__(self):
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"stop_gradient={self.stop_gradient},\n{self.numpy()})")

    def __getitem__(self, idx):
        idx = _unwrap_index(idx)
        return apply(lambda x: x[idx], self)

    def __setitem__(self, idx, value):
        idx = _unwrap_index(idx)
        val = to_array(value)
        if (_STATE.grad_enabled and not self.stop_gradient
                and dtypes.is_floating_point(self.dtype)):
            # Route through the tape (the reference's set_value op participates
            # in autograd). A leaf that requires grad cannot be mutated in
            # place without orphaning its grad accumulator — fail loudly.
            if self._node is None:
                raise RuntimeError(
                    "in-place __setitem__ on a leaf tensor that requires "
                    "grad; use x = x.at_set(...) style functional update or "
                    "wrap in no_grad() if gradients through the assignment "
                    "are not needed")
            # apply() snapshots self's pre-write (node, index) into the new
            # node's in_links, so the cotangent w.r.t. the old value flows
            # into the existing graph even after we rebind self._node below
            args = [self]
            if isinstance(value, Tensor) and not value.stop_gradient:
                def f(x, v):
                    return x.at[idx].set(v.astype(x.dtype))
                args.append(value)
            else:
                def f(x):
                    return x.at[idx].set(val.astype(x.dtype))
            out = apply(f, *args)
            _rebind_inplace(self, out)
        else:
            self.data = self.data.at[idx].set(val.astype(self.data.dtype))

    # arithmetic operators are patched in by paddle_tpu.tensor.math to avoid a
    # circular import; see paddle_tpu/tensor/__init__.py::monkey_patch_tensor.


def _rebind_inplace(t: "Tensor", out: "Tensor"):
    """Make `t` the user-visible result of an in-place op traced as `out`.

    Downstream consumers hold `t`, so the new node must report gradients
    through it — and the OLD producer node must stop listing `t` as its
    output (else capture_ids would double-count the pre- and post-op
    cotangents for grads w.r.t. the mutated tensor)."""
    old_node, old_idx = t._node, t._out_index
    if old_node is not None and old_node.outputs[old_idx] is t:
        ph = Tensor(t.data, stop_gradient=True)  # shape donor for zeros_like
        old_node.outputs[old_idx] = ph
    t.data = out.data
    t._node = out._node
    t._out_index = out._out_index
    if t._node is not None:
        t._node.outputs[t._out_index] = t


def inplace_guard(t: "Tensor", opname: str = "op"):
    """Shared leaf guard for every in-place op (relu_/tanh_/add_/clip_/
    scatter_/…): a leaf that requires grad cannot be mutated in place
    without orphaning its grad accumulator — fail loudly, matching the
    reference's inplace leaf check."""
    if _STATE.grad_enabled and not t.stop_gradient and t._node is None:
        raise RuntimeError(
            f"in-place {opname} on a leaf tensor that requires grad is "
            "not allowed (matches the reference's inplace leaf guard)")


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx.data
    if isinstance(idx, tuple):
        return tuple(i.data if isinstance(i, Tensor) else i for i in idx)
    return idx


class Parameter(Tensor):
    """Trainable tensor (stop_gradient=False, persistable). Unlike activations
    (slotted for footprint), Parameters carry an open __dict__ for attrs like
    optimize_attr / partition_spec / no_weight_decay."""

    __slots__ = ("trainable", "__dict__")

    def __init__(self, data, dtype=None, name: Optional[str] = None,
                 trainable: bool = True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable


def _wrap_outputs(outs, node_needed: bool):
    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)
    tensors = []
    for o in outs_t:
        t = Tensor(o, stop_gradient=not node_needed)
        tensors.append(t)
    return tensors, single


def apply(fn: Callable, *args, **kwargs):
    """Run a pure jax function over Tensor/array args, recording a tape node when
    any floating-point Tensor input requires grad. Returns Tensor(s)."""
    raw = [a.data if isinstance(a, Tensor) else a for a in args]
    diff_idx = []
    if _STATE.grad_enabled:
        for i, a in enumerate(args):
            if (isinstance(a, Tensor) and not a.stop_gradient
                    and dtypes.is_floating_point(a.dtype)):
                diff_idx.append(i)

    try:
        if not diff_idx:
            outs = fn(*raw, **kwargs)
        else:
            def closed(*diff_vals):
                vals = list(raw)
                for i, v in zip(diff_idx, diff_vals):
                    vals[i] = v
                return fn(*vals, **kwargs)

            outs, vjp_fn = jax.vjp(closed, *[raw[i] for i in diff_idx])
    except (TypeError, ValueError):
        # JAX's own complaint about an operand it cannot read says nothing
        # of which parameter it was
        for r in raw:
            if isinstance(r, Unassigned):
                r._refuse()
        raise
    tensors, single = _wrap_outputs(outs, node_needed=bool(diff_idx))
    if diff_idx:
        _STATE.seq += 1
        node = _Node(vjp_fn, [args[i] for i in diff_idx], tensors, single,
                     _STATE.seq, fn_info=(fn, raw, diff_idx, kwargs))
        for k, t in enumerate(tensors):
            t._node = node
            t._out_index = k
    return tensors[0] if single else tuple(tensors)


def _reachable_nodes(roots: List[_Node]) -> List[_Node]:
    """All nodes reachable from the roots, sorted by seq descending."""
    seen = {}
    stack = list(roots)
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen[id(node)] = node
        for pnode, _ in node.in_links:
            if pnode is not None:
                stack.append(pnode)
    return sorted(seen.values(), key=lambda n: -n.seq)


def _second_order_vjp(node, cotangents):
    """Re-derive this node's vjp THROUGH the tape (create_graph): the
    pullback is re-expressed as a function of the primal input Tensors, so
    the returned gradients are themselves differentiable."""
    fn, raw, diff_idx, kwargs = node.fn_info
    n_p = len(diff_idx)
    single = node.single
    for i, inp in zip(diff_idx, node.inputs):
        if inp.data is not raw[i]:
            # an in-place rebind replaced this input's value after the op
            # was recorded; re-deriving at the CURRENT value would be
            # silently wrong — the normal (create_graph=False) path handles
            # this via the residual-closed vjp_fn + in_links snapshot
            raise RuntimeError(
                "create_graph through an op whose input was later mutated "
                "in place is not supported; compute the double-grad region "
                "without in-place updates")

    def second(*vals):
        prim = vals[:n_p]
        cots = vals[n_p:]

        def closed(*dv):
            vv = list(raw)
            for i, v in zip(diff_idx, dv):
                vv[i] = v
            return fn(*vv, **kwargs)

        _, pull = jax.vjp(closed, *prim)
        ct = cots[0] if single else tuple(cots)
        return pull(ct)

    outs = apply(second, *node.inputs, *cotangents)
    return outs if isinstance(outs, tuple) else (outs,)


import itertools as _itertools

_HOOK_IDS = _itertools.count()


class _TensorHookRemover:
    def __init__(self, t: "Tensor", hid: int):
        import weakref
        self._ref, self._hid = weakref.ref(t), hid  # don't pin the tensor
        # (or its tape) just because a remover handle is retained

    def remove(self):
        t = self._ref()
        if t is not None and t._hooks is not None:
            t._hooks.pop(self._hid, None)


def _add_grads(a, b):
    """Sum two gradient contributions of any flavor (array/Tensor/
    SelectedRows) — the leaf-hook buffer's accumulator."""
    from .selected_rows import SelectedRows
    if isinstance(a, SelectedRows) and isinstance(b, SelectedRows):
        return a.merge(b)
    if isinstance(a, SelectedRows):
        a = a.to_dense()
    if isinstance(b, SelectedRows):
        b = b.to_dense()
    if isinstance(a, Tensor) or isinstance(b, Tensor):
        a = a if isinstance(a, Tensor) else Tensor(a)
        b = b if isinstance(b, Tensor) else Tensor(b)
    return a + b


def _run_tensor_hooks(t: "Tensor", g):
    """Fold a tensor's hooks over a flowing gradient. g may be a raw array,
    a Tensor (create_graph), or a SelectedRows (densified for the hook)."""
    from .selected_rows import SelectedRows
    was_raw = not isinstance(g, Tensor)
    if isinstance(g, SelectedRows):
        g = g.to_dense()
    for hook in list(t._hooks.values()):
        out = hook(g if isinstance(g, Tensor) else Tensor(g))
        if out is not None:
            g = out
    if was_raw and isinstance(g, Tensor):
        return g.data
    return g


def backward(loss: Tensor, grad_tensor: Optional[Tensor] = None,
             retain_graph: bool = False, only_ids: Optional[set] = None,
             capture_ids: Optional[set] = None, create_graph: bool = False):
    """Reverse graph sweep (basic_engine.cc:305 analog).

    only_ids: if set, restrict leaf .grad accumulation to these tensor ids
    (paddle.grad uses this so model params aren't polluted).
    capture_ids: non-leaf tensors whose flowing cotangent should be recorded
    into .grad (paddle.grad w.r.t. intermediates).
    """
    if grad_tensor is None:
        seed = jnp.ones_like(loss.data)
    elif create_graph and isinstance(grad_tensor, Tensor):
        seed = grad_tensor  # keep its tape: d(grad)/d(grad_outputs) flows
    else:
        seed = grad_tensor.data
    if loss._node is None:
        if not loss.stop_gradient and (only_ids is None
                                       or id(loss) in only_ids):
            if loss._hooks:
                seed = _run_tensor_hooks(loss, seed)
            loss._accumulate_grad(seed)
        return
    if loss._node.vjp_fn is None:
        return  # graph already consumed by a prior backward (paddle no-ops)
    loss._node.seed(loss._out_index, seed)

    nodes = _reachable_nodes([loss._node])
    hook_buf: dict = {}  # id(leaf) -> [leaf, summed contributions]: leaf
    # hooks fire ONCE on the total gradient of this sweep, not per consumer
    try:
        _sweep(nodes, only_ids, capture_ids, create_graph, hook_buf)
    except BaseException:
        # leave no stale seeds behind: a caught-and-retried backward on
        # the same graph must not double-accumulate
        for node in nodes:
            node.out_grads = [None] * len(node.outputs)
        raise
    for t, g in hook_buf.values():
        t._accumulate_grad(_run_tensor_hooks(t, g))
    if not (retain_graph or create_graph):
        for node in nodes:
            node.vjp_fn = None  # free residuals; second backward is a no-op
            node.fn_info = None  # and the primal snapshots/closures


def _sweep(nodes, only_ids, capture_ids, create_graph, hook_buf=None):
    for node in nodes:
        if node.vjp_fn is None or all(g is None for g in node.out_grads):
            continue
        seeded = [g is not None for g in node.out_grads]
        cotangents = tuple(
            g if g is not None else jnp.zeros_like(t.data)
            for g, t in zip(node.out_grads, node.outputs)
        )
        # non-leaf hooks: by reverse-seq order every consumer has seeded by
        # now, so the cotangent is final — fire before capture and the vjp.
        # Outputs that received NO cotangent (unused siblings of a multi-
        # output node) keep their zero-fill: their hooks must not fire.
        if any(t._hooks and s for t, s in zip(node.outputs, seeded)):
            cotangents = tuple(
                _run_tensor_hooks(t, g) if (t._hooks and s) else g
                for t, g, s in zip(node.outputs, cotangents, seeded))
        if capture_ids:
            for t, g in zip(node.outputs, cotangents):
                if id(t) in capture_ids:
                    t._accumulate_grad(g)
        if create_graph and node.fn_info is None:
            raise RuntimeError(
                "create_graph through a custom tape node without re-"
                "derivable fn_info (e.g. the sparse-embedding backward) is "
                "not supported; use a dense embedding in double-grad "
                "regions")
        if create_graph and node.fn_info is not None:
            in_grads = _second_order_vjp(node, cotangents)
        else:
            raw_cots = tuple(c.data if isinstance(c, Tensor) else c
                             for c in cotangents)
            in_grads = node.vjp_fn(raw_cots[0] if node.single else raw_cots)
        for inp, (pnode, pidx), g in zip(node.inputs, node.in_links,
                                         in_grads):
            if g is None:
                continue
            if pnode is not None and pnode.vjp_fn is not None:
                pnode.seed(pidx, g)
            elif only_ids is None or id(inp) in only_ids:
                if inp._hooks and hook_buf is not None:
                    # bank: leaf hooks see the SUM over consumers
                    ent = hook_buf.setdefault(id(inp), [inp, None])
                    ent[1] = g if ent[1] is None else _add_grads(ent[1], g)
                else:
                    inp._accumulate_grad(g)
        node.out_grads = [None] * len(node.outputs)


def grad(outputs: Sequence[Tensor], inputs: Sequence[Tensor],
         grad_outputs: Optional[Sequence[Tensor]] = None,
         retain_graph: bool = False, create_graph: bool = False):
    """paddle.grad analog (partial_grad_engine.cc): grads of outputs w.r.t.
    inputs (leaves OR intermediates) without touching .grad on other leaves."""
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    saved = [(t, t.grad) for t in inputs]
    for t in inputs:
        t.grad = None
    leaf_ids = {id(t) for t in inputs if t._node is None}
    cap_ids = {id(t) for t in inputs if t._node is not None}
    try:
        for i, out in enumerate(outputs):
            g = None if grad_outputs is None else grad_outputs[i]
            backward(out, g,
                     retain_graph=(retain_graph or i < len(outputs) - 1),
                     only_ids=leaf_ids, capture_ids=cap_ids,
                     create_graph=create_graph)
        result = [t.grad if t.grad is not None else None for t in inputs]
    finally:
        # a raising backward must not clobber pre-existing .grad values
        for t, old in saved:
            t.grad = old
    return result
