"""Layer: the module base class.

Reference: python/paddle/fluid/dygraph/layers.py (class Layer) — parameter/buffer/
sublayer registries, state_dict, hooks, train/eval. Redesigned for TPU: a Layer is
also a *functional* object — `functional_state` / `functional_call` flatten it to a
pytree of jax arrays and back, which is what jit / grad / pjit consume. The stateful
eager path and the pure path share the same forward() code.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ...core import dtypes
from ...core.tensor import (Parameter, Tensor, Unassigned,
                            UnassignedParameterError, no_grad)
from .. import initializer as I


class ParamAttr:
    """paddle.ParamAttr analog: bundles name/initializer/regularizer/lr for a param."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None or attr is True:
            return ParamAttr()
        if attr is False:
            return None
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        raise TypeError(f"cannot convert {attr!r} to ParamAttr")


# the type a Layer creates its parameters in when its constructor names
# none; `parameter_dtype` sets it for the layers built inside its block
_PARAMETER_DTYPE = ["float32"]


@contextlib.contextmanager
def parameter_dtype(dtype):
    """Layers constructed inside the block create their parameters in
    `dtype` from the start (a model that only fits the device in bf16 is
    never materialised in float32 and cast)."""
    outer = _PARAMETER_DTYPE[0]
    _PARAMETER_DTYPE[0] = dtypes.dtype_name(dtypes.convert_dtype(dtype))
    try:
        yield
    finally:
        _PARAMETER_DTYPE[0] = outer


_LAZY = [False]


class LazyGuard(contextlib.ContextDecorator):
    """Layers constructed inside the block allocate nothing: every
    `create_parameter` records shape, type and (as its layer sets them)
    partition spec and the other attributes, and the parameter holds an
    `Unassigned` in place of an array. `p.data = array` makes it real.
    For a caller that is about to give the model its weights (a checkpoint,
    seeded weights) and cannot hold the constructor's copy beside them.
    A parameter used before it is assigned raises
    `UnassignedParameterError` by name. (Paddle's own later API name.)"""

    def __enter__(self):
        self._outer, _LAZY[0] = _LAZY[0], True
        return self

    def __exit__(self, *exc):
        _LAZY[0] = self._outer
        return False


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self._non_persistable_buffer_names = set()
        self.training = True
        self._dtype = dtype or _PARAMETER_DTYPE[0]
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._hook_id = 0

    # ---- attribute plumbing ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            params[name] = value
            if isinstance(value.data, Unassigned):
                value.data.label = f"{type(self).__name__}.{name}"
            buffers.pop(name, None) if buffers else None
            layers.pop(name, None) if layers else None
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            layers[name] = value
            object.__setattr__(self, name, value)
        elif isinstance(value, Tensor) and buffers is not None and name in buffers:
            buffers[name] = value
            object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                del params[name]
            object.__setattr__(self, name, value)

    # ---- construction helpers ----
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Parameter:
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        dtype = dtype or self._dtype
        init = attr.initializer or default_initializer or (
            I.Constant(0.0) if is_bias else I._GLOBAL_DEFAULT[0])
        data = Unassigned(shape, dtype) if _LAZY[0] else init(shape, dtype)
        p = Parameter(data, name=attr.name, trainable=attr.trainable)
        p.optimize_attr = {"learning_rate": attr.learning_rate}
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters.pop(name, None)
            object.__setattr__(self, name, None)
        else:
            setattr(self, name, parameter)
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        setattr(self, name, sublayer)
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor],
                        persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        object.__setattr__(self, name, tensor)

    # ---- traversal ----
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None:
                    continue
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers()]

    def named_sublayers(self, prefix="", include_self=False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(prefix=sub_prefix, include_self=True)

    def sublayers(self, include_self=False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_children(self):
        yield from self._sub_layers.items()

    def children(self):
        return list(self._sub_layers.values())

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ---- mode ----
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # ---- dtype / device movement ----
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = dtypes.convert_dtype(dtype)
            for p in self.parameters():
                if dtypes.is_floating_point(p.dtype):
                    p.data = p.data.astype(d)
            for _, b in self.named_buffers():
                if dtypes.is_floating_point(b.dtype):
                    b.data = b.data.astype(d)
            for layer in self.sublayers(include_self=True):
                layer._dtype = dtypes.dtype_name(d)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # ---- state ----
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True) -> Dict[str, Tensor]:
        out = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            out[name] = p
        for name, layer in self.named_sublayers(
                prefix=structured_name_prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                out[f"{name}.{bname}" if name else bname] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            arr = v.numpy() if hasattr(v, "numpy") else np.asarray(v)
            own[k].set_value(arr)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ---- hooks ----
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return _HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # ---- call ----
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    # ---- functional bridge (the TPU fast path) ----
    def functional_state(self):
        """Return (param_arrays, buffer_arrays) pytrees keyed by structured name."""
        lazy = [k for k, p in self.named_parameters()
                if isinstance(p.data, Unassigned)]
        if lazy:
            raise UnassignedParameterError(
                f"{len(lazy)} parameter(s) created under LazyGuard have not "
                f"been assigned: {lazy[:4]}{' ...' if len(lazy) > 4 else ''}")
        params = {k: p.data for k, p in self.named_parameters() if p.trainable}
        frozen = {k: p.data for k, p in self.named_parameters() if not p.trainable}
        bufs = {k: b.data for k, b in self.named_buffers()}
        bufs.update(frozen)
        return params, bufs

    @contextlib.contextmanager
    def _bound_state(self, params: Dict[str, Any], buffers: Dict[str, Any]):
        """Temporarily swap in arrays for parameters/buffers (by structured name)."""
        named_p = dict(self.named_parameters())
        named_b = dict(self.named_buffers())
        saved = []
        try:
            for k, arr in params.items():
                t = named_p.get(k)
                if t is None:
                    t = named_b.get(k)
                if t is None:
                    raise KeyError(f"unknown parameter {k}")
                saved.append((t, t.data))
                t.data = arr
            for k, arr in buffers.items():
                t = named_b.get(k)
                if t is None:
                    t = named_p.get(k)
                if t is None:
                    raise KeyError(f"unknown buffer {k}")
                saved.append((t, t.data))
                t.data = arr
            yield self
        finally:
            for t, old in saved:
                t.data = old

    def functional_call(self, params, buffers, *inputs, rng=None, **kwargs):
        """Pure call: forward() with given arrays bound, tape disabled.

        Differentiate with jax.grad over `params`; this is what jit/pjit trace.
        `rng` (a PRNG key, possibly a tracer) feeds dropout/random draws so
        they stay data-dependent under jit.
        """
        out, _ = self.functional_call_with_state(params, buffers, *inputs,
                                                 rng=rng, **kwargs)
        return out

    def functional_call_with_state(self, params, buffers, *inputs, rng=None,
                                   **kwargs):
        """Like functional_call but also returns the post-call buffer arrays
        (BatchNorm running stats etc.), which the caller must carry — inside a
        traced step the in-place buffer mutation is rolled back on exit."""
        import contextlib as _ctx
        from ...core.random import key_context
        named_b = dict(self.named_buffers())
        with self._bound_state(params, buffers):
            with no_grad():
                rng_ctx = key_context(rng) if rng is not None else \
                    _ctx.nullcontext()
                with rng_ctx:
                    wrapped = [Tensor(x) if not isinstance(x, Tensor) else x
                               for x in inputs]
                    out = self(*wrapped, **kwargs)
            new_buffers = {k: named_b[k].data if k in named_b
                           else buffers[k] for k in buffers}
        out = jax.tree_util.tree_map(
            lambda o: o.data if isinstance(o, Tensor) else o, out,
            is_leaf=lambda o: isinstance(o, Tensor))
        return out, new_buffers

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def __repr__(self):
        extra = []
        for name, layer in self._sub_layers.items():
            extra.append(f"  ({name}): {layer.__class__.__name__}")
        body = "\n".join(extra)
        return f"{self.__class__.__name__}(\n{body}\n)" if body else \
            f"{self.__class__.__name__}()"


class _HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._id = hook_id

    def remove(self):
        self._hooks.pop(self._id, None)


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __len__(self):
        return len(self._sub_layers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __setitem__(self, idx, layer):
        keys = list(self._sub_layers.keys())
        self.add_sublayer(keys[idx], layer)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)) and \
                layers[0] and isinstance(layers[0][0], tuple):
            for name, layer in layers[0]:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                if isinstance(layer, tuple):
                    self.add_sublayer(layer[0], layer[1])
                else:
                    self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        keys = list(self._sub_layers.keys())
        return self._sub_layers[keys[idx]]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __len__(self):
        return len(self._parameters)

    def __getitem__(self, idx):
        keys = list(self._parameters.keys())
        return self._parameters[keys[idx]]

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


class LayerDict(Layer):
    """Ordered dict of sublayers (reference: nn/layer/container.py
    LayerDict)."""

    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, sublayer):
        self.add_sublayer(key, sublayer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        v = self._sub_layers[key]
        del self._sub_layers[key]
        return v

    def keys(self):
        return self._sub_layers.keys()

    def items(self):
        return self._sub_layers.items()

    def values(self):
        return self._sub_layers.values()

    def update(self, sublayers):
        pairs = (sublayers.items() if hasattr(sublayers, "items")
                 else sublayers)
        for k, v in pairs:
            self.add_sublayer(k, v)
        return self
