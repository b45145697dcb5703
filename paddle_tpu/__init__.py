"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's API surface.

Built from scratch on JAX/XLA/Pallas: eager mode records jax.vjp pullbacks on a tape
(dygraph parity), jit mode traces the same code into XLA (static-graph parity), and
distributed training maps Fleet semantics onto jax.sharding meshes and ICI
collectives. See SURVEY.md for the reference layer map this mirrors.
"""
from __future__ import annotations

__version__ = "0.1.0"

import time as _time

_IMPORT_T0 = _time.perf_counter()

import jax as _jax  # noqa: E402

from .profiler import SPAN_SETUP_IMPORT as _SPAN_IMPORT  # noqa: E402
from .profiler import SetupSpan as _SetupSpan  # noqa: E402

# this import, as the set-up ledger's first phase (on a profiler's trace
# only the part from here on: a span needs jax)
_import_span = _SetupSpan(_SPAN_IMPORT, t0=_IMPORT_T0).__enter__()

# fp32 tensors must get true-fp32 matmul/conv accumulation (reference CUDA fp32
# kernel semantics). jax's DEFAULT precision lowers fp32 matmuls to bf16 passes
# on TPU; the perf path here is explicit bf16/AMP dtypes, which are unaffected.
_jax.config.update("jax_default_matmul_precision", "highest")

from .core import dtypes  # noqa: F401
from .core.device import (CPUPlace, CUDAPinnedPlace, CUDAPlace,  # noqa: F401
    NPUPlace, Place, TPUPlace,
                          device_count, get_device, is_compiled_with_cuda,
                          is_compiled_with_tpu, set_device)
from .core.dtype import (bfloat16, bool_, complex64, complex128,  # noqa: F401
                         float16, float32, float64, get_default_dtype, int8,
                         int16, int32, int64, set_default_dtype, uint8)
from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401

# CUDA-rng compat (framework.py get/set_cuda_rng_state): on TPU there is
# one program-level PRNG state; the cuda-named accessors alias it so
# checkpoint/restore code written against the reference keeps working
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
from .core.tensor import (Parameter, Tensor, enable_grad, grad,  # noqa: F401
    set_grad_enabled,
                          is_grad_enabled, no_grad)
from .framework_io import load, save  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .tensor import einsum  # noqa: F401
from .tensor.manipulation import (array_length, array_read,  # noqa: F401,E501
                                  array_write, cast, create_array, diagonal,
                                  numel, rank, reverse, scatter_, shape,
                                  shard_index, squeeze_, tolist, unsqueeze_)
from .tensor.math import add_n, tanh_  # noqa: F401
from .tensor.linalg import inverse, mv  # noqa: F401
from .utils import set_printoptions  # noqa: F401

# root-namespace parity tail (reference python/paddle/__init__.py):
# `bool`/`dtype` are the dtype-object aliases the reference exports at the
# root; create_parameter mirrors the static helper at the root the way
# fluid re-exported it; check_shape is the static-graph shape validator
from .core.dtype import bool_ as bool  # noqa: F401,A001
# paddle.dtype parity: Tensor.dtype returns numpy dtype objects in this
# build, so the dtype TYPE is numpy's — isinstance(t.dtype, paddle.dtype)
# holds, and calling it (paddle.dtype("float32")) normalizes a spec
import numpy as _np  # noqa: E402

dtype = _np.dtype


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    from .static import create_parameter as _cp
    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


def check_shape(shape):
    """framework.py check_shape: validate a shape spec before building a
    variable — entries may be ints (incl. numpy ints), -1 for unknown
    dims, or Tensors (the reference accepts Variable dims)."""
    import numbers
    from .core.tensor import Tensor as _T
    if isinstance(shape, _T):
        return
    for s in shape:
        if isinstance(s, (list, tuple)):
            check_shape(s)
        elif isinstance(s, _T):
            continue
        elif not isinstance(s, numbers.Integral) or s < -1 or s == 0:
            raise ValueError(
                f"shape entries must be positive ints, -1, or Tensors, "
                f"got {s!r}")


from . import amp  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import linalg  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from .batch import batch  # noqa: F401,E402
from . import checkpoint  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import models  # noqa: F401,E402
from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import parallel  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from .flags import get_flags, set_flags  # noqa: F401,E402
from .distributed.data_parallel import DataParallel  # noqa: F401,E402
from .hapi import Model  # noqa: F401,E402
from .nn.layer.layers import LazyGuard, ParamAttr  # noqa: F401,E402

# paddle.disable_static / enable_static parity: eager is the default and the
# "static" mode is jax.jit tracing — both are always available, so these are
# no-ops kept for API compatibility.


def disable_static(place=None):
    return None


def enable_static():
    return None


def in_dynamic_mode():
    return True


def flops(net, input_size=None, inputs=None, custom_ops=None,
          print_detail=False):
    """paddle.flops parity (hapi/dynamic_flops.py): MACs of one forward."""
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size=input_size, inputs=inputs,
                  custom_ops=custom_ops, print_detail=print_detail)


def summary(net, input_size=None, dtypes=None):
    total = sum(p.size for p in net.parameters())
    trainable = sum(p.size for p in net.parameters() if p.trainable)
    lines = [f"Total params: {total:,}", f"Trainable params: {trainable:,}"]
    report = "\n".join(lines)
    print(report)
    return {"total_params": total, "trainable_params": trainable}


# the set-up ledger (obs.goodput.CompileLedger): its jax.monitoring
# listeners are registered here, once, so that no program the process
# traces or compiles escapes it
from .obs.goodput import register_listeners as _register  # noqa: E402

_register()
_import_span.end()
