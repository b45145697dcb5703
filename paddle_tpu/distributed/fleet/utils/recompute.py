"""Activation recomputation (reference: fleet/utils/recompute.py:63 —
RecomputeFunction stashes RNG, re-runs forward in backward).

TPU-native: jax.checkpoint (remat) does exactly this inside a traced program, and
XLA decides placement. Eager mode gets the same semantics with a custom-vjp whose
forward saves only the inputs and whose backward re-runs the function under vjp —
RNG state is snapshotted and restored like swith_rng_state:54."""
from __future__ import annotations

import functools

import jax

from ....core import random as rnd
from ....core.tensor import Tensor, apply, no_grad

# What a replayed sublayer keeps beside its inputs: the flash forward
# kernel's output and row log-sum-exp, named in ops.attention._flash_vjp_fwd.
# The backward kernels need exactly these two, so the replay holds no second
# forward call. A layer with no flash call has nothing named and keeps nothing.
KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")


def recompute(function, *args, preserve_rng_state=True, use_reentrant=True,
              **kwargs):
    """Run `function(*args)` keeping, for its backward pass, its tensor
    inputs and the attention kernel's output and log-sum-exp (two
    `[B, S, hidden]`-sized arrays a layer where it was one: the second is
    the attention output, the log-sum-exp is `[B, heads, S]` float32);
    everything else inside is computed again. The flash forward kernel is
    the one part of a block whose replay buys nothing: it would produce the
    very bits the first run already held. Whole-loss remat
    (`strategy.recompute` without checkpoints) keeps neither: only the step's
    inputs."""
    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    rng_state = rnd.get_rng_state() if preserve_rng_state else None

    def raw(*arrays):
        if preserve_rng_state:
            saved = rnd.get_rng_state()
            rnd.set_rng_state(rng_state)
        try:
            call_args = list(args)
            for i, arr in zip(tensor_idx, arrays):
                t = Tensor(arr)
                call_args[i] = t
            with no_grad():  # tape off: jax.checkpoint/vjp own differentiation
                out = function(*call_args, **kwargs)
        finally:
            if preserve_rng_state:
                rnd.set_rng_state(saved)
        single = not isinstance(out, (tuple, list))
        outs = (out,) if single else tuple(out)
        return tuple(o.data if isinstance(o, Tensor) else o for o in outs), \
            single

    @functools.partial(jax.checkpoint, policy=KEEP_FLASH_RESIDUALS)
    def ck(*arrays):
        outs, single = raw(*arrays)
        return outs[0] if single else outs

    out = apply(ck, *[args[i] for i in tensor_idx])
    return out
