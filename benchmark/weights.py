"""Weights from the seed: every leaf of a model, on the device, in one
jitted call, in the type it is served or trained in.

The program's constructors draw their own initial values (float32, leaf by
leaf); the benchmark replaces them so that the weights are a function of
`--seed` alone and the plain reference can be given the very same arrays.
The rule is GPT-2's: matrices and embeddings N(0, 0.02), norm scales 1,
biases 0.
"""
from __future__ import annotations

import functools

STD = 0.02


def leaf_kind(name: str, shape) -> str:
    if name.endswith("bias"):
        return "zeros"
    if len(shape) == 1:           # LayerNorm / RMSNorm scale
        return "ones"
    return "normal"


@functools.lru_cache(maxsize=None)
def _maker(spec: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(spec):
            kind = leaf_kind(name, shape)
            if kind == "normal":
                leaf = STD * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                leaf = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                jnp.float32)
            out[name] = leaf.astype(dtype)
        return out

    return jax.jit(make)


def seeded_weights(shapes: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """{name: array} for {name: shape}, drawn from `seed`."""
    import jax
    spec = tuple((k, tuple(int(d) for d in v))
                 for k, v in sorted(shapes.items()))
    return _maker(spec, dtype)(jax.random.PRNGKey(seed))


def load_into(model, weights: dict):
    """Rebind the program's model to `weights` (by structured name); the
    arrays the constructor made are released as they are replaced."""
    named = dict(model.named_parameters())
    if set(named) != set(weights):
        raise KeyError(f"weight names differ: "
                       f"{sorted(set(named) ^ set(weights))[:8]}")
    for k, p in named.items():
        if tuple(p.shape) != tuple(weights[k].shape):
            raise ValueError(f"{k}: {p.shape} vs {weights[k].shape}")
        p.data = weights[k]
