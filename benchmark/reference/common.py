"""What every family's reference shares: the loss on top of `logits`, and
the jitted entry points the jobs call (weights are arguments, never
constants of the compiled program)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _frozen(config: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _loss_fn(logits_fn, frozen_config):
    config = dict(frozen_config)

    def loss(weights, ids, labels):
        """Mean next-token cross-entropy over [B, S], one sequence at a
        time so that only one sequence's logits are alive."""
        def one(args):
            row, lab = args
            lg = logits_fn(weights, row[None], config)[0]
            lp = jax.nn.log_softmax(lg, -1)
            return -jnp.take_along_axis(lp, lab[:, None], -1)[:, 0]
        return jnp.mean(jax.lax.map(one, (ids, labels)))

    return loss


def loss(logits_fn, weights, ids, labels, config) -> float:
    return float(jax.jit(_loss_fn(logits_fn, _frozen(config)))(
        weights, jnp.asarray(ids), jnp.asarray(labels)))


def loss_and_grads(logits_fn, weights, ids, labels, config):
    """(loss, {name: d loss / d weight}) with the weights taken as float32
    values, for the gradient comparison."""
    fn = _loss_fn(logits_fn, _frozen(config))
    w32 = {k: v.astype(jnp.float32) for k, v in weights.items()}
    return jax.jit(jax.value_and_grad(fn))(
        w32, jnp.asarray(ids), jnp.asarray(labels))


@functools.lru_cache(maxsize=None)
def _logprob_fn(logits_fn, frozen_config):
    config = dict(frozen_config)

    def score(weights, ids):
        """ids [B, S] -> (log-softmax of the logits at the next token
        [B, S-1], best logit minus next token's logit [B, S-1])."""
        lg = logits_fn(weights, ids, config)[:, :-1]
        nxt = ids[:, 1:, None]
        lp = jnp.take_along_axis(jax.nn.log_softmax(lg, -1), nxt, -1)[..., 0]
        margin = jnp.max(lg, -1) - jnp.take_along_axis(lg, nxt, -1)[..., 0]
        return lp, margin

    return jax.jit(score)


def next_token_scores(logits_fn, weights, ids, config):
    return _logprob_fn(logits_fn, _frozen(config))(weights, jnp.asarray(ids))
