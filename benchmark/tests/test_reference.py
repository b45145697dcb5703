"""The plain references against the program at tiny widths on the CPU, in
float32: logits, loss and (for both families) gradients. On the chip the
jobs compare at the published widths (loss, log-probabilities)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, weights as W
from benchmark.reference import common as ref_common
from benchmark.tests.test_cells import CELLS


def _setup(cell_name):
    import paddle_tpu as paddle  # noqa: F401
    cell = cells.load_cell(cell_name, CELLS)
    config = cell["config_data"]
    model = cells.family_module(config).build(config, recompute=False)
    shapes = {k: tuple(p.shape) for k, p in model.named_parameters()}
    weights = W.seeded_weights(shapes, 11, config["dtype"])
    W.load_into(model, weights)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, config["vocab_size"], (2, 48)).astype(np.int32)
    return config, model, weights, ids, np.roll(ids, -1, 1)


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.serve"])
def test_logits_loss_and_gradients_match_the_program(cell):
    import paddle_tpu as paddle
    config, model, weights, ids, labels = _setup(cell)
    ref = cells.reference_module(config)
    model.eval()
    got = np.asarray(model(paddle.to_tensor(ids)).data)
    want = np.asarray(ref.logits(weights, jnp.asarray(ids), config))
    # float32 on both sides; what differs is the order of summation
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())

    model.train()

    def program_loss(params):
        out, _ = model.functional_call_with_state(
            params, {}, jnp.asarray(ids), jnp.asarray(labels))
        return out.astype(jnp.float32)

    loss_p, grads_p = jax.value_and_grad(program_loss)(dict(weights))
    loss_r, grads_r = ref_common.loss_and_grads(ref.logits, weights, ids,
                                                labels, config)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5
    assert set(grads_p) == set(grads_r)
    for name in grads_r:
        scale = float(jnp.abs(grads_r[name]).max()) + 1e-12
        err = float(jnp.abs(grads_p[name] - grads_r[name]).max())
        assert err <= 1e-3 * scale + 1e-7, (name, err, scale)


def test_seeded_weights_are_a_function_of_the_seed():
    shapes = {"a.weight": (8, 16), "a.bias": (16,), "norm.weight": (8,)}
    one, two = W.seeded_weights(shapes, 3), W.seeded_weights(shapes, 3)
    other = W.seeded_weights(shapes, 4)
    assert all(np.array_equal(one[k], two[k]) for k in shapes)
    assert not np.array_equal(one["a.weight"], other["a.weight"])
    assert float(jnp.abs(one["a.bias"]).max()) == 0.0
    assert float(one["norm.weight"].astype(jnp.float32).min()) == 1.0
    assert one["a.weight"].dtype == jnp.bfloat16
