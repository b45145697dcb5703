"""The traffic generator: a function of (file, seed) and nothing else."""
import numpy as np
import pytest

from benchmark import traffic as T

MIX = {"prompt_tokens": {"dist": "loguniform", "lo": 16, "hi": 128,
                         "strata": 8},
       "output_tokens": {"dist": "uniform", "lo": 32, "hi": 96, "strata": 8},
       "prompt_ids": {"dist": "uniform"}}


def _take(stream, n):
    return [next(stream) for _ in range(n)]


def test_same_seed_same_requests_other_seed_others():
    a = _take(T.request_stream(MIX, 1000, 3), 20)
    b = _take(T.request_stream(MIX, 1000, 3), 20)
    c = _take(T.request_stream(MIX, 1000, 4), 20)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    for other in (c,):
        assert any(len(x[0]) != len(y[0]) or not np.array_equal(x[0], y[0])
                   for x, y in zip(a, other))


@pytest.mark.parametrize("spec", [
    {"dist": "loguniform", "lo": 16, "hi": 128, "strata": 8},
    {"dist": "uniform", "lo": 4, "hi": 8, "strata": 8},
    {"dist": "uniform", "lo": 7, "hi": 7, "strata": 4},
    {"dist": "uniform", "lo": 32, "hi": 96}])
def test_lengths_stay_in_range_and_strata_cover_the_quantiles(spec):
    gen = T.Lengths(spec, np.random.default_rng(0))
    xs = np.array([gen.next() for _ in range(800)])
    assert xs.min() >= spec["lo"] and xs.max() <= spec["hi"]
    k = spec.get("strata", 1)
    if k > 1:
        # every block of k draws has one draw from each k-quantile
        lo_q = T._quantile(spec, np.arange(k) / k)
        hi_q = T._quantile(spec, np.minimum(np.arange(1, k + 1) / k,
                                            1 - 1e-12))
        for block in xs.reshape(-1, k):
            ordered = np.sort(block)
            assert np.all(ordered >= lo_q) and np.all(ordered <= hi_q)


def test_check_requests_have_the_same_lengths_whatever_the_seed():
    mix = dict(MIX, check_output_tokens=24)
    one = T.check_requests(mix, 1000, 1, 4)
    two = T.check_requests(mix, 1000, 2, 4)
    assert [len(p) for p, _ in one] == [len(p) for p, _ in two] == [
        20, 34, 58, 99]
    assert [o for _, o in one] == [24, 24, 24, 24]
    assert not np.array_equal(one[0][0], two[0][0])
    uncapped = T.check_requests(MIX, 1000, 1, 4)
    assert [o for _, o in uncapped] == [88, 72, 56, 40]


def test_zipf_tokens_are_learnable_and_prompts_avoid_id_zero():
    rng = np.random.default_rng(0)
    zipf = T.Tokens({"dist": "zipf", "s": 1.0}, 50304, rng)
    flat = T.Tokens({"dist": "uniform"}, 50304, rng, first=1)
    draw = zipf.draw((4, 2049))
    # a handful of ids carry a large share of a Zipf draw, none of a flat one
    top = np.sort(np.bincount(draw.ravel(), minlength=50304))[-10:].sum()
    assert top > 0.2 * draw.size
    flat_draw = flat.draw((4, 2049))
    assert np.bincount(flat_draw.ravel()).max() < 0.01 * flat_draw.size
    assert draw.dtype == np.int32 and draw.min() >= 0 and draw.max() < 50304
    assert flat.draw((1000,)).min() >= 1
    ids, labels = next(T.training_samples(
        {"token_ids": {"dist": "zipf"}, "sequence_length": 64}, 512, 1))
    assert ids.shape == labels.shape == (64,)
    assert np.array_equal(ids[1:], labels[:-1])


@pytest.mark.parametrize("engine,wanted", [
    ({}, None),
    ({"enable_prefix_cache": False}, None),
    ({"no_such_field": 1}, "no_such_field"),
    ({"num_slots": 2}, "num_slots"),       # the mix's sizes set it already
])
def test_a_mix_sets_engine_fields_as_data(engine, wanted):
    """`"engine"` in a serve mix sets `LLMEngineConfig` fields; everything
    else stays the program's default, and a field that does not exist or
    that the mix's sizes set is an error, not a silent no-op."""
    from paddle_tpu.serving import LLMEngineConfig
    from benchmark import cells
    from benchmark.jobs.serve_closed_loop import _engine_config
    mix = dict(MIX, clients=3, slots=4, context_tokens=128, engine=engine)
    if wanted:
        with pytest.raises(cells.CellError, match=wanted):
            _engine_config(mix)
        return
    cfg, default = _engine_config(mix), LLMEngineConfig()
    assert cfg.num_slots == 4 and cfg.n_blocks == 128 // default.block_len
    assert cfg.enable_prefix_cache is engine.get("enable_prefix_cache",
                                                 default.enable_prefix_cache)
    assert cfg.prefill_chunk == default.prefill_chunk
