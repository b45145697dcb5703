"""Laguna-XS.2's block on the serving path: query heads that differ by
layer type (`LlamaConfig.num_attention_heads_per_layer`: groups of 6 on
full layers, of 8 on window layers, over the same KV heads), a sigmoid gate
a head on the attention output, half a head turned by YaRN on full layers
and the whole head plainly on window layers, a dense layer 0 and then a
sigmoid router over small experts beside a shared one. The uncached
forward, the cached forward through the ring and `LLMEngine` against the
benchmark's plain reference (`benchmark/reference/laguna.py`, logits) and
against `generate()` (bits); the paged kernels interpreted at both group
sizes; the new `LlamaConfig` fields at their defaults. CPU, float32, tiny
widths: hidden 48, heads of 16, 12 / 16 query heads over 2 KV heads, full +
dense, sliding x 3, full, window 32, 16 experts of width 32 (4 per token) +
a shared one of 32, a dense layer of 96, YaRN over 64 original positions.

Initial values: q and k projections N(0, 0.2), the routers and the gates'
projections N(0, 0.3), the other matrices N(0, 0.1), so that attention is
far from uniform, a gate is far from one half and a router's choice depends
on the token: each mechanism left out of the reference moves the logits by
0.1 to 3 against a tolerance of 1e-4 (`test_each_mechanism_carries_the_
logits`).
"""
import dataclasses
import math
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.generation import generate, make_decoder_fns
from paddle_tpu.models.llama import (DENSE, FULL, SLIDING, SPARSE,
                                     LlamaConfig, LlamaForCausalLM,
                                     SharedExpertMoE, rope_inv_freq)
from paddle_tpu.nn.layer.moe import DroplessMoE
from paddle_tpu.serving.llm.kv_pool import SlotPagedKVPool

from benchmark.reference import laguna as ref

VOCAB, WINDOW, CHUNK = 128, 32, 16
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 64, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}
HEADS = [12, 16, 16, 16, 12]
KINDS = [FULL, SLIDING, SLIDING, SLIDING, FULL]
MLPS = [DENSE] + [SPARSE] * 4
TINY = dict(vocab_size=VOCAB, hidden_size=48, intermediate_size=96,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            num_hidden_layers=5, num_attention_heads=12,
            num_attention_heads_per_layer=HEADS, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=512, rms_norm_eps=1e-6,
            layer_types=KINDS, mlp_layer_types=MLPS, sliding_window=WINDOW,
            rope_parameters=ROPE, num_experts=16,
            num_experts_per_tok=4, norm_topk_prob=True,
            router_scoring="sigmoid", routed_scaling_factor=2.5,
            attn_output_gate=True)
# the same sizes as the reference reads them (the benchmark's keys)
REF = dict(num_hidden_layers=5, num_attention_heads_per_layer=HEADS,
           num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
           layer_types=KINDS, mlp_layer_types=MLPS, rope_parameters=ROPE,
           sliding_window=WINDOW, num_experts_per_tok=4,
           moe_routed_scaling_factor=2.5,
           shared_expert_intermediate_size=32, gating=True,
           router_scoring="sigmoid")


def _seed_weights(model, seed=5):
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if len(p.shape) < 2:
            continue                                  # norm scales stay 1
        std = 0.2 if ("q_proj" in name or "k_proj" in name) else \
            0.3 if ("router" in name or "g_proj" in name) else 0.1
        p.data = jnp.asarray(rng.normal(0.0, std, p.shape), p.data.dtype)
    return model


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(0)
    model = _seed_weights(LlamaForCausalLM(LlamaConfig(**TINY)))
    model.eval()
    return model


def _weights(model):
    return {k: p.data for k, p in model.named_parameters()}


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32) for n in lengths]


def _engine(model, block_len=8, num_slots=3, tokens=256, **kw):
    cfg = serving.LLMEngineConfig(
        num_slots=num_slots, block_len=block_len,
        n_blocks=tokens // block_len, max_new_tokens=48,
        max_queue_depth=128, **kw)
    return serving.LLMEngine(model, cfg, clock=serving.SimClock())


def _drain(eng):
    while eng.has_work():
        eng.pump()


# ---- the model against the plain reference ----

def test_a_layers_shapes_follow_its_type(tiny):
    for layer, heads, kind, mlp in zip(tiny.llama.layers, HEADS, KINDS, MLPS):
        attn = layer.self_attn
        assert attn.num_heads == heads and attn.num_kv_heads == 2
        assert tuple(attn.q_proj.weight.shape) == (48, heads * 16)
        assert tuple(attn.o_proj.weight.shape) == (heads * 16, 48)
        assert tuple(attn.g_proj.weight.shape) == (48, heads)
        assert tuple(attn.k_proj.weight.shape) == (48, 32)
        assert attn.window == (WINDOW if kind == SLIDING else None)
        assert attn.rotary_dim == (16 if kind == SLIDING else 8)
        assert attn.rope["rope_type"] == (
            "default" if kind == SLIDING else "yarn")
        assert layer.sparse == (mlp == SPARSE)
        if mlp == DENSE:
            assert tuple(layer.mlp.gate_proj.weight.shape) == (48, 96)
        else:
            assert isinstance(layer.mlp, SharedExpertMoE)
            assert isinstance(layer.mlp.experts, DroplessMoE)
            assert layer.mlp.experts.router == dict(
                scoring="sigmoid", n_group=1, topk_group=1, routed_scale=2.5)
            assert tuple(layer.mlp.experts.w_gate.shape) == (16, 48, 32)
            assert tuple(layer.mlp.shared_experts.down_proj.weight.shape) \
                == (32, 48)
    assert tiny.query_heads_by_layer() == HEADS
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        LlamaConfig(num_hidden_layers=2, num_key_value_heads=2,
                    num_attention_heads_per_layer=[4, 3])
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        LlamaConfig(num_hidden_layers=2, num_key_value_heads=2,
                    num_attention_heads_per_layer=[4])
    with pytest.raises(ValueError, match="mlp_layer_types"):
        LlamaConfig(num_hidden_layers=2, mlp_layer_types=[DENSE, "moe"])


def test_yarn_over_the_turned_dimensions_against_hand_computed_values():
    """Laguna-XS.2's own parameters: a head of 128 of which 64 dimensions
    turn, theta 500,000, factor 64 over 4,096 positions, beta_fast 64. Over
    dimension 64: low = floor(64 ln(4096 / (64 * 2 pi)) / (2 ln 5e5)) =
    floor(5.66) = 5, high = ceil(64 ln(4096 / (2 pi)) / (2 ln 5e5)) =
    ceil(15.80) = 16: dimensions up to 5 keep their frequency, from 16 on
    take a sixty-fourth, a ramp of elevenths between. Over the head's 128
    the edges would be 11 and 32."""
    yarn = {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
            "original_max_position_embeddings": 4096, "beta_fast": 64,
            "beta_slow": 1, "attention_factor": 1.4158883083359672}
    base = 500000.0 ** (-np.arange(32) / 32.0)
    for compute in (rope_inv_freq, ref.inv_freq):
        inv, factor = compute(64, yarn)
        inv = np.asarray(inv, np.float64)
        assert inv.shape == (32,) and factor == 1.4158883083359672
        np.testing.assert_allclose(inv[:6], base[:6], rtol=2e-6)
        np.testing.assert_allclose(inv[16:], base[16:] / 64, rtol=2e-6)
        np.testing.assert_allclose(
            inv[10], base[10] * (1 - 5 / 11 + 5 / 11 / 64), rtol=2e-6)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)
    cfg = LlamaConfig(**{**TINY, "head_dim": 128, "hidden_size": 256,
                         "rope_parameters": {**ROPE, FULL: {
                             **yarn, "partial_rotary_factor": 0.5}}})
    attn = LlamaForCausalLM(cfg).llama.layers[0].self_attn
    assert attn.rotary_dim == 64


def test_uncached_forward_equals_reference(tiny):
    ids = np.stack(_prompts([120, 120], seed=2))
    got = np.asarray(tiny(paddle.to_tensor(ids)).data)
    want = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids), REF))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


FAULTS = {
    "no gate": {"gating": False},
    "the whole head turned on full layers": {"rope_parameters": {
        **ROPE, FULL: {**ROPE[FULL], "partial_rotary_factor": 1.0}}},
    "plain rotary on full layers": {"rope_parameters": {
        **ROPE, FULL: {**ROPE[SLIDING], "partial_rotary_factor": 0.5}}},
    "head groups swapped": {"gqa_group": {FULL: 8, SLIDING: 6}},
    "no window": {"sliding_window": None},
    "no shared expert": {"shared_expert_intermediate_size": 0},
    "no routed scaling factor": {"moe_routed_scaling_factor": 1.0},
    "softmax scores": {"router_scoring": "softmax"},
    "the router in bfloat16": {"router_dtype": "bfloat16"},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_mechanism_carries_the_logits(tiny, fault):
    """What the comparisons would miss if a mechanism did nothing at these
    weights: the reference with it taken out is far from the model (the
    benchmark's controls take the same ones out on the chip)."""
    ids = np.stack(_prompts([120], seed=3))
    got = np.asarray(tiny(paddle.to_tensor(ids)).data)
    off = np.asarray(ref.logits(_weights(tiny), jnp.asarray(ids),
                                {**REF, **FAULTS[fault]}))
    # (a router in bfloat16 moves a gate by a rounding, not a mechanism:
    # ten tolerances where no position's choice flips, as here)
    floor = 1e-3 if fault == "the router in bfloat16" else 0.1
    assert np.abs(got - off).max() > floor
    if fault == "no window":        # behind the window's reach it agrees
        assert np.abs(got - off)[:, :WINDOW].max() < 1e-4


# ---- the cached forward through the ring: logits ----

@pytest.mark.parametrize("block_len", [8, 16])
def test_chunked_prefill_and_decode_through_the_ring_equal_reference(
        tiny, block_len):
    """The engine's own cached forward (`make_decoder_fns`' prefill with
    the pool's slabs and its `paged` operand) over prompts shorter than the
    window, crossing it, and twice round the ring: chunks of 16, then one
    token at a time, logits at every position against the reference's full
    forward. Both walks run at their own group size in every step."""
    lengths = [20, 43, 130]
    decode = 12
    pool = SlotPagedKVPool(tiny.init_cache, len(lengths), block_len,
                           160 // block_len, pad_tokens=CHUNK)
    assert pool.layer_kinds == ["paged", "window", "window", "window",
                                "paged"]
    assert pool.ring_len == 48
    # 2 KV heads on both kinds: one slab shape a kind, whatever the heads
    assert {k.shape[1] for k, _ in pool.slabs} == {2}
    params, prefill, _ = make_decoder_fns(tiny)
    seqs = _prompts([n + decode for n in lengths], seed=4)
    paged = jax.jit(lambda toks, pos, adv, slabs: prefill(
        params, toks, slabs, pos,
        paged=pool.view(pool.device_block_table(), pos + adv)))
    got = [[] for _ in lengths]
    done = np.zeros(len(lengths), np.int32)
    slabs = pool.slabs
    while (done < [len(s) for s in seqs]).any():
        toks = np.zeros((len(lengths), CHUNK), np.int32)
        adv = np.zeros(len(lengths), np.int32)
        pos = np.full(len(lengths), pool.capacity, np.int32)   # parked
        for b, seq in enumerate(seqs):
            if done[b] >= len(seq):
                continue
            n = min(CHUNK, lengths[b] - done[b]) \
                if done[b] < lengths[b] else 1
            toks[b, :n], adv[b], pos[b] = seq[done[b]:done[b] + n], n, \
                done[b]
        logits, slabs = paged(jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray(adv), slabs)
        for b in range(len(lengths)):
            got[b].append(np.asarray(logits[b, :adv[b]]))
            done[b] += adv[b]
    for b, seq in enumerate(seqs):
        want = np.asarray(ref.logits(_weights(tiny),
                                     jnp.asarray(seq[None]), REF))[0]
        np.testing.assert_allclose(np.concatenate(got[b]), want,
                                   atol=1e-4, rtol=0)


# ---- LLMEngine ----

@pytest.mark.parametrize("block_len,num_slots", [(8, 3), (16, 3), (8, 40)])
def test_engine_streams_equal_generate_and_the_reference(tiny, block_len,
                                                         num_slots):
    """Prompts below, across and twice round the ring through the engine's
    default path (unpacked step, and packed at 40 slots), mixed prefill
    and decode rows, slots reused: every stream is `generate()`'s
    (bit-identical at its block size, 8), and every token's
    log-probability the reference's. The engine has no branch for the
    model: the pool's kinds and the step are a window model's."""
    eng = _engine(tiny, block_len, num_slots)
    assert eng.pool.layer_kinds == ["paged"] + ["window"] * 3 + ["paged"]
    assert eng.enable_prefix_cache is False      # a ring: the engine's rule
    assert eng.step_tokens == min(num_slots * 16, 512)
    prompts = _prompts([9, 20, 43, 130, 31, 97, 48, 64], seed=6)
    handles = [eng.submit(p, max_new_tokens=24, logprobs=True)
               for p in prompts]
    _drain(eng)
    w = _weights(tiny)
    for p, h in zip(prompts, handles):
        out = np.asarray(h.result(timeout=10))
        want = np.asarray(generate(tiny, p[None],
                                   max_new_tokens=24).data)[0, len(p):]
        if block_len == 8:
            assert np.array_equal(out, want), len(p)
        ids = np.concatenate([p, out])
        lg = np.asarray(ref.logits(w, jnp.asarray(ids[None]), REF))[0]
        lp = np.asarray(jax.nn.log_softmax(lg, -1))
        at = np.arange(len(p) - 1, len(ids) - 1)
        np.testing.assert_allclose(
            np.asarray(h.logprobs_so_far()), lp[at, out], atol=1e-4, rtol=0)
        if block_len != 8:          # the same token unless a near-tie
            assert (lg[at].max(-1) - lg[at, out]).max() < 1e-3
    assert eng.pool.check_balance()
    snap = eng.metrics.snapshot()
    assert snap["rows_discarded"] == 0
    assert eng._step()._cache_size() == 1
    # query-head rows by walk: slots x chunk positions a layer and step, 12
    # heads on each of the 2 full layers, 16 on each of the 3 window layers
    positions = num_slots * 16 * snap["unified_steps"]
    assert snap["attn_query_positions"] == 5 * positions
    assert snap["attn_query_heads_full"] == 2 * 12 * positions
    assert snap["attn_query_heads_window"] == 3 * 16 * positions
    assert "pdtpu_llm_attn_query_heads_window_total" in eng.metrics.render()
    # the four sparse layers' table; the dense layer has no row
    table = eng.moe_expert_tokens()
    assert table.shape == (4, 16)
    assert table.sum() == eng.metrics.snapshot()["moe_assignments"] > 0


# ---- the paged kernels at the two group sizes ----

@pytest.mark.parametrize("walk,heads", [("full", 12), ("window-ring", 16)])
def test_interpreted_kernels_at_both_group_sizes_match_the_scan(walk, heads):
    """A mixed step through the kernel (interpreted) at 6 and at 8 query
    heads a KV head: one-column rows (the one-column body, `fold` rows a
    head), chunk rows, a verify-width row, a free row; every live position
    within the documented tolerance of the scan, a one-column row's dead
    columns zeros. Fold 6 fills 6 of a float32 sublane tile's 8 rows."""
    from paddle_tpu.ops import pallas_mode
    from paddle_tpu.ops.paged_attention import (WINDOW_KERNEL,
                                                ragged_paged_attention)
    rng = np.random.RandomState(7)
    Tq, bl, D, Hkv = 16, 16, 64, 2
    ends = np.array([1, 48, 130, 0, 77, 150], np.int32)
    adv = np.array([1, 16, 1, 0, 3, 16], np.int32)
    B, nb = len(ends), 10
    q = jnp.asarray(rng.standard_normal((B, heads, Tq, D)), jnp.float32)
    kw = dict(block_len=bl)
    if walk == "window-ring":
        ring = 4 * bl                              # 64 >= window 40 + 16
        logical = rng.standard_normal((2, B, Hkv, nb * bl, D))
        k, v = (np.full((B, Hkv, ring + Tq, D), 1e3, np.float32)
                for _ in "kv")
        for slab, x in ((k, logical[0]), (v, logical[1])):
            for b in range(B):
                for p in range(ends[b]):
                    slab[b, :, p % ring] = x[b, :, p]
        k, v, table = jnp.asarray(k), jnp.asarray(v), None
        kw.update(pages_per_row=4, window=40)
    else:
        k = jnp.asarray(rng.standard_normal((B, Hkv, nb * bl, D)),
                        jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, Hkv, nb * bl, D)),
                        jnp.float32)
        table = rng.permutation(B * nb).astype(np.int32).reshape(B, nb)
        kw.update(pages_per_row=nb)

    def call(impl):
        return np.asarray(ragged_paged_attention(
            q, k, v, table, ends, ends - adv, impl=impl, **kw))

    pallas_mode.KERNEL_TILINGS.clear()
    got, want = call("pallas"), call("scan")
    (kernel, tiling), = pallas_mode.KERNEL_TILINGS
    assert kernel == (WINDOW_KERNEL if walk == "window-ring"
                      else "paged_attention")
    assert dict(tiling)["one_column_rows"] == heads // Hkv
    assert dict(tiling)["rows"] == heads // Hkv * Tq
    assert np.isfinite(got).all()
    for b in range(B):
        live = got[b, :, :adv[b]]
        assert np.abs(live - want[b, :, :adv[b]]).max(initial=0) <= 1e-6, b
        if adv[b] == 1:
            assert live.any() and not got[b, :, 1:].any(), b
    assert not got[adv == 0].any()


# ---- what is there stays what it was ----

def test_new_fields_at_their_defaults_build_the_models_they_built():
    """Mistral's, OLMoE's and Mellum's tiny models (the benchmark's test
    configurations through their families): every parameter's name and
    shape as before this file's fields existed, no gate, the whole head
    turned, every FFN of one kind, the routers' plain softmax."""
    from benchmark import cells
    from benchmark.tests.test_cells import CELLS
    fresh = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
    assert (fresh["num_attention_heads_per_layer"], fresh["mlp_layer_types"],
            fresh["moe_intermediate_size"]) == (None, None, None)
    assert (fresh["attn_output_gate"],
            fresh["shared_expert_intermediate_size"],
            fresh["router_scoring"], fresh["routed_scaling_factor"]) \
        == (False, 0, "softmax", 1.0)
    attention = ["self_attn.q_proj.weight", "self_attn.k_proj.weight",
                 "self_attn.v_proj.weight", "self_attn.o_proj.weight"]
    norms = {"input_layernorm.weight", "post_attention_layernorm.weight"}
    dense = ["mlp.gate_proj.weight", "mlp.up_proj.weight",
             "mlp.down_proj.weight"]
    experts = ["mlp.router_weight", "mlp.w_gate", "mlp.w_up", "mlp.w_down"]
    # layer 0 of each, leaf by leaf, as the parent commit built it
    want = {
        "tiny.serve": dict(zip(            # GQA 4 / 2 heads of 32, dense
            attention + dense,
            [(128, 128), (128, 64), (128, 64), (128, 128), (128, 352),
             (128, 352), (352, 128)])),
        "tiny.serve-moe": dict(zip(        # MHA, q/k norm, 8 experts of 32
            attention + experts + ["self_attn.q_norm.weight",
                                   "self_attn.k_norm.weight"],
            [(64, 64)] * 4 + [(64, 8), (8, 64, 32), (8, 64, 32),
                              (8, 32, 64), (64,), (64,)])),
        "tiny.mellum-serve": dict(zip(     # heads of 16, 4 of 8 held
            attention + experts,
            [(48, 64), (48, 32), (48, 32), (64, 48), (48, 8), (4, 48, 32),
             (4, 48, 32), (4, 32, 48)])),
    }
    for cell, layer0 in want.items():
        config = cells.load_cell(cell, CELLS)["config_data"]
        model = cells.family_module(config).build(config)
        shapes = {k: tuple(p.shape) for k, p in model.named_parameters()}
        hidden = model.config.hidden_size
        for i in range(model.config.num_hidden_layers):
            got = {k.split(".", 3)[-1]: v for k, v in shapes.items()
                   if k.startswith(f"llama.layers.{i}.")}
            assert got == {**layer0, **{n: (hidden,) for n in norms}}, \
                (cell, i)
        sparse = "mlp.router_weight" in layer0
        for layer in model.llama.layers:
            attn = layer.self_attn
            assert attn.num_heads == model.config.num_attention_heads
            assert attn.rotary_dim == attn.head_dim
            assert not hasattr(attn, "g_proj")
            if sparse:
                assert isinstance(layer.mlp, DroplessMoE)
                assert layer.mlp.router == dict(
                    scoring="softmax", n_group=1, topk_group=1,
                    routed_scale=1.0)


@pytest.mark.parametrize("cell", [
    "tiny.serve", "tiny.serve-ssm", "tiny.jamba-serve", "tiny.glm-serve",
    "tiny.train", "tiny.laguna.serve"])
def test_every_served_model_class_states_its_query_heads(cell):
    """The engine's `attn_query_heads_*` counters ask every model the same
    question: one entry an `init_cache` entry, a multiple of nothing but
    positive where the layer attends, 0 exactly where it keeps a
    recurrence's state. So a 0 in a counter means no layer of that kind."""
    from benchmark import cells
    from benchmark.tests.test_cells import CELLS
    from paddle_tpu.models.generation import RecurrentState
    config = cells.load_cell(cell, CELLS)["config_data"]
    model = cells.family_module(config).build(config)
    heads = model.query_heads_by_layer()
    entries = model.init_cache(1, 16, dtype=jnp.float32)
    assert len(heads) == len(entries)
    assert [h == 0 for h in heads] \
        == [isinstance(e, RecurrentState) for e in entries]


def test_nothing_of_this_model_is_on_the_packages_import_path():
    """The model needed no module of its own inside the package: what it
    forced lives in `models/llama.py`, which the package imported before.
    So the package's import path is the parent's: the shared-expert FFN
    that `models/deepseek.py` now takes from `models/llama.py` brought no
    import the other way, and the benchmark's family and reference load
    when a Laguna configuration is asked for."""
    code = ("import sys, paddle_tpu, paddle_tpu.serving, paddle_tpu.models\n"
            "bad = [m for m in ('paddle_tpu.models.deepseek', "
            "'paddle_tpu.nn.layer.hyper_connection', "
            "'benchmark.families.laguna', 'benchmark.reference.laguna', "
            "'benchmark') if m in sys.modules]\n"
            "print(bad); sys.exit(1 if bad else 0)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout + r.stderr[-800:]
