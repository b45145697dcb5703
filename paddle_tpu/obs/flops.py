"""Analytic FLOPs / peak-FLOPs accounting (ISSUE 10 satellite).

The per-chip peak table (`peak_flops`: read by `chip_smoke.py`, which
refuses a chip that is not in it), the 6ND train-step formula with the MoE
active-param correction (what callers register with the goodput ledger's
live MFU, `obs.goodput`), and the decode-MFU formula behind the serving
ledger's gauge (`obs.serving_ledger`). The benchmark's utilization metrics
have their own cost model, which counts attention too:
`benchmark/kernel_costs.py`.

Stdlib-only: callers pass device_kind/backend strings and parameter
counts; nothing here imports jax.
"""
from __future__ import annotations

# per-chip peak bf16 FLOP/s by device_kind substring (longest match wins).
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s; "TPU v5 lite" is the device_kind libtpu 0.0.34 reports for
# that chip (chip_smoke.py fails unless the chip it runs on is a key here).
# The other rows are the same documentation's per-generation peaks; only the
# v5e row has been run against.
PEAK_BF16 = {
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}

# CPU runs are sanity-only, never MFU claims — a nominal 1 TFLOP/s keeps
# the arithmetic defined without pretending to know the host's peak
CPU_NOMINAL_FLOPS = 1e12


def peak_flops(device_kind: str, backend: str) -> float:
    """Per-chip peak bf16 FLOP/s for a jax device_kind/backend pair. An
    accelerator whose device_kind is not in PEAK_BF16 is an error, not a
    default: a utilization against a guessed peak is not a measurement."""
    if backend == "cpu":
        return CPU_NOMINAL_FLOPS
    kind = (device_kind or "").lower()
    for key in sorted(PEAK_BF16, key=len, reverse=True):
        if key in kind:
            return PEAK_BF16[key]
    raise ValueError(
        f"device_kind {device_kind!r} (backend {backend!r}) is not in "
        "paddle_tpu.obs.flops.PEAK_BF16; add its published peak with its "
        "source before reporting a utilization on it")


def train_flops_per_step(n_params: int, tokens_per_step: int,
                         expert_params: int = 0, moe_top_k: int = 2,
                         moe_num_experts: int = 0) -> float:
    """6ND fwd+bwd FLOPs for one dense-transformer train step.

    MoE models count ACTIVE params: each token runs top_k of E experts,
    so expert weights contribute top_k/E of their size (plain 6ND would
    overstate the work and inflate MFU). Pass expert_params (all MoE
    expert weights, gate excluded) and the router config to apply the
    correction; with moe_num_experts == 0 this is exactly 6ND.
    """
    n_active = int(n_params)
    if moe_num_experts:
        n_active = (n_params - expert_params
                    + expert_params * moe_top_k // moe_num_experts)
    return 6.0 * n_active * tokens_per_step


def lora_decode_flops_per_token(rank: int, target_dims) -> float:
    """Extra forward FLOPs per token for one LoRA-adapted row (ISSUE 20).

    Each adapted site adds two skinny matmuls to the base projection:
    ``x[in] @ A.T[in, r]`` then ``z[r] @ B.T[r, out]`` — `2*r*(in+out)`
    FLOPs (2 per multiply-add, as the 2N decode formula counts).
    `target_dims` is an iterable of per-site `(in_features,
    out_features)` pairs covering EVERY adapted site of EVERY layer
    (i.e. `num_layers * len(targets)` entries — the caller flattens,
    mirroring how the MoE correction counts active params, not per-layer
    shorthand). The sizing math of docs/serving.md's multi-LoRA section."""
    r = int(rank)
    return float(sum(2.0 * r * (int(i) + int(o)) for i, o in target_dims))


def decode_mfu(flops_per_token: float, tokens: int, seconds: float,
               peak_flops_total: float):
    """Effective decode MFU: achieved decode FLOP/s over peak.

    The serving ledger's live gauge (ISSUE 11); `flops_per_token` is 2N
    for a dense decoder (forward only, KV-cache decode). Returns None when
    any input is degenerate (no tokens, no measured seconds, no registered
    peak)."""
    if not (flops_per_token and tokens and seconds and peak_flops_total):
        return None
    if seconds <= 0 or peak_flops_total <= 0:
        return None
    return flops_per_token * tokens / seconds / peak_flops_total
