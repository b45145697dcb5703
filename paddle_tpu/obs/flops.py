"""Analytic FLOPs / peak-FLOPs accounting (ISSUE 10 satellite).

ONE source of truth for the model-FLOPs arithmetic that used to live
inline in bench.py: the per-chip peak table, the 6ND train-step formula
(with MoE active-param correction), the conv MAC→FLOP convention, and
the 2ND decode formula. bench.py's offline MFU and the goodput ledger's
live MFU (obs.goodput) both call these helpers, so the two numbers can
never diverge by formula — only by what they measured.

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


def conv_train_flops_per_step(fwd_mac_flops: float, batch: int) -> float:
    """Conv-net train-step FLOPs from measured forward MACs.

    paddle.flops counts MACs (one multiply-add = 1); true FLOPs are 2x
    that, and fwd+bwd ~ 3x the forward.
    """
    return 3.0 * (2.0 * float(fwd_mac_flops)) * batch


def decode_flops_per_token(n_params: int) -> float:
    """2N forward-only FLOPs per generated token (KV-cache decode)."""
    return 2.0 * n_params


def lora_decode_flops_per_token(rank: int, target_dims) -> float:
    """Extra forward FLOPs per token for one LoRA-adapted row (ISSUE 20).

    Each adapted site adds two skinny matmuls to the base projection:
    ``x[in] @ A.T[in, r]`` then ``z[r] @ B.T[r, out]`` — `2*r*(in+out)`
    FLOPs under the same 2·MAC convention as `decode_flops_per_token`.
    `target_dims` is an iterable of per-site `(in_features,
    out_features)` pairs covering EVERY adapted site of EVERY layer
    (i.e. `num_layers * len(targets)` entries — the caller flattens,
    mirroring how the MoE correction counts active params, not per-layer
    shorthand). The adapter-overhead analytics in bench.py's lora phase
    and docs sizing math both call this, so the bound can never diverge
    from the measured `llm_lora_overhead_pct` by formula."""
    r = int(rank)
    return float(sum(2.0 * r * (int(i) + int(o)) for i, o in target_dims))


def decode_mfu(flops_per_token: float, tokens: int, seconds: float,
               peak_flops_total: float):
    """Effective decode MFU: achieved decode FLOP/s over peak.

    ONE formula for bench.py's offline row and the serving ledger's live
    gauge (ISSUE 11), mirroring how train MFU shares
    `train_flops_per_step`. Returns None when any input is degenerate
    (no tokens, no measured seconds, no registered peak)."""
    if not (flops_per_token and tokens and seconds and peak_flops_total):
        return None
    if seconds <= 0 or peak_flops_total <= 0:
        return None
    return flops_per_token * tokens / seconds / peak_flops_total
