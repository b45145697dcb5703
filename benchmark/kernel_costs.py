"""What a kernel call must do at the least: its operations and its bytes,
from its shapes alone, and the least time a chip with the given peaks could
take for them. The yardstick for every `<kernel>_roofline` metric; kept
here so that no PR that changes a kernel can move it.
"""
from __future__ import annotations


def min_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


# Causal flash attention over [BH, S, D], bf16 in and out. One S x S x D
# matmul is 2*S*S*D operations; causality needs half of each.
#   flash_fwd      s = q k^T, o = p v                          -> 2 matmuls
#   flash_bwd_dq   s = q k^T, dp = do v^T, dq = ds k           -> 3 matmuls
#   flash_bwd_dkv  s, dp as above, dv = p^T do, dk = ds^T q    -> 4 matmuls
# (each kernel recomputes s and dp because it is handed q, k, v, do and not
# the probabilities: that is what the kernel needs for what it returns.)
# Bytes: every operand read once and every result written once.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
FLASH_ARRAYS = {"flash_fwd": 4,       # q k v -> o        (+ lse, f32 [S])
                "flash_bwd_dq": 5,    # q k v do -> dq    (+ lse, delta)
                "flash_bwd_dkv": 6}   # q k v do -> dk dv (+ lse, delta)


def flash_cost(kernel: str, bh: int, seq: int, head_dim: int,
               itemsize: int = 2) -> tuple:
    """(operations, bytes) one call of `kernel` needs."""
    flops = FLASH_MATMULS[kernel] * 2.0 * seq * seq * head_dim * bh / 2
    bytes_ = FLASH_ARRAYS[kernel] * bh * seq * head_dim * itemsize \
        + 2 * bh * seq * 4
    return flops, bytes_


def paged_cost(kv_tokens: float, active_rows: float, query_tokens: float,
               block_len: int, heads: int, kv_heads: int, head_dim: int,
               itemsize: int = 2) -> tuple:
    """(operations, bytes) of one paged-attention call over a batch of
    rows: `kv_tokens` tokens resident in the cache over `active_rows` rows,
    `query_tokens` real query positions in this call. Bytes: every resident
    page of K and of V read once per KV head (a page is read whole, so each
    row rounds up by half a page on average), queries read and outputs
    written once. Operations: q k^T and p v for every query against its
    row's mean resident length."""
    pages_tokens = kv_tokens + active_rows * (block_len - 1) / 2.0
    bytes_ = 2 * pages_tokens * kv_heads * head_dim * itemsize \
        + 2 * query_tokens * heads * head_dim * itemsize
    mean_len = kv_tokens / active_rows if active_rows else 0.0
    flops = 4.0 * query_tokens * mean_len * heads * head_dim
    return flops, bytes_


def train_flops_per_token(matmul_params: int, layers: int, hidden: int,
                          seq: int) -> float:
    """Operations the forward and backward passes need per token: 6 per
    matmul parameter, plus causal attention (q k^T and p v: 2 * 2*S*hidden
    forward, half of it under the causal mask, three times that with the
    backward pass). Recomputed operations do not count."""
    return 6.0 * matmul_params + 6.0 * layers * seq * hidden
