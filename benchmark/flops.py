"""Operations and bytes the algorithm *requires*, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can move the
yardstick.  Nothing here is measured: recomputed work (remat, the flash
backward's second QK^T) does not count, and neither do biases, norms,
`wpe` or the embedding gather.  Sizes are the GPT-2 family's
(`n_embd` E, `n_layer` L, `vocab_size` V, 4E MLP).
"""
from __future__ import annotations


def matmul_params(n_embd: int, n_layer: int, vocab_size: int) -> int:
    """Parameters that sit in a matrix multiplication on every token:
    per block QKV 3E^2 + out-proj E^2 + MLP 8E^2, plus the tied head V*E."""
    return 12 * n_embd * n_embd * n_layer + vocab_size * n_embd


def causal_attention_flops_per_token(n_embd: int, n_layer: int, seq: int,
                                     passes: int = 1) -> float:
    """QK^T and AV are 2*S*E each a token for full attention; the causal
    half is required.  ``passes`` = 1 forward, 3 forward + backward."""
    return passes * 2.0 * n_layer * n_embd * seq


def train_flops_per_token(n_embd: int, n_layer: int, vocab_size: int,
                          seq: int) -> float:
    """6 x matmul parameters + causal attention forward and backward."""
    return (6.0 * matmul_params(n_embd, n_layer, vocab_size)
            + causal_attention_flops_per_token(n_embd, n_layer, seq, 3))


def flash_train_bytes_per_token(n_embd: int, n_layer: int,
                                dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token: read
    q,k,v and write o forward; read q,k,v,o,do and write dq,dk,dv
    backward (12 E-vectors a layer)."""
    return 12.0 * n_layer * n_embd * dtype_bytes


def kv_bytes_per_token(n_embd: int, n_layer: int, dtype_bytes: int = 2) -> int:
    """K and V of one position through every layer."""
    return 2 * n_layer * n_embd * dtype_bytes


def decode_tick_bytes(n_embd: int, n_layer: int, vocab_size: int,
                      live_kv_tokens: float, weight_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """Bytes one decode tick must stream: every matmul weight once (the
    tied head included) plus the K/V of every live context position."""
    return (matmul_params(n_embd, n_layer, vocab_size) * weight_bytes
            + live_kv_tokens * kv_bytes_per_token(n_embd, n_layer,
                                                  kv_dtype_bytes))


def decode_tick_flops(n_embd: int, n_layer: int, vocab_size: int,
                      rows: int, live_kv_tokens: float) -> float:
    """2 x matmul parameters a row plus attention over the live context."""
    return (2.0 * matmul_params(n_embd, n_layer, vocab_size) * rows
            + 4.0 * n_embd * n_layer * live_kv_tokens)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound applies) on a chip of ``peak``."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
