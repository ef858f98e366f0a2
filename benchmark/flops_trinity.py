"""Operations and bytes a Trinity (AFMoE) share *requires*, from shapes
alone: ``num_dense_layers`` leading blocks with a dense SwiGLU of
``intermediate_size``, then blocks with a router over ``routed_experts``,
``num_shared_experts`` shared SwiGLUs of ``moe_intermediate_size`` that
every token runs, and sparse ones of the same width of which this chip
holds ``num_experts``; attention with a fourth projection (the output
gate), grouped queries, windowed or full by ``layer_types``.  The
counterpart of ``benchmark/flops_mellum2.py`` and kept with the benchmark
for the same reason.  Nothing here is measured: recomputed work (remat, the
flash backward's second QK^T) does not count, and neither do norms, rotary,
the gate's sigmoid, the router's scores or the embedding gather.

The grouped matmul is the HELD ROUTED experts' alone: the shared expert and
the dense layer are dense matmuls, counted in the step and not in
``expert_gemm_*``.  The rows a chip must multiply there are the (token,
choice) pairs routed to the experts it holds: ``held_share`` of all
``tokens x num_experts_per_tok`` pairs, the even share ``num_experts /
routed_experts`` without a reading, else what the program's counter read
over the window (``moe_held_pair_pct`` / 100).
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
# the same attention geometry as Mellum 2's (grouped queries at head_dim
# apart from hidden / heads, windowed or full by layer_types; the output
# gate's product is outside the kernels) and the same share of the pairs
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    attention_flops_per_token, causal_attention_flops_per_token,
    expert_rows_per_step, flash_train_bytes_per_token, held_share,
    kept_keys_per_token, layer_kinds)

SLIDING, FULL = "sliding_attention", "full_attention"


def _shape(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["moe_intermediate_size"]))


def sparse_layers(conf: dict) -> int:
    return int(conf["num_hidden_layers"]) - int(conf["num_dense_layers"])


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: per block q,
    the gate and o 3*E*(H*D), k and v 2*E*(KV*D); a dense block 3*E*F; a
    sparse one the router E*routed, the shared experts 3*E*I each and the
    held share of the token's ``num_experts_per_tok`` experts of 3*E*I;
    plus the head over the vocabulary slice (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    attn = 3 * E * H * D + 2 * E * KV * D
    dense = 3 * E * int(conf["intermediate_size"])
    sparse = (E * int(conf["routed_experts"])
              + (int(conf["num_shared_experts"])
                 + int(conf["num_experts_per_tok"]) * held_share(conf, held))
              * 3 * E * I)
    return (int(conf["num_hidden_layers"]) * attn
            + int(conf["num_dense_layers"]) * dense
            + sparse_layers(conf) * sparse + int(conf["vocab_size"]) * E)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3))


def expert_gemm_flops_per_step(conf: dict, tokens: int, held=None) -> float:
    """The grouped matmuls of one optimizer step: 3 matrices (gate, up,
    down) x 3 passes (forward, d-rows, d-weights) of 2*rows*E*I a sparse
    layer, the held routed experts alone."""
    E, H, KV, D, I = _shape(conf)
    return (9.0 * 2.0 * expert_rows_per_step(conf, tokens, held) * E * I
            * sparse_layers(conf))


def expert_gemm_bytes_per_step(conf: dict, tokens: int, dtype_bytes: int = 2,
                               held=None) -> float:
    """Least HBM traffic of those 9 grouped matmuls a sparse layer: each
    reads or writes every held expert's matrix once (num_experts*E*I) and
    moves the rows once on the wide side (rows*E) and once on the narrow
    (rows*I)."""
    E, H, KV, D, I = _shape(conf)
    one = (int(conf["num_experts"]) * E * I
           + expert_rows_per_step(conf, tokens, held) * (E + I))
    return 9.0 * one * dtype_bytes * sparse_layers(conf)
