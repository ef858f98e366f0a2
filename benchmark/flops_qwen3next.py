"""Operations and bytes a Qwen3-Next share *requires*, from shapes alone:
``layer_types`` mixes ``linear_attention`` blocks (Gated DeltaNet: one
projection to ``[q | k | v | z]``, one to ``[b | a]``, a causal depthwise
filter over ``[q | k | v]``, the gated delta rule over
``linear_num_value_heads`` states of ``d x d``, a gated norm, one
projection back) and ``full_attention`` blocks (grouped queries with an
output gate, no window); every block routes over ``routed_experts`` sparse
SwiGLUs of ``moe_intermediate_size``, of which this chip holds
``num_experts``, beside one shared SwiGLU of
``shared_expert_intermediate_size`` under a scalar gate; the head is untied,
over the vocabulary slice.  The counterpart of ``benchmark/flops_lfm2.py``
and kept with the benchmark for the same reason.  Nothing here is measured:
recomputed work (remat, the flash backward's second QK^T, the chunked
form's extra products) does not count, and neither do norms, rotary, the
filter, the gates, the router's scores or the embedding gather.

The delta rule is counted apart (``gated_delta_*``), **by the recurrence
and whatever implements it**: a token a value head forward is three
products of ``2 d d`` operations (``S^T k``, ``k (x) delta``, ``S^T q``),
backward twice that; it must read q and k at the key heads and v at the
value heads and write o (bf16) beside g and beta (float32) forward, and
backward read all of those and dO and write five cotangents.  By that count
it is bound by memory about twofold (74,496 bytes against 9.44 MFLOP a
token a layer at the published sizes).

The rows a chip must multiply in its grouped matmuls are the (token,
choice) pairs routed to the experts it holds: ``held_share`` of all
``tokens x num_experts_per_tok`` pairs, the even share without a reading,
else what the program's counter read over the window.
"""
from __future__ import annotations

from benchmark import flops_mellum2 as _geometry
from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
# the same share of the pairs, the same grouped matmuls (every layer is
# sparse) and the same grouped-query attention geometry as Mellum 2's,
# counted over the full_attention layers alone
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    _shape, expert_gemm_bytes_per_step, expert_gemm_flops_per_step,
    expert_rows_per_step, held_share, kept_keys_per_token, layer_kinds)

LINEAR, FULL = "linear_attention", "full_attention"


def linear_layers(conf: dict) -> int:
    return layer_kinds(conf).count(LINEAR)


def _linear_shape(conf: dict):
    """``(key heads, value heads, channels a head)``."""
    assert conf["linear_key_head_dim"] == conf["linear_value_head_dim"], conf
    return (int(conf["linear_num_key_heads"]),
            int(conf["linear_num_value_heads"]),
            int(conf["linear_value_head_dim"]))


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """QK^T and AV of the attention layers alone."""
    return _geometry.attention_flops_per_token(conf, seq, passes, kind=FULL)


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Keys and values move at their own 2 heads."""
    return _geometry.flash_train_bytes_per_token(conf, dtype_bytes, kind=FULL)


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: a DeltaNet
    block E*(2 Hk d + 2 Hv d) + E*2 Hv in and Hv d*E out; an attention
    block q, the gate and o 3*E*(H*D), k and v 2*E*(KV*D); every block the
    router E*routed, the shared expert 3*E*Is with its gate E, and the held
    share of the token's ``num_experts_per_tok`` experts of 3*E*I; plus the
    head over the vocabulary slice (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    Hk, Hv, d = _linear_shape(conf)
    linears = linear_layers(conf)
    n_layer = int(conf["num_hidden_layers"])
    linear = E * (2 * Hk * d + 2 * Hv * d) + E * 2 * Hv + Hv * d * E
    attn = 3 * E * H * D + 2 * E * KV * D
    sparse = (E * int(conf["routed_experts"])
              + 3 * E * int(conf["shared_expert_intermediate_size"]) + E
              + int(conf["num_experts_per_tok"]) * held_share(conf, held)
              * 3 * E * I)
    return (linears * linear + (n_layer - linears) * attn + n_layer * sparse
            + int(conf["vocab_size"]) * E)


def gated_delta_flops_per_token(conf: dict, passes: int = 1) -> float:
    """Three products of ``2 d d`` a value head a layer forward; ``passes``
    = 3 is forward + backward."""
    Hk, Hv, d = _linear_shape(conf)
    return passes * 3 * 2.0 * d * d * Hv * linear_layers(conf)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention and the delta rule forward
    + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3)
            + gated_delta_flops_per_token(conf, 3))


def gated_delta_flops_per_step(conf: dict, tokens: int) -> float:
    return gated_delta_flops_per_token(conf, 3) * tokens


def gated_delta_bytes_per_step(conf: dict, tokens: int,
                               dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the delta rules of one optimizer step, a token
    a layer: forward q, k (Hk d each) and v in, o out (Hv d each), g and
    beta (Hv float32 each) in; backward all of those and dO in, dq, dk, dv,
    dg and dbeta out.  A remat's second forward does not count."""
    Hk, Hv, d = _linear_shape(conf)
    qk, vo, gb = 2 * Hk * d * dtype_bytes, Hv * d * dtype_bytes, 2 * Hv * 4
    forward = qk + 2 * vo + gb
    backward = forward + vo + (qk + vo + gb)
    return float(forward + backward) * tokens * linear_layers(conf)
