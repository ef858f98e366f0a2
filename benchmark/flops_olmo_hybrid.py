"""Operations and bytes an Olmo-Hybrid stage *requires*, from shapes alone:
``layer_types`` mixes ``linear_attention`` blocks (Gated DeltaNet: one
projection to ``[q | k | v | z]``, one to ``[b | a]``, a causal depthwise
filter over ``[q | k | v]``, the gated delta rule over
``linear_num_value_heads`` states of ``linear_key_head_dim x
linear_value_head_dim``, a gated norm, one projection back) and
``full_attention`` blocks (30 heads on 30 key-value heads, no rotation, no
gate); every block a dense SwiGLU of ``intermediate_size``; the head is
untied, over the vocabulary slice.  The counterpart of
``benchmark/flops_qwen3next.py`` (which asserts square states, rightly for
its model, and is left as it is) and kept with the benchmark for the same
reason.  Nothing here is measured: recomputed work (remat, the flash
backward's second QK^T, the chunked form's extra products, the zeros of a
lane slot) does not count, and neither do norms, the filter, the gates or
the embedding gather.

The delta rule is counted apart (``gated_delta_*``), **by the recurrence
with ``dk x dv`` and whatever implements it**: a token a value head forward
is three products of ``2 dk dv`` operations (``S^T k``, ``k (x) delta``,
``S^T q``), backward twice that: 9,953,280 FLOP a token a layer at 30 heads
of 96 x 192; it must read q and k at ``dk`` and v at ``dv`` and write o
(bf16) beside g and beta (float32) forward, and backward read all of those
and dO and write five cotangents: 104,400 B a token a layer.  By that count
it is bound by memory about 2.5-fold on the v5e (127 ns against 51 ns).
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    kept_keys_per_token, layer_kinds)

LINEAR, FULL = "linear_attention", "full_attention"


def linear_layers(conf: dict) -> int:
    return layer_kinds(conf).count(LINEAR)


def _linear_shape(conf: dict):
    """``(key heads, value heads, key channels, value channels)``."""
    return (int(conf["linear_num_key_heads"]),
            int(conf["linear_num_value_heads"]),
            int(conf["linear_key_head_dim"]),
            int(conf["linear_value_head_dim"]))


def _attention_shape(conf: dict):
    """``(heads, key-value heads, channels a head)``: the config has no
    ``head_dim`` key, a head is ``hidden_size / num_attention_heads``."""
    H = int(conf["num_attention_heads"])
    return H, int(conf["num_key_value_heads"]), \
        int(conf.get("head_dim") or int(conf["hidden_size"]) // H)


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """QK^T and AV of the attention layers alone: 2 (H D) each a kept key,
    ``sum_i (i + 1) / seq`` keys a query."""
    H, _, D = _attention_shape(conf)
    layers = layer_kinds(conf).count(FULL)
    return passes * 4.0 * H * D * kept_keys_per_token(seq) * layers


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token
    (``flops_mellum2.flash_train_bytes_per_token``'s count: six vectors of
    H D and six of KV D a layer); K, V, dK and dV move at all 30 heads."""
    H, KV, D = _attention_shape(conf)
    return 6.0 * layer_kinds(conf).count(FULL) * (H + KV) * D * dtype_bytes


def dense_ffn_params(conf: dict) -> float:
    """One block's SwiGLU: gate, up and down."""
    return 3.0 * int(conf["hidden_size"]) * int(conf["intermediate_size"])


def active_matmul_params(conf: dict) -> float:
    """Parameters in a matrix multiplication on a token: a DeltaNet block
    E (2 Hk dk + 2 Hv dv) + E 2 Hv in and Hv dv E out; an attention block
    q and o 2 E (H D), k and v 2 E (KV D); every block the dense SwiGLU
    3 E I; plus the head over the vocabulary slice (the embedding is a
    gather)."""
    E = int(conf["hidden_size"])
    H, KV, D = _attention_shape(conf)
    Hk, Hv, dk, dv = _linear_shape(conf)
    linears = linear_layers(conf)
    n_layer = int(conf["num_hidden_layers"])
    linear = E * (2 * Hk * dk + 2 * Hv * dv) + E * 2 * Hv + Hv * dv * E
    attn = 2 * E * H * D + 2 * E * KV * D
    return (linears * linear + (n_layer - linears) * attn
            + n_layer * dense_ffn_params(conf) + int(conf["vocab_size"]) * E)


def gated_delta_flops_per_token(conf: dict, passes: int = 1) -> float:
    """Three products of ``2 dk dv`` a value head a layer forward;
    ``passes`` = 3 is forward + backward."""
    Hk, Hv, dk, dv = _linear_shape(conf)
    return passes * 3 * 2.0 * dk * dv * Hv * linear_layers(conf)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """6 x active matmul parameters + attention and the delta rule forward
    + backward."""
    return (6.0 * active_matmul_params(conf)
            + attention_flops_per_token(conf, seq, 3)
            + gated_delta_flops_per_token(conf, 3))


def gated_delta_flops_per_step(conf: dict, tokens: int) -> float:
    return gated_delta_flops_per_token(conf, 3) * tokens


def gated_delta_bytes_per_step(conf: dict, tokens: int,
                               dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the delta rules of one optimizer step, a token
    a layer: forward q, k (Hk dk each) and v in, o out (Hv dv each), g and
    beta (Hv float32 each) in; backward all of those and dO in, dq, dk, dv,
    dg and dbeta out.  A remat's second forward does not count."""
    Hk, Hv, dk, dv = _linear_shape(conf)
    qk, vo, gb = 2 * Hk * dk * dtype_bytes, Hv * dv * dtype_bytes, 2 * Hv * 4
    forward = qk + 2 * vo + gb
    backward = forward + vo + (qk + vo + gb)
    return float(forward + backward) * tokens * linear_layers(conf)
