"""Operations and bytes an LFM2-MoE share *requires*, from shapes alone:
``layer_types`` mixes ``conv`` blocks (the double-gated short convolution:
one projection to three thirds of ``hidden_size``, a causal depthwise
filter of ``conv_L_cache`` taps, one projection back) and ``full_attention``
blocks (grouped queries, no window); ``num_dense_layers`` leading blocks
keep a dense SwiGLU of ``intermediate_size`` and the rest route over
``routed_experts`` sparse ones of ``moe_intermediate_size``, of which this
chip holds ``num_experts``; no shared expert; the head is the embedding
table (tied), over the vocabulary slice.  The counterpart of
``benchmark/flops_trinity.py`` and kept with the benchmark for the same
reason.  Nothing here is measured: recomputed work (remat, the flash
backward's second QK^T) does not count, and neither do norms, rotary, the
router's scores or the embedding gather.

The filter is counted apart (``short_conv_filter_*``): whatever implements
it, forward it must read the three thirds and write the gated output (4
vectors of ``hidden_size`` a token a layer), backward read those three and
the cotangent and write three cotangents (7; the taps' gradient is
``hidden_size x conv_L_cache`` floats a layer, nothing beside them), at
``2 L + 2`` multiply-adds a channel a pass.  It is bound by memory ~100-fold.

The rows a chip must multiply in its grouped matmuls are the (token,
choice) pairs routed to the experts it holds: ``held_share`` of all
``tokens x num_experts_per_tok`` pairs, the even share without a reading,
else what the program's counter read over the window.
"""
from __future__ import annotations

from benchmark import flops_mellum2 as _geometry
from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
# the same share of the pairs and the same grouped-query attention geometry
# as Mellum 2's, counted over the full_attention layers alone
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    expert_rows_per_step, held_share, kept_keys_per_token, layer_kinds)
# the grouped matmuls of the held routed experts over the sparse layers, as
# Trinity's (a leading dense block; the shared expert it has is no part of
# them)
from benchmark.flops_trinity import (  # noqa: F401  (re-exported)
    _shape, expert_gemm_bytes_per_step, expert_gemm_flops_per_step,
    sparse_layers)

CONV, FULL = "conv", "full_attention"


def conv_layers(conf: dict) -> int:
    return layer_kinds(conf).count(CONV)


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """QK^T and AV of the attention layers alone: a conv layer has none."""
    return _geometry.attention_flops_per_token(conf, seq, passes, kind=FULL)


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Keys and values move at their own 8 heads, whatever the kernel is
    handed."""
    return _geometry.flash_train_bytes_per_token(conf, dtype_bytes, kind=FULL)


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: a conv block
    E*3E + E*E; an attention block q and o 2*E*(H*D), k and v 2*E*(KV*D); a
    dense block 3*E*F; a sparse one the router E*routed and the held share
    of the token's ``num_experts_per_tok`` experts of 3*E*I; plus the tied
    head over the vocabulary slice (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    convs = conv_layers(conf)
    conv = 3 * E * E + E * E
    attn = 2 * E * H * D + 2 * E * KV * D
    dense = 3 * E * int(conf["intermediate_size"])
    sparse = (E * int(conf["routed_experts"])
              + int(conf["num_experts_per_tok"]) * held_share(conf, held)
              * 3 * E * I)
    return (convs * conv + (int(conf["num_hidden_layers"]) - convs) * attn
            + int(conf["num_dense_layers"]) * dense
            + sparse_layers(conf) * sparse + int(conf["vocab_size"]) * E)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3))


def short_conv_filter_bytes_per_step(conf: dict, tokens: int,
                                     dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the filters of one optimizer step: 4 vectors of
    E a token a conv layer forward (Bg, Cg, u in; the gated output out), 7
    backward (those three and the cotangent in; three cotangents out).  A
    remat's second forward does not count."""
    E = int(conf["hidden_size"])
    return 11.0 * E * dtype_bytes * tokens * conv_layers(conf)


def short_conv_filter_flops_per_step(conf: dict, tokens: int) -> float:
    """``2 L + 2`` multiply-adds a channel a pass, forward and two passes'
    worth backward."""
    E, L = int(conf["hidden_size"]), int(conf["conv_L_cache"])
    return 3.0 * 2.0 * (2 * L + 2) * E * tokens * conv_layers(conf)
