"""Operations and bytes a Mellum 2 share *requires*, from shapes alone: the
LLaMA-style block with ``head_dim`` apart from ``hidden_size / heads``,
grouped-query attention that is windowed or full by ``layer_types``, and
sparse SwiGLU experts of ``moe_intermediate_size`` of which this chip holds
``num_experts`` of ``routed_experts``.  The counterpart of
``benchmark/flops_moe.py`` (OLMoE's) and kept with the benchmark for the
same reason.  Nothing here is measured: recomputed work (remat, the flash
backward's second QK^T) does not count, and neither do norms, rotary, the
softmax of the router or the embedding gather.

``conf`` is a configuration file's dict with the Hugging Face keys.  The
expert rows a chip must multiply are the (token, choice) pairs routed to
the experts it holds: ``held_share`` of all ``tokens x
num_experts_per_tok`` pairs.  Without a reading that is the EVEN share,
``num_experts / routed_experts``; the driver passes what the program's
counter read over the window (``moe_held_pair_pct`` / 100), because the
work the routing did not send here was not required here.
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)

SLIDING, FULL = "sliding_attention", "full_attention"


def _shape(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["moe_intermediate_size"]))


def layer_kinds(conf: dict) -> list:
    """The layer type of each layer that is run."""
    return list(conf["layer_types"][:int(conf["num_hidden_layers"])])


def held_share(conf: dict, measured=None) -> float:
    """The share of the pairs this chip multiplies: ``measured`` (0..1)
    where the counter was read, else the even share."""
    if measured is not None:
        return float(measured)
    return int(conf["num_experts"]) / int(conf["routed_experts"])


def kept_keys_per_token(seq: int, window=None) -> float:
    """Mean keys a query keeps: ``sum_i min(i + 1, window) / seq`` over the
    positions of a row, ``sum_i (i + 1) / seq`` without a window."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: per block q
    and o 2*E*(H*D), k and v 2*E*(KV*D), the router E*routed and the held
    share of the token's ``num_experts_per_tok`` experts of 3*E*I; plus
    the head over the vocabulary slice (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    block = (2 * E * H * D + 2 * E * KV * D + E * int(conf["routed_experts"])
             + int(conf["num_experts_per_tok"]) * held_share(conf, held)
             * 3 * E * I)
    return int(conf["num_hidden_layers"]) * block + int(conf["vocab_size"]) * E


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1,
                              kind=None) -> float:
    """QK^T and AV are 2*(H*D) each a kept key; ``kind`` counts the layers
    of one type alone.  ``passes`` = 1 forward, 3 forward + backward."""
    E, H, KV, D, I = _shape(conf)
    keys = sum(kept_keys_per_token(
        seq, int(conf["sliding_window"]) if k == SLIDING else None)
        for k in layer_kinds(conf) if kind in (None, k))
    return passes * 4.0 * H * D * keys


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """Every layer's attention, banded or full: ``drivers/train_lm.py``
    asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3))


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2,
                                kind=None) -> float:
    """Least HBM traffic of attention forward + backward a token: q, o, do,
    dq and again q, o of H*D (6 vectors) and k, v, dk, dv and again k, v
    of KV*D (6 vectors) a layer: keys and values move at 4 heads."""
    E, H, KV, D, I = _shape(conf)
    n = sum(1 for k in layer_kinds(conf) if kind in (None, k))
    return 6.0 * n * (H + KV) * D * dtype_bytes


def expert_rows_per_step(conf: dict, tokens: int, held=None) -> float:
    return tokens * int(conf["num_experts_per_tok"]) * held_share(conf, held)


def expert_gemm_flops_per_step(conf: dict, tokens: int, held=None) -> float:
    """The grouped matmuls of one optimizer step: 3 matrices (gate, up,
    down) x 3 passes (forward, d-rows, d-weights) of 2*rows*E*I a layer."""
    E, H, KV, D, I = _shape(conf)
    return (9.0 * 2.0 * expert_rows_per_step(conf, tokens, held) * E * I
            * int(conf["num_hidden_layers"]))


def expert_gemm_bytes_per_step(conf: dict, tokens: int, dtype_bytes: int = 2,
                               held=None) -> float:
    """Least HBM traffic of those 9 grouped matmuls a layer: each reads or
    writes every held expert's matrix once (num_experts*E*I) and moves the
    rows once on the wide side (rows*E) and once on the narrow (rows*I)."""
    E, H, KV, D, I = _shape(conf)
    one = (int(conf["num_experts"]) * E * I
           + expert_rows_per_step(conf, tokens, held) * (E + I))
    return 9.0 * one * dtype_bytes * int(conf["num_hidden_layers"])
