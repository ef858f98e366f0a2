"""Operations and bytes a Keye-VL-2.0 share *requires* a token, from shapes
alone: the LLaMA-style block with ``head_dim`` apart from ``hidden_size /
heads``, grouped-query attention over the keys a learned indexer SELECTS
(``sa_config``), and sparse SwiGLU experts of ``moe_intermediate_size`` of
which this chip holds ``num_experts`` of ``routed_experts``.  The
counterpart of ``benchmark/flops_mellum2.py`` and kept with the benchmark
for the same reason.  Nothing here is measured: recomputed work (remat, a
backward's second QK^T, the indexer's scores rebuilt a tile in every
kernel, the second sweep that reads the heads' probabilities for the
indexer's loss) does not count, and neither do norms, rotary, the softmax
of the router or the embedding gather.

Attention is required over the SELECTED pairs, whatever a kernel
multiplies: query t keeps ``min(t + 1, topk)`` keys, so a dense sweep under
a mask reads a low share of this roofline by design and a kernel that
skips what is not selected reads a high one.  The indexer is required over
ALL causal pairs (it must score a key to leave it out): 16 heads of 64
channels a pair, forward and backward.

``conf`` is a configuration file's dict with the Hugging Face keys.  The
expert rows a chip must multiply are the (token, choice) pairs routed to
the experts it holds: ``held_share`` of all ``tokens x
num_experts_per_tok`` pairs, the EVEN share unless the driver passes what
the program's counter read over the window.
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)


def _shape(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["moe_intermediate_size"]))


def _indexer(conf: dict):
    sa = conf["sa_config"]
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def held_share(conf: dict, measured=None) -> float:
    """The share of the pairs this chip multiplies: ``measured`` (0..1)
    where the counter was read, else the even share."""
    if measured is not None:
        return float(measured)
    return int(conf["num_experts"]) / int(conf["routed_experts"])


def kept_keys_per_token(seq: int, topk: int) -> float:
    """Mean keys a query keeps: ``sum_t min(t + 1, topk) / seq``."""
    if topk >= seq:
        return (seq + 1) / 2.0
    return (topk * (topk + 1) / 2.0 + (seq - topk) * topk) / seq


def kept_pair_share(seq: int, topk: int) -> float:
    """Selected pairs over causal pairs of a row."""
    return kept_keys_per_token(seq, topk) / ((seq + 1) / 2.0)


def indexer_params(conf: dict) -> int:
    """The indexer's three projections: hidden -> heads x channels, one
    key of channels, a weight a head."""
    NI, DI, _ = _indexer(conf)
    return int(conf["hidden_size"]) * (NI * DI + DI + NI)


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: per block q
    and o 2*E*(H*D), k and v 2*E*(KV*D), the indexer's projections, the
    router E*routed and the held share of the token's
    ``num_experts_per_tok`` experts of 3*E*I; plus the head over the
    vocabulary slice (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    block = (2 * E * H * D + 2 * E * KV * D + indexer_params(conf)
             + E * int(conf["routed_experts"])
             + int(conf["num_experts_per_tok"]) * held_share(conf, held)
             * 3 * E * I)
    return int(conf["num_hidden_layers"]) * block \
        + int(conf["vocab_size"]) * E


def selected_attention_flops_per_token(conf: dict, seq: int,
                                       passes: int = 1) -> float:
    """QK^T and AV are 2*(H*D) each a SELECTED pair.  ``passes`` = 1
    forward, 3 forward + backward."""
    E, H, KV, D, I = _shape(conf)
    return (passes * 4.0 * H * D * kept_keys_per_token(seq, _indexer(conf)[2])
            * int(conf["num_hidden_layers"]))


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """What the attention kernels (``trace_names.flash``) are required to
    do: attention over the selected pairs and, in the backward, the two
    passes of the indexer's scores (dq_I and dk_I run inside
    ``indexed_attn_dq`` / ``_dkv``, so their time is in those kernels').
    The scores' forward is the selection's (:func:`indexer_flops_per_token`
    at one pass), not theirs.  (``drivers/train_lm.py`` asks under this
    name for ``flash_roofline``; the pairs are no longer all causal ones.)"""
    return (selected_attention_flops_per_token(conf, seq, passes)
            + indexer_flops_per_token(conf, seq, 2 if passes == 3 else 0))


def indexer_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """The indexer's scores: one product of ``heads x channels`` a causal
    pair, 2 operations a channel; forward 1 pass, with dq_I and dk_I 3."""
    NI, DI, _ = _indexer(conf)
    return (passes * 2.0 * NI * DI * (seq + 1) / 2.0
            * int(conf["num_hidden_layers"]))


def indexer_bytes_per_token(conf: dict, passes: int = 1,
                            dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the indexer's scores a token a layer: qI and
    kI and the head weights (float32) read once a pass (forward; backward
    again, and their gradients written)."""
    NI, DI, _ = _indexer(conf)
    return int(conf["num_hidden_layers"]) * float(passes) * (
        (NI * DI + DI) * dtype_bytes + NI * 4)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention over the selected pairs
    and the indexer over the causal ones, forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + selected_attention_flops_per_token(conf, seq, 3)
            + indexer_flops_per_token(conf, seq, 3))


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the attention kernels forward + backward a
    token: q, o, do, dq and again q, o of H*D (6 vectors) and k, v, dk, dv
    and again k, v of KV*D (6 vectors) a layer, and the indexer's operands
    read and their gradients written in the backward."""
    E, H, KV, D, I = _shape(conf)
    return (6.0 * int(conf["num_hidden_layers"]) * (H + KV) * D * dtype_bytes
            + indexer_bytes_per_token(conf, 2, dtype_bytes))


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
