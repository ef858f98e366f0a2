"""Operations and bytes an SDAR share *requires* a DATA token under
block-diffusion training, from shapes alone.  The counterpart of
``benchmark/flops_mellum2.py`` and kept with the benchmark for the same
reason.  Nothing here is measured: recomputed work (remat, the flash
backward's second QK^T) does not count, and neither do norms, rotary, the
softmax of the router, the embedding gather or drawing the noise.

A data token is one token of the corpus; the step runs TWO positions for
it, its noisy and its clean copy (``[x~ ; x]``), through every block's
matrix multiplications.  Attention keeps, over a row of L tokens in blocks
of g, ``L (L + g) / 2`` pairs for the clean half (``b(j) <= b(i)``) and
``L (L - g) / 2 + L g`` for the noisy half (earlier clean blocks, its own
noisy block): ``L (L + g)`` pairs a row, ``L + g`` kept keys a data token,
against ``(L + 1) / 2`` of a causal model.  The head is required at the
MASKED positions alone: a position of weight 0 needs no logits, and the
yardstick reads the same whether or not the program computes them.
``masked`` is the share of the data tokens the window's noise masked (the
program's counter, ``diffusion_tokens_total``), ``(1 + t_min) / 2`` under
``t ~ U(t_min, 1]`` where it was not read.

``conf`` is a configuration file's dict with the Hugging Face keys and the
``diffusion`` section.  The expert rows a chip must multiply are the
(position, choice) pairs routed to the experts it holds: ``held_share`` of
all ``2 x tokens x num_experts_per_tok`` pairs, the EVEN share
``num_experts / routed_experts`` unless the driver passes what the
program's counter read over the window.
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)

COPIES = 2      # positions the step runs a data token: noisy and clean


def _shape(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["num_key_value_heads"]), int(conf["head_dim"]),
            int(conf["moe_intermediate_size"]))


def held_share(conf: dict, measured=None) -> float:
    """The share of the pairs this chip multiplies: ``measured`` (0..1)
    where the counter was read, else the even share."""
    if measured is not None:
        return float(measured)
    return int(conf["num_experts"]) / int(conf["routed_experts"])


def masked_share(conf: dict, measured=None) -> float:
    """The share of the data tokens that are masked: ``measured`` where
    the counter was read, else the mean of ``U(t_min, 1]``."""
    if measured is not None:
        return float(measured)
    return (1.0 + float(conf["diffusion"]["t_min"])) / 2.0


def kept_pairs_per_row(seq: int, block: int) -> int:
    """Kept (query, key) pairs of one data row, both halves."""
    clean = seq * (seq + block) // 2
    noisy = seq * (seq - block) // 2 + seq * block
    return clean + noisy


def kept_keys_per_token(seq: int, block: int) -> float:
    return kept_pairs_per_row(seq, block) / seq         # L + g


def block_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on ONE position of one block
    here: q and o 2*E*(H*D), k and v 2*E*(KV*D), the router E*routed and
    the held share of the position's ``num_experts_per_tok`` experts."""
    E, H, KV, D, I = _shape(conf)
    return (2 * E * H * D + 2 * E * KV * D + E * int(conf["routed_experts"])
            + int(conf["num_experts_per_tok"]) * held_share(conf, held)
            * 3 * E * I)


def head_params(conf: dict, masked=None) -> float:
    """The head's parameters a DATA token meets: the vocabulary slice
    where the token is masked, nothing where it is not."""
    return (masked_share(conf, masked) * int(conf["vocab_size"])
            * int(conf["hidden_size"]))


def active_matmul_params(conf: dict, held=None, masked=None) -> float:
    """Matmul parameters a DATA token meets: two positions through every
    block, and the head where it is masked."""
    return (COPIES * int(conf["num_hidden_layers"])
            * block_matmul_params(conf, held) + head_params(conf, masked))


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """QK^T and AV are 2*(H*D) each a kept pair, ``L + g`` pairs a data
    token a layer.  ``passes`` = 1 forward, 3 forward + backward."""
    E, H, KV, D, I = _shape(conf)
    block = int(conf["diffusion"]["block_length"])
    return (passes * 4.0 * H * D * kept_keys_per_token(seq, block)
            * int(conf["num_hidden_layers"]))


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name; the mask is block
    diffusion's, not a causal one."""
    return attention_flops_per_token(conf, seq, passes)


def train_flops_per_token(conf: dict, seq: int, held=None,
                          masked=None) -> float:
    """6 x matmul parameters a data token + attention forward + backward."""
    return (6.0 * active_matmul_params(conf, held, masked)
            + attention_flops_per_token(conf, seq, 3))


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a DATA token: for
    each of its two positions q, o, do, dq and again q, o of H*D (6
    vectors) and k, v, dk, dv and again k, v of KV*D (6 vectors) a layer."""
    E, H, KV, D, I = _shape(conf)
    return (COPIES * 6.0 * int(conf["num_hidden_layers"]) * (H + KV) * D
            * dtype_bytes)


def expert_rows_per_step(conf: dict, tokens: int, held=None) -> float:
    """``tokens`` are data tokens: each routes two positions."""
    return (COPIES * tokens * int(conf["num_experts_per_tok"])
            * held_share(conf, held))


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
