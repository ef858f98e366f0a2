"""Operations and bytes a JoyAI-LLM-Flash share (the DeepSeek-V3 family)
*requires*, from shapes alone: ``num_hidden_layers`` blocks and
``num_nextn_predict_layers`` prediction blocks of latent attention (q
through a ``q_lora_rank`` latent to ``qk_nope_head_dim + qk_rope_head_dim``
channels a head, keys and values through a ``kv_lora_rank`` latent, ONE
rope key of ``qk_rope_head_dim`` for all heads, values ``v_head_dim``
wide); ``num_dense_layers`` leading blocks with a dense SwiGLU of
``intermediate_size``, the others (the prediction block too) with a router
over ``routed_experts``, ``n_shared_experts`` shared SwiGLUs of
``moe_intermediate_size`` and sparse ones of which this chip holds
``num_experts``; the prediction block's ``eh_proj`` (2E -> E); and the head
over the vocabulary slice TWICE, once a loss.  The counterpart of
``benchmark/flops_trinity.py`` and kept with the benchmark for the same
reason.  Nothing here is measured: recomputed work (remat, the flash
backward's second score) does not count, and neither do norms, rotary, the
router's scores or the embedding gathers.

Attention: the score contracts ``nope + rope`` (192) channels and the
values ``v_head_dim`` (128), over the ``sum_i (i + 1) / S`` keys a causal
query keeps.  Its least bytes move the shared rope key as ONE
``qk_rope_head_dim`` vector a token, not one a head.
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    expert_rows_per_step, held_share, kept_keys_per_token)


def _attn(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"]),
            int(conf["v_head_dim"]))


def blocks(conf: dict) -> int:
    """Decoder blocks a token runs: the stack's and the prediction's."""
    return int(conf["num_hidden_layers"]) \
        + int(conf.get("num_nextn_predict_layers", 0))


def sparse_layers(conf: dict) -> int:
    return blocks(conf) - int(conf["num_dense_layers"])


def attention_matmul_params(conf: dict) -> int:
    """One block's projections: q_a, q_b, kv_a (latent + rope key), kv_b
    (keys and values), o."""
    E, H, Dn, Dr, Dv = _attn(conf)
    q, kv = int(conf["q_lora_rank"]), int(conf["kv_lora_rank"])
    return (E * q + q * H * (Dn + Dr) + E * (kv + Dr) + kv * H * (Dn + Dv)
            + H * Dv * E)


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: every
    block's attention; a dense block 3*E*F; a sparse one the router
    E*routed, the shared experts 3*E*I each and the held share of the
    token's ``num_experts_per_tok`` experts of 3*E*I; ``eh_proj`` 2*E*E a
    prediction block; the head over the slice once a loss (the embeddings
    are gathers)."""
    E = int(conf["hidden_size"])
    I = int(conf["moe_intermediate_size"])
    mtp = int(conf.get("num_nextn_predict_layers", 0))
    dense = 3 * E * int(conf["intermediate_size"])
    sparse = (E * int(conf["routed_experts"])
              + (int(conf["n_shared_experts"])
                 + int(conf["num_experts_per_tok"]) * held_share(conf, held))
              * 3 * E * I)
    return (blocks(conf) * attention_matmul_params(conf)
            + int(conf["num_dense_layers"]) * dense
            + sparse_layers(conf) * sparse + mtp * 2 * E * E
            + (1 + mtp) * int(conf["vocab_size"]) * E)


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """A kept key costs a head 2*(nope + rope) for its score and 2*v for
    its value.  ``passes`` = 1 forward, 3 forward + backward."""
    E, H, Dn, Dr, Dv = _attn(conf)
    return (passes * 2.0 * H * (Dn + Dr + Dv) * kept_keys_per_token(seq)
            * blocks(conf))


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3))


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token a block:
    of H*nope-wide vectors q_nope three times (read, read again, dq), o
    twice, dO once, k_nope three times (read, read again, dk); v three
    times at H*v; q_rope three times at H*rope; and the shared rope key
    three times at ONE rope width."""
    E, H, Dn, Dr, Dv = _attn(conf)
    return (blocks(conf) * dtype_bytes
            * (9.0 * H * Dn + 3.0 * H * Dv + 3.0 * H * Dr + 3.0 * Dr))


def expert_gemm_flops_per_step(conf: dict, tokens: int, held=None) -> float:
    """The grouped matmuls of one optimizer step: 3 matrices (gate, up,
    down) x 3 passes (forward, d-rows, d-weights) of 2*rows*E*I a sparse
    layer, the held routed experts alone."""
    E, I = int(conf["hidden_size"]), int(conf["moe_intermediate_size"])
    return (9.0 * 2.0 * expert_rows_per_step(conf, tokens, held) * E * I
            * sparse_layers(conf))


def expert_gemm_bytes_per_step(conf: dict, tokens: int, dtype_bytes: int = 2,
                               held=None) -> float:
    """Least HBM traffic of those 9 grouped matmuls a sparse layer: each
    reads or writes every held expert's matrix once and moves the rows
    once on the wide side and once on the narrow."""
    E, I = int(conf["hidden_size"]), int(conf["moe_intermediate_size"])
    one = (int(conf["num_experts"]) * E * I
           + expert_rows_per_step(conf, tokens, held) * (E + I))
    return 9.0 * one * dtype_bytes * sparse_layers(conf)
