"""Operations and bytes a Ling-3.0-flash share *requires*, from shapes
alone: ``layer_types`` mixes ``kda_attention`` blocks (Kimi Delta Attention:
q, k, v, the decay gate's, the output gate's and the output projection, each
E x H d, beta's E x H, three causal depthwise filters, the delta rule under
a decay a key channel over ``num_attention_heads`` states of ``head_dim x
head_dim``, a gated norm) and ``full_attention`` blocks (latent attention
without a query latent: q E x H (nope + rope), keys and values through a
``kv_lora_rank`` latent, ONE rope key for all heads, a gate a head E x H);
``num_dense_layers`` leading blocks with a dense SwiGLU of
``intermediate_size``, the others with a router over ``routed_experts``,
``num_shared_experts`` shared SwiGLUs of ``moe_intermediate_size`` and
sparse ones of which this chip holds ``num_experts``; a prediction block
(latent attention, sparse) where ``num_nextn_predict_layers`` says one and
``mtp_loss_scaling_factor`` is not 0 (at 0 the program builds none); the
head over the vocabulary slice once a loss.  The counterpart of
``benchmark/flops_qwen3next.py`` and ``flops_joyai.py`` and kept with the
benchmark for the same reason.  Nothing here is measured: recomputed work
(remat, the flash backward's second score, the chunked form's extra
products) does not count, and neither do norms, rotary, the filters, the
gates' activations, the router's scores or the embedding gather.

The delta rule is counted apart (``kda_*``), **by the recurrence and
whatever implements it**: a token a head forward is the decay of the state
(``d d`` multiplications) and three products of ``2 d d`` operations (``S^T
k``, ``k (x) delta``, ``S^T q``): ``7 d d``; backward twice that.  It must
read q, k, v and write o (bf16) beside g (float32, AS WIDE AS k: the decay
a key channel) and beta (float32 a head) forward, and backward read all of
those and dO and write five cotangents.  By that count it is bound by
memory about threefold (147,840 bytes against 11.0 MFLOP a token a layer at
the published sizes).
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
from benchmark.flops_mellum2 import (  # noqa: F401  (re-exported)
    expert_rows_per_step, held_share, kept_keys_per_token)

KDA, FULL = "kda_attention", "full_attention"


def layer_kinds(conf: dict) -> list:
    return list(conf["layer_types"][:int(conf["num_hidden_layers"])])


def kda_layers(conf: dict) -> int:
    return layer_kinds(conf).count(KDA)


def mtp_blocks(conf: dict) -> int:
    """Prediction blocks the program builds: those the loss weighs (under
    the published ``mtp_loss_scaling_factor`` 0, none)."""
    return int(conf.get("num_nextn_predict_layers", 0)) \
        if float(conf.get("mtp_loss_scaling_factor", 0)) else 0


def mla_blocks(conf: dict) -> int:
    """Blocks whose mixer is latent attention: the stack's and the
    prediction's."""
    return layer_kinds(conf).count(FULL) + mtp_blocks(conf)


def sparse_layers(conf: dict) -> int:
    return int(conf["num_hidden_layers"]) + mtp_blocks(conf) \
        - int(conf["num_dense_layers"])


def _kda(conf: dict):
    """``(E, heads, channels a head)``."""
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["head_dim"]))


def _mla(conf: dict):
    return (int(conf["hidden_size"]), int(conf["num_attention_heads"]),
            int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"]),
            int(conf["v_head_dim"]))


def kda_matmul_params(conf: dict) -> int:
    """One KDA mixer's projections: q, k, v, f (the decay gate), g (the
    output gate), o, each E x H d, and beta's E x H."""
    E, H, d = _kda(conf)
    return 6 * E * H * d + E * H


def mla_matmul_params(conf: dict) -> int:
    """One latent-attention mixer's: q (no latent), kv_a (latent + rope
    key), kv_b (keys and values), o, the gate a head."""
    E, H, Dn, Dr, Dv = _mla(conf)
    kv = int(conf["kv_lora_rank"])
    return (E * H * (Dn + Dr) + E * (kv + Dr) + kv * H * (Dn + Dv)
            + H * Dv * E + E * H)


def active_matmul_params(conf: dict, held=None) -> float:
    """Parameters in a matrix multiplication on a token HERE: every block's
    mixer; a dense block 3*E*F; a sparse one the router E*routed, the shared
    experts 3*E*I each and the held share of the token's
    ``num_experts_per_tok`` experts of 3*E*I; ``eh_proj`` 2*E*E a prediction
    block; the head over the slice once a loss (the embeddings are
    gathers)."""
    E = int(conf["hidden_size"])
    I = int(conf["moe_intermediate_size"])
    mtp = mtp_blocks(conf)
    dense = 3 * E * int(conf["intermediate_size"])
    sparse = (E * int(conf["routed_experts"])
              + (int(conf["num_shared_experts"])
                 + int(conf["num_experts_per_tok"]) * held_share(conf, held))
              * 3 * E * I)
    return (kda_layers(conf) * kda_matmul_params(conf)
            + mla_blocks(conf) * mla_matmul_params(conf)
            + int(conf["num_dense_layers"]) * dense
            + sparse_layers(conf) * sparse + mtp * 2 * E * E
            + (1 + mtp) * int(conf["vocab_size"]) * E)


def attention_flops_per_token(conf: dict, seq: int, passes: int = 1) -> float:
    """A kept key costs a head 2*(nope + rope) for its score and 2*v for
    its value, in the latent-attention blocks alone.  ``passes`` = 1
    forward, 3 forward + backward."""
    E, H, Dn, Dr, Dv = _mla(conf)
    return (passes * 2.0 * H * (Dn + Dr + Dv) * kept_keys_per_token(seq)
            * mla_blocks(conf))


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token a
    latent-attention block (``flops_joyai.py``'s count): q_nope, o and
    k_nope nine H*nope-wide moves, v three at H*v, q_rope three at H*rope,
    the shared rope key three at ONE rope width."""
    E, H, Dn, Dr, Dv = _mla(conf)
    return (mla_blocks(conf) * dtype_bytes
            * (9.0 * H * Dn + 3.0 * H * Dv + 3.0 * H * Dr + 3.0 * Dr))


def kda_flops_per_token(conf: dict, passes: int = 1) -> float:
    """``7 d d`` a head a KDA layer forward (the state's decay and three
    products); ``passes`` = 3 is forward + backward."""
    E, H, d = _kda(conf)
    return passes * 7.0 * d * d * H * kda_layers(conf)


def kda_flops_per_step(conf: dict, tokens: int) -> float:
    return kda_flops_per_token(conf, 3) * tokens


def kda_bytes_per_step(conf: dict, tokens: int, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the delta rules of one optimizer step, a token
    a layer: forward q, k, v in and o out (H d each), g (H d float32) and
    beta (H float32) in; backward all of those and dO in, dq, dk, dv, dg
    and dbeta out.  A remat's second forward does not count."""
    E, H, d = _kda(conf)
    qkv, o, gb = 3 * H * d * dtype_bytes, H * d * dtype_bytes, (H * d + H) * 4
    forward = qkv + o + gb
    backward = forward + o + (qkv + gb)
    return float(forward + backward) * tokens * kda_layers(conf)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention and the delta rule forward
    + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3)
            + kda_flops_per_token(conf, 3))


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
