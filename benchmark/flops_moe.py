"""Operations and bytes a sparse-expert decoder *requires*, from shapes
alone: the LLaMA-style block (separate q/k/v/o projections, SwiGLU FFN,
untied head) with ``num_experts`` experts of which ``num_experts_per_tok``
run a token.  The counterpart of ``benchmark/flops.py`` (the GPT-2
family's) and kept with the benchmark for the same reason.  Nothing here
is measured: recomputed work (remat, the flash backward's second QK^T)
does not count, and neither do norms, rotary, the softmax of the router or
the embedding gather.

``conf`` is a configuration file's dict with the Hugging Face keys
(``hidden_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``vocab_size``,
``num_experts``, ``num_experts_per_tok``).
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)


def _shape(conf: dict):
    E = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    KV = int(conf.get("num_key_value_heads") or H)
    return E, H, KV, E // H, int(conf["intermediate_size"])


def active_matmul_params(conf: dict) -> int:
    """Parameters that sit in a matrix multiplication on EVERY token: per
    block q and o projections 2*E*(H*D), k and v 2*E*(KV*D), the router
    E*experts and ``num_experts_per_tok`` experts of 3*E*I; plus the
    untied head V*E (the embedding is a gather)."""
    E, H, KV, D, I = _shape(conf)
    block = (2 * E * H * D + 2 * E * KV * D + E * int(conf["num_experts"])
             + int(conf["num_experts_per_tok"]) * 3 * E * I)
    return int(conf["num_hidden_layers"]) * block + int(conf["vocab_size"]) * E


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """QK^T and AV are 2*S*(H*D) each a token for full attention; the
    causal half is required.  ``passes`` = 1 forward, 3 forward+backward."""
    E, H, KV, D, I = _shape(conf)
    return passes * 2.0 * int(conf["num_hidden_layers"]) * H * D * seq


def train_flops_per_token(conf: dict, seq: int) -> float:
    """6 x active matmul parameters + causal attention forward+backward."""
    return (6.0 * active_matmul_params(conf)
            + causal_attention_flops_per_token(conf, seq, 3))


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token: q, o, do,
    dq and again q, o of H*D (6 vectors) and k, v, dk, dv and again k, v
    of KV*D (6 vectors) a layer: 12 E-vectors at KV = H."""
    E, H, KV, D, I = _shape(conf)
    return 6.0 * int(conf["num_hidden_layers"]) * (H + KV) * D * dtype_bytes


def expert_gemm_flops_per_step(conf: dict, tokens: int) -> float:
    """The grouped matmuls of one optimizer step: 3 matrices (gate, up,
    down) x 3 passes (forward, d-rows, d-weights) of 2*(T*k)*E*I a layer."""
    E, H, KV, D, I = _shape(conf)
    rows = tokens * int(conf["num_experts_per_tok"])
    return 9.0 * 2.0 * rows * E * I * int(conf["num_hidden_layers"])


def expert_gemm_bytes_per_step(conf: dict, tokens: int,
                               dtype_bytes: int = 2) -> float:
    """Least HBM traffic of those 9 grouped matmuls a layer: each reads or
    writes every expert's matrix once (experts*E*I) and moves the gathered
    rows once on the wide side (T*k*E) and once on the narrow (T*k*I)."""
    E, H, KV, D, I = _shape(conf)
    rows = tokens * int(conf["num_experts_per_tok"])
    one = int(conf["num_experts"]) * E * I + rows * (E + I)
    return 9.0 * one * dtype_bytes * int(conf["num_hidden_layers"])
