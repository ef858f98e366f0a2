"""Operations and bytes an Ouro stage *requires*, from shapes alone: the
SAME ``num_hidden_layers`` blocks (16 heads on 16 key-value heads of 128
under a rotation, a dense SwiGLU of ``intermediate_size``, four norms)
applied ``total_ut_steps`` times a token, and after every pass the final
norm, a gate of ``hidden_size`` numbers and the untied head over the
vocabulary slice.  A leaf that is used T times multiplies T times: the count
goes by block APPLICATIONS, not by parameters held.  Kept with the benchmark
so that no PR that claims a gain can move the yardstick; nothing here is
measured: recomputed work (remat, the flash backward's second QK^T) does
not count, and neither do norms, the rotation, the exit distribution or the
embedding gather.

At the cell's sizes (6 layers x 4 passes, 2048 wide, FFN 5632, slice 6144,
8192-token rows): matmuls 6 x 51,380,224 x 2 x 4 = 2.466 GFLOP a token
forward, causal attention 24 x 33.56 MFLOP = 0.805, four exits of the slice
0.101 (the gates 12 kFLOP): 3.372 forward, 10.12 GFLOP a token forward and
backward.
"""
from __future__ import annotations

from benchmark.flops import roofline_seconds  # noqa: F401  (re-exported)
from benchmark.flops_mellum2 import kept_keys_per_token  # noqa: F401


def passes(conf: dict) -> int:
    return int(conf["total_ut_steps"])


def applications(conf: dict) -> int:
    """Block applications a token: every layer once a pass."""
    return int(conf["num_hidden_layers"]) * passes(conf)


def _attention_shape(conf: dict):
    """``(heads, key-value heads, channels a head)``."""
    H = int(conf["num_attention_heads"])
    return H, int(conf["num_key_value_heads"]), \
        int(conf.get("head_dim") or int(conf["hidden_size"]) // H)


def attention_flops_per_token(conf: dict, seq: int, passes_: int = 1) -> float:
    """QK^T and AV: 2 (H D) each a kept key, ``sum_i (i + 1) / seq`` keys a
    query, every block application; ``passes_`` = 3 is forward + backward."""
    H, _, D = _attention_shape(conf)
    return passes_ * 4.0 * H * D * kept_keys_per_token(seq) * applications(conf)


def causal_attention_flops_per_token(conf: dict, seq: int,
                                     passes: int = 1) -> float:
    """``drivers/train_lm.py`` asks under this name."""
    return attention_flops_per_token(conf, seq, passes)


def flash_train_bytes_per_token(conf: dict, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of attention forward + backward a token
    (``flops_mellum2.flash_train_bytes_per_token``'s count: six vectors of
    H D and six of KV D a call), every block application."""
    H, KV, D = _attention_shape(conf)
    return 6.0 * applications(conf) * (H + KV) * D * dtype_bytes


def block_matmul_params(conf: dict) -> float:
    """One block's matrices: q and o 2 E (H D), k and v 2 E (KV D), the
    SwiGLU 3 E I."""
    E = int(conf["hidden_size"])
    H, KV, D = _attention_shape(conf)
    return 2.0 * E * H * D + 2.0 * E * KV * D \
        + 3.0 * E * int(conf["intermediate_size"])


def exit_matmul_params(conf: dict) -> float:
    """What one exit multiplies a token: the head over the slice and the
    gate's ``hidden_size`` numbers (the last pass's gate is never read)."""
    E, T = int(conf["hidden_size"]), passes(conf)
    return int(conf["vocab_size"]) * E + E * (T - 1) / T


def active_matmul_products(conf: dict) -> float:
    """Multiply-adds in a matrix multiplication a token forward: the blocks'
    once an application, an exit's once a pass (the embedding is a
    gather)."""
    return applications(conf) * block_matmul_params(conf) \
        + passes(conf) * exit_matmul_params(conf)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """6 x the multiply-adds + attention forward + backward."""
    return 6.0 * active_matmul_products(conf) \
        + attention_flops_per_token(conf, seq, 3)
