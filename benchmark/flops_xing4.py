"""Operations and bytes a Xing4.0-29B-A4B share *requires*, from shapes
alone: the DeepSeek-V3 family's block as ``benchmark/flops_joyai.py`` counts
it (latent attention, a leading dense SwiGLU, sparse layers of which this
chip holds ``num_experts``, the prediction block, the head over the slice
once a loss; YaRN changes no count), and around every sublayer a
manifold-constrained hyper-connection over ``hc_mult`` lanes.  Kept with the
benchmark so that no PR that claims a gain can move the yardstick; nothing
here is measured, and a remat's second forward does not count.

A hyper-connection, a token a sublayer, n = ``hc_mult``, E =
``hidden_size``, k = n^2 + 2n.  Operations forward: the row's sum of
squares 2 n E, ``x @ phi`` 2 n E k, the mix ``H_pre @ X`` 2 n E, the
write-back ``H_res @ X + H_post^T y`` 2 n^2 E + 2 n E, and
``hc_sinkhorn_iters`` sweeps of 4 n^2 (two sums and two divisions an entry);
backward twice that.  Least bytes, each lane read once a pass and written
once a sublayer, in the stream's bf16: forward one pass reads the lanes for
the maps AND the mix (a row's statistics fit on the chip) and writes ``u``,
one reads the lanes and ``y`` and writes the lanes, (3 n + 2) E elements;
backward reads the lanes, ``y`` and both cotangents and writes the lanes'
and ``y``'s cotangents, (3 n + 3) E.
"""
from __future__ import annotations

from benchmark.flops_joyai import (  # noqa: F401  (re-exported)
    active_matmul_params, attention_flops_per_token, blocks,
    causal_attention_flops_per_token,
    expert_gemm_bytes_per_step, expert_gemm_flops_per_step,
    expert_rows_per_step, flash_train_bytes_per_token, held_share,
    kept_keys_per_token, roofline_seconds, sparse_layers)


def sublayers(conf: dict) -> int:
    """Hyper-connections a token passes: two a block, the prediction
    block's too."""
    return 2 * blocks(conf)


def _lanes(conf: dict) -> tuple:
    n = int(conf["hc_mult"])
    return n, int(conf["hidden_size"]), n * n + 2 * n


def mhc_flops_per_token(conf: dict, passes: int = 1) -> float:
    """``passes`` = 1 forward, 3 forward + backward."""
    n, E, k = _lanes(conf)
    forward = (2.0 * n * E * (1 + k) + 2.0 * n * E + 2.0 * n * n * E
               + 2.0 * n * E + 4.0 * n * n * int(conf["hc_sinkhorn_iters"]))
    return passes * forward * sublayers(conf)


def mhc_matmul_params(conf: dict) -> int:
    """``phi`` of every sublayer: parameters in a matrix multiplication on
    every token (what ``6 x parameters`` counts of the mechanism)."""
    n, E, k = _lanes(conf)
    return n * E * k * sublayers(conf)


def mhc_flops_per_step(conf: dict, tokens: int) -> float:
    return mhc_flops_per_token(conf, 3) * tokens


def mhc_bytes_per_step(conf: dict, tokens: int, dtype_bytes: int = 2) -> float:
    n, E, _ = _lanes(conf)
    return float((3 * n + 2) + (3 * n + 3)) * E * dtype_bytes * tokens \
        * sublayers(conf)


def train_flops_per_token(conf: dict, seq: int, held=None) -> float:
    """6 x active matmul parameters + attention forward + backward + the
    hyper-connections' products, mixes and sweeps forward + backward."""
    return (6.0 * active_matmul_params(conf, held)
            + attention_flops_per_token(conf, seq, 3)
            + mhc_flops_per_token(conf, 3))
