"""Fused elementwise/normalization Pallas kernels — the training-kernel set.

TPU-native replacement for the reference's fused BERT-layer CUDA kernels
(``csrc/transformer/normalize_kernels.cu`` layernorm fwd/bwd,
``csrc/transformer/gelu_kernels.cu`` fused bias-gelu,
``csrc/transformer/softmax_kernels.cu`` masked/causal attention softmax).
On TPU, XLA already fuses most elementwise chains into neighboring matmuls;
these kernels exist for the cases where an explicit fusion wins — a single
VMEM-resident pass producing the activation *and* the saved statistics the
backward needs (the reference saves mean/var the same way rather than
recomputing, ``normalize_kernels.cu`` fused backward) — and to give the op
library a compiled, parity-testable analog of every native row in SURVEY.md
§2.4.

Each op is a ``jax.custom_vjp`` whose forward and backward are Pallas
kernels; ``interpret=True`` runs them on CPU for tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _pad_rows(x2: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Pad the leading (row) dim up to a multiple of ``block`` so odd row
    counts keep full-size tiles (padded rows carry zero cotangents, so the
    partial-sum reductions in the backward kernels are unaffected)."""
    R = x2.shape[0]
    rem = R % block
    if rem == 0:
        return x2, R
    pad = block - rem
    return jnp.pad(x2, ((0, pad),) + ((0, 0),) * (x2.ndim - 1)), R


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    # mean/rstd are carried (rows, 1): a partial 1-D block over (R,) hits
    # Mosaic's 1024-lane 1-D tiling and fails to lower on hardware
    x = x_ref[...].astype(jnp.float32)                     # (rows, D)
    mean = x.mean(axis=-1, keepdims=True)                  # (rows, 1)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                   dx_ref, dg_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]                                   # (rows, 1)
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    dxhat = dy * g_ref[...].astype(jnp.float32)
    m1 = dxhat.mean(axis=-1, keepdims=True)
    m2 = (dxhat * xhat).mean(axis=-1, keepdims=True)
    dx = rstd * (dxhat - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-row-block partial reductions (nb, 1, D); summed by the caller
    dg_ref[...] = (dy * xhat).sum(axis=0)[None, None, :]
    db_ref[...] = dy.sum(axis=0)[None, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _layer_norm(x, gamma, beta, eps, block_rows, interpret):
    y, _ = _layer_norm_fwd(x, gamma, beta, eps, block_rows, interpret)
    return y


def _layer_norm_fwd(x, gamma, beta, eps, block_rows, interpret):
    R, D = x.shape
    grid = (R // block_rows,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, gamma, beta)
    return y, (x, gamma, mean, rstd)


def _layer_norm_bwd(eps, block_rows, interpret, res, dy):
    x, gamma, mean, rstd = res
    R, D = x.shape
    nb = R // block_rows
    dx, dg_part, db_part = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ],
        interpret=interpret,
    )(x, gamma, mean, rstd, dy)
    dgamma = dg_part.sum(axis=(0, 1)).astype(gamma.dtype)
    dbeta = db_part.sum(axis=(0, 1)).astype(gamma.dtype)
    return dx, dgamma, dbeta


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, *,
               eps: float = 1e-5, block_rows: int = 128,
               interpret: bool = False) -> jax.Array:
    """Fused layernorm over the last dim; any leading shape."""
    lead = x.shape[:-1]
    D = x.shape[-1]
    R = 1
    for s in lead:
        R *= s
    br = min(block_rows, R)
    x2, R0 = _pad_rows(x.reshape(R, D), br)
    out = _layer_norm(x2, gamma, beta, eps, br, interpret)
    return out[:R0].reshape(*lead, D)


# ---------------------------------------------------------------------------
# Fused bias + GeLU
# ---------------------------------------------------------------------------

_SQRT_2_OVER_PI = 0.7978845608028654


def _gelu_tanh(u):
    inner = _SQRT_2_OVER_PI * (u + 0.044715 * u * u * u)
    return 0.5 * u * (1.0 + jnp.tanh(inner))


def _gelu_tanh_grad(u):
    u3 = 0.044715 * u * u * u
    inner = _SQRT_2_OVER_PI * (u + u3)
    t = jnp.tanh(inner)
    sech2 = 1.0 - t * t
    return 0.5 * (1.0 + t) + 0.5 * u * sech2 * _SQRT_2_OVER_PI * \
        (1.0 + 3.0 * 0.044715 * u * u)


def _bias_gelu_fwd_kernel(x_ref, b_ref, y_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _gelu_tanh(u).astype(y_ref.dtype)


def _bias_gelu_bwd_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dx = dy_ref[...].astype(jnp.float32) * _gelu_tanh_grad(u)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    db_ref[...] = dx.sum(axis=0)[None, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bias_gelu(x, bias, block_rows, interpret):
    y, _ = _bias_gelu_fwd(x, bias, block_rows, interpret)
    return y


def _bias_gelu_fwd(x, bias, block_rows, interpret):
    R, D = x.shape
    y = pl.pallas_call(
        _bias_gelu_fwd_kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(x, bias)
    return y, (x, bias)


# Mosaic's default scoped-VMEM limit; the backward kernel below holds
# three double-buffered (block_rows, D) tiles plus ~4 fp32 temporaries.
# Measured on the v5e (PR 21): bf16 (128, 6400) tiles asked for 23.09 MB
# and Mosaic refused — the estimate reproduces that figure.
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _bias_gelu_bwd(block_rows, interpret, res, dy):
    x, bias = res
    R, D = x.shape
    row_bytes = D * (6 * x.dtype.itemsize + 16)
    if not interpret and block_rows * row_bytes > _SCOPED_VMEM_BYTES:
        raise ValueError(
            f"bias_gelu backward: (block_rows={block_rows}, D={D}) "
            f"{x.dtype} tiles need ~{block_rows * row_bytes / 2**20:.1f} MiB "
            f"of scoped VMEM and Mosaic refuses past "
            f"{_SCOPED_VMEM_BYTES // 2**20} MiB; pass block_rows <= "
            f"{_SCOPED_VMEM_BYTES // row_bytes // 8 * 8} or use the XLA gelu")
    nb = R // block_rows
    dx, db_part = pl.pallas_call(
        _bias_gelu_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, D), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((nb, 1, D), jnp.float32),
        ],
        interpret=interpret,
    )(x, bias, dy)
    return dx, db_part.sum(axis=(0, 1)).astype(bias.dtype)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def bias_gelu(x: jax.Array, bias: jax.Array, *, block_rows: int = 128,
              interpret: bool = False) -> jax.Array:
    """Fused ``gelu(x + bias)`` (tanh approximation, matching the
    reference's ``gelu_kernels.cu`` polynomial)."""
    lead = x.shape[:-1]
    D = x.shape[-1]
    R = 1
    for s in lead:
        R *= s
    br = min(block_rows, R)
    x2, R0 = _pad_rows(x.reshape(R, D), br)
    return _bias_gelu(x2, bias, br, interpret)[:R0].reshape(*lead, D)


# ---------------------------------------------------------------------------
# Masked / causal attention softmax
# ---------------------------------------------------------------------------

def _softmax_fwd_kernel(s_ref, p_ref, *, causal, block_q, scale, q_offset):
    qi = pl.program_id(1)
    s = s_ref[0].astype(jnp.float32) * scale               # (bq, Sk)
    if causal:
        # bottom-aligned triangle (query i sits at absolute position
        # Sk - Sq + i), matching ops.attention._jnp_attention's tril offset
        q_pos = q_offset + qi * block_q + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(m == NEG_INF, 0.0, m)
    e = jnp.exp(s - m)
    p_ref[0] = (e / e.sum(axis=-1, keepdims=True)).astype(p_ref.dtype)


def _softmax_bwd_kernel(p_ref, dy_ref, ds_ref, *, scale):
    p = p_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    dot = (p * dy).sum(axis=-1, keepdims=True)
    ds_ref[0] = (p * (dy - dot) * scale).astype(ds_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _softmax(s, causal, scale, block_q, q_offset, interpret):
    p, _ = _softmax_fwd(s, causal, scale, block_q, q_offset, interpret)
    return p


def _softmax_fwd(s, causal, scale, block_q, q_offset, interpret):
    BH, Sq, Sk = s.shape
    p = pl.pallas_call(
        functools.partial(_softmax_fwd_kernel, causal=causal,
                          block_q=block_q, scale=scale, q_offset=q_offset),
        grid=(BH, Sq // block_q),
        in_specs=[pl.BlockSpec((1, block_q, Sk), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, block_q, Sk), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Sk), s.dtype),
        interpret=interpret,
    )(s)
    return p, (p,)


def _softmax_bwd(causal, scale, block_q, q_offset, interpret, res, dy):
    (p,) = res
    BH, Sq, Sk = p.shape
    ds = pl.pallas_call(
        functools.partial(_softmax_bwd_kernel, scale=scale),
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, Sk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, Sk), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Sk), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Sk), p.dtype),
        interpret=interpret,
    )(p, dy)
    return (ds,)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


def attention_softmax(scores: jax.Array, *, causal: bool = True,
                      scale: float = 1.0, block_q: int = 128,
                      interpret: bool = False) -> jax.Array:
    """Fused (scaled, causally masked) attention softmax over the last dim.

    ``scores``: ``(..., Sq, Sk)``.  Analog of the reference's
    ``attn_softmax``/triangular-masked softmax kernels.
    """
    lead = scores.shape[:-2]
    Sq, Sk = scores.shape[-2:]
    BH = 1
    for d in lead:
        BH *= d
    s2 = scores.reshape(BH, Sq, Sk)
    bq = min(block_q, Sq)
    rem = Sq % bq
    if rem:
        # pad queries past the bottom of the triangle (fully masked rows
        # come out uniform and are sliced off)
        s2 = jnp.pad(s2, ((0, 0), (0, bq - rem), (0, 0)))
    p = _softmax(s2, causal, scale, bq, Sk - Sq, interpret)
    return p[:, :Sq].reshape(*lead, Sq, Sk)
