"""Fused transformer-MLP Pallas kernel: ``gelu(x @ w1 + b1) @ w2 + b2``.

The reference fuses the FFN pair with bias-gelu between GEMMs in its
training kernel (``csrc/transformer/ds_transformer_cuda.cpp`` feed-forward
+ ``gelu_kernels.cu``).  On TPU the motivation is HBM traffic: XLA computes
the pair as two HLO matmuls with the ``(tokens, 4·E)`` hidden activation
round-tripping HBM between them — at 125M-model shapes that is 2×75 MB per
layer per direction, and measured on the bench chip the MLP runs ~4× slower
than its flop count warrants.  This kernel tiles over token rows, keeps the
hidden tile resident in VMEM, and streams both weight panels once per grid
pass.

Backward recomputes the hidden tile per row-block (flash-attention-style
rematerialization in VMEM) and accumulates ``dw1/dw2/db1/db2`` across the
sequential TPU grid into shared output blocks.

``interpret=True`` runs on CPU for tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SQRT_2_OVER_PI = 0.7978845608028654


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


def _fwd_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, y_ref):
    # biases travel as (1, F)/(1, E): 1-D operands get 1024-lane Mosaic
    # tiling that rejects odd block sizes
    x = x_ref[...]
    u = jax.lax.dot_general(
        x, w1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b1_ref[0].astype(jnp.float32)
    h = _gelu_tanh(u).astype(x.dtype)
    y = jax.lax.dot_general(
        h, w2_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b2_ref[0].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_dx_kernel(x_ref, w1_ref, b1_ref, w2_ref, dy_ref, dx_ref):
    # grid (nr, nf): row tile OUTER so dx accumulates over CONSECUTIVE
    # inner-f iterations (TPU output blocks are undefined on
    # non-consecutive revisits — accumulation must ride the innermost dim)
    fi = pl.program_id(1)
    x = x_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    u = jax.lax.dot_general(
        x, w1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b1_ref[0].astype(jnp.float32)
    dh = jax.lax.dot_general(
        dy, w2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    du = dh * _gelu_tanh_grad(u)
    dx = jax.lax.dot_general(
        du.astype(x.dtype), w1_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dx_ref.dtype)

    @pl.when(fi == 0)
    def _init():
        dx_ref[...] = dx

    @pl.when(fi != 0)
    def _acc():
        dx_ref[...] += dx


def _bwd_dw_kernel(x_ref, w1_ref, b1_ref, w2_ref, dy_ref,
                   dw1_ref, db1_ref, dw2_ref, db2_ref):
    # grid (nf, nr): f tile OUTER so dw/db accumulate over consecutive
    # inner-r iterations; u/h recomputed per tile (VMEM remat)
    fi = pl.program_id(0)
    ri = pl.program_id(1)
    x = x_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    u = jax.lax.dot_general(
        x, w1_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b1_ref[0].astype(jnp.float32)
    h = _gelu_tanh(u)
    dh = jax.lax.dot_general(
        dy, w2_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    du = dh * _gelu_tanh_grad(u)
    xf = x.astype(jnp.float32)
    dw1_tile = jax.lax.dot_general(xf, du, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    dw2_tile = jax.lax.dot_general(h, dy, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    @pl.when(ri == 0)
    def _w_init():
        dw1_ref[...] = dw1_tile
        db1_ref[...] = du.sum(axis=0, keepdims=True)
        dw2_ref[...] = dw2_tile

    @pl.when(ri != 0)
    def _w_acc():
        dw1_ref[...] += dw1_tile
        db1_ref[...] += du.sum(axis=0, keepdims=True)
        dw2_ref[...] += dw2_tile

    # db2 = sum_rows(dy) is f-independent: accumulate on the first f-pass only
    @pl.when(jnp.logical_and(fi == 0, ri == 0))
    def _db2_init():
        db2_ref[...] = dy.sum(axis=0, keepdims=True)

    @pl.when(jnp.logical_and(fi == 0, ri != 0))
    def _db2_acc():
        db2_ref[...] += dy.sum(axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_mlp(x, w1, b1, w2, b2, block_rows, interpret):
    y, _ = _fused_mlp_fwd(x, w1, b1, w2, b2, block_rows, interpret)
    return y


def _fused_mlp_fwd(x, w1, b1, w2, b2, block_rows, interpret):
    R, E = x.shape
    F = w1.shape[1]
    y = pl.pallas_call(
        _fwd_kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
            pl.BlockSpec((E, F), lambda i: (0, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
            pl.BlockSpec((F, E), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, E), x.dtype),
        interpret=interpret,
    )(x, w1, b1[None, :], w2, b2[None, :])
    return y, (x, w1, b1, w2)


_BWD_VMEM_BUDGET = 5 * 1024 * 1024   # module-level so tests can force tiling


def _pick_block_f(e: int, f: int, itemsize: int) -> int:
    """Largest divisor-of-F hidden tile whose w-slices + fp32 dw
    accumulators fit the budget (Pallas double-buffers row-varying blocks,
    so budget ~1/3 of the 16MB scoped VMEM).  Must DIVIDE F — a partial
    tail tile would silently drop hidden columns."""
    block_f = f
    while block_f > 128 and 2 * e * block_f * (4 + itemsize) > _BWD_VMEM_BUDGET:
        if block_f % 2:
            break
        block_f //= 2
    if f % block_f:
        raise ValueError(
            f"fused_mlp backward: no VMEM-sized tile divides hidden dim {f}"
            " — use the unfused path for this shape")
    return block_f


def _fused_mlp_bwd(block_rows, interpret, res, dy):
    x, w1, b1, w2 = res
    R, E = x.shape
    F = w1.shape[1]
    block_f = _pick_block_f(E, F, w1.dtype.itemsize)
    br = min(block_rows, 128)
    while R % br:
        br //= 2
    nf, nr = F // block_f, R // br
    b1_2d = b1[None, :]

    # dx: row tile outer, f inner (dx accumulates over consecutive f)
    dx = pl.pallas_call(
        _bwd_dx_kernel,
        grid=(nr, nf),
        in_specs=[
            pl.BlockSpec((br, E), lambda r, f: (r, 0)),
            pl.BlockSpec((E, block_f), lambda r, f: (0, f)),
            pl.BlockSpec((1, block_f), lambda r, f: (0, f)),
            pl.BlockSpec((block_f, E), lambda r, f: (f, 0)),
            pl.BlockSpec((br, E), lambda r, f: (r, 0)),
        ],
        out_specs=pl.BlockSpec((br, E), lambda r, f: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, E), x.dtype),
        interpret=interpret,
    )(x, w1, b1_2d, w2, dy)

    # dw/db: f tile outer, rows inner (dw accumulates over consecutive r)
    dw1, db1, dw2, db2 = pl.pallas_call(
        _bwd_dw_kernel,
        grid=(nf, nr),
        in_specs=[
            pl.BlockSpec((br, E), lambda f, r: (r, 0)),
            pl.BlockSpec((E, block_f), lambda f, r: (0, f)),
            pl.BlockSpec((1, block_f), lambda f, r: (0, f)),
            pl.BlockSpec((block_f, E), lambda f, r: (f, 0)),
            pl.BlockSpec((br, E), lambda f, r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((E, block_f), lambda f, r: (0, f)),
            pl.BlockSpec((1, block_f), lambda f, r: (0, f)),
            pl.BlockSpec((block_f, E), lambda f, r: (f, 0)),
            pl.BlockSpec((1, E), lambda f, r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((E, F), jnp.float32),
            jax.ShapeDtypeStruct((1, F), jnp.float32),
            jax.ShapeDtypeStruct((F, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        interpret=interpret,
    )(x, w1, b1_2d, w2, dy)
    return (dx, dw1.astype(w1.dtype), db1[0].astype(b1.dtype),
            dw2.astype(w2.dtype), db2[0])


_fused_mlp.defvjp(_fused_mlp_fwd, _fused_mlp_bwd)


def fused_mlp(x: jax.Array, w1: jax.Array, b1: jax.Array,
              w2: jax.Array, b2: jax.Array, *, block_rows: int = 256,
              interpret: bool = False) -> jax.Array:
    """``gelu(x @ w1 + b1) @ w2 + b2`` with the hidden kept in VMEM.

    ``x``: ``(..., E)``; ``w1``: ``(E, F)``; ``w2``: ``(F, E)``.
    Returns ``(..., E)`` in ``x.dtype``.  ``db2`` accumulates fp32 and is
    cast by the caller's autodiff machinery.
    """
    lead = x.shape[:-1]
    E = x.shape[-1]
    R = 1
    for s in lead:
        R *= s
    br = min(block_rows, R)
    x2, R0 = _pad_rows(x.reshape(R, E), br)
    y = _fused_mlp(x2, w1, b1, w2, b2.astype(jnp.float32), br, interpret)
    return y[:R0].reshape(*lead, E)


def fits_vmem(e: int, f: int, block_rows: int, itemsize: int) -> bool:
    """Both weight panels + hidden/x tiles must fit VMEM (~16MB/core).

    Weight blocks have a constant index map, so Mosaic keeps ONE buffer for
    them; only the row-varying tiles are double-buffered."""
    weights = 2 * e * f * itemsize
    tiles = block_rows * (f * (4 + itemsize)       # u fp32 + h in x.dtype
                          + 2 * 2 * e * itemsize)  # x/y double-buffered
    return weights + tiles <= 15 * 1024 * 1024


def fused_mlp_spmd(x, w1, b1, w2, b2, *, block_rows: int = 128,
                   interpret: bool = False):
    """SPMD dispatch for :func:`fused_mlp`: on a multi-device mesh the
    pallas_call is opaque to the partitioner, so shard_map it over the
    batch axes with replicated weights (requires tp == 1; under ZeRO-3 the
    per-layer weight all-gather happens at the shard_map boundary, exactly
    where XLA would put it anyway).  Returns None when the mesh shards
    something this kernel cannot handle (caller takes the XLA path); past
    that guard the kernel's errors propagate.  Dispatch policy (pp/sp/tp
    guards, no-mesh multi-device) lives in :mod:`.spmd`."""
    from . import spmd

    # its callers ask for a TPU (or the interpreter) before they come here
    plan = spmd.plan("fused_mlp", x.shape[0], None, "both matmuls fused",
                     kernel="kernel", tpu=False)
    if plan is None:
        return None
    return spmd.over_batch(
        functools.partial(fused_mlp, block_rows=block_rows,
                          interpret=interpret),
        plan, (x, w1, b1, w2, b2), whole=(1, 2, 3, 4))
