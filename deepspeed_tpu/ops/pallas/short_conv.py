"""The double-gated short convolution (``ops/short_conv.py`` has the
equation) on the rows its projection wrote: ``bcu`` (B, S, 3C) holds
``[Bg ; Cg ; u]`` side by side, the kernel reads them where they lie and
writes ``y = Cg * filter(Bg * u)`` (B, S, C); its backward reads ``bcu`` and
``dy`` and writes ``d bcu`` (B, S, 3C) whole plus the taps' gradient.

**Why a kernel.**  The filter reads the ``L - 1`` rows before a position.
In XLA a shift by one or two rows of an ``(8, 128)``-tiled array is no
view: compiled for the v5e each shifted operand became a copy
(``bf16[4,8191,2048]``, ``bf16[4,8190,2048]`` a term) or, with the product
shared, ``Bg * u`` was written out in float32 and read three times, and the
backward kept four ``f32[4,8192,2048]`` of its own (PERF.md section 6,
PR 45).  Here a block of rows is in VMEM once: the product goes to a
float32 scratch that begins with the 8 rows BEFORE the block (a second,
8-row view of the same operand; zeros for a row's first block), a tap is a
sublane roll of a chunk of that scratch, and what moves through HBM is
what the filter requires: 3 vectors in and 1 out forward, 4 in and 3 out
backward.  At ``(4, 8192, 3 x 2048)`` on the v5e, forward + backward: 2.30
ms against 1.80 at the HBM rate; XLA's shifted form 9.03, its depthwise
convolution 19.84 (``chip_smoke.py kernel_short_conv``, PR 45).

The backward needs the rows AFTER a block too (``d z_t = sum_k w_{L-1-k}
dc_{t+k}`` with ``dc = dy * Cg``): a third view, of the 8 rows that follow,
feeds the tail of a second scratch.  The taps' gradient is summed over a
block's own rows in the kernel (one ``(8, C)`` partial a grid step, rows
``0 .. L-1`` used) and over the blocks by XLA.

Inside a block the work goes by chunks of :data:`CHUNK` rows and
:data:`LANES` lanes, so that a chunk with its 8 rows of halo stays in
vector registers.  Arithmetic is float32, rounded once to the operands'
type.  The HLO custom calls are ``short_conv_rows`` and
``short_conv_rows_back``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 8        # rows of the views before and after a block: one sublane tile
CHUNK = 16      # rows worked on at a time
LANES = 512     # lanes worked on at a time
BLOCK = 256     # rows of a block (a grid step)
_VMEM_LIMIT = 48 << 20


def supported(S: int, C: int, L: int, dtype) -> Optional[str]:
    """None where the kernels take the shape, else why not."""
    if C % LANES:
        return f"{C} channels are no multiple of {LANES}"
    if S % BLOCK:
        return f"{S} positions are no multiple of {BLOCK}"
    if not 1 <= L <= HALO:
        return f"{L} taps: 1 to {HALO} are written"
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return f"dtype {jnp.dtype(dtype).name}"
    return None


def _f32(x):
    return x.astype(jnp.float32)


def _taps(w_ref, lanes, L):
    """The taps of a lane block, ``[(1, LANES)] * L``, tap j at [j]."""
    return [_f32(w_ref[pl.ds(j, 1), lanes]) for j in range(L)]


def _fill(buf, C, rows, block, near, skip, near_at, at):
    """``buf[at : at + rows] = block(rows, lane block)`` chunk by chunk and
    ``buf[near_at : near_at + HALO] = near(all 8 rows, lane block)``, zeros
    where ``skip`` (the 8-row view then lies outside the row)."""
    for l0 in range(0, C, LANES):
        lanes = pl.ds(l0, LANES)
        buf[pl.ds(near_at, HALO), lanes] = jnp.where(
            skip, 0.0, near(slice(None), l0))

        def chunk(r, _):
            at_r = pl.multiple_of(r * CHUNK, CHUNK)
            buf[pl.ds(at + at_r, CHUNK), lanes] = block(
                pl.ds(at_r, CHUNK), l0)
            return _

        lax.fori_loop(0, rows // CHUNK, chunk, 0)


def _z(ref, C):
    """``Bg * u`` of rows ``at`` of a (1, rows, 3C) ref, float32."""
    def make(at, l0):
        return _f32(ref[0, at, pl.ds(l0, LANES)]) \
            * _f32(ref[0, at, pl.ds(2 * C + l0, LANES)])
    return make


def _dc(ref, dy_ref, C):
    """``dy * Cg`` of rows ``at``, float32."""
    def make(at, l0):
        return _f32(dy_ref[0, at, pl.ds(l0, LANES)]) \
            * _f32(ref[0, at, pl.ds(C + l0, LANES)])
    return make


def _forward_kernel(bcu_ref, before_ref, w_ref, y_ref, z_buf, *, C, L, rows):
    first = pl.program_id(1) == 0
    _fill(z_buf, C, rows, _z(bcu_ref, C), _z(before_ref, C), first, 0, HALO)
    for l0 in range(0, C, LANES):
        lanes = pl.ds(l0, LANES)
        w = _taps(w_ref, lanes, L)

        def chunk(r, _):
            at_r = pl.multiple_of(r * CHUNK, CHUNK)
            # the chunk's rows behind 8 rows of what came before them
            ext = z_buf[pl.ds(at_r, CHUNK + HALO), lanes]
            c = ext[HALO:] * w[L - 1]
            for back in range(1, L):        # row t of roll(., back) is t - back
                c = c + pltpu.roll(ext, back, 0)[HALO:] * w[L - 1 - back]
            cg = _f32(bcu_ref[0, pl.ds(at_r, CHUNK), pl.ds(C + l0, LANES)])
            y_ref[0, pl.ds(at_r, CHUNK), lanes] = (cg * c).astype(y_ref.dtype)
            return _

        lax.fori_loop(0, rows // CHUNK, chunk, 0)


def _backward_kernel(bcu_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, dbcu_ref, dw_ref, z_buf, dc_buf, *, C, L, rows):
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    _fill(z_buf, C, rows, _z(bcu_ref, C), _z(before_ref, C), first, 0, HALO)
    # dc of the block, then of the 8 rows that follow it
    _fill(dc_buf, C, rows, _dc(bcu_ref, dy_ref, C),
          _dc(after_ref, dy_after_ref, C), last, rows, 0)
    dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
    for l0 in range(0, C, LANES):
        lanes = pl.ds(l0, LANES)
        w = _taps(w_ref, lanes, L)

        def chunk(r, dw):
            at_r = pl.multiple_of(r * CHUNK, CHUNK)
            here = pl.ds(at_r, CHUNK)
            z_ext = z_buf[pl.ds(at_r, CHUNK + HALO), lanes]     # rows before
            dc_ext = dc_buf[pl.ds(at_r, CHUNK + HALO), lanes]   # rows after
            dc = dc_ext[:CHUNK]
            c = z_ext[HALO:] * w[L - 1]
            dz = dc * w[L - 1]
            dw = list(dw)
            dw[L - 1] = dw[L - 1] + (dc * z_ext[HALO:]).reshape(
                CHUNK // 8, 8, LANES).sum(0)
            for k in range(1, L):
                z_back = pltpu.roll(z_ext, k, 0)[HALO:]             # z_{t-k}
                dc_ahead = pltpu.roll(dc_ext, CHUNK + HALO - k, 0)[:CHUNK]
                c = c + z_back * w[L - 1 - k]
                dz = dz + dc_ahead * w[L - 1 - k]                   # dc_{t+k}
                dw[L - 1 - k] = dw[L - 1 - k] + (dc * z_back).reshape(
                    CHUNK // 8, 8, LANES).sum(0)
            bg = _f32(bcu_ref[0, here, lanes])
            u = _f32(bcu_ref[0, here, pl.ds(2 * C + l0, LANES)])
            dy = _f32(dy_ref[0, here, lanes])
            out = dbcu_ref.dtype
            dbcu_ref[0, here, lanes] = (dz * u).astype(out)
            dbcu_ref[0, here, pl.ds(C + l0, LANES)] = (dy * c).astype(out)
            dbcu_ref[0, here, pl.ds(2 * C + l0, LANES)] = (dz * bg).astype(out)
            return tuple(dw)

        dw = lax.fori_loop(0, rows // CHUNK, chunk,
                           (jnp.zeros((8, LANES), jnp.float32),) * L)
        for j in range(L):
            dw_ref[0, pl.ds(j, 1), lanes] = dw[j].sum(0, keepdims=True)


def _views(S, C, rows):
    """BlockSpecs of a (B, S, n*C) operand: the block and the 8 rows before
    and after it (clamped inside the row; the kernels zero what the clamp
    brought)."""
    tiles = rows // HALO

    def spec(width):
        return (pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, HALO, width),
                             lambda b, i: (b, jnp.maximum(i * tiles - 1, 0),
                                           0)),
                pl.BlockSpec((1, HALO, width),
                             lambda b, i: (b, jnp.minimum((i + 1) * tiles,
                                                          S // HALO - 1), 0)))
    return spec


def _padded_taps(w):
    """(C, L) taps as the (8, C) float32 rows the kernels read."""
    C, L = w.shape
    return jnp.zeros((HALO, C), jnp.float32).at[:L].set(_f32(w).T)


def _forward(bcu, w, interpret):
    B, S, C3 = bcu.shape
    C, L = w.shape
    block, before, _ = _views(S, C, BLOCK)(C3)
    return pl.pallas_call(
        functools.partial(_forward_kernel, C=C, L=L, rows=BLOCK),
        grid=(B, S // BLOCK),
        in_specs=[block, before, pl.BlockSpec((HALO, C), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, BLOCK, C), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK + HALO, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (L + 2) * B * S * C, transcendentals=0,
            bytes_accessed=4 * B * S * C * bcu.dtype.itemsize),
        name="short_conv_rows", interpret=interpret,
    )(bcu, bcu, _padded_taps(w))


def _backward(bcu, w, dy, interpret):
    B, S, C3 = bcu.shape
    C, L = w.shape
    steps = S // BLOCK
    views = _views(S, C, BLOCK)
    block, before, after = views(C3)
    dy_block, _, dy_after = views(C)
    dbcu, dw = pl.pallas_call(
        functools.partial(_backward_kernel, C=C, L=L, rows=BLOCK),
        grid=(B, steps),
        in_specs=[block, before, after, dy_block, dy_after,
                  pl.BlockSpec((HALO, C), lambda b, i: (0, 0))],
        out_specs=[pl.BlockSpec((1, BLOCK, C3), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, HALO, C),
                                lambda b, i: (b * steps + i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((B * steps, HALO, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BLOCK + HALO, C), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * (3 * L + 5) * B * S * C, transcendentals=0,
            bytes_accessed=7 * B * S * C * bcu.dtype.itemsize),
        name="short_conv_rows_back", interpret=interpret,
    )(bcu, bcu, bcu, dy, dy, _padded_taps(w))
    return dbcu, dw.sum(0)[:L].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def short_conv_rows(bcu: jax.Array, w: jax.Array,
                    interpret: bool = False) -> jax.Array:
    """``Cg * filter(Bg * u)`` (B, S, C) of ``bcu`` (B, S, 3C) = ``[Bg ; Cg
    ; u]`` and taps ``w`` (C, L) whose last column is the current
    position; :func:`supported` says which shapes."""
    return _forward(bcu, w, interpret)


def _fwd(bcu, w, interpret):
    return _forward(bcu, w, interpret), (bcu, w)


def _bwd(interpret, saved, dy):
    bcu, w = saved
    return _backward(bcu, w, dy, interpret)


short_conv_rows.defvjp(_fwd, _bwd)
