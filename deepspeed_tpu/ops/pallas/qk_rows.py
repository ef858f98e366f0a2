"""What happens to q and k between their projections and the flash kernels,
on the ``(B, S, heads * head_dim)`` rows both of those use: the per-head RMSNorm
(``qk_norm="head"``) and the half-split rotary embedding, one pass over q
and k together.

**Why a row form.**  ``ops/rotary.py apply_rotary`` works in the
``(B, S, H, D)`` view.  On the chip that view is no bitcast of the rows the
projection wrote and the flash kernels read (lanes are tiled by 128 and
XLA keeps activations feature-major), so each pass paid a ``reshape`` copy
in and one out, and the rotation itself - two 64-lane halves sliced,
multiplied in bf16 and concatenated - ran at about a fifth of the HBM rate
(PERF.md section 6, PR 34).  Here a head is one block of ``head_dim`` lanes
of the row (a multiple of 128):

    out = x * (cos || cos) + roll(x, head_dim / 2) * (-sin || sin)

one lane rotation by half a head, two multiplies and an add, in float32,
rounded once to the operand's dtype.  Its transpose is the same expression
with the sine negated, so the backward pass is this kernel again.  The
angles arrive as one ``(B, S, head_dim)`` float32 table ``cos || sin`` (a
scaling rule's factor in it); a chunk of rows widens it to the two factors
above with one roll and two selects, shared by all its heads.  With
``q_scale`` / ``k_scale`` (head_dim,) each head is first normalised as
``models/common.py rms_norm`` does it (mean of squares over the head's lanes,
float32: :func:`_mean`); the backward pass then reads the projection's rows again
and gives dx and the scales' gradients, summed a grid step here and over
the steps by XLA.  Without a table the pass only normalises (a layer type
that carries no position).

Blocks are whole rows, ``(1, rows, heads * head_dim)``; inside, a loop over
chunks of :data:`CHUNK` rows keeps the chunk's cos and sin in registers
across the heads.  (Results that take their operand's buffer were tried:
XLA then copies the operand first, 4 x ``bf16[4,8192,4096]`` a step of
Mellum 2 and +0.26 GB of temporaries, compiled for a described v5e.)  The HLO custom calls are ``qk_rows`` and
``qk_rows_back``.  Both sit behind ``jax.jit``, as the row kernels of
``moe_rows.py`` do: a model traces each body once a signature and tracing
context, not once a layer and remat pass (``qk_rows_traces_total``
counts), and lowers it once a module; inside a body the arithmetic of one
head sits behind a ``jit`` of its own, traced once for all the heads.

**Who else normalises here** (PR 53).  A Gated DeltaNet layer
(``models/llama.py GatedDeltaNet``) has two per-head norms between kernels
that read and write rows, and wrote both on the ``(B, S, H, d)`` float32
view, where each cost a ``copy`` into the 4-D tiling and a ``reshape``
back.  Its l2-norms of q and k are this file's norm under constant scales
and no table (``x / |x| = rms_norm(x, d^-1/2, eps / d)``: no new body).
Its output norm, ``rms_norm(o, w, eps) * silu(z)`` a value head, is the
body :func:`_gated_kernel` beside the two above: the same blocks, chunks
and :func:`_mean`, one more operand (the gate ``z``), custom calls
``gated_norm_rows`` and ``gated_norm_rows_back`` (:func:`gated_norm_rows`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry
from .short_conv import _dsilu, _silu

# rows of a block that share one load of cos and sin
CHUNK = 32
# bytes of blocks a grid step may hold, double-buffered (Mosaic's default
# scoped VMEM is 16 MB)
_VMEM_BLOCKS = 10 << 20


def block_rows(seq: int, width: int, itemsize: int, arrays: int
               ) -> Optional[int]:
    """Rows a block: the most that divide ``seq`` and keep ``arrays``
    double-buffered ``(rows, width)`` blocks under the budget; None where
    no whole number of chunks divides ``seq``."""
    for rows in (1024, 512, 256, 128, 64, 32):
        if seq % rows == 0 and \
                2 * arrays * rows * width * itemsize <= _VMEM_BLOCKS:
            return rows
    return None


def _refusal(seq: int, width: int, dtype, arrays: int) -> Optional[str]:
    """``None`` where a pass over ``arrays`` rows of ``width`` lanes has a
    block, else why not."""
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return f"rows of {jnp.dtype(dtype).name}"
    if block_rows(seq, width, jnp.dtype(dtype).itemsize, arrays) is None:
        return f"sequence {seq} is no whole number of {CHUNK}-row chunks"
    return None


def supported(seq: int, q_width: int, k_width: int, dtype,
              norm: bool = False) -> Optional[str]:
    """``None`` where the kernels take rows of these widths, else why not."""
    return _refusal(seq, q_width + k_width, dtype, 3 if norm else 2)


def _note_trace(kernel: str, *signature) -> None:
    """Count, at trace time, one entry into a kernel's builder."""
    _registry.counter(
        "qk_rows_traces_total",
        "times a qk_rows kernel body was traced, by kernel and signature "
        "(once a process, signature and tracing context: the calls sit "
        "behind jax.jit)",
        labelnames=("kernel", "signature")).labels(
            kernel, " ".join(str(s) for s in signature)).inc()


def _mean(x, ones, terms: int):
    """Mean over a head's lanes of ``x`` (rows, D) float32, in every lane:
    a product with a matrix of ones on the MXU, which idles here, ``x``
    split into ``terms`` bfloat16 parts (8 bits of mantissa each: two hold
    a product of two bfloat16 values exactly, three a float32) and summed
    in float32.  The lane reduction ``jnp.mean(axis=1)`` it replaces ran
    the norm's forward pass at 3.18 ms and its backward at 3.52 where this
    takes 0.66 and 0.99 (v5e, Trinity's 3 rows of 8192, PR 34)."""
    total = None
    for _ in range(terms):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        dot = jnp.dot(part, ones, preferred_element_type=jnp.float32)
        total = dot if total is None else total + dot
    return total * (1.0 / ones.shape[0])


def _turn(x, cos, sin):
    return x * cos + pltpu.roll(x, x.shape[1] // 2, 1) * sin


def _kernel(*refs, head_dim: int, rotate: bool, norm: bool, backward: bool,
            eps: float):
    """One block of rows of q and of k.  ``refs``: the table (rotate),
    then q and k - the cotangents of the outputs when ``backward`` - then
    the two scales (norm), then the projection's own q and k (norm and
    backward); outputs the two results and, for norm and backward, the two
    scale gradients of this block."""
    refs = list(refs)
    table_ref = refs.pop(0) if rotate else None
    ins = [refs.pop(0), refs.pop(0)]
    scales = [refs.pop(0), refs.pop(0)] if norm else [None, None]
    xs = [refs.pop(0), refs.pop(0)] if norm and backward else [None, None]
    outs = [refs.pop(0), refs.pop(0)]
    dscales = refs if norm and backward else [None, None]
    rows = ins[0].shape[1]
    ones = jnp.ones((head_dim, head_dim), jnp.bfloat16) if norm else None
    terms = 2 if ins[0].dtype == jnp.bfloat16 else 3

    @jax.jit
    def head(x, x_in, cos, sin, scale, total):
        """A head's chunk: ``x`` in (the cotangent when ``backward``) and
        the result out, float32; ``total`` gathers the scale's gradient.
        Behind ``jit`` so that a kernel body traces it once, not once a
        head: the heads stay unrolled (a loop over them ran the norm's
        passes at half the speed, v5e) and tracing is what they cost."""
        if not backward:
            if norm:
                x = x * lax.rsqrt(_mean(x * x, ones, terms) + eps) * scale
            return (_turn(x, cos, sin) if rotate else x), total
        g = _turn(x, cos, sin) if rotate else x
        if norm:
            inv = lax.rsqrt(_mean(x_in * x_in, ones, terms) + eps)
            unit = x_in * inv
            total = total + g * unit
            g = g * scale
            g = inv * (g - unit * _mean(g * unit, ones, terms))
        return g, total

    def chunk(i, sums):
        r = pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)
        cos = sin = None
        if rotate:      # cos || sin -> cos || cos and -sin || sin
            table = table_ref[0, r, :]
            turned = pltpu.roll(table, head_dim // 2, 1)
            first = lax.broadcasted_iota(jnp.int32, table.shape, 1) \
                < head_dim // 2
            cos = jnp.where(first, table, turned)
            sin = jnp.where(first, turned, -table) if backward \
                else jnp.where(first, -turned, table)
        sums = list(sums)
        for n, (in_ref, out_ref) in enumerate(zip(ins, outs)):
            scale = scales[n][...] if norm else None    # (1, head_dim)
            for h in range(in_ref.shape[2] // head_dim):
                at = (0, r, pl.ds(h * head_dim, head_dim))
                x_in = xs[n][at].astype(jnp.float32) if sums else None
                out, total = head(in_ref[at].astype(jnp.float32), x_in, cos,
                                  sin, scale, sums[n] if sums else None)
                out_ref[at] = out.astype(out_ref.dtype)
                if sums:
                    sums[n] = total
        return tuple(sums)

    zero = jnp.zeros((CHUNK, head_dim), jnp.float32)
    sums = lax.fori_loop(0, rows // CHUNK, chunk,
                         (zero, zero) if norm and backward else ())
    for ref, total in zip(dscales, sums):
        ref[0] = total.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "eps", "backward", "interpret"))
def rows_call(q, k, table=None, q_scale=None, k_scale=None,
              q_in=None, k_in=None, *, head_dim: int, eps: float = 0.0,
              backward: bool = False, interpret: bool = False):
    """The pass over ``q`` (B, S, H*D) and ``k`` (B, S, KV*D), D =
    ``head_dim``.  Forward:
    ``(q', k')``, each head normalised under ``q_scale`` / ``k_scale``
    (D,) float32 where given and turned by ``table`` (B or 1, S, D) float32
    (``cos || sin``) where given.  ``backward``:
    q and k are the cotangents of those results and ``q_in`` / ``k_in``
    what the forward read (only the norm needs them); ``(dq, dk)`` and,
    with scales, ``(dq, dk, dq_scale, dk_scale)``."""
    B, S, _ = q.shape
    rotate, norm = table is not None, q_scale is not None
    _note_trace("back" if backward else "fwd", q.shape, k.shape,
                q.dtype.name, rotate, norm)
    reread = norm and backward
    rows = block_rows(S, q.shape[2] + k.shape[2], q.dtype.itemsize,
                      3 if reread else 2)
    row_block = lambda a: pl.BlockSpec((1, rows, a.shape[2]),
                                       lambda b, i: (b, i, 0))
    whole = pl.BlockSpec((1, head_dim), lambda b, i: (0, 0))
    operands, in_specs = [], []
    if rotate:      # one row of positions serves every row of the batch
        if table.shape[0] not in (1, B):
            raise ValueError(f"a table of {table.shape[0]} rows for {B}")
        shared = table.shape[0] == 1
        operands.append(table)
        in_specs.append(pl.BlockSpec(
            (1, rows, head_dim), lambda b, i: (0 if shared else b, i, 0)))
    operands += [q, k]
    in_specs += [row_block(q), row_block(k)]
    if norm:
        operands += [q_scale.astype(jnp.float32).reshape(1, head_dim),
                     k_scale.astype(jnp.float32).reshape(1, head_dim)]
        in_specs += [whole, whole]
    if reread:
        operands += [q_in, k_in]
        in_specs += [row_block(q), row_block(k)]
    out_specs = [row_block(q), row_block(k)]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct(k.shape, k.dtype)]
    steps = S // rows
    if reread:      # a grid step's own sums; XLA adds the steps up
        part = pl.BlockSpec((1, 1, head_dim),
                            lambda b, i: (b * steps + i, 0, 0))
        out_specs += [part, part]
        out_shape += [jax.ShapeDtypeStruct((B * steps, 1, head_dim),
                                           jnp.float32)] * 2
    elements = q.size + k.size
    moved = sum(a.size * a.dtype.itemsize for a in operands) \
        + elements * q.dtype.itemsize
    outs = pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim, rotate=rotate,
                          norm=norm, backward=backward, eps=eps),
        grid=(B, steps), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(4 * rotate + 10 * norm) * elements,
            transcendentals=norm * elements // head_dim,
            bytes_accessed=moved),
        name="qk_rows_back" if backward else "qk_rows",
        interpret=interpret,
    )(*operands)
    if not reread:
        return tuple(outs)
    dq, dk, dq_scale, dk_scale = outs
    return dq, dk, dq_scale.sum(axis=(0, 1)), dk_scale.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def qk_rows(q, k, table, q_scale, k_scale, head_dim: int,
            eps: float = 0.0, interpret: bool = False):
    """``(q', k')`` of :func:`rows_call`, differentiable in q, k and the
    scales.  ``table`` and the scales (both or neither) may be None."""
    return rows_call(q, k, table, q_scale, k_scale, head_dim=head_dim,
                     eps=eps, interpret=interpret)


def _qk_rows_fwd(q, k, table, q_scale, k_scale, head_dim, eps, interpret):
    out = qk_rows(q, k, table, q_scale, k_scale, head_dim, eps, interpret)
    kept = (q, k) if q_scale is not None else (None, None)
    return out, (table, q_scale, k_scale, *kept)


def _qk_rows_bwd(head_dim, eps, interpret, res, g):
    table, q_scale, k_scale, q, k = res
    grads = rows_call(*g, table, q_scale, k_scale, q, k,
                      head_dim=head_dim, eps=eps, backward=True,
                      interpret=interpret)
    dq, dk = grads[:2]
    if q_scale is None:
        return dq, dk, None, None, None
    return (dq, dk, None, grads[2].astype(q_scale.dtype),
            grads[3].astype(k_scale.dtype))


qk_rows.defvjp(_qk_rows_fwd, _qk_rows_bwd)


# -- rms_norm(o, w, eps) * silu(z), a value head of a Gated DeltaNet ---------

def gated_norm_supported(seq: int, width: int, dtype) -> Optional[str]:
    """``None`` where :func:`gated_norm_rows` takes rows of this width,
    else why not (the backward's five blocks decide)."""
    return _refusal(seq, width, dtype, 5)


def _gated_kernel(*refs, head_dim: int, backward: bool, eps: float):
    """One block of rows of ``o`` and of the gate ``z``, then the scale
    ``w`` (1, head_dim); forward the result ``y``; ``backward`` the
    cotangent ``dy`` in, ``do``, ``dz`` and this block's ``dw`` out."""
    o_ref, z_ref, w_ref, *rest = refs
    dy_ref = rest.pop(0) if backward else None
    rows = o_ref.shape[1]
    ones = jnp.ones((head_dim, head_dim), jnp.bfloat16)
    terms = 2 if o_ref.dtype == jnp.bfloat16 else 3

    @jax.jit
    def head(o, z, w, dy, total):
        """A head's chunk in float32, traced once for all the heads."""
        inv = lax.rsqrt(_mean(o * o, ones, terms) + eps)
        unit = o * inv
        if not backward:
            return (unit * w * _silu(z),), total
        g = dy * _silu(z)                   # the norm's cotangent
        dz = dy * (unit * w) * _dsilu(z)
        total = total + g * unit
        g = g * w
        return (inv * (g - unit * _mean(g * unit, ones, terms)), dz), total

    def chunk(i, total):
        r = pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)
        w = w_ref[...]
        for h in range(o_ref.shape[2] // head_dim):
            at = (0, r, pl.ds(h * head_dim, head_dim))
            outs, total = head(
                o_ref[at].astype(jnp.float32), z_ref[at].astype(jnp.float32),
                w, dy_ref[at].astype(jnp.float32) if backward else None,
                total)
            for ref, out in zip(rest, outs):
                ref[at] = out.astype(ref.dtype)
        return total

    total = lax.fori_loop(
        0, rows // CHUNK, chunk,
        jnp.zeros((CHUNK, head_dim), jnp.float32) if backward else None)
    if backward:
        rest[2][0] = total.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("head_dim", "eps", "interpret"))
def gated_norm_call(o, z, w, dy=None, *, head_dim: int, eps: float,
                    interpret: bool = False):
    """``y = rms_norm(o, w, eps) * silu(z)`` over each head of ``head_dim``
    lanes of ``o`` and ``z`` (B, S, H * head_dim), ``w`` (head_dim,);
    with the cotangent ``dy``: ``(do, dz, dw)``, ``dw`` float32."""
    B, S, width = o.shape
    if z.shape != o.shape or z.dtype != o.dtype:
        raise ValueError(f"a gate of {z.shape} {z.dtype.name} for rows of "
                         f"{o.shape} {o.dtype.name}")
    if width % head_dim:
        raise ValueError(f"rows of {width} lanes are no heads of {head_dim}")
    backward = dy is not None
    _note_trace("gated_norm_back" if backward else "gated_norm", o.shape,
                o.dtype.name)
    rows = block_rows(S, width, o.dtype.itemsize, 5 if backward else 3)
    steps = S // rows
    row_block = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    rows_out = jax.ShapeDtypeStruct(o.shape, o.dtype)
    operands = [o, z, w.astype(jnp.float32).reshape(1, head_dim)]
    in_specs = [row_block, row_block,
                pl.BlockSpec((1, head_dim), lambda b, i: (0, 0))]
    out_specs, out_shape = [row_block], [rows_out]
    if backward:    # a grid step's own sum of dw; XLA adds the steps up
        operands.append(dy)
        in_specs.append(row_block)
        out_specs += [row_block, pl.BlockSpec(
            (1, 1, head_dim), lambda b, i: (b * steps + i, 0, 0))]
        out_shape += [rows_out, jax.ShapeDtypeStruct(
            (B * steps, 1, head_dim), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_gated_kernel, head_dim=head_dim,
                          backward=backward, eps=eps),
        grid=(B, steps), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(40 if backward else 16) * o.size,
            transcendentals=o.size + o.size // head_dim,
            bytes_accessed=(5 if backward else 3) * o.size
            * o.dtype.itemsize),
        name="gated_norm_rows_back" if backward else "gated_norm_rows",
        interpret=interpret,
    )(*operands)
    if not backward:
        return outs[0]
    do, dz, dw = outs
    return do, dz, dw.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gated_norm_rows(o, z, w, head_dim: int, eps: float,
                    interpret: bool = False):
    """``rms_norm(o, w, eps) * silu(z)`` a head of :func:`gated_norm_call`,
    differentiable in ``o``, ``z`` and ``w``."""
    return gated_norm_call(o, z, w, head_dim=head_dim, eps=eps,
                           interpret=interpret)


def _gated_norm_fwd(o, z, w, head_dim, eps, interpret):
    return gated_norm_rows(o, z, w, head_dim, eps, interpret), (o, z, w)


def _gated_norm_bwd(head_dim, eps, interpret, res, dy):
    o, z, w = res
    do, dz, dw = gated_norm_call(o, z, w, dy, head_dim=head_dim, eps=eps,
                                 interpret=interpret)
    return do, dz, dw.astype(w.dtype)


gated_norm_rows.defvjp(_gated_norm_fwd, _gated_norm_bwd)
