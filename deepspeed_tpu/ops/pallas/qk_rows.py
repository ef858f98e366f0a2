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

**Heads that are no whole lane tiles** (PR 55; Olmo-Hybrid's keys of 96 and
values of 192 channels).  The delta rule's kernels read such a head from
the first lane of a SLOT of the next multiple of 128 lanes, zeros behind
it; XLA made the slots by a pad through a 4-D reshape and cut ``o`` and
three cotangents back, inside the rule's row loop, and both norms kept the
4-D view.  Here the slots are what the kernels write and read: ``128 /
gcd(d, 128)`` heads side by side are whole lane tiles (a period: four
heads of 96, two of 192), a kernel walks its rows a period at a time and
moves the period's heads to their slots, or back, in registers
(:func:`_spread`, :func:`_pack`: whole tiles loaded and stored, one select
and one lane rotation a tile), and a norm over a slot whose lanes behind
the head are zero is the norm over the head (a zero lane adds nothing to
the sum and stays zero).  :func:`slot_rows` (custom calls ``slot_rows`` /
``slot_rows_back``) reads the filter's ``[q | k | v]`` rows once and writes
``q / |q| dk^-1/2``, ``k / |k|`` and ``v`` into slots; the gated norm's body
takes ``o`` a head a slot beside ``z`` and ``y`` a head every ``head_dim``
lanes - where a head is whole tiles a slot is the head, and it is the
kernel it was.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry
from .gated_delta import _LANES as LANES, _slot as slot
from .short_conv import _dsilu, _silu

# rows of a block that share one load of cos and sin
CHUNK = 32
# rows of a block that a period of heads in lane slots goes through at once:
# a period is a loop's step, its latency is paid a step, and the step's time
# went as 1 / rows (slot_rows at (2, 8192) rows of 11,520 lanes, v5e: 4.77,
# 2.56 and 1.48 ms at 16, 32 and 64 rows; PR 55)
SLOT_ROWS = 64
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


def _mean(x, ones, terms: int, live: Optional[int] = None):
    """Mean over a head's lanes of ``x`` (rows, D) float32, in every lane:
    a product with a matrix of ones on the MXU, which idles here, ``x``
    split into ``terms`` bfloat16 parts (8 bits of mantissa each: two hold
    a product of two bfloat16 values exactly, three a float32) and summed
    in float32.  The lane reduction ``jnp.mean(axis=1)`` it replaces ran
    the norm's forward pass at 3.18 ms and its backward at 3.52 where this
    takes 0.66 and 0.99 (v5e, Trinity's 3 rows of 8192, PR 34).  Of a slot
    whose lanes behind the head's ``live`` channels are zero, the mean over
    those channels."""
    total = None
    for _ in range(terms):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        dot = jnp.dot(part, ones, preferred_element_type=jnp.float32)
        total = dot if total is None else total + dot
    return total * (1.0 / (live or ones.shape[0]))


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


# -- heads that are no whole lane tiles: lane slots (PR 55) -------------------
#
# A head of d channels that is no multiple of 128 (Olmo-Hybrid's keys of 96,
# values of 192) lies in the rows a projection or the filter wrote at lane
# h * d, across tile boundaries, and in the delta rule's kernels from the
# first lane of a SLOT of slot(d) lanes (``gated_delta.py``), zeros behind
# it.  ``128 / gcd(d, 128)`` such heads side by side fill whole lane tiles
# (a PERIOD: four heads of 96 in three tiles, two of 192 in three): a
# kernel walks the rows a period at a time (a loop, the lane offset a
# multiple of 128 known at run time, so that a body is traced and lowered
# for one period and not for thirty heads), loads and stores whole tiles,
# and the two helpers below move the period's heads between the two
# layouts: one select and one lane rotation a tile.  The period's heads go
# through the arithmetic one below the other, SLOT_ROWS rows of each: a
# loop's steps do not overlap, so a step has to be wide.


def _slot_rows(rows: int) -> int:
    """Rows of a block of ``rows`` that go through a period's arithmetic
    at once."""
    return SLOT_ROWS if rows % SLOT_ROWS == 0 else CHUNK


def _period(d: int) -> int:
    """Heads of ``d`` channels that fill whole lane tiles side by side."""
    return LANES // math.gcd(d, LANES)


def _spread(x, d: int, lane):
    """The heads of ``d`` channels that lie side by side in ``x`` (rows,
    n * d) float32, whole lane tiles: each as ``(rows, slot(d))``, zeros
    behind its ``d`` channels."""
    tile = lambda t: x[:, t * LANES:(t + 1) * LANES]
    heads = []
    for h in range(x.shape[1] // d):
        first, shift = divmod(h * d, LANES)
        tiles = []
        for j in range(slot(d) // LANES):
            t, live = tile(first + j), min(d - j * LANES, LANES)
            if shift:   # the tile's head lanes, then those of the next one
                if LANES - shift < live:
                    t = jnp.where(lane >= shift, t, tile(first + j + 1))
                t = pltpu.roll(t, LANES - shift, 1)
            tiles.append(t if live == LANES else jnp.where(lane < live, t,
                                                           0.0))
        heads.append(tiles[0] if len(tiles) == 1
                     else jnp.concatenate(tiles, axis=1))
    return heads


def _pack(heads, d: int, lane):
    """:func:`_spread` back: ``(rows, n * d)`` of ``n`` heads ``(rows,
    slot(d))`` float32; what lies behind a head's ``d`` channels is not
    read."""
    tiles, open_tile, filled = [], None, 0      # lanes written so far
    for h, x in enumerate(heads):
        shift = (h * d) % LANES
        for j in range(slot(d) // LANES):
            piece = x[:, j * LANES:(j + 1) * LANES]
            if shift:
                piece = pltpu.roll(piece, shift, 1)
            end = h * d + min(d, (j + 1) * LANES)   # of this piece's lanes
            while filled < end:
                lo = filled % LANES
                open_tile = piece if lo == 0 else jnp.where(
                    lane >= lo, piece, open_tile)
                filled = min(end, filled - lo + LANES)
                if filled % LANES == 0:
                    tiles.append(open_tile)
    assert filled == len(tiles) * LANES, (filled, d, len(heads))
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


class Heads(NamedTuple):
    """The heads of a Gated DeltaNet layer's filtered rows ``[q | k | v]``:
    ``keys`` key heads of ``dk`` channels (q, then k), ``values`` value
    heads of ``dv``."""
    keys: int
    dk: int
    values: int
    dv: int

    @property
    def width(self) -> int:
        return 2 * self.keys * self.dk + self.values * self.dv

    @property
    def key_slots(self) -> int:
        return self.keys * slot(self.dk)

    @property
    def value_slots(self) -> int:
        return self.values * slot(self.dv)


def slot_rows_supported(seq: int, heads: Heads, dtype) -> Optional[str]:
    """``None`` where :func:`slot_rows` takes the filtered rows of these
    heads, else why not (the backward's blocks decide)."""
    keys, values = 2 * heads.keys * heads.dk, heads.values * heads.dv
    if keys % LANES or values % LANES:      # no whole periods of heads
        return (f"[q | k] of {keys} lanes and v of {values} are no whole "
                f"lane tiles")
    return _refusal(seq, 2 * (heads.width + heads.key_slots)
                    + heads.value_slots, dtype, 1)


def _at(start, size: int):
    """``size`` lanes from ``start``, a multiple of 128 known at run time
    (a loop's index times a period's lanes) or before."""
    return pl.ds(start if isinstance(start, int)
                 else pl.multiple_of(start, LANES), size)


def _slot_kernel(*refs, heads: Heads, backward: bool, eps: float):
    """One block of the filtered rows ``x``; forward the slotted ``q / |q|
    dk^-1/2``, ``k / |k|`` and ``v`` out; ``backward`` their three
    cotangents in and the rows' cotangent out."""
    x_ref, *rest = refs
    q_ref, k_ref, v_ref = rest[:3]
    dx_ref = rest[3] if backward else None
    Hk, dk, Hv, dv = heads
    sk, sv, pk, pv = slot(dk), slot(dv), _period(dk), _period(dv)
    ones = jnp.ones((sk, sk), jnp.bfloat16)
    terms = 2 if x_ref.dtype == jnp.bfloat16 else 3
    R = _slot_rows(x_ref.shape[1])
    lane = lax.broadcasted_iota(jnp.int32, (R, LANES), 1)

    def chunk(i, _):
        r = pl.ds(pl.multiple_of(i * R, R), R)

        def keys(g, queries: tuple):
            """Period ``g`` of the ``2 Hk`` heads of ``[q | k]``, its heads
            one below the other through the arithmetic: ``x / |x| c`` over a
            slot whose lanes behind the head are zero is the module's norm
            under the constant scale ``c sk^-1/2`` and ``eps / sk`` (the
            mean is over the slot's sk lanes; a zero lane stays zero).
            ``queries[j]`` says whether head ``j`` is one of q."""
            rows = (0, r, _at(g * (pk * dk), pk * dk))
            x = jnp.concatenate(_spread(x_ref[rows].astype(jnp.float32), dk,
                                        lane), axis=0)
            inv = lax.rsqrt(_mean(x * x, ones, terms) + eps / sk)
            unit = x * inv
            slots = [((q_ref, dk ** -0.5, 0) if query else (k_ref, 1.0, Hk))
                     for query in queries]
            slots = [(ref, c * sk ** -0.5,
                      (0, r, _at((g * pk + (j - first)) * sk, sk)))
                     for j, (ref, c, first) in enumerate(slots)]
            if not backward:
                for j, (ref, c, at) in enumerate(slots):
                    ref[at] = (unit[j * R:(j + 1) * R] * c).astype(ref.dtype)
                return
            g_ = jnp.concatenate([ref[at].astype(jnp.float32) * c
                                  for ref, c, at in slots], axis=0)
            dx = inv * (g_ - unit * _mean(g_ * unit, ones, terms))
            dx_ref[rows] = _pack([dx[j * R:(j + 1) * R] for j in range(pk)],
                                 dk, lane).astype(dx_ref.dtype)

        def values(g):
            """Period ``g`` of the ``Hv`` heads of v: moved, no more."""
            rows = (0, r, _at(2 * Hk * dk + g * (pv * dv), pv * dv))
            slots = (0, r, _at(g * (pv * sv), pv * sv))
            if backward:
                dv_ = v_ref[slots].astype(jnp.float32)
                dx_ref[rows] = _pack(
                    [dv_[:, j * sv:(j + 1) * sv] for j in range(pv)], dv,
                    lane).astype(dx_ref.dtype)
            else:
                v_ref[slots] = jnp.concatenate(_spread(
                    x_ref[rows].astype(jnp.float32), dv, lane),
                    axis=1).astype(v_ref.dtype)

        # the periods that are all q, the one that holds q's last heads
        # and k's first, those that are all k
        lax.fori_loop(0, Hk // pk,
                      lambda g, _: keys(g, (True,) * pk), None)
        if Hk % pk:
            keys(Hk // pk, tuple(j < Hk % pk for j in range(pk)))
        lax.fori_loop(-(-Hk // pk), 2 * Hk // pk,
                      lambda g, _: keys(g, (False,) * pk), None)
        lax.fori_loop(0, Hv // pv, lambda g, _: values(g), None)
        return _

    lax.fori_loop(0, x_ref.shape[1] // R, chunk, None)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def slot_rows_call(x, dq=None, dk=None, dv=None, *, heads: Heads, eps: float,
                   interpret: bool = False):
    """Of the filtered rows ``x`` (B, S, ``heads.width``) = ``[q | k | v]``:
    ``(q / |q| dk^-1/2, k / |k|)`` (B, S, keys * slot(dk)) a head (float32
    sums over the head's own channels, ``eps`` under the root) and ``v`` (B,
    S, values * slot(dv)), each head from the first lane of its slot, zeros
    behind.  With the three cotangents (their lanes behind a head are not
    read): the rows' cotangent."""
    B, S, width = x.shape
    if width != heads.width:
        raise ValueError(f"rows of {width} lanes for {heads}")
    backward = dq is not None
    _note_trace("slots_back" if backward else "slots", x.shape, x.dtype.name,
                *heads)
    lanes = (1 + backward) * width + 2 * heads.key_slots + heads.value_slots
    rows = block_rows(S, lanes, x.dtype.itemsize, 1)
    block = lambda w: pl.BlockSpec((1, rows, w), lambda b, i: (b, i, 0))
    like = lambda w: jax.ShapeDtypeStruct((B, S, w), x.dtype)
    slots = (heads.key_slots, heads.key_slots, heads.value_slots)
    elements = B * S * (2 * heads.key_slots)
    outs = pl.pallas_call(
        functools.partial(_slot_kernel, heads=heads, backward=backward,
                          eps=eps),
        grid=(B, S // rows),
        in_specs=[block(width)] + ([block(w) for w in slots] if backward
                                   else []),
        out_specs=block(width) if backward else [block(w) for w in slots],
        out_shape=like(width) if backward else [like(w) for w in slots],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(24 if backward else 10) * elements,
            transcendentals=2 * B * S * heads.keys,
            bytes_accessed=B * S * lanes * x.dtype.itemsize),
        name="slot_rows_back" if backward else "slot_rows",
        interpret=interpret,
    )(x, *((dq, dk, dv) if backward else ()))
    return outs if backward else tuple(outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def slot_rows(x, heads: Heads, eps: float, interpret: bool = False):
    """``(q, k, v)`` of :func:`slot_rows_call`, differentiable in ``x``."""
    return slot_rows_call(x, heads=heads, eps=eps, interpret=interpret)


def _slot_rows_fwd(x, heads, eps, interpret):
    return slot_rows(x, heads, eps, interpret), x


def _slot_rows_bwd(heads, eps, interpret, x, g):
    return (slot_rows_call(x, *g, heads=heads, eps=eps,
                           interpret=interpret),)


slot_rows.defvjp(_slot_rows_fwd, _slot_rows_bwd)


# -- rms_norm(o, w, eps) * silu(z), a value head of a Gated DeltaNet ---------

def gated_norm_supported(seq: int, width: int, dtype,
                         head_dim: Optional[int] = None) -> Optional[str]:
    """``None`` where :func:`gated_norm_rows` takes rows of this width (of
    heads of ``head_dim`` channels that are no whole lane tiles: ``o`` then
    holds a head a slot), else why not (the backward's five blocks
    decide)."""
    if width % LANES:                       # no whole periods of heads
        return f"rows of {width} lanes are no whole lane tiles"
    slots = width // head_dim * slot(head_dim) if head_dim else width
    return _refusal(seq, 2 * slots + 3 * width, dtype, 1)


def _gated_kernel(*refs, head_dim: int, backward: bool, eps: float):
    """One block of rows of ``o`` and of the gate ``z``, then the scale
    ``w`` (1, slot); forward the result ``y``; ``backward`` the
    cotangent ``dy`` in, ``do``, ``dz`` and this block's ``dw`` out.  ``o``
    and ``do`` hold a head a slot (``w``'s width: the head itself where it
    is whole lane tiles), ``z``, ``y``, ``dy`` and ``dz`` a head every
    ``head_dim`` lanes, as ``in_proj`` wrote and ``out_proj`` reads them."""
    o_ref, z_ref, w_ref, *rest = refs
    dy_ref = rest.pop(0) if backward else None
    rows, width = o_ref.shape[1], w_ref.shape[1]
    ones = jnp.ones((width, width), jnp.bfloat16)
    terms = 2 if o_ref.dtype == jnp.bfloat16 else 3
    live = head_dim if width != head_dim else None
    R = CHUNK if live is None else _slot_rows(rows)
    lane = lax.broadcasted_iota(jnp.int32, (R, LANES), 1) if live else None

    @jax.jit
    def head(o, z, w, dy, total):
        """A head's chunk in float32, traced once for all the heads."""
        inv = lax.rsqrt(_mean(o * o, ones, terms, live) + eps)
        unit = o * inv
        if not backward:
            return (unit * w * _silu(z),), total
        g = dy * _silu(z)                   # the norm's cotangent
        dz = dy * (unit * w) * _dsilu(z)
        total = total + g * unit
        g = g * w
        return (inv * (g - unit * _mean(g * unit, ones, terms, live)),
                dz), total

    def chunk(i, total):
        r = pl.ds(pl.multiple_of(i * R, R), R)
        w = w_ref[...]
        if live is None:        # a slot is the head
            for h in range(o_ref.shape[2] // head_dim):
                at = (0, r, pl.ds(h * head_dim, head_dim))
                outs, total = head(
                    o_ref[at].astype(jnp.float32),
                    z_ref[at].astype(jnp.float32), w,
                    dy_ref[at].astype(jnp.float32) if backward else None,
                    total)
                for ref, out in zip(rest, outs):
                    ref[at] = out.astype(ref.dtype)
            return total
        n = _period(head_dim)

        def period(g, total):   # n heads: whole tiles of z, y, dy and dz
            at = (0, r, _at(g * (n * head_dim), n * head_dim))
            slots = [(0, r, _at((g * n + j) * width, width))
                     for j in range(n)]
            below = lambda ref: jnp.concatenate(_spread(
                ref[at].astype(jnp.float32), head_dim, lane), axis=0)
            # the period's heads one below the other through the arithmetic
            outs, total = head(
                jnp.concatenate([o_ref[a].astype(jnp.float32)
                                 for a in slots], axis=0),
                below(z_ref), w, below(dy_ref) if backward else None, total)
            if backward:
                for j, a in enumerate(slots):
                    rest[0][a] = outs[0][j * R:(j + 1) * R].astype(
                        rest[0].dtype)
            ref = rest[1 if backward else 0]
            ref[at] = _pack([outs[-1][j * R:(j + 1) * R] for j in range(n)],
                            head_dim, lane).astype(ref.dtype)
            return total

        return lax.fori_loop(0, o_ref.shape[2] // width // n, period, total)

    total = lax.fori_loop(
        0, rows // R, chunk,
        jnp.zeros((R if live is None else R * _period(head_dim), width),
                  jnp.float32) if backward else None)
    if backward:
        rest[2][0] = total.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("head_dim", "eps", "interpret"))
def gated_norm_call(o, z, w, dy=None, *, head_dim: int, eps: float,
                    interpret: bool = False):
    """``y = rms_norm(o, w, eps) * silu(z)`` over each head of ``head_dim``
    lanes of ``z`` (B, S, H * head_dim), ``w`` (head_dim,), ``o`` (B, S, H *
    slot(head_dim)) a head a slot, zeros behind it (where a head is whole
    lane tiles: ``z``'s shape); ``y`` as ``z``.  With the cotangent ``dy``:
    ``(do, dz, dw)``, ``do`` as ``o``, ``dw`` float32."""
    B, S, width = z.shape
    if z.shape[:2] != o.shape[:2] or z.dtype != o.dtype \
            or width * slot(head_dim) != o.shape[2] * head_dim:
        raise ValueError(f"a gate of {z.shape} {z.dtype.name} for rows of "
                         f"{o.shape} {o.dtype.name}")
    if width % head_dim or width % LANES:
        raise ValueError(f"rows of {width} lanes are no heads of {head_dim}"
                         f" in whole lane tiles")
    backward = dy is not None
    _note_trace("gated_norm_back" if backward else "gated_norm", o.shape,
                o.dtype.name, head_dim)
    slots, lanes = o.shape[2], slot(head_dim)
    moved = (2 * slots + 3 * width) if backward else slots + 2 * width
    rows = block_rows(S, moved, o.dtype.itemsize, 1)
    steps = S // rows
    slot_block = pl.BlockSpec((1, rows, slots), lambda b, i: (b, i, 0))
    row_block = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    rows_out = jax.ShapeDtypeStruct(z.shape, z.dtype)
    scale = w.astype(jnp.float32).reshape(1, head_dim)
    if lanes != head_dim:
        scale = jnp.pad(scale, ((0, 0), (0, lanes - head_dim)))
    operands = [o, z, scale]
    in_specs = [slot_block, row_block,
                pl.BlockSpec((1, lanes), lambda b, i: (0, 0))]
    out_specs, out_shape = [row_block], [rows_out]
    if backward:    # a grid step's own sum of dw; XLA adds the steps up
        operands.append(dy)
        in_specs.append(row_block)
        out_specs = [slot_block, row_block, pl.BlockSpec(
            (1, 1, lanes), lambda b, i: (b * steps + i, 0, 0))]
        out_shape = [jax.ShapeDtypeStruct(o.shape, o.dtype), rows_out,
                     jax.ShapeDtypeStruct((B * steps, 1, lanes), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_gated_kernel, head_dim=head_dim,
                          backward=backward, eps=eps),
        grid=(B, steps), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(40 if backward else 16) * o.size,
            transcendentals=o.size + o.size // lanes,
            bytes_accessed=B * S * moved * o.dtype.itemsize),
        name="gated_norm_rows_back" if backward else "gated_norm_rows",
        interpret=interpret,
    )(*operands)
    if not backward:
        return outs[0]
    do, dz, dw = outs
    return do, dz, dw.sum(axis=(0, 1))[:head_dim]


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
