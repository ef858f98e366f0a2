"""The causal depthwise filter of ``ops/short_conv.py`` as row kernels.  First
the double-gated short convolution (that file has the equation) on the rows
its projection wrote: ``bcu`` (B, S, 3C) holds
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

**The same filter without the gates and with an activation after it**
(:func:`causal_conv_rows`, PR 51): ``y = silu(filter(x))`` of ``x`` (B, S, C),
what a Gated DeltaNet layer runs over ``[q ; k ; v]``.  The forward is the
gated forward's body, the gates and the activation static options of it
(the gated instance traces to the jaxpr it had before:
``tests/unit/test_short_conv.py``).  Channels are independent, so they go by
blocks of at most :data:`CHANNELS` on a third grid axis and 8192 of them cost
the VMEM that LFM2's 3 x 2048 do; one operand a chunk instead of three lets a
chunk be :data:`WIDE`.  A count that is whole lane tiles but no multiple of
:data:`LANES` (Olmo-Hybrid's 11,520 = 90 x 128) goes by the widest of 384,
256 or 128 lanes at a time that divides a block (:func:`_grid`: six blocks
of 1,920 channels, 384 lanes a chunk).  The backward is a body of its own
(:func:`_causal_backward_kernel`): ``dc = dy * silu'(c)`` with ``silu'(c) =
s (1 + c (1 - s))``, ``s = sigmoid(c)``, needs ``c`` again, also for the 8
rows AFTER the block (their ``dc`` reaches this block's ``dx``, and their
taps reach back into the block's own tail, which the scratch holds), so it
walks a block once from its last chunk to its first and hands each chunk's
first 8 rows of ``dc`` to the chunk before in the loop's carry: no second
scratch, ``x`` rolled once for ``c`` and the taps' gradient.  Traffic: 1
vector in and 1 out forward, 2 in and 1 out backward.  At ``(3, 8192,
8192)``, 4 taps, silu, on the v5e: forward 1.36 ms (0.98 at the HBM rate),
backward 2.38 (1.47), together 3.88 against XLA's shifted form's 13.98
(``chip_smoke.py kernel_short_conv``, PR 51).  Both wait for the VECTOR
unit, not for HBM: without silu they read 1.24 and 1.97, a chunk of 16 rows
1.72 and 2.81, and the division inside ``sigmoid`` alone cost 0.15 and 0.34
(:func:`_sigmoid`).  The custom calls are ``causal_conv_rows`` and
``causal_conv_rows_back``.
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
CHANNELS = 2048  # most channels of a block of the ungated filter (a grid axis)
WIDE = 32       # rows at a time where a chunk holds one operand, not three
WIDTHS = (LANES, 384, 256, 128)     # lanes at a time of the ungated filter
_VMEM_LIMIT = 48 << 20


def supported(S: int, C: int, L: int, dtype,
              gated: bool = True) -> Optional[str]:
    """None where the kernels take the shape, else why not.  The gated
    rows' thirds go by :data:`LANES`; the ungated filter's channels by the
    widest of :data:`WIDTHS` that divides a block of them."""
    lanes = LANES if gated else WIDTHS[-1]
    if C % lanes:
        return f"{C} channels are no multiple of {lanes}"
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


def _fill(buf, C, rows, block, near, skip, near_at, at, step=CHUNK,
          width=LANES):
    """``buf[at : at + rows] = block(rows, lane block)`` chunk by chunk and
    ``buf[near_at : near_at + HALO] = near(all 8 rows, lane block)``, zeros
    where ``skip`` (the 8-row view then lies outside the row)."""
    for l0 in range(0, C, width):
        lanes = pl.ds(l0, width)
        buf[pl.ds(near_at, HALO), lanes] = jnp.where(
            skip, 0.0, near(slice(None), l0))

        def chunk(r, _):
            at_r = pl.multiple_of(r * step, step)
            buf[pl.ds(at + at_r, step), lanes] = block(
                pl.ds(at_r, step), l0)
            return _

        lax.fori_loop(0, rows // step, chunk, 0)


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


def _x(ref, C, width=LANES):
    """Rows ``at`` of a (1, rows, C) ref as they are, float32."""
    def make(at, l0):
        return _f32(ref[0, at, pl.ds(l0, width)])
    return make


def _filter(ext, w, L):
    """``c`` of the rows behind the first 8 of ``ext``: row t of
    ``roll(., back)`` is t - back."""
    c = ext[HALO:] * w[L - 1]
    for back in range(1, L):
        c = c + pltpu.roll(ext, back, 0)[HALO:] * w[L - 1 - back]
    return c


def _sigmoid(c):
    """``1 / (1 + exp(-c))``, the quotient as the approximate reciprocal and
    one Newton step: on the v5e as near float64 as the division is (worst
    6.39e-6 of the value both, mean 7.50e-7 against 7.48e-7: the exponential
    decides) at four vector operations less an element, which is what these
    kernels wait for.  ``exp`` stays finite, or the step would make 0 * inf."""
    d = 1.0 + jnp.exp(jnp.minimum(-c, 80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _silu(c):
    return c * _sigmoid(c)


def _dsilu(c):
    """``silu'(c) = s (1 + c (1 - s))`` with ``s = sigmoid(c)``."""
    s = _sigmoid(c)
    return s * (1.0 + c * (1.0 - s))


def _forward_kernel(x_ref, before_ref, w_ref, y_ref, z_buf, *, C, L, rows,
                    gated=True, activation=None):
    """``x_ref`` holds ``[Bg ; Cg ; u]`` (``gated``, C channels each) or the
    C channels of a block of ``x``; ``z_buf`` what the filter reads."""
    first = pl.program_id(1) == 0
    width = LANES if gated else _width(C)
    z = _z if gated else functools.partial(_x, width=width)
    step = CHUNK if gated else WIDE
    _fill(z_buf, C, rows, z(x_ref, C), z(before_ref, C), first, 0, HALO, step,
          width)
    for l0 in range(0, C, width):
        lanes = pl.ds(l0, width)
        w = _taps(w_ref, lanes, L)

        def chunk(r, _):
            at_r = pl.multiple_of(r * step, step)
            # the chunk's rows behind 8 rows of what came before them
            c = _filter(z_buf[pl.ds(at_r, step + HALO), lanes], w, L)
            if gated:
                y = _f32(x_ref[0, pl.ds(at_r, step),
                               pl.ds(C + l0, LANES)]) * c
            else:
                y = _silu(c) if activation == "silu" else c
            y_ref[0, pl.ds(at_r, step), lanes] = y.astype(y_ref.dtype)
            return _

        lax.fori_loop(0, rows // step, chunk, 0)


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


def _causal_backward_kernel(x_ref, before_ref, after_ref, dy_ref,
                            dy_after_ref, w_ref, dx_ref, dw_ref, x_buf, *,
                            C, L, rows, activation):
    """The ungated filter's backward, a body of its own because it is ONE
    walk, from a block's last chunk to its first, where the gated one is two
    passes through a second scratch: a chunk's ``dc = dy * act'(c)`` is made
    once, from the rolled ``x`` that the taps' gradient reads too, and its
    first 8 rows ride in the loop's carry to the chunk before, whose ``dx``
    reads up to ``L - 1`` rows ahead.  Written as the gated body's second
    form it read 3.22 ms a call at ``(3, 8192, 8192)``, so 2.81, both at 16
    rows a chunk (PERF.md section 6, PR 51); the gated kernel is held to its
    parent's jaxpr."""
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    width = _width(C)
    _fill(x_buf, C, rows, _x(x_ref, C, width), _x(before_ref, C, width),
          first, 0, HALO, WIDE, width)
    dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)
    steps = rows // WIDE
    for l0 in range(0, C, width):
        lanes = pl.ds(l0, width)
        w = _taps(w_ref, lanes, L)

        def cotangent(ext, dy):
            """``dc`` of the rows behind the first 8 of ``ext``, and those
            rows ``k`` positions back."""
            back = [ext[HALO:]] + [pltpu.roll(ext, k, 0)[HALO:]
                                   for k in range(1, L)]
            if activation != "silu":
                return dy, back
            c = back[0] * w[L - 1]
            for k in range(1, L):
                c = c + back[k] * w[L - 1 - k]
            return dy * _dsilu(c), back

        # the 8 rows after the block: their taps reach into its tail
        ahead, _ = cotangent(
            jnp.concatenate([x_buf[pl.ds(rows, HALO), lanes],
                             _f32(after_ref[0, :, lanes])], 0),
            _f32(dy_after_ref[0, :, lanes]))

        def chunk(i, carry):
            ahead, dw = carry[0], list(carry[1:])
            at_r = pl.multiple_of((steps - 1 - i) * WIDE, WIDE)
            here = pl.ds(at_r, WIDE)
            dc, back = cotangent(x_buf[pl.ds(at_r, WIDE + HALO), lanes],
                                 _f32(dy_ref[0, here, lanes]))
            dc_ext = jnp.concatenate([dc, ahead], 0)
            dx = dc * w[L - 1]
            for k in range(L):
                dw[L - 1 - k] = dw[L - 1 - k] + (dc * back[k]).reshape(
                    WIDE // 8, 8, width).sum(0)
                if k:                                               # dc_{t+k}
                    dx = dx + pltpu.roll(dc_ext, WIDE + HALO - k,
                                         0)[:WIDE] * w[L - 1 - k]
            dx_ref[0, here, lanes] = dx.astype(dx_ref.dtype)
            return (dc[:HALO],) + tuple(dw)

        out = lax.fori_loop(
            0, steps, chunk, (jnp.where(last, 0.0, ahead),)
            + (jnp.zeros((8, width), jnp.float32),) * L)
        for j in range(L):
            dw_ref[0, pl.ds(j, 1), lanes] = out[1 + j].sum(0, keepdims=True)


def _views(S, rows):
    """BlockSpecs of a (B, S, width) operand: the block and the 8 rows before
    and after it (clamped inside the row; the kernels zero what the clamp
    brought).  ``j`` is the channel block where the grid has that axis."""
    tiles = rows // HALO

    def spec(width):
        return (pl.BlockSpec((1, rows, width), lambda b, i, j=0: (b, i, j)),
                pl.BlockSpec((1, HALO, width),
                             lambda b, i, j=0: (
                                 b, jnp.maximum(i * tiles - 1, 0), j)),
                pl.BlockSpec((1, HALO, width),
                             lambda b, i, j=0: (
                                 b, jnp.minimum((i + 1) * tiles,
                                                S // HALO - 1), j)))
    return spec


def _padded_taps(w):
    """(C, L) taps as the (8, C) float32 rows the kernels read."""
    C, L = w.shape
    return jnp.zeros((HALO, C), jnp.float32).at[:L].set(_f32(w).T)


def _width(Cb):
    """Lanes the ungated kernels work on at a time in a block of ``Cb``
    channels: the widest of :data:`WIDTHS` that divides it."""
    return next(w for w in WIDTHS if Cb % w == 0)


def _grid(B, S, C, gated):
    """``(grid, channels of a block)``.  The gated rows go whole, their three
    thirds lie side by side; the ungated filter's channels are independent
    and go by blocks of at most :data:`CHANNELS`, so that 8192 of them cost
    the VMEM that 3 x 2048 do: the largest block of the widest lanes
    (:data:`WIDTHS`) that divides them (8192: 2048 by 512; 11,520: 1920 by
    384)."""
    if gated:
        return (B, S // BLOCK), C
    Cb = next(max(fits) for fits in (
        [c for c in range(w, CHANNELS + 1, w) if C % c == 0] for w in WIDTHS)
        if fits)
    return (B, S // BLOCK, C // Cb), Cb


def _params(grid):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * len(grid),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _cost(x, w, flops, vectors, activation=None):
    """A pass that does ``flops`` multiply-adds an element of (B, S, C) and
    moves ``vectors`` of them through HBM."""
    n = x.shape[0] * x.shape[1] * w.shape[0]
    return pl.CostEstimate(
        flops=2 * flops * n, transcendentals=n if activation else 0,
        bytes_accessed=vectors * n * x.dtype.itemsize)


def _forward(x, w, interpret, gated=True, activation=None):
    B, S, width = x.shape
    C, L = w.shape
    grid, Cb = _grid(B, S, C, gated)
    block, before, _ = _views(S, BLOCK)(width if gated else Cb)
    return pl.pallas_call(
        functools.partial(_forward_kernel, C=Cb, L=L, rows=BLOCK, gated=gated,
                          activation=activation),
        grid=grid,
        in_specs=[block, before,
                  pl.BlockSpec((HALO, Cb), lambda b, i, j=0: (0, j))],
        out_specs=pl.BlockSpec((1, BLOCK, Cb), lambda b, i, j=0: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), x.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK + HALO, Cb), jnp.float32)],
        compiler_params=_params(grid),
        cost_estimate=_cost(x, w, L + 2, 4) if gated else _cost(
            x, w, L + (4 if activation else 0), 2, activation),
        name="short_conv_rows" if gated else "causal_conv_rows",
        interpret=interpret,
    )(x, x, _padded_taps(w))


def _backward(x, w, dy, interpret, gated=True, activation=None):
    B, S, width = x.shape
    C, L = w.shape
    steps = S // BLOCK
    grid, Cb = _grid(B, S, C, gated)
    views = _views(S, BLOCK)
    block, before, after = views(width if gated else Cb)
    dy_block, _, dy_after = views(Cb)
    dx, dw = pl.pallas_call(
        functools.partial(_backward_kernel, C=Cb, L=L, rows=BLOCK) if gated
        else functools.partial(_causal_backward_kernel, C=Cb, L=L,
                               rows=BLOCK, activation=activation),
        grid=grid,
        in_specs=[block, before, after, dy_block, dy_after,
                  pl.BlockSpec((HALO, Cb), lambda b, i, j=0: (0, j))],
        out_specs=[pl.BlockSpec((1, BLOCK, width if gated else Cb),
                                lambda b, i, j=0: (b, i, j)),
                   pl.BlockSpec((1, HALO, Cb),
                                lambda b, i, j=0: (b * steps + i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B * steps, HALO, C), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((BLOCK + HALO, Cb), jnp.float32)]
        * (2 if gated else 1),
        compiler_params=_params(grid),
        cost_estimate=_cost(x, w, 3 * L + 5, 7) if gated else _cost(
            x, w, 3 * L + (L + 8 if activation else 0), 3, activation),
        name="short_conv_rows_back" if gated else "causal_conv_rows_back",
        interpret=interpret,
    )(x, x, x, dy, dy, _padded_taps(w))
    return dx, dw.sum(0)[:L].T.astype(w.dtype)


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


# The ungated kernels sit behind a jit, so a stack of Gated DeltaNet layers
# traces and lowers each body once a signature, not once a layer and remat
# pass (the eighth cell's step: 12 s to lower in the sandbox without, 9.5
# with, 9 to 12 the parent's).
@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def _causal_forward(x, w, *, activation, interpret):
    return _forward(x, w, interpret, False, activation)


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def _causal_backward(x, w, dy, *, activation, interpret):
    return _backward(x, w, dy, interpret, False, activation)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def causal_conv_rows(x: jax.Array, w: jax.Array, activation=None,
                     interpret: bool = False) -> jax.Array:
    """``act(filter(x))`` (B, S, C) of ``x`` (B, S, C) and taps ``w`` (C, L)
    whose last column is the current position; ``activation`` is ``"silu"``
    or ``None``; :func:`supported` says which shapes."""
    return _causal_forward(x, w, interpret=interpret, activation=activation)


def _causal_fwd(x, w, activation, interpret):
    return _causal_forward(x, w, interpret=interpret,
                           activation=activation), (x, w)


def _causal_bwd(activation, interpret, saved, dy):
    x, w = saved
    return _causal_backward(x, w, dy, interpret=interpret,
                            activation=activation)


causal_conv_rows.defvjp(_causal_fwd, _causal_bwd)
