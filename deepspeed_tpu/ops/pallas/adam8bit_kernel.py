"""The int8 AdamW update of one leaf in one pass over HBM, in place.

``ops/adam8bit.py`` inside a compiled step becomes two XLA fusions a leaf:
the first reads gradient, both code arrays and the master, writes the
master and reduces the two row maxima; the second reads gradient and codes
AGAIN, recomputes both moments and writes the new codes: 18 bytes a
parameter with the gradient in bf16.  This kernel reads each once and
writes master and codes once, 14 bytes, the moments living only in VMEM:

    read  g (bf16 or fp32)  p (fp32)  m codes (int8)  r codes (uint8)
    write p (fp32)  m codes  r codes         + two fp32 scales a row

Three things decide whether that wins (PERF.md section 6, PR 27):

- the gradient arrives in the dtype the backward wrote it in and is
  up-cast a strip at a time in VMEM: XLA fuses no producer into a Pallas
  operand, so an fp32 operand costs a gradient-sized buffer first;
- the row scales ``(..., 1)`` reach the kernel as a ``(1, R)`` view, rows
  along the lanes: an ``(R, 1)`` block is padded 128-fold in HBM and VMEM
  and XLA relayouts it in and out of every call.  Inside, a 128-row chunk
  of scales is turned to a column (and the new maxima back to lanes) by a
  select against the diagonal and a sum: exact, and ~2% of a strip's work;
- master, codes and scales are aliased in to out.

Rows (the quantisation granularity) stay whole inside a block.  Blocks are
a multiple of 128 rows (one lane tile of scales) of a height that depends
on the row's width alone; the body walks a block in 32-row strips (the
int8 sublane tile) so its temporaries are a strip's, not a block's.  Rows
too wide for a 128-row block in VMEM (an untied ``(embed, vocab)`` head)
take 32-row blocks with the whole scale vectors resident in VMEM.

The caller donates the state to the program it stages this in (the
engine's steps all do): undonated, XLA has to copy master and codes first,
and with the call pinned to HBM its memory-space assignment fails a check
(libtpu 0.0.34) instead of placing the copy.

The mathematics is ``adam8bit._leaf_moments`` + decay + learning rate,
with the clip factor and ``1 / (denom * loss scale)`` folded into one
scalar and reciprocal multiplies for the per-row divides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows whose scales share one lane tile, and the int8 sublane tile
_CHUNK = 128
_STRIP = 32
# elements a block aims at (p, g, both codes, in and out, double-buffered:
# 28 bytes an element with a bf16 gradient, so ~14 MB of VMEM)
_TARGET_ELEMS = 512 * 1024
# what a block's buffers may take of the v5e's 128 MiB of VMEM, and what
# the body's temporaries get on top: a floor plus some fp32 strips
_MAX_BLOCK_BYTES = 48 * 2**20
_HEADROOM_BYTES = 16 * 2**20
_LIVE_STRIPS = 6


def _bytes_an_element(g_itemsize: int) -> int:
    """Read p, g, both codes; write p and both codes."""
    return 4 + g_itemsize + 1 + 1 + 4 + 1 + 1


def _block_bytes(rows: int, cols: int, g_itemsize: int) -> int:
    """VMEM of one block's double-buffered operands and results."""
    return 2 * rows * cols * _bytes_an_element(g_itemsize)


def block_plan(rows: int, cols: int, g_itemsize: int = 2):
    """``(block rows, scales resident)`` for a ``(rows, cols)`` leaf, or
    ``None`` where no block of whole rows fits VMEM.

    A leaf of up to one block is one block.  Otherwise the height is the
    multiple of 128 nearest under ``_TARGET_ELEMS`` (at least 128, so a
    block's scales are whole lane tiles); where 128 rows overflow the
    budget, 32 rows with the scale vectors resident, which needs the rows
    to fill whole lane tiles."""
    if rows * cols <= _TARGET_ELEMS and \
            _block_bytes(rows, cols, g_itemsize) <= _MAX_BLOCK_BYTES:
        return rows, False
    # of the heights up to the target, the one that pads the last block
    # least (1600 rows: 13 blocks of 128 waste 4%, 7 of 256 waste 12%)
    top = max(_CHUNK, _TARGET_ELEMS // cols // _CHUNK * _CHUNK)
    br = min(range(top, 0, -_CHUNK), key=lambda b: -(-rows // b) * b)
    if _block_bytes(br, cols, g_itemsize) <= _MAX_BLOCK_BYTES:
        return br, False
    if rows % _CHUNK == 0 and \
            _block_bytes(_STRIP, cols, g_itemsize) <= _MAX_BLOCK_BYTES:
        return _STRIP, True
    return None


def stored_transposed(shape) -> bool:
    """Whether the TPU keeps a 2-D array of this shape column-major.

    The device's default layout puts whichever of the last two dimensions
    pads less on the 128 lanes (a tie keeps row-major): f32[6400, 1600]
    lives as {0,1:T(8,128)}, physically a row-major (1600, 6400).  A
    Pallas operand is pinned to the row-major layout of the shape it is
    given, so such a leaf goes to the kernel as its transpose, a bitcast,
    and is reduced along sublanes; given as it is, XLA would transpose
    all four arrays in and three out around every call."""
    if len(shape) != 2:
        return False

    def padded(minor, second):
        return -(-minor // _CHUNK) * _CHUNK * (-(-second // 8) * 8)

    return padded(shape[0], shape[1]) < padded(shape[1], shape[0])


def _moments(g, p, mc, rc, scm, scr, scalars, axis, *, b1, b2, eps, wd, l2):
    """The update of one strip, its rows along ``axis`` reduced to their
    maxima: ``(p', m codes, r codes, m scale, r scale)``; ``scm`` / ``scr``
    broadcast against the strip."""
    gscale, lr, c1, c2 = scalars
    # division is the VPU's slow path: ONE divide an element (Adam's
    # denominator); the rest are multiplies by a scalar or a row's reciprocal
    inv_c1 = 1.0 / c1
    rs_c2 = jax.lax.rsqrt(c2)
    g = g.astype(jnp.float32) * gscale
    if l2:
        g = g + l2 * p
    m = b1 * (mc.astype(jnp.float32) * scm) + (1.0 - b1) * g
    r0 = rc.astype(jnp.int32).astype(jnp.float32) * scr
    v = b2 * (r0 * r0) + (1.0 - b2) * (g * g)
    r = jnp.sqrt(v)
    upd = (m * inv_c1) / (r * rs_c2 + eps)
    if wd:
        upd = upd + wd * p
    amax_m = jnp.max(jnp.abs(m), axis=axis, keepdims=True)
    inv_m = jnp.where(amax_m > 0, 127.0 / amax_m, 1.0)   # a divide a ROW
    mc2 = jnp.clip(jnp.round(m * inv_m), -127, 127).astype(jnp.int8)
    amax_r = jnp.max(r, axis=axis, keepdims=True)
    inv_r = jnp.where(amax_r > 0, 255.0 / amax_r, 1.0)
    # rounded UP, as adam8bit._quant_pos: never under the true root
    rc2 = jnp.minimum(jnp.ceil(r * inv_r), 255.0
                      ).astype(jnp.int32).astype(jnp.uint8)
    return (p - lr * upd, mc2, rc2,
            jnp.where(amax_m > 0, amax_m * (1.0 / 127.0), 1.0),
            jnp.where(amax_r > 0, amax_r * (1.0 / 255.0), 1.0))


def _diagonal(n: int, width: int, off):
    """``(n, width)`` mask of the entries (i, off + i), and the ``(1,
    width)`` mask of the lanes it covers."""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return lane == row + off, (lane1 >= off) & (lane1 < off + n)


def _kernel_rows(hyper, sr, cw, br, resident,
                 s_ref, g_ref, p_ref, mc_ref, rc_ref, scm_ref, scr_ref,
                 po_ref, mco_ref, rco_ref, scmo_ref, scro_ref):
    """A block of ``br`` whole rows, walked in ``sr``-row strips; the
    scales are lanes of a ``(1, ...)`` block, ``cw`` to a chunk."""
    scalars = (s_ref[0], s_ref[1], s_ref[2], s_ref[3])
    # first row of this block within the scale block (all rows if resident)
    base = pl.program_id(0) * br if resident else 0

    def strip(s, carry):
        r0 = s * sr
        rows = pl.ds(pl.multiple_of(r0, sr), sr) if sr != br else slice(None)
        at = base + r0
        lanes = pl.ds(pl.multiple_of(at // cw * cw, cw), cw) \
            if cw == _CHUNK else slice(None)
        diag, mine = _diagonal(sr, cw, at % cw)

        def column(ref):        # the strip's scales, lanes -> sublanes
            return jnp.sum(jnp.where(diag, ref[:, lanes], 0.0), axis=1,
                           keepdims=True)

        def store(ref, col):    # sublanes -> the strip's lanes of the chunk
            new = jnp.sum(jnp.where(diag, col, 0.0), axis=0, keepdims=True)
            ref[:, lanes] = jnp.where(mine, new, ref[:, lanes])

        po_ref[rows, :], mco_ref[rows, :], rco_ref[rows, :], scm, scr = \
            _moments(g_ref[rows, :], p_ref[rows, :], mc_ref[rows, :],
                     rc_ref[rows, :], column(scm_ref), column(scr_ref),
                     scalars, 1, **hyper)
        store(scmo_ref, scm)
        store(scro_ref, scr)
        return carry

    if sr == br:
        strip(0, None)
    else:
        jax.lax.fori_loop(0, br // sr, strip, None)


def _kernel_lanes(hyper, bl,
                  s_ref, g_ref, p_ref, mc_ref, rc_ref, scm_ref, scr_ref,
                  po_ref, mco_ref, rco_ref, scmo_ref, scro_ref):
    """A block of a leaf stored transposed: ``bl`` whole rows along the
    lanes, walked a lane tile at a time; scales broadcast as they are."""
    scalars = (s_ref[0], s_ref[1], s_ref[2], s_ref[3])
    lw = _CHUNK if bl % _CHUNK == 0 else bl

    def strip(s, carry):
        at = pl.ds(pl.multiple_of(s * lw, lw), lw) if lw != bl \
            else slice(None)
        (po_ref[:, at], mco_ref[:, at], rco_ref[:, at], scmo_ref[:, at],
         scro_ref[:, at]) = _moments(
            g_ref[:, at], p_ref[:, at], mc_ref[:, at], rc_ref[:, at],
            scm_ref[:, at], scr_ref[:, at], scalars, 0, **hyper)
        return carry

    if lw == bl:
        strip(0, None)
    else:
        jax.lax.fori_loop(0, bl // lw, strip, None)


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "l2", "transposed", "interpret"))
def _leaf_update(g, p, mc, rc, scm, scr, scalars, *, b1, b2, eps, wd, l2,
                 transposed, interpret):
    """One update of a leaf of R rows of C, its scales as (1, R):
    operands (R, C), or (C, R) where ``transposed``; ``scalars`` =
    [gradient factor, lr, c1, c2]."""
    hyper = dict(b1=b1, b2=b2, eps=eps, wd=wd, l2=l2)
    R = scm.shape[1]
    C = p.size // R
    gb = g.dtype.itemsize
    if transposed:
        bl, _ = block_plan(R, C, gb)
        kern = functools.partial(_kernel_lanes, hyper, bl)
        data_spec = pl.BlockSpec((C, bl), lambda i: (0, i))
        sc_spec = pl.BlockSpec((1, bl), lambda i: (0, i))
        grid, need = pl.cdiv(R, bl), _block_bytes(bl, C, gb)
        strip = C * min(bl, _CHUNK)
    else:
        br, resident = block_plan(R, C, gb)
        sr = _STRIP if br % _STRIP == 0 else br
        sw = R if resident or br == R else br       # scale block width
        cw = _CHUNK if sw % _CHUNK == 0 else sw
        kern = functools.partial(_kernel_rows, hyper, sr, cw, br, resident)
        data_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
        sc_spec = pl.BlockSpec((1, sw), (lambda i: (0, 0)) if sw == R
                               else (lambda i: (0, i)))
        grid, need = pl.cdiv(R, br), _block_bytes(br, C, gb)
        strip = sr * C
    # operands and results stay in HBM and the kernel streams them itself:
    # left free, XLA stages whatever fits through VMEM first and the step
    # waits on those copies (0.3-1.3 ms of copy-done a 150 M parameters
    # beside 2.6 ms of kernel; v5e, PERF.md section 6, PR 27).  The
    # interpreter knows no memory spaces.
    def hbm(x):
        return x if interpret else pltpu.with_memory_space_constraint(
            x, pltpu.HBM)

    def out(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype) if interpret \
            else pltpu.HBM(shape, dtype)

    return pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), data_spec,
                  data_spec, data_spec, data_spec, sc_spec, sc_spec],
        out_specs=[data_spec, data_spec, data_spec, sc_spec, sc_spec],
        out_shape=[out(p.shape, jnp.float32), out(p.shape, jnp.int8),
                   out(p.shape, jnp.uint8), out((1, R), jnp.float32),
                   out((1, R), jnp.float32)],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + _HEADROOM_BYTES
            + _LIVE_STRIPS * 4 * strip),
        # without one XLA takes the call for free, and its memory-space
        # assignment then searches minutes for where to prefetch ~200
        # calls' operands (GPT-2-XL's step: 239 s of compile against 86)
        cost_estimate=pl.CostEstimate(
            flops=40 * p.size, transcendentals=2 * p.size,
            bytes_accessed=p.size * _bytes_an_element(gb)),
        name="adam8bit",
        interpret=interpret,
    )(scalars, hbm(g), hbm(p), hbm(mc), hbm(rc), hbm(scm), hbm(scr))


def _rows_cols(shape):
    cols = shape[-1] if shape else 1
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return rows, cols


def leaf_refusal(shape, dtype, g_itemsize: int = 2):
    """Why the kernel does not take a leaf, or ``None`` where it does.

    A leaf under one 128-row, 128-lane tile (biases, norms, a 64-expert
    router) stays on the XLA chain: a call each costs more than its second
    read saves."""
    rows, cols = _rows_cols(shape)
    if rows < _CHUNK or cols < _CHUNK:
        return "leaf under one block"
    if dtype != jnp.float32:
        return f"master weights in {jnp.dtype(dtype).name}"
    plan = block_plan(rows, cols, g_itemsize)
    # (a column-major leaf has no 32-row form: its rows lie along lanes)
    if plan is None or (plan[1] and stored_transposed(shape)):
        return "a block of whole rows overflows VMEM"
    return None


def apply_leaf(g, p, mc, rc, scales, scalars, *, b1, b2, eps, wd, l2,
               interpret):
    """Bring a leaf to the kernel's view (rows, or their transpose where
    the device stores it so: bitcasts both), run it, restore shapes.

    Returns ``(p', mc', rc', {"m": scm', "r": scr'})`` as one step of
    ``scale_by_adam8bit`` + decay + lr; ``g`` in the dtype it has.
    """
    shape = p.shape
    R, C = _rows_cols(shape)
    transposed = stored_transposed(shape)

    def view(x):
        return x.T if transposed else x.reshape(R, C)

    po, mco, rco, scmo, scro = _leaf_update(
        view(g), view(p), view(mc), view(rc),
        scales["m"].reshape(1, R), scales["r"].reshape(1, R), scalars,
        b1=b1, b2=b2, eps=eps, wd=wd, l2=l2, transposed=transposed,
        interpret=interpret)

    def back(x):
        return x.T if transposed else x.reshape(shape)

    sshape = scales["m"].shape
    return (back(po), back(mco), back(rco),
            {"m": scmo.reshape(sshape), "r": scro.reshape(sshape)})
