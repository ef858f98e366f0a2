"""Fused int8-state AdamW update: one HBM pass per parameter leaf.

The unfused ``ops/adam8bit.py`` math inside a compiled step makes XLA
materialize fp32 moment temporaries between the elementwise update and
the row-wise requantization reductions (dequant → m/v update → amax →
requant → param update spans several fusions).  At GPT-2-1.5B that is
tens of GB of extra HBM traffic per optimizer step — the round-2 bench's
measured optimizer bottleneck (VERDICT round 2, item 1).

This kernel does the whole leaf update in ONE Pallas pass:

    read  g(fp32) p(fp32) mc(int8) rc(uint8) scales(fp32/row)
    write p'(fp32) mc'(int8) rc'(uint8) scales'(fp32/row)

≈16 bytes/element of traffic, with the moments living only in VMEM.
Rows (the quantization granularity) stay whole inside a block, so the
absmax requant reductions are block-local.  Covers the same math as the
reference's fused CUDA optimizers (``csrc/adam/multi_tensor_adam.cu``,
here with int8 state) — clip scale, decoupled weight decay (AdamW) and
L2-into-grad (Adam) included, so the optimizer is one kernel per leaf.

Used on the single-device path (the 1.5B-on-one-chip bench regime);
multi-device meshes keep the unfused XLA math, which pjit partitions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os

# a leaf row must fit VMEM alongside its fp32 temporaries
_MAX_ROW = 100_000
# elements per grid block: big blocks amortize the per-step (row, 1)
# scale DMAs; ~256k × (16B io + fp32 temporaries) ≈ 7 MB of VMEM with
# Mosaic's double buffering
_TARGET_ELEMS = int(os.environ.get("DS_TPU_ADAM8BIT_BLOCK", 262_144))


def _block_rows(rows: int, cols: int) -> int:
    """Row-block height: multiple of 32 (the int8 sublane tile — the
    codes' loads/stores relayout on misaligned offsets) when possible."""
    br = max(1, _TARGET_ELEMS // max(cols, 1))
    if br >= 32:
        br -= br % 32
    elif br > 8:
        br -= br % 8
    return min(rows, br)


def _kernel(b1, b2, eps, wd, l2,
            s_ref, g_ref, p_ref, mc_ref, rc_ref, scm_ref, scr_ref,
            po_ref, mco_ref, rco_ref, scmo_ref, scro_ref):
    gscale, lr, c1, c2 = (s_ref[0], s_ref[1], s_ref[2], s_ref[3])
    # division is the VPU's slow path: keep ONE per-element divide (the
    # Adam denominator); everything else becomes a multiply by a scalar
    # or per-row reciprocal
    inv_c1 = 1.0 / c1
    rs_c2 = jax.lax.rsqrt(c2)
    p = p_ref[:]
    g = g_ref[:] * gscale
    if l2:
        g = g + l2 * p
    m = b1 * (mc_ref[:].astype(jnp.float32) * scm_ref[:]) + (1.0 - b1) * g
    # Mosaic has no uint8 casts: the uint8 r-codes arrive bitcast to int8;
    # wrap negatives back into [0, 255] through int32
    rci = rc_ref[:].astype(jnp.int32)
    rci = jnp.where(rci < 0, rci + 256, rci)
    r0 = rci.astype(jnp.float32) * scr_ref[:]
    v = b2 * (r0 * r0) + (1.0 - b2) * (g * g)
    r = jnp.sqrt(v)                       # needed for requant anyway
    upd = (m * inv_c1) / (r * rs_c2 + eps)
    if wd:
        upd = upd + wd * p
    po_ref[:] = p - lr * upd
    amax_m = jnp.max(jnp.abs(m), axis=-1, keepdims=True)
    inv_m = jnp.where(amax_m > 0, 127.0 / amax_m, 1.0)   # div per ROW
    mco_ref[:] = jnp.clip(jnp.round(m * inv_m), -127, 127).astype(jnp.int8)
    scmo_ref[:] = jnp.where(amax_m > 0, amax_m * (1.0 / 127.0), 1.0)
    amax_r = jnp.max(r, axis=-1, keepdims=True)
    inv_r = jnp.where(amax_r > 0, 255.0 / amax_r, 1.0)
    # rounded up, as adam8bit._quant_pos: never under the true denominator
    rcode = jnp.clip(jnp.ceil(r * inv_r), 0, 255).astype(jnp.int32)
    rco_ref[:] = jnp.where(rcode > 127, rcode - 256, rcode).astype(jnp.int8)
    scro_ref[:] = jnp.where(amax_r > 0, amax_r * (1.0 / 255.0), 1.0)


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "l2", "interpret"))
def _leaf_update(g, p, mc, rc, scm, scr, scalars, *, b1, b2, eps, wd, l2,
                 interpret):
    """One fused update on a (R, C) leaf; scalars = [gscale, lr, c1, c2]."""
    R, C = p.shape
    br = _block_rows(R, C)
    grid = (pl.cdiv(R, br),)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    sc_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    kern = functools.partial(_kernel, b1, b2, eps, wd, l2)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  row_spec, row_spec, row_spec, row_spec, sc_spec, sc_spec],
        out_specs=[row_spec, row_spec, row_spec, sc_spec, sc_spec],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        interpret=interpret,
    )(scalars, g, p, mc,
      jax.lax.bitcast_convert_type(rc, jnp.int8), scm, scr)


def fused_leaf_supported(shape) -> bool:
    """Rows fit VMEM and the row-block tiles legally (Mosaic requires the
    sublane block dim divisible by 8 unless it spans the whole axis)."""
    if not (len(shape) >= 1 and 0 < shape[-1] <= _MAX_ROW):
        return False
    C = shape[-1]
    R = 1
    for d in shape[:-1]:
        R *= d
    br = _block_rows(R, C)
    return br == R or br % 8 == 0


def apply_fused_leaf(g, p, mc, rc, scales, scalars, *, b1, b2, eps, wd, l2,
                     interpret):
    """Reshape a leaf to rows, run the kernel, restore shapes.

    Returns ``(p', mc', rc', {"m": scm', "r": scr'})`` exactly like one
    step of the unfused ``scale_by_adam8bit`` + decay + lr chain.
    """
    shape = p.shape
    C = shape[-1]
    R = p.size // C
    scm = scales["m"].reshape(R, 1)
    scr = scales["r"].reshape(R, 1)
    po, mco, rco, scmo, scro = _leaf_update(
        g.astype(jnp.float32).reshape(R, C), p.reshape(R, C),
        mc.reshape(R, C), rc.reshape(R, C), scm, scr, scalars,
        b1=b1, b2=b2, eps=eps, wd=wd, l2=l2, interpret=interpret)
    sshape = shape[:-1] + (1,)
    rco = jax.lax.bitcast_convert_type(rco, jnp.uint8)
    return (po.reshape(shape), mco.reshape(shape), rco.reshape(shape),
            {"m": scmo.reshape(sshape), "r": scro.reshape(sshape)})
