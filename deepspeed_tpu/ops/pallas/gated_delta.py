"""The sequential part of the chunked gated delta rule (``ops/gated_delta.py``)
as two Pallas kernels, the state resident in VMEM across the chunk axis of
the grid, as the flash kernels keep their accumulators.

What depends on no state (``U``, ``W``, ``P = lower_incl(Q K^T * G)``, ``Q *
exp(gamma)``, ``K * exp(gamma_C - gamma)``, ``exp(gamma_C)``; ``_prepare``
there) is XLA's, batched over all chunks, and so is its transposition.  What
is sequential, a head-sequence ``(b, h)`` and a chunk ``n`` with ``S`` the
state entering it (keys x values, float32):

    V' = U - W S            O = Qg S + P V'            S <- gl S + Kd^T V'

runs here: ``gated_delta_fwd`` walks the chunks of a head-sequence in
order, :data:`GROUP` chunks a grid step (a chunk is ~5 MFLOP of products,
far less than a grid step's fixed cost), its state in a ``(d, d)`` float32
scratch that is zeroed at a head-sequence's first step.  Under ``jax.vjp``
it also writes the state ENTERING each chunk (``(BH, N, d, d)`` float32:
64 KB a chunk a head), which ``gated_delta_bwd`` reads walking the chunks
backwards with the state's cotangent resident the same way:

    dV' = P^T dO + Kd dS'   dP = dO V'^T   dQg = dO S^T   dKd = V' dS'^T
    dU = dV'   dW = -dV' S^T   dgl = <dS', S>   dS = gl dS' + Qg^T dO - W^T dV'

(``V'`` is recomputed from ``U``, ``W`` and ``S``: one product).  Operands
of every product are in the arrays' own type (bf16 in a bf16 model; the
state and ``V'`` are rounded to it as operands), sums float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# chunks a grid step
GROUP = 4
_VMEM_LIMIT = 64 * 1024 * 1024


def supported(n_chunks: int, chunk: int, d: int, dtype) -> Optional[str]:
    """``None`` where the kernels take ``n_chunks`` chunks of ``chunk``
    positions and heads of ``d`` channels, else the reason they do not."""
    if dtype != jnp.bfloat16:
        return f"operands of {jnp.dtype(dtype).name}"
    if d % 128:
        return f"head channels {d} are no multiple of 128"
    if chunk % 16:
        return f"chunks of {chunk} positions are no whole bf16 tiles of 16"
    if n_chunks % GROUP:
        return f"{n_chunks} chunks are no whole groups of {GROUP}"
    return None


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _nt(a, b):          # a @ b.T
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _tn(a, b):          # a.T @ b
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(gl_ref, u_ref, w_ref, p_ref, qg_ref, kd_ref, o_ref, *rest,
                n_chunks, save):
    state = rest[-1]
    head, step = pl.program_id(0), pl.program_id(1)
    cdt = w_ref.dtype

    @pl.when(step == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    for i in range(GROUP):
        s = state[...]
        if save:
            rest[0][0, i] = s
        sb = s.astype(cdt)
        v_new = u_ref[0, i] - _nn(w_ref[0, i], sb)
        vb = v_new.astype(cdt)
        o = _nn(qg_ref[0, i], sb) + _nn(p_ref[0, i], vb)
        o_ref[0, i] = o.astype(o_ref.dtype)
        gl = gl_ref[head * n_chunks + step * GROUP + i]
        state[...] = gl * s + _tn(kd_ref[0, i], vb)


def _bwd_kernel(gl_ref, u_ref, w_ref, p_ref, qg_ref, kd_ref, s_ref, do_ref,
                du_ref, dw_ref, dp_ref, dqg_ref, dkd_ref, dgl_ref, dstate, *,
                n_chunks):
    head, step = pl.program_id(0), pl.program_id(1)
    cdt = w_ref.dtype
    last = n_chunks // GROUP - 1

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, dstate.dtype)

    for i in reversed(range(GROUP)):
        s = s_ref[0, i]
        sb = s.astype(cdt)
        ds1 = dstate[...]
        ds1b = ds1.astype(cdt)
        w, kd, do = w_ref[0, i], kd_ref[0, i], do_ref[0, i]
        vb = (u_ref[0, i] - _nn(w, sb)).astype(cdt)
        dv = _tn(p_ref[0, i], do) + _nn(kd, ds1b)
        dvb = dv.astype(cdt)
        du_ref[0, i] = dv
        dw_ref[0, i] = (-_nt(dvb, sb)).astype(dw_ref.dtype)
        dp_ref[0, i] = _nt(do, vb).astype(dp_ref.dtype)
        dqg_ref[0, i] = _nt(do, sb).astype(dqg_ref.dtype)
        dkd_ref[0, i] = _nt(vb, ds1b).astype(dkd_ref.dtype)
        dgl_ref[0, i] = jnp.full(dgl_ref.shape[2:], jnp.sum(ds1 * s),
                                 jnp.float32)
        gl = gl_ref[head * n_chunks + (last - step) * GROUP + i]
        dstate[...] = gl * ds1 + _tn(qg_ref[0, i], do) - _tn(w, dvb)


def _specs(C, d, index):
    """Block specs of ``(u | w | qg | kd | o, p)`` under ``index``."""
    return (pl.BlockSpec((1, GROUP, C, d), index),
            pl.BlockSpec((1, GROUP, C, C), index))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("save", "interpret"))
def forward(u, w, p, qg, kd, g_last, *, save: bool = False,
            interpret: bool = False):
    """``o`` (BH, N, C, d) in ``w``'s type of ``u`` (float32), ``w``, ``qg``,
    ``kd`` (BH, N, C, d), ``p`` (BH, N, C, C) and ``g_last`` (BH, N)
    float32; with ``save`` also the states entering the chunks, (BH, N, d,
    d) float32."""
    BH, N, C, d = u.shape
    wide, square = _specs(C, d, lambda h, j, gl: (h, j, 0, 0))
    out_specs, out_shape = [wide], [jax.ShapeDtypeStruct(u.shape, w.dtype)]
    if save:
        out_specs.append(pl.BlockSpec((1, GROUP, d, d),
                                      lambda h, j, gl: (h, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, N, d, d), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_chunks=N, save=save),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH, N // GROUP),
            in_specs=[wide, wide, square, wide, wide], out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
        out_shape=out_shape, compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * BH * N * C * d * (3 * d + C), transcendentals=0,
            bytes_accessed=BH * N * C * (14 * d + 2 * C)
            + (4 * BH * N * d * d if save else 0)),
        name="gated_delta_fwd", interpret=interpret,
    )(g_last.reshape(-1), u, w, p, qg, kd)
    return tuple(out) if save else out[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def backward(u, w, p, qg, kd, g_last, states, do, *, interpret: bool = False):
    """The cotangents ``(du, dw, dp, dqg, dkd, dg_last)`` of
    :func:`forward`'s operands under ``do``, in the operands' types."""
    BH, N, C, d = u.shape
    steps = N // GROUP

    def back(h, j, gl):
        return (h, steps - 1 - j, 0, 0)

    wide, square = _specs(C, d, back)
    state = pl.BlockSpec((1, GROUP, d, d), back)
    scalar = pl.BlockSpec((1, GROUP, 8, 128), back)
    du, dw, dp, dqg, dkd, dgl = pl.pallas_call(
        functools.partial(_bwd_kernel, n_chunks=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH, steps),
            in_specs=[wide, wide, square, wide, wide, state, wide],
            out_specs=[wide, wide, square, wide, wide, scalar],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(qg.shape, qg.dtype),
                   jax.ShapeDtypeStruct(kd.shape, kd.dtype),
                   jax.ShapeDtypeStruct((BH, N, 8, 128), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * BH * N * C * d * (7 * d + 2 * C), transcendentals=0,
            bytes_accessed=BH * N * (C * (26 * d + 4 * C) + 4 * d * d)),
        name="gated_delta_bwd", interpret=interpret,
    )(g_last.reshape(-1), u, w, p, qg, kd, states, do)
    return du, dw, dp, dqg, dkd, dgl[:, :, 0, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan_chunks(u, w, p, qg, kd, g_last, interpret=False):
    """:func:`ops.gated_delta._scan_xla` by the kernels: operands (B, Hv, N,
    C, .), ``o`` (B, Hv, N, C, d)."""
    return _merged(u, w, p, qg, kd, g_last, interpret, False)[0]


def _merged(u, w, p, qg, kd, g_last, interpret, save):
    B, H = u.shape[:2]
    flat = [x.reshape((B * H,) + x.shape[2:])
            for x in (u, w, p, qg, kd, g_last)]
    out = forward(*flat, save=save, interpret=interpret)
    o, states = out if save else (out, None)
    return o.reshape(u.shape[:2] + o.shape[1:]), flat, states


def _scan_fwd(u, w, p, qg, kd, g_last, interpret):
    o, flat, states = _merged(u, w, p, qg, kd, g_last, interpret, True)
    return o, (flat, states)


def _scan_bwd(interpret, res, do):
    flat, states = res
    B, H = do.shape[:2]
    grads = backward(*flat, states, do.reshape((B * H,) + do.shape[2:]),
                     interpret=interpret)
    return tuple(g.reshape((B, H) + g.shape[1:]) for g in grads)


scan_chunks.defvjp(_scan_fwd, _scan_bwd)
