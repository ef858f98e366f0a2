"""Pallas fused cross-entropy head: matmul + online-logsumexp, no HBM
logits.

The LM head is the single largest non-attention cost of small-model
training (GPT-2-125M: the (N,V)=(24576,50304) fp32 logits are ~4.9 GB
written+re-read per pass).  The XLA chunked head (``models/common.py
_fused_ce``) bounds residency and multiplies each chunk's logits out
once (its forward rule makes ``dh`` and ``dW`` from them, its backward
scales the two) but still materializes the chunk's fp32 logits in HBM,
between that product and the passes that read them.  This kernel
computes per-token ``logsumexp`` and the label logit ONLINE while
streaming vocab blocks through VMEM — logits never touch HBM, in either
pass (reference analog:
``csrc/transformer/general_kernels.cu`` fused logits/softmax path).

Layout contract (Mosaic tiling): per-token vectors ride as
``(nt, 1, bq)`` so every block's last-two dims equal the array dims.
``E`` and ``Vp`` must be lane-aligned (the model zoo pads vocab to 128);
``bv`` must divide ``Vp``.

Backward recomputes each logits block (one extra head matmul vs saving
them — measured CHEAPER than any O(N·V) HBM traffic; see
BENCH_NORTHSTAR.md round-3 sweep: replaying saved bf16 logits lost 20%
e2e) in two kernels: ``dh`` (grid token×vocab, accumulate over vocab)
and ``dwte`` (grid vocab×token, accumulate over token).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fwd_kernel(lbl_ref, h_ref, w_ref, nll_ref, lse_ref, m_sc, l_sc, ll_sc,
                *, bq, bv, nv, vocab_size, ignore_index):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        ll_sc[...] = jnp.zeros_like(ll_sc)

    h = h_ref[...].astype(jnp.float32)                     # (bq, E)
    w = w_ref[...].astype(jnp.float32)                     # (E, bv)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (bq, bv)
    vpos = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bq, bv), 1)
    logits = jnp.where(vpos < vocab_size, logits, NEG)
    lbl = lbl_ref[0, 0]                                    # (bq,) int32

    m_old = m_sc[0]
    m_new = jnp.maximum(m_old, logits.max(axis=1))
    corr = jnp.exp(m_old - m_new)
    l_sc[0] = l_sc[0] * corr + jnp.exp(logits - m_new[:, None]).sum(axis=1)
    m_sc[0] = m_new
    ll_sc[0] = ll_sc[0] + jnp.sum(
        jnp.where(vpos == lbl[:, None], logits, 0.0), axis=1)

    @pl.when(j == nv - 1)
    def _fin():
        lse = m_sc[0] + jnp.log(l_sc[0])
        valid = lbl != ignore_index
        nll_ref[0, 0] = jnp.where(valid, lse - ll_sc[0], 0.0)
        lse_ref[0, 0] = lse


def _dh_kernel(lbl_ref, h_ref, w_ref, lse_ref, dh_ref,
               *, bq, bv, nv, vocab_size, ignore_index):
    j = pl.program_id(1)
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    vpos = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bq, bv), 1)
    logits = jnp.where(vpos < vocab_size, logits, NEG)
    lbl = lbl_ref[0, 0]
    lse = lse_ref[0, 0]
    p = jnp.exp(logits - lse[:, None])
    coeff = (lbl != ignore_index).astype(jnp.float32)      # (bq,)
    dlog = (p - (vpos == lbl[:, None]).astype(jnp.float32)) \
        * coeff[:, None]                                   # (bq, bv) f32
    contrib = jax.lax.dot_general(
        dlog.astype(w_ref.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (bq, E)

    @pl.when(j == 0)
    def _first():
        dh_ref[...] = contrib

    @pl.when(j > 0)
    def _rest():
        dh_ref[...] = dh_ref[...] + contrib


def _dw_kernel(lbl_ref, h_ref, w_ref, lse_ref, dw_ref,
               *, bq, bv, nt, vocab_size, ignore_index):
    t = pl.program_id(1)
    j = pl.program_id(0)
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    vpos = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bq, bv), 1)
    logits = jnp.where(vpos < vocab_size, logits, NEG)
    lbl = lbl_ref[0, 0]
    lse = lse_ref[0, 0]
    p = jnp.exp(logits - lse[:, None])
    coeff = (lbl != ignore_index).astype(jnp.float32)
    dlog = (p - (vpos == lbl[:, None]).astype(jnp.float32)) \
        * coeff[:, None]
    # dw_blk = h^T @ dlog: contract the token dim → (E, bv)
    contrib = jax.lax.dot_general(
        h.astype(h_ref.dtype), dlog.astype(h_ref.dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _first():
        dw_ref[...] = contrib

    @pl.when(t > 0)
    def _rest():
        dw_ref[...] = dw_ref[...] + contrib


def _pick_bv(Vp: int, cap: int = 512) -> int:
    """Largest lane-aligned divisor of Vp not above cap."""
    best = 128
    for mult in range(1, cap // 128 + 1):
        bv = 128 * mult
        if Vp % bv == 0:
            best = bv
    return best


@functools.lru_cache(maxsize=None)
def _build(N, E, Vp, bq, bv, vocab_size, ignore_index, interpret):
    nt, nv = N // bq, Vp // bv
    kw = dict(bq=bq, bv=bv, vocab_size=vocab_size,
              ignore_index=ignore_index)
    f32 = jnp.float32

    lbl_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, 0))
    h_spec = pl.BlockSpec((bq, E), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((E, bv), lambda i, j: (0, j))
    tok_spec = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, 0))

    fwd = pl.pallas_call(
        functools.partial(_fwd_kernel, nv=nv, **kw),
        grid=(nt, nv),
        in_specs=[lbl_spec, h_spec, w_spec],
        out_specs=[tok_spec, tok_spec],
        out_shape=[jax.ShapeDtypeStruct((nt, 1, bq), f32),
                   jax.ShapeDtypeStruct((nt, 1, bq), f32)],
        scratch_shapes=[pltpu.VMEM((1, bq), f32)] * 3,
        interpret=interpret,
    )

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, nv=nv, **kw),
        grid=(nt, nv),
        in_specs=[lbl_spec, h_spec, w_spec, tok_spec],
        out_specs=pl.BlockSpec((bq, E), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, E), f32),
        interpret=interpret,
    )

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, nt=nt, **kw),
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bq), lambda j, t: (t, 0, 0)),
            pl.BlockSpec((bq, E), lambda j, t: (t, 0)),
            pl.BlockSpec((E, bv), lambda j, t: (0, j)),
            pl.BlockSpec((1, 1, bq), lambda j, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((E, bv), lambda j, t: (0, j)),
        out_shape=jax.ShapeDtypeStruct((E, Vp), f32),
        interpret=interpret,
    )
    return fwd, dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def fused_ce_sum(h, wteT, labels, vocab_size, ignore_index, bq, bv,
                 interpret):
    """Σ-over-tokens masked NLL of a tied LM head, logits never in HBM.

    ``h``: (N, E) bf16/f32; ``wteT``: (E, Vp); ``labels``: (N,) int32.
    ``N % bq == 0`` and ``Vp % bv == 0`` (caller pads tokens with
    ignore_index rows).  Returns the un-normalized sum (caller divides
    by the valid count), matching ``models/common._fused_ce``.
    """
    nll, _ = _fwd_pair(h, wteT, labels, vocab_size, ignore_index, bq, bv,
                       interpret)
    return nll.sum()


def _fwd_pair(h, wteT, labels, vocab_size, ignore_index, bq, bv, interpret):
    N, E = h.shape
    Vp = wteT.shape[1]
    fwd, _, _ = _build(N, E, Vp, bq, bv, vocab_size, ignore_index,
                       interpret)
    lbl3 = labels.reshape(N // bq, 1, bq)
    nll, lse = fwd(lbl3, h, wteT)
    return nll, lse


def _ce_fwd(h, wteT, labels, vocab_size, ignore_index, bq, bv, interpret):
    nll, lse = _fwd_pair(h, wteT, labels, vocab_size, ignore_index, bq, bv,
                         interpret)
    return nll.sum(), (h, wteT, labels, lse)


def _ce_bwd(vocab_size, ignore_index, bq, bv, interpret, res, g):
    h, wteT, labels, lse = res
    N, E = h.shape
    Vp = wteT.shape[1]
    _, dh_call, dw_call = _build(N, E, Vp, bq, bv, vocab_size,
                                 ignore_index, interpret)
    lbl3 = labels.reshape(N // bq, 1, bq)
    dh = dh_call(lbl3, h, wteT, lse)
    dw = dw_call(lbl3, h, wteT, lse)
    gf = g.astype(jnp.float32)
    return (dh * gf).astype(h.dtype), (dw * gf).astype(wteT.dtype), \
        np.zeros(labels.shape, jax.dtypes.float0)


fused_ce_sum.defvjp(_ce_fwd, _ce_bwd)


def supported(Vp: int) -> bool:
    """E rides as a fully-covered block dim (any size) and callers pad
    the token dim to ``bq``; the only hard constraint is a lane-aligned
    padded vocab (the model zoo pads to 128)."""
    return Vp % 128 == 0
