"""Attention over a key set that is DATA: a learned indexer scores every
causal key of a query and attention keeps the ``topk`` best
(DeepSeek-Sparse-Attention, the sparse training stage of the
DeepSeek-V3.2-Exp report; Keye-VL-2.0's ``sa_config``).

For a query t and the causal keys s <= t of its row::

    I[t, s]  = sum_j w[t, j] * relu(qI[t, j] . kI[s])           # float32
    S_t      = the min(topk, t + 1) keys of largest I[t, .],
               ties to the lower position (lax.top_k's rule)
    o[t, i]  = sum_{s in S_t} softmax_{s in S_t}(q[t, i] . k[s, g(i)] * scale)
               v[s, g(i)]
    pbar[t]  = stop_gradient(mean_i p[t, i, .])                  # over S_t
    kl[t]    = KL(pbar[t] || softmax_{s in S_t} I[t, s])

``w`` arrives with its constant scales folded in.  The selection passes no
gradient; ``q, k, v`` get theirs from ``o`` alone and ``qI, kI, w`` from
``kl`` alone.  One set a query serves all heads.

Two implementations behind :func:`indexed_attention` (``impl``):

``"pallas"``  ``ops/pallas/indexed_attention.py``: the exact selection as
              two numbers a query (the ``topk``-th largest score and the
              position up to which a tie at it is kept), then a dense
              causal sweep masked by them.  Nothing of ``S x S`` is ever in
              HBM and no kernel holds a whole row of q, dO or dq.
``"jnp"``     the plain form: a block of queries at a time, ``lax.top_k``
              over the block's panel of scores, a dense softmax over the
              kept pairs; differentiated by JAX.
``"auto"``    the kernels on a TPU where the shapes tile and the operands
              are one device's own, else the plain form;
              ``kernel_dispatch_total{site="indexed_attention"}`` says
              which and why.

``selection=`` takes the kept pairs from outside (bool ``(B, S, S)``; the
causal part is used): the second stage under a reference's selection.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import trace

IMPLS = ("auto", "pallas", "jnp")
STAT_TILE = 512     # tile_counts' tiles: what block skipping could skip
# queries and keys a kernel program (a shorter row is one block; tests set
# smaller ones)
BLOCK_Q, BLOCK_K = 256, 512


class IndexedAttention(NamedTuple):
    out: jax.Array          # (B, S, H, D)
    kl: jax.Array           # (B, S) float32: KL(pbar_t || softmax_kept I_t)
    # kept pairs a (tile x tile) square of the score matrix, tile =
    # min(STAT_TILE, S): (B, S / tile, S / tile) float32
    tile_counts: jax.Array


class _Static(NamedTuple):
    heads: int
    kv_heads: int
    scale: float
    block_q: int
    block_k: int
    interpret: bool


def indexer_scores(qi, ki, w):
    """``I`` (B, T, S) float32 of the queries of ``qi`` (B, T, heads,
    channels) and ``w`` (B, T, heads) against every key of ``ki`` (B, S,
    channels), causal or not: the plain form's scores."""
    z = jnp.einsum("bqjd,bsd->bqjs", qi, ki,
                   preferred_element_type=jnp.float32)
    scores = (w[..., None] * jax.nn.relu(z)).sum(2)
    return jnp.where(scores == 0.0, 0.0, scores)     # -0 reads +0


def stat_tile(S: int) -> int:
    return min(STAT_TILE, S)


def impl_of(attn_impl: str) -> str:
    """A model's ``attn_impl`` as this op's ``impl`` (``"flash"`` asks for
    the kernels)."""
    return {"flash": "pallas"}.get(attn_impl, attn_impl)


# --------------------------------------------------------------------------
# the plain form


def _top_keys(scores, causal, topk):
    """The kept pairs of a block of queries: ``lax.top_k`` over the causal
    scores (B, T, S), as a mask."""
    B, T, S = scores.shape
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, S))
    return jnp.zeros(scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None],
        idx].set(True) & causal


def _block_scores(qi, ki, w, t0, T: int):
    """``(causal (T, S) bool, I (B, T, S))`` of the queries ``t0 .. t0 + T -
    1`` against every key."""
    causal = jnp.arange(ki.shape[1])[None, :] <= (t0 + jnp.arange(T))[:, None]
    return causal, indexer_scores(
        jax.lax.dynamic_slice_in_dim(qi, t0, T, 1), ki,
        jax.lax.dynamic_slice_in_dim(w, t0, T, 1))


def plain_selection(qi, ki, w, topk: int):
    """The plain form's selection as a mask, bool (B, S, S): for tests and
    comparisons at sizes where that may exist."""
    S = qi.shape[1]
    T = stat_tile(S)

    def block(t0):
        causal, scores = _block_scores(qi, ki, w, t0, T)
        return _top_keys(scores, causal, topk)

    kept = jax.lax.map(block, jnp.arange(0, S, T))      # (S / T, B, T, S)
    return jnp.moveaxis(kept, 0, 1).reshape(qi.shape[0], S, S)


def _jnp_form(q, k, v, qi, ki, w, topk, scale, selection):
    B, S, H, D = q.shape
    KV = k.shape[2]
    T = stat_tile(S)
    if S % T:
        raise ValueError(f"a row of {S} positions is no whole tiles of {T}")
    qg = q.reshape(B, S, KV, H // KV, D)

    @jax.checkpoint         # the backward recomputes a block's panels
    def block(t0):
        causal, scores = _block_scores(qi, ki, w, t0, T)
        if selection is None:
            kept = _top_keys(scores, causal, topk)
        else:
            kept = jax.lax.dynamic_slice_in_dim(selection, t0, T, 1) & causal
        s = jnp.einsum("bqgid,bsgd->bgiqs",
                       jax.lax.dynamic_slice_in_dim(qg, t0, T, 1), k,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(kept[:, None, None], s, -jnp.inf), -1)
        out = jnp.einsum("bgiqs,bsgd->bqgid", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        pbar = jax.lax.stop_gradient(p.mean((1, 2)))            # (B, T, S)
        log_soft = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
        live = kept & (pbar > 0.0)
        kl = jnp.sum(jnp.where(live, pbar * (
            jnp.log(jnp.where(live, pbar, 1.0))
            - jnp.where(live, log_soft, 0.0)), 0.0), -1)
        counts = kept.reshape(B, T, S // T, T).sum((1, 3))
        return (out.reshape(B, T, H, D).astype(q.dtype), kl,
                counts.astype(jnp.float32))

    out, kl, counts = jax.lax.map(block, jnp.arange(0, S, T))
    return IndexedAttention(
        jnp.moveaxis(out, 0, 1).reshape(B, S, H, D),
        jnp.moveaxis(kl, 0, 1).reshape(B, S),
        jax.lax.stop_gradient(jnp.moveaxis(counts, 0, 1)))


# --------------------------------------------------------------------------
# the kernels


def _zero_cotangent(x):
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _attend(q, k, v, qi, ki, w, sel, st: _Static):
    return _attend_fwd(q, k, v, qi, ki, w, sel, st)[0]


def _attend_fwd(q, k, v, qi, ki, w, sel, st: _Static):
    from .pallas.indexed_attention import forward_call

    kit = jnp.swapaxes(ki, 1, 2)
    out, lse, kl, lse_i, counts = forward_call(
        q, k, v, qi, kit, w, sel, heads=st.heads, kv_heads=st.kv_heads,
        scale=st.scale, block_q=st.block_q, block_k=st.block_k,
        interpret=st.interpret)
    # named as the flash kernels' residuals: a "<policy>+flash" remat keeps
    # them, and the backward of a block runs no forward kernel again
    out = checkpoint_name(out, "flash_out")
    lse, lse_i = (checkpoint_name(x, "flash_lse") for x in (lse, lse_i))
    return (out, kl, counts), (q, k, v, qi, ki, w, sel, out, lse, lse_i)


def _attend_bwd(st: _Static, res, cotangents):
    from .pallas.indexed_attention import dkv_call, dq_call

    q, k, v, qi, ki, w, sel, out, lse, lse_i = res
    do, dkl, _ = cotangents
    B, S, W = q.shape
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
        B, S, st.heads, W // st.heads).sum(-1)
    kit = jnp.swapaxes(ki, 1, 2)
    kw = dict(heads=st.heads, kv_heads=st.kv_heads, scale=st.scale,
              block_q=st.block_q, block_k=st.block_k, interpret=st.interpret)
    dq, dqi, dw = dq_call(q, k, v, do, lse, delta, qi, kit, ki, w, lse_i,
                          dkl, sel, **kw)
    dk, dv, dki = dkv_call(q, k, v, do, lse, delta, qi, kit, w, lse_i, dkl,
                           sel, **kw)
    return (dq, dk, dv, dqi.astype(qi.dtype), dki.astype(ki.dtype),
            dw.astype(w.dtype), tuple(_zero_cotangent(x) for x in sel))


_attend.defvjp(_attend_fwd, _attend_bwd)


def select(qi, ki, w, topk: int, *, interpret: bool = False):
    """The kernels' first stage alone: ``(tau (B, S) float32, cut (B, S)
    int32)``, the ``topk``-th largest causal score of each query and the
    position up to which a key that ties at it is kept (S: all of them)."""
    from .pallas.indexed_attention import select_call

    S = qi.shape[1]
    tau, cut = select_call(
        jnp.swapaxes(qi, 1, 2), jnp.swapaxes(ki, 1, 2),
        w.astype(jnp.float32), topk=int(topk), block_q=min(BLOCK_Q, S),
        block_k=min(BLOCK_K, S), interpret=interpret)
    return tau[..., 0], cut[..., 0]


def _pallas_form(q, k, v, qi, ki, w, topk, scale, selection, interpret):
    B, S, H, D = q.shape
    KV = k.shape[2]
    bq, bk = min(BLOCK_Q, S), min(BLOCK_K, S)
    T = stat_tile(S)
    if S % bq or S % bk or T % bq or T % bk:
        raise ValueError(
            f"rows of {S} positions under blocks of {bq} queries and {bk} "
            f"keys: both divide the row and the statistics' tile {T}")
    qi4 = jnp.swapaxes(qi, 1, 2)                # (B, heads, S, channels)
    w = w.astype(jnp.float32)
    if selection is None:
        with trace.device_span("attn/select"):
            tau, cut = select(jax.lax.stop_gradient(qi),
                              jax.lax.stop_gradient(ki),
                              jax.lax.stop_gradient(w), topk,
                              interpret=interpret)
            sel = tuple(checkpoint_name(x[..., None], "flash_lse")
                        for x in (tau, cut))
    else:
        sel = (selection.astype(jnp.int8),)
    with trace.device_span("self_attn_indexed"):
        out, kl, counts = _attend(
            q.reshape(B, S, H * D), k.reshape(B, S, KV * D),
            v.reshape(B, S, KV * D), qi4, ki, w, sel,
            _Static(H, KV, float(scale), bq, bk, bool(interpret)))
    counts = counts.reshape(B, S // T, T // bq, S // T, T // bk).sum((2, 4))
    return IndexedAttention(out.reshape(B, S, H, D), kl[..., 0],
                            jax.lax.stop_gradient(counts))


def indexed_attention(q, k, v, qi, ki, w, *, topk: int,
                      scale: Optional[float] = None, impl: str = "auto",
                      selection=None,
                      interpret: bool = False) -> IndexedAttention:
    """``q`` (B, S, H, D), ``k`` and ``v`` (B, S, KV, D), the indexer's
    ``qi`` (B, S, heads, channels), ``ki`` (B, S, channels) and ``w`` (B,
    S, heads); see the module's docstring."""
    from .pallas import spmd

    if impl not in IMPLS:
        raise ValueError(f"impl {impl!r}; there are {IMPLS}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, S, H, D = q.shape
    # the kernels where they were asked for, or the shapes tile, this is a
    # TPU (or the interpreter runs them) and the operands one device's own
    asked = impl != "auto"
    if impl == "jnp":
        refusal = "impl='jnp' requested"
    elif asked or not (D % 128 or S % 128):
        refusal = None
    else:
        refusal = f"auto: head_dim {D} or row {S} is no multiple of 128"
    if spmd.plan("indexed_attention", None if asked else B, refusal,
                 "impl='pallas' requested" if asked
                 else "auto: TPU, head_dim and row tile", fallback="jnp",
                 tpu=not (asked or interpret), shard=False) is None:
        return _jnp_form(q, k, v, qi, ki, w, int(topk), scale, selection)
    return _pallas_form(q, k, v, qi, ki, w, int(topk), scale, selection,
                        interpret)
