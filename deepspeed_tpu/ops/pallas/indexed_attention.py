"""Pallas kernels of attention over a learned, per-query key set
(DeepSeek-Sparse-Attention's training stage; ``ops/indexed_attention.py``
has the equations, the dispatch and the plain ``jax.numpy`` form).

Four calls a layer, none of which holds an ``S x S`` array or a whole row
of q, dO or dq anywhere:

``select_call``     a program a block of queries: the indexer's scores of
                    the block against every causal key live as sortable
                    int32 keys in ONE VMEM panel ``(block_q, S)``; the
                    ``k``-th largest of each row is found exactly by a
                    bisection over the 32 bits of the key (a count a bit),
                    and where more keys tie at that value than the row may
                    keep, the position up to which a tie is kept by a second
                    bisection over positions.  Out: ``tau`` and ``cut``, two
                    numbers a query.
``forward_call``    grid ``(rows, query blocks, 2, key tiles)``.  Every
                    tile rebuilds the indexer's scores of its pairs from
                    the same operands in the same order (so they are the
                    numbers ``tau`` was read from) and keeps the pairs
                    ``I > tau or (I == tau and s <= cut)``; phase 0 is the
                    online softmax of all query heads over the kept pairs,
                    phase 1 sweeps the keys again WITHOUT the values: the
                    heads' probabilities from the finished ``lse``, their
                    mean ``pbar`` and the four row sums that make
                    ``KL(pbar || softmax_kept I)``.
``dq_call`` / ``dkv_call``  the backward over the same tiles, the first a
                    block of queries at a time (dq, and the indexer's dq_I
                    and dw), the second a block of keys (dk, dv, dk_I).

A selection from outside is an int8 ``(rows, S, S)`` mask in ``tau``'s and
``cut``'s place (tests and the benchmark's comparison hand in a
reference's; the step never builds one).

A dense sweep under a mask: every causal tile is multiplied whatever it
keeps.  What skipping could save is counted (``tile_counts``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_INT_MIN = -2 ** 31
_VMEM_LIMIT = 100 << 20


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _flip(bits):
    """Float bits <-> an int32 whose signed order is the floats': an
    involution, the same map turns a key back."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _sort_key(x):
    return _flip(pltpu.bitcast(x, jnp.int32))


def _index_tile(qi_ref, kit, w):
    """The indexer's scores of a tile: ``sum_j w[:, j] relu(qI_j kI^T)`` in
    float32, heads in order; a zero of either sign reads +0.  ``qi_ref``
    (1, heads, block_q, channels), ``kit`` (channels, block_k), ``w``
    (block_q, heads) with the two scales folded in."""
    acc = None
    for j in range(qi_ref.shape[1]):
        z = jnp.dot(qi_ref[0, j], kit, preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(z, 0.0)
        acc = term if acc is None else acc + term
    return jnp.where(acc == 0.0, 0.0, acc)


def _positions(q0, k0, block_q, block_k):
    rows = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _last_live(i, block_q, block_k):
    """The last key tile that holds a causal pair of query block ``i``."""
    return (i * block_q + block_q - 1) // block_k


# --------------------------------------------------------------------------
# selection


def _select_kernel(qi_ref, kit_ref, w_ref, tau_ref, cut_ref, panel, *, topk,
                   block_k):
    block_q, S = panel.shape
    q0 = pl.program_id(1) * block_q
    n_live = _last_live(pl.program_id(1), block_q, block_k) + 1
    w = w_ref[0]
    lanes = min(128, block_k)

    def fill(kt, carry):
        k0 = pl.multiple_of(kt * block_k, block_k)
        scores = _index_tile(qi_ref, kit_ref[0, :, pl.ds(k0, block_k)], w)
        rows, cols = _positions(q0, k0, block_q, block_k)
        panel[:, pl.ds(k0, block_k)] = jnp.where(
            cols <= rows, _sort_key(scores), _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_live, fill, 0)
    row = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    want = jnp.minimum(topk, row + 1)           # keys this query keeps

    def count(test):
        """Pairs a row for which ``test(keys, positions)`` holds."""
        def tile(kt, cnt):
            k0 = pl.multiple_of(kt * block_k, block_k)
            for c in range(block_k // lanes):
                at = k0 + c * lanes
                pos = at + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, lanes), 1)
                cnt = cnt + test(panel[:, pl.ds(at, lanes)],
                                 pos).astype(jnp.int32)
            return cnt

        cnt = jax.lax.fori_loop(0, n_live, tile,
                                jnp.zeros((block_q, lanes), jnp.int32))
        return cnt.sum(axis=1, keepdims=True)

    def value_bit(b, found):        # keys as unsigned: key ^ INT_MIN
        cand = found | jnp.left_shift(jnp.int32(1), 31 - b)
        signed = cand ^ _INT_MIN
        enough = count(lambda keys, pos: keys >= signed) >= want
        return jnp.where(enough, cand, found)

    tau = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros((block_q, 1), jnp.int32)) ^ _INT_MIN
    above = count(lambda keys, pos: keys > tau)
    ties = count(lambda keys, pos: keys == tau)
    need = want - above                         # ties kept, lowest first

    def find_cut():
        """The largest position P with fewer than ``need`` ties before it:
        the ``need``-th tie sits at P."""
        def position_bit(b, found):
            cand = found | jnp.left_shift(jnp.int32(1), bits - 1 - b)
            few = count(lambda keys, pos: (keys == tau) & (pos < cand)) < need
            return jnp.where(few, cand, found)

        bits = max(1, (S - 1).bit_length())
        return jax.lax.fori_loop(0, bits, position_bit,
                                 jnp.zeros((block_q, 1), jnp.int32))

    cut = jax.lax.cond(jnp.max(ties - need) > 0, find_cut,
                       lambda: jnp.full((block_q, 1), S, jnp.int32))
    tau_ref[0] = pltpu.bitcast(_flip(tau), jnp.float32)
    cut_ref[0] = cut


@functools.partial(jax.jit, static_argnames=("topk", "block_q", "block_k",
                                             "interpret"), inline=True)
def select_call(qi, kit, w, *, topk, block_q, block_k, interpret):
    """``(tau (B, S, 1) float32, cut (B, S, 1) int32)`` of ``qi`` (B, heads,
    S, channels), ``kit`` (B, channels, S), ``w`` (B, S, heads)."""
    B, NI, S, DI = qi.shape
    row = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k),
        grid=(B, S // block_q),
        in_specs=[pl.BlockSpec((1, NI, block_q, DI),
                               lambda b, i: (b, 0, i, 0)),
                  pl.BlockSpec((1, DI, S), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, block_q, NI), lambda b, i: (b, i, 0))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((B, S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_q, S), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
        cost_estimate=pl.CostEstimate(
            flops=B * S * S * NI * DI, transcendentals=0,
            bytes_accessed=B * S * (NI * DI + DI) * qi.dtype.itemsize),
        interpret=interpret, name="indexer_select",
    )(qi, kit, w)


# --------------------------------------------------------------------------
# what every attention kernel does with a tile first


def _kept(sel_refs, scores, q0, k0):
    """``(kept bool, 0 / NEG float32)`` of a tile: the causal pairs the
    selection keeps.  ``sel_refs`` is ``(tau, cut)`` (two numbers a query,
    against this tile's scores) or ``(mask,)`` (int8, from outside)."""
    rows, cols = _positions(q0, k0, *scores.shape)
    if len(sel_refs) == 1:      # widened first: Mosaic relays no int8 mask
        kept = sel_refs[0][0].astype(jnp.int32) != 0
    else:
        tau, cut = sel_refs[0][0], sel_refs[1][0]
        kept = (scores > tau) | ((scores == tau) & (cols <= cut))
    kept = kept & (cols <= rows)
    return kept, jnp.where(kept, 0.0, NEG)


def _head_lanes(ref, h, width):
    return ref[0, :, pl.ds(pl.multiple_of(h * width, width), width)]


def _nt(a, b):
    """``a b^T``, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a^T b``, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _column(block, h):
    """Column ``h`` (traced) of a ``(rows, heads)`` block as ``(rows, 1)``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == h, block, 0.0), axis=1, keepdims=True)


# --------------------------------------------------------------------------
# forward


def _forward_kernel(*refs, scale, heads, group, n_sel, block_k):
    (q_ref, k_ref, v_ref, qi_ref, kit_ref, w_ref), rest = refs[:6], refs[6:]
    sel_refs, rest = rest[:n_sel], rest[n_sel:]
    (out_ref, lse_ref, kl_ref, lsei_ref, count_ref,
     acc, m_ref, l_ref, sums) = rest
    block_q, D = q_ref.shape[1], q_ref.shape[2] // heads
    i, phase, kt = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    q0, k0 = i * block_q, kt * block_k
    last = _last_live(i, block_q, block_k)
    dtype = q_ref.dtype

    @pl.when((phase == 0) & (kt == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        sums[...] = jnp.zeros_like(sums)
        sums[3] = jnp.full((block_q, 1), NEG, jnp.float32)
        count_ref[...] = jnp.zeros_like(count_ref)

    def tile():
        scores = _index_tile(qi_ref, kit_ref[0], w_ref[0])
        kept, neg = _kept(sel_refs, scores, q0, k0)
        return scores, kept, neg

    def logits(h, neg):
        kg = _head_lanes(k_ref, h // group, D)
        return _nt(_head_lanes(q_ref, h, D), kg) * scale + neg

    @pl.when((phase == 0) & (kt <= last))
    def _():
        _, _, neg = tile()

        def head(h, carry):
            s = logits(h, neg)
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            at = pl.ds(pl.multiple_of(h * D, D), D)
            acc[:, at] = alpha * acc[:, at] + jnp.dot(
                p.astype(dtype), _head_lanes(v_ref, h // group, D),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when((phase == 0) & (kt == last))
    def _():
        for h in range(heads):
            at = slice(h * D, (h + 1) * D)
            out_ref[0, :, at] = (acc[:, at] / l_ref[h]).astype(out_ref.dtype)
            m_ref[h] = m_ref[h] + jnp.log(l_ref[h])     # phase 1 reads lse
            lse_ref[0, :, h:h + 1] = m_ref[h]

    @pl.when((phase == 1) & (kt <= last))
    def _():
        scores, kept, neg = tile()

        def head(h, psum):      # as the backward reads them: exp(s - lse)
            return psum + jnp.exp(logits(h, neg) - m_ref[h])

        pbar = jax.lax.fori_loop(
            0, heads, head, jnp.zeros(scores.shape, jnp.float32)) / heads
        # sum pbar log pbar, sum pbar I, sum pbar; the kept scores' online lse
        sums[0] += jnp.sum(jnp.where(pbar > 0.0, pbar * jnp.log(
            jnp.where(pbar > 0.0, pbar, 1.0)), 0.0), axis=1, keepdims=True)
        sums[1] += jnp.sum(pbar * scores, axis=1, keepdims=True)
        sums[2] += jnp.sum(pbar, axis=1, keepdims=True)
        masked = scores + neg
        m_old = sums[3]
        m_new = jnp.maximum(m_old, masked.max(axis=1, keepdims=True))
        sums[4] = sums[4] * jnp.exp(m_old - m_new) + jnp.sum(
            jnp.where(kept, jnp.exp(masked - m_new), 0.0), axis=1,
            keepdims=True)
        sums[3] = m_new
        lane = jax.lax.broadcasted_iota(jnp.int32, count_ref.shape[2:], 1)
        count_ref[0, 0] = jnp.where(
            lane == kt, jnp.sum(kept.astype(jnp.float32)), count_ref[0, 0])

    @pl.when((phase == 1) & (kt == last))
    def _():
        lse_i = sums[3] + jnp.log(sums[4])
        lsei_ref[0] = lse_i
        kl_ref[0] = sums[0] - sums[1] + sums[2] * lse_i


def _sel_specs(sel, block_q, block_k, at_q, at_k):
    """Block specs of a selection: two numbers a query, or a mask tile."""
    if len(sel) == 1:
        return [pl.BlockSpec((1, block_q, block_k),
                             lambda *g: (g[0], at_q(*g), at_k(*g)))]
    return [pl.BlockSpec((1, block_q, 1), lambda *g: (g[0], at_q(*g), 0))] * 2


def _attention_cost(B, S, heads, D, NI, DI, products, out_bytes):
    pairs = B * S * S // 2
    return pl.CostEstimate(
        flops=pairs * 2 * (heads * D * products + NI * DI),
        transcendentals=pairs * heads, bytes_accessed=out_bytes)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "block_q", "block_k",
                                             "interpret"), inline=True)
def forward_call(q, k, v, qi, kit, w, sel, *, heads, kv_heads, scale, block_q,
                 block_k, interpret):
    """``(out (B, S, H D), lse (B, S, H), kl (B, S, 1), lse_I (B, S, 1),
    kept pairs a tile (B, S / block_q, 1, S / block_k))`` of rows ``q`` (B,
    S, H D), ``k`` and ``v`` (B, S, KV D), the indexer's ``qi`` (B, heads,
    S, channels), ``kit`` (B, channels, S), ``w`` (B, S, heads) and the
    selection ``sel``."""
    B, S, W = q.shape
    NI, DI = qi.shape[1], qi.shape[3]
    nq, nk = S // block_q, S // block_k

    def live(b, i, ph, kt):
        return jnp.minimum(kt, _last_live(i, block_q, block_k))

    def held(b, i, ph, kt):     # phase 1 reads no values: keep the block
        return jnp.where(ph == 0, live(b, i, ph, kt),
                         _last_live(i, block_q, block_k))

    def rows(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, ph, kt: (b, i, 0))

    key = pl.BlockSpec((1, block_k, k.shape[2]),
                       lambda *g: (g[0], live(*g), 0))
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=scale, heads=heads,
                          group=heads // kv_heads, n_sel=len(sel),
                          block_k=block_k),
        grid=(B, nq, 2, nk),
        in_specs=[rows(W), key,
                  pl.BlockSpec((1, block_k, v.shape[2]),
                               lambda *g: (g[0], held(*g), 0)),
                  pl.BlockSpec((1, NI, block_q, DI),
                               lambda b, i, ph, kt: (b, 0, i, 0)),
                  pl.BlockSpec((1, DI, block_k),
                               lambda *g: (g[0], 0, live(*g))),
                  rows(NI),
                  *_sel_specs(sel, block_q, block_k, lambda *g: g[1], live)],
        out_specs=[rows(W), rows(heads), rows(1), rows(1),
                   pl.BlockSpec((1, 1, 1, nk),
                                lambda b, i, ph, kt: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, S, W), q.dtype),
                   jax.ShapeDtypeStruct((B, S, heads), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, nq, 1, nk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, W), jnp.float32),
                        pltpu.VMEM((heads, block_q, 1), jnp.float32),
                        pltpu.VMEM((heads, block_q, 1), jnp.float32),
                        pltpu.VMEM((5, block_q, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        cost_estimate=_attention_cost(B, S, heads, W // heads, NI, DI, 3,
                                      2 * q.size * q.dtype.itemsize),
        interpret=interpret, name="indexed_attn_fwd",
    )(q, k, v, qi, kit, w, *sel)


# --------------------------------------------------------------------------
# backward


def _grad_tile(q_ref, k_ref, v_ref, do_ref, neg, lse_of, delta_of, scale,
               heads, group, per_head):
    """The heads' loop of a backward tile: ``per_head(h, p, ds)`` for each,
    and the sum of the heads' probabilities."""
    D = q_ref.shape[2] // heads

    def head(h, psum):
        g = h // group
        s = _nt(_head_lanes(q_ref, h, D), _head_lanes(k_ref, g, D)) * scale
        p = jnp.exp(s + neg - lse_of(h))
        dp = _nt(_head_lanes(do_ref, h, D), _head_lanes(v_ref, g, D))
        per_head(h, p, p * (dp - delta_of(h)) * scale)
        return psum + p

    return jax.lax.fori_loop(0, heads, head,
                             jnp.zeros(neg.shape, jnp.float32))


def _score_grad(scores, kept, pbar, lsei_ref, dkl_ref):
    """d loss / d I of a tile: ``dkl (softmax_kept I - pbar)`` on the kept."""
    return jnp.where(kept, dkl_ref[0] * (
        jnp.exp(jnp.where(kept, scores, NEG) - lsei_ref[0]) - pbar), 0.0)


def _dq_kernel(*refs, scale, heads, group, n_sel, block_k):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi_ref, kit_ref, ki_ref,
     w_ref, lsei_ref, dkl_ref), rest = refs[:12], refs[12:]
    sel_refs, rest = rest[:n_sel], rest[n_sel:]
    dq_ref, dqi_ref, dw_ref, dq_acc, dqi_acc, dw_acc, lse_s, delta_s = rest
    block_q, D = q_ref.shape[1], q_ref.shape[2] // heads
    i, kt = pl.program_id(1), pl.program_id(2)
    q0, k0 = i * block_q, kt * block_k
    last = _last_live(i, block_q, block_k)
    dtype = q_ref.dtype

    @pl.when(kt == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        for h in range(heads):      # a head's column, once a program
            lse_s[h] = lse_ref[0, :, h:h + 1]
            delta_s[h] = delta_ref[0, :, h:h + 1]

    @pl.when(kt <= last)
    def _():
        w = w_ref[0]
        scores = _index_tile(qi_ref, kit_ref[0], w)
        kept, neg = _kept(sel_refs, scores, q0, k0)

        def per_head(h, p, ds):
            at = pl.ds(pl.multiple_of(h * D, D), D)
            dq_acc[:, at] += jnp.dot(ds.astype(dtype),
                                     _head_lanes(k_ref, h // group, D),
                                     preferred_element_type=jnp.float32)

        pbar = _grad_tile(q_ref, k_ref, v_ref, do_ref, neg,
                          lambda h: lse_s[h], lambda h: delta_s[h], scale,
                          heads, group, per_head) / heads
        d_scores = _score_grad(scores, kept, pbar, lsei_ref, dkl_ref)
        ki = ki_ref[0]
        for j in range(qi_ref.shape[1]):
            z = jnp.dot(qi_ref[0, j], kit_ref[0],
                        preferred_element_type=jnp.float32)
            dw_acc[:, j:j + 1] += jnp.sum(d_scores * jnp.maximum(z, 0.0),
                                          axis=1, keepdims=True)
            dz = jnp.where(z > 0.0, d_scores * w[:, j:j + 1], 0.0)
            dqi_acc[j] += jnp.dot(dz.astype(dtype), ki,
                                  preferred_element_type=jnp.float32)

    @pl.when(kt == last)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dqi_ref[0] = dqi_acc[...]
        dw_ref[0] = dw_acc[...]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "block_q", "block_k",
                                             "interpret"), inline=True)
def dq_call(q, k, v, do, lse, delta, qi, kit, ki, w, lse_i, dkl, sel, *,
            heads, kv_heads, scale, block_q, block_k, interpret):
    """``(dq (B, S, H D), dq_I (B, heads, S, channels) float32, dw (B, S,
    heads) float32)``."""
    B, S, W = q.shape
    NI, DI = qi.shape[1], qi.shape[3]

    def live(b, i, kt):
        return jnp.minimum(kt, _last_live(i, block_q, block_k))

    def rows(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, kt: (b, i, 0))

    def keys(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda *g: (g[0], live(*g), 0))

    index_rows = pl.BlockSpec((1, NI, block_q, DI),
                              lambda b, i, kt: (b, 0, i, 0))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, heads=heads,
                          group=heads // kv_heads, n_sel=len(sel),
                          block_k=block_k),
        grid=(B, S // block_q, S // block_k),
        in_specs=[rows(W), keys(k.shape[2]), keys(v.shape[2]), rows(W),
                  rows(heads), rows(heads), index_rows,
                  pl.BlockSpec((1, DI, block_k),
                               lambda *g: (g[0], 0, live(*g))),
                  keys(DI), rows(NI), rows(1), rows(1),
                  *_sel_specs(sel, block_q, block_k, lambda *g: g[1], live)],
        out_specs=[rows(W), index_rows, rows(NI)],
        out_shape=[jax.ShapeDtypeStruct((B, S, W), q.dtype),
                   jax.ShapeDtypeStruct(qi.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, S, NI), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, W), jnp.float32),
                        pltpu.VMEM((NI, block_q, DI), jnp.float32),
                        pltpu.VMEM((block_q, NI), jnp.float32),
                        pltpu.VMEM((heads, block_q, 1), jnp.float32),
                        pltpu.VMEM((heads, block_q, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=_attention_cost(B, S, heads, W // heads, NI, DI, 3,
                                      3 * q.size * q.dtype.itemsize),
        interpret=interpret, name="indexed_attn_dq",
    )(q, k, v, do, lse, delta, qi, kit, ki, w, lse_i, dkl, *sel)


def _dkv_kernel(*refs, scale, heads, group, n_sel, block_q):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi_ref, kit_ref, w_ref,
     lsei_ref, dkl_ref), rest = refs[:11], refs[11:]
    sel_refs, rest = rest[:n_sel], rest[n_sel:]
    dk_ref, dv_ref, dki_ref, dk_acc, dv_acc, dki_acc = rest
    block_k, D = k_ref.shape[1], q_ref.shape[2] // heads
    j, i = pl.program_id(1), pl.program_id(2)
    first = (j * block_k) // block_q
    q0, k0 = i * block_q, j * block_k
    dtype = q_ref.dtype

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when(i >= first)
    def _():
        w = w_ref[0]
        scores = _index_tile(qi_ref, kit_ref[0], w)
        kept, neg = _kept(sel_refs, scores, q0, k0)
        lse, delta = lse_ref[0], delta_ref[0]

        def per_head(h, p, ds):
            at = pl.ds(pl.multiple_of((h // group) * D, D), D)
            dv_acc[:, at] += _tn(p.astype(dtype), _head_lanes(do_ref, h, D))
            dk_acc[:, at] += _tn(ds.astype(dtype), _head_lanes(q_ref, h, D))

        pbar = _grad_tile(q_ref, k_ref, v_ref, do_ref, neg,
                          lambda h: _column(lse, h),
                          lambda h: _column(delta, h), scale, heads, group,
                          per_head) / heads
        d_scores = _score_grad(scores, kept, pbar, lsei_ref, dkl_ref)
        for n in range(qi_ref.shape[1]):
            z = jnp.dot(qi_ref[0, n], kit_ref[0],
                        preferred_element_type=jnp.float32)
            dz = jnp.where(z > 0.0, d_scores * w[:, n:n + 1], 0.0)
            dki_acc[...] += _tn(dz.astype(dtype), qi_ref[0, n])

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        dki_ref[0] = dki_acc[...]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "block_q", "block_k",
                                             "interpret"), inline=True)
def dkv_call(q, k, v, do, lse, delta, qi, kit, w, lse_i, dkl, sel, *, heads,
             kv_heads, scale, block_q, block_k, interpret):
    """``(dk, dv (B, S, KV D), dk_I (B, S, channels) float32)``."""
    B, S, W = q.shape
    NI, DI = qi.shape[1], qi.shape[3]

    def live(b, j, i):
        return jnp.maximum(i, (j * block_k) // block_q)

    def rows(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda *g: (g[0], live(*g), 0))

    def keys(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, j, 0))

    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, heads=heads,
                          group=heads // kv_heads, n_sel=len(sel),
                          block_q=block_q),
        grid=(B, S // block_k, S // block_q),
        in_specs=[rows(W), keys(k.shape[2]), keys(v.shape[2]), rows(W),
                  rows(heads), rows(heads),
                  pl.BlockSpec((1, NI, block_q, DI),
                               lambda *g: (g[0], 0, live(*g), 0)),
                  pl.BlockSpec((1, DI, block_k), lambda b, j, i: (b, 0, j)),
                  rows(NI), rows(1), rows(1),
                  *_sel_specs(sel, block_q, block_k, live, lambda *g: g[1])],
        out_specs=[keys(k.shape[2]), keys(v.shape[2]), keys(DI)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, S, DI), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, k.shape[2]), jnp.float32),
                        pltpu.VMEM((block_k, v.shape[2]), jnp.float32),
                        pltpu.VMEM((block_k, DI), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=_attention_cost(B, S, heads, W // heads, NI, DI, 4,
                                      3 * q.size * q.dtype.itemsize),
        interpret=interpret, name="indexed_attn_dkv",
    )(q, k, v, do, lse, delta, qi, kit, w, lse_i, dkl, *sel)
