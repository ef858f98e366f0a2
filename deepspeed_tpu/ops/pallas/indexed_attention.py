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

What a tile waits for on the v5e is the unit that moves data across lanes
and the latency of one head's chain, not the MXU (PR 47 and PR 43 found
both in the flash kernels; PR 57 carried them here).  So the heads' loops
run four heads a trip (:func:`_head_trips`), a sum over a tile's keys is kept
a partial sum a lane and reduced once a program (``flash_attention.py
_lane_blocks_sum``; a row MAXIMUM stays a reduction), and ``dkv_call``'s
tile stands KEYS BY QUERIES, so that every product that sums over the
queries contracts the tile as it stands and a query's numbers (``lse``,
``delta``, ``tau`` ...) lie along its lanes as rows.
``indexed_attn_tile_ops_total`` counts, as a body is traced, what is left.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry
from .flash_attention import _lane_blocks_sum, _sum_lanes

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


def _index_tile_t(qi_ref, ki, wt):
    """:func:`_index_tile` standing keys by queries: the same products
    (64 channels, one MXU pass) and the same sum over heads in order, so
    the same float32 scores (``chip_smoke.kernel_indexed_attention`` counts
    the kept pairs of every tile in both orientations).  ``ki`` (block_k,
    channels), ``wt`` (heads, block_q)."""
    acc = None
    for j in range(qi_ref.shape[1]):
        term = wt[j:j + 1] * jnp.maximum(_nt(ki, qi_ref[0, j]), 0.0)
        acc = term if acc is None else acc + term
    return jnp.where(acc == 0.0, 0.0, acc)


def _positions(q0, k0, shape, keys_first=False):
    """``(queries', keys')`` positions of a tile of ``shape``."""
    q_axis = int(keys_first)
    return (q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis),
            k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


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
        rows, cols = _positions(q0, k0, (block_q, block_k))
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


def _kept(sel_refs, scores, q0, k0, keys_first=False):
    """``(kept bool, 0 / NEG float32)`` of a tile: the causal pairs the
    selection keeps.  ``sel_refs`` is ``(tau, cut)`` (two numbers a query,
    against this tile's scores) or ``(mask,)`` (int8, from outside); each
    stands as the tile does (``keys_first``: keys by queries)."""
    rows, cols = _positions(q0, k0, scores.shape, keys_first)
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


def _mm(a, b):
    """``a b``, float32."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _turned(x):
    """``(B, S, n)`` as ``(B, n, S)``: by XLA, outside the kernels."""
    return jnp.swapaxes(x, 1, 2)


def _note(kernel, op, n=1):
    _registry.counter(
        "indexed_attn_tile_ops_total",
        "what a head's tile of the indexed-attention kernel body being "
        "traced (fwd, dq, dkv) still asks of the unit that moves data "
        "across lanes: heads_a_trip (independent heads' chains in one trip "
        "of the heads' loops), lane_reduction_a_head (sums or maxima over "
        "lanes a head a tile), transposed_contraction (products a tile that "
        "contract dimension 0 of both operands); counted at trace time, not "
        "per call", labelnames=("kernel", "op")).labels(kernel, op).inc(n)


def _heads_a_trip(heads):
    """Four, where the count allows: a call at the tenth cell's shape read
    -7 / -0 / -15% with one (the other changes of PR 57 alone), -23 / -10 /
    -22% with two, -30 / -14 / -26% with four and -33 / -15 / -28% with
    eight (forward / dq / dkv against PR 54's kernels; my chip runs,
    PR 57); eight compiled 3.5 s longer a program that holds the three, and
    the cell's cold set-up had 8 s to spare."""
    return next(n for n in (4, 2, 1) if heads % n == 0)


def _note_body(kernel, heads):
    """A kernel body is being traced: its heads a trip, and a zero said (not
    left out) for what its tiles may still ask of the cross-lane unit."""
    _note(kernel, "heads_a_trip", _heads_a_trip(heads))
    for op in ("lane_reduction_a_head", "transposed_contraction"):
        _note(kernel, op, 0)


def _head_trips(heads, trip, carry):
    """The heads' loop of a tile: ``trip(hs, carry)`` for the heads ``hs``
    of each trip (:func:`_heads_a_trip`).  Their chains are independent and
    stand in one basic block, so one's products run under the others'
    vector work (a loop's trips do not overlap: PR 43, PR 55; all of them
    unrolled cost PR 55 15% of its warm set-up)."""
    per = _heads_a_trip(heads)
    return jax.lax.fori_loop(
        0, heads // per,
        lambda t, c: trip([t * per + u for u in range(per)], c), carry)


def _group_lanes(ref, hs, group, width):
    """The key-value head's lanes of ``ref`` for each head of a trip,
    loaded once where they share it."""
    if group % len(hs) == 0:    # a trip starts at a multiple of its length
        return [_head_lanes(ref, hs[0] // group, width)] * len(hs)
    return [_head_lanes(ref, h // group, width) for h in hs]


# --------------------------------------------------------------------------
# forward


def _forward_kernel(*refs, scale, heads, group, n_sel, block_k):
    (q_ref, k_ref, v_ref, qi_ref, kit_ref, w_ref), rest = refs[:6], refs[6:]
    sel_refs, rest = rest[:n_sel], rest[n_sel:]
    (out_ref, lse_ref, kl_ref, lsei_ref, count_ref,
     acc, m_ref, l_ref, sums, mi_ref) = rest
    block_q, D = q_ref.shape[1], q_ref.shape[2] // heads
    i, phase, kt = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    q0, k0 = i * block_q, kt * block_k
    last = _last_live(i, block_q, block_k)
    dtype = q_ref.dtype
    _note_body("fwd", heads)

    @pl.when((phase == 0) & (kt == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        sums[...] = jnp.zeros_like(sums)
        mi_ref[...] = jnp.full_like(mi_ref, NEG)
        count_ref[...] = jnp.zeros_like(count_ref)

    def tile():
        scores = _index_tile(qi_ref, kit_ref[0], w_ref[0])
        kept, neg = _kept(sel_refs, scores, q0, k0)
        return scores, kept, neg

    def logits(hs, neg):
        return [_nt(_head_lanes(q_ref, h, D), kg) * scale + neg
                for h, kg in zip(hs, _group_lanes(k_ref, hs, group, D))]

    @pl.when((phase == 0) & (kt <= last))
    def _():
        _, _, neg = tile()

        def trip(hs, carry):
            ss = logits(hs, neg)
            m_old = [m_ref[h] for h in hs]
            _note("fwd", "lane_reduction_a_head")   # the maximum: PR 47
            m_new = [jnp.maximum(m, s.max(axis=1, keepdims=True))
                     for m, s in zip(m_old, ss)]
            ps = [jnp.exp(s - m) for s, m in zip(ss, m_new)]
            pv = [_mm(p.astype(dtype), vg)
                  for p, vg in zip(ps, _group_lanes(v_ref, hs, group, D))]
            for h, m0, m, p, o in zip(hs, m_old, m_new, ps, pv):
                alpha = jnp.exp(m0 - m)
                l_ref[h] = alpha * l_ref[h] + _lane_blocks_sum(p)
                m_ref[h] = m
                at = pl.ds(pl.multiple_of(h * D, D), D)
                acc[:, at] = alpha * acc[:, at] + o
            return carry

        _head_trips(heads, trip, 0)

    @pl.when((phase == 0) & (kt == last))
    def _():
        for h in range(heads):      # over lanes: once a program
            l = l_ref[h].sum(axis=1, keepdims=True)
            at = slice(h * D, (h + 1) * D)
            out_ref[0, :, at] = (acc[:, at] / l).astype(out_ref.dtype)
            m_ref[h] = m_ref[h] + jnp.log(l)            # phase 1 reads lse
            lse_ref[0, :, h:h + 1] = m_ref[h]

    @pl.when((phase == 1) & (kt <= last))
    def _():
        scores, kept, neg = tile()

        def trip(hs, psum):     # as the backward reads them: exp(s - lse)
            return psum + sum(jnp.exp(s - m_ref[h])
                              for h, s in zip(hs, logits(hs, neg)))

        pbar = _head_trips(heads, trip,
                           jnp.zeros(scores.shape, jnp.float32)) / heads
        # sum pbar log pbar, sum pbar I, sum pbar, the kept scores' online
        # sum of exponentials: a partial sum a lane each
        sums[0] += _lane_blocks_sum(jnp.where(pbar > 0.0, pbar * jnp.log(
            jnp.where(pbar > 0.0, pbar, 1.0)), 0.0))
        sums[1] += _lane_blocks_sum(pbar * scores)
        sums[2] += _lane_blocks_sum(pbar)
        masked = scores + neg
        m_old = mi_ref[...]
        m_new = jnp.maximum(m_old, masked.max(axis=1, keepdims=True))
        sums[3] = sums[3] * jnp.exp(m_old - m_new) + _lane_blocks_sum(
            jnp.where(kept, jnp.exp(masked - m_new), 0.0))
        mi_ref[...] = m_new
        lane = jax.lax.broadcasted_iota(jnp.int32, count_ref.shape[2:], 1)
        count_ref[0, 0] = jnp.where(
            lane == kt, jnp.sum(kept.astype(jnp.float32)), count_ref[0, 0])

    @pl.when((phase == 1) & (kt == last))
    def _():
        total = [sums[n].sum(axis=1, keepdims=True) for n in range(4)]
        lse_i = mi_ref[...] + jnp.log(total[3])
        lsei_ref[0] = lse_i
        kl_ref[0] = total[0] - total[1] + total[2] * lse_i


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
    lanes = _sum_lanes(block_k)

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
                        pltpu.VMEM((heads, block_q, lanes), jnp.float32),
                        pltpu.VMEM((4, block_q, lanes), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        cost_estimate=_attention_cost(B, S, heads, W // heads, NI, DI, 3,
                                      2 * q.size * q.dtype.itemsize),
        interpret=interpret, name="indexed_attn_fwd",
    )(q, k, v, qi, kit, w, *sel)


# --------------------------------------------------------------------------
# backward


def _grad_tile(q_ref, k_ref, v_ref, do_ref, neg, lse_of, delta_of, scale,
               heads, group, per_trip, keys_first=False):
    """The heads' loop of a backward tile: ``per_trip(hs, [(p, ds), ...])``
    for the heads of each trip, and the sum of the heads' probabilities.
    The tile stands as ``neg`` does: queries by keys, or keys by queries."""
    D = q_ref.shape[2] // heads

    def trip(hs, psum):
        made = []
        for h, kg, vg in zip(hs, _group_lanes(k_ref, hs, group, D),
                             _group_lanes(v_ref, hs, group, D)):
            qh, doh = _head_lanes(q_ref, h, D), _head_lanes(do_ref, h, D)
            s, dp = ((_nt(kg, qh), _nt(vg, doh)) if keys_first
                     else (_nt(qh, kg), _nt(doh, vg)))
            p = jnp.exp(s * scale + neg - lse_of(h))
            made.append((p, p * (dp - delta_of(h)) * scale))
        per_trip(hs, made)
        return psum + sum(p for p, _ in made)

    return _head_trips(heads, trip, jnp.zeros(neg.shape, jnp.float32))


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
    _note_body("dq", heads)

    @pl.when(kt == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        lse, delta = lse_ref[0].T, delta_ref[0].T   # (block_q, heads)
        for h in range(heads):      # a head's column, once a program
            lse_s[h] = lse[:, h:h + 1]
            delta_s[h] = delta[:, h:h + 1]

    @pl.when(kt <= last)
    def _():
        w = w_ref[0]
        scores = _index_tile(qi_ref, kit_ref[0], w)
        kept, neg = _kept(sel_refs, scores, q0, k0)

        def per_trip(hs, made):
            dqs = [_mm(ds.astype(dtype), kg) for (_, ds), kg in zip(
                made, _group_lanes(k_ref, hs, group, D))]
            for h, dq in zip(hs, dqs):
                dq_acc[:, pl.ds(pl.multiple_of(h * D, D), D)] += dq

        pbar = _grad_tile(q_ref, k_ref, v_ref, do_ref, neg,
                          lambda h: lse_s[h], lambda h: delta_s[h], scale,
                          heads, group, per_trip) / heads
        d_scores = _score_grad(scores, kept, pbar, lsei_ref, dkl_ref)
        ki = ki_ref[0]
        for j in range(qi_ref.shape[1]):
            z = _mm(qi_ref[0, j], kit_ref[0])
            dw_acc[:, j:j + 1] += jnp.sum(d_scores * jnp.maximum(z, 0.0),
                                          axis=1, keepdims=True)
            dz = jnp.where(z > 0.0, d_scores * w[:, j:j + 1], 0.0)
            dqi_acc[j] += _mm(dz.astype(dtype), ki)

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
    heads) float32)``.  ``lse`` and ``delta`` reach the kernel as
    :func:`dkv_call` takes them, ``(B, heads, S)`` (one turn by XLA serves
    both; ``(B, S, 32)`` float32 is padded to 128 lanes in HBM, four times
    its bytes), and a program turns its block back once."""
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
    heads_rows = pl.BlockSpec((1, heads, block_q), lambda b, i, kt: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, heads=heads,
                          group=heads // kv_heads, n_sel=len(sel),
                          block_k=block_k),
        grid=(B, S // block_q, S // block_k),
        in_specs=[rows(W), keys(k.shape[2]), keys(v.shape[2]), rows(W),
                  heads_rows, heads_rows, index_rows,
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
    )(q, k, v, do, _turned(lse), _turned(delta), qi, kit, ki, w, lse_i, dkl,
      *sel)


def _dkv_kernel(*refs, scale, heads, group, n_sel, block_q):
    """A block of keys against the blocks of queries at or after it; the
    tile stands keys by queries, a query's numbers are rows along its lanes
    and a head's a sublane of ``lse_ref`` / ``delta_ref`` (1, heads,
    block_q)."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi_ref, ki_ref, wt_ref,
     lsei_ref, dkl_ref), rest = refs[:11], refs[11:]
    sel_refs, rest = rest[:n_sel], rest[n_sel:]
    dk_ref, dv_ref, dki_ref, dk_acc, dv_acc, dki_acc = rest
    block_k, D = k_ref.shape[1], q_ref.shape[2] // heads
    j, i = pl.program_id(1), pl.program_id(2)
    first = (j * block_k) // block_q
    q0, k0 = i * block_q, j * block_k
    dtype = q_ref.dtype
    _note_body("dkv", heads)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when(i >= first)
    def _():
        wt, ki = wt_ref[0], ki_ref[0]
        scores = _index_tile_t(qi_ref, ki, wt)
        kept, neg = _kept(sel_refs, scores, q0, k0, keys_first=True)

        def per_trip(hs, made):
            grads = [(_mm(p.astype(dtype), _head_lanes(do_ref, h, D)),
                      _mm(ds.astype(dtype), _head_lanes(q_ref, h, D)))
                     for h, (p, ds) in zip(hs, made)]
            if group % len(hs) == 0:    # one key-value head's: one add
                hs, grads = hs[:1], [[sum(g) for g in zip(*grads)]]
            for h, (dv, dk) in zip(hs, grads):
                at = pl.ds(pl.multiple_of((h // group) * D, D), D)
                dv_acc[:, at] += dv
                dk_acc[:, at] += dk

        pbar = _grad_tile(q_ref, k_ref, v_ref, do_ref, neg,
                          lambda h: lse_ref[0, pl.ds(h, 1)],
                          lambda h: delta_ref[0, pl.ds(h, 1)], scale, heads,
                          group, per_trip, keys_first=True) / heads
        d_scores = _score_grad(scores, kept, pbar, lsei_ref, dkl_ref)
        for n in range(qi_ref.shape[1]):
            z = _nt(ki, qi_ref[0, n])
            dz = jnp.where(z > 0.0, d_scores * wt[n:n + 1], 0.0)
            dki_acc[...] += _mm(dz.astype(dtype), qi_ref[0, n])

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
    """``(dk, dv (B, S, KV D), dk_I (B, S, channels) float32)``.  What is a
    number a query (and head) reaches the kernel turned, ``(B, heads | 1,
    S)`` (a mask keys by queries), so that it lies along the lanes of a
    tile that stands keys by queries: a few MB, turned by XLA (the whole
    step reserves 10.872 GiB where it reserved 10.880: sandbox compile,
    PR 57)."""
    B, S, W = q.shape
    NI, DI = qi.shape[1], qi.shape[3]

    def live(b, j, i):
        return jnp.maximum(i, (j * block_k) // block_q)

    def rows(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda *g: (g[0], live(*g), 0))

    def lanes(height):
        return pl.BlockSpec((1, height, block_q),
                            lambda *g: (g[0], 0, live(*g)))

    def keys(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, j, 0))

    if len(sel) == 1:
        sel_specs = [pl.BlockSpec((1, block_k, block_q),
                                  lambda *g: (g[0], g[1], live(*g)))]
    else:
        sel_specs = [lanes(1)] * 2
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, heads=heads,
                          group=heads // kv_heads, n_sel=len(sel),
                          block_q=block_q),
        grid=(B, S // block_k, S // block_q),
        in_specs=[rows(W), keys(k.shape[2]), keys(v.shape[2]), rows(W),
                  lanes(heads), lanes(heads),
                  pl.BlockSpec((1, NI, block_q, DI),
                               lambda *g: (g[0], 0, live(*g), 0)),
                  keys(DI), lanes(NI), lanes(1), lanes(1), *sel_specs],
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
    )(q, k, v, do, _turned(lse), _turned(delta), qi, _turned(kit), _turned(w),
      _turned(lse_i), _turned(dkl), *map(_turned, sel))
