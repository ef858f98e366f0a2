"""The chunked gated delta rule (``ops/gated_delta.py``) as two Pallas
kernels that make the chunks' preparation in VMEM beside the scan, from the
layer's own ``q``, ``k``, ``v`` and gates: no ``U``, ``W``, ``P``, decayed
``q`` / ``k`` or solve operand is ever an array in HBM.

**What XLA still prepares** (:func:`_gates`): ``gamma``, the cumulative sum
of ``g`` inside each chunk, beside ``beta``, as ``(B, Hk, 8, S)`` float32
(rows ``gamma`` of the key head's ``r = Hv / Hk`` value heads, then their
``beta``; 32 B a position a key head), and in the backward the reverse
cumulative sum ``dgamma -> dg`` on what comes back in the same layout.

**A grid step** ``(row, key head, group)`` holds :data:`GROUP` chunks of
``C`` positions of ONE key head of ``dk`` channels and its ``r`` value
heads of ``dv``, read in the layout the layer wrote (``q``, ``k`` ``(B, S,
Hk dk)`` blocked ``(1, GROUP C, dk)`` at the head's lane offset, ``v`` and
``o`` ``(1, GROUP C, r dv)``), the ``r`` states (keys x values, ``dk x
dv``, float32) resident in a ``(r, dk, dv)`` scratch across the group axis,
which is ``arbitrary``.

**Heads that are no whole lane tiles** (Olmo-Hybrid: keys of 96 channels,
values of 192) reach the kernels in SLOTS (:func:`_slots`): each head's
channels from the first lane of a slot of the next multiple of 128 (128 and
256), zeros behind them: as the operands arrive where the caller keeps its
heads so (``models/llama.py GatedDeltaNet`` through ``ops/pallas/qk_rows.py
slot_rows``, PR 55: :func:`_slots` is then the identity and ``o`` and the
cotangents go back in slots), else written by XLA beside what made ``q``,
``k`` and ``v``, and ``o`` and the cotangents cut back the same way.  A zero key
channel adds nothing to ``K K^T`` or ``Q K^T`` and its row of the state
stays zero; a zero value channel's column of ``U``, ``V'`` and the state
stays zero: the rule over the slots is the rule over the heads, to the bit
of each sum's order.  The array takes 128 rows or columns a pass whatever
they hold, so a product over 96 or 192 channels costs what one over 128 or
256 does; the slots cost HBM bytes (a third more of q, k, v, o and their
cotangents) and the pass that writes them.  The gates arrive positions
on lanes; their column form is a transpose of a padded ``(128, 128)`` tile
in VMEM.  A chunk: ``K K^T`` and ``Q K^T`` once for the key head, then a
value head

    G  = exp(gamma_i - gamma_j), i >= j     (masked before the exp)
    A  = strict_lower(beta_i K K^T G)
    T  = (I + A)^-1                         (:func:`_inverses`, float32)
    [U | W] = T beta [V | K exp(gamma)]     (float32, HIGHEST)
    V' = U - W S      O = (Q exp(gamma)) S + (Q K^T G) V'
    S <- exp(gamma_C) S + (K exp(gamma_C - gamma))^T V'

``T`` is block forward substitution on the identity: the 16-row diagonal
blocks of ``I + A`` inverted by ``(I + D^8)(I + D^4)(I + D^2)(I - D)``
(exact: ``D^16 = 0``), then pairs of blocks joined, ``[[P, 0], [R, Q]]^-1 =
[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, 16 -> 32 -> 64 rows; every product
float32 at ``HIGHEST`` like the solve of the XLA form, never a product form
over the whole chunk.  What these products cost on the chip is the ROWS of
their left operand (a pass of the array takes them one a cycle, whatever
the other two sizes up to 128), and the left operands here are blocks on a
diagonal: summed into one block's rows (:func:`_pack`) they go through in
16 or 32 rows instead of 64, and a power times ``[factor | power]`` gives
the next factor's share and the next power in one pass of 128 lanes: eight
products of 16, 16, 16, 16, 16, 16, 32, 32 rows a chunk-head where the plain
form streams ten of 64 (my chip runs, PR 49: 4.50 -> 2.67 ms a row forward),
every result the same to the bit.  All of a grid step's chunk-heads go
through a stage together (eight independent chains: 8.29 -> 4.50 ms).  The
large products (those with ``d`` in them) take operands in the arrays' type
(bf16; the state and ``V'`` rounded to it as operands) and sum in float32.

``gated_delta_fwd`` writes ``o``; asked for ``states`` (the backward's first
walk) it writes the state ENTERING each chunk instead, ``(B, Hv, N, dk,
dv)`` float32 (of the slots), and leaves ``Q`` out.  ``gated_delta_bwd`` walks the groups and
their chunks backwards with the states' cotangents resident, makes each
chunk's preparation again from the same inputs and the saved state, and
transposes scan and preparation in place:

    dV' = P^T dO + Kd dS'   dP = dO V'^T   dQg = dO S^T   dKd = V' dS'^T
    dW = -dV' S^T   dgl = <dS', S>   dS = gl dS' + Qg^T dO - W^T dV'
    dR = T^T [dV' | dW]     dA = -strict_lower(dR [U | W]^T)    (HIGHEST)
    dv = beta dR_u          dbeta = <dR, [V | K e^gamma]> + <dA, K K^T G>
    d(K K^T) = beta dA G    d(Q K^T) = dP G     E = A dA + (Q K^T G) dP
    dgamma_i = sum_j E_ij - sum_j E_ji + e^gamma_i <beta dR_w, K>_i
               + e^gamma_i <dQg, Q>_i - e^(gamma_C - gamma_i) <dKd, K>_i
    dgamma_C += gl dgl + sum_i e^(gamma_C - gamma_i) <dKd, K>_i

``dq`` and ``dk`` are summed over the key head's value heads inside the
step.  VMEM a step: the double-buffered blocks (forward 1.3 MB with the
saved states, backward 2.5 MB at ``C`` 64, ``d`` 128, ``r`` 2) and what the
compiler keeps of eight chunk-heads' ``C x C`` and ``C x 2d`` float32 tiles
(~1.5 MB); the limit asked is 64 MB.

**Under a decay a key channel** (Kimi Delta Attention: ``g`` (B, S, Hv,
dk); PR 60) the rule has bodies of its own, ``gated_delta_channel_fwd`` /
``gated_delta_channel_bwd``, that share :func:`_inverses`, :func:`_pack`
and :class:`_Masks` with the above and nothing of the data path: there is
no ``K K^T`` to multiply by a ``C x C`` decay, and gamma is wanted beside
``k``, not positions on lanes.  XLA prepares ``gamma`` as float32 ROWS ``(B,
S, Hv dk)`` (:func:`_chunk_sums`), blocked ``(1, GROUP C, r dk)`` at the
key head's lane offset as ``v`` is, and ``beta`` alone in the gate tile
(:func:`_beta_tile`).  A grid step is ``(row, key head, group)`` as above;
the states are held TRANSPOSED, values x keys ``(r, dv, dk)``, so that a
state's decay ``Diag(exp(gamma_C))`` is a ``(1, dk)`` row over its lanes
and ``<dS', S>`` a sum over sublanes.  A chunk-head, a block of
:data:`SOLVE_BLOCK` = 16 rows ``i`` at a time against the block's OWN first
row ``r`` (``exp(gamma_i - gamma_r) <= 1`` on the rows' side,
``exp(gamma_r - gamma_j)`` on the keys' ``j`` up to the block's last row,
at most ``exp(16 x 5.5)``: nothing overflows float32, and bf16 has its
exponent):

    rows_b = [K near_b | Q near_b]  (32, dk)    keys_b = K far_b  (C, dk)
    [K K^T | Q K^T]_b = rows_b keys_b^T         (q's and k's rows stream
                                    against ONE stationary block of keys)
    A = strict_lower(beta K K^T)    T = (I + A)^-1      (:func:`_inverses`)
    [U | W] = T beta [V | K exp(gamma)]         (float32, HIGHEST)
    V' = U - W S      O = (Q exp(gamma)) S + lower_incl(Q K^T) V'
    S <- Diag(exp(gamma_C)) S + (K exp(gamma_C - gamma))^T V'

The backward walks back with ``dS`` resident, makes the preparation again
from the saved entering states (``channel_forward(states=True)``) and
transposes the scan as the head form does; the block products go back as
``[dK K^T | dQ K^T]_b keys_b`` (the rows' cotangent, 32 rows) and its
transpose against ``rows_b`` (the keys'), ``dq`` and ``dk`` summed in float32
with the decays they were formed under.  **The log-decays' cotangent** is
no difference of rounded outputs: gamma enters every operand as ``exp(+
gamma)`` (a block's rows, ``Q e^gamma``, ``K e^gamma`` of ``W``) or
``exp(-gamma)`` (a block's keys, ``K e^(gamma_C - gamma)``), so each
operand AS IT WAS ROUNDED times its own float32 cotangent is that
appearance's share,

    dgamma = sum_b rows_b * drows_b - sum_b keys_b * dkeys_b
             + Q e^gamma * dQg + K e^gamma * beta dR_w - K e^(gamma_C - gamma) * dKd
    dgamma_C += e^gamma_C <dS', S> + sum_i (K e^(gamma_C - gamma) * dKd)_i

and what cancels between a block's rows and its keys (``sum_i rows * drows
= sum_j keys * dkeys``, the same triple sum) cancels to float32's rounding
as the sums are formed; the blocks' reference rows, of which the result
does not depend, get nothing (``jax.vjp`` of the XLA form hands them
rounding noise).  Against the recurrence in float32 ``dg`` reads 8e-7 (the
XLA form 4.6e-6), in bf16 summed over a head's channels 0.003-0.004 (the
XLA form 0.008-0.010; ``tests/unit/test_gated_delta.py``).  VMEM a step at
``C`` 64, ``d`` 128, ``r`` 1: the double-buffered blocks 1.3 MB forward
and 2.8 MB backward (gamma and ``dgamma`` 128 KB each a buffer), the states
as above, and what the compiler keeps of four chunk-heads' ``(C, dk)``
float32 tiles (~30 of 32 KB each): :func:`channel_supported` counts them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# chunks a grid step
GROUP = 4
# rows of a diagonal block that is inverted by its nilpotent product
SOLVE_BLOCK = 16
_SQUARINGS = (SOLVE_BLOCK - 1).bit_length() - 1     # D^2, D^4, D^8
CHUNKS = (32, 64, 128)
_LANES = 128
_GATE_ROWS = 8
_VMEM_LIMIT = 64 * 1024 * 1024
# what the resident states and the double-buffered block of saved ones may
# take of it (128 x 128 at two heads a key head: 2.25 MiB)
_STATE_BYTES = 24 * 1024 * 1024
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def _slot(d: int) -> int:
    """Lanes of the slot a head of ``d`` channels is written into."""
    return -(-d // _LANES) * _LANES


def _chunks_refusal(n_chunks: int, chunk: int) -> Optional[str]:
    """Why ``n_chunks`` chunks of ``chunk`` positions are no whole grid
    steps of either form's kernels, or None."""
    if chunk not in CHUNKS:
        return (f"chunks of {chunk} positions: the kernels take "
                f"{', '.join(map(str, CHUNKS))} (a group of {GROUP} fills "
                f"whole tiles of {_LANES} lanes, 16-row blocks join in pairs)")
    if n_chunks % GROUP:
        return f"{n_chunks} chunks are no whole groups of {GROUP}"
    return None


def supported(n_chunks: int, chunk: int, dk: int, dv: int, dtype,
              heads_a_key: int = 1) -> Optional[str]:
    """``None`` where the kernels take ``n_chunks`` chunks of ``chunk``
    positions, key heads of ``dk`` and value heads of ``dv`` channels (any
    widths: :func:`_slots`) and ``heads_a_key`` value heads a key head, else
    the reason they do not."""
    if dtype != jnp.bfloat16:
        return f"operands of {jnp.dtype(dtype).name}"
    held = 4 * heads_a_key * _slot(dk) * _slot(dv) * (1 + 2 * GROUP)
    if held > _STATE_BYTES:
        return (f"{heads_a_key} states of {_slot(dk)} x {_slot(dv)} float32 "
                f"(heads of {dk} and {dv} channels in their lane slots): "
                f"resident beside two blocks of {GROUP} saved ones they are "
                f"{held >> 20} MiB of VMEM, more than {_STATE_BYTES >> 20}")
    said = _chunks_refusal(n_chunks, chunk)
    if said:
        return said
    if 2 * heads_a_key > _GATE_ROWS:
        return (f"{heads_a_key} value heads a key head: their gamma and beta "
                f"fill more than one tile of {_GATE_ROWS} rows")
    return None


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _nt(a, b):          # a @ b.T
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _tn(a, b):          # a.T @ b
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _hi(a, b, dims=((1,), (0,))):
    """A float32 product at ``HIGHEST``: the solve's."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                           preferred_element_type=_F32)


class _Masks:
    """The index masks of a ``(C, C)`` tile, made once a kernel body."""

    def __init__(self, C: int):
        row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        self.lower, self.strict = row >= col, row > col
        self.eye = (row == col).astype(_F32)
        n = SOLVE_BLOCK
        self.diagonal = (row // n) == (col // n)
        # joining diagonal blocks of n rows in pairs: the block below the
        # diagonal of each pair, and the second diagonal block of each
        self.joins = []
        while n < C:
            pair = (row // (2 * n)) == (col // (2 * n))
            second = (row // n) % 2 == 1
            self.joins.append((n, pair & second & ((col // n) % 2 == 0),
                               second & ((row // n) == (col // n))))
            n *= 2
        self.last_row = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1


def _pack(x, n: int):
    """``(n, C)``: the sum of the ``n``-row blocks of ``x`` (C, C).  Where
    each block of rows is zero outside a column block of its own, nothing
    is lost, and as the LEFT operand of a product the ``n`` rows do the work
    of all ``C`` (the array's time goes by the rows that stream through)."""
    out = x[:n]
    for s in range(n, x.shape[0], n):
        out = out + x[s:s + n]
    return out


def _unpack(x, mask):
    """``(C, C)`` of a packed ``(n, C)``: every ``n``-row block a copy,
    kept where ``mask`` says that block's columns lie."""
    C = x.shape[1]
    return jnp.where(mask, jnp.concatenate([x] * (C // x.shape[0]), axis=0),
                     0.0)


def _inverses(mats, m: _Masks):
    """``(I + a)^-1`` of each strictly lower triangular ``a`` (C, C) float32
    of ``mats`` (the module's text), the independent chains written a stage
    of all at a time: a product's result is some hundred cycles away, and
    the next stage of the same chain can only wait for it."""
    C, n = mats[0].shape[0], SOLVE_BLOCK
    xs = [jnp.where(m.diagonal, -a, 0.0) for a in mats]          # -D
    invs = [m.eye + x for x in xs]
    packed = [_hi(_pack(x, n), x) for x in xs]                   # D^2
    for s in range(_SQUARINGS):         # (I + D^2), (I + D^4), (I + D^8)
        if s + 1 < _SQUARINGS:
            # one product with the power on the left gives the next
            # factor's share and the next power: 128 lanes of one pass
            both = [_hi(p, jnp.concatenate(
                [inv, _unpack(p, m.diagonal)], axis=1))
                for p, inv in zip(packed, invs)]
            invs = [inv + _unpack(b[:, :C], m.diagonal)
                    for inv, b in zip(invs, both)]
            packed = [b[:, C:] for b in both]
        else:
            invs = [inv + _unpack(_hi(p, inv), m.diagonal)
                    for p, inv in zip(packed, invs)]
    for n, below, second in m.joins:
        lower = [_unpack(_hi(_pack(jnp.where(below, a, 0.0), n), inv), below)
                 for a, inv in zip(mats, invs)]
        invs = [inv - _unpack(_hi(_pack(jnp.where(second, inv, 0.0), n), b),
                              below)
                for inv, b in zip(invs, lower)]
    return invs


def _columns(rows):
    """``(L, 128)`` of gate rows ``(8, L)``: lane ``c`` of position ``p`` is
    row ``c`` at lane ``p``, a transpose a tile of 128 positions."""
    pad = jnp.zeros((_LANES - rows.shape[0], _LANES), rows.dtype)
    tiles = [jnp.concatenate([rows[:, t:t + _LANES], pad], axis=0).T
             for t in range(0, rows.shape[1], _LANES)]
    return jnp.concatenate(tiles, axis=0)


def _last_row(gcol, d: int, m: _Masks):
    """``exp(gamma_C)`` as a ``(1, d)`` row, to scale a state: Mosaic has no
    broadcast of one element over sublanes and lanes at once, so the column
    goes over the lanes first and its last row is picked by a sum."""
    wide = jnp.broadcast_to(gcol, (gcol.shape[0], d))
    return jnp.exp(jnp.sum(jnp.where(m.last_row, wide, 0.0), axis=0,
                           keepdims=True))


@dataclasses.dataclass
class _Head:
    """What of a chunk-head depends on no state and on no query, float32:
    ``decay``, ``a``, ``e_gamma``, ``rhs`` (before ``beta``), ``t`` and ``x
    = [U | W]`` of the module's text, beside its gates as columns ``gcol``
    and ``bcol``, its index ``n`` among the key head's value heads and its
    lanes ``on`` in the ``v`` / ``o`` blocks.  ``x``'s first ``dv`` columns
    are ``U``, the ``dk`` behind them ``W``."""
    n: int
    on: slice
    gcol: jax.Array
    bcol: jax.Array
    decay: jax.Array
    a: jax.Array
    e_gamma: jax.Array
    rhs: jax.Array
    t: Optional[jax.Array] = None
    x: Optional[jax.Array] = None


@dataclasses.dataclass
class _Key:
    """A chunk of the key head of a grid step: its rows ``at`` in the
    blocks, ``k`` as it arrived and in float32, ``kk = K K^T``, and the
    :class:`_Head` of each of its value heads."""
    at: slice
    k: jax.Array
    kf: jax.Array
    kk: jax.Array
    heads: list


def _prepare(k_ref, v_ref, gate_ref, m: _Masks, r: int):
    """The :class:`_Key` of each chunk of a grid step, all of the step's
    chunk-heads a stage at a time (:func:`_inverses`)."""
    C, dv = m.eye.shape[0], v_ref.shape[2] // r
    rows = gate_ref[0, 0]
    cols = _columns(rows)
    chunks = []
    for i in range(GROUP):
        at = slice(i * C, (i + 1) * C)
        k = k_ref[0, at, :]
        key = _Key(at, k, k.astype(_F32), _nt(k, k), [])
        for n in range(r):
            on = slice(n * dv, (n + 1) * dv)
            gcol, bcol = cols[at, n:n + 1], cols[at, r + n:r + n + 1]
            decay = jnp.exp(jnp.where(m.lower, gcol - rows[n:n + 1, at],
                                      -jnp.inf))
            e_gamma = jnp.exp(gcol)
            key.heads.append(_Head(
                n, on, gcol, bcol, decay,
                a=jnp.where(m.strict, bcol * key.kk * decay, 0.0),
                e_gamma=e_gamma, rhs=jnp.concatenate(
                    [v_ref[0, at, on].astype(_F32), key.kf * e_gamma],
                    axis=1)))
        chunks.append(key)
    flat = [it for key in chunks for it in key.heads]
    for it, t in zip(flat, _inverses([it.a for it in flat], m)):
        it.t = t
    for it in flat:
        it.x = _hi(it.t, it.rhs * it.bcol)
    return chunks


def _fwd_kernel(*refs, chunk, r, states):
    if states:
        k_ref, v_ref, gate_ref, out_ref, state = refs
    else:
        q_ref, k_ref, v_ref, gate_ref, out_ref, state = refs
    C, dv = chunk, v_ref.shape[2] // r
    cdt = k_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    m = _Masks(C)
    for i, key in enumerate(_prepare(k_ref, v_ref, gate_ref, m, r)):
        if not states:
            q = q_ref[0, key.at, :]
            qk, qf = _nt(q, key.k), q.astype(_F32)
        for it in key.heads:
            g_last = it.gcol[C - 1:C, :]
            s = state[it.n]
            if states:
                out_ref[0, it.n, i] = s
            sb = s.astype(cdt)
            vb = (it.x[:, :dv]
                  - _nn(it.x[:, dv:].astype(cdt), sb)).astype(cdt)
            if not states:
                o = _nn((qf * it.e_gamma).astype(cdt), sb) \
                    + _nn((qk * it.decay).astype(cdt), vb)
                out_ref[0, key.at, it.on] = o.astype(out_ref.dtype)
            kd = (key.kf * jnp.exp(g_last - it.gcol)).astype(cdt)
            state[it.n] = _last_row(it.gcol, dv, m) * s + _tn(kd, vb)


def _bwd_kernel(q_ref, k_ref, v_ref, gate_ref, s_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dgate_ref, dstate, *, chunk, r):
    C, dk_, dv = chunk, k_ref.shape[2], v_ref.shape[2] // r
    cdt = k_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, dstate.dtype)

    m = _Masks(C)
    chunks = _prepare(k_ref, v_ref, gate_ref, m, r)
    lane = lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    dcols, drows = [None] * GROUP, []
    for i in reversed(range(GROUP)):
        key = chunks[i]
        at, k, kf, kk = key.at, key.k, key.kf, key.kk
        q = q_ref[0, at, :]
        qf, qk = q.astype(_F32), _nt(q, k)
        dq = dk = jnp.zeros((C, dk_), _F32)
        dkk = dqk = jnp.zeros((C, C), _F32)
        dcol = jnp.zeros((C, _LANES), _F32)
        for it in key.heads:
            gcol, bcol, decay, x = it.gcol, it.bcol, it.decay, it.x
            g_last = gcol[C - 1:C, :]
            e_last, gl = jnp.exp(g_last - gcol), jnp.exp(g_last)
            w = x[:, dv:].astype(cdt)
            pf = qk * decay
            p, qg = pf.astype(cdt), (qf * it.e_gamma).astype(cdt)
            kd = (kf * e_last).astype(cdt)
            # the scan, transposed
            s, ds1 = s_ref[0, it.n, i], dstate[it.n]
            do = do_ref[0, at, it.on]
            sb, ds1b = s.astype(cdt), ds1.astype(cdt)
            vb = (x[:, :dv] - _nn(w, sb)).astype(cdt)
            dv_new = _tn(p, do) + _nn(kd, ds1b)
            dvb = dv_new.astype(cdt)
            dw, dp = -_nt(dvb, sb), _nt(do, vb)
            dqg, dkd = _nt(do, sb), _nt(vb, ds1b)
            dgl = jnp.sum(jnp.sum(ds1 * s, axis=1, keepdims=True), axis=0,
                          keepdims=True)
            dstate[it.n] = _last_row(gcol, dv, m) * ds1 + _tn(qg, do) \
                - _tn(w, dvb)
            # the preparation, transposed
            dr = _hi(it.t, jnp.concatenate([dv_new, dw], axis=1),
                     ((0,), (0,)))
            da = jnp.where(m.strict, -_hi(dr, x, ((1,), (1,))), 0.0)
            dr_u, dr_w = dr[:, :dv], dr[:, dv:] * bcol
            dv_ref[0, at, it.on] = (dr_u * bcol).astype(dv_ref.dtype)
            dkd_k = dkd * kf * e_last
            dk = dk + dr_w * it.e_gamma + dkd * e_last
            dq = dq + dqg * it.e_gamma
            weighed = da * decay
            dkk, dqk = dkk + weighed * bcol, dqk + dp * decay
            dbeta = jnp.sum(dr * it.rhs, axis=1, keepdims=True) \
                + jnp.sum(weighed * kk, axis=1, keepdims=True)
            e = it.a * da + pf * dp
            decayed = jnp.sum(dkd_k, axis=1, keepdims=True)
            dgamma = jnp.sum((dr_w * kf + dqg * qf) * it.e_gamma - dkd_k,
                             axis=1, keepdims=True) \
                + jnp.sum(e, axis=1, keepdims=True) \
                + jnp.where(m.last_row, gl * dgl + jnp.sum(
                    decayed, axis=0, keepdims=True), 0.0)
            drows.append((it.n, at, -jnp.sum(e, axis=0, keepdims=True)))
            dcol = jnp.where(lane == it.n, dgamma,
                             jnp.where(lane == r + it.n, dbeta, dcol))
        dkkb, dqkb = dkk.astype(cdt), dqk.astype(cdt)
        dq_ref[0, at, :] = (dq + _nn(dqkb, k)).astype(dq_ref.dtype)
        dk_ref[0, at, :] = (dk + _nn(dkkb, k) + _tn(dkkb, k)
                            + _tn(dqkb, q)).astype(dk_ref.dtype)
        dcols[i] = dcol
    # the gates' cotangents go back positions on lanes, as the gates came
    dcols = jnp.concatenate(dcols, axis=0)
    for t in range(0, GROUP * C, _LANES):
        dgate_ref[0, 0, :, t:t + _LANES] = dcols[t:t + _LANES].T[:_GATE_ROWS]
    for h, at, row in drows:
        dgate_ref[0, 0, h:h + 1, at] = dgate_ref[0, 0, h:h + 1, at] + row


def _gates(g, beta, chunk: int, Hk: int):
    """``(B, Hk, 8, S)`` float32: a key head's rows are the in-chunk
    cumulative sums of ``g`` of its value heads, then their ``beta``, then
    zeros."""
    B, S, Hv = g.shape
    gamma = jnp.cumsum(g.astype(_F32).reshape(B, S // chunk, chunk, Hv),
                       axis=2).reshape(B, S, Hv)
    r = Hv // Hk
    both = jnp.stack([gamma, beta.astype(_F32)], axis=1)    # (B, 2, S, Hv)
    both = both.reshape(B, 2, S, Hk, r).transpose(0, 3, 1, 4, 2)
    return jnp.pad(both.reshape(B, Hk, 2 * r, S),
                   ((0, 0), (0, 0), (0, _GATE_ROWS - 2 * r), (0, 0)))


def _ungates(dgates, chunk: int, r: int):
    """``(dg, dbeta)`` (B, S, Hv) of :func:`_gates`'s cotangent: the reverse
    cumulative sum inside each chunk turns ``dgamma`` into ``dg``."""
    B, Hk, _, S = dgates.shape
    both = dgates[:, :, :2 * r].reshape(B, Hk, 2, r, S).transpose(
        2, 0, 4, 1, 3).reshape(2, B, S, Hk * r)
    dgamma = both[0].reshape(B, S // chunk, chunk, Hk * r)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, 2), axis=2), 2)
    return dg.reshape(B, S, Hk * r), both[1]


def _specs(span: int, dk: int, dv: int, r: int, group):
    """The blocks of a grid step ``(b, j, n)``, which holds group
    ``group(n)`` of key head ``j``: ``(q | k, v | o, gates, states)``."""
    return (pl.BlockSpec((1, span, dk), lambda b, j, n: (b, group(n), j)),
            pl.BlockSpec((1, span, r * dv), lambda b, j, n: (b, group(n), j)),
            pl.BlockSpec((1, 1, _GATE_ROWS, span),
                         lambda b, j, n: (b, j, 0, group(n))),
            pl.BlockSpec((1, r, GROUP, dk, dv),
                         lambda b, j, n: (b, j, group(n), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _products(C: int, dk: int, dv: int) -> int:
    """Multiply-adds of a chunk-head's preparation as the array sees them:
    the ``HIGHEST`` products at their six passes, the packed ones by the
    rows that stream (:func:`_pack`)."""
    n = SOLVE_BLOCK
    rows = n * (_SQUARINGS + 1)
    while n < C:
        rows, n = rows + 2 * n, 2 * n
    return 6 * (rows * C * C + C * C * (dk + dv))


def _slots(x, heads: int, d: int):
    """``x`` (B, S, heads * d) with each head's ``d`` channels from the
    first lane of a slot of :func:`_slot` ``(d)`` lanes, zeros behind them;
    ``x`` itself where a head is whole tiles already."""
    if d == _slot(d):
        return x
    B, S, _ = x.shape
    return jnp.pad(x.reshape(B, S, heads, d),
                   ((0, 0), (0, 0), (0, 0), (0, _slot(d) - d))
                   ).reshape(B, S, heads * _slot(d))


def _unslots(x, heads: int, d: int):
    """:func:`_slots` back: the heads' own ``d`` channels of each slot."""
    if d == _slot(d):
        return x
    B, S, _ = x.shape
    return x.reshape(B, S, heads, _slot(d))[..., :d].reshape(B, S, heads * d)


def _heads(k, v, g, key_heads: Optional[int]):
    """``(Hk, Hv, dk, dv)`` of the operands; ``key_heads`` None where a key
    head is as wide as a value head."""
    Hv = g.shape[-1]
    dv = v.shape[-1] // Hv
    Hk = key_heads or k.shape[-1] // dv
    return Hk, Hv, k.shape[-1] // Hk, dv


@functools.partial(jax.jit, static_argnames=("chunk", "key_heads", "states",
                                             "interpret"))
def forward(q, k, v, g, beta, *, chunk: int, key_heads: Optional[int] = None,
            states: bool = False, interpret: bool = False):
    """``o`` (B, S, Hv*dv) in ``v``'s type of ``q``, ``k`` (B, S, Hk*dk),
    ``v`` (B, S, Hv*dv), ``g`` and ``beta`` (B, S, Hv); ``key_heads`` is
    ``Hk`` where ``dk != dv``.  With ``states`` the state entering each
    chunk instead, (B, Hv, N, dk, dv) float32 of the heads' slots."""
    B, S, _ = g.shape
    Hk, Hv, dk, dv = _heads(k, v, g, key_heads)
    if not states:
        q = _slots(q, Hk, dk)
    k, v = _slots(k, Hk, dk), _slots(v, Hv, dv)
    sk, sv = _slot(dk), _slot(dv)
    r, C, N = Hv // Hk, chunk, S // chunk
    span = GROUP * C
    key, values, gates, state = _specs(span, sk, sv, r, lambda n: n)
    if states:
        out_spec, out_shape = state, jax.ShapeDtypeStruct((B, Hv, N, sk, sv),
                                                          _F32)
    else:
        out_spec, out_shape = values, jax.ShapeDtypeStruct(v.shape, v.dtype)
    heads = B * Hv * N
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=C, r=r, states=states),
        grid=(B, Hk, N // GROUP),
        in_specs=([] if states else [key]) + [key, values, gates],
        out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, sk, sv), _F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * (_products(C, sk, sv)
                               + C * (3 * sk * sv + C * (sk + sv))),
            transcendentals=heads * C * (C + 2),
            bytes_accessed=2 * B * S * (2 * Hk * sk + 2 * Hv * sv)
            + 4 * B * Hk * _GATE_ROWS * S
            + (4 * heads * sk * sv if states else 0)),
        name="gated_delta_fwd", interpret=interpret,
    )(*(() if states else (q,)), k, v, _gates(g, beta, C, Hk))
    return out if states else _unslots(out, Hv, dv)


@functools.partial(jax.jit, static_argnames=("chunk", "key_heads",
                                             "interpret"))
def backward(q, k, v, g, beta, do, *, chunk: int,
             key_heads: Optional[int] = None, interpret: bool = False):
    """The cotangents ``(dq, dk, dv, dg, dbeta)`` of :func:`forward`'s
    operands under ``do``, in the operands' types: the forward's walk again
    for the states entering the chunks, then the walk back."""
    B, S, _ = g.shape
    Hk, Hv, dk, dv = _heads(k, v, g, key_heads)
    saved = forward(q, k, v, g, beta, chunk=chunk, key_heads=key_heads,
                    states=True, interpret=interpret)
    q, k = _slots(q, Hk, dk), _slots(k, Hk, dk)
    v, do = _slots(v, Hv, dv), _slots(do, Hv, dv)
    sk, sv = _slot(dk), _slot(dv)
    r, C, N = Hv // Hk, chunk, S // chunk
    span, steps = GROUP * C, N // GROUP
    key, values, gates, state = _specs(span, sk, sv, r,
                                       lambda n: steps - 1 - n)
    heads = B * Hv * N
    dq, dk_, dv_, dgates = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=C, r=r),
        grid=(B, Hk, steps),
        in_specs=[key, key, values, gates, state, values],
        out_specs=[key, key, values, gates],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, Hk, _GATE_ROWS, S), _F32)],
        scratch_shapes=[pltpu.VMEM((r, sk, sv), _F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * (_products(C, sk, sv) + 6 * C * C * (sk + sv)
                               + C * (8 * sk * sv + 3 * C * (sk + sv))),
            transcendentals=heads * C * (C + 2),
            bytes_accessed=2 * B * S * (4 * Hk * sk + 3 * Hv * sv)
            + 8 * B * Hk * _GATE_ROWS * S + 4 * heads * sk * sv),
        name="gated_delta_bwd", interpret=interpret,
    )(q, k, v, _gates(g, beta, C, Hk), saved, do)
    dg, dbeta = _ungates(dgates, C, r)
    return (_unslots(dq, Hk, dk), _unslots(dk_, Hk, dk),
            _unslots(dv_, Hv, dv), dg.astype(g.dtype),
            dbeta.astype(beta.dtype))


# -- a decay a key channel (Kimi Delta Attention) ----------------------------
#
# Separate bodies that share the solve (:func:`_inverses`, :func:`_pack`,
# :class:`_Masks`) and nothing of the data path with the kernels above: the
# module's text has what a grid step holds and why.

# float32 ``(C, d)`` tiles a chunk-head that the guard counts against VMEM:
# gamma, its four exponentials' families, q and k in float32 and decayed,
# the block operands and, in the backward, the accumulators.  An estimate, on
# the safe side: Mosaic compiles every corner the guard admits and the next
# ones it refuses (``tests/unit/test_zchip_compile.py -k channel_kernels``, a
# described v5e; on the chip only chunk 64 at 128 x 128 has run: PR 60)
_CHANNEL_TILES = 32


def channel_supported(n_chunks: int, chunk: int, dk: int, dv: int, dtype,
                      heads_a_key: int = 1) -> Optional[str]:
    """:func:`supported` of the kernels under a decay a key channel:
    ``gamma`` is read as rows beside ``k``, so a head is whole lane tiles."""
    if dtype != jnp.bfloat16:
        return f"operands of {jnp.dtype(dtype).name}"
    if dk % _LANES or dv % _LANES:
        return (f"heads of {dk} and {dv} channels under a decay a key "
                f"channel: gamma is read beside k, a head whole tiles of "
                f"{_LANES} lanes")
    said = _chunks_refusal(n_chunks, chunk)
    if said:
        return said
    if heads_a_key > _GATE_ROWS:
        return (f"{heads_a_key} value heads a key head: their beta fills "
                f"more than one tile of {_GATE_ROWS} rows")
    held = 4 * heads_a_key * (
        dk * dv * (1 + 2 * GROUP)
        + GROUP * chunk * max(dk, dv) * _CHANNEL_TILES)
    if held > _STATE_BYTES:
        return (f"{heads_a_key} states of {dk} x {dv} float32 under a decay "
                f"a key channel: resident beside two blocks of {GROUP} saved "
                f"ones and {_CHANNEL_TILES} float32 tiles of {chunk} x "
                f"{max(dk, dv)} a chunk-head they are {held >> 20} MiB of "
                f"VMEM, more than {_STATE_BYTES >> 20}")
    return None


@dataclasses.dataclass
class _Block:
    """A block of :data:`SOLVE_BLOCK` rows ``at`` of a chunk-head against
    its own first row ``r``: ``near = exp(gamma_i - gamma_r)`` of its rows
    (n, dk), ``far = exp(gamma_r - gamma_j)`` of the keys up to its last row
    (C, dk; zeros behind), float32, and the operands they decay: ``rows =
    [K near | Q near]`` (n or 2n, dk; the states' walk has no ``Q``) and
    ``keys = K far`` (C, dk), in the arrays' type."""
    at: slice
    near: jax.Array
    far: jax.Array
    rows: jax.Array
    keys: jax.Array


@dataclasses.dataclass
class _ChannelHead:
    """What of a chunk-head under a decay a key channel depends on no
    state, float32 but the blocks' operands: its rows ``at`` in the step's
    blocks, its index ``n`` among the key head's value heads and its lanes
    ``on`` in the ``v`` / ``o`` blocks and ``over`` in gamma's; ``kf`` and
    ``qf`` (None on the states' walk), the key head's rows in float32;
    ``gamma`` (C, dk), ``bcol``, the :class:`_Block` s, ``kk`` and ``qk``
    (``sum_c x_ic k_jc exp(gamma_ic - gamma_jc)``, right where ``i >= j``),
    ``a``, ``e_gamma``, ``rhs`` (before ``beta``), ``t`` and ``x = [U |
    W]``."""
    at: slice
    n: int
    on: slice
    over: slice
    kf: jax.Array
    qf: Optional[jax.Array]
    gamma: jax.Array
    bcol: jax.Array
    blocks: list
    kk: jax.Array
    qk: Optional[jax.Array]
    a: jax.Array
    e_gamma: jax.Array
    rhs: jax.Array
    t: Optional[jax.Array] = None
    x: Optional[jax.Array] = None


def _channel_prepare(q_ref, k_ref, v_ref, gamma_ref, beta_ref, m: _Masks,
                     r: int):
    """The :class:`_ChannelHead` of each chunk-head of a grid step (a
    chunk's value heads side by side), all of them a stage of the solve at a
    time (:func:`_inverses`).  ``q_ref`` None: the states' walk."""
    C, n = m.eye.shape[0], SOLVE_BLOCK
    dk, dv = k_ref.shape[2], v_ref.shape[2] // r
    cdt = k_ref.dtype
    cols = _columns(beta_ref[0, 0])
    heads = []
    for i in range(GROUP):
        at = slice(i * C, (i + 1) * C)
        kf = k_ref[0, at, :].astype(_F32)
        qf = None if q_ref is None else q_ref[0, at, :].astype(_F32)
        for h in range(r):
            over = slice(h * dk, (h + 1) * dk)
            gamma = gamma_ref[0, at, over]
            blocks, kk, qk = [], [], []
            for lo in range(0, C, n):
                hi = lo + n
                ref = gamma[lo:lo + 1]
                near = jnp.exp(gamma[lo:hi] - ref)
                far = jnp.exp(ref - gamma[:hi])
                if hi < C:
                    far = jnp.concatenate(
                        [far, jnp.zeros((C - hi, dk), _F32)], axis=0)
                rows = (kf[lo:hi] * near).astype(cdt)
                if qf is not None:
                    rows = jnp.concatenate(
                        [rows, (qf[lo:hi] * near).astype(cdt)], axis=0)
                blk = _Block(slice(lo, hi), near, far, rows,
                             (kf * far).astype(cdt))
                prod = _nt(blk.rows, blk.keys)
                if qf is None:
                    kk.append(prod)
                else:
                    kk.append(prod[:n])
                    qk.append(prod[n:])
                blocks.append(blk)
            kk = jnp.concatenate(kk, axis=0)
            bcol = cols[at, h:h + 1]
            e_gamma = jnp.exp(gamma)
            heads.append(_ChannelHead(
                at, h, slice(h * dv, (h + 1) * dv), over, kf, qf, gamma,
                bcol, blocks, kk,
                None if qf is None else jnp.concatenate(qk, axis=0),
                a=jnp.where(m.strict, bcol * kk, 0.0), e_gamma=e_gamma,
                rhs=jnp.concatenate(
                    [v_ref[0, at, h * dv:(h + 1) * dv].astype(_F32),
                     kf * e_gamma], axis=1)))
    for it, t in zip(heads, _inverses([it.a for it in heads], m)):
        it.t = t
    for it in heads:
        it.x = _hi(it.t, it.rhs * it.bcol)
    return heads


def _channel_fwd_kernel(*refs, chunk, r, states):
    """The states are held TRANSPOSED, values x keys ``(r, dv, dk)``: the
    decay of a state is then a ``(1, dk)`` row over its lanes."""
    if states:
        k_ref, v_ref, gamma_ref, beta_ref, out_ref, state = refs
        q_ref = None
    else:
        q_ref, k_ref, v_ref, gamma_ref, beta_ref, out_ref, state = refs
    C, dv = chunk, v_ref.shape[2] // r
    cdt = k_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    m = _Masks(C)
    for it in _channel_prepare(q_ref, k_ref, v_ref, gamma_ref, beta_ref, m,
                               r):
        g_last = it.gamma[C - 1:C]
        s = state[it.n]
        if states:
            out_ref[0, it.n, it.at.start // C] = s
        sb = s.astype(cdt)
        vb = (it.x[:, :dv] - _nt(it.x[:, dv:].astype(cdt), sb)).astype(cdt)
        if not states:
            o = _nt((it.qf * it.e_gamma).astype(cdt), sb) \
                + _nn(jnp.where(m.lower, it.qk, 0.0).astype(cdt), vb)
            out_ref[0, it.at, it.on] = o.astype(out_ref.dtype)
        kd = (it.kf * jnp.exp(g_last - it.gamma)).astype(cdt)
        state[it.n] = jnp.exp(g_last) * s + _tn(vb, kd)


def _channel_bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, s_ref,
                        do_ref, dq_ref, dk_ref, dv_ref, dgamma_ref,
                        dbeta_ref, dstate, *, chunk, r):
    C, dk_, dv = chunk, k_ref.shape[2], v_ref.shape[2] // r
    n = SOLVE_BLOCK
    cdt = k_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, dstate.dtype)

    m = _Masks(C)
    heads = _channel_prepare(q_ref, k_ref, v_ref, gamma_ref, beta_ref, m, r)
    lane = lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    dcols = [None] * GROUP
    for i in reversed(range(GROUP)):
        dq = dk = jnp.zeros((C, dk_), _F32)
        dcol = jnp.zeros((C, _LANES), _F32)
        for it in heads[i * r:(i + 1) * r]:
            at, gamma, bcol, x = it.at, it.gamma, it.bcol, it.x
            kf, qf = it.kf, it.qf
            g_last = gamma[C - 1:C]
            e_last, gl = jnp.exp(g_last - gamma), jnp.exp(g_last)
            w = x[:, dv:].astype(cdt)
            p = jnp.where(m.lower, it.qk, 0.0).astype(cdt)
            qg, kd = (qf * it.e_gamma).astype(cdt), (kf * e_last).astype(cdt)
            # the scan, transposed (states and their cotangents values x keys)
            s, ds1 = s_ref[0, it.n, i], dstate[it.n]
            do = do_ref[0, at, it.on]
            sb, ds1b = s.astype(cdt), ds1.astype(cdt)
            vb = (x[:, :dv] - _nt(w, sb)).astype(cdt)
            dv_new = _tn(p, do) + _nt(kd, ds1b)
            dvb = dv_new.astype(cdt)
            dw = -_nn(dvb, sb)
            dp = jnp.where(m.lower, _nt(do, vb), 0.0)
            dqg, dkd = _nn(do, sb), _nn(vb, ds1b)
            dgl = jnp.sum(ds1 * s, axis=0, keepdims=True)
            dstate[it.n] = gl * ds1 + _tn(do, qg) - _tn(dvb, w)
            # the preparation, transposed
            dr = _hi(it.t, jnp.concatenate([dv_new, dw], axis=1),
                     ((0,), (0,)))
            da = jnp.where(m.strict, -_hi(dr, x, ((1,), (1,))), 0.0)
            dv_ref[0, at, it.on] = (dr[:, :dv] * bcol).astype(dv_ref.dtype)
            dbeta = jnp.sum(dr * it.rhs, axis=1, keepdims=True) \
                + jnp.sum(da * it.kk, axis=1, keepdims=True)
            # q's and k's cotangents in float32, and gamma's beside them:
            # each operand AS IT WAS ROUNDED times its own cotangent, + where
            # it holds exp(+gamma) (a block's rows, Q e^gamma, K e^gamma of
            # W) and - where exp(-gamma) (a block's keys, K e^(gamma_C -
            # gamma)), so that what cancels between a block's rows and its
            # keys cancels to float32's rounding as the sums are formed
            dkd_e = dkd * e_last
            d_q = dqg * it.e_gamma
            d_k = (dr[:, dv:] * bcol) * it.e_gamma
            dgamma = qf * d_q + kf * (d_k - dkd_e)
            d_k = d_k + dkd_e
            dkk, dqk = (da * bcol).astype(cdt), dp.astype(cdt)
            near_k, near_q, of_rows = [], [], []
            for blk in it.blocks:
                g = jnp.concatenate([dkk[blk.at], dqk[blk.at]], axis=0)
                back, keys = _nn(g, blk.keys), _tn(g, blk.rows)
                near_k.append(back[:n] * blk.near)
                near_q.append(back[n:] * blk.near)
                both = blk.rows.astype(_F32) * back
                of_rows.append(both[:n] + both[n:])
                dgamma = dgamma - blk.keys.astype(_F32) * keys
                d_k = d_k + keys * blk.far
            d_q = d_q + jnp.concatenate(near_q, axis=0)
            d_k = d_k + jnp.concatenate(near_k, axis=0)
            last = gl * dgl + jnp.sum(kf * dkd_e, axis=0, keepdims=True)
            dgamma_ref[0, at, it.over] = dgamma \
                + jnp.concatenate(of_rows, axis=0) \
                + jnp.where(m.last_row, last, 0.0)
            dq, dk = dq + d_q, dk + d_k
            dcol = jnp.where(lane == it.n, dbeta, dcol)
        dq_ref[0, heads[i * r].at, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, heads[i * r].at, :] = dk.astype(dk_ref.dtype)
        dcols[i] = dcol
    # beta's cotangent goes back positions on lanes, as beta came
    dcols = jnp.concatenate(dcols, axis=0)
    for t in range(0, GROUP * C, _LANES):
        dbeta_ref[0, 0, :, t:t + _LANES] = dcols[t:t + _LANES].T[:_GATE_ROWS]


def _beta_tile(beta, Hk: int):
    """``(B, Hk, 8, S)`` float32: a key head's rows are the ``beta`` of its
    value heads, then zeros."""
    B, S, Hv = beta.shape
    rows = beta.astype(_F32).reshape(B, S, Hk, Hv // Hk).transpose(0, 2, 3, 1)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, _GATE_ROWS - Hv // Hk), (0, 0)))


def _chunk_sums(x, chunk: int, reverse: bool = False):
    """The cumulative sums of the rows ``x`` (B, S, W) inside each chunk,
    float32: ``g -> gamma``, and in ``reverse`` ``dgamma -> dg``.  As a
    product with a triangle of ones at ``HIGHEST`` (float32 to its
    rounding: the ones are exact in bf16): XLA's ``cumsum`` over the chunk
    axis is a windowed reduction between two relayouts of the whole array,
    2.03 ms against 0.89 at ``(1, 8192, 4096)`` (my chip run, PR 60)."""
    B, S, W = x.shape
    ones = jnp.ones((chunk, chunk), _F32)
    tri = jnp.triu(ones) if reverse else jnp.tril(ones)
    return jnp.einsum(
        "ij,bnjw->bniw", tri, x.astype(_F32).reshape(B, S // chunk, chunk, W),
        precision=_HI).reshape(B, S, W)


def _channel_specs(span: int, dk: int, dv: int, r: int, group):
    """:func:`_specs` under a decay a key channel: ``(q | k, v | o, gamma,
    beta, states)``, gamma rows beside ``k`` at the value heads' lanes, the
    states values x keys."""
    key, values, gates, _ = _specs(span, dk, dv, r, group)
    return (key, values,
            pl.BlockSpec((1, span, r * dk), lambda b, j, n: (b, group(n), j)),
            gates,
            pl.BlockSpec((1, r, GROUP, dv, dk),
                         lambda b, j, n: (b, j, group(n), 0, 0)))


def _channel_work(C: int, dk: int, dv: int):
    """``(multiply-adds, exponentials)`` of a chunk-head's preparation and
    forward scan under a decay a key channel, as the array sees them."""
    blocks = C // SOLVE_BLOCK
    return (_products(C, dk, dv) + 2 * SOLVE_BLOCK * blocks * C * dk
            + C * (3 * dk * dv + C * dv),
            C * dk * (3 + (blocks + 1) // 2 + 1))


def _channel_walk(q, k, v, gamma, tile, *, chunk: int, states: bool,
                  interpret: bool):
    """``gated_delta_channel_fwd`` over ``gamma`` (B, S, Hv dk) of
    :func:`_chunk_sums` and ``tile`` of :func:`_beta_tile``: ``o``, or with
    ``states`` (``q`` None) the state entering each chunk."""
    B, S, _ = gamma.shape
    Hk = tile.shape[1]
    dk = k.shape[-1] // Hk
    Hv = gamma.shape[-1] // dk
    dv = v.shape[-1] // Hv
    r, C, N = Hv // Hk, chunk, S // chunk
    key, values, rows, gates, state = _channel_specs(
        GROUP * C, dk, dv, r, lambda n: n)
    if states:
        out_spec, out_shape = state, jax.ShapeDtypeStruct((B, Hv, N, dv, dk),
                                                          _F32)
    else:
        out_spec, out_shape = values, jax.ShapeDtypeStruct(v.shape, v.dtype)
    heads = B * Hv * N
    macs, exps = _channel_work(C, dk, dv)
    return pl.pallas_call(
        functools.partial(_channel_fwd_kernel, chunk=C, r=r, states=states),
        grid=(B, Hk, N // GROUP),
        in_specs=([] if states else [key]) + [key, values, rows, gates],
        out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, dv, dk), _F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * macs, transcendentals=heads * exps,
            bytes_accessed=2 * B * S * (2 * Hk * dk + 2 * Hv * dv)
            + 4 * B * S * Hv * dk + 4 * B * Hk * _GATE_ROWS * S
            + (4 * heads * dk * dv if states else 0)),
        name="gated_delta_channel_fwd", interpret=interpret,
    )(*(() if states else (q,)), k, v, gamma, tile)


@functools.partial(jax.jit, static_argnames=("chunk", "key_heads", "states",
                                             "interpret"))
def channel_forward(q, k, v, g, beta, *, chunk: int,
                    key_heads: Optional[int] = None, states: bool = False,
                    interpret: bool = False):
    """:func:`forward` under a decay a key channel, ``g`` (B, S, Hv, dk):
    ``o`` (B, S, Hv*dv), or with ``states`` the state entering each chunk,
    (B, Hv, N, dv, dk) float32 (values x keys)."""
    B, S, Hv, dk = g.shape
    return _channel_walk(
        q, k, v, _chunk_sums(g.reshape(B, S, Hv * dk), chunk),
        _beta_tile(beta, key_heads or k.shape[-1] // dk), chunk=chunk,
        states=states, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "key_heads",
                                             "interpret"))
def channel_backward(q, k, v, g, beta, do, *, chunk: int,
                     key_heads: Optional[int] = None,
                     interpret: bool = False):
    """:func:`backward` under a decay a key channel: ``(dq, dk, dv, dg,
    dbeta)``, ``dg`` (B, S, Hv, dk).  ``gamma`` is made once for the states'
    walk and the walk back."""
    B, S, Hv, dk = g.shape
    dv = v.shape[-1] // Hv
    Hk = key_heads or k.shape[-1] // dk
    rows = _chunk_sums(g.reshape(B, S, Hv * dk), chunk)
    tile = _beta_tile(beta, Hk)
    saved = _channel_walk(None, k, v, rows, tile, chunk=chunk, states=True,
                          interpret=interpret)
    r, C, N = Hv // Hk, chunk, S // chunk
    steps = N // GROUP
    key, values, gamma, gates, state = _channel_specs(
        GROUP * C, dk, dv, r, lambda n: steps - 1 - n)
    heads = B * Hv * N
    macs, exps = _channel_work(C, dk, dv)
    dq, dk_, dv_, dgamma, dbeta = pl.pallas_call(
        functools.partial(_channel_bwd_kernel, chunk=C, r=r),
        grid=(B, Hk, steps),
        in_specs=[key, key, values, gamma, gates, state, values],
        out_specs=[key, key, values, gamma, gates],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(rows.shape, _F32),
                   jax.ShapeDtypeStruct((B, Hk, _GATE_ROWS, S), _F32)],
        scratch_shapes=[pltpu.VMEM((r, dv, dk), _F32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * (macs + 6 * C * C * (dk + dv)
                               + 3 * SOLVE_BLOCK * (C // SOLVE_BLOCK) * C * dk
                               + C * (5 * dk * dv + 2 * C * dv)),
            transcendentals=heads * exps,
            bytes_accessed=2 * B * S * (4 * Hk * dk + 3 * Hv * dv)
            + 8 * B * S * Hv * dk + 8 * B * Hk * _GATE_ROWS * S
            + 4 * heads * dk * dv),
        name="gated_delta_channel_bwd", interpret=interpret,
    )(q, k, v, rows, tile, saved, do)
    dg = _chunk_sums(dgamma, C, reverse=True).reshape(g.shape)
    dbeta = dbeta[:, :, :r].transpose(0, 3, 1, 2).reshape(B, S, Hv)
    return dq, dk_, dv_, dg.astype(g.dtype), dbeta.astype(beta.dtype)
