"""Flash attention in Pallas — the training-kernel flagship.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` softmax/strided-batch-gemm
path for training; ``csrc/transformer/inference/csrc/softmax.cu``
triangular-masked softmax for inference).  Design follows the standard
flash-attention tiling: per (batch·head, q-block) program, stream K/V
blocks through VMEM with an online-softmax accumulator, so the S×S score
matrix never materializes in HBM — O(S) memory, MXU-sized matmul tiles.

Backward uses the saved logsumexp to recompute P blockwise in ONE kernel
per k-block that feeds dq, dk and dv from a single ds.

Both kernels follow one causal tile schedule
(:func:`score_tile_schedule`): a score tile wholly above the diagonal
runs no code, one wholly below it builds no mask, and one the diagonal
crosses is masked (the backward walks it in half-edge sub-tiles, each
classed the same way).

All kernels run under ``interpret=True`` on CPU for tests.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ...telemetry import registry as _registry

NEG_INF = float("-inf")

HEADS_PER_PROGRAM = 1   # module knob; see flash_attention()
UNROLL_MAX = 4          # static-unroll K/Q sweeps at or below this length

VOID, FULL, DIAGONAL = "void", "full", "diagonal"


# ---------------------------------------------------------------------------
# The causal tile schedule: which score tiles exist, and what each needs
# ---------------------------------------------------------------------------

def _tile_kind(d: int, rows: int, cols: int, causal: bool) -> str:
    """Class of the rows×cols score tile whose first query position lies
    ``d`` after its first key position: VOID has no ``q_pos >= k_pos``
    entry, FULL has nothing else, DIAGONAL has both."""
    if not causal or d >= cols - 1:
        return FULL
    return VOID if d <= -rows else DIAGONAL


class TileSchedule(NamedTuple):
    S: int
    Sk: int
    block_q: int
    block_k: int
    causal: bool
    sub_q: int
    sub_k: int
    # what a kernel walks inside a (block_q × block_k) tile the diagonal
    # crosses, keyed by the tile's offset d0 = q0 - k0:
    # ((d0, ((r0, c0, kind), ...)), ...), void sub-tiles left out
    diagonal: tuple
    # every (sub_q × sub_k) sub-tile of one head-sequence, (q0, k0, kind):
    # what the kernels' sweeps amount to, and what the counter counts
    tiles: tuple


@functools.lru_cache(maxsize=None)
def score_tile_schedule(S: int, Sk: int, block_q: int, block_k: int,
                        causal: bool, halve_diagonal: bool) -> TileSchedule:
    """The score tiles of one head-sequence, from shapes alone.  The DMA
    blocks stay (block_q × block_k).  With ``halve_diagonal`` a tile the
    diagonal crosses is walked in sub-tiles of half its edge (while that
    stays a multiple of the 128-lane tile), so that of its four quarters
    one is void, one full and two are masked; the backward does, the
    forward does not (its online-softmax steps chain, and three quarter
    steps cost more than one whole one: PERF.md §6, PR 25)."""
    def half(block):
        return block // 2 if halve_diagonal and block % 256 == 0 else block

    sq, sk = half(block_q), half(block_k)

    def subs(d0):
        return tuple((r0, c0, _tile_kind(d0 + r0 - c0, sq, sk, causal))
                     for r0 in range(0, block_q, sq)
                     for c0 in range(0, block_k, sk))

    step = math.gcd(block_q, block_k)
    diagonal = tuple(
        (d0, tuple(s for s in subs(d0) if s[2] != VOID))
        for d0 in range(-(block_q // step - 1) * step, block_k, step)
        if _tile_kind(d0, block_q, block_k, causal) == DIAGONAL)
    tiles = []
    for q0 in range(0, S, block_q):
        for k0 in range(0, Sk, block_k):
            kind = _tile_kind(q0 - k0, block_q, block_k, causal)
            tiles += [(q0 + r0, k0 + c0, sub if kind == DIAGONAL else kind)
                      for r0, c0, sub in subs(q0 - k0)]
    return TileSchedule(S, Sk, block_q, block_k, causal, sq, sk, diagonal,
                        tuple(tiles))


def _note_score_tiles(pass_: str, sched: TileSchedule) -> None:
    """Count, at trace time, the sub-tiles a head-sequence visits or skips
    in the kernel being traced (``pass_`` is ``"fwd"`` or ``"bwd"``)."""
    family = _registry.counter(
        "flash_score_tiles_total",
        "score sub-tiles of one head-sequence by what the flash kernel "
        "does with them: void runs no code, full builds no mask, diagonal "
        "is masked (counted at trace time, not per call)",
        labelnames=("pass", "kind"))
    for kind, n in collections.Counter(t[2] for t in sched.tiles).items():
        family.labels(pass_, kind).inc(n)


def _full_tiles(own, sched: TileSchedule, *, own_is_q: bool):
    """``(lo, hi)``: the swept tiles ``[lo, hi)`` that are FULL for the
    program that owns tile ``own`` (a python int or the traced program
    id).  The forward owns a query tile and sweeps key tiles, the full
    ones come first; the backward owns a key tile and sweeps query tiles,
    the full ones come last."""
    bq, bk = sched.block_q, sched.block_k
    nq, nk = sched.S // bq, sched.Sk // bk
    if not sched.causal:
        return 0, (nk if own_is_q else nq)
    lowest = jnp.minimum if isinstance(own, jax.Array) else min
    if own_is_q:    # FULL: k0 + bk - 1 <= q0
        return 0, lowest(nk, (own * bq + 1) // bk)
    # FULL: q0 >= k0 + bk - 1, from the first such query tile on
    return lowest(nq, ((own + 1) * bk - 1 + bq - 1) // bq), nq


def _for_program(own, sched: TileSchedule, program, *, own_is_q: bool):
    """Run ``program(sweep, looped)`` for the grid program that owns tile
    ``own`` of its axis (a query tile in the forward, a key tile in the
    backward); ``looped`` says that the sweep is a loop.

    ``sweep(carry, full_tile, diagonal_tile)`` threads ``carry`` through
    ``full_tile(t0, carry)`` for every FULL tile the program meets and
    ``diagonal_tile(t0, d0, subs, carry)`` for every DIAGONAL one; ``t0``
    is the first position of the tile on the swept axis.  VOID tiles get
    no code at all.

    Short sweeps (S=1024, block 512 → 2 tiles) get one straight-line
    program per value of ``own`` under a ``pl.when``: every position is
    static and nothing separates the tiles, so Mosaic overlaps one tile's
    matmuls with its neighbour's vector work (a branch per tile was 50%
    slower on the v5e than computing the void tile as well).  Long sweeps
    loop over the full tiles and place the diagonal ones from ``own``."""
    bq, bk = sched.block_q, sched.block_k
    own_block, swept_block = (bq, bk) if own_is_q else (bk, bq)
    n_own = (sched.S // bq) if own_is_q else (sched.Sk // bk)
    n_swept = (sched.Sk // bk) if own_is_q else (sched.S // bq)
    # own*own_block = t0 + sign*d0, with d0 = q0 - k0
    sign = 1 if own_is_q else -1

    def diagonal_of(o):
        """[(t0, d0, subs)] of the program that owns tile ``o`` (an int)."""
        met = [(o * own_block - sign * d0, d0, subs)
               for d0, subs in sched.diagonal]
        return [m for m in met if m[0] % swept_block == 0
                and 0 <= m[0] < n_swept * swept_block]

    def static_sweep(o):
        def sweep(carry, full_tile, diagonal_tile):
            for t in range(*_full_tiles(o, sched, own_is_q=own_is_q)):
                carry = full_tile(t * swept_block, carry)
            for t0, d0, subs in diagonal_of(o):
                carry = diagonal_tile(t0, d0, subs, carry)
            return carry
        return sweep

    def dynamic_sweep(carry, full_tile, diagonal_tile):
        lo, hi = _full_tiles(own, sched, own_is_q=own_is_q)
        carry = jax.lax.fori_loop(
            lo, hi, lambda t, c: full_tile(t * swept_block, c), carry)
        for d0, subs in sched.diagonal:
            # programs that meet a diagonal tile at this offset: all of
            # them (the usual case: no branch), none, or some
            meets = [any(m[1] == d0 for m in diagonal_of(o))
                     for o in range(n_own)]
            t0 = own * own_block - sign * d0
            step = functools.partial(diagonal_tile, t0, d0, subs)
            if all(meets):
                carry = step(carry)
            elif any(meets):
                carry = jax.lax.cond(
                    (t0 >= 0) & (t0 % swept_block == 0)
                    & (t0 < n_swept * swept_block), step, lambda c: c, carry)
        return carry

    if n_swept > UNROLL_MAX or (sched.causal and n_own > UNROLL_MAX):
        program(dynamic_sweep, True)
    elif not sched.causal or n_own == 1:    # all programs meet the same tiles
        program(static_sweep(0), False)
    else:
        for o in range(n_own):
            pl.when(own == o)(
                functools.partial(program, static_sweep(o), False))


def _causal_mask(s, d: int):
    """Void the entries of score tile ``s`` with ``q_pos < k_pos``, its
    first query position lying ``d`` after its first key position."""
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row + d >= col, s, NEG_INF)


def _col(x):
    """(rows,) → (rows, 1).  The lax op that ``x[:, None]`` ends in: jnp
    indexing costs ~1 ms of tracing a use, and a step traces ~150 kernels."""
    return jax.lax.expand_dims(x, (1,))


def _rows(x, start: int, size: int):
    """``x[start:start + size]`` (static), traced as cheaply."""
    if start == 0 and size == x.shape[0]:
        return x
    return jax.lax.slice_in_dim(x, start, start + size, axis=0)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, sched, G):
    # G heads per program (leading block dim): amortizes per-program
    # overhead — measured 1.6x faster at G=2 on the bench chip
    bq, D = q_ref.shape[1:]

    for g in range(G):
        def program(sweep, looped, g=g):
            q = q_ref[g].astype(jnp.float32) * scale            # (bq, D)

            def fold(k0, carry, d=None):
                """One online-softmax step: key tile [k0, +block_k) into
                the carry ``(m, l, acc)``; ``d`` is the mask offset, None
                for a FULL tile."""
                m, l, acc = carry
                ks = pl.ds(k0, sched.block_k)
                k = k_ref[g, ks].astype(jnp.float32)
                v = v_ref[g, ks].astype(jnp.float32)
                s = _dot(q, k, ((1,), (1,)))                    # (bq, bk)
                if d is not None:
                    s = _causal_mask(s, d)
                m_new = jnp.maximum(m, s.max(axis=-1))
                # rows with everything masked keep m=-inf; keep exp
                # well-defined
                m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
                p = jnp.exp(s - _col(m_safe))
                corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_safe))
                return (m_new, l * corr + p.sum(axis=-1),
                        acc * _col(corr) + _dot(p, v, ((1,), (0,))))

            def diagonal_tile(k0, d0, subs, carry):
                assert len(subs) == 1   # the forward leaves them whole
                return fold(k0, carry, d0)

            m, l, acc = sweep((jnp.full((bq,), NEG_INF, jnp.float32),
                               jnp.zeros((bq,), jnp.float32),
                               jnp.zeros((bq, D), jnp.float32)),
                              fold, diagonal_tile)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[g] = (acc / _col(l_safe)).astype(o_ref.dtype)
            m_safe = jnp.where(m == NEG_INF, 0.0, m)
            lse_ref[g, 0] = m_safe + jnp.log(l_safe)

        _for_program(pl.program_id(1), sched, program, own_is_q=True)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, *, scale, sched, G):
    """Backward: dq, dk AND dv in ONE grid pass over k-blocks.

    ds is computed once per score tile and feeds all three cotangents (5
    MXU ops a tile; K/V streamed once).  dq is accumulated in a
    VMEM-resident fp32 output block whose index map ignores the k-block
    grid dim — TPU grids are sequential, so the block is revisited across
    k-blocks and flushed once per (batch·head) program.  dk and dv are
    summed as values, one per ``sub_k`` band of the program's keys, and
    stored once; a looped sweep sums them in their output blocks.  dk
    carries ``scale`` via the pre-scaled q; dq is scaled by the caller
    after the final cast."""
    bk, D = k_ref.shape[1:]
    sk = sched.sub_k

    @pl.when(pl.program_id(1) == 0)
    def _init_dq():
        dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

    for g in range(G):
        def program(sweep, looped, g=g):
            k_blk = k_ref[g].astype(jnp.float32)                 # (bk, D)
            v_blk = v_ref[g].astype(jnp.float32)

            def visit(q0, sums, r0=0, c0=0, rows=sched.block_q, cols=bk,
                      d=None):
                """Queries [q0+r0, +rows) against keys [c0, +cols) of the
                program's block; ``d`` is the mask offset, None for FULL.
                ``sums`` maps each key band to its ``(dk, dv)`` so far,
                or is None where they are summed in dk_ref and dv_ref."""
                rs = pl.ds(q0 + r0, rows)
                k, v = _rows(k_blk, c0, cols), _rows(v_blk, c0, cols)
                q = q_ref[g, rs].astype(jnp.float32) * scale
                do = do_ref[g, rs].astype(jnp.float32)
                lse = lse_ref[g, 0, rs]
                delta = delta_ref[g, 0, rs]
                s = _dot(q, k, ((1,), (1,)))                     # (rows, cols)
                if d is not None:
                    s = _causal_mask(s, d)
                p = jnp.exp(s - _col(lse))
                dv = _dot(p, do, ((0,), (0,)))
                dp = _dot(do, v, ((1,), (1,)))
                ds = p * (dp - _col(delta))
                dk = _dot(ds, q, ((0,), (0,)))
                dq_ref[g, rs] += _dot(ds, k, ((1,), (0,)))
                if sums is None:
                    dk_ref[g, pl.ds(c0, cols)] += dk
                    dv_ref[g, pl.ds(c0, cols)] += dv
                    return None
                sums = dict(sums)
                for b in range(c0, c0 + cols, sk):
                    sums[b] = (sums[b][0] + _rows(dk, b - c0, sk),
                               sums[b][1] + _rows(dv, b - c0, sk))
                return sums

            def diagonal_tile(q0, d0, subs, sums):
                for r0, c0, kind in subs:
                    sums = visit(q0, sums, r0, c0, sched.sub_q, sk,
                                 None if kind == FULL else d0 + r0 - c0)
                return sums

            if looped:     # a loop would carry the sums through VMEM anyway
                dk_ref[g] = jnp.zeros(dk_ref.shape[1:], dk_ref.dtype)
                dv_ref[g] = jnp.zeros(dv_ref.shape[1:], dv_ref.dtype)
                sweep(None, visit, diagonal_tile)
                return
            zero = jnp.zeros((sk, D), jnp.float32)
            sums = sweep({b: (zero, zero) for b in range(0, bk, sk)},
                         visit, diagonal_tile)
            for b, (dk, dv) in sums.items():
                dk_ref[g, pl.ds(b, sk)] = dk
                dv_ref[g, pl.ds(b, sk)] = dv

        _for_program(pl.program_id(1), sched, program, own_is_q=False)


def _largest_dividing_block(s: int, cap: int) -> int:
    """Largest tile ≤ cap that divides s (so S=1536 gets 512, S=1152 gets
    128 — any S that a smaller default handled keeps working)."""
    b = min(cap, s)
    while b > 128 and s % b:
        b //= 2
    return b if s % b == 0 else min(s, 128)


def _flatten_bh(x):
    B, H, S, D = x.shape
    return x.reshape(B * H, S, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, G, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, G, interpret)
    return out


# The two pallas_calls sit behind ``jit(inline=True)``: nothing of the jit
# stays in the caller's jaxpr, but its trace cache means a model traces
# each kernel body once, not once a layer and remat pass (a step of the
# 48-layer benchmark model stages ~200 flash calls).
_STATIC = ("causal", "scale", "block_q", "block_k", "G", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _fwd_call(q, k, v, *, causal, scale, block_q, block_k, G, interpret):
    BH, S, D = q.shape
    Sk = k.shape[1]
    sched = score_tile_schedule(S, Sk, block_q, block_k, causal, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sched=sched, G=G),
        grid=(BH // G, S // block_q),
        in_specs=[
            pl.BlockSpec((G, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((G, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((G, Sk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((G, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((G, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, G, interpret):
    _note_score_tiles("fwd", score_tile_schedule(
        q.shape[1], k.shape[1], block_q, block_k, causal, False))
    out, lse = _fwd_call(q, k, v, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k, G=G,
                         interpret=interpret)
    # named so a "<policy>+flash" remat policy can SAVE the kernel's
    # residuals: out/lse aren't dot outputs, so dots_saveable alone
    # recomputes the whole fwd kernel inside every backward pass
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, G, interpret, res, do):
    q, k, v, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]
    return _flash_bwd_impl(causal, scale, block_q, block_k, G, interpret,
                           q, k, v, lse, do, delta)


def _flash_bwd_impl(causal, scale, block_q, block_k, G, interpret,
                    q, k, v, lse, do, delta):
    _note_score_tiles("bwd", score_tile_schedule(
        q.shape[1], k.shape[1], block_q, block_k, causal, True))
    dq, dk, dv = _bwd_call(q, k, v, do, lse, delta, causal=causal,
                           scale=scale, block_q=block_q, block_k=block_k,
                           G=G, interpret=interpret)
    return ((dq * scale).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_call(q, k, v, do, lse, delta, *, causal, scale, block_q, block_k,
              G, interpret):
    BH, S, D = q.shape
    Sk = k.shape[1]
    sched = score_tile_schedule(S, Sk, block_q, block_k, causal, True)
    return pl.pallas_call(
        functools.partial(_dqkv_kernel, scale=scale, sched=sched, G=G),
        grid=(BH // G, Sk // block_k),
        in_specs=[
            pl.BlockSpec((G, S, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((G, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((G, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((G, S, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((G, 1, S), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((G, 1, S), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            # dq revisited across j (map ignores the k-block dim):
            # fp32 VMEM accumulator, flushed once per (batch·head)
            pl.BlockSpec((G, S, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((G, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((G, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    heads_per_program: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Public API, shapes ``(B, S, H, D)`` like ``ops.attention``.

    Default blocks are ``min(S, 512)``: large tiles beat the flash-paper-
    style 128x128 by ~1.8x on the bench chip (fewer programs, K/V panel
    streamed once), and an interleaved A/B sweep at S=1024 measured
    512x512 another ~3% faster e2e than whole-sequence 1024 tiles
    (GPT-2-125M train step 132.7ms vs 136.4ms — smaller score tiles
    double-buffer better); the online-softmax loop engages automatically
    for S > block.
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    block_q = _largest_dividing_block(S, block_q)
    block_k = _largest_dividing_block(Sk, block_k)
    if S % block_q or Sk % block_k:
        raise ValueError(f"seq lengths ({S},{Sk}) must divide block sizes "
                         f"({block_q},{block_k})")
    qt = _flatten_bh(q.transpose(0, 2, 1, 3))
    kt = _flatten_bh(k.transpose(0, 2, 1, 3))
    vt = _flatten_bh(v.transpose(0, 2, 1, 3))
    # heads-per-program: G=2 wins ~1.6x on the isolated fwd kernel but is
    # e2e-neutral-to-negative inside the full training step (XLA already
    # overlaps programs); default 1, knob kept for other chips/models
    hpp = HEADS_PER_PROGRAM if heads_per_program is None else heads_per_program
    G = hpp if (B * H) % hpp == 0 and \
        hpp * Sk * D * q.dtype.itemsize <= 512 * 1024 else 1
    out = _flash(qt, kt, vt, causal, scale, block_q, block_k, G, interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# LSE-exposing variant — building block for distributed (ring) attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, G, interpret):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k, G,
                          interpret)
    return out, res[4][:, 0, :]          # lse as (BH, S)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, G, interpret):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k, G,
                          interpret)
    return (out, res[4][:, 0, :]), res


def _flash_lse_bwd(causal, scale, block_q, block_k, G, interpret, res, ct):
    do, dlse = ct
    q, k, v, out, lse = res
    # the lse cotangent folds into the shared backward exactly:
    # ds = p·(dp - δ') with δ' = δ - dlse, because ∂lse_i/∂s_ij = p_ij
    delta = (jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1) - dlse.astype(jnp.float32))[:, None, :]
    return _flash_bwd_impl(causal, scale, block_q, block_k, G, interpret,
                           q, k, v, lse, do, delta)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = 512, block_k: int = 512,
                             interpret: bool = False):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``(B, S, H)`` — differentiable in BOTH outputs, which is what a
    distributed (ring) attention needs to merge per-block results exactly.
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    block_q = _largest_dividing_block(S, block_q)
    block_k = _largest_dividing_block(Sk, block_k)
    qt = _flatten_bh(q.transpose(0, 2, 1, 3))
    kt = _flatten_bh(k.transpose(0, 2, 1, 3))
    vt = _flatten_bh(v.transpose(0, 2, 1, 3))
    G = HEADS_PER_PROGRAM if (B * H) % HEADS_PER_PROGRAM == 0 and \
        HEADS_PER_PROGRAM * Sk * D * q.dtype.itemsize <= 512 * 1024 else 1
    out, lse = _flash_lse(qt, kt, vt, causal, scale, block_q, block_k, G,
                          interpret)
    out = out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    lse = lse.reshape(B, H, S).transpose(0, 2, 1)
    return out, lse
