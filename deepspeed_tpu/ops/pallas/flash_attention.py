"""Flash attention in Pallas — the training-kernel flagship.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` softmax/strided-batch-gemm
path for training; ``csrc/transformer/inference/csrc/softmax.cu``
triangular-masked softmax for inference).  Design follows the standard
flash-attention tiling: per (batch, lane block of heads, q-block) program,
stream K/V blocks through VMEM with an online-softmax accumulator, so the
S×S score matrix never materializes in HBM — O(S) memory, MXU-sized
matmul tiles.

The kernels read and write ``(B, S, H·D)``, the layout of the projections
on either side of them, a 128-lane block of whole heads a program
(:class:`Lanes`): no operand is transposed, copied or cast on its way in
or out.

Backward uses the saved logsumexp to recompute P blockwise in ONE kernel
per k-block that feeds dq, dk and dv from a single ds, and writes them in
the operands' own types.

Both kernels follow one tile schedule (:func:`score_tile_schedule`): a
score tile wholly above the diagonal, or wholly past a sliding ``window``
below it, runs no code, one wholly inside the band builds no mask, and one
that the diagonal or the band's lower edge crosses is masked (the backward
walks it in half-edge sub-tiles, each classed the same way).  Block
diffusion's whole mask over ``[noisy ; clean]`` rows is one schedule too
(``diag = (g, HALVES)``, :func:`flash_attention_halves`; the diagonal in
blocks of g positions, ``q_pos // g >= k_pos // g`` or its strict form
``>``): a noisy query tile folds the tile of its own noisy blocks (``q_pos
// g == k_pos // g``) into the same online softmax as the clean keys'
tiles, and the backward program of that noisy key tile owns its ``dk`` and
``dv`` whole.

A score tile is a sum of products, ``s = Σ_t q_t · k_t`` (:class:`Term`):
one for plain attention; two for latent attention, ``q_h · k_h + q_rope_h ·
k_rope`` against ONE rotated key that all heads share.  A term says how
many consecutive head programs read one block of its keys: 1, or ``group``
for grouped queries (``k`` and ``v`` narrower than ``q``, head_dim a
multiple of 128: query lane block ``c`` reads key-value lane block ``c //
group``), or all of them.  The backward sums such a block's gradient over
the programs that share it in VMEM, so nothing key- or value-shaped is ever
as wide as ``q``.  Everything after ``s`` (mask, online softmax, ``p``,
``dp``, ``ds``) is written once.

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
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry

NEG_INF = float("-inf")

UNROLL_MAX = 4          # static-unroll K/Q sweeps at or below this length
# A long windowed sweep meets the same few tiles in every program (window /
# block + 1 or + 2 of them): at or below this many it is ONE straight-line
# block (:func:`_sweep_form`).  Six covers a window of five blocks; the
# forward and backward kernels of such a sweep at (3, 8192, 32 / 4, 128)
# compile in 4.9 s against 3.9 as a loop (three tiles, Mellum 2: 6.2
# against 5.5; five, Trinity: 6.3 against 3.4; sandbox compile for a
# described v5e, PR 43), and a program at the start of a row computes up to
# five of its six tiles void: past that a loop over the FULL tiles is the
# better trade.
STRAIGHT_MAX = 6

VOID, FULL, DIAGONAL = "void", "full", "diagonal"
# the band's lower edge crosses the tile; both edges do (window < a tile)
BAND_EDGE, CROSSED = "band_edge", "diagonal+band_edge"
# a block-granular diagonal crosses the tile (``block``: q_pos // g against
# k_pos // g)
BLOCK_DIAGONAL = "block_diagonal"
# noisy queries against the noisy keys of their own blocks (block diffusion
# over [noisy ; clean] rows): the tile keeps ``q_pos // g == k_pos // g``
OWN_BLOCK = "own_block"
MASKED = (DIAGONAL, BAND_EDGE, CROSSED, BLOCK_DIAGONAL, OWN_BLOCK)
# in ``strict``'s place in ``diag``: the rows are the two halves [noisy ;
# clean] of one sequence under the whole of block diffusion's mask
HALVES = "halves"


# ---------------------------------------------------------------------------
# The causal tile schedule: which score tiles exist, and what each needs
# ---------------------------------------------------------------------------

def _tile_kind(d: int, rows: int, cols: int, causal: bool,
               window: Optional[int] = None, diag: Optional[tuple] = None
               ) -> str:
    """Class of the rows×cols score tile whose first query position lies
    ``d`` after its first key position, by the entries it keeps: those
    with ``0 <= q_pos - k_pos`` (causal) ``< window`` (a sliding window).
    VOID keeps none, FULL all; DIAGONAL loses some above the diagonal,
    BAND_EDGE some past the window, CROSSED some of both.  ``diag = (g,
    strict)`` keeps ``q_pos // g >= k_pos // g`` (``>`` when strict)
    instead: tiles start at multiples of g, so that is the causal class of
    the tile counted in blocks, one block further back when strict, and
    what the diagonal crosses is BLOCK_DIAGONAL."""
    if diag is not None:
        g, strict = diag
        kind = _tile_kind(d // g - int(strict), rows // g, cols // g, True)
        return BLOCK_DIAGONAL if kind == DIAGONAL else kind
    lo, hi = d - (cols - 1), d + rows - 1       # extremes of q_pos - k_pos
    if (causal and hi < 0) or (window is not None and lo >= window):
        return VOID
    above = causal and lo < 0
    below = window is not None and hi >= window
    if above:
        return CROSSED if below else DIAGONAL
    return BAND_EDGE if below else FULL


def _halves_tile_kind(q0: int, k0: int, rows: int, cols: int, half: int,
                      g: int) -> str:
    """Class of the rows×cols score tile at ``(q0, k0)`` of block
    diffusion's mask over ``[noisy ; clean]`` rows of ``half`` positions
    each (``ops/attention.py block_diffusion_mask``; tiles start at
    multiples of g and lie in one half).  A noisy key is kept by the noisy
    queries of its own block alone: OWN_BLOCK where the tile holds such
    pairs.  A clean key is kept under the block-granular diagonal, strict
    for a noisy query.  One program serves both halves, the strictness a
    traced offset of its mask, so a tile that the two forms class apart (a
    block as long as the tile) is masked under both."""
    if k0 < half:
        meets = q0 < half and q0 < k0 + cols and k0 < q0 + rows
        return OWN_BLOCK if meets else VOID
    d = q0 % half - (k0 - half)
    loose, strict = (_tile_kind(d, rows, cols, True, None, (g, s))
                     for s in (False, True))
    return loose if loose == strict else BLOCK_DIAGONAL


class TileSchedule(NamedTuple):
    S: int
    Sk: int
    block_q: int
    block_k: int
    causal: bool
    sub_q: int
    sub_k: int
    # what a kernel walks inside a (block_q × block_k) tile that an edge of
    # the band crosses, keyed by the tile's offset d0 = q0 - k0:
    # ((d0, ((r0, c0, kind), ...)), ...), void sub-tiles left out; of the
    # halves' schedule, keyed by the tile's kind (OWN_BLOCK, BLOCK_DIAGONAL)
    diagonal: tuple
    # every (sub_q × sub_k) sub-tile of one head-sequence, (q0, k0, kind):
    # what the kernels' sweeps amount to, and what the counter counts
    tiles: tuple
    window: Optional[int] = None
    # (g, strict): the diagonal in blocks of g positions (:func:`_tile_kind`);
    # (g, HALVES): block diffusion's mask over [noisy ; clean] rows
    # (:func:`_halves_tile_kind`)
    diag: Optional[tuple] = None

    @property
    def halves(self) -> bool:
        return self.diag is not None and self.diag[1] == HALVES


@functools.lru_cache(maxsize=None)
def score_tile_schedule(S: int, Sk: int, block_q: int, block_k: int,
                        causal: bool, halve_diagonal: bool,
                        window: Optional[int] = None,
                        diag: Optional[tuple] = None) -> TileSchedule:
    """The score tiles of one head-sequence, from shapes alone.  The DMA
    blocks stay (block_q × block_k).  With ``halve_diagonal`` a tile that
    an edge of the band crosses is walked in sub-tiles of half its edge
    (while that stays a multiple of the 128-lane tile), so that of its
    four quarters one is void, one full and two are masked; the backward
    does, the forward does not (its online-softmax steps chain, and three
    quarter steps cost more than one whole one: PERF.md §6, PR 25).
    ``window`` keeps, of the causal entries, those less than ``window``
    positions back: per 512-row query block at window 1024 one diagonal,
    one full and one band-edge tile, however long the sequence.  ``diag =
    (g, HALVES)`` is block diffusion's schedule over ``S = Sk = 2L`` rows
    ``[noisy ; clean]`` in square tiles that divide L: a noisy query tile
    meets its OWN_BLOCK tile, the clean key tiles before it (FULL) and the
    clean one at it (BLOCK_DIAGONAL, strict); a clean query tile the same
    clean tiles under the loose diagonal; everything else is VOID."""
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a sliding window ({window}) is causal and >= 1")
    if diag is not None:
        g = diag[0]
        if not causal or window is not None:
            raise NotImplementedError(
                f"a block-granular diagonal (block {g}) is causal and has "
                f"no sliding window")
        if g < 1 or 128 % g:
            raise ValueError(
                f"block length {g} does not divide the 128-position tile "
                f"the schedule is cut in")
    halves = diag is not None and diag[1] == HALVES
    if halves and (S != Sk or block_q != block_k or S % (2 * block_q)):
        raise ValueError(
            f"[noisy ; clean] rows ({S} against {Sk}) in tiles of {block_q} "
            f"x {block_k}: the halves' schedule takes square tiles that "
            f"divide a half")

    def half(block):
        return block // 2 if halve_diagonal and block % 256 == 0 else block

    sq, sk = half(block_q), half(block_k)

    def kind_of(q0, k0, rows, cols):
        if halves:
            return _halves_tile_kind(q0, k0, rows, cols, S // 2, diag[0])
        return _tile_kind(q0 - k0, rows, cols, causal, window, diag)

    def subs(q0, k0):
        return tuple((r0, c0, kind_of(q0 + r0, k0 + c0, sq, sk))
                     for r0 in range(0, block_q, sq)
                     for c0 in range(0, block_k, sk))

    def kept(q0, k0):
        return tuple(s for s in subs(q0, k0) if s[2] != VOID)

    if halves:      # the noisy half's first tile; the clean half's
        diagonal = ((OWN_BLOCK, kept(0, 0)),
                    (BLOCK_DIAGONAL, kept(S // 2, S // 2)))
    else:
        step = math.gcd(block_q, block_k)
        last = block_k if window is None else window + block_k
        diagonal = tuple(
            (d0, kept(d0, 0))
            for d0 in range(-(block_q // step - 1) * step, last, step)
            if kind_of(d0, 0, block_q, block_k) in MASKED)
    tiles = []
    for q0 in range(0, S, block_q):
        for k0 in range(0, Sk, block_k):
            kind = kind_of(q0, k0, block_q, block_k)
            tiles += [(q0 + r0, k0 + c0, sub if kind in MASKED else kind)
                      for r0, c0, sub in subs(q0, k0)]
    return TileSchedule(S, Sk, block_q, block_k, causal, sq, sk, diagonal,
                        tuple(tiles), window, diag)


def _note_score_tiles(pass_: str, sched: TileSchedule, heads: int) -> None:
    """Count, at trace time, the sub-tiles a head-sequence visits or skips
    in the kernel being traced (``pass_`` is ``"fwd"`` or ``"bwd"``), and
    the basic blocks its sweeps are cut into (``heads`` a lane block)."""
    _note_sweep_blocks(pass_, sched, heads)
    family = _registry.counter(
        "flash_score_tiles_total",
        "score sub-tiles of one head-sequence by what the flash kernel "
        "does with them: void runs no code, full builds no mask, diagonal, "
        "band_edge (a sliding window's lower edge), block_diagonal (a "
        "diagonal in blocks of positions: block diffusion) and own_block "
        "(block diffusion's noisy queries on the noisy keys of their own "
        "blocks) are masked (counted at trace time, not per call)",
        labelnames=("pass", "kind"))
    for kind, n in collections.Counter(t[2] for t in sched.tiles).items():
        family.labels(pass_, kind).inc(n)


def _full_tiles(own, sched: TileSchedule, *, own_is_q: bool):
    """``(lo, hi)``: the swept tiles ``[lo, hi)`` that are FULL for the
    program that owns tile ``own`` (a python int or the traced program
    id); empty where ``lo >= hi``.  The forward owns a query tile and
    sweeps key tiles, the full ones come first; the backward owns a key
    tile and sweeps query tiles, the full ones come last.  A window cuts
    the run at its other end."""
    bq, bk = sched.block_q, sched.block_k
    nq, nk = sched.S // bq, sched.Sk // bk
    if not sched.causal:
        return 0, (nk if own_is_q else nq)
    traced = isinstance(own, jax.Array)
    lowest, highest = (jnp.minimum, jnp.maximum) if traced else (min, max)
    w = sched.window
    if own_is_q:    # FULL: k0 + bk - 1 <= q0
        hi = lowest(nk, (own * bq + 1) // bk)
        if w is None:
            return 0, hi
        # ... and q0 + bq - 1 - k0 < window
        return highest(0, (own * bq + bq - w + bk - 1) // bk), hi
    # FULL: q0 >= k0 + bk - 1, from the first such query tile on
    lo = lowest(nq, ((own + 1) * bk - 1 + bq - 1) // bq)
    if w is None:
        return lo, nq
    # ... to the last with q0 + bq - 1 - k0 < window
    return lo, lowest(nq, (own * bk + w - bq) // bq + 1)


def _tiles_a_body(heads: int) -> int:
    """FULL tiles a loop body folds, one or two.  Mosaic schedules one basic block at a
    time, and a tile's fold is one dependent chain (scores, maximum,
    exponential, product): alone in its block it has nothing to overlap
    (2.5 us a 512 x 512 tile alone against 1.2 overlapped, PR 25).  A block
    wants two chains: the heads of a lane block are independent ones, so two
    heads a block (head_dim 64) keep one tile a body, and one head a block
    (head_dim 128 and up, the two-product kernels) folds two."""
    return max(1, 2 // heads)


def _fold_run(n, fold_at, carry, per: int, *, whole: bool = False,
              branch: bool = False):
    """Thread ``carry`` through ``fold_at(u, carry, live)`` for ``u`` in
    ``[0, n)``, in order, ``per`` a loop body.  What is left over when a
    traced ``n`` is no multiple of ``per`` follows the loop as ``per - 1``
    tiles, in one of two ways, both measured (v5e, PR 43, a call at (4,
    8192, 32 / 4, 128), one tile a trip → void / branch).  Computed VOID
    where ``u >= n`` (``live`` is the traced ``u < n``; None for a tile that
    is always there), in straight-line code: one block with the sweep's
    masked tiles that follow.  The forward's way: 20.32 → 19.08 / 19.46 ms
    (two-product 13.32 → 12.69 / 12.69, the halves' 22.69 → 21.17 / 21.57).
    Or under a BRANCH: the backward's way, 37.18 → 36.30 / 35.59 ms
    (two-product 27.17 → 27.47 / 26.43): its tile is five products, two and
    a half times the forward's, so a void one wastes more than a boundary
    costs, and the block that follows already holds the diagonal tile's
    three sub-tiles.  ``whole`` says that ``n`` is a multiple of ``per`` by
    construction."""
    def body(p, c):
        for u in range(per):
            c = fold_at(p * per + u, c, None)
        return c

    carry = jax.lax.fori_loop(0, n // per, body, carry)
    if whole or per == 1:
        return carry
    if isinstance(n, int):
        for u in range(n - n % per, n):
            carry = fold_at(u, carry, None)
        return carry
    for u in range(per - 1):
        at = n // per * per + u
        if branch:
            carry = jax.lax.cond(
                at < n, functools.partial(fold_at, at, live=None),
                lambda c: c, carry)
        else:
            carry = fold_at(at, carry, at < n)
    return carry


def _halves_sweep(own, sched: TileSchedule, *, own_is_q: bool, per: int = 1):
    """The ``sweep`` (:func:`_for_program`) of the program that owns tile
    ``own`` (the traced program id) of the halves' schedule, one body for
    both halves: which half ``own`` lies in decides at run time what it
    meets, and the mask of a BLOCK_DIAGONAL tile takes its strictness as
    the offset ``-g`` (a noisy query tile) or 0 (a clean one).

    Forward, a query tile: a noisy one folds its OWN_BLOCK tile, first, so
    that its first block's rows, which keep no clean key, never meet a
    running maximum of nothing; then either half folds the clean key tiles
    before it, ``per`` a loop body (:func:`_fold_run`), and the one at it.
    Backward, a key tile: a noisy one meets its own query tile alone (its dk
    and dv are whole there), walked in the sub-tiles on its diagonal; a
    clean one meets the query tiles past it, always an even number, and the
    one at it, of both halves."""
    b, g = sched.block_q, sched.diag[0]
    n = sched.S // (2 * b)                          # tiles a half
    subs = dict(sched.diagonal)
    noisy = own < n
    r = jnp.where(noisy, own, own - n)              # the tile of its half

    def sweep(carry, full_tile, diagonal_tile):
        def own_block(c):
            return diagonal_tile(r * b, 0, subs[OWN_BLOCK], c)

        if own_is_q:
            def before(t, c, live):     # clean key tile t <= r: in range
                return full_tile((n + t) * b, c, live=live)

            carry = jax.lax.cond(noisy, own_block, lambda c: c, carry)
            carry = _fold_run(r, before, carry, per)
            return diagonal_tile((n + r) * b, jnp.where(noisy, -g, 0),
                                 subs[BLOCK_DIAGONAL], carry)

        def clean_keys(c):
            def past(u, c, live):   # [r + 1, n) of the noisy, then of the clean
                t = r + 1 + u
                return full_tile(jnp.where(t < n, t, t + r + 1) * b, c)

            c = _fold_run(2 * (n - 1 - r), past, c, per, whole=True)
            return jax.lax.fori_loop(
                0, 2, lambda h, c: diagonal_tile(
                    (h * n + r) * b, (h - 1) * g, subs[BLOCK_DIAGONAL], c), c)

        return jax.lax.cond(noisy, own_block, clean_keys, carry)

    return sweep


def _is_looped(sched: TileSchedule, *, own_is_q: bool) -> bool:
    """Whether a program's sweep places its tiles from the traced program
    id (:func:`_for_program`): one body for every program, a loop or a
    straight-line block (:func:`_sweep_form`), its backward summing dk and
    dv in scratch."""
    if sched.halves:
        return True
    nq, nk = sched.S // sched.block_q, sched.Sk // sched.block_k
    n_own, n_swept = (nq, nk) if own_is_q else (nk, nq)
    return n_swept > UNROLL_MAX or (sched.causal and n_own > UNROLL_MAX)


def _own_axis(sched: TileSchedule, own_is_q: bool):
    """``(n_own, own_block, n_swept, swept_block, sign)`` of a sweep, with
    ``own * own_block = t0 + sign * d0`` where ``d0 = q0 - k0``."""
    nq, nk = sched.S // sched.block_q, sched.Sk // sched.block_k
    if own_is_q:
        return nq, sched.block_q, nk, sched.block_k, 1
    return nk, sched.block_k, nq, sched.block_q, -1


def _diagonal_of(o: int, sched: TileSchedule, own_is_q: bool):
    """[(t0, d0, subs)] of the program that owns tile ``o`` (an int): the
    masked tiles it meets."""
    _, own_block, n_swept, swept_block, sign = _own_axis(sched, own_is_q)
    met = [(o * own_block - sign * d0, d0, subs)
           for d0, subs in sched.diagonal]
    return [m for m in met if m[0] % swept_block == 0
            and 0 <= m[0] < n_swept * swept_block]


def _sweep_form(sched: TileSchedule, *, own_is_q: bool):
    """``(straight, slots)`` of a sweep placed from the program id
    (:func:`_is_looped`), from the schedule alone.  ``slots`` is the most
    FULL tiles a program meets.  ``straight``: under a sliding window every
    program but the first few (forward; the last few, backward) meets the
    same ``slots`` FULL tiles and the same masked ones, so at ``STRAIGHT_MAX``
    tiles or fewer the sweep is one straight-line block, the tiles a program
    does not meet computed void.  Any other sweep loops over its FULL
    tiles."""
    n_own = _own_axis(sched, own_is_q)[0]
    runs = [_full_tiles(o, sched, own_is_q=own_is_q) for o in range(n_own)]
    slots = max(max(hi - lo, 0) for lo, hi in runs)
    straight = (sched.window is not None
                and slots + len(sched.diagonal) <= STRAIGHT_MAX)
    return straight, slots


def sweep_blocks(sched: TileSchedule, *, own_is_q: bool, heads: int) -> dict:
    """The basic blocks that the sweeps of one head-sequence are cut into,
    by form: ``straight`` a run of tiles in straight-line code (any number
    of them: what Mosaic's scheduler overlaps), ``paired_loop`` and
    ``single_loop`` one trip of a loop that folds two tiles or one,
    ``branch`` a run under a ``lax.cond`` of the sweep, where a program
    takes it.  From shapes alone, as the kernels decide."""
    blocks = collections.Counter()
    per = _tiles_a_body(heads)
    loop = "paired_loop" if per > 1 else "single_loop"
    n_own = _own_axis(sched, own_is_q)[0]
    if sched.halves:
        n = n_own // 2
        for r in range(n):
            if own_is_q:    # a noisy and a clean query tile r
                blocks["branch"] += 1
                blocks[loop] += 2 * (r // per)
                blocks["straight"] += 2
            else:           # a noisy key tile; a clean one
                blocks["branch"] += 1
                blocks[loop] += 2 * (n - 1 - r) // per
                blocks["single_loop"] += 2
        return {form: n for form, n in blocks.items() if n}
    if not _is_looped(sched, own_is_q=own_is_q):
        return {"straight": n_own}
    straight, _ = _sweep_form(sched, own_is_q=own_is_q)
    for o in range(n_own):
        lo, hi = _full_tiles(o, sched, own_is_q=own_is_q)
        blocks["straight"] += 1
        if not straight:
            blocks[loop] += max(hi - lo, 0) // per
            if (sched.causal and sched.window is None and not own_is_q
                    and (hi - lo) % per):
                blocks["branch"] += 1   # the odd tile out (:func:`_fold_run`)
    return {form: n for form, n in blocks.items() if n}


def _note_sweep_blocks(pass_: str, sched: TileSchedule, heads: int) -> None:
    """Count, at trace time beside :func:`_note_score_tiles`, the basic
    blocks of one head-sequence's sweeps in the kernel being traced."""
    family = _registry.counter(
        "flash_sweep_blocks_total",
        "basic blocks that the flash kernel's sweeps of one head-sequence "
        "are cut into, by form: straight (a run of tiles in straight-line "
        "code), paired_loop / single_loop (one trip of a loop over full "
        "tiles that folds two / one), branch (a run under a lax.cond); with "
        "flash_score_tiles_total, tiles a block (counted at trace time, not "
        "per call)",
        labelnames=("pass", "form"))
    blocks = sweep_blocks(sched, own_is_q=pass_ == "fwd", heads=heads)
    for form in ("straight", "paired_loop", "single_loop", "branch"):
        family.labels(pass_, form).inc(blocks.get(form, 0))


def _for_program(own, sched: TileSchedule, program, *, own_is_q: bool,
                 heads: int = 1):
    """Run ``program(sweep, looped)`` for the grid program that owns tile
    ``own`` of its axis (a query tile in the forward, a key tile in the
    backward); ``looped`` says that the sweep is placed from the traced
    ``own`` (:func:`_is_looped`); ``heads`` is how many independent chains
    a tile's fold already holds (:func:`_tiles_a_body`).

    ``sweep(carry, full_tile, diagonal_tile)`` threads ``carry`` through
    ``full_tile(t0, carry, live=None)`` for every FULL tile the program
    meets and ``diagonal_tile(t0, d0, subs, carry, live=None)`` for every
    masked one; ``t0`` is the first position of the tile on the swept axis.
    VOID tiles get no code at all, but for one case: where ``live`` is
    given (a traced flag) the tile is computed at a position clamped into
    range and wholly masked where the flag is false (:func:`_band_mask`).

    Mosaic schedules one basic block at a time, so every block of a sweep
    should hold two tiles' worth of independent work.  Short sweeps
    (S=1024, block 512 → 2 tiles) get one straight-line program per value
    of ``own`` under a ``pl.when``: every position is static and nothing
    separates the tiles, so Mosaic overlaps one tile's matmuls with its
    neighbour's vector work (a branch per tile was 50% slower on the v5e
    than computing the void tile as well).  Long sweeps are one body for
    every program: under a sliding window one straight-line block
    (:func:`_sweep_form`); else a loop over the FULL tiles, two a trip at
    one head a lane block (:func:`_fold_run`), then the masked tiles placed
    from ``own``.  A masked tile that only some programs meet is computed
    void by the others; the one branch left is around the backward loop's
    odd tile out, where the chip read it faster (:func:`_fold_run`), and
    never under a window.  The halves' schedule has a sweep of its own
    (:func:`_halves_sweep`)."""
    per = _tiles_a_body(heads)
    if sched.halves:
        return program(
            _halves_sweep(own, sched, own_is_q=own_is_q, per=per), True)
    n_own, own_block, n_swept, swept_block, sign = _own_axis(sched, own_is_q)

    def static_sweep(o):
        def sweep(carry, full_tile, diagonal_tile):
            for t in range(*_full_tiles(o, sched, own_is_q=own_is_q)):
                carry = full_tile(t * swept_block, carry)
            for t0, d0, subs in _diagonal_of(o, sched, own_is_q):
                carry = diagonal_tile(t0, d0, subs, carry)
            return carry
        return sweep

    def dynamic_sweep(carry, full_tile, diagonal_tile):
        straight, slots = _sweep_form(sched, own_is_q=own_is_q)
        lo, hi = _full_tiles(own, sched, own_is_q=own_is_q)

        def full_at(u, c, live):        # FULL tile lo + u of the sweep
            t = lo + u
            if live is not None:
                t = jnp.clip(t, 0, n_swept - 1)
            return full_tile(t * swept_block, c, live=live)

        if straight:
            for u in range(slots):
                carry = full_at(u, carry, lo + u < hi)
        else:   # traced bounds under a diagonal; no branch under a window
            n = jnp.maximum(hi - lo, 0) if sched.causal else hi - lo
            carry = _fold_run(n, full_at, carry, per,
                              branch=not own_is_q and sched.window is None)
        for d0, subs in sched.diagonal:
            # programs that meet a masked tile at this offset: all of them
            # (the usual case), none, or some: those compute it void
            meets = [any(m[1] == d0 for m in _diagonal_of(o, sched, own_is_q))
                     for o in range(n_own)]
            t0 = own * own_block - sign * d0
            if all(meets):
                carry = diagonal_tile(t0, d0, subs, carry)
            elif any(meets):
                live = ((t0 >= 0) & (t0 % swept_block == 0)
                        & (t0 < n_swept * swept_block))
                at = jnp.clip(t0 // swept_block, 0, n_swept - 1)
                carry = diagonal_tile(at * swept_block, d0, subs, carry,
                                      live=live)
        return carry

    if _is_looped(sched, own_is_q=own_is_q):
        program(dynamic_sweep, True)
    elif not sched.causal or n_own == 1:    # all programs meet the same tiles
        program(static_sweep(0), False)
    else:
        for o in range(n_own):
            pl.when(own == o)(
                functools.partial(program, static_sweep(o), False))


def _band_mask(s, d: int, kind: str, sched: TileSchedule, live=None,
               keys_first: bool = False):
    """Void the entries of score tile ``s`` (queries by keys; keys by
    queries with ``keys_first``) outside the band, its first
    query position lying ``d`` after its first key position: those with
    ``q_pos < k_pos`` in a DIAGONAL tile, those with ``q_pos - k_pos >=
    window`` in a BAND_EDGE one, both in a CROSSED one; in a
    BLOCK_DIAGONAL one those whose key block lies after the query's block
    (at or after it when strict: the strictness is in ``d``, traced,
    :func:`_halves_sweep`); in an OWN_BLOCK one those of another block than
    the query's; none in a FULL one (``kind`` None too).
    ``live`` (a traced flag) voids all of them where it is false: this
    program does not meet the tile and computes it in place of a branch
    (:func:`_for_program`)."""
    def keep(inside):
        if live is not None:
            inside = live if inside is None else inside & live
        return jnp.where(inside, s, NEG_INF)

    if kind in (None, FULL):
        return keep(None)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, int(keys_first))
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, int(not keys_first))
    window = sched.window
    if kind in (BLOCK_DIAGONAL, OWN_BLOCK):
        g = sched.diag[0]
        shift = g.bit_length() - 1          # g divides 128: a power of two
        if kind == OWN_BLOCK:
            return keep((row >> shift) + d // g == (col >> shift))
        back = d >> shift
        return keep((row >> shift) + back >= (col >> shift))
    if kind == DIAGONAL:
        return keep(row + d >= col)
    inside = row + (d - window) < col
    if kind == CROSSED:
        inside &= row + d >= col
    return keep(inside)


def _col(x):
    """(rows,) → (rows, 1).  The lax op that ``x[:, None]`` ends in: jnp
    indexing costs ~1 ms of tracing a use, and a step traces ~150 kernels."""
    return jax.lax.expand_dims(x, (1,))


def _row(x):
    """(lanes,) → (1, lanes), as cheaply as :func:`_col`."""
    return jax.lax.expand_dims(x, (0,))


def _rows(x, start: int, size: int):
    """``x[start:start + size]`` (static), traced as cheaply."""
    if start == 0 and size == x.shape[0]:
        return x
    return jax.lax.slice_in_dim(x, start, start + size, axis=0)


def _sum_lanes(width: int) -> int:
    """Lanes of the forward's running row sums for score tiles ``width``
    keys wide (:func:`_lane_blocks_sum`)."""
    return 128 if width % 128 == 0 else width


def _lane_blocks_sum(p):
    """The 128-lane column blocks of ``p`` added up: ``(rows, 128)`` whose
    sum over lanes is ``p``'s.  A sum over lanes a tile was 4 to 10% of a
    forward call on the v5e (PR 47: the unit that moves data across lanes
    is what the kernels wait for); these adds run on a vector unit with
    slots to spare."""
    w = _sum_lanes(p.shape[1])
    out = p[:, :w]
    for c in range(w, p.shape[1], w):
        out = out + p[:, c:c + w]
    return out


def _dot(a, b, contract, pass_: str):
    """``a · b`` over ``contract``, summed in float32, in the kernel of
    ``pass_`` (``"fwd"`` or ``"bwd"``); counted at trace time by the type of
    its operands.  They are float32 whatever the call's type, and that costs
    nothing: under Mosaic's default contract precision the v5e rounds a
    float32 operand to bf16 on its way into the MXU, bit for bit what
    ``astype`` gives and at a bf16 operand's rate (PR 47, ``chip_smoke.py
    kernel_mxu_operand_rounding``); with bf16 operands a forward call read
    0 to 2% slower and a backward call 3 to 8% (PERF.md section 6)."""
    _registry.counter(
        "flash_mxu_operands_total",
        "products of the flash kernel body being traced by the type of "
        "their operands (the sum is float32 whatever it is); counted at "
        "trace time, not per call",
        labelnames=("pass", "dtype")).labels(pass_, a.dtype.name).inc()
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


class Lanes(NamedTuple):
    """How the kernels cut the last dimension of their operands, from
    shapes alone.  ``rows`` operands are ``(B, S, H·D)``, the free reshape
    of what the projections write: a program's ``block`` lanes hold
    ``heads`` whole heads (head_dim 64 → 2 a 128-lane block, 32 → 4, 128
    and 256 → 1), and the last block may be ragged (25 heads of 64 are
    12.5 blocks).  A head_dim that does not tile 128 lanes (96, 80) keeps
    one head a program over ``(B·H, S, D)`` panels: the same kernels, one
    head wide."""
    width: int      # lanes of an operand: H·D in rows, D head-major
    block: int      # lanes of a program
    head_dim: int

    @property
    def rows(self) -> bool:
        return 128 % self.head_dim == 0 or self.head_dim % 128 == 0

    @property
    def heads(self) -> int:
        return self.block // self.head_dim

    @property
    def blocks(self) -> int:
        return -(-self.width // self.block)

    @property
    def ragged(self) -> bool:
        return self.width % self.block != 0

    @property
    def reason(self) -> str:
        """What ``kernel_dispatch_total{site="attention"}`` says ran."""
        if not self.rows:
            return (f"head-major: head_dim {self.head_dim} does not tile "
                    f"128 lanes")
        return (f"rows layout, {self.heads} "
                f"head{'s' if self.heads > 1 else ''} a {self.block}-lane "
                f"block")


def grouped_in_kernel(D: int) -> bool:
    """Whether the kernels take fewer key-value heads than query heads at
    this head_dim (one head a lane block), from the shape alone."""
    return D % 128 == 0


def flash_lanes(H: int, D: int) -> Lanes:
    """The layout the kernels run for ``H`` heads of ``D``: a function of
    the shape alone, never of a model or a switch."""
    lanes = Lanes(H * D, min(max(D, 128), H * D), D)
    return lanes if lanes.rows else Lanes(D, D, D)


def _for_lane_block(lanes: Lanes, body) -> None:
    """Run ``body(heads, limit)`` for this program's lane block: the
    heads it holds and the lane they end at.  That is all of the block,
    except in a ragged last block: its lanes from ``limit`` on may hold
    anything, and what is stored there is dropped.  The last block is a
    program of its own, so no other one pays for its edge."""
    if not lanes.ragged:
        return body(lanes.heads, lanes.block)
    last = lanes.blocks - 1
    left = lanes.width - last * lanes.block
    c = pl.program_id(1)
    pl.when(c < last)(functools.partial(body, lanes.heads, lanes.block))
    pl.when(c == last)(functools.partial(body, left // lanes.head_dim, left))


def _keep_lanes(x, lo: int, hi: int):
    """``x`` with every lane outside ``[lo, hi)`` zeroed (by a select: what
    is there may be NaN).  Two uses.  A head's lanes of the block:
    contracted over all lanes with an untouched operand, that gives the
    head's product alone, in the MXU passes a head_dim-wide contraction
    padded to the block takes.  And the lanes an operand has, on the other
    side of such a contraction in a ragged block: 0 · NaN must not meet."""
    if lo == 0 and hi >= x.shape[1]:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < hi), x, 0.0)


def _head_lanes(x, h: int, lanes: Lanes):
    """``x`` with only head ``h``'s lanes of the block kept."""
    return _keep_lanes(x, h * lanes.head_dim, (h + 1) * lanes.head_dim)


def _own_lanes(per_head, lanes: Lanes):
    """One block-wide array that holds, in each head's lanes, that head's
    entry of ``per_head`` (each computed over all lanes of the block)."""
    out = per_head[0]
    if len(per_head) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(1, len(per_head)):
            out = jnp.where(lane >= h * lanes.head_dim, per_head[h], out)
    return out


class Term(NamedTuple):
    """One product ``q_t · k_t`` of a score tile ``s = Σ_t q_t · k_t``, as
    the grid's head programs read its operands (one program a lane block of
    the first term's ``q``, the :class:`Lanes` of the call).  The first term
    is the per-head product; a further one is taken at one head a lane block
    (:func:`mla_lanes`)."""
    block: int      # lanes of a program's block of q_t and of k_t
    # consecutive head programs that read ONE block of k_t: 1, their own
    # keys; ``group``, grouped queries; H, one key that all heads share
    keys: int = 1
    # head programs that share one lane block of q_t, each keeping its own
    # head's ``block // heads`` lanes: 128 // R for rope lanes R < 128 wide
    heads: int = 1


def _key_grads(terms: tuple) -> tuple:
    """The term whose key blocks each gradient of a backward program is
    shaped and shared as, in the order the kernel keeps them: dk of the
    first term, dv (values go with the first term's keys), dk of every
    further term."""
    return (terms[0], *terms)


def _shared(c, n: int):
    """Index of the block that ``n`` consecutive head programs share (an
    index map with nothing shared stays the plain ``c``: no ``// 1``)."""
    return c if n == 1 else c // n


def _last_of(c, n: int, at):
    """Row index of a gradient block that ``n`` consecutive head programs
    sum: ``at`` under the last of them, where the block is whole; until then
    it stays at tile 0 and nothing of it is written back."""
    return at if n == 1 else jnp.where(c % n == n - 1, at, 0)


def _own_head(x, c, term: Term):
    """``x`` (rows, ``term.block``), a lane block that ``term.heads`` head
    programs share, with only the lanes of program ``c``'s head kept."""
    if term.heads == 1:
        return x
    width = term.block // term.heads
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    lo = (c % term.heads) * width
    return jnp.where((lane >= lo) & (lane < lo + width), x, 0.0)


def _split(refs, *counts):
    """``refs`` cut into consecutive runs of ``counts`` refs, and the rest."""
    runs, at = [], 0
    for n in counts:
        runs.append(refs[at:at + n])
        at += n
    return (*runs, refs[at:])


def _zero(ref) -> None:
    ref[...] = jnp.zeros(ref.shape, ref.dtype)


def _fwd_kernel(*refs, scale, sched, lanes, terms):
    """One (batch, lane block, query tile) program; ``refs`` are q of every
    term, k of every term, v, o and lse.  The heads of the block are
    independent online-softmax chains in one basic block, so Mosaic overlaps
    one head's matmuls with another's vector work; each keeps a block-wide
    accumulator, and its own lanes are picked once, at the store.  The
    softmax denominator is kept a partial sum a lane (:func:`_lane_blocks_sum`)
    and summed over lanes once a program."""
    n = len(terms)
    (q_ref, *more_q), (k_ref, *more_k), (v_ref, o_ref, lse_ref) = _split(
        refs, n, n)
    bq, L = q_ref.shape[1:]
    dot = functools.partial(_dot, pass_="fwd")
    i = pl.program_id(2)    # read here: not inside a branch
    c = pl.program_id(1) if any(t.heads > 1 for t in terms) else None

    def lane_block(heads, limit):
        def program(sweep, looped):
            q = q_ref[0].astype(jnp.float32) * scale             # (bq, L)
            qs = [_head_lanes(q, h, lanes) for h in range(heads)]
            more = [(_own_head(r[0].astype(jnp.float32) * scale, c, t), kr)
                    for r, kr, t in zip(more_q, more_k, terms[1:])]

            def fold(k0, carry, d=None, kind=None, live=None):
                """One online-softmax step a head: key tile [k0,
                +block_k) into each ``(m, l, acc)``; ``d`` is the mask
                offset and ``kind`` the tile's, None for a FULL tile;
                ``live`` is false where this program computes the tile
                void (:func:`_band_mask`), None where it never does."""
                ks = pl.ds(k0, sched.block_k)
                k = _keep_lanes(k_ref[0, ks].astype(jnp.float32), 0, limit)
                v = v_ref[0, ks].astype(jnp.float32)
                out = []
                for q_h, (m, l, acc) in zip(qs, carry):
                    s = dot(q_h, k, ((1,), (1,)))               # (bq, bk)
                    for q_t, k_t in more:
                        s = s + dot(q_t, k_t[0, ks].astype(jnp.float32),
                                     ((1,), (1,)))
                    if d is not None or live is not None:
                        s = _band_mask(s, d, kind, sched, live)
                    m_new = jnp.maximum(m, s.max(axis=-1))
                    # rows with everything masked keep m=-inf; keep exp
                    # well-defined
                    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
                    p = jnp.exp(s - _col(m_safe))
                    corr = _col(jnp.where(m == NEG_INF, 0.0,
                                          jnp.exp(m - m_safe)))
                    out.append((m_new, l * corr + _lane_blocks_sum(p),
                                acc * corr + dot(p, v, ((1,), (0,)))))
                return tuple(out)

            def diagonal_tile(k0, d0, subs, carry, live=None):
                assert len(subs) == 1   # the forward leaves them whole
                return fold(k0, carry, d0, subs[0][2], live)

            carry = sweep(((jnp.full((bq,), NEG_INF, jnp.float32),
                            jnp.zeros((bq, _sum_lanes(sched.block_k)),
                                      jnp.float32),
                            jnp.zeros((bq, L), jnp.float32)),) * heads,
                          fold, diagonal_tile)
            outs = []
            for h, (m, l, acc) in enumerate(carry):
                l = l.sum(axis=-1)      # over lanes: once a program
                l_safe = jnp.where(l == 0.0, 1.0, l)
                outs.append(acc / _col(l_safe))
                m_safe = jnp.where(m == NEG_INF, 0.0, m)
                lse_ref[0, 0, h] = m_safe + jnp.log(l_safe)
            for h in range(heads, lanes.heads):      # heads that are not
                lse_ref[0, 0, h] = jnp.zeros((bq,), jnp.float32)
            o_ref[0] = _own_lanes(outs, lanes).astype(o_ref.dtype)

        _for_program(i, sched, program, own_is_q=True, heads=lanes.heads)

    _for_lane_block(lanes, lane_block)


def _dqkv_kernel(*refs, scale, sched, lanes, terms):
    """Backward: dq and dk of every term AND dv in ONE grid pass over
    k-blocks; ``refs`` are q of every term, k of every term, v, dO, lse and
    delta, then dq of every term, dk of every term and dv, then the scratch
    (:func:`_bwd_call`).

    ds is computed once per score tile and head and feeds all cotangents
    (3 + 2 a term MXU ops; K/V streamed once).  A score tile stands KEYS BY
    QUERIES here (``k · qᵀ``): dv = p · dO and dk = ds · q contract it as it
    stands, lse and delta lie along its lanes as they are stored, and only
    dq wants it turned, once a tile and head.  Queries by keys, p and ds
    were each turned for a transposed contraction and lse and delta for the
    subtraction: 5 to 14% of a call on the v5e, whose unit for moving data
    across lanes, not the MXU, is what these kernels wait for (PR 47).  A
    head's keys and values
    are the program's block with the other heads' lanes zeroed, once a
    program, so q and dO are contracted as they are loaded.  A term's dq
    sums in a float32 scratch across the k-block grid dim (TPU grids are
    sequential) and over the head programs that share its lane block, and
    is stored once, scaled, in q's type; its output block ignores the
    k-block dim, so it is flushed once, when whole.  The gradients a key
    tile owns (:func:`_key_grads`) are summed block-wide as values, one a
    head and ``sub_k`` band of the program's keys, and each head's lanes
    picked at the store; a looped sweep sums them in scratch (a loop would
    carry them through VMEM anyway).  dk carries ``scale`` via the
    pre-scaled q.

    A gradient whose block several consecutive head programs share (a
    key-value head's dk and dv under grouped queries; dk of the one key all
    heads read): the programs add theirs, key tile by key tile, in a
    whole-sequence float32 scratch; every program stores the running sum,
    and the output's block index moves on from key tile 0 only under the
    last of them (:func:`_last_of`), so what reaches HBM is each tile's
    complete sum, once."""
    n = len(terms)
    grads = _key_grads(terms)
    ((q_ref, *more_q), (k_ref, *more_k), (v_ref, do_ref, lse_ref, delta_ref),
     dq_refs, (dk_ref, *more_dk), (dv_ref,), dq_accs, scratch) = _split(
        refs, n, n, 4, n, n, 1, n)
    grad_refs = (dk_ref, dv_ref, *more_dk)
    bk, L = k_ref.shape[1:]
    sk = sched.sub_k
    dot = functools.partial(_dot, pass_="bwd")
    j = pl.program_id(2)
    c = pl.program_id(1) if any(t.keys > 1 or t.heads > 1 for t in terms) \
        else None
    # whether this is the first of the programs that share a block, once
    # for every number of them
    first = {m: c % m == 0 for m in dict.fromkeys(t.keys for t in grads)
             if m > 1}
    # a looped sweep's sums, one a gradient, or none; then the sums over
    # the programs that share a block, one a gradient that has them
    kv_acc = scratch[:len(scratch) - sum(t.keys > 1 for t in grads)]
    rest = iter(scratch[len(kv_acc):])
    shared = [next(rest) if t.keys > 1 else None for t in grads]

    for acc, t in zip(dq_accs, terms):
        pl.when(j == 0 if t.heads == 1 else (j == 0) & (c % t.heads == 0))(
            functools.partial(_zero, acc))

    def lane_block(n_heads, limit):
        heads = range(n_heads)

        def program(sweep, looped):
            k_blk = k_ref[0].astype(jnp.float32)                 # (bk, L)
            v_blk = v_ref[0].astype(jnp.float32)
            ks = [_head_lanes(k_blk, h, lanes) for h in heads]
            vs = [_head_lanes(v_blk, h, lanes) for h in heads]
            more_blk = [r[0].astype(jnp.float32) for r in more_k]

            def visit(q0, sums, r0=0, c0=0, rows=sched.block_q, cols=bk,
                      d=None, kind=None, live=None):
                """Queries [q0+r0, +rows) against keys [c0, +cols) of the
                program's block; ``d`` is the mask offset and ``kind`` the
                tile's, None for FULL; ``live`` is false where this program
                computes the tile void, None where it never does.
                ``sums`` maps each head and key band to its gradients so
                far (:func:`_key_grads`), or is None where they are summed
                in ``kv_acc``."""
                rs = pl.ds(q0 + r0, rows)
                q = _keep_lanes(q_ref[0, rs].astype(jnp.float32) * scale, 0,
                                limit)
                do = _keep_lanes(do_ref[0, rs].astype(jnp.float32), 0, limit)
                q_more = [_own_head(r[0, rs].astype(jnp.float32) * scale, c, t)
                          for r, t in zip(more_q, terms[1:])]
                k_more = [_rows(x, c0, cols) for x in more_blk]
                sums = None if sums is None else dict(sums)
                dq = None
                for h in heads:
                    k, v = _rows(ks[h], c0, cols), _rows(vs[h], c0, cols)
                    s = dot(k, q, ((1,), (1,)))                 # (cols, rows)
                    for q_t, k_t in zip(q_more, k_more):
                        s = s + dot(k_t, q_t, ((1,), (1,)))
                    if d is not None or live is not None:
                        s = _band_mask(s, d, kind, sched, live,
                                       keys_first=True)
                    p = jnp.exp(s - _row(lse_ref[0, 0, h, rs]))
                    dv = dot(p, do, ((1,), (0,)))
                    dp = dot(v, do, ((1,), (1,)))
                    ds = p * (dp - _row(delta_ref[0, 0, h, rs]))
                    dk = dot(ds, q, ((1,), (0,)))
                    made = [dk, dv] + [dot(ds, q_t, ((1,), (0,)))
                                       for q_t in q_more]
                    ds = ds.T                                   # (rows, cols)
                    dq_h = dot(ds, k, ((1,), (0,)))     # zero off the head
                    dq = dq_h if dq is None else dq + dq_h
                    for k_t, t, acc in zip(k_more, terms[1:], dq_accs[1:]):
                        acc[rs] += _own_head(dot(ds, k_t, ((1,), (0,))), c, t)
                    if sums is None:
                        for acc, g in zip(kv_acc, made):
                            acc[h, pl.ds(c0, cols)] += g
                        continue
                    for b in range(c0, c0 + cols, sk):
                        sums[h, b] = tuple(
                            a + _rows(g, b - c0, sk)
                            for a, g in zip(sums[h, b], made))
                dq_accs[0][rs] += dq
                return sums

            def diagonal_tile(q0, d0, subs, sums, live=None):
                for r0, c0, kind in subs:
                    sums = visit(q0, sums, r0, c0, sched.sub_q, sk,
                                 None if kind == FULL else d0 + r0 - c0, kind,
                                 live)
                return sums

            def store(g, b, size, per_head):
                """Keys [b, +size) of the program's block of gradient
                ``g``, summed over the programs that share the block."""
                ref, acc = grad_refs[g], shared[g]
                val = _own_lanes(per_head, lanes)
                if acc is not None:
                    at = pl.ds(j * bk + b, size)
                    # a select: the scratch holds anything before the
                    # first of the programs has written it
                    val = val + jnp.where(first[grads[g].keys], 0.0, acc[at])
                    acc[at] = val
                ref[0, pl.ds(b, size)] = val.astype(ref.dtype)

            if looped:
                for acc in kv_acc:
                    _zero(acc)
                sweep(None, visit, diagonal_tile)
                for g, acc in enumerate(kv_acc):
                    store(g, 0, bk, [acc[h] for h in heads])
                return
            zeros = {w: jnp.zeros((sk, w), jnp.float32)
                     for w in dict.fromkeys(t.block for t in grads)}
            zero = tuple(zeros[t.block] for t in grads)
            sums = sweep({(h, b): zero for h in heads
                          for b in range(0, bk, sk)}, visit, diagonal_tile)
            for b in range(0, bk, sk):
                for g in range(len(grads)):
                    store(g, b, sk, [sums[h, b][g] for h in heads])

        _for_program(j, sched, program, own_is_q=False, heads=lanes.heads)

    _for_lane_block(lanes, lane_block)

    def store_dq(ref, acc):
        for r in range(0, sched.S, sched.block_q):   # tile-sized values
            rs = pl.ds(r, sched.block_q)
            ref[0, rs] = (acc[rs] * scale).astype(ref.dtype)

    whole = j == pl.num_programs(2) - 1
    for ref, acc, t in zip(dq_refs, dq_accs, terms):
        pl.when(whole if t.heads == 1
                else whole & (c % t.heads == t.heads - 1))(
            functools.partial(store_dq, ref, acc))


def _largest_dividing_block(s: int, cap: int) -> int:
    """Largest tile ≤ cap that divides s (so S=1536 gets 512, S=1152 gets
    128 — any S that a smaller default handled keeps working)."""
    b = min(cap, s)
    while b > 128 and s % b:
        b //= 2
    return b if s % b == 0 else min(s, 128)


def _pack(x, lanes: Lanes):
    """``(B, S, H, D)`` as the kernels take it: ``(B, S, H·D)``, a free
    reshape, or one ``(S, D)`` panel a head."""
    B, S, H, D = x.shape
    if lanes.rows:
        return x.reshape(B, S, H * D)
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unpack(x, lanes: Lanes, B: int):
    N, S, _ = x.shape
    if lanes.rows:
        return x.reshape(B, S, -1, lanes.head_dim)
    return x.reshape(B, N // B, S, lanes.head_dim).transpose(0, 2, 1, 3)


def _head_rows(x, lanes: Lanes):
    """A per-row, per-head vector ``(N, S, heads)`` (lse, delta) as the
    kernels index it: ``(N, lane blocks, heads a block, S)``, the heads of
    a ragged last block padded with zeros."""
    N, S, H = x.shape
    x = x.transpose(0, 2, 1)
    pad = lanes.blocks * lanes.heads - H
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x.reshape(N, lanes.blocks, lanes.heads, S)


def _row_heads(x, lanes: Lanes):
    """Inverse of :func:`_head_rows`: ``(N, S, heads)``."""
    N, _, _, S = x.shape
    x = x.reshape(N, lanes.blocks * lanes.heads, S)
    return x[:, :lanes.width // lanes.head_dim].transpose(0, 2, 1)


def _delta(do, out, lanes: Lanes):
    """Row sums of dO · O a head, float32, laid out as :func:`_head_rows`
    does.  A reshape to ``(..., H, D)`` costs XLA a float32 copy of the
    product before it can reduce - where a head is narrower than the 128
    lanes an array is tiled in, and at 128 too, because XLA keeps the
    product feature-major (``f32[4096,8,32,128]`` a layer of Mellum 2:
    2.6 ms by the compiler's own estimate, PR 34).  So the sum over a
    head's lanes is a product with a 0/1 matrix instead (one column a
    head, the padding heads' all zero), which XLA fuses the multiply
    into: dO and O are read once."""
    N, S, W = do.shape
    D = lanes.head_dim
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    if W == D:
        return _head_rows(prod.reshape(N, S, W // D, D).sum(axis=-1), lanes)
    heads = lanes.blocks * lanes.heads
    own = (jnp.arange(W)[None, :] // D
           == jnp.arange(heads)[:, None]).astype(jnp.float32)
    return jnp.einsum("nsw,hw->nhs", prod, own, precision="highest"
                      ).reshape(N, lanes.blocks, lanes.heads, S)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(qs, ks, v, causal, scale, block_q, block_k, lanes, terms,
           interpret, window=None, diag=None):
    """``qs`` and ``ks``: the packed q and k of every term, tuples."""
    out, _ = _flash_fwd(qs, ks, v, causal, scale, block_q, block_k, lanes,
                        terms, interpret, window, diag)
    return out


# The two pallas_calls sit behind ``jit(inline=True)``: nothing of the jit
# stays in the caller's jaxpr, but its trace cache means a model traces
# each kernel body once, not once a layer and remat pass (a step of the
# 48-layer benchmark model stages ~200 flash calls).
_STATIC = ("causal", "scale", "block_q", "block_k", "lanes", "terms",
           "interpret", "window", "diag")
# Mosaic gives a kernel 16 MB of VMEM unless told otherwise; the v5e has
# 128.  A kernel whose whole-sequence panels (double-buffered) and scratch
# come near the default asks for what they need and this much again for its
# key-tile blocks and the values of its body.
_VMEM_DEFAULT, _VMEM_HEADROOM = 12 << 20, 16 << 20


def _vmem(need: int) -> dict:
    """``pallas_call`` keywords for a kernel that holds ``need`` bytes of
    blocks (double-buffered) and scratch: none below the default."""
    if need <= _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + _VMEM_HEADROOM)}


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _fwd_call(qs, ks, v, *, causal, scale, block_q, block_k, lanes, terms,
              interpret, window=None, diag=None):
    N, S, W = qs[0].shape
    Sk = ks[0].shape[1]
    L, NB, P = lanes.block, lanes.blocks, lanes.heads
    sched = score_tile_schedule(S, Sk, block_q, block_k, causal, False,
                                window, diag)

    def tile(t):        # a program's rows of q_t; of o, as the first term's
        return pl.BlockSpec((1, block_q, t.block),
                            lambda n, c, i: (n, i, _shared(c, t.heads)))

    def panel(t):       # fetched once where its index holds: a key-value
        return pl.BlockSpec(    # head's queries, or every head of a row
            (1, Sk, t.block), lambda n, c, i: (n, 0, _shared(c, t.keys)))

    q_lanes = sum(t.block for t in terms)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, sched=sched, lanes=lanes,
                          terms=terms),
        grid=(N, NB, S // block_q),
        in_specs=[*map(tile, terms), *map(panel, terms), panel(terms[0])],
        out_specs=[
            tile(Term(L)),
            pl.BlockSpec((1, 1, P, block_q), lambda n, c, i: (n, c, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, S, W), qs[0].dtype),
            jax.ShapeDtypeStruct((N, NB, P, S), jnp.float32),
        ],
        interpret=interpret,
        **_vmem(2 * Sk * (q_lanes + L) * v.dtype.itemsize
                + 4 * block_q * q_lanes * 4),
    )(*qs, *ks, v)


def _flash_fwd(qs, ks, v, causal, scale, block_q, block_k, lanes, terms,
               interpret, window=None, diag=None):
    _note_score_tiles("fwd", score_tile_schedule(
        qs[0].shape[1], ks[0].shape[1], block_q, block_k, causal, False,
        window, diag), lanes.heads)
    out, lse = _fwd_call(qs, ks, v, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k, lanes=lanes,
                         terms=terms, interpret=interpret, window=window,
                         diag=diag)
    # named so a "<policy>+flash" remat policy can SAVE the kernel's
    # residuals: out/lse aren't dot outputs, so dots_saveable alone
    # recomputes the whole fwd kernel inside every backward pass
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (qs, ks, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, lanes, terms, interpret,
               window, diag, res, do):
    qs, ks, v, out, lse = res
    return _flash_bwd_impl(causal, scale, block_q, block_k, lanes, terms,
                           interpret, qs, ks, v, lse, do,
                           _delta(do, out, lanes), window, diag)


def _flash_bwd_impl(causal, scale, block_q, block_k, lanes, terms, interpret,
                    qs, ks, v, lse, do, delta, window=None, diag=None):
    """``(dqs, dks, dv)``, the first two tuples as ``qs`` and ``ks``."""
    _note_score_tiles("bwd", score_tile_schedule(
        qs[0].shape[1], ks[0].shape[1], block_q, block_k, causal, True,
        window, diag), lanes.heads)
    n = len(terms)
    grads = _bwd_call(qs, ks, v, do, lse, delta, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k, lanes=lanes,
                      terms=terms, interpret=interpret, window=window,
                      diag=diag)
    return tuple(grads[:n]), tuple(grads[n:2 * n]), grads[2 * n]


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _bwd_call(qs, ks, v, do, lse, delta, *, causal, scale, block_q, block_k,
              lanes, terms, interpret, window=None, diag=None):
    N, S, W = qs[0].shape
    Sk = ks[0].shape[1]
    L, NB, P = lanes.block, lanes.blocks, lanes.heads
    sched = score_tile_schedule(S, Sk, block_q, block_k, causal, True, window,
                                diag)
    grads = _key_grads(terms)

    def panel(t):       # whole rows of q_t and dq_t; of dO, as the first's
        return pl.BlockSpec((1, S, t.block),
                            lambda n, c, j: (n, 0, _shared(c, t.heads)))

    def block(t):       # the program's key tile of k_t; of v
        return pl.BlockSpec((1, block_k, t.block),
                            lambda n, c, j: (n, j, _shared(c, t.keys)))

    def grad(t):        # ... and of its gradient (:func:`_last_of`)
        return pl.BlockSpec(
            (1, block_k, t.block),
            lambda n, c, j: (n, _last_of(c, t.keys, j), _shared(c, t.keys)))

    rows = pl.BlockSpec((1, 1, P, S), lambda n, c, j: (n, c, 0, 0))
    # dq_t, summed over j (and over the programs that share its lanes)
    scratch = [pltpu.VMEM((S, t.block), jnp.float32) for t in terms]
    if _is_looped(sched, own_is_q=False):                # a sweep's sums
        scratch += [pltpu.VMEM((P, block_k, t.block), jnp.float32)
                    for t in grads]
    # ... and over the programs that share a key block
    scratch += [pltpu.VMEM((Sk, t.block), jnp.float32)
                for t in grads if t.keys > 1]
    item = qs[0].dtype.itemsize
    need = (2 * item * S * L + 4 * 4 * 8 * S
            + sum((4 + 2 * 2 * item) * S * t.block for t in terms)
            + sum(4 * Sk * t.block for t in grads if t.keys > 1))
    first, *more = terms
    return pl.pallas_call(
        functools.partial(_dqkv_kernel, scale=scale, sched=sched,
                          lanes=lanes, terms=terms),
        grid=(N, NB, Sk // block_k),
        in_specs=[*map(panel, terms), *map(block, terms), block(first),
                  panel(Term(L)), rows, rows],
        # dq's block ignores j: written once, when its sum is complete
        out_specs=[*map(panel, terms), *map(grad, terms), grad(first)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (*qs, *ks, v)],
        scratch_shapes=scratch,
        interpret=interpret,
        **_vmem(need),
    )(*qs, *ks, v, do, lse, delta)


_flash.defvjp(_flash_fwd, _flash_bwd)


def mla_lanes(heads: int, nope_dim: int, rope_dim: int,
              v_dim: int) -> Optional[Term]:
    """The second term of latent attention's score, ``q_rope_h · k_rope``
    against ONE rotated key for all heads, or None where the kernels have
    no layout for these widths: a further term is taken at one head a lane
    block, so values as wide as the per-head (``nope``) keys, a multiple of
    128 lanes, and rope heads that fill 128-lane blocks whole.  A program
    reads the 128-lane block of ``q_rope`` that holds its head (two heads at
    R = 64), keeps its head's lanes and contracts over the block against the
    shared key tiled to one block: never as wide as the heads, and fetched
    once a row, not once a head."""
    if v_dim != nope_dim or nope_dim % 128 or rope_dim < 8:
        return None
    per = max(1, 128 // rope_dim)       # heads a 128-lane block of q_rope
    if (rope_dim % 128 and 128 % rope_dim) or heads % per:
        return None
    return Term(per * rope_dim, heads, per)


def _prepare(q, k, scale, block_q, block_k, v=None, q_rope=None, k_rope=None):
    """``(scale, block_q, block_k, lanes, terms)`` of a call, checked."""
    B, S, H, D = q.shape
    Sk = k.shape[1]
    lanes = flash_lanes(H, D)
    if k.shape[2] != H and (H % k.shape[2] or not grouped_in_kernel(D)):
        raise ValueError(
            f"{H} query heads over {k.shape[2]} key-value heads of {D}: the "
            f"kernel groups whole heads of a multiple of 128 lanes; repeat "
            f"k and v to q's heads for any other shape")
    terms = (Term(lanes.block, H // k.shape[2]),)
    if q_rope is not None:
        R = q_rope.shape[-1]
        rope = mla_lanes(H, D, R, v.shape[-1])
        if rope is None or k_rope.shape[2] != 1 or k.shape[2] != H:
            raise ValueError(
                f"no two-product kernel for {H} heads of {D} + {R} rope "
                f"lanes, values {v.shape[-1]} wide, {k_rope.shape[2]} rope "
                f"keys on {k.shape[2]} key heads")
        terms += (rope,)
        D += R
    if scale is None:
        scale = D ** -0.5
    if lanes.block > 128:
        # a backward program keeps its keys, values and their gradients
        # in float32 beside whole-sequence panels of q, dO and dq: at 256
        # lanes and S 2048 a 512-row key block no longer fits the 16 MB
        # of VMEM a kernel may use
        block_k = min(block_k, 256)
    block_q = _largest_dividing_block(S, block_q)
    block_k = _largest_dividing_block(Sk, block_k)
    if S % block_q or Sk % block_k:
        raise ValueError(f"seq lengths ({S},{Sk}) must divide block sizes "
                         f"({block_q},{block_k})")
    return scale, block_q, block_k, lanes, terms


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_rope: Optional[jax.Array] = None,
                    k_rope: Optional[jax.Array] = None,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Public API, shapes ``(B, S, H, D)`` like ``ops.attention``; ``k``
    and ``v`` may have fewer heads than ``q`` (query head h reads
    key-value head ``h // (H // KV)``; head_dim a multiple of 128), and
    ``window`` keeps, of the causal keys, the last ``window``.

    With ``q_rope`` ``(B, S, H, R)`` and ``k_rope`` ``(B, S, 1, R)`` the
    score is two products, ``softmax((q_h · k_h + q_rope_h · k_rope) ·
    scale) v_h`` (latent attention: ONE rotated key for all heads; see
    :func:`mla_lanes` for the widths), and ``scale`` defaults to ``(D + R)
    ** -0.5``; all five gradients come from the one backward kernel.

    The kernels read q, k, v and dO and write o, dq, dk and dv as
    ``(B, S, H·D)``, the layout the projections on either side use, so
    nothing is transposed, copied or cast around them (:class:`Lanes`; in
    GPT-2-XL's step the eight layout copies a layer and the float32 round
    trip of the gradients were 7.7 ms of 191).  A head_dim that does not
    tile 128 lanes is transposed to one panel a head, as every shape was
    before; :func:`flash_lanes` decides from the shape, and
    ``kernel_dispatch_total`` says which ran.

    Default blocks are ``min(S, 512)``: large tiles beat the flash-paper-
    style 128x128 by ~1.8x on the bench chip (fewer programs, K/V panel
    streamed once), and an interleaved A/B sweep at S=1024 measured
    512x512 another ~3% faster e2e than whole-sequence 1024 tiles
    (GPT-2-125M train step 132.7ms vs 136.4ms — smaller score tiles
    double-buffer better); the online-softmax loop engages automatically
    for S > block.
    """
    scale, block_q, block_k, lanes, terms = _prepare(
        q, k, scale, block_q, block_k, v, q_rope, k_rope)
    qs, ks = (_pack(q, lanes),), (_pack(k, lanes),)
    if q_rope is not None:
        B, S, H, R = q_rope.shape
        kr = k_rope.reshape(B, -1, R)
        if terms[1].heads > 1:  # the shared key fills one lane block: a
            kr = jnp.tile(kr, (1, 1, terms[1].heads))   # copy per heads
        qs, ks = qs + (q_rope.reshape(B, S, H * R),), ks + (kr,)
    out = _flash(qs, ks, _pack(v, lanes), causal, scale, block_q, block_k,
                 lanes, terms, interpret, window, None)
    return _unpack(out, lanes, q.shape[0])


def block_length(block) -> int:
    """A block length of block diffusion, checked."""
    if not isinstance(block, int) or block < 1 or 128 % block:
        raise ValueError(
            f"block length {block!r}: the block-granular diagonal takes a "
            f"divisor of 128 (the schedule's tiles start at multiples of "
            f"128 positions and must start at multiples of a block)")
    return int(block)


def flash_attention_halves(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block: int, scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """Block diffusion's attention over ``[noisy ; clean]`` rows in one
    forward and one backward call: ``q`` ``(B, 2L, H, D)``, ``k`` and ``v``
    ``(B, 2L, KV, D)`` as the projections wrote them, the mask
    ``ops/attention.py block_diffusion_mask`` with blocks of ``block``
    positions (a divisor of 128 and of L).  Heads, layouts and types are
    :func:`flash_attention`'s; the tiles are square and divide L, and the
    schedule is ``diag = (block, HALVES)`` (:func:`score_tile_schedule`)."""
    B, S2, _, _ = q.shape
    if S2 % 2 or k.shape[1] != S2:
        raise ValueError(f"{S2} query and {k.shape[1]} key positions are "
                         f"not the two halves of one sequence")
    g = block_length(block)

    def half(x):
        return jax.ShapeDtypeStruct((B, S2 // 2) + x.shape[2:], x.dtype)

    scale, block_q, block_k, lanes, terms = _prepare(half(q), half(k), scale,
                                                     block_q, block_k)
    tile = min(block_q, block_k)
    out = _flash((_pack(q, lanes),), (_pack(k, lanes),), _pack(v, lanes),
                 True, scale, tile, tile, lanes, terms, interpret, None,
                 (g, HALVES))
    return _unpack(out, lanes, B)


# ---------------------------------------------------------------------------
# LSE-exposing variant — building block for distributed (ring) attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, lanes, terms,
               interpret):
    return _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, lanes,
                          terms, interpret)[0]


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, lanes, terms,
                   interpret):
    out, res = _flash_fwd((q,), (k,), v, causal, scale, block_q, block_k,
                          lanes, terms, interpret)
    return (out, _row_heads(res[4], lanes)), res      # lse as (N, S, heads)


def _flash_lse_bwd(causal, scale, block_q, block_k, lanes, terms, interpret,
                   res, ct):
    do, dlse = ct
    qs, ks, v, out, lse = res
    # the lse cotangent folds into the shared backward exactly:
    # ds = p·(dp - δ') with δ' = δ - dlse, because ∂lse_i/∂s_ij = p_ij
    delta = _delta(do, out, lanes) - _head_rows(dlse.astype(jnp.float32),
                                                lanes)
    (dq,), (dk,), dv = _flash_bwd_impl(causal, scale, block_q, block_k, lanes,
                                       terms, interpret, qs, ks, v, lse, do,
                                       delta)
    return dq, dk, dv


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
    B, S, H, _ = q.shape
    scale, block_q, block_k, lanes, terms = _prepare(q, k, scale, block_q,
                                                     block_k)
    out, lse = _flash_lse(_pack(q, lanes), _pack(k, lanes), _pack(v, lanes),
                          causal, scale, block_q, block_k, lanes, terms,
                          interpret)
    if not lanes.rows:      # (B·H, S, 1): a head a panel
        lse = lse.reshape(B, H, S).transpose(0, 2, 1)
    return _unpack(out, lanes, B), lse
