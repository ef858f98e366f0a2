"""Row movements of the sorted MoE dispatch as Pallas kernels: one DMA a
row that holds a pair, none for a row that holds none.

**Why a row form.**  Mosaic (jax 0.9.0) slices an HBM or VMEM array along
its second-minor dimension only by whole tiles of 8 rows (``Slice shape
along dimension 0 must be aligned to tiling (8), but is 1``): a 2-D
``bf16[N, M]`` is stored in (8, 128) tiles with two rows packed into each
32-bit word, so one row is not an addressable piece of memory and no DMA
moves it.  A row becomes addressable as a *leading* index, so the kernels
move rows in the **row form** ``uint32[N, 1, M/2]``: word ``w`` of row
``n`` holds ``x[n, w]`` in its low half and ``x[n, M/2 + w]`` in its high
half, tiled (1, 128) - ``M/256`` contiguous 512-byte pieces a row, for any
``M`` that is a multiple of 256 (2048: 8, 2304: 9).  XLA converts 2-D to row
form and back at HBM speed, a full read and write each way
(``bf16[262144, 2304]``: 5.1 and 3.8 ms on the v5e, as long as the gather
it serves); here the conversion happens in VMEM, beside the DMAs:

- :func:`pack_rows` reads a 2-D array block by block and writes its row
  form (two shifts and an or a word); blocks past the ``live`` rows are
  not read.
- :func:`gather_rows` (``out[j] = src[idx[j]]``, optionally scaled a
  row) DMAs rows of a row-form source into a VMEM stage and *assembles*
  the 2-D output block from it: in the stage a row's 128-word pieces lie
  ``M/256`` sublanes apart, so one strided load brings the same piece of
  8 rows, its halves unpack to two (8, 128) float32 tiles (exact), and two
  of those pack to one (16, 128) bf16 tile of the output.
- :func:`combine_rows` (``out[s] = sum_j w[s, j] * y[inv[s, j]]``) DMAs
  the k rows of each token the same way; the strided load brings choice
  ``j`` of 8 tokens, products and the sum stay in float32 and are rounded
  once.  In ``dw`` mode the same rows are multiplied with the token's
  cotangent instead and summed over the width:
  ``dw[s, j] = <g[s], y[inv[s, j]]>``.  No ``(S, k, M)`` array exists.

Between a share's grouped matmuls (PR 61) :func:`swiglu_rows` and
:func:`swiglu_rows_back` run ``silu(a) * b`` and its cotangent over ONE
2-D buffer ``[a | b]``, bf16 in and out and float32 inside, a block of
:data:`GLU` rows a grid step and, like :func:`pack_rows`, only the blocks
that hold a live row: XLA's elementwise passes run over every row of the
buffer, and three in four hold no pair.

Indices arrive in SMEM a block of :data:`STEP` a grid step (XLA lays
``s32[n]`` out in tiles of 1024 and the whole of 262,144 indices is the
chip's entire 1 MB of SMEM, so they cannot be scalar-prefetched whole);
only counts are prefetched.  Rows are in flight :data:`SUB` at a time on
one DMA semaphore a stage, two stages (:func:`_stream`): a sub-block's
rows are awaited a group of :data:`WAIT` at a time (all of a full one in
one wait: the semaphore counts bytes, not DMAs), then the next
sub-block's DMAs are started while this one is consumed.  Going out, a
trip of the vector work that assembles 16 rows also starts the 16 that
take their place in the other stage, one or two after each piece's loads,
where the scheduler puts their scalar work beside the unpacking (v5e, a
call of 65,655 rows of 2304: 2.40 ms with loops of starts and a wait a
row, 1.79 with the waits grouped, 1.57 with the starts dealt through the
trips, 24 ns a row).  Coming back the starts stay in a loop of their own:
a DMA start orders the loads and stores around it, and the combine's
trips are all loads and stores (:func:`_combine_kernel`).  Index a stage
as ``stage[slot, ...]``: through a view ``stage.at[slot]`` the same loads
ran the kernels at half the speed.

An index of ``src.shape[0]`` means "no row" (a share's ``absent``).  No DMA is issued
for it and it reads zeros (the stage is cleared before a sub-block's DMAs
land in it).  Going out, blocks wholly past the ``live`` rows are not
written at all (:func:`gather_rows` says who reads them: nobody).  The loops that issue DMAs hold no branch - measured
on the v5e a branch a row costs what the DMA it spares would (16 against
25 ns) - so each is told how many rows to bring: going out the rows that
hold a pair come first, and coming back :func:`_fetch_list` sorts each
sub-block's pairs that have a row to its front (one XLA sort of packed
words, inside the same jit).

Every ``pallas_call`` sits behind a ``jax.jit`` of this module, so a
kernel body is traced once a process, distinct signature and tracing
context (the plain one and the one under ``grad`` of a remat block differ)
- not once a layer, remat pass, ``eval_shape``, init, eval step and expert
check - and lowered once a module (``moe_rows_traces_total`` counts the
traces).  The loops over a row's pieces stay unrolled in the gather and
the combine: rolled they trace in half the time and run 1.2 to 2.3 ms a
call slower at Mellum 2's shape (v5e, PERF.md section 6, PR 32).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry

# rows (or pairs) a grid step: one SMEM block of indices
STEP = 1024
# rows in flight on one semaphore; two stages of this many rows
SUB = 256
# rows a block of pack_rows
PACK = 256
# rows one semaphore wait awaits
WAIT = 16
# rows a block of the SwiGLU kernels
GLU = 256
_HIGH = 0xFFFF0000


def supported(n_tokens: int, k: int, width: int, dtype) -> Optional[str]:
    """``None`` where the kernels take ``(n_tokens, width)`` tokens wanted
    ``k`` times each, else the reason they do not."""
    if dtype != jnp.bfloat16:
        return f"rows of {jnp.dtype(dtype).name}"
    if width % 256:
        return f"row width {width} is no multiple of 256"
    if not 1 <= k <= 16:
        return f"top-{k} is not in 1..16"
    if n_tokens % PACK or (n_tokens * k) % STEP:
        return (f"{n_tokens} tokens x {k} are no whole blocks of {PACK} "
                f"tokens and {STEP} rows")
    return None


def _dealt(k: int) -> int:
    """The choices a token :func:`combine_rows` deals its blocks by: ``k``
    where it is a power of two, else the next one (top-10: 16), the choices
    past ``k`` "no row" pairs of weight 0 - the kernels' own absent form,
    which costs them no DMA and ``16 / k`` of the combine's vector work (a
    sub-block of :data:`SUB` pairs is ``SUB / k`` whole tokens only where k
    divides it)."""
    return 1 << (k - 1).bit_length()


def _note_trace(kernel: str, *signature) -> None:
    """Count, at trace time, one entry into a kernel's builder."""
    _registry.counter(
        "moe_rows_traces_total",
        "times a moe_rows kernel body was traced, by kernel and signature "
        "(once a process, signature and tracing context: the calls sit "
        "behind jax.jit)",
        labelnames=("kernel", "signature")).labels(
            kernel, " ".join(str(s) for s in signature)).inc()


def _unpack(words):
    """The two bf16 halves of uint32 ``words`` as float32, exactly."""
    low = lax.bitcast_convert_type(words << 16, jnp.float32)
    high = lax.bitcast_convert_type(words & jnp.uint32(_HIGH), jnp.float32)
    return low, high


def _live_block(rows: int):
    """Index map of a (``rows``, ...) block over an array whose first
    ``live`` rows alone matter: a step past them stays on the last live
    block, so that it neither fetches nor writes one."""
    def block(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0) // rows)
    return block


def _pack_kernel(live_ref, x_ref, out_ref):
    rows, half = out_ref.shape[0], out_ref.shape[2]

    def group(i, carry):
        r0 = pl.multiple_of(i * 16, 16)

        def bits(col):
            tile = x_ref[pl.ds(r0, 16), pl.ds(col, 128)]
            return lax.bitcast_convert_type(tile.astype(jnp.float32),
                                            jnp.uint32)

        def piece(c, carry):
            col = pl.multiple_of(c * 128, 128)
            words = (bits(col) >> 16) | bits(half + col)
            out_ref[pl.ds(r0, 8), 0, pl.ds(col, 128)] = words[:8]
            out_ref[pl.ds(r0 + 8, 8), 0, pl.ds(col, 128)] = words[8:]
            return carry

        return lax.fori_loop(0, half // 128, piece, carry)

    @pl.when(pl.program_id(0) * rows < live_ref[0])
    def _():
        lax.fori_loop(0, rows // 16, group, 0)


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def pack_rows(x: jax.Array, live: jax.Array, *, name: str,
              interpret: bool = False) -> jax.Array:
    """The row form ``uint32[N, 1, M/2]`` of ``x`` (N, M) bf16.  Blocks
    past the first ``live`` (1,) int32 rows are neither read nor written
    (they hold whatever memory held)."""
    N, M = x.shape
    _note_trace("pack", name, N, M)

    block = _live_block(PACK)
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // PACK,),
            in_specs=[pl.BlockSpec((PACK, M),
                                   lambda i, live: (block(i, live), 0))],
            out_specs=pl.BlockSpec((PACK, 1, M // 2),
                                   lambda i, live: (block(i, live), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((N, 1, M // 2), jnp.uint32),
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=4 * N * M),
        name=name, interpret=interpret,
    )(live, x)


def _deal(starts, places: int):
    """``starts`` dealt over ``places`` points of a trip's vector work, in
    order and as evenly as they go."""
    return [starts[len(starts) * p // places:len(starts) * (p + 1) // places]
            for p in range(places)]


def _stream(src_ref, stage, sem, count, fetch, trips, work, chunk=0):
    """Bring this grid step's :data:`STEP` rows into ``stage`` a sub-block
    of :data:`SUB` at a time and run the vector work of each landed
    sub-block: ``work(q, slot, i, starts)`` for ``i`` in ``range(trips)``.
    Sub-block ``q`` has ``count(q)`` rows to bring; its ``t``-th is
    ``src[row]`` and lands in stage row ``at``, with ``(row, at) =
    fetch(q, t)``.

    The rows of a slot are awaited a group at a time: every row's DMA
    signals the slot's one semaphore and is as long as the first, so one
    wait on a descriptor of ``g`` rows awaits any ``g`` of them.

    ``chunk`` is the gather's alone (the combine passes one trip and no
    chunk: :func:`_combine_kernel` says what the chip read).  With it, the
    next sub-block's starts sit inside this one's trips, where the
    scheduler (it fills a bundle from one basic block) can put their
    scalar work beside vector work: as many trips as ``count(q + 1)``
    covers whole chunks run a body that is handed ``chunk`` ``starts`` to
    call between its pieces, the others the bare body, and what no chunk
    covers - and all of a grid step's first sub-block, which has no trip
    before it - is started in a loop of its own.  No loop holds a branch:
    a branch a row costs as much as the DMA it would spare."""
    subs = STEP // SUB

    def start(q, slot, t):
        row, at = fetch(q, t)
        pltpu.make_async_copy(src_ref.at[row], stage.at[slot, at],
                              sem.at[slot]).start()

    def start_loop(q, slot, lo, hi):
        def eight(i, carry):    # the loop's own scalar work rivals a DMA's
            for u in range(8):
                start(q, slot, lo + i * 8 + u)
            return carry

        eights = (hi - lo) // 8
        lax.fori_loop(0, eights, eight, 0)
        lax.fori_loop(lo + eights * 8, hi,
                      lambda t, carry: start(q, slot, t), None)

    def wait(n, slot):
        def rows(g):
            pltpu.make_async_copy(src_ref.at[pl.ds(0, g)],
                                  stage.at[slot, pl.ds(0, g)],
                                  sem.at[slot]).wait()

        def grouped():
            groups = n // WAIT
            lax.fori_loop(0, groups, lambda i, carry: rows(WAIT), None)
            lax.fori_loop(groups * WAIT, n, lambda i, carry: rows(1), None)

        lax.cond(n == SUB, lambda: rows(SUB), grouped)

    def clear(slot):            # a row without a DMA reads zeros
        stage[slot] = jnp.zeros(stage.shape[1:], stage.dtype)

    def sub_block(q, carry):
        slot = q % 2
        wait(count(q), slot)
        ahead = jnp.where(q + 1 < subs, count(jnp.minimum(q + 1, subs - 1)),
                          0)
        # a full sub-block lands on every row of the stage
        pl.when((ahead < SUB) & (q + 1 < subs))(lambda: clear(1 - slot))
        covered = jnp.minimum(ahead // chunk, trips) if chunk else 0
        start_loop(q + 1, 1 - slot, covered * chunk, ahead)

        def both(i, carry):
            work(q, slot, i, [
                functools.partial(start, q + 1, 1 - slot, i * chunk + u)
                for u in range(chunk)])
            return carry

        def bare(i, carry):
            work(q, slot, i, [])
            return carry

        if chunk:
            lax.fori_loop(0, covered, both, 0)
        lax.fori_loop(covered, trips, bare, 0)
        return carry

    clear(0)
    start_loop(0, 0, 0, count(0))
    lax.fori_loop(0, subs, sub_block, 0)


# the gather: a trip assembles 16 rows and starts the 16 that take their place
GATHER_TRIPS, GATHER_CHUNK = SUB // 16, 16


def gather_starts(live: int, rows: int) -> Tuple[int, int]:
    """``(block, loop)``: the row DMAs of one :func:`gather_rows` call of
    ``rows`` rows, the first ``live`` of which hold a pair, by where
    :func:`_stream` starts them: handed to a trip of the sub-block before
    (every whole chunk of a sub-block that is not its grid step's first),
    in a loop of their own.  A function of ``live`` alone, so the program
    counts nothing: ``chip_smoke.py`` and the scope probes book
    ``moe_rows_dma_starts_total`` from it on the host.  The combine has no
    such split: every pair that has a row starts in a loop
    (:func:`_combine_kernel`)."""
    block = sum(
        min(max(live - q * SUB, 0), SUB) // GATHER_CHUNK * GATHER_CHUNK
        for q in range(rows // SUB) if q % (STEP // SUB))
    return block, live - block


def _gather_kernel(live_ref, idx_ref, src_ref, *rest, scaled):
    scale_ref = rest[0] if scaled else None
    out_ref, stage, sem = rest[-3:]
    half = stage.shape[-1]
    first = pl.program_id(0) * STEP

    def assemble(q, slot, i, starts):
        t0 = pl.multiple_of(i * 16, 16)
        r0 = pl.multiple_of(q * SUB + t0, 16)
        # a start after each piece's loads: beside the unpacking, not in
        # a burst at the trip's head (v5e: 1.57 against 1.74 ms a call)
        for c, some in enumerate(_deal(starts, half // 128)):
            parts = [_unpack(stage[slot, pl.ds(t0 + h, 8), 0,
                                   pl.ds(c * 128, 128)])
                     for h in (0, 8)]
            for go in some:
                go()
            if scaled:
                parts = [tuple(p * scale_ref[pl.ds(r0 + h, 8), :]
                               for p in part)
                         for part, h in zip(parts, (0, 8))]
            for side, col in ((0, c * 128), (1, half + c * 128)):
                tile = jnp.concatenate([part[side] for part in parts], 0)
                out_ref[pl.ds(r0, 16), pl.ds(col, 128)] = tile.astype(
                    out_ref.dtype)

    def count(q):       # the rows that hold a pair come first
        return jnp.clip(live_ref[0] - first - q * SUB, 0, SUB)

    @pl.when(first < live_ref[0])   # a step past the live rows does nothing
    def _():
        _stream(src_ref, stage, sem, count,
                lambda q, t: (idx_ref[q * SUB + t], t), GATHER_TRIPS,
                assemble, GATHER_CHUNK)


def _stage(half):
    return [pltpu.VMEM((2, SUB, 1, half), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,))]


def _wide_rows(half: int) -> dict:
    """``pallas_call`` arguments for :func:`gather_rows` where its
    double-buffered ``(STEP, width)`` block, the two stages and the scales
    pass Mosaic's default 16 MiB scope: rows wider than ~2,900 channels
    (3,584: 17.5 MiB asked) get a scope of 32 of the v5e's 128 MiB.  {}
    where they fit, and the call is then what it always was (:data:`STEP`
    cannot shrink instead: an SMEM block of indices is 1,024 long)."""
    need = 2 * STEP * 2 * half * 2 + 2 * SUB * half * 4 + (1 << 20)
    if need <= 15 << 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=32 << 20)}


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def gather_rows(src: jax.Array, idx: jax.Array, live: jax.Array,
                scale: Optional[jax.Array] = None, *, name: str,
                interpret: bool = False) -> jax.Array:
    """``out[j] = src[idx[j]] * scale[j]`` as 2-D bf16 (R, M): ``src`` in
    row form (N, 1, M/2), ``idx`` (R,) int32, ``scale`` (R, 1) float32 or
    none.  Rows from ``live`` (1,) int32 on hold no pair (``idx[j] = N``
    there).  Up to the end of the block of :data:`STEP`
    that holds the last live row they are written as zeros; the blocks
    past it are **not written** and hold whatever memory held: their one
    reader, the grouped matmul, reads no row past its groups, and three
    quarters of a share's buffer are such rows (zeros there cost the v5e
    1.7 ms of a 4.3 ms call at Mellum 2's shape)."""
    N, _, half = src.shape
    R = idx.shape[0]
    _note_trace("gather", name, N, R, 2 * half, scale is not None)
    block = _live_block(STEP)
    in_specs = [pl.BlockSpec((STEP,), lambda i, live: (block(i, live),),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    if scale is not None:
        in_specs.append(pl.BlockSpec((STEP, 1),
                                     lambda i, live: (block(i, live), 0)))
    return pl.pallas_call(
        functools.partial(_gather_kernel, scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // STEP,), in_specs=in_specs,
            out_specs=pl.BlockSpec((STEP, 2 * half),
                                   lambda i, live: (block(i, live), 0)),
            scratch_shapes=_stage(half)),
        out_shape=jax.ShapeDtypeStruct((R, 2 * half), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * half * (scale is not None), transcendentals=0,
            bytes_accessed=8 * R * half + 8 * R),
        name=name, interpret=interpret, **_wide_rows(half),
    )(live, idx, src, *(() if scale is None else (scale,)))


def _combine_kernel(count_ref, list_ref, src_ref, w_ref, *rest, k, dw):
    g_ref = rest[0] if dw else None
    out_ref, stage, sem = rest[-3:]
    half = stage.shape[-1]
    tokens = SUB // k           # a sub-block's

    def choice(slot, t0, j, c):
        """Piece ``c`` of choice ``j`` of the sub-block's 8 tokens from
        ``t0`` on, as two (8, 128) float32 halves: the stage holds a
        sub-block choice by choice, so they are 8 rows in a run."""
        return _unpack(stage[slot, pl.ds(j * tokens + t0, 8), 0,
                             pl.ds(c * 128, 128)])

    def consume(q, slot):
        def group(i, carry):
            t0 = pl.multiple_of(q * tokens + i * 16, 16)
            s0 = pl.multiple_of(i * 16, 16)
            if dw:
                dots = [[jnp.zeros((8, 128), jnp.float32)] * k
                        for _ in (0, 8)]
                for c in range(half // 128):
                    g = [g_ref[pl.ds(t0, 16), pl.ds(col, 128)].astype(
                        jnp.float32) for col in (c * 128, half + c * 128)]
                    for n, h in enumerate((0, 8)):
                        for j in range(k):
                            low, high = choice(slot, s0 + h, j, c)
                            dots[n][j] = (dots[n][j] + low * g[0][h:h + 8]
                                          + high * g[1][h:h + 8])
                for n, h in enumerate((0, 8)):
                    for j in range(k):
                        out_ref[pl.ds(t0 + h, 8), pl.ds(j, 1)] = \
                            dots[n][j].sum(axis=1, keepdims=True)
                return carry
            w = [[w_ref[pl.ds(t0 + h, 8), pl.ds(j, 1)] for j in range(k)]
                 for h in (0, 8)]
            for c in range(half // 128):
                sums = []
                for n, h in enumerate((0, 8)):
                    low = high = jnp.zeros((8, 128), jnp.float32)
                    for j in range(k):
                        a, b = choice(slot, s0 + h, j, c)
                        low, high = low + a * w[n][j], high + b * w[n][j]
                    sums.append((low, high))
                for side, col in ((0, c * 128), (1, half + c * 128)):
                    tile = jnp.concatenate([s[side] for s in sums], 0)
                    out_ref[pl.ds(t0, 16), pl.ds(col, 128)] = tile.astype(
                        out_ref.dtype)
            return carry

        lax.fori_loop(0, tokens // 16, group, 0)

    first = pl.program_id(0) * (STEP // SUB)

    def fetch(q, t):
        entry = list_ref[q * SUB + t]
        return entry >> 8, entry & (SUB - 1)

    # One trip, the whole of ``consume``, and no starts inside it (chunk 0).
    # Measured on the v5e at Mellum 2's shape (PERF.md section 6, PR 46): a
    # DMA start orders the loads and stores around it, so a quarter of a
    # sub-block's starts dealt through the trips ran 5.48 ms a call against
    # 4.39 with every start in its loop, at a trip's head 4.29, and 4.00
    # only with the slots static (the sub-blocks unrolled in pairs: the
    # bodies four times over, twice the set-up of the bodies twice over,
    # which is already past its budget).
    _stream(src_ref, stage, sem, lambda q: count_ref[first + q], fetch, 1,
            lambda q, slot, i, starts: consume(q, slot))


def _fetch_list(idx, n_rows: int, k: int):
    """What :func:`_combine_kernel` brings, sub-block by sub-block of
    :data:`SUB` pairs: ``counts`` (pairs that have a row) and ``entries``,
    those pairs first, each its row ``<< 8 |`` its place in the stage -
    choice j of the sub-block's token i at ``j * tokens + i``, so that a
    choice of 8 tokens is 8 stage rows in a run.  Made here with one sort
    a sub-block, so that the kernel's loop meets no absent pair: a branch
    a pair costs as much as the DMA it would spare."""
    t = jnp.arange(SUB, dtype=jnp.int32)
    place = (t % k) * (SUB // k) + t // k
    entries = (idx.reshape(-1, SUB) << 8) | place
    counts = jnp.sum(idx.reshape(-1, SUB) < n_rows, axis=1, dtype=jnp.int32)
    return counts, jnp.sort(entries, axis=1).reshape(-1)     # n_rows last


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def combine_rows(src: jax.Array, idx: jax.Array, weights: jax.Array,
                 g: Optional[jax.Array] = None, *, name: str,
                 interpret: bool = False) -> jax.Array:
    """``out[s] = sum_j weights[s, j] * src[idx[s*k + j]]`` as 2-D bf16
    (S, M), summed in float32 and rounded once: ``src`` in row form
    (N, 1, M/2), ``idx`` (S*k,) int32, ``weights`` (S, k) float32.  With
    ``g`` (S, M) bf16 instead ``out[s, j] = <g[s], src[idx[s*k + j]]>``,
    (S, k) float32 (the weights are not read).  An index of N is "no row"
    and adds (or gives) zero."""
    N, _, half = src.shape
    S, k_given = weights.shape
    k = _dealt(k_given)
    if k != k_given:        # "no row" pairs of weight 0 up to a power of two
        idx = jnp.pad(idx.reshape(S, k_given), ((0, 0), (0, k - k_given)),
                      constant_values=N).reshape(-1)
        weights = jnp.pad(weights, ((0, 0), (0, k - k_given)))
    dw = g is not None
    _note_trace("combine", name, N, S, k, 2 * half, dw)
    tokens = STEP // k
    counts, entries = _fetch_list(idx, N, k)
    in_specs = [pl.BlockSpec((STEP,), lambda i, counts: (i,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((tokens, k), lambda i, counts: (i, 0))]
    if dw:
        in_specs.append(pl.BlockSpec((tokens, 2 * half),
                                     lambda i, counts: (i, 0)))
        out_spec = pl.BlockSpec((tokens, k), lambda i, counts: (i, 0))
        out_shape = jax.ShapeDtypeStruct((S, k), jnp.float32)
    else:
        out_spec = pl.BlockSpec((tokens, 2 * half), lambda i, counts: (i, 0))
        out_shape = jax.ShapeDtypeStruct((S, 2 * half), jnp.bfloat16)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, k=k, dw=dw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S * k // STEP,), in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=_stage(half)),
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=4 * S * k * half, transcendentals=0,
            bytes_accessed=4 * S * k * half + 4 * S * half + 8 * S * k),
        name=name, interpret=interpret,
    )(counts, entries, src, weights, *((g,) if dw else ()))
    return out[:, :k_given] if dw else out


def _live_pieces(live_ref, rows, width, piece):
    """``piece(at, lo, hi)`` for every 16 rows ``at`` and 128 lanes ``lo``
    of ``a`` (``hi``: the same lanes of ``b``) of a block of ``[a | b]``
    that holds a live row."""
    def group(i, carry):
        at = pl.ds(pl.multiple_of(i * 16, 16), 16)
        for col in range(0, width, 128):
            piece(at, pl.ds(col, 128), pl.ds(width + col, 128))
        return carry

    @pl.when(pl.program_id(0) * rows < live_ref[0])
    def _():
        lax.fori_loop(0, rows // 16, group, 0)


def _swiglu_kernel(live_ref, ab_ref, out_ref):
    def piece(at, lo, hi):
        a = ab_ref[at, lo].astype(jnp.float32)
        b = ab_ref[at, hi].astype(jnp.float32)
        out_ref[at, lo] = (a * jax.nn.sigmoid(a) * b).astype(out_ref.dtype)

    _live_pieces(live_ref, *out_ref.shape, piece)


def _swiglu_back_kernel(live_ref, dh_ref, ab_ref, out_ref):
    def piece(at, lo, hi):
        a = ab_ref[at, lo].astype(jnp.float32)
        b = ab_ref[at, hi].astype(jnp.float32)
        dh = dh_ref[at, lo].astype(jnp.float32)
        s = jax.nn.sigmoid(a)
        # silu'(a) = s + a s (1 - s), from the forward's sigmoid
        out_ref[at, lo] = (dh * b * (s * (1 + a * (1 - s)))).astype(
            out_ref.dtype)
        out_ref[at, hi] = (dh * (a * s)).astype(out_ref.dtype)

    _live_pieces(live_ref, *dh_ref.shape, piece)


def swiglu_supported(rows: int, width: int, dtype) -> Optional[str]:
    """``None`` where the SwiGLU kernels take ``[a | b]`` (``rows``,
    ``width``), else the reason they do not."""
    if dtype != jnp.bfloat16:
        return f"rows of {jnp.dtype(dtype).name}"
    if width % 256:
        return f"[a | b] of {width} is no two halves of whole 128-lane tiles"
    if rows % GLU:
        return f"{rows} rows are no whole blocks of {GLU}"
    return None


def _live_rows(kernel, live, operands, out_width, flops, name, interpret,
               donor=None):
    """``kernel`` over (:data:`GLU`, width) blocks of 2-D ``operands`` with
    as many rows, ``[a | b]`` the last, the blocks past the ``live`` rows
    left alone.  ``donor``: the operand whose buffer the output takes (a
    block is read before it is written)."""
    R = operands[0].shape[0]
    widths = [operand.shape[1] for operand in operands]
    block = _live_block(GLU)

    def spec(width):
        return pl.BlockSpec((GLU, width), lambda i, live: (block(i, live), 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // GLU,),
            in_specs=[spec(w) for w in widths], out_specs=spec(out_width)),
        out_shape=jax.ShapeDtypeStruct((R, out_width), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=R * widths[-1] // 2,
            bytes_accessed=2 * R * (sum(widths) + out_width)),
        input_output_aliases={} if donor is None else {1 + donor: 0},
        name=name, interpret=interpret,
    )(live, *operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def swiglu_rows(ab: jax.Array, live: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """``silu(a) * b`` of ``ab = [a | b]`` (R, 2F) bf16 as (R, F) bf16,
    float32 inside.  Blocks of :data:`GLU` rows wholly past the first
    ``live`` (1,) int32 rows are neither read nor written (they hold
    whatever memory held): their one reader, the grouped matmul, reads no
    row past its groups."""
    R, width = ab.shape
    _note_trace("swiglu", R, width)
    return _live_rows(_swiglu_kernel, live, (ab,), width // 2, 2 * R * width,
                      "moe_swiglu_rows", interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def swiglu_rows_back(dh: jax.Array, ab: jax.Array, live: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """:func:`swiglu_rows`'s cotangent ``d[a | b]`` (R, 2F) bf16 of ``dh``
    (R, F): ``[dh b silu'(a) | dh silu(a)]``, with the same blocks left
    alone."""
    R, width = ab.shape
    _note_trace("swiglu_back", R, width)
    return _live_rows(_swiglu_back_kernel, live, (dh, ab), width,
                      5 * R * width, "moe_swiglu_rows_back", interpret,
                      donor=1)
