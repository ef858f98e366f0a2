"""Grouped matrix multiplication over ragged groups, and the row
permutations of a sorted MoE dispatch.

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (N, K) lie
sorted by group, group ``g`` owns the next ``group_sizes[g]`` rows and is
multiplied by ``rhs[g]`` (K, M).  No capacity, no padding: a group of no
rows costs nothing, forward or backward.

On one TPU device it is the Pallas grouped matmul that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and
d-rows, ``tgmm`` d-weights, the names the device trace shows; since PR 61
the d-weights first, :func:`_megablox`) at :data:`TILES`.  PR 26 measured
it on the v5e against ``jax.lax.ragged_dot`` (which the TPU compiler lowers
to a Mosaic grouped matmul of its own, ``ragged-dot-none`` in the trace)
at OLMoE's shapes, (65536, 2048) x (64, 2048, 1024): alone 5.40 against
8.01 ms forward + backward with even groups and 7.42 against 10.6 ms with
uneven ones; end to end 33,120 against 28,938 / 29,741 tokens/s/chip, and
steady where the ragged dot's time moved 2.7% with the routing (PERF.md
section 6).  A Pallas call is
opaque to the partitioner, so the kernel takes one device's own rows: the
caller says ``per_device`` when it is on one device or inside a
``shard_map`` (``parallel/moe.py sorted_dispatch`` runs it so on every
data-parallel rank).  Where there is no TPU (the CPU tests), where the
operands are global arrays of a mesh that ``kernel_mesh_plan`` refused, or
at shapes the tiles do not divide, it is ``jax.lax.ragged_dot``;
``kernel_dispatch_total{site="grouped_matmul"}`` says which, and why.

``repeat_gather`` / ``combine_rows`` move rows into expert order and back
(``unsort_rows`` + a weighted sum where no kernel runs).  Each is a row
gather whose transpose XLA would write as a scatter-add; both know the
inverse permutation, so their backward passes are gathers too.  For a
share of the experts the permutation is partial (``absent``): pairs held
elsewhere have no row and rows past the groups no pair.  On one TPU device
(or a rank of the ``shard_map``), for bf16 rows of a width that is a
multiple of 256 in whole blocks, the movements are the Pallas kernels of
``ops/pallas/moe_rows.py`` - one DMA a row that holds a pair, none for a
row that holds none, the weighted sum over a token's k rows in VMEM with
no ``(S, k, M)`` array: HLO custom calls ``moe_rows_out`` (into expert
order) and ``moe_rows_back`` (back to token order).  Anywhere else they
are ``jnp.take``; ``kernel_dispatch_total{site="moe_rows"}`` says which,
and why.  A row without a pair reads zeros, with one exception: going out,
the kernel writes nothing past the block that holds the last pair, because
the one reader of those rows, the grouped matmul, skips them.

Between the grouped matmuls of a share's SwiGLU experts stands ONE buffer
``[a | b]`` (:func:`swiglu_plan`): one product with ``[gate | up]``, the
activation a Pallas row kernel over the rows that hold a pair
(:func:`swiglu_rows`), and in the backward one product for the rows'
cotangent - summed over ``2F`` in float32 where two products were rounded
and added - and one for ``d[gate | up]``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# (rows, contraction, columns) a grid step: the best of five measured at
# (65536, 2048) x (64, 2048, 1024) bf16 on the v5e, forward and backward
# ((512, 2048, 512) 29% slower, (512, 512, 1024) 14%, (256, 2048, 1024)
# overflows VMEM in tgmm)
TILES = (512, 1024, 1024)


def _tiles(m: int, k: int, n: int) -> Optional[Tuple[int, int, int]]:
    """:data:`TILES` cut to the shape, or ``None`` where they cannot be:
    rows come in whole tiles; the backward reuses the tiles with the
    contraction and column sizes swapped."""
    def fit(size, tile):
        # the largest multiple of 128 up to ``tile`` that divides: 1024 of
        # 2048, 768 of 2304, 896 of 896
        return next((t for t in range(min(tile, size) // 128 * 128, 0, -128)
                     if size % t == 0), None)

    tm, tk, tn = fit(m, TILES[0]), fit(k, TILES[1]), fit(n, TILES[2])
    return None if None in (tm, tk, tn) else (tm, tk, tn)


# what ``per_device=False`` books: the caller's ``shard_map`` did not engage
GLOBAL = "global arrays of a mesh of several devices"


def _own(per_device: Optional[bool], rows: int) -> Optional[int]:
    """The rows ``ops/pallas/spmd.py plan`` asks the mesh about: none where
    the caller has said whose the operands are."""
    return rows if per_device is None else None


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, per_device: Optional[bool] = None) -> jax.Array:
    """``out[i] = lhs[i] @ rhs[g(i)]``: (N, K) x (G, K, M) -> (N, M), the
    rows sorted by group and ``group_sizes`` (G,) int32 summing to N.
    ``per_device``: the operands are one device's own (inside a
    ``shard_map``, or a single device); ``None`` asks the mesh."""
    from .pallas import spmd

    group_sizes = group_sizes.astype(jnp.int32)
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2])
    if per_device is False:
        refusal = GLOBAL
    elif lhs.dtype != rhs.dtype \
            or lhs.dtype not in (jnp.bfloat16, jnp.float32):
        refusal = f"operand types {lhs.dtype} x {rhs.dtype}"
    else:
        refusal = None if tiles else \
            f"no tile divides {lhs.shape} x {rhs.shape}"
    if spmd.plan("grouped_matmul", _own(per_device, lhs.shape[0]), refusal,
                 f"tiles {tiles}", fallback="ragged_dot", kernel="megablox",
                 shard=False) is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _megablox(lhs, rhs, group_sizes, tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _megablox(lhs, rhs, sizes, tiles):
    """megablox's ``gmm`` with its two backward products in an order: the
    weights' gradient (``tgmm``) first and, behind an optimization barrier,
    the rows' (``gmm``).  Left to itself XLA's scheduler puts off the
    weight gradients of the first layer it differentiates, and the rows
    they read - (S k, M) each, most of a share's dead - stay allocated
    under the rows' cotangent: Qwen3-Next's step reserved 10.10 GiB of
    temporaries so and 8.64 in this order (sandbox compiles for the
    described v5e, PR 61)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(lhs, rhs, sizes, lhs.dtype, tiles)


def _megablox_fwd(lhs, rhs, sizes, tiles):
    return _megablox(lhs, rhs, sizes, tiles), (lhs, rhs, sizes)


def _megablox_bwd(tiles, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, sizes = res
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype, tiles,
                 num_actual_groups=rhs.shape[0])
    g, d_rhs = jax.lax.optimization_barrier((g, d_rhs))
    return gmm(g, rhs, sizes, lhs.dtype, tiles, transpose_rhs=True), \
        d_rhs, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def _rows(a: jax.Array, index: jax.Array, absent: bool) -> jax.Array:
    """``a[index]`` by rows.  With ``absent`` an index of ``a.shape[0]``
    stands for "no row" and reads zeros; without, every index is a row
    (the gather the sorted dispatch has always made)."""
    if absent:
        return jnp.take(a, index, axis=0, mode="fill", fill_value=0)
    return jnp.take(a, index, axis=0)


def _rows_plan(x, k: int, per_device: Optional[bool], absent: bool) -> bool:
    """Whether the Pallas row kernels move ``k`` rows a token of ``x``
    (S, M); counted, with the guard that decided, in
    ``kernel_dispatch_total{site="moe_rows"}``.  They run for a share
    (``absent``), where three rows in four hold no pair and cost them
    nothing.  A full permutation stays with XLA: its gather of 4 KB rows
    already runs at the rate one DMA a row can be issued (v5e, 65,536
    rows of 2048: 1.35 ms against the kernel's 2.8; PERF.md section 6,
    PR 32)."""
    from .pallas import moe_rows, spmd

    S, M = x.shape
    if not absent:
        refusal = "every row holds a pair"
    else:
        refusal = GLOBAL if per_device is False \
            else moe_rows.supported(S, k, M, x.dtype)
    return spmd.plan("moe_rows", _own(per_device, S), refusal,
                     f"rows {S * k} x {M}, block {moe_rows.STEP}",
                     shard=False) is not None


def _live(order: jax.Array, absent: bool) -> jax.Array:
    """(1,) int32: the rows that hold a pair (they come first)."""
    R = order.shape[0]
    if not absent:
        return jnp.full((1,), R, jnp.int32)
    return jnp.sum(order < R, dtype=jnp.int32)[None]


def _tokens_of(order: jax.Array, S: int) -> jax.Array:
    """Row j's token ``order[j] // k``; S ("no row") where it holds no
    pair."""
    R = order.shape[0]
    return jnp.where(order < R, order // (R // S), S)


def repeat_gather(x: jax.Array, order: jax.Array, inv: jax.Array,
                  absent: bool = False, *,
                  per_device: Optional[bool] = None) -> jax.Array:
    """``x`` (S, M), each row wanted ``k`` times -> (S*k, M) with row ``j``
    = ``x[order[j] // k]``; ``order`` is a permutation of ``range(S*k)``
    and ``inv`` its inverse.  With ``absent`` (a share of the experts)
    both are partial: ``order[j] = S*k`` where row j holds no pair (it
    reads zeros; the kernel leaves whole blocks of such rows unwritten,
    ``ops/pallas/moe_rows.py gather_rows``) and ``inv[p] = S*k`` where
    pair p has no row (it gives its token no gradient).  ``per_device`` as
    :func:`grouped_matmul`."""
    if _rows_plan(x, order.shape[0] // x.shape[0], per_device, absent):
        return _gather_pallas(x, order, inv, absent)
    return _gather_xla(x, order, inv, absent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_xla(x, order, inv, absent):
    return _rows(x, order // (order.shape[0] // x.shape[0]), absent)


def _gather_xla_fwd(x, order, inv, absent):
    return _gather_xla(x, order, inv, absent), (inv, x.shape[0])


def _gather_xla_bwd(absent, res, g):
    inv, S = res
    gx = _rows(g, inv, absent).reshape(S, -1, g.shape[-1])
    return gx.sum(axis=1).astype(g.dtype), None, None


_gather_xla.defvjp(_gather_xla_fwd, _gather_xla_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_pallas(x, order, inv, absent):
    from .pallas import moe_rows

    S = x.shape[0]
    packed = moe_rows.pack_rows(x, jnp.full((1,), S, jnp.int32),
                                name="moe_rows_out")
    return moe_rows.gather_rows(packed, _tokens_of(order, S),
                                _live(order, absent), name="moe_rows_out")


def _gather_pallas_fwd(x, order, inv, absent):
    return _gather_pallas(x, order, inv, absent), (order, inv, x.shape[0])


def _gather_pallas_bwd(absent, res, g):
    from .pallas import moe_rows

    order, inv, S = res
    packed = moe_rows.pack_rows(g, _live(order, absent), name="moe_rows_back")
    ones = jnp.ones((S, inv.shape[0] // S), jnp.float32)
    return moe_rows.combine_rows(packed, inv, ones,
                                 name="moe_rows_back"), None, None


_gather_pallas.defvjp(_gather_pallas_fwd, _gather_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def unsort_rows(y: jax.Array, order: jax.Array, inv: jax.Array,
                absent: bool = False) -> jax.Array:
    """Undo the sort: ``out[order[j]] = y[j]``, computed as ``y[inv]``
    (``absent``: as :func:`repeat_gather`; a pair without a row reads
    zeros, whatever the rows past the groups hold)."""
    return _rows(y, inv, absent)


def _unsort_rows_fwd(y, order, inv, absent):
    return unsort_rows(y, order, inv, absent), order


def _unsort_rows_bwd(absent, order, g):
    return _rows(g, order, absent), None, None


unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


def combine_rows(y: jax.Array, weights: jax.Array, order: jax.Array,
                 inv: jax.Array, absent: bool = False, *,
                 per_device: Optional[bool] = None) -> jax.Array:
    """Back to token order and summed: ``out[s] = sum_j weights[s, j] *
    y[inv[s*k + j]]``, (S, M) in ``y``'s type; a pair without a row adds
    nothing, whatever the rows past the groups hold.  The kernel sums in
    float32 and rounds once; the XLA path is :func:`unsort_rows` and an
    einsum over the ``(S, k, M)`` array it makes."""
    S, k = weights.shape
    if _rows_plan(jax.ShapeDtypeStruct((S, y.shape[1]), y.dtype), k,
                  per_device, absent):
        return _combine_pallas(y, weights.astype(jnp.float32), order, inv,
                               absent).astype(y.dtype)
    rows = unsort_rows(y, order, inv, absent).reshape(S, k, -1)
    return jnp.einsum("skm,sk->sm", rows, weights.astype(rows.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_pallas(y, weights, order, inv, absent):
    return _combine_pallas_fwd(y, weights, order, inv, absent)[0]


def _combine_pallas_fwd(y, weights, order, inv, absent):
    from .pallas import moe_rows

    packed = moe_rows.pack_rows(y, _live(order, absent), name="moe_rows_back")
    out = moe_rows.combine_rows(packed, inv, weights, name="moe_rows_back")
    return out, (packed, weights, order, inv)


def _combine_pallas_bwd(absent, res, g):
    """d-rows: row j gets its pair's weight times its token's cotangent, a
    scaled gather into expert order; d-weights: the rows a token combined,
    gathered once more and multiplied with its cotangent in VMEM."""
    from .pallas import moe_rows

    packed, weights, order, inv = res
    S = g.shape[0]
    scale = jnp.take(weights.reshape(-1), order, mode="fill",
                     fill_value=0)[:, None]
    d_rows = moe_rows.gather_rows(
        moe_rows.pack_rows(g, jnp.full((1,), S, jnp.int32),
                           name="moe_rows_out"),
        _tokens_of(order, S), _live(order, absent), scale,
        name="moe_rows_out")
    d_weights = moe_rows.combine_rows(packed, inv, weights, g,
                                      name="moe_rows_back")
    return d_rows, d_weights, None, None


_combine_pallas.defvjp(_combine_pallas_fwd, _combine_pallas_bwd)


def swiglu_plan(x, width: int, per_device: Optional[bool],
                absent: bool) -> bool:
    """Whether a SwiGLU expert FFN over the rows ``x`` (R, M) in expert
    order keeps ONE buffer ``[a | b]`` (R, 2 ``width``) between its
    grouped matmuls - one product with ``[gate | up]``, then
    :func:`swiglu_rows` - or a product each and XLA's ``silu(a) * b``;
    counted, with the guard that decided, in
    ``kernel_dispatch_total{site="moe_swiglu"}``.  The one buffer is a
    share's (``absent``) on one TPU device: XLA's elementwise passes and
    the sum of the rows' two cotangents run over every row of the buffer,
    dead or live, and three rows in four hold no pair.  A full permutation
    has nothing to skip, and the copies that make ``[gate | up]`` and split
    its gradient (XLA fuses neither with the leaves' casts) cost its many
    experts more than the sum they spare (OLMoE: 2.1 against 1.3 GB a
    layer; PERF.md section 6, PR 61)."""
    from .pallas import moe_rows, spmd

    R = x.shape[0]
    if not absent:
        refusal = "every row holds a pair"
    else:
        refusal = GLOBAL if per_device is False \
            else moe_rows.swiglu_supported(R, 2 * width, x.dtype)
    return spmd.plan("moe_swiglu", _own(per_device, R), refusal,
                     f"rows {R} x {2 * width}, block {moe_rows.GLU}",
                     shard=False) is not None


def swiglu_rows(ab: jax.Array, order: jax.Array) -> jax.Array:
    """``silu(a) * b`` of ``ab = [a | b]`` (R, 2F), the rows in expert
    order as :func:`repeat_gather` left a share's (``order``: its partial
    permutation) -> (R, F), where :func:`swiglu_plan` said so: the Pallas
    row kernel over the blocks that hold a pair, forward and backward (HLO
    custom calls ``moe_swiglu_rows`` / ``moe_swiglu_rows_back``).  The
    blocks past them are neither read nor written: their readers, the
    grouped matmuls, skip those rows."""
    return _swiglu_pallas(ab, _live(order, True))


@jax.custom_vjp
def _swiglu_pallas(ab, live):
    from .pallas import moe_rows

    return moe_rows.swiglu_rows(ab, live)


def _swiglu_pallas_fwd(ab, live):
    return _swiglu_pallas(ab, live), (ab, live)


def _swiglu_pallas_bwd(res, dh):
    from .pallas import moe_rows

    ab, live = res
    return moe_rows.swiglu_rows_back(dh, ab, live), None


_swiglu_pallas.defvjp(_swiglu_pallas_fwd, _swiglu_pallas_bwd)
