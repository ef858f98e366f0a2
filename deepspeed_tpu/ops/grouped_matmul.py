"""Grouped matrix multiplication over ragged groups, and the row
permutations of a sorted MoE dispatch.

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (N, K) lie
sorted by group, group ``g`` owns the next ``group_sizes[g]`` rows and is
multiplied by ``rhs[g]`` (K, M).  No capacity, no padding: a group of no
rows costs nothing, forward or backward.

On one TPU device it is the Pallas grouped matmul that ships with JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and
d-rows, ``tgmm`` d-weights, the names the device trace shows) at
:data:`TILES`.  PR 26 measured it on the v5e against ``jax.lax.ragged_dot``
(which the TPU compiler lowers to a Mosaic grouped matmul of its own,
``ragged-dot-none`` in the trace) at OLMoE's shapes, (65536, 2048) x
(64, 2048, 1024): alone 5.40 against 8.01 ms forward + backward with even
groups and 7.42 against 10.6 ms with uneven ones; end to end 33,120
against 28,938 / 29,741 tokens/s/chip, and steady where the ragged dot's
time moved 2.7% with the routing (PERF.md section 6).  A Pallas call is
opaque to the partitioner, so the kernel takes one device's own rows: the
caller says ``per_device`` when it is on one device or inside a
``shard_map`` (``parallel/moe.py sorted_dispatch`` runs it so on every
data-parallel rank).  Where there is no TPU (the CPU tests), where the
operands are global arrays of a mesh that ``kernel_mesh_plan`` refused, or
at shapes the tiles do not divide, it is ``jax.lax.ragged_dot``;
``kernel_dispatch_total{site="grouped_matmul"}`` says which, and why.

``repeat_gather`` / ``unsort_rows`` move rows into expert order and back.
Each is a row gather whose transpose XLA would write as a scatter-add;
both know the inverse permutation, so their backward passes are gathers
too.
"""
from __future__ import annotations

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
        return next((t for t in (tile, tile // 2, tile // 4, 128)
                     if t <= size and size % t == 0), None)

    tm, tk, tn = fit(m, TILES[0]), fit(k, TILES[1]), fit(n, TILES[2])
    return None if None in (tm, tk, tn) else (tm, tk, tn)


def _plan(lhs, rhs, per_device: Optional[bool]) -> Tuple[Optional[tuple], str]:
    from .attention import on_tpu
    from .pallas.spmd import kernel_mesh_plan

    if not on_tpu():
        return None, "no TPU"
    if per_device is None:
        per_device = kernel_mesh_plan(lhs.shape[0])[0] == "direct"
    if not per_device:
        return None, "global arrays of a mesh of several devices"
    if lhs.dtype != rhs.dtype or lhs.dtype not in (jnp.bfloat16, jnp.float32):
        return None, f"operand types {lhs.dtype} x {rhs.dtype}"
    tiles = _tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2])
    if tiles is None:
        return None, f"no tile divides {lhs.shape} x {rhs.shape}"
    return tiles, f"tiles {tiles}"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, per_device: Optional[bool] = None) -> jax.Array:
    """``out[i] = lhs[i] @ rhs[g(i)]``: (N, K) x (G, K, M) -> (N, M), the
    rows sorted by group and ``group_sizes`` (G,) int32 summing to N.
    ``per_device``: the operands are one device's own (inside a
    ``shard_map``, or a single device); ``None`` asks the mesh."""
    from .pallas.spmd import note_dispatch

    group_sizes = group_sizes.astype(jnp.int32)
    tiles, reason = _plan(lhs, rhs, per_device)
    if tiles is None:
        note_dispatch("grouped_matmul", "ragged_dot", reason)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    note_dispatch("grouped_matmul", "megablox", reason)
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiles)


@jax.custom_vjp
def repeat_gather(x: jax.Array, order: jax.Array, inv: jax.Array) -> jax.Array:
    """``x`` (S, M), each row wanted ``k`` times -> (S*k, M) with row ``j``
    = ``x[order[j] // k]``; ``order`` is a permutation of ``range(S*k)``
    and ``inv`` its inverse."""
    return jnp.take(x, order // (order.shape[0] // x.shape[0]), axis=0)


def _repeat_gather_fwd(x, order, inv):
    return repeat_gather(x, order, inv), (inv, x.shape[0])


def _repeat_gather_bwd(res, g):
    inv, S = res
    gx = jnp.take(g, inv, axis=0).reshape(S, -1, g.shape[-1])
    return gx.sum(axis=1).astype(g.dtype), None, None


repeat_gather.defvjp(_repeat_gather_fwd, _repeat_gather_bwd)


@jax.custom_vjp
def unsort_rows(y: jax.Array, order: jax.Array, inv: jax.Array) -> jax.Array:
    """Undo the sort: ``out[order[j]] = y[j]``, computed as ``y[inv]``."""
    return jnp.take(y, inv, axis=0)


def _unsort_rows_fwd(y, order, inv):
    return unsort_rows(y, order, inv), order


def _unsort_rows_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)
