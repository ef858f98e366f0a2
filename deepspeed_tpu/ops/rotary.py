"""Rotary position embeddings.

Kernel-parity analog of reference
``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu`` (378 LoC CUDA):
rotate the leading ``rotary_dim`` channels of q/k by position-dependent
angles.  One fused XLA computation; supports GPT-NeoX style (half-split)
rotation and partial rotary (``rotary_pct``).

Which shapes take which path.  :func:`apply_rotary` and
:func:`apply_rotary_interleaved` work in the ``(B, S, H, D)`` view and take
every shape.  On the chip that view is no bitcast of the ``(B, S, H*D)``
rows a projection writes and the flash kernels read, so where the rows
allow it a model keeps q and k flat and calls :func:`rotate_rows` (the
Pallas pass of ``ops/pallas/qk_rows.py``, which can also normalise each
head first): :func:`rows_plan` says yes where ``head_dim`` is a multiple of
128 (a head is whole lane blocks), the rotation is half-split over all of
``head_dim``, the call is no decode step (the cache keeps ``(B, S, KV,
D)``), the process is on a TPU and the operands are one device's own
(``kernel_mesh_plan``: one device, or a ``shard_map`` over the batch
axes).  Anything else - head_dim 64 / 80 / 96, ``rotary_dim < head_dim``,
interleaved pairs, heads split over ``tp``, the CPU - keeps the ``(B, S,
H, D)`` functions; ``kernel_dispatch_total{site="qk_rows"}`` counts each
decision with the guard that made it.

The same pass without positions is any per-head norm of rows: a Gated
DeltaNet's l2-norms of q and k are ``rotate_rows`` under constant scales
(PR 53), and its gated output norm has a guard and a pass of its own,
:func:`gated_norm_plan` / :func:`gated_norm_rows`
(``kernel_dispatch_total{site="gated_norm_rows"}``).  Where that layer's
heads are no whole lane tiles (Olmo-Hybrid's 96 x 192, PR 55) one guard,
:func:`slots_plan`, decides for both norms and the rule between them: the
heads then lie in lane slots from :func:`slot_rows` to
:func:`gated_norm_rows`.  Attention's heads of 64 / 80 / 96 channels keep
the ``(B, S, H, D)`` functions: a rotation over a slot is not written.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class RotaryTable(NamedTuple):
    """What one kind of layer rotates by: the angle a position advances in
    each channel pair, and the factor its cos and sin carry (1 unless the
    scaling rule says otherwise).  Made once a layer type, from the
    configuration alone (:func:`rotary_table`)."""
    inv_freq: Tuple[float, ...]
    scale: float = 1.0


@functools.lru_cache(maxsize=None)
def rotary_table(rotary_dim: int, rope_type: str = "default",
                 rope_theta: float = 10000.0, factor: float = 1.0,
                 original_max_position_embeddings: Optional[int] = None,
                 beta_fast: float = 32.0, beta_slow: float = 1.0,
                 attention_factor: Optional[float] = None,
                 truncate: bool = True) -> RotaryTable:
    """The table of a Hugging Face ``rope_parameters`` entry (its keys are
    the arguments).  ``default``: ``theta^(-2m/d)``.  ``yarn`` (Peng et
    al. 2023, arXiv:2309.00071, as ``modeling_rope_utils`` computes it):
    channel pairs that turn more than ``beta_fast`` times over the
    original context keep their frequency, those that turn less than
    ``beta_slow`` times are slowed by ``factor``, a linear ramp between
    them; cos and sin are scaled by ``attention_factor`` (``0.1 ln factor
    + 1`` where the entry gives none)."""
    half = rotary_dim // 2
    extrap = float(rope_theta) ** (-np.arange(half, dtype=np.float64) * 2
                                   / rotary_dim)
    if rope_type == "default":
        return RotaryTable(tuple(extrap.tolist()))
    if rope_type != "yarn":
        raise NotImplementedError(f"rope_type {rope_type!r}: 'default' and "
                                  f"'yarn' are written")
    if original_max_position_embeddings is None:
        raise ValueError("yarn needs original_max_position_embeddings")

    def turns_at(rotations):        # the channel pair that turns this often
        return (rotary_dim * math.log(original_max_position_embeddings
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(rope_theta)))

    low, high = turns_at(beta_fast), turns_at(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    inv_freq = extrap / factor * ramp + extrap * (1.0 - ramp)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return RotaryTable(tuple(inv_freq.tolist()), float(attention_factor))


def rotary_angles(positions: jax.Array, rotary_dim: int,
                  theta: float = 10000.0,
                  table: Optional[RotaryTable] = None):
    """cos/sin tables for integer positions; shapes (..., rotary_dim/2).
    A ``table`` replaces ``theta``'s frequencies and scales both."""
    if table is not None:
        inv_freq = jnp.asarray(table.inv_freq, jnp.float32)
        ang = positions[..., None].astype(jnp.float32) * inv_freq
        return jnp.cos(ang) * table.scale, jnp.sin(ang) * table.scale
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                                / rotary_dim))
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, rd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 rotary_dim: Optional[int] = None) -> jax.Array:
    """Rotate ``x`` (B, S, H, D) half-split style (GPT-NeoX/LLaMA):
    ``x1' = x1·cos − x2·sin``, ``x2' = x2·cos + x1·sin`` over the first
    ``rotary_dim`` channels; the rest pass through."""
    D = x.shape[-1]
    rd = D if rotary_dim is None else rotary_dim
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    cos = cos[:, :, None, :].astype(x.dtype)   # (B, S, 1, rd/2)
    sin = sin[:, :, None, :].astype(x.dtype)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.concatenate([out1, out2], axis=-1)
    if rd < D:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def apply_rotary_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array,
                             rotary_dim: Optional[int] = None) -> jax.Array:
    """GPT-J style ("rotate every two"): channel pairs ``(2i, 2i+1)`` are
    rotated by angle ``i`` (reference rotary kernel's interleaved mode,
    ``apply_rotary_pos_emb.cu`` with ``rotate_every_two``)."""
    D = x.shape[-1]
    rd = D if rotary_dim is None else rotary_dim
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    pairs = x_rot.reshape(*x_rot.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos = cos[:, :, None, :].astype(x.dtype)   # (B, S, 1, rd/2)
    sin = sin[:, :, None, :].astype(x.dtype)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    out = jnp.stack([out1, out2], axis=-1).reshape(*x_rot.shape)
    if rd < D:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def apply_rotary_pos_emb(q: jax.Array, k: jax.Array, positions: jax.Array,
                         rotary_dim: Optional[int] = None,
                         theta: float = 10000.0,
                         interleaved: bool = False,
                         table: Optional[RotaryTable] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """q/k (B, S, H, D); positions (B, S) int."""
    rd = q.shape[-1] if rotary_dim is None else rotary_dim
    cos, sin = rotary_angles(positions, rd, theta, table)
    rot = apply_rotary_interleaved if interleaved else apply_rotary
    return (rot(q, cos, sin, rd), rot(k, cos, sin, rd))


def rows_plan(q: jax.Array, k: jax.Array, head_dim: int, *,
              rotary_dim: Optional[int] = None, interleaved: bool = False,
              decode: bool = False, norm: bool = False) -> Optional[tuple]:
    """Whether q (B, S, H*D) and k (B, S, KV*D) stay rows through their
    rotation (and per-head ``norm``): ``kernel_mesh_plan``'s verdict and
    batch axes where :func:`rotate_rows` takes them, None where the
    ``(B, S, H, D)`` functions do.  Counted, with the guard that decided,
    in ``kernel_dispatch_total{site="qk_rows"}``."""
    from .attention import on_tpu
    from .pallas import qk_rows

    if head_dim % 128:
        reason = f"head_dim {head_dim} is no multiple of 128"
    elif rotary_dim not in (None, head_dim):
        reason = f"rotary_dim {rotary_dim} < head_dim {head_dim}"
    elif interleaved:
        reason = "interleaved pairs"
    elif decode:
        reason = "decode: the cache keeps (B, S, KV, D)"
    elif not on_tpu():
        reason = "no TPU"
    else:
        reason = qk_rows.supported(q.shape[1], q.shape[2], k.shape[2],
                                   q.dtype, norm)
    return _mesh_plan("qk_rows", reason, q.shape[0],
                      f"head_dim {head_dim}, rows {q.shape[2]} + {k.shape[2]}")


def _mesh_plan(site: str, reason: Optional[str], batch: int, rows: str
               ) -> Optional[tuple]:
    """What a row guard ends on: where no ``reason`` has refused the shape,
    ``kernel_mesh_plan``'s verdict and batch axes for ``batch`` rows, or
    None; booked under ``site`` with the guard that decided, or with
    ``rows`` and how the mesh runs them."""
    from .pallas.spmd import kernel_mesh_plan, note_dispatch

    verdict = axes = None
    if reason is None:
        verdict, axes = kernel_mesh_plan(batch)
        if verdict is None:
            reason = "kernel_mesh_plan refused the mesh"
    if reason is not None:
        note_dispatch(site, "xla", reason)
        return None
    note_dispatch(site, "pallas", f"{rows}; " + (
        "one device" if verdict == "direct"
        else f"shard_map over batch axes {axes}"))
    return verdict, axes


def _over_batch(kernel, plan: tuple, args: tuple, outs: int):
    """``kernel(*args)`` under a ``plan``: directly on one device, else a
    ``shard_map`` over the plan's batch axes.  Of ``args`` (None where
    absent) those with the batch's leading dimension are split, the rest -
    scales, a ``(1, S)`` table that serves every row - go to every rank."""
    verdict, axes = plan
    if verdict == "direct":
        return kernel(*args)
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import get_mesh

    rows = P(axes if axes else None, None, None)
    specs = tuple(None if a is None else rows
                  if a.ndim == 3 and a.shape[0] == args[0].shape[0] else P()
                  for a in args)
    return jax.shard_map(kernel, mesh=get_mesh(), in_specs=specs,
                         out_specs=(rows,) * outs if outs > 1 else rows,
                         check_vma=False)(*args)


def row_table(positions: jax.Array, head_dim: int, theta: float = 10000.0,
              table: Optional[RotaryTable] = None) -> jax.Array:
    """``cos || sin``, (B, S, head_dim) float32: a position's angles as the
    row kernels read them (a ``table``'s factor is in both halves)."""
    return jnp.concatenate(rotary_angles(positions, head_dim, theta, table),
                           axis=-1)


def rotate_rows(q: jax.Array, k: jax.Array, positions: Optional[jax.Array],
                head_dim: int, plan: tuple, *, theta: float = 10000.0,
                table: Optional[RotaryTable] = None,
                q_scale: Optional[jax.Array] = None,
                k_scale: Optional[jax.Array] = None, eps: float = 0.0,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """q (B, S, H*D) and k (B, S, KV*D) as rows, under a ``plan`` of
    :func:`rows_plan`: each head normalised under ``q_scale`` / ``k_scale``
    (D,) where given (``models/common.py rms_norm`` over the head), then
    turned half-split by ``positions`` (B, S), or (1, S) for every row,
    where given.  Float32 arithmetic, rounded once."""
    from .pallas.qk_rows import qk_rows

    angles = None if positions is None \
        else row_table(positions, head_dim, theta, table)
    return _over_batch(lambda *a: qk_rows(*a, head_dim, eps, interpret),
                       plan, (q, k, angles, q_scale, k_scale), 2)


def gated_norm_plan(o: jax.Array, head_dim: int) -> Optional[tuple]:
    """Whether a Gated DeltaNet's output ``o`` (B, S, H*D) and its gate stay
    rows through the gated per-head norm: as :func:`rows_plan`, for
    :func:`gated_norm_rows`, counted in
    ``kernel_dispatch_total{site="gated_norm_rows"}``."""
    from .attention import on_tpu
    from .pallas import qk_rows

    if head_dim % 128:
        reason = f"head_dim {head_dim} is no multiple of 128"
    elif not on_tpu():
        reason = "no TPU"
    else:
        reason = qk_rows.gated_norm_supported(o.shape[1], o.shape[2], o.dtype)
    return _mesh_plan("gated_norm_rows", reason, o.shape[0],
                      f"head_dim {head_dim}, rows {o.shape[2]}")


def gated_norm_rows(o: jax.Array, z: jax.Array, w: jax.Array, head_dim: int,
                    plan: tuple, *, eps: float, interpret: bool = False
                    ) -> jax.Array:
    """``rms_norm(o, w, eps) * silu(z)`` over each head of ``head_dim``
    lanes of the rows ``o`` and ``z`` (B, S, H*D), ``w`` (D,), under a
    ``plan`` of :func:`gated_norm_plan` (the Pallas pass
    ``ops/pallas/qk_rows.py gated_norm_rows``, forward and backward).
    Under a plan of :func:`slots_plan` ``o`` holds a head a lane slot, as
    the delta rule's kernels wrote it; ``z`` and the result stay rows.
    Float32 arithmetic, rounded once."""
    from .pallas.qk_rows import gated_norm_rows as kernel

    return _over_batch(lambda *a: kernel(*a, head_dim, eps, interpret),
                       plan, (o, z, w), 1)


def slots_plan(rows: jax.Array, key_heads: int, dk: int, value_heads: int,
               dv: int, chunk: int) -> Optional[tuple]:
    """Whether a Gated DeltaNet layer whose heads are no whole lane tiles
    (``dk`` or ``dv`` no multiple of 128) keeps them in LANE SLOTS from the
    filter's ``rows`` (B, S, 2 Hk dk + Hv dv) to ``out_proj``'s operand:
    :func:`slot_rows` writes the normalised q, k and v into the slots the
    delta rule's kernels read (``ops/pallas/gated_delta.py``), the rule takes
    and returns slots, :func:`gated_norm_rows` reads them.  As
    :func:`rows_plan`; it needs all three, so it is also None where the
    rule would keep XLA's form (``ops/gated_delta.py kernels_refusal``),
    and the ``(B, S, H, d)`` lines run.  Counted in
    ``kernel_dispatch_total`` under both ``site="qk_rows"`` and
    ``site="gated_norm_rows"``."""
    from .gated_delta import kernels_refusal
    from .pallas import qk_rows

    heads = qk_rows.Heads(key_heads, dk, value_heads, dv)
    sk, sv = qk_rows.slot(dk), qk_rows.slot(dv)
    B, S, _ = rows.shape
    rule = kernels_refusal(S, chunk, key_heads, value_heads, dk, dv,
                           rows.dtype)
    reason = f"the delta rule keeps XLA's form: {rule}" if rule else (
        qk_rows.slot_rows_supported(S, heads, rows.dtype)
        or qk_rows.gated_norm_supported(S, value_heads * dv, rows.dtype, dv))
    plan = _mesh_plan(
        "qk_rows", reason, B, f"heads of {dk} and {dv} in slots of {sk} and "
        f"{sv}, rows {heads.width}")
    _mesh_plan("gated_norm_rows", reason, B, f"head_dim {dv} in slots of "
               f"{sv}, rows {value_heads * dv}")
    return plan


def slot_rows(rows: jax.Array, key_heads: int, dk: int, value_heads: int,
              dv: int, plan: tuple, *, eps: float = 1e-6,
              interpret: bool = False
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Of a Gated DeltaNet's filtered rows ``[q | k | v]`` (B, S, 2 Hk dk +
    Hv dv), under a ``plan`` of :func:`slots_plan`: ``q / |q| dk^-1/2`` and
    ``k / |k|`` a head (float32 sums over the head's own channels) as (B, S,
    Hk slot(dk)) and ``v`` as (B, S, Hv slot(dv)), each head from the first
    lane of its slot, zeros behind (the Pallas pass
    ``ops/pallas/qk_rows.py slot_rows``, forward and backward)."""
    from .pallas import qk_rows

    heads = qk_rows.Heads(key_heads, dk, value_heads, dv)
    return _over_batch(lambda x: qk_rows.slot_rows(x, heads, eps, interpret),
                       plan, (rows,), 3)


def rotate_rope_rows(x: jax.Array, positions: jax.Array, rotary_dim: int, *,
                     theta: float = 10000.0,
                     interleaved: bool = False) -> jax.Array:
    """Rotate ``x`` (B, S, n·rotary_dim), the rows a projection writes for
    ``n`` heads of ``rotary_dim`` channels each (n = 1: one key for all
    heads), every channel of a head by its position: pairs ``(2i, 2i+1)``
    when ``interleaved``, ``(i, i + rotary_dim/2)`` otherwise, by
    ``theta^(-2i/rotary_dim)``.  Elementwise on the rows as they lie (a
    channel's partner is one lane roll away), in float32: no ``(B, S, n,
    d/2, 2)`` view is formed, which on the chip is a copy each way.  What
    the latent attention's rope channels take; ``rotate_rows`` is the
    whole-head path."""
    n, half = x.shape[-1] // rotary_dim, rotary_dim // 2
    lane = np.arange(rotary_dim)
    if interleaved:
        shift, first, freq = 1, lane % 2 == 0, lane // 2
    else:
        shift, first, freq = half, lane < half, lane % half
    # one head's table, a channel a lane (no gather: the chip runs one an
    # index at a time), then the same for every head
    inv_freq = jnp.asarray(theta ** (-2.0 * freq / rotary_dim), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv_freq   # (B, S, d)
    cos = jnp.tile(jnp.cos(ang), (1, 1, n))
    sin = jnp.tile(jnp.where(first, -jnp.sin(ang), jnp.sin(ang)), (1, 1, n))
    xf = x.astype(jnp.float32)
    partner = jnp.where(np.tile(first, n), jnp.roll(xf, -shift, axis=-1),
                        jnp.roll(xf, shift, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)
