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
(PR 53; ``ops/gated_delta.py normalised_heads``, which also holds that
layer's plans that rotate nothing).  Attention's heads of 64 / 80 / 96
channels keep the ``(B, S, H, D)`` functions: a rotation over a lane slot is
not written.
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
    + 1`` where the entry gives none).  The latent path passes its own:
    the DeepSeek-V2/V3 rule puts ``mscale^2`` on the softmax scale and
    ``mscale / mscale_all_dim`` on cos and sin (:func:`yarn_mscale`,
    ``LlamaConfig.latent_rotary``), not this default."""
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


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 mscale ln factor + 1`` (1 at ``factor <= 1``): the DeepSeek
    family's YaRN magnitude, which its latent attention squares onto the
    softmax scale (``mscale_all_dim``) instead of scaling cos and sin."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


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
    rotation (and per-head ``norm``): ``ops/pallas/spmd.py plan``'s verdict
    and batch axes where :func:`rotate_rows` takes them, None where the
    ``(B, S, H, D)`` functions do.  Counted, with the guard that decided,
    in ``kernel_dispatch_total{site="qk_rows"}``."""
    from .pallas import qk_rows, spmd

    if head_dim % 128:
        refusal = f"head_dim {head_dim} is no multiple of 128"
    elif rotary_dim not in (None, head_dim):
        refusal = f"rotary_dim {rotary_dim} < head_dim {head_dim}"
    elif interleaved:
        refusal = "interleaved pairs"
    elif decode:
        refusal = "decode: the cache keeps (B, S, KV, D)"
    else:
        refusal = qk_rows.supported(q.shape[1], q.shape[2], k.shape[2],
                                    q.dtype, norm)
    return spmd.plan("qk_rows", q.shape[0], refusal, f"head_dim {head_dim}, "
                     f"rows {q.shape[2]} + {k.shape[2]}")


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
    from .pallas import spmd
    from .pallas.qk_rows import qk_rows

    angles = None if positions is None \
        else row_table(positions, head_dim, theta, table)
    # the scales, and a (1, S) table that serves every row, go to every rank
    per_row = angles is None or angles.shape[0] == q.shape[0]
    return spmd.over_batch(
        lambda *a: qk_rows(*a, head_dim, eps, interpret), plan,
        (q, k, angles, q_scale, k_scale), outs=2,
        whole=(3, 4) if per_row else (2, 3, 4))


def rotate_rope_rows(x: jax.Array, positions: jax.Array, rotary_dim: int, *,
                     theta: float = 10000.0, interleaved: bool = False,
                     table: Optional[RotaryTable] = None) -> jax.Array:
    """Rotate ``x`` (B, S, n·rotary_dim), the rows a projection writes for
    ``n`` heads of ``rotary_dim`` channels each (n = 1: one key for all
    heads), every channel of a head by its position: pairs ``(2i, 2i+1)``
    when ``interleaved``, ``(i, i + rotary_dim/2)`` otherwise, by
    ``theta^(-2i/rotary_dim)``, or by a ``table``'s frequencies with its
    factor on cos and sin.  Elementwise on the rows as they lie (a
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
    inv_freq = jnp.asarray(
        theta ** (-2.0 * freq / rotary_dim) if table is None
        else np.asarray(table.inv_freq)[freq], jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv_freq   # (B, S, d)
    scale = 1.0 if table is None else table.scale
    if scale != 1.0:
        ang_cos, ang_sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
        cos = jnp.tile(ang_cos, (1, 1, n))
        sin = jnp.tile(jnp.where(first, -ang_sin, ang_sin), (1, 1, n))
    else:
        cos = jnp.tile(jnp.cos(ang), (1, 1, n))
        sin = jnp.tile(jnp.where(first, -jnp.sin(ang), jnp.sin(ang)),
                       (1, 1, n))
    xf = x.astype(jnp.float32)
    partner = jnp.where(np.tile(first, n), jnp.roll(xf, -shift, axis=-1),
                        jnp.roll(xf, shift, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)
