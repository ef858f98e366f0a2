"""Rotary position embeddings.

Kernel-parity analog of reference
``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu`` (378 LoC CUDA):
rotate the leading ``rotary_dim`` channels of q/k by position-dependent
angles.  One fused XLA computation; supports GPT-NeoX style (half-split)
rotation and partial rotary (``rotary_pct``).
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
