"""The double-gated short convolution of the LFM2 family (Liquid AI, 2025;
``model_type: lfm2`` / ``lfm2_moe``): a causal depthwise filter of ``L`` taps
a channel between two elementwise gates,

    z   = bg * u
    c_t = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}        (z before 0 is 0)
    y   = cg * c

``bg``, ``cg`` and ``u`` are the three thirds of one projection of the
hidden states, ``(B, S, C)`` each; ``w`` is ``(C, L)`` and its LAST tap
multiplies the current position.  No activation, no bias, nothing carried
from one row of the batch to the next.

Two forms:

- ``"pallas"`` (what ``"auto"`` takes on a TPU where the shape allows):
  ``ops/pallas/short_conv.py``, one pass over the projection's rows forward
  and one backward; its text says why XLA's form is not enough.
- ``"shift"`` (``"auto"`` everywhere else, and what the tests hold the
  kernels to): ``L`` shifted multiply-adds along the sequence axis in plain
  XLA, the backward by autodiff.

At ``(4, 8192, 3 x 2048)`` on the v5e, forward + backward: the kernels 2.30
ms, ``shift`` 9.03, the HBM rate's least 1.80; a depthwise
``lax.conv_general_dilated`` between the gates read 19.84 and is not kept
(my chip run, PR 45, PERF.md section 6).

:func:`short_conv_rows` takes the projection's output whole, ``(B, S, 3C)``
= ``[Bg ; Cg ; u]``, which is what a model has and what the kernels read.
The arithmetic inside is float32 whatever the operands are and the result
has the operands' type.  ``kernel_dispatch_total{site="short_conv"}`` says
which form a call resolved to, and why.

:func:`causal_conv_rows` is the same filter without the gates, with an
activation after it: what a Gated DeltaNet layer (``model_type:
qwen3_next``) runs over the channels of ``[q ; k ; v]`` before its
recurrence.  It has the same two forms behind the same plan (PR 51): the
row kernels ``causal_conv_rows`` / ``causal_conv_rows_back`` where a TPU and
the shape allow, ``shift`` (the backward by autodiff) elsewhere and for the
tests.  At ``(3, 8192, 8192)``, 4 taps, silu, on the v5e, forward +
backward: the kernels 3.88 ms, ``shift`` 13.98, the HBM rate's least for the
five vectors 2.46 (my chip run, PR 51).  The counter's ``pallas`` row of
such a call starts ``ungated`` and gives rows, channels, taps and the
activation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

IMPLS = ("auto", "pallas", "shift")


def _filter_shift(z: jax.Array, w: jax.Array) -> jax.Array:
    """``c_t = sum_j w[:, j] z_{t-(L-1)+j}`` by shifts of ``z`` (B, S, C),
    float32 with float32 taps.  The shifts are taken of ``z`` as it arrived
    and each term is widened on its own: shifted float32 copies of bf16
    ``(4, 8192, 8192)`` rows are 1 GiB apiece where XLA keeps one."""
    f32 = jnp.float32
    S, L = z.shape[1], w.shape[1]
    c = z.astype(f32) * w[:, L - 1]
    for back in range(1, min(L, S)):       # the tap ``back`` positions ago
        shifted = jnp.pad(z[:, :S - back], ((0, 0), (back, 0), (0, 0)))
        c = c + shifted.astype(f32) * w[:, L - 1 - back]
    return c


def _shift(bcu: jax.Array, w: jax.Array) -> jax.Array:
    f32 = jnp.float32
    C = w.shape[0]
    bg, cg, u = (bcu[..., i * C:(i + 1) * C].astype(f32) for i in range(3))
    return (cg * _filter_shift(bg * u, w.astype(f32))).astype(bcu.dtype)


def _rows(kernel, x, w, impl, form, shift):
    """``kernel(x, w)`` on this rank's rows of the batch where the shapes,
    the device and the mesh take the Pallas form (``ops/pallas/spmd.py
    plan``; ``form`` is what its row says of the call; the gated rows are
    three thirds of the taps' channels wide), else ``shift(x, w)``."""
    from .pallas import short_conv as kernels
    from .pallas import spmd

    if impl not in IMPLS:
        raise ValueError(f"short_conv impl {impl!r}: one of {IMPLS}")
    C, L = w.shape
    refusal = "impl='shift' asked for" if impl == "shift" \
        else kernels.supported(x.shape[1], C, L, x.dtype,
                               gated=x.shape[-1] == 3 * C)
    plan = spmd.plan("short_conv", x.shape[0], refusal, form,
                     fallback="shift", tpu=impl == "auto",
                     must=impl == "pallas")
    if plan is None:
        return shift(x, w)
    return spmd.over_batch(kernel, plan, (x, w), whole=(1,))


def short_conv_rows(bcu: jax.Array, w: jax.Array, impl: str = "auto",
                    interpret: bool = False) -> jax.Array:
    """``Cg * filter(Bg * u)`` (B, S, C) of ``bcu`` (B, S, 3C) = ``[Bg ; Cg
    ; u]``, a projection's output as it lies, with the taps ``w`` (C, L);
    see the module's text."""
    from .pallas.short_conv import short_conv_rows as kernel

    if bcu.ndim != 3 or w.ndim != 2 or bcu.shape[-1] != 3 * w.shape[0]:
        raise ValueError(
            f"short_conv_rows takes (B, S, 3C) rows and (C, L) taps, got "
            f"{bcu.shape} and {w.shape}")
    C, L = w.shape
    return _rows(lambda b, w: kernel(b, w, interpret), bcu, w, impl,
                 f"rows {bcu.shape[1]} x 3 x {C}, {L} taps", _shift)


def causal_conv_rows(x: jax.Array, w: jax.Array, activation: str = "silu",
                     impl: str = "auto", interpret: bool = False
                     ) -> jax.Array:
    """``act(c)`` with ``c_t = sum_j w[:, j] x_{t-(L-1)+j}`` of rows ``x``
    (B, S, C) and taps ``w`` (C, L): the plain causal depthwise filter,
    ``x`` before position 0 is 0 and the last tap is the current position.
    ``activation`` is ``"silu"`` or ``None``.  Float32 inside, one rounding
    to ``x``'s type; see the module's text."""
    from .pallas.short_conv import causal_conv_rows as kernel

    if activation not in ("silu", None):
        raise ValueError(f"causal_conv_rows activation {activation!r}: "
                         f"'silu' or None")
    if x.ndim != 3 or w.ndim != 2 or x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"causal_conv_rows takes (B, S, C) rows and (C, L) taps, got "
            f"{x.shape} and {w.shape}")

    def shift(x, w):
        c = _filter_shift(x, w.astype(jnp.float32))
        if activation == "silu":
            c = jax.nn.silu(c)
        return c.astype(x.dtype)

    C, L = w.shape
    return _rows(lambda x, w: kernel(x, w, activation, interpret), x, w, impl,
                 f"ungated, rows {x.shape[1]} x {C}, {L} taps, "
                 f"{activation or 'no activation'}", shift)
