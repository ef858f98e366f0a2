"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880 section 4,
over Hyper-Connections, arXiv:2409.19606): a residual stream of ``n`` lanes
that every sublayer reads through a learned mix and writes back through a
learned map beside a doubly stochastic lane-to-lane map.

The stream is carried FLAT, ``(B, S, n*E)``: lane ``i`` is columns ``[i*E,
(i+1)*E)``, the row-major bitcast of ``(B, S, n, E)``.  On the chip a lane
is then whole 128-lane tiles of a row wherever ``E`` is a multiple of 128,
a slice of it costs nothing inside a fusion, and the ``x @ phi`` product
reads the row as it lies; a ``(.., n, E)`` view would put 4 lanes on a
tile's 16 sublanes.

A sublayer, a token's stream ``X`` (n x E), ``x = vec(X)``::

    r = rsqrt(mean(x^2) + rms_eps);  m = r * (x @ phi)       (n^2 + 2n numbers)
    H_pre  = sigmoid(a_pre * m[:n] + b_pre)                   (1 x n)
    H_post = 2 sigmoid(a_post * m[n:2n] + b_post)             (1 x n)
    M      = exp(clip(a_res * mat(m[2n:]) + b_res, lo, hi))   (n x n)
    iters times:  M /= rowsum(M) + eps;  M /= colsum(M) + eps
    u = H_pre @ X;  y = F(u);  X' = M @ X + H_post^T y

Everything on the ``n^2 + 2n`` numbers runs in float32 with TOKENS ON THE
LANE AXIS (``(n, T)`` / ``(n, n, T)``, T = B*S): a ``(T, n, n)`` array pads
64-fold into ``(8, 128)`` tiles.  The sweeps are ONE ``lax.fori_loop`` (a
static trip count, so reverse mode scans it back): forward, a remat's second
forward and backward of every sublayer each hold one loop body, not
``iters`` copies.  The three passes over the lanes (:func:`maps`' statistics
and product, :func:`pre`, :func:`post`) are XLA's fusions: bf16 lanes in,
float32 sums, rounded once.

Device scopes: ``mhc/maps``, ``mhc/pre``, ``mhc/post``, ``mhc/widen``,
``mhc/collapse``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..telemetry import trace


class Maps(NamedTuple):
    """A sublayer's three maps a token, float32, tokens last (T = B*S)."""
    pre: jax.Array      # (n, T): what the sublayer reads of each lane
    post: jax.Array     # (n, T): what each lane takes of its output
    res: jax.Array      # (n, n, T): res[j, i] of lane i goes to lane j


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` sweeps of ``m`` (n, n, T), positive: rows to sum 1, then
    columns."""
    def sweep(_, m):
        m = m / (m.sum(1, keepdims=True) + eps)
        return m / (m.sum(0, keepdims=True) + eps)

    return jax.lax.fori_loop(0, iters, sweep, m)


def maps(x: jax.Array, phi: jax.Array, gains, biases, *, n: int, iters: int,
         eps: float, clamp: tuple, rms_eps: float) -> Maps:
    """The maps of stream ``x`` (B, S, n*E) under ``phi`` (n*E, n^2 + 2n),
    ``gains`` ``(a_pre, a_post, a_res)`` (each one number) and ``biases``
    ``(b_pre (n,), b_post (n,), b_res (n, n))``."""
    a_pre, a_post, a_res = (jnp.reshape(a, ()).astype(jnp.float32)
                            for a in gains)
    b_pre, b_post, b_res = (b.astype(jnp.float32) for b in biases)
    with trace.device_span("mhc/maps"):
        rows = x.reshape(-1, x.shape[-1])                       # (T, n*E)
        xf = rows.astype(jnp.float32)
        r = jax.lax.rsqrt((xf * xf).mean(-1) + rms_eps)         # (T,)
        m = jnp.einsum("te,ek->kt", rows, phi.astype(x.dtype),
                       preferred_element_type=jnp.float32) * r  # (k, T)
        pre = jax.nn.sigmoid(a_pre * m[:n] + b_pre[:, None])
        post = 2.0 * jax.nn.sigmoid(a_post * m[n:2 * n] + b_post[:, None])
        raw = a_res * m[2 * n:].reshape(n, n, -1) + b_res[:, :, None]
        res = sinkhorn(jnp.exp(jnp.clip(raw, *clamp)), iters, eps)
    return Maps(pre, post, res)


def _lanes(x: jax.Array, n: int) -> list:
    E = x.shape[-1] // n
    return [x[..., i * E:(i + 1) * E].astype(jnp.float32) for i in range(n)]


def _column(h: jax.Array, like: jax.Array) -> jax.Array:
    """A map's row ``h`` (T,) beside the rows it weighs: (B, S, 1)."""
    return h.reshape(*like.shape[:-1], 1)


def pre(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """``u = H_pre @ X``: (B, S, E) of stream ``x`` (B, S, n*E)."""
    n = h_pre.shape[0]
    with trace.device_span("mhc/pre"):
        u = sum(_column(h_pre[i], x) * lane
                for i, lane in enumerate(_lanes(x, n)))
        return u.astype(x.dtype)


def post(x: jax.Array, y: jax.Array, h_res: jax.Array,
         h_post: jax.Array) -> jax.Array:
    """``X' = H_res @ X + H_post^T y``: the stream after a sublayer whose
    output is ``y`` (B, S, E)."""
    n = h_post.shape[0]
    with trace.device_span("mhc/post"):
        lanes, yf = _lanes(x, n), y.astype(jnp.float32)
        return jnp.concatenate(
            [(sum(_column(h_res[j, i], x) * lanes[i] for i in range(n))
              + _column(h_post[j], x) * yf).astype(x.dtype)
             for j in range(n)], axis=-1)


def widen(h: jax.Array, n: int) -> jax.Array:
    """A row (B, S, E) copied into all ``n`` lanes."""
    with trace.device_span("mhc/widen"):
        return jnp.tile(h, (1, 1, n))


def collapse(x: jax.Array, n: int) -> jax.Array:
    """The lanes' sum, (B, S, E), in float32 and rounded once."""
    with trace.device_span("mhc/collapse"):
        return sum(_lanes(x, n)).astype(x.dtype)


def gauges(m: Maps) -> dict:
    """What ``record_step_stats`` books of a sublayer's maps: how far
    ``res`` is from doubly stochastic (max over tokens, rows and columns),
    the share of a lane that comes from OTHER lanes (0: the mechanism has
    collapsed to a plain residual), and the two vectors' means."""
    n = m.pre.shape[0]
    err = jnp.maximum(jnp.abs(m.res.sum(1) - 1.0).max(),
                      jnp.abs(m.res.sum(0) - 1.0).max())
    diag = sum(m.res[i, i] for i in range(n))
    return {"mhc_res_marginal_err": err,
            "mhc_res_offdiag": (1.0 - diag / n).mean(),
            "mhc_pre_mean": m.pre.mean(), "mhc_post_mean": m.post.mean()}
