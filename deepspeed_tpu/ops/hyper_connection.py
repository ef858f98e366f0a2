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
``iters`` copies.

The passes over the lanes - the statistic, the product and the mix a sublayer
reads (:func:`maps`, :func:`pre`), the write back (:func:`post`) and their
backward - are what a model calls as :func:`read` and :func:`write`: on a
TPU, where a lane is whole 128-lane tiles, the four row kernels of
``ops/pallas/mhc_rows.py`` under a backward of their own (PR 65: every lane
is read once a pass; ``kernel_dispatch_total{site="mhc_rows"}`` says which
form ran and why), and elsewhere the ``jax.numpy`` form below, which JAX
differentiates and XLA fuses as it likes, and which the kernels are tested
against.  Either way: bf16 lanes in, float32 sums, rounded once, ``x @ phi``
with both operands in the stream's dtype.  Everything on the ``n^2 + 2n``
numbers stays XLA's under both.

Device scopes: ``mhc/maps`` (with the kernels: the read pass and its
backward, ``u`` and the stream's whole cotangent among them), ``mhc/pre``
(the ``jax.numpy`` form only), ``mhc/post``, ``mhc/widen``, ``mhc/collapse``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..telemetry import trace


class Maps(NamedTuple):
    """A sublayer's three maps a token, float32, tokens last (T = B*S)."""
    pre: jax.Array      # (n, T): what the sublayer reads of each lane
    post: jax.Array     # (n, T): what each lane takes of its output
    res: jax.Array      # (n, n, T): res[j, i] of lane i goes to lane j
    # under the row kernels, what :func:`write` needs of :func:`read`: the
    # stream as the kernels' backward follows it, and the calls' _Spec
    carried: Optional[tuple] = None


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
    with trace.device_span("mhc/maps"):
        rows = x.reshape(-1, x.shape[-1])                       # (T, n*E)
        xf = rows.astype(jnp.float32)
        r = jax.lax.rsqrt((xf * xf).mean(-1) + rms_eps)         # (T,)
        m = jnp.einsum("te,ek->kt", rows, phi.astype(x.dtype),
                       preferred_element_type=jnp.float32) * r  # (k, T)
        return _from_numbers(m, gains, biases, n, iters, eps, clamp)


def _from_numbers(m: jax.Array, gains, biases, n: int, iters: int, eps: float,
                  clamp: tuple) -> Maps:
    """The maps of the tokens' numbers ``m`` (n^2 + 2n, T) float32."""
    a_pre, a_post, a_res = (jnp.reshape(a, ()).astype(jnp.float32)
                            for a in gains)
    b_pre, b_post, b_res = (b.astype(jnp.float32) for b in biases)
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


# -- the same passes as row kernels (ops/pallas/mhc_rows.py) ------------------

class _Spec(NamedTuple):
    """What a sublayer's kernel calls are built from, all static."""
    n: int
    iters: int
    eps: float
    clamp: tuple
    rms_eps: float
    plan: tuple         # ops/pallas/spmd.py Plan
    interpret: bool


def _plan(x: jax.Array, n: int) -> Optional[tuple]:
    """``ops/pallas/spmd.py plan``'s verdict for the row kernels on stream
    ``x`` (B, S, n*E), booked in ``kernel_dispatch_total{site="mhc_rows"}``."""
    from .pallas import mhc_rows, spmd

    E = x.shape[-1] // n
    return spmd.plan("mhc_rows", x.shape[0],
                     mhc_rows.supported(x.shape[1], n, E, x.dtype),
                     f"{n} lanes of {E}")


def _rows_of(numbers: jax.Array, like: jax.Array) -> jax.Array:
    """Numbers a token ``(c, T)`` as the kernels take them: rows (B, S, COLS)
    float32 beside the stream ``like``, zero behind column ``c``."""
    from .pallas.mhc_rows import COLS

    rows = jnp.pad(numbers.T, ((0, 0), (0, COLS - numbers.shape[0])))
    return rows.reshape(*like.shape[:2], COLS)


def _numbers_of(rows: jax.Array, count: int) -> jax.Array:
    """:func:`_rows_of` back: the first ``count`` columns as ``(count, T)``."""
    return rows.reshape(-1, rows.shape[-1])[:, :count].T


def _over_batch(call, spec: _Spec, args: tuple, outs: int = 1,
                whole: tuple = ()):
    from .pallas import spmd

    return spmd.over_batch(
        functools.partial(call, n=spec.n, interpret=spec.interpret),
        spec.plan, args, outs=outs, whole=whole)


def _read_pass(x, phi, gains, biases, spec: _Spec):
    """The read kernel: ``u``, the rows ``[m | r]`` and the two small
    operands the backward takes again."""
    from .pallas import mhc_rows

    n, k = spec.n, mhc_rows.numbers(spec.n)
    phi_t = jnp.pad(phi.astype(x.dtype).T, ((0, mhc_rows.COLS - k), (0, 0)))
    ab = jnp.pad(jnp.stack([jnp.broadcast_to(jnp.reshape(gains[0], ()), (n,)),
                            biases[0]]).astype(jnp.float32),
                 ((0, 0), (0, mhc_rows.COLS - n)))
    u, m_row = _over_batch(
        functools.partial(mhc_rows.read_call, rms_eps=spec.rms_eps), spec,
        (x, phi_t, ab), outs=2, whole=(1, 2))
    return u, m_row, phi_t, ab


def _maps_of_rows(m_row, gains, biases, spec: _Spec) -> tuple:
    from .pallas.mhc_rows import numbers

    return tuple(_from_numbers(
        _numbers_of(m_row, numbers(spec.n)), gains, biases, spec.n,
        spec.iters, spec.eps, spec.clamp)[:3])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _read(x, phi, gains, biases, spec: _Spec):
    """``(u, (pre, post, res), x)``: what a sublayer reads, its maps, and the
    stream again for :func:`_write`.  THE PAIR'S BACKWARD IS ONE: the
    cotangent that :func:`_write` hands its stream is ``dX'`` as it came,
    and this function's backward, which holds ``res``, applies ``H_res^T``
    to it inside the kernel that writes the whole ``dX`` - the sublayer's
    own backward lies between the two, so one call cannot hold both."""
    u, m_row, _, _ = _read_pass(x, phi, gains, biases, spec)
    return u, _maps_of_rows(m_row, gains, biases, spec), x


def _read_fwd(x, phi, gains, biases, spec):
    u, m_row, phi_t, ab = _read_pass(x, phi, gains, biases, spec)
    made, back = jax.vjp(
        lambda *a: _maps_of_rows(*a, spec), m_row, gains, biases)
    return (u, made, x), (x, phi, m_row, phi_t, ab, made[2], back)


def _read_bwd(spec, kept, cotangents):
    from .pallas import mhc_rows

    x, phi, m_row, phi_t, ab, res, back = kept
    du, dmaps, g = cotangents
    n, k = spec.n, mhc_rows.numbers(spec.n)
    with trace.device_span("mhc/maps"):
        dm_row, dgains, dbiases = back(dmaps)
        dx, dp, gh = _over_batch(
            mhc_rows.read_back_call, spec,
            (x, g, du, m_row, dm_row, _rows_of(res.reshape(n * n, -1), x),
             phi_t, ab), outs=3, whole=(6, 7))
        dphi = jnp.einsum(
            "te,kt->ek", x.reshape(-1, x.shape[-1]),
            _numbers_of(dp, k).astype(x.dtype),
            preferred_element_type=jnp.float32).astype(phi.dtype)
        # a_pre and b_pre also stand in the kernel's own H_pre
        gh = _numbers_of(gh, n)
        da = (gh * _numbers_of(m_row, n)).sum()
        dgains = (dgains[0] + da.reshape(dgains[0].shape).astype(
            dgains[0].dtype),) + tuple(dgains[1:])
        dbiases = (dbiases[0] + gh.sum(1).astype(dbiases[0].dtype),) \
            + tuple(dbiases[1:])
    return dx, dphi, dgains, dbiases


_read.defvjp(_read_fwd, _read_bwd)


def _coefficients(res, post, like):
    return _rows_of(jnp.concatenate(
        [res.reshape(-1, res.shape[-1]), post], axis=0), like)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _write(x, y, res, post, spec: _Spec):
    """``X' = H_res @ X + H_post^T y`` of the stream ``x`` that :func:`_read`
    returned (see there for what its cotangent is)."""
    from .pallas import mhc_rows

    return _over_batch(mhc_rows.post_call, spec,
                       (x, y, _coefficients(res, post, x)))


def _write_fwd(x, y, res, post, spec):
    return _write(x, y, res, post, spec), (x, y, res, post)


def _write_bwd(spec, kept, g):
    from .pallas import mhc_rows

    x, y, res, post = kept
    n = spec.n
    with trace.device_span("mhc/post"):
        dy, sums = _over_batch(
            mhc_rows.post_back_call, spec,
            (g, x, y, _coefficients(res, post, x)), outs=2)
        sums = _numbers_of(sums, n * n + n)
        return g, dy, sums[:n * n].reshape(res.shape), sums[n * n:]


_write.defvjp(_write_fwd, _write_bwd)


def read(x: jax.Array, phi: jax.Array, gains, biases, *, n: int, iters: int,
         eps: float, clamp: tuple, rms_eps: float, interpret: bool = False):
    """``(u, maps)``: what a sublayer reads of the stream ``x`` (B, S, n*E),
    (B, S, E), and its :class:`Maps` - :func:`maps` and :func:`pre`, as the
    row kernels where :func:`_plan` takes them."""
    plan = _plan(x, n)
    if plan is None:
        made = maps(x, phi, gains, biases, n=n, iters=iters, eps=eps,
                    clamp=clamp, rms_eps=rms_eps)
        return pre(x, made.pre), made
    spec = _Spec(n, iters, eps, tuple(clamp), rms_eps, plan, interpret)
    with trace.device_span("mhc/maps"):
        u, made, carried = _read(x, phi, tuple(gains), tuple(biases), spec)
    return u, Maps(*made, (carried, spec))


def write(x: jax.Array, y: jax.Array, maps: Maps) -> jax.Array:
    """:func:`post` of the stream ``x`` that :func:`read` took and the
    sublayer's output ``y`` under ``maps``, by the form that :func:`read`
    chose."""
    if maps.carried is None:
        return post(x, y, maps.res, maps.post)
    carried, spec = maps.carried
    with trace.device_span("mhc/post"):
        return _write(carried, y, maps.res, maps.post, spec)


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
