"""The hyper-connections' passes over a token's ``n`` lanes
(``ops/hyper_connection.py`` has the equations) as row kernels: a pass reads
each lane of the flat stream ``(B, S, n*E)`` from HBM once and writes each
result once.

**Why kernels.**  Differentiated by JAX, the ``jax.numpy`` form is a dozen
float32 fusions a sublayer and pass, each reading the stream again: 98.9 ms
of ``train-xing4-mhc-8k-1chip``'s 490 ms step for 20.4 GB of necessary
traffic (25% of the HBM rate; PERF.md section 6, PR 65).  Four calls take
their place, forward and backward of the two places a sublayer touches the
stream (``E`` = a lane's channels, reads / writes a token):

- :func:`read_call` (custom call ``mhc_read``), ``n E`` / ``E``: the row's
  ``m = rsqrt(mean(x^2) + rms_eps) * (x @ phi)`` and ``u = H_pre @ X`` with
  ``H_pre = sigmoid(a_pre m[:n] + b_pre)`` formed here from the same ``m``.
- :func:`post_call` (``mhc_post``), ``(n + 1) E`` / ``n E``:
  ``X' = H_res @ X + H_post^T y``.
- :func:`post_back_call` (``mhc_post_back``), ``(2n + 1) E`` / ``E``: of
  ``dX'``, ``X`` and ``y`` the cotangent ``dy = H_post dX'`` and the ``n^2 +
  n`` sums a token ``dH_res[j, i] = <dX'_j, X_i>``, ``dH_post[j] = <dX'_j,
  y>``.
- :func:`read_back_call` (``mhc_read_back``), ``(2n + 1) E`` / ``n E``: once
  the sublayer's ``du`` and the maps' ``dm`` exist, the whole ``dX = H_res^T
  dX' + H_pre^T du`` + the product's term ``(r dm) phi^T`` + the statistic's
  ``-r^2 <dm, m> x / (n E)``, ``dm`` completed here by ``H_pre``'s own path
  (``<du, X_i>``, a sum over the row, so this body has two sweeps of its
  block in VMEM).  ``dphi`` is left to XLA as one matmul over ``x`` and the
  ``r dm`` this call returns.

**Numbers a token travel as rows.**  What the kernels take and give beside
the lanes - ``m``, the maps' coefficients, the sums - are ``(B, S, COLS)``
float32: a token a sublane, as its lanes lie.  A coefficient multiplies a
whole lane of its token, so it is needed in every LANE of a vector register:
a block's columns are spread to that form by one product on the MXU, which
idles here, with a 0/1 matrix (:func:`_spread`; the float32 operand in three
bfloat16 parts, exact), into a VMEM scratch the sweeps load from; the sums
over a lane's channels are kept a lane of the register while the sweep runs
and brought to columns by the same matrix at the block's end
(:func:`_lane_sums`).  XLA turns the rows to and from the maps' ``(.., T)``
arrays: 1 MB a call beside the stream's 235.

Blocks are whole rows ``(1, rows, width)``; inside, :data:`GROUP` rows at a
time sweep their lanes :data:`TILE` channels a step, float32 sums, rounded
once.  Every call sits behind one ``jax.jit``: a model traces a body once a
signature, not once a sublayer and remat pass (``mhc_rows_traces_total``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...telemetry import registry as _registry

LANES = 128
# columns of a row of numbers: the maps' n^2 + 2n and, behind them, r
COLS = 32
# rows that sweep their lanes together, and channels of a lane a step of the
# sweep (a step's vectors: GROUP / 8 x TILE / 128 a lane); the read pass,
# whose sweeps hold one sum or n coefficients, takes more rows a group
GROUP = 16
READ_GROUP = 64
TILE = 512
# the most rows a block, and the bytes its double-buffered blocks and scratch
# may take of the VMEM the calls ask for (the v5e has 128 MiB)
MAX_ROWS = 256
_VMEM_BLOCKS = 40 << 20
_VMEM_LIMIT = 64 << 20
_F32 = jnp.float32
# a product over both operands' last dimension
_LAST = (((1,), (1,)), ((), ()))


def numbers(n: int) -> int:
    """``k``: the numbers a token's maps are made from."""
    return n * n + 2 * n


def _row_bytes(kernel: str, n: int, E: int, itemsize: int) -> int:
    """VMEM bytes a row of a block: the lanes' blocks twice (Mosaic double
    buffers), a 128-lane float32 row a small operand, and the scratch."""
    lanes = {"read": n + 1, "post": 2 * n + 1, "post_back": 2 * n + 2,
             "read_back": 3 * n + 1}[kernel]
    spread = {"read": n + 1, "post": n * n + n, "post_back": n * n + 2 * n,
              "read_back": n * n + 2 * n + 1}[kernel]
    product = n * E * 4 if kernel == "read_back" else 0
    return 2 * lanes * E * itemsize + (spread + 10) * LANES * 4 + product


def block_rows(kernel: str, seq: int, n: int, E: int, itemsize: int
               ) -> Optional[int]:
    """Rows a block of ``kernel``: the most that divide ``seq`` within the
    VMEM budget; None where no whole number of groups does."""
    rows = MAX_ROWS
    while rows >= GROUP:
        if seq % rows == 0 and \
                rows * _row_bytes(kernel, n, E, itemsize) <= _VMEM_BLOCKS:
            return rows
        rows //= 2
    return None


def supported(seq: int, n: int, E: int, dtype) -> Optional[str]:
    """``None`` where the four kernels take a stream of ``n`` lanes of ``E``
    channels, else why not."""
    if jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32):
        return f"a stream of {jnp.dtype(dtype).name}"
    if E % LANES:
        return f"lanes of {E} channels are no whole lane tiles"
    if n < 2 or numbers(n) >= COLS:
        return f"{n} lanes: the rows of numbers hold {COLS} columns"
    if seq % GROUP:
        return f"sequence {seq} is no whole number of {GROUP}-row groups"
    if block_rows("read_back", seq, n, E, jnp.dtype(dtype).itemsize) is None:
        return (f"{GROUP} rows of {n} lanes of {E} channels are past the "
                f"blocks' VMEM")
    return None


def _note_trace(kernel: str, *signature) -> None:
    """Count, at trace time, one entry into a kernel's builder."""
    _registry.counter(
        "mhc_rows_traces_total",
        "times a mhc_rows kernel body was traced, by kernel and signature "
        "(once a process, signature and tracing context: the calls sit "
        "behind jax.jit)",
        labelnames=("kernel", "signature")).labels(
            kernel, " ".join(str(s) for s in signature)).inc()


# -- columns to lanes and back, on the MXU ------------------------------------

def _selector(first: int, count: int):
    """``(COLS, count * 128)`` 0/1: column ``first + t`` to every lane of
    tile ``t``."""
    col = lax.broadcasted_iota(jnp.int32, (COLS, count * LANES), 0)
    tile = lax.broadcasted_iota(jnp.int32, (COLS, count * LANES), 1) // LANES
    return (col == tile + first).astype(jnp.bfloat16)


def _parts(x):
    """``x`` float32 as three bfloat16 parts that sum to it (8 bits of
    mantissa each)."""
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(_F32)
        yield part


def _spread(c, first: int, count: int):
    """Columns ``first .. first + count`` of ``c`` (rows, COLS) float32, each
    in every lane of a tile of its own: (rows, count * 128), exact."""
    sel = _selector(first, count)
    return functools.reduce(jnp.add, (
        jnp.dot(p, sel, preferred_element_type=_F32) for p in _parts(c)))


def _lane_sums(acc, first: Optional[int]):
    """Of ``acc`` (rows, count * 128) float32 the sum over each tile's lanes,
    tile ``t``'s in column ``first + t`` of (rows, COLS) and zero elsewhere;
    ``first`` None: one tile, its sum in every column."""
    count = acc.shape[1] // LANES
    sel = jnp.ones((COLS, LANES), jnp.bfloat16) if first is None \
        else _selector(first, count)
    return functools.reduce(jnp.add, (
        lax.dot_general(p, sel, _LAST, preferred_element_type=_F32)
        for p in _parts(acc)))


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _pre_map(m_row, ab_ref, k: int):
    """``(m, H_pre)`` of a block's rows ``[m | r]`` under ``ab`` = the gain
    ``a_pre`` and the biases ``b_pre`` in columns ``< n``, zero behind:
    ``m`` zero from column ``k``, ``H_pre`` in columns ``< n``."""
    col = lax.broadcasted_iota(jnp.int32, m_row.shape, 1)
    m = jnp.where(col < k, m_row, 0.0)
    return m, _sigmoid(ab_ref[0:1, :] * m + ab_ref[1:2, :])


# -- the sweeps ---------------------------------------------------------------

def _tile(E: int) -> int:
    return next(t for t in (TILE, 256, LANES) if t <= TILE and E % t == 0)


def _groups(rows: int, body, group: Optional[int] = None) -> None:
    """``body(r)`` for each group ``r`` of ``group`` (:data:`GROUP`) of a
    block's ``rows``."""
    group = group or GROUP

    def step(g, _):
        body(pl.ds(pl.multiple_of(g * group, group), group))
        return _

    lax.fori_loop(0, rows // group, step, None)


def _sweep(width: int, body, carry=()):
    """``carry = body(at, carry)`` for each 128 channels ``at`` of ``width``,
    :func:`_tile` of them a step of the loop."""
    tile = _tile(width)

    def step(e, carry):
        for s in range(tile // LANES):
            carry = body(pl.multiple_of(e * tile + s * LANES, LANES), carry)
        return carry

    return lax.fori_loop(0, width // tile, step, carry)


def _tiles(ref, r, count: int, first: int = 0) -> list:
    """Group ``r``'s tiles ``first .. first + count`` of a spread scratch."""
    return [ref[r, (first + t) * LANES:(first + t + 1) * LANES]
            for t in range(count)]


def _lane(ref, r, i: int, E: int, at):
    """128 channels from ``at`` of lane ``i`` of group ``r``, float32."""
    return ref[0, r, pl.ds(pl.multiple_of(i * E + at, LANES), LANES)
               ].astype(_F32)


def _dot(coeffs, values):
    out = coeffs[0] * values[0]
    for c, v in zip(coeffs[1:], values[1:]):
        out = out + c * v
    return out


def _read_kernel(x_ref, phi_ref, ab_ref, u_ref, m_ref, bc_ref, acc_ref, *,
                 n: int, rms_eps: float):
    rows, width = x_ref.shape[1:]
    E, k, group = width // n, numbers(n), min(READ_GROUP, rows)
    piece = next(c for c in (2048, 1024, 512, 256, LANES) if width % c == 0)

    def product(c, p):      # x @ phi, a piece of the row at a time
        at = pl.ds(pl.multiple_of(c * piece, LANES), piece)
        return p + lax.dot_general(x_ref[0, :, at], phi_ref[:, at], _LAST,
                                   preferred_element_type=_F32)

    p = lax.fori_loop(0, width // piece, product,
                      jnp.zeros((rows, COLS), _F32))

    def squares(r):
        def add(at, acc):
            v = x_ref[0, r, pl.ds(at, LANES)].astype(_F32)
            return acc + v * v

        acc_ref[r, :] = _sweep(width, add, jnp.zeros((group, LANES), _F32))

    _groups(rows, squares, group)
    inv = lax.rsqrt(_lane_sums(acc_ref[...], None) * (1.0 / width) + rms_eps)
    col = lax.broadcasted_iota(jnp.int32, p.shape, 1)
    m_row = jnp.where(col == k, inv, p * inv)       # phi is zero from row k
    m_ref[0] = m_row
    bc_ref[...] = _spread(_pre_map(m_row, ab_ref, k)[1], 0, n)

    def mix(r):
        h = _tiles(bc_ref, r, n)

        def tile(at, _):
            u = _dot(h, [_lane(x_ref, r, i, E, at) for i in range(n)])
            u_ref[0, r, pl.ds(at, LANES)] = u.astype(u_ref.dtype)
            return _

        _sweep(E, tile)

    _groups(rows, mix, group)


def _post_kernel(x_ref, y_ref, c_ref, out_ref, bc_ref, *, n: int):
    rows, E = y_ref.shape[1:]
    bc_ref[...] = _spread(c_ref[0], 0, n * n + n)

    def write(r):
        c = _tiles(bc_ref, r, n * n + n)

        def tile(at, _):
            values = [_lane(x_ref, r, i, E, at) for i in range(n)] \
                + [_lane(y_ref, r, 0, E, at)]
            for j in range(n):
                out = _dot(c[j * n:(j + 1) * n] + [c[n * n + j]], values)
                out_ref[0, r, pl.ds(pl.multiple_of(j * E + at, LANES), LANES)
                        ] = out.astype(out_ref.dtype)
            return _

        _sweep(E, tile)

    _groups(rows, write)


def _post_back_kernel(g_ref, x_ref, y_ref, c_ref, dy_ref, sums_ref, bc_ref,
                      acc_ref, *, n: int):
    rows, E = y_ref.shape[1:]
    bc_ref[...] = _spread(c_ref[0], n * n, n)

    def back(r):
        h_post = _tiles(bc_ref, r, n)

        def tile(at, acc):
            g = [_lane(g_ref, r, j, E, at) for j in range(n)]
            values = [_lane(x_ref, r, i, E, at) for i in range(n)] \
                + [_lane(y_ref, r, 0, E, at)]
            dy_ref[0, r, pl.ds(at, LANES)] = _dot(h_post, g).astype(
                dy_ref.dtype)
            # dH_res[j, i] in tile j n + i, dH_post[j] in tile n n + j
            order = [(j, i) for j in range(n) for i in range(n)] \
                + [(j, n) for j in range(n)]
            return tuple(a + g[j] * values[i]
                         for a, (j, i) in zip(acc, order))

        acc = _sweep(E, tile, (jnp.zeros((GROUP, LANES), _F32),)
                     * (n * n + n))
        for t, a in enumerate(acc):
            acc_ref[r, t * LANES:(t + 1) * LANES] = a

    _groups(rows, back)
    sums_ref[0] = _lane_sums(acc_ref[...], 0)


def _read_back_kernel(x_ref, g_ref, du_ref, m_ref, dm_ref, c_ref, phi_ref,
                      ab_ref, dx_ref, dp_ref, gh_ref, bc_ref, acc_ref,
                      prod_ref, *, n: int):
    rows, width = x_ref.shape[1:]
    E, k = width // n, numbers(n)
    m_row = m_ref[0]
    col = lax.broadcasted_iota(jnp.int32, m_row.shape, 1)
    inv = jnp.sum(jnp.where(col == k, m_row, 0.0), axis=1, keepdims=True)
    m, h = _pre_map(m_row, ab_ref, k)

    def pre_sums(r):        # <du, X_i>: what u's cotangent leaves on H_pre
        def tile(at, acc):
            du = _lane(du_ref, r, 0, E, at)
            return tuple(a + du * _lane(x_ref, r, i, E, at)
                         for i, a in enumerate(acc))

        acc = _sweep(E, tile, (jnp.zeros((GROUP, LANES), _F32),) * n)
        for t, a in enumerate(acc):
            acc_ref[r, t * LANES:(t + 1) * LANES] = a

    _groups(rows, pre_sums)
    gh = _lane_sums(acc_ref[...], 0) * h * (1.0 - h)    # zero from column n
    gh_ref[0] = gh
    dm = dm_ref[0] + ab_ref[0:1, :] * gh
    dp = inv * dm
    dp_ref[0] = dp
    # the statistic's term: d mean(x^2) = -r^3 / 2 <dm, p>, p = m / r
    scale = -(inv * inv) * jnp.sum(dm * m, axis=1, keepdims=True) \
        * (1.0 / width)
    for i in range(n):
        prod_ref[:, i * E:(i + 1) * E] = jnp.dot(
            dp.astype(phi_ref.dtype), phi_ref[:, i * E:(i + 1) * E],
            preferred_element_type=_F32)
    bc_ref[:, :n * n * LANES] = _spread(c_ref[0], 0, n * n)
    bc_ref[:, n * n * LANES:(n * n + n) * LANES] = _spread(h, 0, n)
    bc_ref[:, (n * n + n) * LANES:] = jnp.broadcast_to(scale, (rows, LANES))

    def back(r):
        res, h_pre = _tiles(bc_ref, r, n * n), _tiles(bc_ref, r, n, n * n)
        s = _tiles(bc_ref, r, 1, n * n + n)[0]

        def tile(at, _):
            g = [_lane(g_ref, r, j, E, at) for j in range(n)]
            du = _lane(du_ref, r, 0, E, at)
            for i in range(n):
                here = pl.ds(pl.multiple_of(i * E + at, LANES), LANES)
                dx = _dot(res[i::n], g) + h_pre[i] * du \
                    + s * _lane(x_ref, r, i, E, at) + prod_ref[r, here]
                dx_ref[0, r, here] = dx.astype(dx_ref.dtype)
            return _

        _sweep(E, tile)

    _groups(rows, back)


# -- the calls ----------------------------------------------------------------

def _call(kernel, name: str, rows: int, operands, whole, outs, scratch,
          flops: int, interpret: bool):
    """One ``pallas_call`` over blocks of ``rows`` rows of every operand and
    result ``(B, S, width)``; the operands at the positions ``whole`` go to
    every step as they are.  ``outs``: ``(width, dtype)`` a result;
    ``scratch``: columns a float32 ``(rows, columns)`` scratch."""
    B, S = operands[0].shape[:2]
    for i, a in enumerate(operands):        # Pallas checks no block index
        if i not in whole and a.shape[:2] != (B, S):
            raise ValueError(f"{name}: operand {i} of {a.shape} beside rows "
                             f"of {(B, S)}")
    block = lambda w: pl.BlockSpec((1, rows, w), lambda b, i: (b, i, 0))
    in_specs = [pl.BlockSpec(a.shape, lambda b, i: (0, 0)) if i in whole
                else block(a.shape[2]) for i, a in enumerate(operands)]
    out_shape = [jax.ShapeDtypeStruct((B, S, w), d) for w, d in outs]
    moved = sum(a.size * a.dtype.itemsize for a in operands) \
        + sum(o.size * o.dtype.itemsize for o in out_shape)
    return pl.pallas_call(
        kernel, grid=(B, S // rows), in_specs=in_specs,
        out_specs=[block(w) for w, _ in outs], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, c), _F32) for c in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(flops=flops, transcendentals=B * S * 8,
                                      bytes_accessed=moved),
        name=name, interpret=interpret)(*operands)


def _lanes_of(x, n: int, *others) -> int:
    E, rem = divmod(x.shape[2], n)
    if rem or any(o.shape[2] != w * E or o.dtype != x.dtype
                  for o, w in others):
        raise ValueError(f"a stream of {x.shape} {x.dtype.name}, {n} lanes, "
                         f"beside {[(o.shape, o.dtype.name) for o, _ in others]}")
    return E


def _rows(kernel: str, x, n: int, E: int) -> int:
    rows = block_rows(kernel, x.shape[1], n, E, x.dtype.itemsize)
    if rows is None:
        raise ValueError(f"mhc_{kernel}: {supported(x.shape[1], n, E, x.dtype)}")
    return rows


@functools.partial(jax.jit, static_argnames=("n", "rms_eps", "interpret"))
def read_call(x, phi_t, ab, *, n: int, rms_eps: float,
              interpret: bool = False):
    """Of the stream ``x`` (B, S, n*E) under ``phi_t`` (COLS, n*E) in the
    stream's dtype (``phi``'s transpose, zero from row ``k``) and ``ab`` (2,
    COLS) float32 (``a_pre`` then ``b_pre`` in columns ``< n``, zero
    behind): ``u`` (B, S, E) and the rows ``[m | r]`` (B, S, COLS) float32."""
    E = _lanes_of(x, n)
    _note_trace("read", x.shape, x.dtype.name, n)
    rows = _rows("read", x, n, E)
    return _call(
        functools.partial(_read_kernel, n=n, rms_eps=rms_eps), "mhc_read",
        rows, (x, phi_t, ab), (1, 2), ((E, x.dtype), (COLS, _F32)),
        (n * LANES, LANES), x.size * (2 * COLS + 6), interpret)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def post_call(x, y, coef, *, n: int, interpret: bool = False):
    """``X' = H_res @ X + H_post^T y`` (B, S, n*E) under the rows ``coef``
    (B, S, COLS) float32: ``H_res[j, i]`` in column ``j n + i``, ``H_post[j]``
    in column ``n n + j``."""
    E = _lanes_of(x, n, (y, 1))
    _note_trace("post", x.shape, x.dtype.name, n)
    rows = _rows("post", x, n, E)
    return _call(functools.partial(_post_kernel, n=n), "mhc_post", rows,
                 (x, y, coef), (), ((n * E, x.dtype),),
                 ((n * n + n) * LANES,), x.size * (2 * n + 2), interpret)[0]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def post_back_call(g, x, y, coef, *, n: int, interpret: bool = False):
    """:func:`post_call`'s cotangents but the stream's: ``dy`` (B, S, E) and
    the rows of sums (B, S, COLS) float32, laid out as ``coef``."""
    E = _lanes_of(x, n, (y, 1), (g, n))
    _note_trace("post_back", x.shape, x.dtype.name, n)
    rows = _rows("post_back", x, n, E)
    return _call(functools.partial(_post_back_kernel, n=n), "mhc_post_back",
                 rows, (g, x, y, coef), (), ((E, x.dtype), (COLS, _F32)),
                 (n * LANES, (n * n + n) * LANES), x.size * (2 * n + 4),
                 interpret)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def read_back_call(x, g, du, m_row, dm_row, coef, phi_t, ab, *, n: int,
                   interpret: bool = False):
    """The stream's whole cotangent (B, S, n*E) of a sublayer: ``g`` is
    ``dX'`` (what :func:`post_call`'s result received), ``du`` and ``dm_row``
    (B, S, COLS; zero from column ``k``) what :func:`read_call`'s results
    received, ``m_row`` its second result, ``coef`` as :func:`post_call`
    took it.  Also ``r dm`` (B, S, COLS), ``dm`` with ``H_pre``'s own path
    added (``dphi = x^T (r dm)``), and ``<du, X_i> H_pre_i (1 - H_pre_i)`` in
    column ``i`` (B, S, COLS): what ``a_pre`` and ``b_pre`` receive through
    ``u``."""
    E = _lanes_of(x, n, (g, n), (du, 1))
    _note_trace("read_back", x.shape, x.dtype.name, n)
    rows = _rows("read_back", x, n, E)
    return _call(
        functools.partial(_read_back_kernel, n=n), "mhc_read_back", rows,
        (x, g, du, m_row, dm_row, coef, phi_t, ab), (6, 7),
        ((n * E, x.dtype), (COLS, _F32), (COLS, _F32)),
        ((n * n + n + 1) * LANES, n * LANES, n * E),
        x.size * (2 * COLS + 2 * n + 8), interpret)
