"""The gated delta rule over a sequence: a state a value head, ``S`` (keys
x values, ``dk x dv``), carried ALONG the sequence.  Which family runs
which decay: **one log-decay a head a position**, ``g`` (B, S, Hv) - Gated
DeltaNet (arXiv:2412.06464), the token mixer of three layers in four of
``model_type: qwen3_next`` (states 128 x 128) and ``olmo_hybrid`` (96 x
192), ``models/llama.py GatedDeltaNet``; **one a KEY CHANNEL**, ``g`` (B,
S, Hv, dk) - Kimi Delta Attention (arXiv:2510.26692), five layers in six of
``model_type: bailing_hybrid`` (Ling 3.0; 128 x 128), ``models/llama.py
KimiDeltaAttention``.  One op: the second is the first with ``exp(g_t)``
read as ``Diag(exp(g_t))``, and with every channel of a head equal it IS
the first (``tests/unit/test_gated_delta.py`` holds the two to each
other).  Under a decay a head,

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                                   S_0 = 0 at every row

with ``g_t <= 0`` a log-decay and ``beta_t`` a value head a token, in (0,
1) or, where the caller allows the transition ``I - beta k k^T`` a negative
eigenvalue, in (0, 2): nothing here knows which (``A`` below doubles; the
block solve is exact for either).  ``q`` and ``k`` arrive normalised and
scaled (the caller's); key head ``j`` serves value heads ``j * r .. j * r +
r - 1``, ``r = Hv / Hk``.

**The chunked form** (what runs; the per-token recurrence above is
``benchmark/reference/qwen3next.py``'s and the tests').  In a chunk of
``C`` positions, ``gamma_i = sum_{j<=i} g_j``, ``G_ij = exp(gamma_i -
gamma_j)`` for i >= j (all <= 1), ``S`` the state entering the chunk:

    A  = strict_lower(diag(beta) (K K^T * G))
    [U | W] = (I + A)^-1 diag(beta) [V | K * exp(gamma)]
    V' = U - W S
    O  = (Q * exp(gamma)) S + lower_incl(Q K^T * G) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

``A``, ``U``, ``W`` and ``Q K^T`` depend on no state; the last three lines
are sequential over the chunks of a row.  ``(I + A)^-1`` is never a product
over the whole chunk: ``I + A`` is unit lower triangular and the system is
solved by block forward substitution over 16-row blocks (:func:`_solve_unit_lower`:
rows inside a block by its nilpotent product, blocks by products), which is
exact for any keys; the product form ``(I - A)(I + A^2)(I + A^4)...`` over
the whole ``A`` is the same matrix in exact arithmetic and cancels
catastrophically in float32 once keys correlate (powers of a 64 x 64 ``A``
with entries near 1 reach 1e18).  Decays, ``gamma``, the solve and ``S``
are float32; the operands of the large products have the type the call
arrived in (float32 accumulation).

**Under a decay a key channel** ``gamma`` is a vector a position and
``K K^T * G`` is no product masked by a ``C x C`` matrix any more: ``A_ij =
beta_i sum_c k_ic k_jc exp(gamma_ic - gamma_jc)``, i.e. ``(K exp(gamma)) (K
exp(-gamma))^T`` - whose second factor overflows float32 within a chunk
(``gamma`` reaches ``-5 x 64``).  :func:`_channel_products` forms it, and
``Q K^T * G`` beside it, a block of :data:`SOLVE_BLOCK` = 16 rows at a time
against that block's FIRST row ``r``: ``exp(gamma_i - gamma_r) <= 1`` on
the rows' side, ``exp(gamma_r - gamma_j)`` on the keys' (at most 1 for the
keys of earlier blocks, at most ``exp(16 x 5)`` inside the block: the
caller's gate is bounded below, ``LlamaConfig.kda_lower_bound`` >= -5.5).
Everything else is the text above with ``exp(gamma)`` a ``(C, dk)`` array:
``W = ... K * exp(gamma)``, ``Q * exp(gamma)``, ``K * exp(gamma_C - gamma)``
(all factors <= 1; what underflows is a decay of ``e^-87`` and contributes
nothing), ``S <- Diag(exp(gamma_C)) S + ...``.  Since PR 60 it has kernels
of its own (``ops/pallas/gated_delta.py channel_forward`` /
``channel_backward``, HLO custom calls ``gated_delta_channel_fwd`` /
``gated_delta_channel_bwd``): separate bodies that share the solve and
nothing of the data path with a decay a head's, chosen by ``g.ndim`` and by
a guard of their own (``channel_supported``: bf16, chunks of 32 / 64 / 128
in whole groups of four, heads whose ``dk`` and ``dv`` are whole tiles of
128 lanes, the VMEM the resident state, the saved blocks and the ``(C,
dk)`` float32 tiles take).  XLA then makes ``gamma`` as float32 ROWS ``(B,
S, Hv dk)`` beside ``k`` (the cumulative sum a chunk) and ``beta`` as ``(B,
Hk, 8, S)``, and turns ``dgamma`` into ``dg`` (the reverse sum);
:func:`_prepare` and :func:`_channel_products` run zero times.  Where the
guard refuses, XLA's form runs and
``kernel_dispatch_total{site="gated_delta", impl="xla"}`` says ``a decay a
key channel (H heads x dk): <the guard's reason>``; the kernels' reason
ends ``..., a decay a key channel, fused``; the gauge
``gated_delta_decay_channels`` is 1 or ``dk`` by which form a traced pass
ran.  At ``(1, 8192, 32 heads of 128 x 128)`` on the v5e, wall a layer-row:
XLA's form 20.2 ms forward and 52.0 forward + backward, the kernels 6.2
forward and about 19.8 forward + backward (13.6 of it the backward call
alone: the states' walk and the walk back; my chip run, PR 60).

One ``custom_vjp``: the forward keeps its five inputs and nothing else, so
a block's ``dots_saveable`` policy sees none of the inner products; the
backward walks the forward again and then back, which keeps the per-chunk
states for the length of a row's backward (``N x Hv x dk x dv`` float32:
128 x 32 x 64 KB = 256 MiB at S 8192).  Both passes walk the batch a row at a
time (``lax.map``).

``impl``: ``"xla"`` is the above as XLA's own program, on every backend and
what the tests hold the kernels to: :func:`_prepare` makes what depends on
no state for all chunks at once as batched products (``U``, ``W``, ``P``,
the decayed ``q`` and ``k`` through HBM), :func:`_scan_xla` is a
``lax.scan`` over the chunks, the backward is ``jax.vjp`` of both.
``"pallas"`` (what ``"auto"`` takes on a TPU where the operands allow: bf16,
chunks of 32 / 64 / 128 in whole groups of four, at most four value heads a
key head, a mesh that ``kernel_mesh_plan`` takes; heads of any width, those
that are no multiple of 128 channels in lane slots of the next one, zeros
behind them: padded and cut here by XLA, or - ``slots=(dk, dv)``, what
``models/llama.py GatedDeltaNet`` does since PR 55 - written so by the
caller's row kernel and handed on so to the next, ``o`` and the cotangents
in the same slots and nothing padded or cut inside the row loop) hands the
WHOLE chunked form to
``ops/pallas/gated_delta.py`` (HLO custom calls ``gated_delta_fwd`` /
``gated_delta_bwd``, PR 49): a grid step reads ``q``, ``k``, ``v`` in the
layout the layer wrote, makes ``A``, the inverse, ``U``, ``W``, ``P`` and
the decayed ``q`` and ``k`` in VMEM and scans, the states resident across
the chunk axis; the backward runs ``gated_delta_fwd`` once more for the
state entering each chunk, then ``gated_delta_bwd`` recomputes a chunk's
preparation beside the transposed scan and transposes it in place.  XLA
then prepares ``gamma`` (the cumulative sum of ``g`` a chunk) and ``beta``
as ``(B, Hk, 8, S)`` float32 and turns ``dgamma`` into ``dg``, nothing
else: :func:`_prepare` runs zero times.  At ``(4, 8192, 32 heads of 128)``
on the v5e the scan as XLA's while loop read 93.6 ms forward and 211 forward
+ backward a layer (my chip run, PR 48; PERF.md section 6 has the
kernels').  ``kernel_dispatch_total{site="gated_delta"}`` says what a call
resolved to and why (the kernels' reason names the tile: ``128 chunks of 64
x 16 key heads x 2 value heads of 128, fused; one device``, or ``... x 30
key heads of 96 x 1 value heads of 192, ...``);
``gated_delta_chunks_total{pass}`` counts, at trace time, the chunks of one
head-sequence a traced pass walks in sequence: ``fwd`` N, ``bwd`` 2 N (the
forward's walk again, then the walk back), under either ``impl``;
``gated_delta_state_elems{dk, dv}`` is ``dk * dv`` of the rule a traced
pass ran, so that a snapshot says which shape of state ran (the heads' own
widths, 96 and 192, also where the operands arrive in slots of 128 and
256).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import registry as _registry

IMPLS = ("auto", "pallas", "xla")
_HI = lax.Precision.HIGHEST
SOLVE_BLOCK = 16


def _note_chunks(pass_: str, n: int) -> None:
    _registry.counter(
        "gated_delta_chunks_total",
        "chunks of one head-sequence that a traced pass of the gated delta "
        "rule walks in sequence (counted at trace time, not per call)",
        labelnames=("pass",)).labels(pass_).inc(n)


def _note_state(dk: int, dv: int, decay_channels: int) -> None:
    _registry.gauge(
        "gated_delta_decay_channels",
        "log-decays a head a position of the gated delta rule that a traced "
        "pass ran: 1 (a decay a head: Gated DeltaNet) or the key head's "
        "channels (a decay a key channel: Kimi Delta Attention; set at trace "
        "time)").set(decay_channels)
    _registry.gauge(
        "gated_delta_state_elems",
        "elements of one state (keys x values) of the gated delta rule that "
        "a traced pass ran, by the key and value heads' channels (set at "
        "trace time)", labelnames=("dk", "dv")).labels(
            str(dk), str(dv)).set(dk * dv)


def _solve_unit_lower(a: jax.Array, rhs: jax.Array) -> jax.Array:
    """``x`` with ``(I + a) x = rhs``: ``a`` (..., C, C) strictly lower
    triangular, ``rhs`` (..., C, n), float32.  Block forward substitution
    over blocks of :data:`SOLVE_BLOCK` rows: block row ``i`` of ``x`` from
    the block rows before it by products, through the inverse of its own
    diagonal block ``I + D``.  ``D`` is strictly lower triangular, so
    ``D^16 = 0`` and ``(I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8)``
    exactly: three squarings of 16 x 16 matrices in float32, whose largest
    power's entries stay under ``C(15, 7) = 6435`` for any keys (the same
    product over a whole 64 x 64 ``a`` would reach 1e18 and cancel to
    nothing)."""
    C = a.shape[-1]
    b = min(SOLVE_BLOCK, C)
    assert C % b == 0, (C, b)
    nb = C // b

    def mm(x, y):
        return jnp.einsum("...ij,...jn->...in", x, y, precision=_HI)

    # the diagonal blocks together, (..., nb, b, b)
    diag = jnp.stack([a[..., i * b:(i + 1) * b, i * b:(i + 1) * b]
                      for i in range(nb)], axis=-3)
    eye = jnp.eye(b, dtype=a.dtype)
    inv, power = eye - diag, mm(diag, diag)
    for _ in range(max(b - 1, 1).bit_length() - 1):     # D^2, D^4, D^8
        inv, power = mm(inv, eye + power), mm(power, power)
    xs = []
    for i in range(nb):
        lo, hi = i * b, (i + 1) * b
        r_i = rhs[..., lo:hi, :]
        if i:
            r_i = r_i - mm(a[..., lo:hi, :lo], jnp.concatenate(xs, axis=-2))
        xs.append(mm(inv[..., i, :, :], r_i))
    return jnp.concatenate(xs, axis=-2)


def _channel_products(q_, k_, gamma, cdt):
    """``sum_c x_ic k_jc exp(gamma_ic - gamma_jc)`` for ``x`` = ``k_`` and
    ``q_`` (B, Hv, N, C, dk) under a decay a key channel, ``gamma`` (B, Hv,
    N, C, dk) float32: two (B, Hv, N, C, C) float32, right where ``i >= j``
    (above the diagonal they hold finite numbers that the caller masks).
    A block of :data:`SOLVE_BLOCK` rows ``i`` is formed against its OWN
    first row ``r``, ``exp(gamma_i - gamma_r)`` on its side and
    ``exp(gamma_r - gamma_j)`` on the keys' (the module's text has why), so
    a log-decay below ``-88 / 16 = -5.5`` a position is not this op's."""
    f32 = jnp.float32
    C = gamma.shape[-2]
    b = min(SOLVE_BLOCK, C)
    kk, qk = [], []
    for lo in range(0, C, b):
        hi = lo + b
        ref = gamma[..., lo:lo + 1, :]
        near = jnp.exp(gamma[..., lo:hi, :] - ref)
        far = (k_[..., :hi, :].astype(f32)
               * jnp.exp(ref - gamma[..., :hi, :])).astype(cdt)
        for x, rows in ((k_, kk), (q_, qk)):
            prod = jnp.einsum(
                "bhnid,bhnjd->bhnij",
                (x[..., lo:hi, :].astype(f32) * near).astype(cdt), far,
                preferred_element_type=f32)
            rows.append(jnp.pad(prod, [(0, 0)] * 4 + [(0, C - hi)]))
    return jnp.concatenate(kk, axis=-2), jnp.concatenate(qk, axis=-2)


def _prepare(q, k, v, g, beta, chunk: int, key_heads=None):
    """What of the chunked form depends on no state, for every chunk at
    once: ``(u, w, p, qg, kd, g_last)`` as ``(B, Hv, N, C, .)``, ``u`` and
    ``g_last`` float32, the others in ``v``'s type: ``U``, ``W``,
    ``lower_incl(Q K^T * G)``, ``Q * exp(gamma)``, ``K * exp(gamma_C -
    gamma)`` and ``exp(gamma_C)`` of the module's text; ``g_last`` is ``(B,
    Hv, N)`` under a decay a head and ``(B, Hv, N, dk)`` under a decay a
    key channel (``g`` (B, S, Hv, dk))."""
    f32 = jnp.float32
    B, S, Hv = g.shape[:3]
    dv = v.shape[-1] // Hv
    Hk = key_heads or k.shape[-1] // dv
    C, N = chunk, S // chunk
    cdt = v.dtype                   # operands of the large products

    def heads(x, H):                # (B, S, H*d) -> (B, H, N, C, d)
        return x.reshape(B, N, C, H, -1).transpose(0, 3, 1, 2, 4)

    def per_head(x):                # (B, S, Hv) -> (B, Hv, N, C) float32
        return x.astype(f32).reshape(B, N, C, Hv).transpose(0, 3, 1, 2)

    r = Hv // Hk
    q_, k_ = (jnp.repeat(heads(x, Hk), r, axis=1) if r > 1 else heads(x, Hk)
              for x in (q, k))
    v_ = heads(v, Hv)
    beta_ = per_head(beta)
    if g.ndim == 4:                 # a decay a key channel
        gamma = jnp.cumsum(heads(g.astype(f32).reshape(B, S, -1), Hv),
                           axis=-2)
        idx = jnp.arange(C)
        kk, qk = _channel_products(q_, k_, gamma, cdt)
        a = jnp.where(idx[:, None] > idx[None, :], beta_[..., None] * kk, 0.0)
        e_gamma = jnp.exp(gamma)

        # lower_incl(Q K^T * G), the decays to the chunk's end, the whole's
        rest = (lambda: jnp.where(idx[:, None] >= idx[None, :], qk, 0.0),
                lambda: jnp.exp(gamma[..., -1:, :] - gamma),
                lambda: jnp.exp(gamma[..., -1, :]))
    else:                           # a decay a head
        gamma = jnp.cumsum(per_head(g), axis=-1)
        idx = jnp.arange(C)
        lower = idx[:, None] >= idx[None, :]
        # exp of a masked difference: above the diagonal the difference is
        # positive and could overflow before a mask multiplies it away
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        kk = jnp.einsum("bhnid,bhnjd->bhnij", k_, k_,
                        preferred_element_type=f32)
        a = jnp.where(idx[:, None] > idx[None, :],
                      beta_[..., None] * kk * decay, 0.0)
        e_gamma = jnp.exp(gamma)[..., None]

        rest = (lambda: jnp.einsum("bhnid,bhnjd->bhnij", q_, k_,
                                   preferred_element_type=f32) * decay,
                lambda: jnp.exp(gamma[..., -1:] - gamma)[..., None],
                lambda: jnp.exp(gamma[..., -1]))
    rhs = jnp.concatenate([v_.astype(f32), k_.astype(f32) * e_gamma],
                          axis=-1) * beta_[..., None]
    uw = _solve_unit_lower(a, rhs)
    u, w = uw[..., :dv], uw[..., dv:].astype(cdt)
    # (thunks: each is traced where the decay a head's form always stood)
    p, to_end, g_last = rest
    p = p().astype(cdt)
    qg = (q_.astype(f32) * e_gamma).astype(cdt)
    kd = (k_.astype(f32) * to_end()).astype(cdt)
    return u, w, p, qg, kd, g_last()


def _scan_xla(u, w, p, qg, kd, g_last):
    """The sequential part, ``lax.scan`` over the chunk axis: ``o`` (B, Hv,
    N, C, dv) in ``w``'s type."""
    f32 = jnp.float32
    cdt = w.dtype

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y.astype(cdt), preferred_element_type=f32)

    def step(state, xs):            # state (B, Hv, dk, dv) float32
        u_n, w_n, p_n, qg_n, kd_n, gl_n = xs
        v_new = u_n - mm("bhcd,bhde->bhce", w_n, state)
        o_n = mm("bhcd,bhde->bhce", qg_n, state) \
            + mm("bhic,bhce->bhie", p_n, v_new)
        # a decay a head (B, Hv), or a key channel (B, Hv, dk)
        state = (gl_n[..., None, None] if gl_n.ndim == 2
                 else gl_n[..., None]) * state \
            + mm("bhcd,bhce->bhde", kd_n, v_new)
        return state, o_n.astype(cdt)

    B, Hv, _, _, dv = u.shape
    chunks = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, p, qg, kd, g_last))
    _, o = lax.scan(step, jnp.zeros((B, Hv, w.shape[-1], dv), f32), chunks)
    return jnp.moveaxis(o, 0, 2)


def _chunked(q, k, v, g, beta, chunk: int, key_heads=None):
    """The module's chunked form by XLA; shapes as :func:`gated_delta_rule`."""
    B, S = g.shape[:2]
    o = _scan_xla(*_prepare(q, k, v, g, beta, chunk, key_heads))
    return o.transpose(0, 2, 3, 1, 4).reshape(B, S, v.shape[-1])


def _row(chunk: int, fused, key_heads=None):
    """The chunked form of one row of the batch, without the batch axis:
    XLA's where ``fused`` is None, else the kernels' (``fused`` their
    ``interpret``)."""
    if fused is None:
        return lambda *xs: _chunked(*(x[None] for x in xs), chunk,
                                    key_heads)[0]
    return lambda *xs: _kernels(xs[3])[0](
        *(x[None] for x in xs), chunk=chunk, key_heads=key_heads,
        interpret=fused)[0]


def _kernels(g):
    """``(forward, backward)`` of ``ops/pallas/gated_delta.py`` for one
    row's ``g``: a decay a head's bodies, (S, Hv), or a key channel's, (S,
    Hv, dk), by what ``g`` says."""
    from .pallas import gated_delta as kernel

    return (kernel.channel_forward, kernel.channel_backward) \
        if g.ndim == 3 else (kernel.forward, kernel.backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, chunk, fused, key_heads=None):
    """A row of the batch at a time (``lax.map``), forward and backward: a
    row's heads and chunks fill the chip, and what the form keeps between
    its products - and, in the backward, for its transposition - is one
    row's and not the batch's (a quarter at four rows: 3 GiB less of a
    step's peak).  The backward maps ``vjp`` itself: the transpose of a
    mapped forward would keep every row's residuals stacked."""
    _note_chunks("fwd", g.shape[1] // chunk)
    return lax.map(lambda xs: _row(chunk, fused, key_heads)(*xs),
                   (q, k, v, g, beta))


def _rule_fwd(q, k, v, g, beta, chunk, fused, key_heads=None):
    return (_rule(q, k, v, g, beta, chunk, fused, key_heads),
            (q, k, v, g, beta))


def _rule_bwd(chunk, fused, key_heads, res, do):
    # the forward's walk again (XLA: under ``jax.vjp``; the kernels: for the
    # states entering the chunks), then the walk back
    _note_chunks("bwd", 2 * (res[3].shape[1] // chunk))

    def one(xs):
        if fused is not None:
            return tuple(x[0] for x in _kernels(xs[3])[1](
                *(x[None] for x in xs), chunk=chunk, key_heads=key_heads,
                interpret=fused))
        _, pull = jax.vjp(_row(chunk, None, key_heads), *xs[:-1])
        return pull(xs[-1])

    return lax.map(one, (*res, do))


_rule.defvjp(_rule_fwd, _rule_bwd)


def kernels_refusal(S: int, chunk: int, Hk: int, Hv: int, dk: int, dv: int,
                    dtype, channels: bool = False) -> Optional[str]:
    """``None`` where the kernels take ``Hk`` key heads of ``dk`` and ``Hv``
    value heads of ``dv`` channels over ``S`` positions (the device and the
    mesh apart: ``ops/pallas/spmd.py plan`` asks those), under a decay a
    head or, ``channels``, a key channel (whose reason then names the
    form); else why XLA's form runs."""
    from .pallas import gated_delta as kernel

    if S % chunk:
        return f"rows of {S} positions are no whole chunks of {chunk}"
    if not channels:
        return kernel.supported(S // chunk, chunk, dk, dv, dtype, Hv // Hk)
    refusal = kernel.channel_supported(S // chunk, chunk, dk, dv, dtype,
                                       Hv // Hk)
    return refusal and (f"a decay a key channel ({Hv} heads x {dk}): "
                        f"{refusal}")


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, *, chunk: int = 64,
                     key_heads: int = None, impl: str = "auto",
                     interpret: bool = False) -> jax.Array:
    """``o`` (B, S, Hv*dv) of the gated delta rule: ``q``, ``k`` (B, S,
    Hk*dk) normalised and scaled by the caller, ``v`` (B, S, Hv*dv), ``g``
    float32 log-decays (<= 0): (B, S, Hv), one a head, or (B, S, Hv, dk),
    one a key channel (then at least -5.5 each: the module's text),
    ``beta`` (B, S, Hv), as a layer's projections and filter wrote them.
    ``key_heads`` is ``Hk``; None where
    key and value heads are as wide (``dk = dv``), which then says it.
    Each row of the batch starts from a zero state; ``S`` is a multiple of
    ``chunk``.  See the module's text."""
    return _dispatch(q, k, v, g, beta, chunk, key_heads, impl, interpret)


def _dispatch(q, k, v, g, beta, chunk, key_heads, impl, interpret,
              slots: Optional[Tuple[int, int]] = None):
    """:func:`gated_delta_rule`; under ``slots = (dk, dv)`` the operands
    hold each head in the kernels' lane slot already (``q``, ``k`` (B, S,
    Hk slot(dk)), ``v`` (B, S, Hv slot(dv)), a head's own ``dk`` / ``dv``
    channels from the slot's first lane, zeros behind) and ``o`` comes back
    so; only the kernels take them (:func:`slots_plan` asks beforehand),
    the widths are named for the dispatch reason and the gauge."""
    from .pallas import spmd
    from .pallas.gated_delta import _slot

    if impl not in IMPLS:
        raise ValueError(f"gated_delta impl {impl!r}: one of {IMPLS}")
    B, S, Hv = beta.shape if beta.ndim == 3 else (0, 0, 1)
    if g.ndim not in (3, 4) or g.shape[:3] != (B, S, Hv) or v.ndim != 3 \
            or v.shape[-1] % Hv or q.shape != k.shape \
            or k.shape[-1] % (key_heads or v.shape[-1] // Hv) \
            or q.shape[:2] != (B, S) or v.shape[:2] != (B, S):
        raise ValueError(
            f"gated_delta_rule takes q, k (B, S, Hk*dk), v (B, S, Hv*dv), g "
            f"(B, S, Hv) or (B, S, Hv, dk) and beta (B, S, Hv), got "
            f"{q.shape}, {k.shape}, {v.shape}, {g.shape}, {beta.shape} at "
            f"key_heads {key_heads}")
    Hk = key_heads or k.shape[-1] // (v.shape[-1] // Hv)
    if Hv % Hk:
        raise ValueError(f"{Hv} value heads are no multiple of {Hk} key "
                         f"heads")
    if S % chunk:
        raise ValueError(f"rows of {S} positions are no whole chunks of "
                         f"{chunk}")
    dk, dv = slots or (k.shape[-1] // Hk, v.shape[-1] // Hv)
    if (k.shape[-1], v.shape[-1]) != ((Hk * _slot(dk), Hv * _slot(dv))
                                      if slots else (Hk * dk, Hv * dv)):
        raise ValueError(f"k {k.shape} and v {v.shape} are no {Hk} and {Hv} "
                         f"lane slots of heads of {slots} channels")
    of = "" if dk == dv else f"of {dk} "
    channels = g.ndim == 4
    if channels and g.shape[3] != dk:
        raise ValueError(f"a decay a key channel is (B, S, Hv, dk = {dk}), "
                         f"got g {g.shape}")
    plan = spmd.plan(
        "gated_delta", B, "impl='xla' asked for" if impl == "xla"
        else kernels_refusal(S, chunk, Hk, Hv, dk, dv, v.dtype, channels),
        f"{S // chunk} chunks of {chunk} x {Hk} key heads {of}x {Hv // Hk} "
        f"value heads of {dv}, " + ("a decay a key channel, " if channels
                                    else "") + "fused",
        tpu=impl == "auto", must=impl == "pallas")
    if slots and plan is None:
        raise NotImplementedError(
            "gated_delta_rule: slotted operands are the kernels' layout, "
            "and XLA's form runs")
    _note_state(dk, dv, dk if channels else 1)
    fused = interpret if plan is not None else None
    # under the name a ``+flash`` remat policy keeps (``models/common.py
    # resolve_remat_policy``): the output is no dot output, and recomputed it
    # is the whole chunked form a second time (a seventh of a step at the
    # eighth cell's shape) for 8 KB a token a layer kept
    return checkpoint_name(spmd.over_batch(
        lambda *args: _rule(*args, chunk, fused, Hk),
        plan or spmd.Plan("direct"),
        (q, k, v, g.astype(jnp.float32), beta)), "gated_delta_out")


# -- a layer's heads around the rule ----------------------------------------
#
# Both per-head norms of a Gated DeltaNet layer stand between kernels that
# read and write ``(B, S, H d)`` rows (the filter before, the rule between,
# ``out_proj`` after), and on the chip the ``(B, S, H, d)`` float32 view they
# are written on is no bitcast of such rows: each cost a copy in and a
# reshape out, forward, recomputation and backward.  Which of three layouts
# a layer's heads take is read here from the shapes, the device and the
# mesh, never set:
#
# - a head is whole lane tiles (128 x 128, Qwen3-Next; PR 53): q and k are
#   normalised on the rows by ``ops/rotary.py rotate_rows`` under constant
#   scales and no positions, the gated norm by :func:`gated_norm_rows`;
# - it is not (96 x 192, Olmo-Hybrid; PR 55): the rule's kernels read each
#   head from the first lane of a slot of the next multiple of 128 lanes,
#   zeros behind it, and those slots are the layout from the filter's output
#   to ``out_proj``'s operand (:func:`slots_plan`, one guard for all three):
#   :func:`slot_rows` writes the normalised q and k and v into slots, the
#   rule takes them and hands ``o`` back in slots (what a ``+flash`` remat
#   policy keeps a layer is that slotted ``o``), :func:`gated_norm_rows`
#   reads it beside the gate as ``in_proj`` wrote it.  No pad, cut or 4-D
#   view is left around the rule;
# - the CPU, a mesh that refuses and a rule that keeps XLA's form run the
#   ``(B, S, H, d)`` float32 lines.
#
# ``kernel_dispatch_total{site="qk_rows" | "gated_norm_rows"}`` says which,
# and why.

class MixerHeads(NamedTuple):
    """What :func:`normalised_heads` made of a layer's filtered rows, for
    :func:`heads_rule` and :func:`gated_norm`."""
    q: jax.Array                    # normalised and scaled, as they lie:
    k: jax.Array                    # rows, slots or (B, S, Hk, dk)
    v: Optional[jax.Array]          # in slots, or None: the rows' own
    rows: jax.Array                 # [q | k | v] as the filter wrote them
    widths: Tuple[int, int, int, int]       # Hk, dk, Hv, dv
    chunk: int
    plan: Optional[tuple]           # of slots_plan where the slots run


def normalised_heads(rows: jax.Array, key_heads: int, dk: int,
                     value_heads: int, dv: int, chunk: int, *,
                     interpret: bool = False) -> MixerHeads:
    """Of a Gated DeltaNet's filtered rows ``[q | k | v]`` (B, S, 2 Hk dk +
    Hv dv): ``q / |q| dk^-1/2`` and ``k / |k|`` a head (eps 1e-6, float32
    sums), in the layout the rule will read them in."""
    from .rotary import rotate_rows, rows_plan

    f32 = jnp.float32
    B, S, _ = rows.shape
    Hk = key_heads
    whole = not (dk % 128 or dv % 128)
    made = functools.partial(MixerHeads, rows=rows, chunk=chunk,
                             widths=(Hk, dk, value_heads, dv))

    def unit(t):                    # each head's channels to length 1
        t = t.astype(f32).reshape(B, S, Hk, dk)
        return t * lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    if not whole:
        plan = slots_plan(rows, Hk, dk, value_heads, dv, chunk)
        if plan is not None:
            return made(*slot_rows(rows, Hk, dk, value_heads, dv, plan,
                                   interpret=interpret), plan=plan)
    else:
        one = jax.ShapeDtypeStruct((B, S, Hk * dk), rows.dtype)
        plan = rows_plan(one, one, dk, norm=True)
        if plan is not None:
            # x / |x| = rms_norm(x, dk^-1/2, eps / dk), on the rows
            return made(*rotate_rows(
                rows[..., :Hk * dk], rows[..., Hk * dk:2 * Hk * dk], None,
                dk, plan, q_scale=jnp.full((dk,), 1 / dk, f32),
                k_scale=jnp.full((dk,), dk ** -0.5, f32), eps=1e-6 / dk,
                interpret=interpret), None, plan=None)
    q = (unit(rows[..., :Hk * dk]) * dk ** -0.5).astype(rows.dtype)
    k = unit(rows[..., Hk * dk:2 * Hk * dk]).astype(rows.dtype)
    return made(q, k, None, plan=None)


def heads_rule(heads: MixerHeads, g: jax.Array, beta: jax.Array, *,
               impl: str = "auto", interpret: bool = False) -> jax.Array:
    """:func:`gated_delta_rule` of ``heads``: ``o`` (B, S, Hv dv) rows, or
    in the slots the heads lie in."""
    Hk, dk, _, dv = heads.widths
    B, S = g.shape[:2]
    return _dispatch(
        heads.q.reshape(B, S, -1), heads.k.reshape(B, S, -1),
        heads.rows[..., 2 * Hk * dk:] if heads.v is None else heads.v, g,
        beta, heads.chunk, Hk, impl, interpret,
        slots=None if heads.v is None else (dk, dv))


def gated_norm(heads: MixerHeads, o: jax.Array, z: jax.Array, w: jax.Array,
               eps: float, *, gate: str = "silu", interpret: bool = False
               ) -> jax.Array:
    """``rms_norm(o, w, eps) * silu(z)`` a value head (norm first, gate
    second) of :func:`heads_rule`'s ``o`` and the gate's rows ``z`` (B, S,
    Hv dv): ``y`` as rows, or (B, S, Hv, dv) off the kernels.  ``gate``
    ``"sigmoid"`` (Kimi Delta Attention's) multiplies by ``sigmoid(z)``
    instead, on the ``(B, S, H, d)`` float32 lines: the row kernel is
    written for ``silu``."""
    from .pallas import spmd

    _, dk, Hv, dv = heads.widths
    if gate not in ("silu", "sigmoid"):
        raise ValueError(f"gated_norm gate {gate!r}: 'silu' or 'sigmoid'")
    if gate == "sigmoid":
        plan = None
        spmd.note_dispatch("gated_norm_rows", "xla", "a sigmoid gate: the "
                           "row kernel multiplies by silu")
    else:
        plan = heads.plan if dk % 128 or dv % 128 else gated_norm_plan(o, dv)
    if plan is not None:
        return gated_norm_rows(o, z, w, dv, plan, eps=eps,
                               interpret=interpret)
    f32 = jnp.float32
    B, S, _ = o.shape
    of = o.reshape(B, S, Hv, dv).astype(f32)
    y = of * lax.rsqrt(jnp.mean(of ** 2, axis=-1, keepdims=True) + eps) * w
    act = jax.nn.silu if gate == "silu" else jax.nn.sigmoid
    return (y * act(z.reshape(B, S, Hv, dv).astype(f32))).astype(o.dtype)


def gated_norm_plan(o: jax.Array, head_dim: int) -> Optional[tuple]:
    """Whether a Gated DeltaNet's output ``o`` (B, S, H*D) and its gate stay
    rows through the gated per-head norm: ``ops/pallas/spmd.py plan``'s
    verdict for :func:`gated_norm_rows`, counted in
    ``kernel_dispatch_total{site="gated_norm_rows"}``."""
    from .pallas import qk_rows, spmd

    return spmd.plan(
        "gated_norm_rows", o.shape[0],
        f"head_dim {head_dim} is no multiple of 128" if head_dim % 128
        else qk_rows.gated_norm_supported(o.shape[1], o.shape[2], o.dtype),
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
    from .pallas import spmd
    from .pallas.qk_rows import gated_norm_rows as kernel

    return spmd.over_batch(lambda *a: kernel(*a, head_dim, eps, interpret),
                           plan, (o, z, w), whole=(2,))


def slots_plan(rows: jax.Array, key_heads: int, dk: int, value_heads: int,
               dv: int, chunk: int) -> Optional[tuple]:
    """Whether a Gated DeltaNet layer whose heads are no whole lane tiles
    (``dk`` or ``dv`` no multiple of 128) keeps them in LANE SLOTS from the
    filter's ``rows`` (B, S, 2 Hk dk + Hv dv) to ``out_proj``'s operand.
    It needs :func:`slot_rows`, the rule's kernels and
    :func:`gated_norm_rows`, so it is also None where the rule would keep
    XLA's form (:func:`kernels_refusal`).  Counted in
    ``kernel_dispatch_total`` under both ``site="qk_rows"`` and
    ``site="gated_norm_rows"``."""
    from .pallas import qk_rows, spmd

    heads = qk_rows.Heads(key_heads, dk, value_heads, dv)
    sk, sv = qk_rows.slot(dk), qk_rows.slot(dv)
    B, S, _ = rows.shape
    rule = kernels_refusal(S, chunk, key_heads, value_heads, dk, dv,
                           rows.dtype)
    refusal = f"the delta rule keeps XLA's form: {rule}" if rule else (
        qk_rows.slot_rows_supported(S, heads, rows.dtype)
        or qk_rows.gated_norm_supported(S, value_heads * dv, rows.dtype, dv))
    plan = spmd.plan(
        "qk_rows", B, refusal, f"heads of {dk} and {dv} in slots of {sk} and "
        f"{sv}, rows {heads.width}")
    spmd.plan("gated_norm_rows", B, refusal, f"head_dim {dv} in slots of "
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
    from .pallas import qk_rows, spmd

    heads = qk_rows.Heads(key_heads, dk, value_heads, dv)
    return spmd.over_batch(
        lambda x: qk_rows.slot_rows(x, heads, eps, interpret), plan, (rows,),
        outs=3)
