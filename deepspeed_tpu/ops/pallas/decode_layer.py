"""Fused decode-row megakernels: the per-layer decode tick in two launches.

The reference's inference headline is its fused transformer decode kernels
(``csrc/transformer/inference/``: bias/residual/LN/softmax fused around the
GEMMs, dispatched from ``pt_binding.cpp``).  Our serving path reproduces
the *scheduling* side (Orca-style continuous batching) but decoded through
~10 separate XLA ops per layer per tick; BENCH_NORTHSTAR round-5 measured
~1.4 ms/tick of fixed non-weight cost (~0.05 ms/layer of op overhead +
head + sampler) shared by the fp and int8 variants — per-op dispatch and
HBM round-trips for (slots, E)-sized activations that never needed to
leave the chip.

This module collapses the chain into two Pallas kernels around the
existing ``decode_attention`` kernel:

- :func:`fused_norm_proj` — ``norm(x) @ W + b`` in one pass: the
  LayerNorm/RMSNorm runs on the VMEM-resident ``(slots, E)`` row tile and
  the projection bias folds into the GEMM epilogue.  Used for the
  ``LN → fused QKV`` prologue (and per-projection for LLaMA's split
  q/k/v).
- :func:`fused_post_attn` — ``o-proj + residual-add → norm → MLP →
  residual-add`` in one pass: the row tile stays in VMEM across both
  fusion groups while the MLP weight panels stream through a grid
  dimension (the decode-row analog of ``fused_mlp.py``).  Handles the
  GELU pair (GPT-2 tanh / NeoX exact, sequential or parallel residual)
  and the SwiGLU triple (LLaMA).

Both kernels take bf16 weights or W8A16 pairs (int8 codes + grouped fp32
scales, the ``ops/w8.py`` layout): dequantization happens inside the fused
contraction — per-group upcast in VMEM, scale folded into the accumulator —
so the int8 path sheds the per-tick dequant epilogue that erased its
batched-serving win (round-3: −11% at batch 8).

Ops carry ``custom_vmap`` rules folding a slot-vmapped axis into the row
dim (the continuous batcher vmaps the decode step over slots), mirroring
``decode_attention`` / ``w8_matmul``.  ``interpret=True`` runs on CPU for
tests and for CPU-mesh serving smoke runs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_mlp import _gelu_tanh, _pad_rows

# Row padding: Mosaic wants >= 8 (f32) / 16 (bf16) sublanes per tile; the
# decode row count (n_slots) is tiny either way, so always pad to 16.
_ROW_PAD = 16
# Streamed-panel budget: weight tiles with row-varying index maps are
# double-buffered, constant-map panels keep ONE buffer (~16MB VMEM/core).
_TILE_BUDGET = 8 * 1024 * 1024
_PANEL_BUDGET = 12 * 1024 * 1024
_MAX_ROWS = 64          # decode regime only; prefill takes the XLA path
_BN_MAX = 512


WeightOrQ = Union[jax.Array, Tuple[jax.Array, jax.Array]]


def decode_fused_metrics():
    """(qkv, post_attn, fallback) dispatch counters — created HERE, next
    to the kernels, so the custom_vmap rules can count their own
    reference-path detours and the model-layer dispatch shares the same
    cells (a refusal that only one layer counted would let the e2e sweep
    attribute XLA-path numbers to the fused kernels)."""
    from ...telemetry import registry as telemetry_registry

    return (
        telemetry_registry.counter(
            "decode_fused_qkv_traces_total",
            "fused norm->QKV kernel dispatches (trace-time, not per-tick)"),
        telemetry_registry.counter(
            "decode_fused_post_attn_traces_total",
            "fused o-proj->norm->MLP kernel dispatches (trace-time)"),
        telemetry_registry.counter(
            "decode_fused_fallback_total",
            "decode_fused enabled but a guard refused (shape, mesh, or a "
            "vmap fold past the row guard); XLA path taken"),
    )


def _note_fold_refusal(kernel: str, rows: int) -> None:
    from .spmd import note_dispatch

    decode_fused_metrics()[2].inc()
    note_dispatch("decode_fused", "xla",
                  f"{kernel}: vmap fold to {rows} rows > {_MAX_ROWS}")


def _norm_rows(x, scale, bias, *, rms: bool, eps: float):
    """fp32 LayerNorm / RMSNorm over the last dim of a (rows, E) tile —
    the same math as the model-zoo norm modules (``models/common.py``)."""
    if rms:
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu_exact(u):
    # HF NeoX uses exact gelu; erf lowers to the VPU rational approximation
    return 0.5 * u * (1.0 + jax.lax.erf(u * (2.0 ** -0.5)))


# ---------------------------------------------------------------------------
# Reference XLA math — the unfused op chains the kernels must reproduce.
# Shared by the tests, chip_smoke.py's kernel phase AND the custom_vmap rules
# (a slot-vmapped fold can exceed the row guard the per-slot trace already
# passed; the rules then compute THIS instead of launching the kernel).
# ---------------------------------------------------------------------------

def _norm_apply(x, scale, bias, rms: bool, eps: float):
    y = _norm_rows(x.astype(jnp.float32), scale,
                   0.0 if bias is None else bias, rms=rms, eps=eps)
    return y.astype(x.dtype)


def _ref_dense(a, w, b):
    if isinstance(w, tuple):
        from ...ops.w8 import w8a16_matmul

        out = w8a16_matmul(a, *w)
    else:
        out = jnp.dot(a, w)
    return out if b is None else out + b.astype(out.dtype)


def reference_norm_proj(x, norm_scale, norm_bias, weight, bias, *,
                        rms: bool = False, eps: float = 1e-5):
    """Unfused ``norm(x) @ W + b`` — the op chain the stock module path
    emits."""
    xn = _norm_apply(x, norm_scale, norm_bias, rms, eps)
    return _ref_dense(xn, weight, bias)


def reference_post_attn(y, x, wo, bo, norm_scale, norm_bias, mlp_weights,
                        *, swiglu: bool = False, rms: bool = False,
                        eps: float = 1e-5, exact_gelu: bool = False,
                        parallel_residual: bool = False):
    """Unfused o-proj + residual → norm → MLP → residual chain."""
    r1 = x + _ref_dense(y, wo, bo)
    h = _norm_apply(x if parallel_residual else r1, norm_scale, norm_bias,
                    rms, eps)
    if swiglu:
        wg, wu, wd = mlp_weights
        gate = _ref_dense(h, wg, None)
        ff = _ref_dense(jax.nn.silu(gate) * _ref_dense(h, wu, None), wd,
                        None)
    else:
        w1, b1, w2, b2 = mlp_weights
        h1 = jax.nn.gelu(_ref_dense(h, w1, b1),
                         approximate=not exact_gelu)
        ff = _ref_dense(h1, w2, b2)
    return r1 + ff


def _dot(a, b_ref):
    return jax.lax.dot_general(a, b_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _qdot_any(a, c_ref, s_ref, g: int):
    """``a (M, K) @ dequant(codes (K, N), scales (L, N))`` with the
    per-group upcast in VMEM and the scale folded into the fp32
    accumulator (the ``w8_matmul.py`` idiom).  ``L == 1`` means one group
    spanning the whole K range of this tile — the scale distributes over
    partial sums, so streamed tiles of a single-group panel stay exact."""
    if s_ref.shape[0] == 1:
        cg = c_ref[...].astype(a.dtype)
        return jax.lax.dot_general(
            a, cg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * s_ref[0][None, :]
    out = jnp.zeros((a.shape[0], c_ref.shape[1]), jnp.float32)
    for u in range(s_ref.shape[0]):
        xg = a[:, u * g:(u + 1) * g]
        cg = c_ref[pl.ds(u * g, g), :].astype(a.dtype)
        out += jax.lax.dot_general(
            xg, cg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * s_ref[u][None, :]
    return out


# ---------------------------------------------------------------------------
# Kernel A: norm -> projection (the LN -> fused-QKV prologue)
# ---------------------------------------------------------------------------

def _norm_proj_kernel(*refs, rms, eps, quant, g):
    if quant:
        x_ref, ns_ref, nb_ref, c_ref, s_ref, b_ref, o_ref = refs
    else:
        x_ref, ns_ref, nb_ref, w_ref, b_ref, o_ref = refs
    x = x_ref[...].astype(jnp.float32)
    # the norm recomputes per N-tile: (rows, E) of VPU work against an
    # (E, bn) MXU panel — noise, and it keeps the kernel stateless
    xn = _norm_rows(x, ns_ref[0].astype(jnp.float32),
                    nb_ref[0].astype(jnp.float32), rms=rms, eps=eps)
    xn = xn.astype(x_ref.dtype)
    if quant:
        y = _qdot_any(xn, c_ref, s_ref, g)
    else:
        y = _dot(xn, w_ref)
    y = y + b_ref[0].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _pick_bn(n: int, e: int, itemsize: int) -> int:
    """Largest divisor-of-N panel width <= 512 whose double-buffered
    (E, bn) weight tile fits the streaming budget; 0 if none."""
    bn = min(_BN_MAX, n)
    while bn > 128 and (n % bn or 2 * e * bn * itemsize > _TILE_BUDGET):
        bn //= 2
    if n % bn or 2 * e * bn * itemsize > _TILE_BUDGET:
        return 0
    return bn


@functools.lru_cache(maxsize=None)
def _norm_proj_op(rms: bool, eps: float, quant: bool, interpret: bool):
    def run(x, ns, nb, wargs, b):
        # row-pad HERE, after any vmap fold, so slot-vmapped calls pad
        # once to the sublane tile instead of 16x per slot
        x, M0 = _pad_rows(x, _ROW_PAD)
        M, E = x.shape
        if quant:
            codes, scale = wargs
            N = codes.shape[1]
            G = scale.shape[0]
            g = E // G
            itemsize = 1
        else:
            (w,) = wargs
            N = w.shape[1]
            G, g = 1, E
            itemsize = w.dtype.itemsize
        bn = _pick_bn(N, E, itemsize)
        const = lambda j: (0, 0)                       # noqa: E731
        ntile = lambda j: (0, j)                       # noqa: E731
        in_specs = [
            pl.BlockSpec((M, E), const),
            pl.BlockSpec((1, E), const),
            pl.BlockSpec((1, E), const),
        ]
        if quant:
            in_specs += [pl.BlockSpec((E, bn), ntile),
                         pl.BlockSpec((G, bn), ntile)]
        else:
            in_specs += [pl.BlockSpec((E, bn), ntile)]
        in_specs += [pl.BlockSpec((1, bn), ntile)]
        kern = functools.partial(_norm_proj_kernel, rms=rms, eps=eps,
                                 quant=quant, g=g)
        out = pl.pallas_call(
            kern, grid=(N // bn,), in_specs=in_specs,
            out_specs=pl.BlockSpec((M, bn), ntile),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            interpret=interpret,
        )(x, ns, nb, *wargs, b)
        return out[:M0]

    def fold(x, was, axis_size):
        return x if was else jnp.broadcast_to(x[None], (axis_size,) + x.shape)

    def folded(call, x, ns, nb, wargs, b, axis_size, in_batched):
        # the per-slot trace passed the row guard at M=1; the folded
        # kernel runs at axis_size*M rows — past the guard, compute the
        # reference chain instead of launching an unguarded kernel
        if any(in_batched[1:]):
            raise NotImplementedError(
                "fused_norm_proj: weights/norm params are broadcast "
                "across serving slots; batched weights unsupported")
        x = fold(x, in_batched[0], axis_size)
        B, M, E = x.shape
        if B * M > _MAX_ROWS:
            _note_fold_refusal("fused_norm_proj", B * M)
            w = wargs if quant else wargs[0]
            out = reference_norm_proj(
                x.reshape(B * M, E), ns[0], None if rms else nb[0], w,
                b[0], rms=rms, eps=eps)
        else:
            out = call(x.reshape(B * M, E), ns, nb, *wargs, b)
        return out.reshape(B, M, -1), True

    if quant:
        @jax.custom_batching.custom_vmap
        def call(x, ns, nb, codes, scale, b):
            return run(x, ns, nb, (codes, scale), b)

        @call.def_vmap
        def _rule(axis_size, in_batched, x, ns, nb, codes, scale, b):
            return folded(call, x, ns, nb, (codes, scale), b, axis_size,
                          in_batched)
    else:
        @jax.custom_batching.custom_vmap
        def call(x, ns, nb, w, b):
            return run(x, ns, nb, (w,), b)

        @call.def_vmap
        def _rule(axis_size, in_batched, x, ns, nb, w, b):
            return folded(call, x, ns, nb, (w,), b, axis_size, in_batched)

    return call


def fused_norm_proj(x: jax.Array, norm_scale: jax.Array,
                    norm_bias: Optional[jax.Array], weight: WeightOrQ,
                    bias: Optional[jax.Array], *, rms: bool = False,
                    eps: float = 1e-5, interpret: bool = False) -> jax.Array:
    """``norm(x) @ W + b`` in one kernel; returns ``(..., N)`` in x.dtype.

    ``x``: ``(..., E)`` decode rows; ``weight``: bf16/fp ``(E, N)`` or a
    ``(codes int8 (E, N), scales fp32 (G, N))`` W8A16 pair; ``norm_bias``
    is ignored under ``rms=True``; ``bias=None`` skips the epilogue add.
    """
    lead, E = x.shape[:-1], x.shape[-1]
    M = 1
    for s in lead:
        M *= s
    quant = isinstance(weight, tuple)
    N = weight[0].shape[1] if quant else weight.shape[1]
    ns = norm_scale.astype(jnp.float32).reshape(1, E)
    nb = (jnp.zeros((1, E), jnp.float32) if norm_bias is None
          else norm_bias.astype(jnp.float32).reshape(1, E))
    b = (jnp.zeros((1, N), x.dtype) if bias is None
         else bias.astype(x.dtype).reshape(1, N))
    x2 = x.reshape(M, E)
    op = _norm_proj_op(bool(rms), float(eps), quant, bool(interpret))
    y = op(x2, ns, nb, *weight, b) if quant else op(x2, ns, nb, weight, b)
    return y.reshape(*lead, N)


def norm_proj_supported(m: int, e: int, n: int, itemsize: int,
                        quant: bool, groups: int = 1) -> bool:
    """Dispatch guard for :func:`fused_norm_proj` (checked in interpret
    mode too, so CPU tests exercise the exact hardware predicate)."""
    if m > _MAX_ROWS or e % 128 or n % 128:
        return False
    g = e // max(groups, 1)
    if quant and groups > 1 and (g % 128 or e % g):
        return False
    return _pick_bn(n, e, 1 if quant else itemsize) > 0


# ---------------------------------------------------------------------------
# Kernel B: o-proj + residual -> norm -> MLP -> residual
# ---------------------------------------------------------------------------

def _post_attn_kernel(*refs, swiglu, quant, rms, eps, exact_gelu,
                      parallel_residual, g_e, g_f, nf):
    if swiglu:
        if quant:
            (y_ref, x_ref, co_ref, so_ref, bo_ref, ns_ref, nb_ref,
             cg_ref, sg_ref, cu_ref, su_ref, cd_ref, sd_ref,
             o_ref, r1_ref, hin_ref, acc_ref) = refs
        else:
            (y_ref, x_ref, wo_ref, bo_ref, ns_ref, nb_ref,
             wg_ref, wu_ref, wd_ref,
             o_ref, r1_ref, hin_ref, acc_ref) = refs
    else:
        if quant:
            (y_ref, x_ref, co_ref, so_ref, bo_ref, ns_ref, nb_ref,
             c1_ref, s1_ref, b1_ref, c2_ref, s2_ref, b2_ref,
             o_ref, r1_ref, hin_ref, acc_ref) = refs
        else:
            (y_ref, x_ref, wo_ref, bo_ref, ns_ref, nb_ref,
             w1_ref, b1_ref, w2_ref, b2_ref,
             o_ref, r1_ref, hin_ref, acc_ref) = refs
    j = pl.program_id(0)
    cdt = x_ref.dtype

    @pl.when(j == 0)
    def _prologue():
        yv = y_ref[...]
        o_part = _qdot_any(yv, co_ref, so_ref, g_e) if quant \
            else _dot(yv, wo_ref)
        r1 = x_ref[...].astype(jnp.float32) + o_part \
            + bo_ref[0].astype(jnp.float32)
        r1_ref[...] = r1
        # NeoX parallel residual: the MLP reads norm(x), not norm(x+attn)
        src = x_ref[...].astype(jnp.float32) if parallel_residual else r1
        hin_ref[...] = _norm_rows(src, ns_ref[0].astype(jnp.float32),
                                  nb_ref[0].astype(jnp.float32),
                                  rms=rms, eps=eps)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hin = hin_ref[...].astype(cdt)
    if swiglu:
        gate = _qdot_any(hin, cg_ref, sg_ref, g_e) if quant \
            else _dot(hin, wg_ref)
        up = _qdot_any(hin, cu_ref, su_ref, g_e) if quant \
            else _dot(hin, wu_ref)
        h = (gate * jax.nn.sigmoid(gate)) * up
        contrib = _qdot_any(h.astype(cdt), cd_ref, sd_ref, g_f) if quant \
            else _dot(h.astype(cdt), wd_ref)
    else:
        u = _qdot_any(hin, c1_ref, s1_ref, g_e) if quant \
            else _dot(hin, w1_ref)
        u = u + b1_ref[0].astype(jnp.float32)
        h = _gelu_exact(u) if exact_gelu else _gelu_tanh(u)
        contrib = _qdot_any(h.astype(cdt), c2_ref, s2_ref, g_f) if quant \
            else _dot(h.astype(cdt), w2_ref)
    acc_ref[...] += contrib

    @pl.when(j == nf - 1)
    def _epilogue():
        out = r1_ref[...] + acc_ref[...]
        if not swiglu:
            out = out + b2_ref[0].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def _pick_bf(e: int, f: int, itemsize: int, n_stream: int,
             g_f: int = 0) -> int:
    """Largest divisor-of-F tile whose ``n_stream`` double-buffered
    (E, bf)-sized panels fit the tile budget (and that the W8 down-panel
    group size divides, so scale tiles stay group-aligned); 0 if none."""
    bf = min(f, 2048)
    def ok(bf):
        if f % bf or (g_f and bf % g_f):
            return False
        # Mosaic sublane rule: the (bf // g_f, E) scale tile of the W8
        # down panel needs its row dim divisible by 8 OR equal to the
        # full group count (bf == f) — interpret mode would not catch it
        if g_f and bf != f and (bf // g_f) % 8:
            return False
        return 2 * n_stream * e * bf * itemsize <= _TILE_BUDGET
    while bf > 128 and not ok(bf):
        bf //= 2
    return bf if ok(bf) else 0


@functools.lru_cache(maxsize=None)
def _post_attn_op(swiglu: bool, quant: bool, rms: bool, eps: float,
                  exact_gelu: bool, parallel_residual: bool,
                  interpret: bool):
    n_mlp = 3 if swiglu else 2

    def run(y, x, flat):
        # row-pad after any vmap fold (see _norm_proj_op); the pad rows
        # flow through norm/MLP as constant garbage and are sliced off
        y, _ = _pad_rows(y, _ROW_PAD)
        x, M0 = _pad_rows(x, _ROW_PAD)
        M, E = x.shape
        if quant:
            co, so, bo, ns, nb = flat[:5]
            mlp = flat[5:]
            g_e = E // so.shape[0] if so.shape[0] > 1 else E
            itemsize = 1
        else:
            wo, bo, ns, nb = flat[:4]
            mlp = flat[4:]
            g_e = E
            itemsize = wo.dtype.itemsize
        if swiglu:
            if quant:
                cg, sg, cu, su, cd, sd = mlp
                F = cg.shape[1]
                Gf = sd.shape[0]
            else:
                wg, wu, wd = mlp
                F = wg.shape[1]
                Gf = 1
        else:
            if quant:
                c1, s1, b1, c2, s2, b2 = mlp
                F = c1.shape[1]
                Gf = s2.shape[0]
            else:
                w1, b1, w2, b2 = mlp
                F = w1.shape[1]
                Gf = 1
        g_f = F // Gf
        bf = _pick_bf(E, F, itemsize, n_stream=n_mlp,
                      g_f=g_f if Gf > 1 else 0)
        nf = F // bf
        const = lambda j: (0, 0)                       # noqa: E731
        ftile = lambda j: (0, j)                       # noqa: E731
        frow = lambda j: (j, 0)                        # noqa: E731
        row_spec = pl.BlockSpec((M, E), const)
        e_vec = pl.BlockSpec((1, E), const)

        def up_panel(G1):       # contraction over E (full K in block)
            if quant:
                return [pl.BlockSpec((E, bf), ftile),
                        pl.BlockSpec((G1, bf), ftile)]
            return [pl.BlockSpec((E, bf), ftile)]

        def down_panel(Gf):     # contraction over the streamed F tile
            if quant:
                s_spec = pl.BlockSpec((1, E), const) if Gf == 1 \
                    else pl.BlockSpec((bf // g_f, E), frow)
                return [pl.BlockSpec((bf, E), frow), s_spec]
            return [pl.BlockSpec((bf, E), frow)]

        in_specs = [row_spec, row_spec]
        if quant:
            in_specs += [pl.BlockSpec((E, E), const),
                         pl.BlockSpec((so.shape[0], E), const)]
        else:
            in_specs += [pl.BlockSpec((E, E), const)]
        in_specs += [e_vec, e_vec, e_vec]              # bo, ns, nb
        G1 = (s1.shape[0] if quant and not swiglu else
              (sg.shape[0] if quant else 1))
        if swiglu:
            in_specs += up_panel(G1) + up_panel(G1) + down_panel(Gf)
        else:
            in_specs += up_panel(G1) + [pl.BlockSpec((1, bf), ftile)] \
                + down_panel(Gf) + [e_vec]
        kern = functools.partial(
            _post_attn_kernel, swiglu=swiglu, quant=quant, rms=rms,
            eps=eps, exact_gelu=exact_gelu,
            parallel_residual=parallel_residual,
            g_e=g_e, g_f=g_f if Gf > 1 else F, nf=nf)
        out = pl.pallas_call(
            kern, grid=(nf,), in_specs=in_specs,
            out_specs=pl.BlockSpec((M, E), const),
            out_shape=jax.ShapeDtypeStruct((M, E), x.dtype),
            scratch_shapes=[pltpu.VMEM((M, E), jnp.float32)] * 3,
            interpret=interpret,
        )(y, x, *flat)
        return out[:M0]

    def reference(y, x, flat):
        """Rebuild :func:`reference_post_attn` args from the flat operand
        list (same layout ``fused_post_attn`` assembles)."""
        if quant:
            co, so, bo, ns, nb = flat[:5]
            wo, mlp = (co, so), flat[5:]
        else:
            wo, bo, ns, nb = flat[0], flat[1], flat[2], flat[3]
            mlp = flat[4:]
        if swiglu:
            if quant:
                cg, sg, cu, su, cd, sd = mlp
                mw = ((cg, sg), (cu, su), (cd, sd))
            else:
                mw = tuple(mlp)
        else:
            if quant:
                c1, s1, b1, c2, s2, b2 = mlp
                mw = ((c1, s1), b1[0], (c2, s2), b2[0])
            else:
                w1, b1, w2, b2 = mlp
                mw = (w1, b1[0], w2, b2[0])
        return reference_post_attn(
            y, x, wo, bo[0], ns[0], None if rms else nb[0], mw,
            swiglu=swiglu, rms=rms, eps=eps, exact_gelu=exact_gelu,
            parallel_residual=parallel_residual)

    @jax.custom_batching.custom_vmap
    def call(y, x, *flat):
        return run(y, x, flat)

    @call.def_vmap
    def _rule(axis_size, in_batched, y, x, *flat):
        if any(in_batched[2:]):
            raise NotImplementedError(
                "fused_post_attn: weights/norm params are broadcast "
                "across serving slots; batched weights unsupported")
        def fold(a, was):
            return a if was else jnp.broadcast_to(
                a[None], (axis_size,) + a.shape)
        y = fold(y, in_batched[0])
        x = fold(x, in_batched[1])
        B, M, E = x.shape
        if B * M > _MAX_ROWS:
            # past the row guard the per-slot trace validated (see
            # _norm_proj_op): reference chain, not an unguarded kernel
            _note_fold_refusal("fused_post_attn", B * M)
            out = reference(y.reshape(B * M, E), x.reshape(B * M, E),
                            flat)
        else:
            out = call(y.reshape(B * M, E), x.reshape(B * M, E), *flat)
        return out.reshape(B, M, E), True

    return call


def fused_post_attn(y: jax.Array, x: jax.Array, wo: WeightOrQ,
                    bo: Optional[jax.Array], norm_scale: jax.Array,
                    norm_bias: Optional[jax.Array], mlp_weights: tuple, *,
                    swiglu: bool = False, rms: bool = False,
                    eps: float = 1e-5, exact_gelu: bool = False,
                    parallel_residual: bool = False,
                    interpret: bool = False) -> jax.Array:
    """``x + y@Wo+bo`` → ``norm`` → MLP → residual, one kernel.

    ``y``: pre-o-proj attention output ``(..., E)``; ``x``: the residual
    stream; ``wo``: ``(E, E)`` or a W8A16 pair.  ``mlp_weights``:
    ``(w1, b1, w2, b2)`` for the GELU pair (biases may be None) or
    ``(w_gate, w_up, w_down)`` for SwiGLU, each weight an array or a
    W8A16 pair.  ``parallel_residual`` feeds the MLP ``norm(x)`` instead
    of ``norm(x + attn)`` (GPT-NeoX).  Returns the new residual stream.
    """
    lead, E = x.shape[:-1], x.shape[-1]
    M = 1
    for s in lead:
        M *= s
    quant = isinstance(wo, tuple)
    bo2 = (jnp.zeros((1, E), x.dtype) if bo is None
           else bo.astype(x.dtype).reshape(1, E))
    ns = norm_scale.astype(jnp.float32).reshape(1, E)
    nb = (jnp.zeros((1, E), jnp.float32) if norm_bias is None
          else norm_bias.astype(jnp.float32).reshape(1, E))
    flat = list(wo) if quant else [wo]
    flat += [bo2, ns, nb]
    if swiglu:
        for w in mlp_weights:
            flat += list(w) if isinstance(w, tuple) else [w]
    else:
        w1, b1, w2, b2 = mlp_weights
        F = w1[0].shape[1] if isinstance(w1, tuple) else w1.shape[1]
        flat += list(w1) if isinstance(w1, tuple) else [w1]
        flat += [jnp.zeros((1, F), x.dtype) if b1 is None
                 else b1.astype(x.dtype).reshape(1, F)]
        flat += list(w2) if isinstance(w2, tuple) else [w2]
        flat += [jnp.zeros((1, E), x.dtype) if b2 is None
                 else b2.astype(x.dtype).reshape(1, E)]
    op = _post_attn_op(bool(swiglu), quant, bool(rms), float(eps),
                       bool(exact_gelu), bool(parallel_residual),
                       bool(interpret))
    out = op(y.reshape(M, E), x.reshape(M, E), *flat)
    return out.reshape(*lead, E)


def post_attn_supported(m: int, e: int, f: int, itemsize: int, quant: bool,
                        groups_e: int = 1, groups_f: int = 1,
                        swiglu: bool = False) -> bool:
    """Dispatch guard for :func:`fused_post_attn`: rows in the decode
    regime, lane-aligned dims, W8 group tiles aligned, and the o-proj
    panel + streamed MLP tiles inside the VMEM budget (SwiGLU streams 3
    panels per grid step, the GELU pair 2)."""
    if m > _MAX_ROWS or e % 128 or f % 128:
        return False
    w_item = 1 if quant else itemsize
    g_e = e // max(groups_e, 1)
    g_f = f // max(groups_f, 1)
    if quant:
        if groups_e > 1 and (g_e % 128 or e % g_e):
            return False
        if groups_f > 1 and (g_f % 128 or f % g_f):
            return False
    if e * e * w_item > _PANEL_BUDGET:      # resident o-proj panel
        return False
    return _pick_bf(e, f, w_item, n_stream=3 if swiglu else 2,
                    g_f=g_f if quant and groups_f > 1 else 0) > 0
