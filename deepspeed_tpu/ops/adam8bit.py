"""8-bit (quantized-state) Adam(W): Adam moments stored as int8/uint8.

The memory lever that makes billion-parameter Adam fit a single chip's
HBM: fp32 m+v cost 8 bytes/param — quantized rows cost 2 bytes/param
(+ ~1/row fp32 scale).  For GPT-2-1.5B that is 12.5 GB → 3.1 GB, the
difference between fitting and not fitting a 16 GB chip alongside the
fp32 master (the regime the reference reaches by sharding optimizer
state across 8 GPUs — ``/root/reference/docs/_tutorials/zero.md:29`` —
or by CPU offload, ``csrc/adam/cpu_adam.cpp``).  Same compressed-state
family as the 1-bit optimizers (reference ``runtime/fp16/onebit/``),
but lossy-compressing *storage* instead of *communication*.

Design (TPU-first):
- Row-wise (last-axis) absmax scaling.  Transformer leaves have rows of
  1.6k–6.4k elements — the same granularity class as the published
  block-2048 dynamic quantization this follows (PAPERS.md: 8-bit
  optimizers via block-wise quantization), without padding/reshape, and
  the codes keep the PARAM's shape, so ZeRO sharding specs apply to the
  quantized state unchanged (``parallel/zero.py:opt_state_specs``).
- ``m`` (signed) → int8 symmetric; ``sqrt(v)`` (non-negative) → uint8.
  Storing the root halves v's dynamic range in log space and is what the
  denominator consumes anyway.
- De/re-quantization happens inside the one compiled update — XLA fuses
  it into the elementwise optimizer math; int8 HBM reads are the point.
- The scale trees are nested one level deeper than params (``{"m","r"}``
  dicts) ON PURPOSE: ``opt_state_specs`` structure-matches param-shaped
  subtrees for sharding, and a (…, 1) scale must fall through to
  replicated, not inherit a row-sharded spec.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

ScalarOrSchedule = Union[float, Callable]


def _quant_sym(x: jax.Array):
    """fp32 → (int8 codes, fp32 row scale), symmetric absmax per last axis."""
    if x.ndim == 0:
        amax = jnp.abs(x)
    else:
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def _quant_pos(x: jax.Array):
    """non-negative fp32 → (uint8 codes, fp32 row scale), rounded UP.

    ``x`` is Adam's denominator ``sqrt(v)``.  Rounded to nearest, an entry
    under 1/510 of its row's largest stored 0 while its first moment,
    scaled by a row maximum of its own, kept a code: the next step divided
    that moment by ``sqrt((1-b2) g^2)`` of whatever gradient came, and by
    ``eps`` alone when it was zero.  Rows whose entries differ by orders
    of magnitude (an untied ``(embed, vocab)`` head under Zipf token
    frequencies) blew OLMoE up at step 60-80 where fp32 AdamW trained 600
    steps (v5e, PERF.md section 6, PR 26).  Rounded up, a stored
    denominator is never under the true one, so an update stays within
    Adam's own bound ``|m| <= 7.3 sqrt(v)``; entries far under their
    row's maximum move more slowly instead."""
    if x.ndim == 0:
        amax = x
    else:
        amax = jnp.max(x, axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 255.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.ceil(x / scale), 0, 255).astype(jnp.uint8)
    return codes, scale


class Adam8bitState(NamedTuple):
    count: jax.Array
    m_codes: Any        # int8, param-shaped (shards like params)
    r_codes: Any        # uint8, param-shaped; r = sqrt(v)
    scales: Any         # {"m": (...,1), "r": (...,1)} per leaf — replicated


def _leaf_moments(g, mc, rc, sc, *, b1, b2, c1, c2, eps):
    """THE adam8bit per-leaf math (the optax chain's, the kernel path's
    small leaves', and what ``ops/pallas/adam8bit_kernel.py`` is the
    one-pass form of): dequant → m/v update → bias-corrected Adam
    direction → requant."""
    m = b1 * (mc.astype(jnp.float32) * sc["m"]) + (1.0 - b1) * g
    r0 = rc.astype(jnp.float32) * sc["r"]
    v = b2 * (r0 * r0) + (1.0 - b2) * (g * g)
    upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
    mc2, ms = _quant_sym(m)
    rc2, rs = _quant_pos(jnp.sqrt(v))
    return upd, mc2, rc2, {"m": ms, "r": rs}


def scale_by_adam8bit(b1: float = 0.9, b2: float = 0.999,
                      eps: float = 1e-8) -> optax.GradientTransformation:
    def init_fn(params):
        m_codes = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.int8), params)
        r_codes = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.uint8), params)

        def scale0(p):
            shp = p.shape[:-1] + (1,) if p.ndim else ()
            return {"m": jnp.ones(shp, jnp.float32),
                    "r": jnp.ones(shp, jnp.float32)}

        return Adam8bitState(count=jnp.zeros([], jnp.int32),
                             m_codes=m_codes, r_codes=r_codes,
                             scales=jax.tree_util.tree_map(scale0, params))

    def update_fn(updates, state, params=None):
        del params
        count = optax.safe_int32_increment(state.count)
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        def leaf(g, mc, rc, sc):
            return _leaf_moments(g.astype(jnp.float32), mc, rc, sc,
                                 b1=b1, b2=b2, c1=c1, c2=c2, eps=eps)

        # scales sit one level deeper than params; tree_map's
        # flatten_up_to treats each {"m","r"} dict as the leaf for its path
        out = jax.tree_util.tree_map(leaf, updates, state.m_codes,
                                     state.r_codes, state.scales)
        upd, m_codes, r_codes, scales_t = jax.tree_util.tree_transpose(
            jax.tree_util.tree_structure(updates),
            jax.tree_util.tree_structure((0, 0, 0, {"m": 0, "r": 0})),
            out)
        # transpose inverts nesting ({"m": param-tree, ...}); restore the
        # param-tree-of-{"m","r"} layout init_fn established
        scales = jax.tree_util.tree_map(
            lambda m, r: {"m": m, "r": r}, scales_t["m"], scales_t["r"])
        return upd, Adam8bitState(count=count, m_codes=m_codes,
                                  r_codes=r_codes, scales=scales)

    return optax.GradientTransformation(init_fn, update_fn)


def adamw_8bit(learning_rate: ScalarOrSchedule, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 0.0,
               mask: Optional[Any] = None) -> optax.GradientTransformation:
    """AdamW with int8 moments (drop-in for ``optax.adamw``)."""
    parts = [scale_by_adam8bit(b1=b1, b2=b2, eps=eps)]
    if weight_decay:
        parts.append(optax.add_decayed_weights(weight_decay, mask=mask))
    parts.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*parts)


# ----------------------------------------------------------------------
# The one-pass update (ops/pallas/adam8bit_kernel.py)
# ----------------------------------------------------------------------
def _find_state(opt_state) -> Adam8bitState:
    if isinstance(opt_state, Adam8bitState):
        return opt_state
    if isinstance(opt_state, tuple):
        for s in opt_state:
            found = _find_state(s)
            if found is not None:
                return found
    return None


def _advance_state(opt_state, new8: Adam8bitState):
    """Rebuild the optax chain state around a stepped Adam8bitState.

    ``ScaleByScheduleState`` counters advance too, so the kernel path and
    the stock ``tx.update`` path stay interchangeable (same checkpoint
    layout, same LR-schedule step)."""
    import optax._src.transform as _T

    if isinstance(opt_state, Adam8bitState):
        return new8
    if isinstance(opt_state, _T.ScaleByScheduleState):
        return _T.ScaleByScheduleState(
            count=optax.safe_int32_increment(opt_state.count))
    if isinstance(opt_state, tuple):
        parts = [_advance_state(s, new8) for s in opt_state]
        if hasattr(opt_state, "_fields"):      # NamedTuple state
            return type(opt_state)(*parts)
        return tuple(parts)
    return opt_state


SITE = "adam8bit"


def kernel_refusal(*, n_devices: int, offload: bool, fp16: bool
                   ) -> Optional[str]:
    """Why a run's update stays ``tx.update`` as a whole, or ``None``
    where its leaves may take the one-pass kernel: what the engine can
    observe of the run, no switch.  A ``pallas_call`` is opaque to the
    SPMD partitioner, so every device has to hold whole leaves (one
    device today); fp16's overflow skip is a ``where(finite, new, old)``
    over the state, which would undo the in-place aliasing."""
    from .pallas.spmd import on_tpu

    if offload:
        return "optimizer offload"
    if fp16:
        return "fp16 overflow skip selects over the state"
    if n_devices > 1:
        return f"mesh of {n_devices} devices"
    if not on_tpu():
        return "not a TPU"
    return None


def note_refusal(params, reason: str) -> None:
    """Book every leaf of a refused run's update on the XLA chain."""
    from .pallas.spmd import note_dispatch

    for _ in jax.tree_util.tree_leaves(params):
        note_dispatch(SITE, "xla", reason)


def kernel_apply_factory(*, learning_rate: ScalarOrSchedule, b1: float,
                         b2: float, eps: float, weight_decay: float = 0.0,
                         l2: float = 0.0, clip: float = 0.0,
                         mask: Optional[Callable] = None):
    """Build ``apply(grads, params, opt_state, grad_norm, factor) →
    (new_params, new_opt_state)``: the build_tx chain ``clip → [L2] →
    adam8bit moments → [AdamW decay] → lr`` of the ``adamw8bit`` family
    with every leaf the kernel takes updated in place by one pass over
    HBM, the others by the same ``_leaf_moments`` as the chain.

    ``grads`` are the raw gradient sums in the dtype the backward wrote
    (no gradient-sized buffer may stand between it and the kernel);
    ``mask`` (``params -> tree of bools``, ``runtime/optimizers.py
    decay_mask``) names the leaves that decay; None: all.
    ``factor`` is what the engine would have multiplied them by
    (``1 / (denom * loss scale)``) and ``grad_norm`` the norm after it:
    factor and clip reach a leaf as one scalar.  ``opt_state`` is the
    UNCHANGED optax chain state (checkpoints stay compatible).  The
    caller asks :func:`kernel_refusal` first."""
    from .pallas.adam8bit_kernel import apply_leaf, leaf_refusal
    from .pallas.spmd import note_dispatch, on_tpu

    def apply(grads, params, opt_state, grad_norm, factor):
        interp = not on_tpu()
        st = _find_state(opt_state)
        if st is None:
            raise ValueError("no Adam8bitState found in opt_state; the "
                             "adam8bit kernel needs the adamw8bit chain")
        count = optax.safe_int32_increment(st.count)
        cf = count.astype(jnp.float32)
        c1 = 1.0 - b1 ** cf
        c2 = 1.0 - b2 ** cf
        lr = learning_rate(st.count) if callable(learning_rate) \
            else jnp.float32(learning_rate)
        gscale = jnp.asarray(factor, jnp.float32)
        if clip and clip > 0:
            gscale = gscale * jnp.where(grad_norm < clip, 1.0,
                                        clip / grad_norm)
        scalars = jnp.stack([gscale, jnp.asarray(lr, jnp.float32),
                             c1, c2]).astype(jnp.float32)

        def leaf(g, p, mc, rc, sc, decays=True):
            wd, l2_ = (weight_decay, l2) if decays else (0.0, 0.0)
            why = leaf_refusal(p.shape, p.dtype, g.dtype.itemsize)
            if why is None:
                note_dispatch(SITE, "kernel", "one device, whole leaves")
                return apply_leaf(
                    g, p, mc, rc, sc, scalars, b1=b1, b2=b2, eps=eps,
                    wd=wd, l2=l2_, interpret=interp)
            note_dispatch(SITE, "xla", why)
            g = g.astype(jnp.float32) * gscale
            if l2_:
                g = g + l2_ * p
            upd, mc2, rc2, sc2 = _leaf_moments(
                g, mc, rc, sc, b1=b1, b2=b2, c1=c1, c2=c2, eps=eps)
            if wd:
                upd = upd + wd * p
            return p - lr * upd, mc2, rc2, sc2

        out = jax.tree_util.tree_map(
            leaf, grads, params, st.m_codes, st.r_codes, st.scales,
            *(() if mask is None else (mask(params),)))
        treedef = jax.tree_util.tree_structure(params)
        new_p, m_codes, r_codes, scales_t = jax.tree_util.tree_transpose(
            treedef, jax.tree_util.tree_structure((0, 0, 0, {"m": 0, "r": 0})),
            out)
        scales = jax.tree_util.tree_map(
            lambda m, r: {"m": m, "r": r}, scales_t["m"], scales_t["r"])
        new8 = Adam8bitState(count=count, m_codes=m_codes, r_codes=r_codes,
                             scales=scales)
        return new_p, _advance_state(opt_state, new8)

    return apply
