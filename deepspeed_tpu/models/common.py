"""Shared model-zoo plumbing: logical-axis vocabulary, losses, helpers.

The reference adapts user models via ``module_inject`` policy classes that
record where q/k/v/mlp weights live per architecture
(``deepspeed/module_inject/replace_policy.py``).  The TPU-native zoo instead
*annotates parameters at definition time* with logical axis names; a rules
table maps logical names → mesh axes per parallelism config, which is the
whole TP/FSDP story (no monkey-patching).

Logical axis vocabulary used by every model in the zoo:

==========  ==================================================
``vocab``   embedding-table vocab dim / LM-head output dim
``embed``   model (hidden) dim
``qkv``     fused attention projection output dim (3·embed)
``heads``   attention-head dim groupings (o-proj input)
``mlp``     feed-forward hidden dim
``experts`` MoE expert dim
``layers``  stacked-layer dim introduced by ``nn.scan``
==========  ==================================================
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..telemetry import registry as _registry

# Mapping logical axis name -> mesh axis (or tuple), per parallelism style.
# ``None`` = replicated along that dim.
TP_RULES = {
    "vocab": "tp",
    "qkv": "tp",
    "kv": "tp",            # GQA K/V projection output (LLaMA)
    "heads": "tp",
    "mlp": "tp",
    "experts": "ep",       # expert dim of MoE weights
    "experts_gate": None,  # gate projection output (one logit per expert)
    "embed": None,
    "layers": None,
    "pos": None,
}


def logical_to_mesh_axes(logical_names: tuple, rules: dict) -> P:
    """Translate a tuple of logical names into a PartitionSpec."""
    return P(*(rules.get(name) for name in logical_names))


def resolve_remat_policy(name: str):
    """Config remat-policy name → ``jax.checkpoint_policies`` callable.

    Beyond the stock names:

    - ``"<base>+flash"`` combines the base policy with saving the
      flash-attention kernel's named residuals (``flash_out`` /
      ``flash_lse``; and ``gated_delta_out``, the gated delta rule's
      output): pallas outputs are not dot outputs, so every
      dot-based policy discards them and remat re-runs the whole forward
      kernel inside each backward — "+flash" trades that recompute for
      O(B·S·E) bf16 of saved activations per layer.
    - ``"<base>+offload"`` is the reference's ``cpu_checkpointing``
      (``activation_checkpointing/checkpointing.py:367-460``): saved
      residuals move to pinned host memory and are fetched back during
      backward — HBM cost becomes O(1) activations at the price of
      PCIe/DMA traffic.  jax ships only the no-batch-dims offload
      policy, so for ``dots_saveable``/``checkpoint_dots`` bases the
      batch-dims dots fall back to RECOMPUTE under ``+offload`` (warned
      once); the exact pairings are the ``*_no_batch_dims*`` bases and
      "+flash" named residuals.  Non-dot bases raise (loudly, not as a
      silent no-op)."""
    parts = name.split("+")
    base, extras = parts[0], parts[1:]
    bad = [e for e in extras if e not in ("flash", "offload")]
    if bad:
        raise ValueError(f"unknown remat policy suffix {bad[0]!r} in "
                         f"{name!r} (supported: '+flash', '+offload')")
    offload = "offload" in extras
    cp = jax.checkpoint_policies
    pol = getattr(cp, base, None)
    if pol is None:
        raise ValueError(f"unknown remat policy {base!r}; see "
                         "jax.checkpoint_policies")
    if offload:
        dot_bases = {"dots_saveable", "checkpoint_dots",
                     "dots_with_no_batch_dims_saveable",
                     "checkpoint_dots_with_no_batch_dims"}
        if base in dot_bases:
            if base in ("dots_saveable", "checkpoint_dots"):
                from ..utils.logging import warning_once

                warning_once(
                    f"remat policy {base!r}+offload: jax only offers a "
                    "no-batch-dims offload policy, so dots WITH batch "
                    "dims are recomputed (not saved in HBM, not "
                    "offloaded); use 'dots_with_no_batch_dims_saveable"
                    "+offload' to silence this")
            pol = cp.offload_dot_with_no_batch_dims("device", "pinned_host")
        else:
            raise NotImplementedError(
                f"cpu_checkpointing (+offload) is not defined for remat "
                f"policy {base!r}; use a dot-based policy")
    if "flash" in extras:
        if offload:
            flash_pol = cp.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(_FLASH_RESIDUALS),
                offload_src="device", offload_dst="pinned_host")
        else:
            flash_pol = cp.save_only_these_names(*_FLASH_RESIDUALS)
        pol = cp.save_from_both_policies(pol, flash_pol)
    return pol


# ``gated_delta_out`` (ops/gated_delta.py): a Gated DeltaNet layer's rule
# is no dot output either, and without it the remat runs the whole chunked
# form a second time for the norm and the projection behind it
_FLASH_RESIDUALS = ("flash_out", "flash_lse", "gated_delta_out")


def offloadable_policy_name(name: str) -> str:
    """Policy name with cpu_checkpointing applied: append ``+offload``,
    upgrading a base that saves nothing offloadable to the no-batch-dims
    dot policy first (so the plain reference-style
    ``{"cpu_checkpointing": true}`` config runs).  Shared by the engine
    config path and the functional ``checkpoint()`` API."""
    if "+offload" in name:
        return name
    parts = name.split("+")
    if parts[0] in ("nothing_saveable", "everything_saveable"):
        if parts[0] == "everything_saveable":
            # save-everything -> recompute-most is a real behavioral
            # downgrade, not just a representation change: warn HERE so
            # the functional checkpoint()/_policy() path surfaces it too
            # (the engine config path additionally logs its upgrade)
            from ..utils.logging import warning_once

            warning_once(
                "cpu_checkpointing: remat policy 'everything_saveable' "
                "has no offloadable saveables; downgrading to "
                "'dots_with_no_batch_dims_saveable+offload' — dots with "
                "batch dims (and everything else non-dot) will be "
                "RECOMPUTED, not saved")
        name = "dots_with_no_batch_dims_saveable" + \
            "".join("+" + p for p in parts[1:])
    return name + "+offload"


def param_with_axes(init_fn, names: tuple):
    """Box an initializer with logical partition names (flax metadata)."""
    return nn.with_partitioning(init_fn, names)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    """fp32 LayerNorm over the last dim, cast back to ``x.dtype`` — the
    ONE norm math shared by every zoo family's norm module and by the
    fused decode kernels' XLA fallback (drift here would silently break
    the fused/unfused parity contract)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """fp32 RMSNorm (LLaMA) — see :func:`layer_norm` for the sharing
    contract."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf ** 2, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def declare_fused_proj(module: nn.Module, cfg, name: str, names: tuple,
                       in_features: int, features: int, *,
                       init_std: Optional[float] = None,
                       bias: bool = False):
    """Declare a dense projection's arrays for the fused decode path —
    the (fp kernel | W8A16 codes+scales pair)[, bias] — with EXACTLY the
    param names/shapes/init the family's ``_dense`` would create, so
    checkpoints load interchangeably across the fused and unfused paths
    (one helper, not one copy per family, so they cannot drift)."""
    if getattr(cfg, "w8", False):
        from ..ops.w8 import declare_w8_dense

        w = declare_w8_dense(module, name, names, in_features, features,
                             cfg.w8_group)
    else:
        std = cfg.initializer_range if init_std is None else init_std
        w = module.param(
            name + "_kernel",
            nn.with_partitioning(nn.initializers.normal(std), names),
            (in_features, features), cfg.param_dtype).astype(cfg.dtype)
    if not bias:
        return w
    b = module.param(name + "_bias",
                     nn.with_partitioning(nn.initializers.zeros, (names[-1],)),
                     (features,), cfg.param_dtype)
    return w, b.astype(cfg.dtype)


# Cache-collection leaf names — THE layout contract ``append_kv_cache``
# establishes.  Everything that walks a cache tree structurally (serving
# placement/retire, the paged KV pool in ``inference/kvreuse.py``)
# classifies leaves through :func:`cache_leaf_kind` instead of repeating
# the string match, so a renamed leaf breaks loudly in one place.
KV_CACHE_LEAVES = ("cached_key", "cached_value")
CACHE_INDEX_LEAF = "cache_index"
# present only in PAGED caches (inference/kvreuse.py builds them): the
# per-row page table mapping token range [j*pt, (j+1)*pt) to an arena
# page.  Its presence is how append_kv_cache detects paged mode.
PAGE_TABLE_LEAF = "page_table"


def cache_leaf_kind(path) -> Optional[str]:
    """``"kv"`` (a K/V buffer — per-slot contiguous or the paged arena),
    ``"index"`` (the write head), ``"table"`` (a paged cache's page
    table) or ``None`` (unknown — present only in models outside the
    ``append_kv_cache`` contract) for a cache-collection tree path."""
    key = getattr(path[-1], "key", None)
    if key in KV_CACHE_LEAVES:
        return "kv"
    if key == CACHE_INDEX_LEAF:
        return "index"
    if key == PAGE_TABLE_LEAF:
        return "table"
    return None


def set_cache_index(cache, value):
    """Return ``cache`` with every ``cache_index`` leaf set to ``value``
    (a scalar, possibly traced) — the ONE write-head rewind discipline
    shared by serving placement/retire and the speculative-decoding
    verify step (``inference/specdec.py``).  Rewinding through
    :func:`cache_leaf_kind` instead of ad-hoc string matches means a
    renamed leaf breaks loudly in one place, and the fused/unfused cache
    layouts cannot drift apart."""
    def leaf_fn(path, leaf):
        if cache_leaf_kind(path) == "index":
            return jnp.full_like(leaf, value)
        return leaf

    return jax.tree_util.tree_map_with_path(leaf_fn, cache)


def append_kv_cache(module: nn.Module, k: jax.Array, v: jax.Array,
                    cache_len: int, dtype):
    """Append this step's K/V ``(B, S, H, D)`` into the module's mutable
    ``cache`` collection (the reference softmax.cu context-cache analog)
    and return ``(k_cache, v_cache, cur)`` — the ONE cache layout shared
    by every decoder family and by both the XLA and fused decode paths,
    so it cannot drift between them.

    When the supplied cache carries a ``page_table`` variable (a PAGED
    cache, built by ``inference/kvreuse.py``), the append instead writes
    each row's new K/V into its tail page IN PLACE and returns
    ``(PagedKV, PagedKV, lengths)`` — ``cached_decode_attention``
    dispatches on the type, so every family's call site serves both
    layouts unchanged."""
    B, S, H, D = k.shape
    if module.has_variable("cache", PAGE_TABLE_LEAF):
        return _append_paged_kv_cache(module, k, v, cache_len, dtype)
    ck = module.variable("cache", "cached_key", jnp.zeros,
                         (B, cache_len, H, D), dtype)
    cv = module.variable("cache", "cached_value", jnp.zeros,
                         (B, cache_len, H, D), dtype)
    idx = module.variable("cache", "cache_index",
                          lambda: jnp.zeros((), jnp.int32))
    cur = idx.value
    ck.value = jax.lax.dynamic_update_slice(
        ck.value, k.astype(dtype), (0, cur, 0, 0))
    cv.value = jax.lax.dynamic_update_slice(
        cv.value, v.astype(dtype), (0, cur, 0, 0))
    idx.value = cur + S
    return ck.value, cv.value, cur


def _append_paged_kv_cache(module: nn.Module, k: jax.Array, v: jax.Array,
                           cache_len: int, dtype):
    """Paged append: the cache's ``cached_key``/``cached_value`` leaves
    are the SHARED page arena ``(P, pt, KV, D)``, ``page_table`` is
    ``(B, T)`` and ``cache_index`` is per-row lengths ``(B,)``.  The new
    K/V lands at each row's write head through the table — a scatter of
    O(new tokens), not O(history); the arena updates in place under the
    caller's donation.  Rows whose head has run past their allocation
    (retired slots ticking to a window boundary, bucket-pad overshoot)
    resolve to the table's trailing trash entries — never another slot's
    pages."""
    from ..ops.pallas.paged_attention import PagedKV

    B, S, H, D = k.shape
    ck = module.variable("cache", "cached_key", jnp.zeros,
                         (B, cache_len, H, D), dtype)
    cv = module.variable("cache", "cached_value", jnp.zeros,
                         (B, cache_len, H, D), dtype)
    tab = module.variable("cache", PAGE_TABLE_LEAF,
                          lambda: jnp.zeros((B, 1), jnp.int32))
    idx = module.variable("cache", CACHE_INDEX_LEAF,
                          lambda: jnp.zeros((B,), jnp.int32))
    lengths = idx.value                                     # (B,)
    pt = ck.value.shape[1]
    T = tab.value.shape[-1]
    pos = lengths[:, None] + jnp.arange(S)[None, :]         # (B, S)
    blk = jnp.minimum(pos // pt, T - 1)                     # overshoot →
    pids = jnp.take_along_axis(tab.value, blk, axis=1)      # trash entry
    offs = pos % pt
    ck.value = ck.value.at[pids, offs].set(k.astype(dtype))
    cv.value = cv.value.at[pids, offs].set(v.astype(dtype))
    idx.value = lengths + S
    return (PagedKV(ck.value, tab.value, cache_len),
            PagedKV(cv.value, tab.value, cache_len), lengths)


# ---------------------------------------------------------------------------
# Fused decode-tick dispatch (ops/pallas/decode_layer.py megakernels)
# ---------------------------------------------------------------------------
#
# The single dispatch point the gpt2/llama/neox decode paths share: a
# ``decode_fused`` config flag (or the DS_TPU_DECODE_FUSED env override)
# turns the per-layer decode op chain into two Pallas launches around
# ``decode_attention``; ``decode_fused_plan`` mirrors ``decode_supported``
# — unsupported shapes keep the XLA path and say so in the dispatch
# report (``ops/pallas/spmd.py``).

DECODE_FUSED_ENV = "DS_TPU_DECODE_FUSED"


def _decode_fused_metrics():
    # one set of cells shared with the kernels' own vmap-fold detour
    # counting (see decode_layer.decode_fused_metrics)
    from ..ops.pallas.decode_layer import decode_fused_metrics

    return decode_fused_metrics()


def decode_fused_mode(cfg) -> Optional[str]:
    """``None`` (off) | ``"kernel"`` (TPU) | ``"interpret"`` (non-TPU:
    the interpreter runs the same kernels for CPU-mesh parity/smoke).

    Default flipped ON for TPU hardware after the round-8 e2e sweep (the
    megakernels are also what restores the W8A16 bandwidth win — the
    dequant epilogue fuses into the contraction).  The flip is
    tri-state so the sweep's verdict and explicit opt-outs coexist:

    - config flag ``None`` (families' default): ON on TPU, OFF elsewhere
      (the interpreter runs the same kernels orders of magnitude slower —
      CPU runs must opt in explicitly);
    - config flag ``True``/``False``: explicit, wins over the default;
    - ``DS_TPU_DECODE_FUSED=0/false/off`` force-disables over ANY config
      (operator kill switch); ``=1/true/on`` force-enables over a False
      config flag (and picks interpret mode off-TPU)."""
    env = os.environ.get(DECODE_FUSED_ENV, "").lower()
    if env in ("0", "false", "off"):
        return None
    from ..ops.attention import on_tpu

    flag = getattr(cfg, "decode_fused", None)
    enabled = env in ("1", "true", "on") or flag is True or \
        (flag is None and on_tpu())
    if not enabled:
        return None
    return "kernel" if on_tpu() else "interpret"


def _w8_groups(cfg, k: int) -> int:
    if not getattr(cfg, "w8", False):
        return 1
    from ..ops.w8 import w8_group_size

    return k // w8_group_size(k, int(getattr(cfg, "w8_group", 128)))


def decode_fused_plan(cfg, rows: int, e: int, proj_outs: tuple,
                      f: int, swiglu: bool = False) -> Optional[dict]:
    """Decide whether THIS decode tick takes the megakernel path.

    ``rows``: B·S of the tick (per-slot 1 under the serving vmap — the
    kernels' custom_vmap folds slots back into rows); ``proj_outs``: the
    attention projection widths (one fused panel, or q/k/v for GQA);
    ``f``: MLP hidden width; ``swiglu``: the 3-panel MLP (LLaMA) vs the
    GELU pair.  Returns ``{"interpret": bool}`` or None (caller keeps
    the stock XLA path)."""
    from ..ops.pallas.spmd import note_dispatch

    mode = decode_fused_mode(cfg)
    if mode is None:
        note_dispatch("decode_fused", "xla",
                      "decode_fused_mode: off (config flag, env, or the "
                      "not-a-TPU default)")
        return None
    from ..ops.pallas.decode_layer import (norm_proj_supported,
                                           post_attn_supported)
    # the megakernels carry no shard_map wrapper: a mesh that SHARDS the
    # decode step's operands (tp splits the weight panels, sp/pp are
    # manual regions) keeps the XLA chain, whose collectives the
    # partitioner handles.  Pure data axes are fine — serving state and
    # weights are replicated across them.
    from ..comm.mesh import get_mesh

    mesh = get_mesh(required=False)
    if mesh is not None and any(mesh.shape.get(a, 1) > 1
                                for a in ("tp", "sp", "pp")):
        refusal = "mesh shards the decode operands (tp/sp/pp > 1)"
    else:
        w8 = bool(getattr(cfg, "w8", False))
        itemsize = jnp.dtype(cfg.dtype).itemsize
        if not all(norm_proj_supported(rows, e, n, itemsize, w8,
                                       _w8_groups(cfg, e))
                   for n in proj_outs):
            refusal = (f"norm_proj_supported(rows={rows}, e={e}, "
                       f"n={proj_outs}, w8={w8}) said no")
        elif not post_attn_supported(rows, e, f, itemsize, w8,
                                     _w8_groups(cfg, e), _w8_groups(cfg, f),
                                     swiglu=swiglu):
            refusal = (f"post_attn_supported(rows={rows}, e={e}, f={f}, "
                       f"w8={w8}, swiglu={swiglu}) said no")
        else:
            refusal = None
    if refusal is not None:
        _decode_fused_metrics()[2].inc()
        note_dispatch("decode_fused", "xla", refusal)
        return None
    note_dispatch("decode_fused", mode, "both megakernel guards said yes")
    return {"interpret": mode == "interpret"}


def fused_decode_qkv(x, norm_scale, norm_bias, weight, bias, *, rms: bool,
                     eps: float, interpret: bool):
    """norm → projection for the decode tick on the Pallas kernel.
    ``decode_fused_plan`` has already said the shape is supported, so a
    kernel error propagates."""
    from ..ops.pallas.decode_layer import fused_norm_proj

    out = fused_norm_proj(x, norm_scale, norm_bias, weight, bias,
                          rms=rms, eps=eps, interpret=interpret)
    _decode_fused_metrics()[0].inc()
    return out


def fused_decode_post_attn(y, x, wo, bo, norm_scale, norm_bias,
                           mlp_weights, *, swiglu: bool = False,
                           rms: bool = False, eps: float = 1e-5,
                           exact_gelu: bool = False,
                           parallel_residual: bool = False,
                           interpret: bool = False):
    """o-proj + residual → norm → MLP → residual for the decode tick on
    the Pallas kernel (see :func:`fused_decode_qkv`)."""
    from ..ops.pallas.decode_layer import fused_post_attn

    out = fused_post_attn(y, x, wo, bo, norm_scale, norm_bias,
                          mlp_weights, swiglu=swiglu, rms=rms, eps=eps,
                          exact_gelu=exact_gelu,
                          parallel_residual=parallel_residual,
                          interpret=interpret)
    _decode_fused_metrics()[1].inc()
    return out


def cross_entropy_loss(
    logits: jax.Array,           # (..., V)
    labels: jax.Array,           # (...,) int
    ignore_index: int = -100,
    z_loss: float = 0.0,
    weights: Optional[jax.Array] = None,    # (...,) float, no gradient
    denominator=None,
) -> jax.Array:
    """Mean token cross-entropy with ignore-index masking, fp32 softmax;
    with ``weights`` and ``denominator``, ``sum_i w_i nll_i / denominator``
    (:func:`chunked_lm_loss`'s)."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1).squeeze(-1)
    nll = logz - label_logits
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(logz)
    nll = jnp.where(valid, nll, 0.0)
    if weights is not None:
        nll = nll * jax.lax.stop_gradient(weights.astype(jnp.float32))
    if denominator is not None:
        return nll.sum() / denominator
    count = jnp.maximum(valid.sum(), 1)
    return nll.sum() / count


def _note_head_products(pass_: str, n: int) -> None:
    """Count, at trace time, the head-sized ``(C, E) x (E, V)`` matrix
    products a chunk in the :func:`_fused_ce` rule being traced."""
    _registry.counter(
        "lm_head_products_total",
        "head-sized matrix products a token chunk in a traced rule of the "
        "chunked language-model loss: primal (no gradient asked), forward "
        "and backward rule of its custom_vjp (counted at trace time, not "
        "per call)", labelnames=("pass",)).labels(pass_).inc(n)


@functools.lru_cache(maxsize=None)
def _fused_ce(vocab_size: int, padded_vocab_size: int, ignore_index: int,
              h_dtype, w_dtype, weighted: bool = False, rows: bool = False):
    """Build the custom-vjp chunked cross-entropy core (cached per config
    and the operands' types, which the backward rule rounds to).

    It scans token chunks: each chunk's ``(C, V)`` fp32 logits exist only
    inside its scan step, never O(N·V), and are multiplied out ONCE.  The
    result is one scalar, so its cotangent ``g`` is a scalar and the
    backward is linear in it: the forward rule makes the cotangents at
    ``g = 1`` from the logits it has in hand (``dlog``, ``dh``, ``dW``
    below) and the backward rule only scales them.  Three head-sized
    products a chunk (``lm_head_products_total``: forward 3, backward 0),
    the three a linear layer requires; the primal (``eval_batch``, any
    call without a gradient) runs the one of the loss.  The residuals are
    fp32 ``dh`` ``(N, E)`` and ``dW`` ``(E, V)`` and nothing else — not
    ``h``, not ``W``, no logits: a caller that keeps a ``jax.vjp`` of the
    loss between two calls keeps those two (the engine's ``forward`` /
    ``backward`` / ``step`` path runs loss and gradient in one program),
    and on a mesh the backward reads no weight.

    ``weighted``: a fourth operand, one float32 weight a token (chunked as
    the labels are), multiplies each token's term of the sum and of the
    cotangents (block diffusion's ``1 / t`` on the masked positions, 0
    elsewhere); it has no gradient.  Without it the rule is traced as it
    was: no operand, no multiply.

    ``rows``: the result is ``(sum, nll)`` with each token's OWN negative
    log-likelihood beside the sum, chunked as the labels are, before its
    weight and 0 where the label is ignored (the forward has them in hand: N
    floats, no logits kept).  They are a READING: their cotangent is dropped,
    so a caller whose weights are learned takes the weights' gradient from
    them itself (:func:`chunked_lm_loss`).  Without it the rule is traced as
    it was.
    """
    Vp = padded_vocab_size

    def _chunk(hc, wteT, tc, wc=None):
        """(C, E) × (E, Vp) → fp32 logits (padded vocab columns out of the
        softmax), per-token logz, each token's factor on its cotangent
        (the valid mask, times its weight), safe labels, nll."""
        logits = jnp.dot(hc, wteT, preferred_element_type=jnp.float32)
        if Vp != vocab_size:
            logits = jnp.where(jnp.arange(Vp) < vocab_size, logits,
                               jnp.finfo(jnp.float32).min)
        valid = tc != ignore_index
        safe = jnp.where(valid, tc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        lbl = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        nll = own = jnp.where(valid, logz - lbl, 0.0)
        if wc is not None:
            valid, nll = valid * wc, nll * wc
        if rows:        # the token's own, before its weight
            return logits, logz, valid, safe, own, nll
        return logits, logz, valid, safe, nll

    @jax.custom_vjp
    def ce(hf, wteT, tf, *wf):
        _note_head_products("primal", 1)

        def body(acc, xs):
            out = _chunk(xs[0], wteT, *xs[1:])
            return acc + out[-1].sum(), out[-2] if rows else None

        total, own = jax.lax.scan(body, jnp.float32(0.0), (hf, tf) + wf)
        return (total, own) if rows else total

    def ce_fwd(hf, wteT, tf, *wf):
        _note_head_products("forward", 3)

        def body(carry, xs):
            acc, dwteT = carry
            hc, tc = xs[:2]
            logits, logz, valid, safe, *own, nll = _chunk(hc, wteT, tc,
                                                          *xs[2:])
            p = jnp.exp(logits - logz[:, None])              # softmax rows
            onehot = (jnp.arange(Vp)[None, :] == safe[:, None])
            dlog = (p - onehot) * valid[:, None]             # (C, Vp) fp32
            dlogb = dlog.astype(hc.dtype)       # rounded once, before both
            # d h_c = dlog @ wteT^T ; d wteT += h_c^T @ dlog (fp32 accum)
            dh_c = jax.lax.dot_general(
                dlogb, wteT, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (C, E)
            dwteT = dwteT + jnp.dot(hc.T, dlogb,
                                    preferred_element_type=jnp.float32)
            return (acc + nll.sum(), dwteT), (dh_c, *own)

        (nll_sum, dwteT), (dhs, *own) = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.zeros(wteT.shape, jnp.float32)),
            (hf, tf) + wf)
        return ((nll_sum, *own) if rows else nll_sum), (dhs, dwteT)

    def ce_bwd(res, g):
        _note_head_products("backward", 0)       # the label reads 0
        dhs, dwteT = res
        if rows:        # the rows are a reading: their cotangent is dropped
            g = g[0]
        # each rounded to its operand's type once, after the scale; both
        # finished before either is used: a scan of one trip is inlined,
        # and XLA's scheduler, left free, puts dW off to the end of the step
        # and multiplies the logits out a second time for it rather than
        # keep them through the layers' backward
        dh, dw = jax.lax.optimization_barrier(
            ((g * dhs).astype(h_dtype), (g * dwteT).astype(w_dtype)))
        no_grad = (np.zeros(dhs.shape[:2], jax.dtypes.float0),)
        if weighted:            # the weights are data: no gradient
            no_grad += (jnp.zeros(dhs.shape[:2], jnp.float32),)
        return (dh, dw) + no_grad

    ce.defvjp(ce_fwd, ce_bwd)
    return ce


def chunked_lm_loss(h: jax.Array, wte: jax.Array, labels: jax.Array, *,
                    vocab_size: int, padded_vocab_size: int, chunk: int,
                    dtype, ignore_index: int = -100,
                    weights: Optional[jax.Array] = None,
                    denominator=None, rows: bool = False):
    """Cross-entropy through a head ``wte`` ``(V, E)``, the tied table or an
    untied head's transpose, WITHOUT materializing the (B, S, V) fp32
    logits or their cotangent, and with the logits multiplied out once a
    step (see :func:`_fused_ce`: the forward makes ``dh`` and ``dW``, the
    backward scales them).  Exact same loss as the dense path (fp32
    logsumexp); ``chunk >= B·S`` degenerates to one full-width chunk,
    which keeps the single big MXU matmul but still skips the O(N·V) fp32
    residency (the round-2 ``lax.map`` version serialized 512-row matmuls
    and LOST 17% e2e — this one is measurement-driven: big chunks, custom
    vjp, no per-chunk remat).

    ``weights`` ``(B, S)`` multiplies each token's negative log-likelihood
    (float32; this function stops the gradient at it) and ``denominator``
    divides the sum in place of the count of labelled tokens: ``sum_i w_i
    nll_i / denominator``.  Weight 1 everywhere and the count as denominator
    is the unweighted value bit for bit.

    ``rows``: returns ``(loss, nll)``, ``nll`` ``(B, S)`` float32 each
    token's own negative log-likelihood (before its weight; 0 where the label
    is ignored) with no gradient through it.  A caller whose weights are
    LEARNED adds ``sum_i (w_i - stop_gradient(w_i)) nll_i / denominator``:
    the value stays and the weights get their exact gradient, ``nll_i /
    denominator`` (``models/llama.py``'s exit distribution)."""
    B, S, E = h.shape
    N = B * S
    chunk = min(chunk, N)
    hf = h.reshape(N, E)
    tf = labels.reshape(N)
    pad = (-N) % chunk
    if pad:
        hf = jnp.concatenate([hf, jnp.zeros((pad, E), hf.dtype)])
        tf = jnp.concatenate(
            [tf, jnp.full((pad,), ignore_index, tf.dtype)])
    hf = hf.reshape(-1, chunk, E)
    tf = tf.reshape(-1, chunk)
    wteT = wte.astype(dtype).T        # (E, V)
    operands = (hf, wteT, tf)
    if weights is not None:
        wf = jax.lax.stop_gradient(weights.astype(jnp.float32)).reshape(N)
        if pad:
            wf = jnp.concatenate([wf, jnp.zeros((pad,), wf.dtype)])
        operands += (wf.reshape(-1, chunk),)
    nll_sum = _fused_ce(vocab_size, padded_vocab_size, ignore_index,
                        hf.dtype, wteT.dtype, weights is not None,
                        rows)(*operands)
    if rows:
        nll_sum, own = nll_sum
        own = jax.lax.stop_gradient(own).reshape(-1)[:N].reshape(B, S)
    if denominator is not None:
        loss = nll_sum / denominator
    else:
        loss = nll_sum / jnp.maximum((tf != ignore_index).sum(), 1)
    return (loss, own) if rows else loss


def shift_labels(input_ids: jax.Array, pad_id: int = -100) -> jax.Array:
    """Next-token labels for causal LM: labels[t] = input_ids[t+1]."""
    return jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], pad_id)], axis=1)


class ModelOutput(dict):
    """Attribute-accessible output dict (loss/logits/aux)."""

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError as e:
            raise AttributeError(k) from e
