"""Attention dispatcher: one API, multiple kernels.

The reference ships attention as fused CUDA (training kernel
``csrc/transformer/ds_transformer_cuda.cpp``; inference softmax w/
triangular masking + KV-cache ``csrc/transformer/inference/csrc/softmax.cu``)
and Triton block-sparse (``deepspeed/ops/sparse_attention/``).  Here the
same surface dispatches between the implementations :data:`IMPLS` names,
and no other:

- ``"jnp"``     — XLA-fused reference implementation (also the CPU-test path)
- ``"flash"``   — Pallas flash-attention kernels (``ops/pallas/flash_attention.py``)
- ``"auto"``    — flash on a TPU when shapes allow, else jnp
- ``"ring"``    — sequence-parallel ring exchange over the ``sp`` mesh axis
- ``"ulysses"`` — sequence-parallel all-to-all over the ``sp`` mesh axis

What is asked of :func:`dot_product_attention` is data, not a choice of
entry point: causal or not, a sliding ``window``, block diffusion's mask
over ``[noisy ; clean]`` rows (``block_diffusion``, a block length), a
second score product (``q_rope``, ``k_rope``), a dense ``mask`` / ``bias`` /
dropout (XLA only).  One function decides which implementation runs a call
and tells ``kernel_dispatch_total{site="attention"}`` (:func:`_plan`).

Shapes follow the JAX convention ``(batch, seq, heads, head_dim)``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

IMPLS = ("auto", "flash", "jnp", "ring", "ulysses")


def on_tpu() -> bool:
    """Shared backend probe (used by the model zoo's kernel dispatch too).
    Kernels infer interpret mode from it, so a backend that fails to
    initialise raises here instead of reading as "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


class _Plan(NamedTuple):
    impl: str           # "flash" or "jnp"
    reason: str         # kernel_dispatch_total's label
    # flash: a shard_map over these mesh axes (heads over ``tp``), or None:
    # the operands are one device's own
    batch_axes: Optional[tuple] = None
    tp: int = 1
    # flash: k and v go to q's heads first (the kernels group whole heads of
    # a multiple of 128 lanes, and whole key-value heads a ``tp`` rank)
    repeat_kv: bool = False


def _plan(impl: str, q, k, v, *, window, block, q_rope, dense: bool,
          interpret: bool) -> _Plan:
    """Which implementation runs this attention, and why: the flash kernels
    where they were asked for (``"auto"``: on a TPU, at a sequence of 128
    and more, at widths that tile), take the form and know the operands'
    sharding (``kernel_mesh_plan``: one device's own, or split over batch
    axes, heads over ``tp`` where the form is written for it: the plain
    one); XLA otherwise.  The one caller notes the verdict."""
    from .pallas.flash_attention import (flash_lanes, grouped_in_kernel,
                                         mla_lanes)
    from .pallas.spmd import kernel_mesh_plan, mesh_said

    B, S, H, D = q.shape
    group = H // k.shape[2]
    form = "" if block is None else \
        f"; block diffusion over [noisy ; clean], block length {block}"

    def xla(reason):
        return _Plan("jnp", reason + (form and form + ", dense mask"))

    how = f"impl={impl!r} requested"
    if impl == "jnp":
        return xla(how)
    if impl == "auto":
        seq = S if block is None else S // 2
        if not (interpret or on_tpu()):
            return xla("auto: not a TPU")
        if seq < 128:
            return xla(f"auto: seq {seq} < 128")
        if q_rope is None and D not in (64, 128, 256):
            return xla(f"auto: head_dim {D} not in (64, 128, 256)")
        how = "auto: TPU, seq >= 128, head_dim tiles"
    if q_rope is not None and mla_lanes(H, D, q_rope.shape[-1],
                                        v.shape[-1]) is None:
        return xla(f"no two-product kernel at {D} + {q_rope.shape[-1]} rope "
                   f"lanes, v {v.shape[-1]}")
    if dense:
        return xla("bias/mask/dropout need the XLA path")
    plain = block is None and q_rope is None    # written for heads over tp
    verdict, axes = kernel_mesh_plan(B, heads=H, allow_tp=plain)
    if verdict is None:
        return xla(mesh_said(verdict, axes))
    tp = 1
    if verdict == "shard":
        from ..comm.mesh import get_mesh

        tp = get_mesh().shape.get("tp", 1)
    said = [how, mesh_said(verdict, axes),
            flash_lanes(H // tp, D).reason]     # the layout a shard runs
    if q_rope is not None:
        said.append(f"{D} + {q_rope.shape[-1]} shared rope lanes, "
                    f"v {v.shape[-1]}")
    if window is not None:
        said.append(f"window {window}")
    reason = "; ".join(said) + form
    repeat = group > 1 and not (grouped_in_kernel(D) and k.shape[2] % tp == 0)
    if group > 1:
        reason += f"; k and v repeated {group}x to q's heads" if repeat \
            else f"; {group} query heads a key-value head"
    return _Plan("flash", reason, axes if verdict == "shard" else None, tp,
                 repeat)


def dot_product_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, KV, D): KV divides H, query head h reads h // (H // KV)
    v: jax.Array,  # (B, T, KV, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,           # keep keys < window back (causal)
    bias: Optional[jax.Array] = None,       # broadcastable to (B, H, S, T)
    mask: Optional[jax.Array] = None,       # bool, True = attend
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
    flash_opts: Optional[dict] = None,
    q_rope: Optional[jax.Array] = None,     # (B, S, H, R)
    k_rope: Optional[jax.Array] = None,     # (B, T, 1, R): one key, all heads
    block_diffusion: Optional[int] = None,  # block length; rows [noisy ; clean]
    interpret: bool = False,                # the kernels' interpreter (tests)
) -> jax.Array:
    """Multi-head scaled dot-product attention; returns ``(B, S, H, D)``.

    With ``q_rope`` and ``k_rope`` the score is a sum of two products
    (latent attention): ``q_h · k_h + q_rope_h · k_rope``, the second
    against ONE rotated key that all heads share, scaled by
    ``(D + R) ** -0.5`` unless ``scale`` says otherwise.

    With ``block_diffusion`` the rows are the two halves ``[noisy ; clean]``
    of one sequence under :func:`block_diffusion_mask`
    (:func:`block_diffusion_attention` checks the rows first).

    ``impl="ring"`` / ``"ulysses"`` are the sequence-parallel paths: the
    sequence dim must be sharded on the ``sp`` mesh axis (the engine does
    this when ``mesh sp > 1``); a partial-manual shard_map runs the ring /
    all-to-all exchange while every other axis stays automatic.

    Grouped queries and a sliding ``window`` are the flash kernel's and
    the XLA path's; where neither takes them as they are (the sequence-
    parallel paths, a head_dim the kernel cannot group, heads split over
    ``tp``), k and v are repeated to q's heads first, and a window raises
    rather than run full attention.
    """
    from .pallas.spmd import note_dispatch

    if impl not in IMPLS:
        raise ValueError(f"attention impl {impl!r}: one of {IMPLS}")
    dense = bias is not None or mask is not None or dropout_rate != 0.0
    if q_rope is not None and (bias is not None or dropout_rate != 0.0
                               or window is not None
                               or block_diffusion is not None):
        raise NotImplementedError(
            "a second score product (q_rope, k_rope) with a bias, dropout, "
            "a sliding window or block diffusion: none is written for it")
    if block_diffusion is not None and (dense or window is not None):
        raise NotImplementedError(
            "block diffusion with a mask, bias, dropout or a sliding "
            "window: none is written for it")
    group = q.shape[2] // k.shape[2]
    if q.shape[2] != group * k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads are no multiple of "
                         f"{k.shape[2]} key-value heads")
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if impl in ("ring", "ulysses"):
        if window is not None or q_rope is not None \
                or block_diffusion is not None:
            raise NotImplementedError(
                f"impl={impl!r} has no sliding window, second score product "
                f"or block-diffusion mask (no sequence-parallel form); "
                f"'flash', 'jnp' and 'auto' do")
        return _sp_attention(q, *_repeat_kv(k, v, group), causal=causal,
                             scale=scale, kind=impl)
    plan = _plan(impl, q, k, v, window=window, block=block_diffusion,
                 q_rope=q_rope, dense=dense, interpret=interpret)
    note_dispatch("attention", plan.impl, plan.reason)
    if plan.impl == "jnp":
        if block_diffusion is not None:
            causal = False
            mask = block_diffusion_mask(q.shape[1] // 2,
                                        block_diffusion)[None, None]
        return _jnp_attention(q, k, v, causal=causal, bias=bias, mask=mask,
                              dropout_rate=dropout_rate,
                              dropout_rng=dropout_rng, scale=scale,
                              window=window, q_rope=q_rope, k_rope=k_rope)
    from .pallas.flash_attention import (flash_attention,
                                         flash_attention_halves)

    opts = dict(scale=scale, interpret=interpret, **(flash_opts or {}))
    if block_diffusion is not None:
        # all 2L rows as the projections wrote them through one flash call
        # a pass: the noisy queries' own blocks are a tile of the kernels'
        # schedule, so nothing is sliced, merged or concatenated around them
        kern = functools.partial(flash_attention_halves,
                                 block=block_diffusion, **opts)
    else:
        kern = functools.partial(flash_attention, causal=causal,
                                 window=window, **opts)
    if plan.repeat_kv:
        k, v = _repeat_kv(k, v, group)
    rope = () if q_rope is None else (q_rope, k_rope)

    def run(q, k, v, *rope):    # by position, as shard_map hands them
        return kern(q, k, v, **dict(zip(("q_rope", "k_rope"), rope)))

    if plan.batch_axes is None:
        return run(q, k, v, *rope)
    return _shard_over_batch(run, plan.batch_axes, plan.tp,
                             3 + len(rope))(q, k, v, *rope)


def block_diffusion_mask(length: int, block: int):
    """The ``(2L, 2L)`` boolean mask of block-diffusion training
    (arXiv:2503.09573) over the positions ``[noisy ; clean]`` of one row,
    True = attend, with ``b(i) = i // block``: a noisy query keeps the
    noisy keys of its own block and the clean keys of earlier blocks; a
    clean query keeps the clean keys of its own and earlier blocks and no
    noisy key."""
    b = jnp.arange(length) // block
    qb, kb = b[:, None], b[None, :]
    none = jnp.zeros((length, length), bool)
    return jnp.block([[qb == kb, qb > kb], [none, qb >= kb]])


def block_diffusion_attention(q, k, v, *, block: int,
                              scale: Optional[float] = None,
                              impl: str = "auto",
                              interpret: bool = False) -> jax.Array:
    """Attention of block-diffusion training over ``[noisy ; clean]``:
    ``q`` ``(B, 2L, H, D)``, ``k`` and ``v`` ``(B, 2L, KV, D)``, the mask
    :func:`block_diffusion_mask`; returns ``(B, 2L, H, D)``.  The rows are
    checked here; :func:`dot_product_attention` does the rest.

    ``"flash"`` (``"auto"`` on a TPU where the shapes tile) runs all
    ``2L`` rows through one flash call a pass
    (``ops/pallas/flash_attention.py flash_attention_halves``), whose tile
    schedule is the mask's four quadrants: no ``2L x 2L`` score exists, no
    score is computed outside the kernels, grouped queries stay at their
    key-value heads in the kernel, void tiles run no code and full tiles
    build no mask.  ``"jnp"`` (the CPU, tests) applies the dense mask to
    XLA scores.  Heads over ``tp`` and sequence-parallel forms are not
    written: ``tp`` takes the XLA path, ``ring`` / ``ulysses`` raise."""
    from .pallas.flash_attention import block_length

    S2 = q.shape[1]
    if S2 % 2 or (S2 // 2) % block:
        raise ValueError(f"{S2} positions are not two halves of whole "
                         f"blocks of {block}")
    block_length(block)         # a divisor of 128, for every path alike
    return dot_product_attention(q, k, v, causal=True, scale=scale,
                                 impl=impl, block_diffusion=block,
                                 interpret=interpret)


def _shard_over_batch(kern, batch_axes, tp: int, n_args: int):
    """Full-manual shard_map of a kernel over ``n_args`` operands ``(B, S,
    heads, ·)``: the batch over ``batch_axes``, heads over ``tp`` where the
    plan let a mesh with one through, everything else whole.  The kernel
    has no collectives; unused axes replicate."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import get_mesh

    spec = P(batch_axes if batch_axes else None, None,
             "tp" if tp > 1 else None, None)
    return jax.shard_map(kern, mesh=get_mesh(), in_specs=(spec,) * n_args,
                         out_specs=spec, check_vma=False)


def _repeat_kv(k, v, group: int):
    """k and v at q's heads: each key-value head ``group`` times over."""
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def cached_decode_attention(q, k_cache, v_cache, cur, attn_mask=None, *,
                            scale=None):
    """Attention over an appended KV cache (decode mode) — the ONE
    dispatch shared by every decoder family (gpt2/llama/gptj/neox):
    single-token ticks ride the fused Pallas kernel when supported
    (GQA-aware — ``k_cache`` may hold fewer heads than ``q``), otherwise
    a masked jnp attention over positions ``<= cur + t``.

    ``q``: (B, S, H, D) new queries; ``k_cache``/``v_cache``:
    (B, S_max, KV, D) caches AFTER the append; ``cur``: scalar cache
    index before the append.

    A PAGED cache (``append_kv_cache``'s paged branch returns
    :class:`~.pallas.paged_attention.PagedKV` carriers and per-row
    ``cur``) dispatches to the paged kernel — attention reads the page
    arena in place, no contiguous materialization — with the
    gather-read XLA reference as the fallback for multi-token queries,
    masks, and non-TPU backends.
    """
    from .pallas.paged_attention import (PagedKV, paged_decode_attention,
                                         paged_decode_supported,
                                         paged_reference_attention)

    from .pallas.spmd import note_dispatch

    B, S, H, D = q.shape
    if S != 1 or attn_mask is not None:
        refusal = "multi-token query or attention mask"
    elif not on_tpu():
        refusal = "not a TPU"
    else:
        refusal = None
    if isinstance(k_cache, PagedKV):
        pages_k, table = k_cache.pages, k_cache.table
        pages_v = v_cache.pages
        pt, KV = pages_k.shape[1], pages_k.shape[2]
        lengths = cur + S          # (B,) valid tokens after the append
        if refusal is None and not paged_decode_supported(
                pt, KV, D, pages_k.dtype.itemsize):
            refusal = f"paged_decode_supported({pt}, {KV}, {D}) said no"
        if refusal is None:
            note_dispatch("decode_attention", "paged_kernel",
                          "single-token tick on a TPU, page geometry fits")
            return paged_decode_attention(q, pages_k, pages_v, table,
                                          lengths, scale=scale)
        note_dispatch("decode_attention", "paged_reference", refusal)
        return paged_reference_attention(q, pages_k, pages_v, table,
                                         lengths, scale=scale,
                                         attn_mask=attn_mask,
                                         s_kv=k_cache.cache_len)
    S_max, KV = k_cache.shape[1], k_cache.shape[2]
    from .pallas.decode_attention import decode_attention, decode_supported

    if refusal is None and not decode_supported(
            S_max, KV, D, k_cache.dtype.itemsize):
        refusal = f"decode_supported({S_max}, {KV}, {D}) said no"
    if refusal is None:
        note_dispatch("decode_attention", "kernel",
                      "single-token tick on a TPU, a KV block fits VMEM")
        return decode_attention(q, k_cache, v_cache, cur + 1, scale=scale)
    note_dispatch("decode_attention", "jnp", refusal)
    if KV != H:   # GQA fallback: repeat KV heads for the dense path
        rep = H // KV
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    q_pos = cur + jnp.arange(S)[:, None]
    k_pos = jnp.arange(S_max)[None, :]
    mask = (k_pos <= q_pos)[None, None, :, :]
    if attn_mask is not None:
        mask = jnp.logical_and(mask, attn_mask)
    return _jnp_attention(q, k_cache, v_cache, causal=False, bias=None,
                          mask=mask, dropout_rate=0.0, dropout_rng=None,
                          scale=scale)


def sp_flash_spec(mesh, batch_size: int, heads: int):
    """PartitionSpec for running the flash ring engine under a FULL-manual
    shard_map when ``sp`` coexists with other active mesh axes: batch over
    the active data axes, heads over ``tp``.  None = not runnable (pp
    nesting, or an axis that doesn't divide its dim) — caller falls back
    to the partial-manual jnp ring.  Policy comes from the shared
    ``kernel_mesh_plan`` (sp-aware mode)."""
    from jax.sharding import PartitionSpec as P

    from .pallas.spmd import kernel_mesh_plan

    verdict, batch_axes = kernel_mesh_plan(batch_size, heads=heads,
                                           allow_tp=True, sp=True, mesh=mesh)
    if verdict != "shard":
        return None
    tp = mesh.shape.get("tp", 1)
    return P(batch_axes if batch_axes else None, "sp",
             "tp" if tp > 1 else None, None)


def _sp_attention(q, k, v, *, causal, scale, kind):
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import get_mesh
    from .pallas.spmd import note_dispatch

    mesh = get_mesh(required=False)
    if mesh is None or mesh.shape.get("sp", 1) == 1:
        # no sequence-parallel axis: plain attention
        note_dispatch("attention", "jnp", f"impl={kind!r} without an sp axis")
        return _jnp_attention(q, k, v, causal=causal, bias=None, mask=None,
                              dropout_rate=0.0, dropout_rng=None, scale=scale)
    from ..parallel.ring_attention import (ring_attention,
                                           ring_attention_flash,
                                           ulysses_attention)

    if on_tpu() and q.shape[3] in (64, 128, 256):
        # flash block engine (pallas): needs full-manual shard_map, so
        # every ACTIVE axis must appear in the specs — batch dims over the
        # data axes, heads over tp (a pallas_call under auto-sharded axes
        # is opaque to the partitioner).  pp refuses: pipeline code is
        # already inside its own manual shard_map.  For "ulysses" the
        # heads additionally split by sp (all-to-all inside), so H must
        # divide tp*sp.
        spec = sp_flash_spec(mesh, q.shape[0], q.shape[2])
        sp_n = mesh.shape.get("sp", 1)
        tp_n = mesh.shape.get("tp", 1)
        if kind == "ulysses" and q.shape[2] % (sp_n * tp_n):
            spec = None
        if spec is not None:
            from .pallas.flash_attention import flash_attention

            if kind == "ring":
                fn = partial(ring_attention_flash, axis_name="sp",
                             causal=causal, scale=scale)
            else:
                # Ulysses with the flash kernel as the full-sequence
                # engine: inside the manual region each rank holds the
                # whole sequence on H/(sp·tp) heads after the all-to-all
                fn = partial(ulysses_attention, axis_name="sp",
                             causal=causal, scale=scale,
                             attend_fn=flash_attention)
            note_dispatch("attention", f"{kind}+flash",
                          "TPU, head_dim tiles, sp_flash_spec covers the "
                          "mesh")
            mapped = jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
            return mapped(q, k, v)
    note_dispatch("attention", f"{kind}+jnp",
                  "not a TPU, head_dim does not tile, or sp_flash_spec "
                  "refused the mesh")
    fn = ring_attention if kind == "ring" else ulysses_attention
    mapped = jax.shard_map(
        partial(fn, axis_name="sp", causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        axis_names={"sp"},
        check_vma=False,
    )
    return mapped(q, k, v)


def _jnp_attention(q, k, v, *, causal, bias, mask, dropout_rate, dropout_rng,
                   scale, window=None, q_rope=None, k_rope=None):
    b, s_q, h, d = q.shape
    s_k, kv = k.shape[1], k.shape[2]
    if scale is None:
        scale = (d if q_rope is None else d + q_rope.shape[-1]) ** -0.5
    # fp32 softmax for stability (the reference kernel does fp32 accumulation
    # in its fused softmax, softmax_kernels.cu)
    if kv != h:     # grouped queries: a key-value head's queries together
        scores = jnp.einsum("bskgd,btkd->bkgst",
                            q.reshape(b, s_q, kv, h // kv, d), k,
                            preferred_element_type=jnp.float32
                            ).reshape(b, h, s_q, s_k)
    else:
        scores = jnp.einsum("bshd,bthd->bhst", q, k,
                            preferred_element_type=jnp.float32)
    if q_rope is not None:      # one rotated key for all heads
        scores = scores + jnp.einsum("bshr,btr->bhst", q_rope, k_rope[:, :, 0],
                                     preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    neg = jnp.finfo(scores.dtype).min
    if causal:
        causal_mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:      # ... and less than ``window`` back
            causal_mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                                     k=s_k - s_q - window)
        scores = jnp.where(causal_mask[None, None, :, :], scores, neg)
    if mask is not None:
        scores = jnp.where(mask, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    if kv != h:
        return jnp.einsum("bkgst,btkd->bskgd",
                          probs.reshape(b, kv, h // kv, s_q, s_k), v
                          ).reshape(b, s_q, h, d)
    return jnp.einsum("bhst,bthd->bshd", probs, v)
