"""Attention dispatcher: one API, multiple kernels.

The reference ships attention as fused CUDA (training kernel
``csrc/transformer/ds_transformer_cuda.cpp``; inference softmax w/
triangular masking + KV-cache ``csrc/transformer/inference/csrc/softmax.cu``)
and Triton block-sparse (``deepspeed/ops/sparse_attention/``).  Here the
same surface dispatches between:

- ``"jnp"``   — XLA-fused reference implementation (also the CPU-test path)
- ``"flash"`` — Pallas flash-attention kernel (``ops/pallas/flash_attention.py``)
- ``"auto"``  — flash on TPU when shapes allow, else jnp

Shapes follow the JAX convention ``(batch, seq, heads, head_dim)``.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    """Shared backend probe (used by the model zoo's kernel dispatch too).
    Kernels infer interpret mode from it, so a backend that fails to
    initialise raises here instead of reading as "not a TPU"."""
    return jax.devices()[0].platform == "tpu"


def _pick_impl(impl: str, q) -> tuple:
    """``(impl, reason)``: the flash kernel needs a TPU and seq/head_dim
    tiling; ``auto`` picks the XLA path otherwise."""
    if impl != "auto":
        return impl, f"impl={impl!r} requested"
    if not on_tpu():
        return "jnp", "auto: not a TPU"
    if q.shape[1] < 128:
        return "jnp", f"auto: seq {q.shape[1]} < 128"
    if q.shape[3] not in (64, 128, 256):
        return "jnp", f"auto: head_dim {q.shape[3]} not in (64, 128, 256)"
    return "flash", "auto: TPU, seq >= 128, head_dim tiles"


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
) -> jax.Array:
    """Multi-head scaled dot-product attention; returns ``(B, S, H, D)``.

    With ``q_rope`` and ``k_rope`` the score is a sum of two products
    (latent attention): ``q_h · k_h + q_rope_h · k_rope``, the second
    against ONE rotated key that all heads share, scaled by
    ``(D + R) ** -0.5`` unless ``scale`` says otherwise
    (:func:`_two_product_attention`).

    ``impl="ring"`` / ``"ulysses"`` are the sequence-parallel paths: the
    sequence dim must be sharded on the ``sp`` mesh axis (the engine does
    this when ``mesh sp > 1``); a partial-manual shard_map runs the ring /
    all-to-all exchange while every other axis stays automatic.

    Grouped queries and a sliding ``window`` are the flash kernel's and
    the XLA path's; where neither takes them as they are (the sequence-
    parallel and stock-JAX paths, a head_dim the kernel cannot group, heads
    split over ``tp``), k and v are repeated to q's heads first, and a
    window raises rather than run full attention.
    """
    if q_rope is not None:
        if bias is not None or dropout_rate != 0.0 or window is not None:
            raise NotImplementedError(
                "a second score product (q_rope, k_rope) with a bias, "
                "dropout or a sliding window: none is written for it")
        return _two_product_attention(q, k, v, q_rope, k_rope, causal=causal,
                                      scale=scale, impl=impl, mask=mask)
    group = q.shape[2] // k.shape[2]
    if q.shape[2] != group * k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads are no multiple of "
                         f"{k.shape[2]} key-value heads")
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if impl in ("ring", "ulysses", "flash_jax"):
        if window is not None:
            raise NotImplementedError(
                f"impl={impl!r} has no sliding window; 'flash', 'jnp' and "
                f"'auto' do")
        k, v = _repeat_kv(k, v, group)
        group = 1
    if impl in ("ring", "ulysses"):
        return _sp_attention(q, k, v, causal=causal, scale=scale, kind=impl)
    if impl == "skip":
        # measurement probe ONLY: attention replaced by identity-on-q so
        # an e2e A/B isolates the attention kernel's true step-time share
        # (isolated kernel probes mislead — see BENCH_NORTHSTAR.md).
        # Gated: outside the probe harness this silently produces garbage.
        if not os.environ.get("DS_TPU_ALLOW_SKIP_ATTN"):
            raise ValueError(
                "attn impl='skip' disables attention entirely (identity on "
                "q) and exists only for step-time A/B probes; set "
                "DS_TPU_ALLOW_SKIP_ATTN=1 if that is really what you want")
        return q
    from .pallas.spmd import kernel_mesh_plan, note_dispatch

    impl, reason = _pick_impl(impl, q)
    if impl in ("flash", "flash_jax"):
        if bias is not None or mask is not None or dropout_rate != 0.0:
            reason = "bias/mask/dropout need the XLA path"
        else:
            out = _flash_spmd(q, k, v, causal=causal, scale=scale,
                              window=window, flash_opts=flash_opts) \
                if impl == "flash" \
                else _flash_jax(q, k, v, causal=causal, scale=scale)
            if out is not None:
                verdict, axes = kernel_mesh_plan(q.shape[0], heads=q.shape[2],
                                                 allow_tp=True)
                plan = "one device" if verdict == "direct" \
                    else f"shard_map over batch axes {axes}"
                if impl == "flash":     # the layout a shard's kernels run
                    from ..comm.mesh import get_mesh
                    from .pallas.flash_attention import flash_lanes

                    tp = 1 if verdict == "direct" \
                        else get_mesh().shape.get("tp", 1)
                    plan += "; " + flash_lanes(q.shape[2] // tp,
                                               q.shape[3]).reason
                    if window is not None:
                        plan += f"; window {window}"
                    if group > 1:
                        plan += (f"; {group} query heads a key-value head"
                                 if _grouped_in_kernel(q, k, tp) else
                                 f"; k and v repeated {group}x to q's heads")
                note_dispatch("attention", impl, f"{reason}; {plan}")
                return out
            reason = "kernel_mesh_plan refused the mesh"
    note_dispatch("attention", "jnp", reason)
    return _jnp_attention(q, k, v, causal=causal, bias=bias, mask=mask,
                          dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                          scale=scale, window=window)


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


def _block_diffusion_flash(q, k, v, *, block, scale, interpret=False):
    """The rows as the projections wrote them through one flash call a
    pass (``ops/pallas/flash_attention.py flash_attention_halves``): the
    noisy queries' own blocks are a tile of the kernels' schedule, folded
    into the same online softmax as the clean keys' tiles, so nothing is
    sliced, merged or concatenated around the kernels."""
    from .pallas.flash_attention import (flash_attention_halves,
                                         grouped_in_kernel)

    if not grouped_in_kernel(q.shape[3]):
        k, v = _repeat_kv(k, v, q.shape[2] // k.shape[2])
    return flash_attention_halves(q, k, v, block=block, scale=scale,
                                  interpret=interpret)


def block_diffusion_attention(q, k, v, *, block: int,
                              scale: Optional[float] = None,
                              impl: str = "auto",
                              interpret: bool = False) -> jax.Array:
    """Attention of block-diffusion training over ``[noisy ; clean]``:
    ``q`` ``(B, 2L, H, D)``, ``k`` and ``v`` ``(B, 2L, KV, D)``, the mask
    :func:`block_diffusion_mask`; returns ``(B, 2L, H, D)``.

    ``"flash"`` (``"auto"`` on a TPU where the shapes tile) runs all
    ``2L`` rows through one flash call a pass
    (:func:`_block_diffusion_flash`), whose tile schedule is the mask's four
    quadrants: no ``2L x 2L`` score exists, no score is computed outside
    the kernels, grouped queries stay at their key-value heads in the
    kernel, void tiles run no code and full tiles build no mask.
    ``"jnp"`` (the CPU, tests) applies the dense mask to XLA scores.
    Heads over ``tp`` and sequence-parallel forms are not written: ``tp``
    takes the XLA path, ``ring`` / ``ulysses`` raise."""
    from .pallas.flash_attention import _diag, flash_lanes
    from .pallas.spmd import kernel_mesh_plan, note_dispatch

    B, S2, H, D = q.shape
    if S2 % 2 or (S2 // 2) % block:
        raise ValueError(f"{S2} positions are not two halves of whole "
                         f"blocks of {block}")
    if H % k.shape[2]:
        raise ValueError(f"{H} query heads are no multiple of "
                         f"{k.shape[2]} key-value heads")
    if impl not in ("auto", "flash", "jnp"):
        raise NotImplementedError(
            f"impl={impl!r} with the block-diffusion mask: 'auto', 'flash' "
            f"and 'jnp' are written (no sequence-parallel form)")
    _diag(block, False)         # a divisor of 128, for every path alike
    if scale is None:
        scale = D ** -0.5
    L = S2 // 2
    how = f"impl={impl!r} requested"
    if impl == "auto":
        impl, how = _pick_impl(impl, q[:, :L])
    if impl == "flash":
        verdict, axes = kernel_mesh_plan(B, heads=H, allow_tp=False)
        if verdict is not None:
            plan = "one device" if verdict == "direct" \
                else f"shard_map over batch axes {axes}"
            group = H // k.shape[2]
            note_dispatch(
                "attention", "flash",
                f"{how}; {plan}; {flash_lanes(H, D).reason}; block diffusion "
                f"over [noisy ; clean], block length {block}"
                + (f"; {group} query heads a key-value head" if group > 1
                   else ""))
            kern = functools.partial(_block_diffusion_flash, block=block,
                                     scale=scale, interpret=interpret)
            if verdict == "direct":
                return kern(q, k, v)
            return _shard_over_batch(kern, axes, 3)(q, k, v)
        how = "kernel_mesh_plan refused the mesh"
    note_dispatch("attention", "jnp",
                  f"{how}; block diffusion over [noisy ; clean], block "
                  f"length {block}, dense mask")
    return _jnp_attention(q, k, v, causal=False, bias=None,
                          mask=block_diffusion_mask(L, block)[None, None],
                          dropout_rate=0.0, dropout_rng=None, scale=scale)


def _two_product_attention(q, k, v, q_rope, k_rope, *, causal, scale, impl,
                           mask=None, interpret=False):
    """Latent attention's dispatch: the two-product flash kernels
    (``ops/pallas/flash_attention.py flash_attention_mla``) on a TPU where
    the widths tile (values as wide as the per-head keys, a multiple of
    128 lanes; rope heads that fill 128-lane blocks) and the operands are
    one device's own or split over batch axes alone; float32-softmax XLA
    otherwise.  ``kernel_dispatch_total{site="attention"}`` says which."""
    from .pallas.flash_attention import flash_attention_mla, mla_lanes
    from .pallas.spmd import kernel_mesh_plan, note_dispatch

    B, S, H, D = q.shape
    R = q_rope.shape[-1]
    if scale is None:
        scale = (D + R) ** -0.5
    lanes = mla_lanes(H, D, R, v.shape[-1])
    if impl not in ("auto", "flash", "jnp"):
        raise NotImplementedError(
            f"impl={impl!r} with a second score product (q_rope, k_rope): "
            f"'auto', 'flash' and 'jnp' are written")
    if impl == "jnp":
        reason = "impl='jnp' requested"
    elif mask is not None:
        reason = "a mask needs the XLA path"
    elif lanes is None:
        reason = (f"no two-product kernel at {D} + {R} rope lanes, v "
                  f"{v.shape[-1]}")
    elif impl == "auto" and not (interpret or on_tpu()):
        reason = "auto: not a TPU"
    elif impl == "auto" and S < 128:
        reason = f"auto: seq {S} < 128"
    else:
        verdict, axes = kernel_mesh_plan(B, heads=H, allow_tp=False)
        kern = functools.partial(flash_attention_mla, causal=causal,
                                 scale=scale, interpret=interpret)
        if verdict is not None:
            how = "auto: TPU, seq >= 128, head_dim tiles" if impl == "auto" \
                else f"impl={impl!r} requested"
            plan = "one device" if verdict == "direct" \
                else f"shard_map over batch axes {axes}"
            note_dispatch("attention", "flash",
                          f"{how}; {plan}; {lanes.reason}")
            if verdict == "direct":
                return kern(q, q_rope, k, k_rope, v)
            return _shard_over_batch(kern, axes, 5)(q, q_rope, k, k_rope, v)
        reason = "kernel_mesh_plan refused the mesh"
    note_dispatch("attention", "jnp", reason)
    s = (jnp.einsum("bshd,bthd->bhst", q, k,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshr,btr->bhst", q_rope, k_rope[:, :, 0],
                      preferred_element_type=jnp.float32)) * scale
    neg = jnp.finfo(s.dtype).min
    if causal:
        T = k.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, T), bool), k=T - S)[None, None],
                      s, neg)
    if mask is not None:
        s = jnp.where(mask, s, neg)
    return jnp.einsum("bhst,bthd->bshd",
                      jax.nn.softmax(s, axis=-1).astype(v.dtype), v)


def _shard_over_batch(kern, batch_axes, n_args: int):
    """Full-manual shard_map of a kernel over ``(B, S, ·, ·)`` operands:
    the batch over ``batch_axes``, everything else whole."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import get_mesh

    spec = P(batch_axes if batch_axes else None, None, None, None)
    return jax.shard_map(kern, mesh=get_mesh(), in_specs=(spec,) * n_args,
                         out_specs=spec, check_vma=False)


def _repeat_kv(k, v, group: int):
    """k and v at q's heads: each key-value head ``group`` times over."""
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _grouped_in_kernel(q, k, tp: int) -> bool:
    """Whether the flash kernel takes k and v at their own heads: one head
    a lane block, and whole key-value heads on every ``tp`` rank."""
    from .pallas.flash_attention import grouped_in_kernel

    return grouped_in_kernel(q.shape[3]) and k.shape[2] % tp == 0


def _flash_spmd(q, k, v, *, causal, scale, window=None, interpret=False,
                flash_opts=None):
    """Flash kernel, SPMD-correct: on a multi-device mesh the pallas_call is
    opaque to the partitioner (XLA would gather operands), so shard_map it
    over the batch (dp/fsdp/ep) and head (tp) axes — attention is
    independent along both.  Returns None when ``kernel_mesh_plan``
    refuses the mesh (caller takes the XLA path); past that guard the
    kernel's errors propagate."""
    from functools import partial

    from .pallas.flash_attention import flash_attention
    from .pallas.spmd import kernel_mesh_plan

    from ..comm.mesh import get_mesh

    B, S, H, D = q.shape
    verdict, batch_axes = kernel_mesh_plan(B, heads=H, allow_tp=True)
    if verdict is None:
        return None
    kern = partial(flash_attention, causal=causal, scale=scale,
                   interpret=interpret, **(flash_opts or {}))
    if window is not None:      # an absent keyword leaves old traces alone
        kern = partial(kern, window=window)
    tp = 1 if verdict == "direct" else get_mesh().shape.get("tp", 1)
    if not _grouped_in_kernel(q, k, tp):
        k, v = _repeat_kv(k, v, H // k.shape[2])
    if verdict == "direct":
        return kern(q, k, v)
    return _shard_over_batch_heads(kern, batch_axes)(q, k, v)


def _shard_over_batch_heads(kern, batch_axes):
    """Full-manual shard_map of a ``(B, S, H, D)`` attention kernel: batch
    over ``batch_axes``, heads over ``tp``.  The kernel has no
    collectives; unused axes replicate."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import get_mesh

    mesh = get_mesh()
    tp = mesh.shape.get("tp", 1)
    spec = P(batch_axes if batch_axes else None, None,
             "tp" if tp > 1 else None, None)
    return jax.shard_map(kern, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _flash_jax(q, k, v, *, causal, scale):
    """Stock JAX/Pallas TPU flash kernel
    (``jax.experimental.pallas.ops.tpu.flash_attention``) as an alternate
    backend — same dispatch contract as :func:`_flash_spmd` (shard_map
    over batch/head axes on active meshes; None when the mesh plan
    refuses)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as jax_flash)

    from .pallas.spmd import kernel_mesh_plan

    B, S, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    verdict, batch_axes = kernel_mesh_plan(B, heads=H, allow_tp=True)
    if verdict is None:
        return None

    def kern(q, k, v):
        out = jax_flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal,
                        sm_scale=scale)
        return out.transpose(0, 2, 1, 3)

    if verdict == "direct":
        return kern(q, k, v)
    return _shard_over_batch_heads(kern, batch_axes)(q, k, v)


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
                   scale, window=None):
    b, s_q, h, d = q.shape
    s_k, kv = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    # fp32 softmax for stability (the reference kernel does fp32 accumulation
    # in its fused softmax, softmax_kernels.cu)
    if kv != h:     # grouped queries: a key-value head's queries together
        scores = jnp.einsum("bskgd,btkd->bkgst",
                            q.reshape(b, s_q, kv, h // kv, d), k,
                            preferred_element_type=jnp.float32
                            ).reshape(b, h, s_q, s_k) * scale
    else:
        scores = jnp.einsum("bshd,bthd->bhst", q, k,
                            preferred_element_type=jnp.float32) * scale
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
