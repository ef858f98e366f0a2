"""Weight-only int8 (W8A16) serving: int8 storage + dequant-fused matmul.

Real int8 — not fake-quant: weights live in HBM as int8 codes plus
per-(group, out-channel) fp32 scales (half the bytes of bf16, quarter of
fp32).  In the decode regime (M ≤ 64 activation rows) the matmul consumes
the codes directly; dequantization happens on-chip inside the fused
contraction, never materializing a full-width weight tensor.  The
prefill regime (M > 64) instead materializes a TRANSIENT dequantized
(K, N) panel per call BY DESIGN — a plain MXU dot over a dequantized
temp beats the grouped einsum's (…, G, N) fp32 partials there (int8
prefill ran 2.3× fp TTFT before the switch, round-5) — so the
int8-storage claim holds for HBM-RESIDENT weights; transient compute
temps may be full width.  Decode is HBM-bandwidth-bound, so halving
stored weight bytes is a direct decode-throughput lever.  The analog of
the reference's int8
inference GEMMs + dequant kernels
(``/root/reference/csrc/transformer/inference/csrc/pt_binding.cpp:622,709,770``
``ds_qkv_gemm_int8`` / ``ds_vector_matmul_int8`` and ``dequantize.cu``),
with the groupwise-scale scheme of its ``quantizer.cu``.

Layout: a (K, N) kernel quantizes along the contraction axis K in groups
of ``group`` rows — codes int8 (K, N), scales fp32 (K/group, N).  The
grouped einsum keeps int8 operands until the MXU upcast, so XLA reads
int8 from HBM and fuses the per-group scale into the output combine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def quantize_weight(w: jax.Array, group: int = 128):
    """(K, N) float → (int8 codes (K, N), fp32 scales (K/group, N)).

    Symmetric absmax per (group, out-channel); ``group`` falls back to K
    when it does not divide K.  A 3-D input is a scanned layer stack
    (L, K, N) and quantizes per layer."""
    if w.ndim in (3, 4):   # scanned stack and/or expert leading dims
        codes, scale = jax.vmap(lambda l: quantize_weight(l, group))(
            jnp.asarray(w))
        return codes, scale
    K, N = w.shape
    g = w8_group_size(K, group)
    wf = jnp.asarray(w, jnp.float32).reshape(K // g, g, N)
    amax = jnp.max(jnp.abs(wf), axis=1, keepdims=True)        # (G, 1, N)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(wf / scale), -127, 127)
    return (codes.reshape(K, N).astype(jnp.int8),
            scale[:, 0, :].astype(jnp.float32))


def w8a16_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array):
    """``x @ dequant(codes, scale)`` without materializing the weight.

    x: (..., K) activation (bf16/fp32); codes: int8 (K, N); scale: fp32
    (G, N) with G | K.  Per-group partial products accumulate in fp32 and
    the scale folds into the combine.

    Decode-sized calls on TPU route to the Pallas panel kernel
    (``ops/pallas/w8_matmul.py``): the einsum path's ``(…, G, N)`` fp32
    partials in HBM cost more than the int8 read saves once weights
    amortize across batched slots (round-3: −11% at batch 8)."""
    K, N = codes.shape
    G = scale.shape[0]
    g = K // G
    from .pallas import spmd
    from .pallas.w8_matmul import supported, w8a16_matmul_pallas

    refusal = None if supported(x.shape, codes.shape, G, mesh_ok=True) \
        else (f"w8_matmul.supported({tuple(x.shape)}, {tuple(codes.shape)}) "
              f"said no")
    if spmd.plan("w8_matmul", x.shape[0] if x.ndim else 1, refusal,
                 "decode-sized rows", kernel="kernel", shard=False):
        M = int(np.prod(x.shape[:-1]))
        y = w8a16_matmul_pallas(x.reshape(M, K).astype(jnp.bfloat16),
                                codes, scale)
        return y.reshape(*x.shape[:-1], N).astype(x.dtype)
    cdt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.bfloat16
    if int(np.prod(x.shape[:-1])) > 64:
        # prefill regime: dequantize the panel ONCE (a K x N temp, ~10 MB
        # at 760M shapes) and run a plain MXU dot.  The grouped einsum
        # materializes (..., G, N) fp32 partials — 50 MB per layer at
        # (8, 32) prompts — and cost int8 prefill 2.3x fp TTFT (round-5)
        w = (codes.reshape(G, g, N).astype(jnp.float32)
             * scale[:, None, :]).reshape(K, N).astype(cdt)
        return jnp.dot(x.astype(cdt), w).astype(x.dtype)
    xg = x.reshape(*x.shape[:-1], G, g)
    cg = codes.reshape(G, g, N)
    # group dot in the activation dtype (TPU MXU accumulates fp32
    # internally; CPU lacks mixed bf16→f32 dots), scale combine in fp32
    part = jnp.einsum("...ug,ugn->...un", xg.astype(cdt), cg.astype(cdt))
    y = jnp.einsum("...un,un->...n", part.astype(jnp.float32), scale)
    return y.astype(x.dtype)


def w8_group_size(k: int, group: int) -> int:
    """Effective contraction-group size for a K-row panel: ``group`` when
    it divides K, else one whole-K group — the ONE rule shared by
    :func:`quantize_weight`, :func:`declare_w8_dense` and the fused
    decode-kernel dispatch (``models/common.decode_fused_plan``), so the
    stored scale shapes and the kernels' group loops can never drift."""
    return group if k % group == 0 else k


def declare_w8_dense(module, name: str, names: tuple, in_features: int,
                     features: int, group: int):
    """Declare the (codes, scales) param pair a W8A16 dense layer stores
    IN PLACE of its fp kernel — shared by every model family's ``_dense``
    so the names/shapes always line up with :func:`quantize_dense_tree`.
    The fused decode megakernels (``ops/pallas/decode_layer.py``) consume
    the same pair directly, dequantizing inside their contractions."""
    import flax.linen as nn

    g = w8_group_size(in_features, group)
    codes = module.param(
        name + "_kernel_q",
        nn.with_partitioning(nn.initializers.zeros, names),
        (in_features, features), jnp.int8)
    scale = module.param(
        name + "_kernel_s",
        nn.with_partitioning(nn.initializers.ones, (None, names[-1])),
        (in_features // g, features), jnp.float32)
    return codes, scale


def w8a16_expert_matmul(x: jax.Array, codes: jax.Array, scale: jax.Array):
    """Per-expert W8A16: ``x`` (E, C, K) × int8 codes (E, K, N) with
    scales (E, G, N) → (E, C, N).  The MoE ``ExpertsMLP`` analog of
    :func:`w8a16_matmul`."""
    E, K, N = codes.shape
    G = scale.shape[1]
    g = K // G
    cdt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.bfloat16
    xg = x.reshape(E, -1, G, g)
    cg = codes.reshape(E, G, g, N)
    part = jnp.einsum("ecug,eugn->ecun", xg.astype(cdt), cg.astype(cdt))
    y = jnp.einsum("ecun,eun->ecn", part.astype(jnp.float32), scale)
    return y.astype(x.dtype)


# expert FFN leaves (parallel/moe.py ExpertsMLP) quantized alongside the
# dense ``*_kernel`` family
_EXPERT_KEYS = ("wi", "wo")


def quantize_dense_tree(params, group: int = 128, suffix: str = "_kernel"):
    """Convert every dense ``*_kernel`` leaf (2-D, or 3-D scanned stack)
    and MoE expert ``wi``/``wo`` leaf (3-D, or 4-D scanned stack) of a
    host param tree to the serving layout: ``name_q`` int8 codes +
    ``name_s`` fp32 scales.  Embeddings / norms / biases / gates pass
    through at full width."""
    def wants(k, v):
        if k.endswith(suffix) and np.ndim(v) in (2, 3):
            return True
        return k in _EXPERT_KEYS and np.ndim(v) in (3, 4)

    def convert(subtree):
        if not isinstance(subtree, dict):
            return subtree
        out = {}
        for k, v in subtree.items():
            if isinstance(v, dict):
                out[k] = convert(v)
            elif wants(k, v):
                codes, scale = quantize_weight(jnp.asarray(v), group)
                out[k + "_q"] = np.asarray(codes)
                out[k + "_s"] = np.asarray(scale)
            else:
                out[k] = v
        return out

    return convert(params)
