"""Plain JoyAI-LLM-Flash (the DeepSeek-V3 family) forward, both training
losses and the bias update: float32 ``jax.numpy`` at "highest" matmul
precision; no kernels, no sort, no grouped matmul, no cache, no chunked
head, nothing of ``deepspeed_tpu``.

Follows the model's public ``config.json`` (jdopensource/JoyAI-LLM-Flash,
``model_type: joyai_llm_flash``, whose keys are DeepSeek-V3's) and, where
the config has no key, the family's technical report (arXiv:2412.19437) and
released weights' names; those places are marked (report) below.

*Latent attention* (MLA), a = RMSNorm(x): ``c_q = RMSNorm(a W_qa)``,
``[q_nope | q_rope] = c_q W_qb``; ``[c_kv | k_rope] = a W_kva``, ``c_kv =
RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb``.  ``k_rope`` is ONE key of
``rope`` channels for all heads.  Rotary on ``q_rope`` and ``k_rope`` only,
over interleaved pairs ``(x_2i, x_2i+1)`` by ``theta^(-2i/rope)``
(``rope_interleave``; the released code de-interleaves and rotates halves:
the same rotation followed by one fixed permutation of q's and k's channels
alike, so every score is equal).  ``score_h = (q_nope_h . k_nope_h +
q_rope_h . k_rope) / sqrt(nope + rope)``, causal; ``out = concat_h(softmax
v_h) W_o``.  No biases; ``rope_scaling`` is null, so no mscale.

*Layout of the up-projections.*  The leaves this reference reads are the
program's: ``q_b_proj``'s columns are all heads' nope channels, then all
heads' rope channels (``H*nope | H*rope``), and ``kv_b_proj``'s all heads'
keys, then all heads' values - a fixed permutation of the released ``(H,
nope + rope)`` and ``(H, nope + v)`` column orders, which changes no score
and no output.

*Residuals*: ``x += attn(RMSNorm(x))``; ``x += f(RMSNorm(x))``.

*f*: the first ``num_dense_layers`` (``first_k_dense_replace``) layers a
SwiGLU of ``intermediate_size``; the others s = sigmoid(m W_r) over ALL
routed experts, selected = top-k of ``s + e_score_correction_bias`` (the
program's ``expert_bias``; it picks and does not weigh), w = s[selected] /
(sum + 1e-20) * ``routed_scaling_factor`` on the expert's OUTPUT, f =
shared SwiGLU(m) + sum of w_e * SwiGLU_e(m).  ``n_group`` = ``topk_group``
= 1: no group limit.  No auxiliary loss (the report's sequence-wise one has
no key).

*Multi-token prediction* (report, section 2.2; ``num_nextn_predict_layers``
1): with h the stack's output BEFORE the final norm, ``x_i = [RMSNorm_e(
E[t_{i+1}]) ; RMSNorm_h(h_i)] W_eh``, one whole sparse block, ``RMSNorm``,
then the MAIN model's table E and head, predicting ``t_{i+2}``.  Loss =
CE_main + ``mtp_weight`` * CE_mtp, the second over the positions that have
a label two ahead.

*Balancing*: ``b_e += rate * sign(mean(c) - c_e)`` (:func:`bias_update`).

Departures, each marked below:

1. **the share**: with more routed experts than the expert leaves hold,
   this is one chip of an expert-parallel layer; ``first_expert`` says
   which contiguous run the leaves are.  The router, its bias, the top-k
   and the denominator are over all routed experts; only the held experts'
   terms of the weighted sum are computed; the shared expert is whole.
2. the vocabulary is the slice the head holds; padded columns are masked.
3. no attention mask: rows are packed documents without padding.

So that an 8192-token row fits beside a trainer's state, attention is
computed in blocks of ``Q_BLOCK`` queries under ``lax.map`` and the held
experts are walked by ``lax.scan``.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first: ``(4, 3)`` is "this forward in
fp8", the precision below the bf16 the configuration computes in.
``fault`` makes :func:`attention` (:data:`FAULTS`), :func:`sparse_ffn`
(:data:`EXPERT_FAULTS`), :func:`dense_ffn` (:data:`DENSE_FAULTS`) or
:func:`mtp` (:data:`MTP_FAULTS`) compute a named WRONG thing, to read what
a tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
FAULTS = ("rope_on_nope", "halves_on_q", "halves_on_k", "scale_nope",
          "k_rope_next_position", "no_q_latent_norm", "no_kv_latent_norm",
          "bf16_accumulation")
EXPERT_FAULTS = ("bias_ignored", "bias_in_weights", "softmax", "no_scale",
                 "held_denominator", "no_shared")
DENSE_FAULTS = ("gate_up_swapped",)
# ("h after the final norm" is no fault a comparison can refuse at fresh
# weights: hnorm follows, and RMSNorm of an RMSNorm with unit weights is the
# same vector.  The file states the side chosen under ``assumed``.)
MTP_FAULTS = ("label_shift_1", "other_table", "other_head", "h_then_e")


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _mm(a, b, bits):
    """``a @ b`` with both operands rounded to ``bits`` (None: as they are)."""
    if bits is not None:
        a, b = (jax.lax.reduce_precision(t, *bits) for t in (a, b))
    return a @ b


def _bf16(x):
    return jax.lax.reduce_precision(x, 8, 7)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rotary(x, theta, interleaved=True, offset=0):
    """Rotate x (B, S, H, D) by position (+ ``offset``): pairs ``(2i,
    2i+1)`` when ``interleaved``, halves ``(i, i + D/2)`` otherwise."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = (np.arange(S, dtype=np.float64) + offset)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, gate, up, down, bits):
    return _mm(jax.nn.silu(_mm(x, _f32(gate), bits)) * _mm(x, _f32(up), bits),
               _f32(down), bits)


def _attention(p, x, n_head, kv_lora_rank, nope, rope, v_dim, rope_theta,
               eps, bits, fault):
    B, S, E = x.shape
    H = n_head
    c_q = _mm(x, _f32(p["q_a_proj_kernel"]), bits)
    if fault != "no_q_latent_norm":
        c_q = _rms_norm(c_q, p["q_a_layernorm"]["scale"], eps)
    q = _mm(c_q, _f32(p["q_b_proj_kernel"]), bits)
    q_nope = q[..., :H * nope].reshape(B, S, H, nope)       # the layout above
    q_rope = q[..., H * nope:].reshape(B, S, H, rope)
    kv_a = _mm(x, _f32(p["kv_a_proj_with_mqa_kernel"]), bits)
    c_kv, k_rope = kv_a[..., :kv_lora_rank], kv_a[..., kv_lora_rank:]
    if fault != "no_kv_latent_norm":
        c_kv = _rms_norm(c_kv, p["kv_a_layernorm"]["scale"], eps)
    kv = _mm(c_kv, _f32(p["kv_b_proj_kernel"]), bits)
    k_nope = kv[..., :H * nope].reshape(B, S, H, nope)
    v = kv[..., H * nope:].reshape(B, S, H, v_dim)
    k_rope = k_rope[:, :, None, :]              # ONE key for all the heads
    q_rope = _rotary(q_rope, rope_theta, fault != "halves_on_q")
    k_rope = _rotary(k_rope, rope_theta, fault != "halves_on_k",
                     offset=1 if fault == "k_rope_next_position" else 0)
    if fault == "rope_on_nope":
        q_nope, k_nope = (_rotary(t, rope_theta) for t in (q_nope, k_nope))
    scale = 1.0 / np.sqrt(nope if fault == "scale_nope" else nope + rope)
    low = fault == "bf16_accumulation"
    qh = jnp.concatenate([q_nope, q_rope], -1)               # (B, S, H, 192)
    kh = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    kt = kh.transpose(0, 2, 3, 1)                            # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)                             # (B, H, S, Dv)
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs_ = qh.transpose(0, 2, 1, 3).reshape(B, H, S // qb, qb, nope + rope)
    j = jnp.arange(S)[None, :]

    def block(args):
        q_blk, i0 = args                                     # (B, H, qb, D)
        s = _mm(q_blk, kt, bits) * scale                     # (B, H, qb, S)
        if low:
            s = _bf16(s)
        keep = (i0 + jnp.arange(qb))[:, None] - j >= 0
        s = jnp.where(keep[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, -1)
        out = _mm(_bf16(pr) if low else pr, vt, bits)
        return _bf16(out) if low else out                    # (B, H, qb, Dv)

    a = jax.lax.map(block, (jnp.moveaxis(qs_, 2, 0), jnp.arange(0, S, qb)))
    a = jnp.moveaxis(a, 0, 2).reshape(B, H, S, v_dim).transpose(0, 2, 1, 3)
    return _mm(a.reshape(B, S, H * v_dim), _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("n_head", "kv_lora_rank", "nope", "rope", "v_dim",
                "rope_theta", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
             v_head_dim, rope_theta, eps, operand_bits=None, fault=None):
    return dict(n_head=n_head, kv_lora_rank=kv_lora_rank,
                nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                v_dim=v_head_dim, rope_theta=float(rope_theta), eps=eps,
                bits=operand_bits, fault=fault)


def attention(h_normed, p_attn, *, n_head, kv_lora_rank, qk_nope_head_dim,
              qk_rope_head_dim, v_head_dim, rope_theta, eps=1e-6,
              operand_bits=None, fault=None):
    """One latent-attention layer alone: normalised hidden states (B, S, E)
    through the layer's ``self_attn`` leaves, float32."""
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(p_attn, _f32(h_normed), **_attn_kw(
        n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
        rope_theta, eps, operand_bits, fault))


def _sparse_ffn(p, h, top_k, route_scale, first_expert, bits, fault):
    """``(out, counts (routed experts,))`` of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    scores = jax.nn.softmax(logits, -1) if fault == "softmax" \
        else jax.nn.sigmoid(logits)
    bias = _f32(p["gate"]["expert_bias"])
    picking = scores if fault == "bias_ignored" else scores + bias
    _, top_e = jax.lax.top_k(picking, top_k)
    weighing = scores + bias if fault == "bias_in_weights" else scores
    top_s = jnp.take_along_axis(weighing, top_e, -1)
    ex = p["experts"]
    held = ex["gate"].shape[0]
    denom = top_s
    if fault == "held_denominator":
        denom = jnp.where((top_e >= first_expert)
                          & (top_e < first_expert + held), top_s, 0.0)
    top_w = top_s / (denom.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        top_w = top_w * route_scale
    rows = jnp.arange(h.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, top_e].set(True)
    weight = jnp.zeros_like(scores).at[rows, top_e].set(top_w)

    def one(out, leaf):          # departure 1: the held experts alone
        gate, up, down, e = leaf
        y = _swiglu(h, gate, up, down, bits)
        w = jax.lax.dynamic_index_in_dim(weight, first_expert + e, 1)  # (T, 1)
        c = jax.lax.dynamic_index_in_dim(chosen, first_expert + e, 1)
        return out + jnp.where(c, y * w, 0.0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    if fault != "no_shared":     # whole on every share, unweighted
        sh = p["shared"]
        out = out + _swiglu(h, sh["gate"], sh["up"], sh["down"], bits)
    return out, chosen.sum(0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attn_block(p, x, **kw):
    """``(x + attention, the normalised input of the layer's attention,
    the normalised input of its FFN)``."""
    eps = kw["eps"]
    with jax.default_matmul_precision(_HI):
        h_attn = _rms_norm(x, p["input_norm"]["scale"], eps)
        x = x + _attention(p["self_attn"], h_attn, **kw)
        return x, h_attn, _rms_norm(x, p["post_attention_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "first_expert", "bits", "fault"))
def _ffn_block(p_moe, h, top_k: int, route_scale: float, first_expert: int,
               bits=None, fault=None):
    with jax.default_matmul_precision(_HI):
        return _sparse_ffn(p_moe, h, top_k, route_scale, first_expert, bits,
                           fault)


@functools.partial(jax.jit, static_argnames=("bits",))
def _dense_block(p, h, bits=None):
    with jax.default_matmul_precision(_HI):
        return _swiglu(h, p["gate_proj_kernel"], p["up_proj_kernel"],
                       p["down_proj_kernel"], bits)


def sparse_ffn(p_moe, h, *, top_k: int, route_scale: float,
               first_expert: int = 0, operand_bits=None, fault=None):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router over all its columns, its bias, top-k, the held
    experts from ``first_expert`` on, the shared expert), float32."""
    assert fault is None or fault in EXPERT_FAULTS, fault
    h = _f32(h)
    return _ffn_block(p_moe, h.reshape(-1, h.shape[-1]), top_k,
                      float(route_scale), first_expert, operand_bits,
                      fault)[0].reshape(h.shape)


def dense_ffn(p_layer, h, *, operand_bits=None, fault=None):
    """The leading dense layer's SwiGLU alone, float32."""
    assert fault is None or fault in DENSE_FAULTS, fault
    gate, up = ("up", "gate") if fault == "gate_up_swapped" else ("gate", "up")
    return _dense_block({"gate_proj_kernel": p_layer[gate + "_proj_kernel"],
                         "up_proj_kernel": p_layer[up + "_proj_kernel"],
                         "down_proj_kernel": p_layer["down_proj_kernel"]},
                        _f32(h), operand_bits)


def bias_update(counts, b, rate: float):
    """``b + rate * sign(mean(counts) - counts)`` in float32 (report,
    section 2.1.2: the bias has no gradient and no optimizer state)."""
    c = np.asarray(counts).astype(np.float32)
    return (np.asarray(b, np.float32)
            + np.float32(rate) * np.sign(c.mean(dtype=np.float32) - c))


@functools.partial(jax.jit, static_argnames=("vocab_size", "bits"))
def _nll(x, lm_head, targets, vocab_size: int, bits=None):
    """Per-position negative log-likelihood of ``targets`` (B, T) under the
    head's logits over normalised hidden states ``x`` (B, T, E)."""
    with jax.default_matmul_precision(_HI):
        logits = _mm(x, _f32(lm_head), bits)
        # departure 2: padded vocabulary columns
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(pad, logits, -jnp.inf)
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[..., None], -1)[..., 0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, scale, eps):
    return _rms_norm(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("eps", "bits", "swap"))
def _eh_proj(p, e, h, eps, bits=None, swap=False):
    with jax.default_matmul_precision(_HI):
        parts = [_rms_norm(e, p["enorm"]["scale"], eps),
                 _rms_norm(h, p["hnorm"]["scale"], eps)]
        return _mm(jnp.concatenate(parts[::-1] if swap else parts, -1),
                   _f32(p["eh_proj_kernel"]), bits)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: the dense block and the
    sparse ones differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


def _block(p, x, sparse, attn_kw, top_k, route_scale, first_expert, bits,
           counts=None, attn_inputs=None, ffn_inputs=None):
    x, h_attn, h = _attn_block(p, x, **attn_kw)
    if sparse:
        ff, c = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                           float(route_scale), first_expert, bits)
        ff = ff.reshape(x.shape)
        if counts is not None:
            counts.append(c)
    else:
        ff = dense_ffn(p, h, operand_bits=bits)
    if attn_inputs is not None:
        attn_inputs.append(h_attn)
    if ffn_inputs is not None:
        ffn_inputs.append(h)
    return x + ff


def hidden(params, input_ids, *, n_layer: int, n_head: int,
           kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
           v_head_dim: int, top_k: int, num_dense_layers: int,
           route_scale: float, rope_theta: float, eps: float = 1e-6,
           routed_experts=None, first_expert: int = 0, operand_bits=None,
           ffn_inputs=None, attn_inputs=None, counts=None, **_):
    """The stack's output (B, S, E) BEFORE the final norm; lists given as
    ``attn_inputs`` / ``ffn_inputs`` receive each layer's normalised hidden
    states before its attention / FFN, ``counts`` each sparse layer's pairs
    an expert."""
    kw = _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim, rope_theta, eps, operand_bits)
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    for i, p in enumerate(layers(params, n_layer)):
        sparse = i >= num_dense_layers
        if sparse:
            assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        x = _block(p, x, sparse, kw, top_k, route_scale, first_expert,
                   operand_bits, counts, attn_inputs, ffn_inputs)
    return x


def mtp_hidden(h, input_ids, params, *, n_head, kv_lora_rank,
               qk_nope_head_dim, qk_rope_head_dim, v_head_dim, top_k,
               route_scale, rope_theta, eps=1e-6, first_expert=0,
               operand_bits=None, fault=None, attn_inputs=None,
               ffn_inputs=None, **_):
    """The prediction block's output after its own norm, (B, S - 1, E):
    position i (< S - 1) combines ``h_i`` with the embedding of token
    ``i + 1`` (report, eq. 21-22).  ``h`` is the stack's output before the
    final norm."""
    assert fault is None or fault in MTP_FAULTS, fault
    p = params["mtp_0"]
    ids = jnp.asarray(input_ids)
    table = _f32(params["embed_tokens"])
    if fault == "other_table":      # a table that is not the main model's
        table = jnp.roll(table, 1, axis=0)
    x = _eh_proj(p, table[ids[:, 1:]], _f32(h)[:, :-1], eps, operand_bits,
                 fault == "h_then_e")
    kw = _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim, rope_theta, eps, operand_bits)
    # one row short of the others: pad to the block size and cut again (the
    # pad is a LATER position, which no causal position reads)
    S = ids.shape[1]
    x = jnp.pad(x, ((0, 0), (0, 1), (0, 0)))
    x = _block(p["block"], x, True, kw, top_k, route_scale, first_expert,
               operand_bits, None, attn_inputs, ffn_inputs)
    return _normed(x, p["shared_head_norm"]["scale"], eps)[:, :S - 1]


def mtp(h, input_ids, params, *, vocab_size, fault=None, operand_bits=None,
        **kw):
    """The multi-token-prediction block alone: per-position negative
    log-likelihood (B, S - 2) of token ``i + 2`` at position ``i``, through
    the MAIN model's table and head, from the stack's output ``h``."""
    ids = jnp.asarray(input_ids)
    x = mtp_hidden(h, ids, params, fault=fault, operand_bits=operand_bits,
                   **kw)[:, :-1]
    head = _f32(params["lm_head"])
    if fault == "other_head":
        head = jnp.roll(head, 1, axis=1)
    targets = ids[:, 1:-1] if fault == "label_shift_1" else ids[:, 2:]
    return _nll(x, head, targets, vocab_size=vocab_size, bits=operand_bits)


def main_nll(h, input_ids, params, *, vocab_size, eps=1e-6,
             operand_bits=None, **_):
    """Per-position negative log-likelihood (B, S - 1) of token ``i + 1``
    at position ``i`` from the stack's output ``h``: the final norm, the
    head."""
    ids = jnp.asarray(input_ids)
    return _nll(_normed(_f32(h), params["norm"]["scale"], eps)[:, :-1],
                params["lm_head"], ids[:, 1:], vocab_size=vocab_size,
                bits=operand_bits)


def loss_parts(params, input_ids, *, vocab_size, mtp_layers: int = 1, **kw):
    """``(CE_main, CE_mtp)``: next-token cross-entropy over positions 0 ..
    S-2 and the prediction block's over 0 .. S-3 (0.0 without one)."""
    ids = jnp.asarray(input_ids)
    h = hidden(params, ids, **kw)
    main = main_nll(h, ids, params, vocab_size=vocab_size, **kw).mean()
    if not mtp_layers:
        return main, jnp.float32(0.0)
    mkw = {k: v for k, v in kw.items()
           if k not in ("ffn_inputs", "attn_inputs", "counts")}
    return main, mtp(h, ids, params, vocab_size=vocab_size, **mkw).mean()


def training_loss(params, input_ids, *, mtp_weight: float = 0.3, **kw):
    """CE_main + ``mtp_weight`` * CE_mtp: there is no router loss."""
    main, second = loss_parts(params, input_ids, **kw)
    return main + mtp_weight * second


def logits(params, input_ids, *, vocab_size, eps: float = 1e-6,
           operand_bits=None, **kw):
    """The main head's logits (B, S, padded vocab), float32; padded columns
    are -inf."""
    h = hidden(params, input_ids, eps=eps, operand_bits=operand_bits, **kw)
    with jax.default_matmul_precision(_HI):
        out = _mm(_normed(h, params["norm"]["scale"], eps),
                  _f32(params["lm_head"]), operand_bits)
    return jnp.where(jnp.arange(out.shape[-1]) < vocab_size, out, -jnp.inf)
