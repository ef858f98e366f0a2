"""Plain Trinity (AFMoE) forward, training loss and bias update: float32
``jax.numpy`` at "highest" matmul precision; no kernels, no sort, no
grouped matmul, no cache, no chunked head.

Follows the model's public ``config.json`` (arcee-ai/Trinity-Mini,
``model_type: afmoe``) and, where the config has no key, the family's
released modeling code (``transformers``, ``models/afmoe``); those places
are marked (released code) below.

*Embedding*: ``E[ids] * sqrt(hidden)`` (``mup_enabled``; released code: no
other muP multiplier in the forward).

*Attention*: a = RMSNorm(x); q = a W_q (heads x head_dim), k, v = a W_k,
a W_v (kv heads x head_dim), g = a W_g (heads x head_dim; released code);
RMSNorm over each head's ``head_dim`` channels of q and of k, one scale
vector each (released code); half-split rotary, ``theta^(-2m/d)``, on
``sliding_attention`` layers ONLY: a ``full_attention`` layer has no
positional encoding at all (released code); scores q k^T / sqrt(head_dim),
query head h reads key-value head ``h // (heads / kv heads)``, causal, and
on sliding layers ``0 <= i - j < sliding_window``; out = (softmax v *
sigmoid(g)) W_o (released code).

*Residuals* (released code: four norms): ``x += RMSNorm(attn)``; m =
RMSNorm(x); ``x += RMSNorm(f(m))``.

*f*: the first ``num_dense_layers`` layers a SwiGLU of ``intermediate_size``;
the others s = sigmoid(m W_r) over ALL routed experts, selected = top-k of
``s + expert_bias`` (the bias picks, it does not weigh; released code),
w = s[selected] / (sum + 1e-20) * ``route_scale``, f = shared SwiGLU(m) +
sum over the selected of w_e * SwiGLU_e(m).  No auxiliary loss.

*Balancing*: after a step that routed c_e pairs to expert e of a layer,
``b_e += rate * sign(mean(c) - c_e)`` (:func:`bias_update`).

Departures, each marked below:

1. **the share**: with more routed experts than the expert leaves hold,
   this is one chip of an expert-parallel layer; ``first_expert`` says
   which contiguous run the leaves are.  The router, its bias, the top-k
   and the ``route_norm`` denominator are over all routed experts; only
   the held experts' terms of the weighted sum are computed; the shared
   expert is whole (model-configs guide, section 4).
2. the vocabulary is the slice the head holds; padded columns are masked
   as the model masks them.
3. no attention mask: rows are packed documents without padding.
4. ``loss_parts`` returns a second part that is always 0: there is no
   router loss; the drivers' comparison adds two parts.

So that an 8192-token row fits beside a trainer's state and compiles fast,
attention is computed in blocks of ``Q_BLOCK`` queries under ``lax.map``
and the held experts are walked by ``lax.scan``.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first: ``(4, 3)`` is "this forward in
fp8", the precision below the bf16 the configuration computes in.
``fault`` makes :func:`attention` (:data:`FAULTS`), :func:`expert_ffn`
(:data:`EXPERT_FAULTS`) or :func:`dense_ffn` (:data:`DENSE_FAULTS`) compute
a named WRONG thing, to read what a tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"
FAULTS = ("rope_on_full", "no_rope_on_sliding", "no_gate", "qk_norm_whole",
          "window+1", "kv_mod")
EXPERT_FAULTS = ("bias_ignored", "bias_in_weights", "softmax", "no_scale",
                 "held_denominator", "no_shared")
DENSE_FAULTS = ("gate_up_swapped",)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _mm(a, b, bits):
    """``a @ b`` with both operands rounded to ``bits`` (None: as they are)."""
    if bits is not None:
        a, b = (jax.lax.reduce_precision(t, *bits) for t in (a, b))
    return a @ b


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rotary(x, theta):
    """Half-split rotation (HF ``rotate_half``) of x (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, gate, up, down, bits):
    return _mm(jax.nn.silu(_mm(x, _f32(gate), bits)) * _mm(x, _f32(up), bits),
               _f32(down), bits)


def _attention(p, x, kind, n_head, n_kv_head, head_dim, sliding_window,
               rope_theta, eps, bits, fault):
    B, S, E = x.shape
    D, group = head_dim, n_head // n_kv_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    g = _mm(x, _f32(p["gate_proj_kernel"]), bits)        # (released code)
    qs, ks = p["q_norm"]["scale"], p["k_norm"]["scale"]
    if fault == "qk_norm_whole":    # OLMoE's: one norm over all the heads
        q = _rms_norm(q, jnp.tile(_f32(qs), n_head), eps)
        k = _rms_norm(k, jnp.tile(_f32(ks), n_kv_head), eps)
    q, k = q.reshape(B, S, n_head, D), k.reshape(B, S, n_kv_head, D)
    if fault != "qk_norm_whole":    # each head's channels (released code)
        q, k = _rms_norm(q, qs, eps), _rms_norm(k, ks, eps)
    # positions on the window layers alone (released code)
    rotate = (kind == SLIDING) != (
        fault == ("no_rope_on_sliding" if kind == SLIDING else "rope_on_full"))
    if rotate:
        q, k = _rotary(q, rope_theta), _rotary(k, rope_theta)
    window = sliding_window if kind == SLIDING else None
    if fault == "window+1" and window is not None:
        window += 1
    # key-value head of each query head
    kv_of = np.arange(n_head) % n_kv_head if fault == "kv_mod" \
        else np.arange(n_head) // group
    kt = k.transpose(0, 2, 3, 1)[:, kv_of]                  # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)[:, kv_of]                  # (B, H, S, D)
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs_ = q.transpose(0, 2, 1, 3).reshape(B, n_head, S // qb, qb, D)
    j = jnp.arange(S)[None, :]

    def block(args):
        q_blk, i0 = args                                    # (B, H, qb, D)
        s = _mm(q_blk, kt, bits) / np.sqrt(D)               # (B, H, qb, S)
        back = (i0 + jnp.arange(qb))[:, None] - j           # i - j
        keep = back >= 0
        if window is not None:
            keep &= back < window
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), vt, bits)         # (B, H, qb, D)

    a = jax.lax.map(block, (jnp.moveaxis(qs_, 2, 0),
                            jnp.arange(0, S, qb)))          # (nb, B, H, qb, D)
    a = jnp.moveaxis(a, 0, 2).reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    a = a.reshape(B, S, n_head * D)
    if fault != "no_gate":          # the output gate (released code)
        a = a * jax.nn.sigmoid(g)
    return _mm(a, _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("kind", "n_head", "n_kv_head", "head_dim", "sliding_window",
                "rope_theta", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(layer_type, p_attn, h, *, n_head, n_kv_head, head_dim,
              sliding_window, rope_theta=10000.0, eps=1e-5,
              operand_bits=None, fault=None):
    """One attention layer alone: normalised hidden states ``h`` (B, S, E)
    through the layer's ``self_attn`` leaves, float32.  What a system's
    attention layer of that type is held to on the same ``h``."""
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(
        p_attn, _f32(h), kind=layer_type, n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, sliding_window=sliding_window,
        rope_theta=float(rope_theta), eps=eps, bits=operand_bits, fault=fault)


def _sparse_ffn(p, h, top_k, route_scale, first_expert, bits, fault):
    """``(out, counts (routed experts,))`` of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    scores = jax.nn.softmax(logits, -1) if fault == "softmax" \
        else jax.nn.sigmoid(logits)
    bias = _f32(p["gate"]["expert_bias"])
    # the bias picks and does not weigh (released code)
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
        # the weight multiplies the expert's output (released code)
        y = _swiglu(h, gate, up, down, bits)
        w = jax.lax.dynamic_index_in_dim(weight, first_expert + e, 1)  # (T, 1)
        c = jax.lax.dynamic_index_in_dim(chosen, first_expert + e, 1)
        return out + jnp.where(c, y * w, 0.0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    if fault != "no_shared":     # whole on every share
        sh = p["shared"]
        out = out + _swiglu(h, sh["gate"], sh["up"], sh["down"], bits)
    return out, chosen.sum(0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attn_block(p, x, **kw):
    """``(x + norm(attention), the normalised input of the layer's
    attention, the normalised input of its FFN)``."""
    eps = kw["eps"]
    with jax.default_matmul_precision(_HI):
        h_attn = _rms_norm(x, p["input_norm"]["scale"], eps)
        a = _attention(p["self_attn"], h_attn, **kw)
        x = x + _rms_norm(a, p["post_attention_norm"]["scale"], eps)
        return x, h_attn, _rms_norm(x, p["pre_mlp_norm"]["scale"], eps)


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


@functools.partial(jax.jit, static_argnames=("eps",))
def _add_normed(x, ff, scale, eps):
    return x + _rms_norm(ff, scale, eps)


def expert_ffn(p_moe, h, *, top_k: int, route_scale: float,
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
    """A leading dense layer's SwiGLU alone, float32."""
    assert fault is None or fault in DENSE_FAULTS, fault
    gate, up = ("up", "gate") if fault == "gate_up_swapped" else ("gate", "up")
    return _dense_block({"gate_proj_kernel": p_layer[gate + "_proj_kernel"],
                         "up_proj_kernel": p_layer[up + "_proj_kernel"],
                         "down_proj_kernel": p_layer["down_proj_kernel"]},
                        _f32(h), operand_bits)


def bias_update(counts, b, rate: float):
    """``b + rate * sign(mean(counts) - counts)`` in float32: an expert
    that received fewer pairs than the mean is picked more readily in the
    next step.  The bias has no gradient and no optimizer state."""
    c = np.asarray(counts).astype(np.float32)
    return (np.asarray(b, np.float32)
            + np.float32(rate) * np.sign(c.mean(dtype=np.float32) - c))


@functools.partial(jax.jit, static_argnames=("vocab_size", "eps", "bits"))
def _head(params, x, vocab_size: int, eps: float, bits=None):
    with jax.default_matmul_precision(_HI):
        logits = _mm(_rms_norm(x, params["norm"]["scale"], eps),
                     _f32(params["lm_head"]), bits)
        # departure 2: padded vocabulary columns
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: dense and sparse blocks
    differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


def forward(params, input_ids, *, n_layer: int, n_head: int, n_kv_head: int,
            head_dim: int, vocab_size: int, top_k: int, layer_types,
            sliding_window: int, num_dense_layers: int, route_scale: float,
            rope_theta: float = 10000.0, eps: float = 1e-5,
            routed_experts=None, first_expert: int = 0, operand_bits=None,
            ffn_inputs=None, attn_inputs=None, counts=None):
    """Logits (B, S, padded vocab) in float32; lists given as
    ``attn_inputs`` / ``ffn_inputs`` receive each layer's normalised hidden
    states (B, S, E) before its attention / FFN (dense layers too), and
    ``counts`` each sparse layer's pairs an expert."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    x = x * np.float32(x.shape[-1] ** 0.5)       # mup_enabled (released code)
    for i, p in enumerate(layers(params, n_layer)):
        x, h_attn, h = _attn_block(
            p, x, kind=layer_types[i], n_head=n_head, n_kv_head=n_kv_head,
            head_dim=head_dim, sliding_window=sliding_window,
            rope_theta=float(rope_theta), eps=eps, bits=operand_bits,
            fault=None)
        if i < num_dense_layers:
            ff = dense_ffn(p, h, operand_bits=operand_bits)
        else:
            assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
            ff, c = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                               float(route_scale), first_expert, operand_bits)
            ff = ff.reshape(x.shape)
            if counts is not None:
                counts.append(c)
        x = _add_normed(x, ff, p["post_mlp_norm"]["scale"], eps)
        if attn_inputs is not None:
            attn_inputs.append(h_attn)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    return _head({"norm": params["norm"], "lm_head": params["lm_head"]}, x,
                 vocab_size=vocab_size, eps=eps, bits=operand_bits)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)


def loss_parts(params, input_ids, **kw):
    """``(next-token cross-entropy, 0.0)``: labels are the inputs shifted by
    one, the last position of each row left out; departure 4."""
    lg = forward(params, input_ids, **kw)[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return nll.mean(), jnp.float32(0.0)


def training_loss(params, input_ids, **kw):
    """Cross-entropy alone: there is no router loss."""
    return loss_parts(params, input_ids, **kw)[0]
