"""Plain LFM2-MoE forward, training loss and bias update: float32
``jax.numpy`` at "highest" matmul precision; no kernels, no sort, no
grouped matmul, no cache, no chunked head.  Gradients are ``jax.grad`` of
:func:`training_loss`.

Follows the model's public ``config.json`` (LiquidAI/LFM2-24B-A2B,
``model_type: lfm2_moe``) and, where the config has no key, the family's
released modeling code (``transformers``, ``models/lfm2_moe``); those
places are marked (released code) below.

*Block* ``l`` (every projection without bias)::

    x <- x + mixer_l(RMSNorm_op(x))        # operator_norm
    x <- x + ffn_l(RMSNorm_ffn(x))         # ffn_norm
    logits = RMSNorm_final(x) @ E^T        # the head is the table (released
                                           # code: tie_word_embeddings)

*mixer_l* where ``layer_types[l] == "conv"`` (h the normed input, L =
``conv_L_cache``)::

    [Bg ; Cg ; u] = h W_in                 # in this order (released code)
    z   = Bg * u
    c_t = sum_{j=0..L-1} w[:, j] z_{t-(L-1)+j}   # z before position 0 is 0:
                                           # w[:, L-1] is the current position
    y   = (Cg * c) W_out

an explicit loop over the taps of a zero-padded sequence, each row of the
batch from zeros.  No activation, no position, no mask between packed
documents.

*mixer_l* where ``full_attention``: q = h W_q (heads x head_dim), k, v =
h W_k, h W_v (kv heads x head_dim); RMSNorm over each head's ``head_dim``
channels of q and of k, one scale vector each (released code); half-split
rotary, ``theta^(-2m/d)``, on q and k after the norm; scores q k^T /
sqrt(head_dim), query head h reads key-value head ``h // (heads / kv
heads)``, causal; ``W_o``.  No gate, no window.

*ffn_l*: the first ``num_dense_layers`` layers a SwiGLU of
``intermediate_size``; the others s = sigmoid(h W_r) over ALL routed
experts, selected = top-k of ``s + expert_bias`` (the bias picks, it does
not weigh; released code ``route_tokens_to_experts``), w = s[selected] /
(sum + 1e-6) * ``routed_scaling_factor``, f = sum over the selected of
w_e * SwiGLU_e(h).  No shared expert, no auxiliary loss.

*Balancing*: after a step that routed c_e pairs to expert e of a layer,
``b_e += rate * sign(mean(c) - c_e)`` (:func:`bias_update`).

Departures, each marked below:

1. **the share**: with more routed experts than the expert leaves hold,
   this is one chip of an expert-parallel layer; ``first_expert`` says
   which contiguous run the leaves are.  The router, its bias, the top-k
   and the denominator are over all routed experts; only the held
   experts' terms of the weighted sum are computed (model-configs guide,
   section 4).
2. the vocabulary is the slice the table holds; padded rows are masked as
   the model masks them.
3. no attention mask: rows are packed documents without padding.
4. ``loss_parts`` returns a second part that is always 0: there is no
   router loss; the drivers' comparison adds two parts.

So that an 8192-token row fits beside a trainer's state and compiles fast,
attention is computed in blocks of ``Q_BLOCK`` queries under ``lax.map``
and the held experts are walked by ``lax.scan``.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication (and of the filter's products) to that float format first:
``(4, 3)`` is "this forward in fp8", the precision below the bf16 the
configuration computes in.  ``fault`` makes :func:`short_conv`
(:data:`CONV_FAULTS`), :func:`attention` (:data:`FAULTS`),
:func:`expert_ffn` (:data:`EXPERT_FAULTS`) or :func:`dense_ffn`
(:data:`DENSE_FAULTS`) compute a named WRONG thing, to read what a
tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
CONV, FULL = "conv", "full_attention"
NORM_EPS = 1e-6         # the routing's denominator (released code)
CONV_FAULTS = ("taps_reversed", "centred", "L-1", "c_before_filter", "no_b",
               "row_leak")
FAULTS = ("kv_mod", "qk_norm_whole", "no_rope", "theta_1e4")
EXPERT_FAULTS = ("bias_ignored", "bias_in_weights", "softmax",
                 "held_denominator", "top_2k")
DENSE_FAULTS = ("gate_up_swapped",)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _round(t, bits):
    return t if bits is None else jax.lax.reduce_precision(t, *bits)


def _mm(a, b, bits):
    """``a @ b`` with both operands rounded to ``bits`` (None: as they are)."""
    return _round(a, bits) @ _round(b, bits)


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


# ----------------------------------------------------------------------
# the short convolution
# ----------------------------------------------------------------------
def _filter(z, w, bits, fault):
    """``c_t = sum_j w[:, j] z_{t-(L-1)+j}`` of z (B, S, C) and w (C, L):
    a loop over the taps of the zero-padded sequence."""
    S, L = z.shape[1], w.shape[1]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    if fault == "L-1":                  # the oldest tap lost
        w = w.at[:, 0].set(0.0)
    ahead = 1 if fault == "centred" else 0      # one FUTURE position read
    padded = jnp.pad(z, ((0, 0), (L - 1 - ahead, ahead), (0, 0)))
    if fault == "row_leak":     # a row starts from the row before's tail
        tail = jnp.concatenate([jnp.zeros_like(z[:1, :L - 1]),
                                z[:-1, S - (L - 1):]])
        padded = padded.at[:, :L - 1].set(tail)
    z_, w_ = _round(padded, bits), _round(w, bits)
    c = jnp.zeros_like(z)
    for j in range(L):
        c = c + w_[:, j] * z_[:, j:j + S]
    return c


def _short_conv(p, h, bits, fault):
    E = h.shape[-1]
    bcu = _mm(h, _f32(p["in_proj_kernel"]), bits)
    # the three thirds in this order (released code: B, C, x = chunk(3))
    bg, cg, u = bcu[..., :E], bcu[..., E:2 * E], bcu[..., 2 * E:]
    z = u if fault == "no_b" else bg * u
    w = _f32(p["conv_kernel"])
    if fault == "c_before_filter":
        y = _filter(cg * z, w, bits, None)
    else:
        y = cg * _filter(z, w, bits, fault)
    return _mm(y, _f32(p["out_proj_kernel"]), bits)


@functools.partial(jax.jit, static_argnames=("bits", "fault"))
def _short_conv_alone(p, h, bits=None, fault=None):
    with jax.default_matmul_precision(_HI):
        return _short_conv(p, h, bits, fault)


def short_conv(p_conv, h, *, operand_bits=None, fault=None):
    """One conv layer's mixer alone: normalised hidden states ``h`` (B, S,
    E) through the layer's ``conv`` leaves, float32.  The filter's length
    is the taps leaf's."""
    assert fault is None or fault in CONV_FAULTS, fault
    return _short_conv_alone(p_conv, _f32(h), operand_bits, fault)


def short_conv_grads(p_conv, h, probe, **kw):
    """Gradients of ``sum(short_conv(h) * probe)`` with respect to ``h``
    and the three leaves: ``(dh, {leaf: d leaf})``."""
    p = {k: _f32(v) for k, v in p_conv.items()}
    return jax.grad(lambda h, p: (short_conv(p, h, **kw)
                                  * _f32(probe)).sum(), (0, 1))(_f32(h), p)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def _attention(p, x, n_head, n_kv_head, head_dim, rope_theta, eps, bits,
               fault):
    B, S, E = x.shape
    D, group = head_dim, n_head // n_kv_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    qs, ks = p["q_norm"]["scale"], p["k_norm"]["scale"]
    if fault == "qk_norm_whole":    # OLMoE's: one norm over all the heads
        q = _rms_norm(q, jnp.tile(_f32(qs), n_head), eps)
        k = _rms_norm(k, jnp.tile(_f32(ks), n_kv_head), eps)
    q, k = q.reshape(B, S, n_head, D), k.reshape(B, S, n_kv_head, D)
    if fault != "qk_norm_whole":    # each head's channels (released code)
        q, k = _rms_norm(q, qs, eps), _rms_norm(k, ks, eps)
    if fault != "no_rope":          # after the norm (released code)
        theta = 1e4 if fault == "theta_1e4" else rope_theta
        q, k = _rotary(q, theta), _rotary(k, theta)
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
        keep = (i0 + jnp.arange(qb))[:, None] - j >= 0      # causal
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), vt, bits)         # (B, H, qb, D)

    a = jax.lax.map(block, (jnp.moveaxis(qs_, 2, 0),
                            jnp.arange(0, S, qb)))          # (nb, B, H, qb, D)
    a = jnp.moveaxis(a, 0, 2).reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return _mm(a.reshape(B, S, n_head * D), _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("n_head", "n_kv_head", "head_dim", "rope_theta", "eps",
                "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(layer_type, p_attn, h, *, n_head, n_kv_head, head_dim,
              rope_theta=1e6, eps=1e-5, operand_bits=None, fault=None):
    """One attention layer alone: normalised hidden states ``h`` (B, S, E)
    through the layer's ``self_attn`` leaves, float32."""
    assert layer_type == FULL, layer_type
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(
        p_attn, _f32(h), n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, rope_theta=float(rope_theta), eps=eps,
        bits=operand_bits, fault=fault)


# ----------------------------------------------------------------------
# the feed-forwards
# ----------------------------------------------------------------------
def _sparse_ffn(p, h, top_k, route_scale, first_expert, bits, fault):
    """``(out, counts (routed experts,))`` of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    scores = jax.nn.softmax(logits, -1) if fault == "softmax" \
        else jax.nn.sigmoid(logits)
    bias = _f32(p["gate"]["expert_bias"])
    # the bias picks and does not weigh (released code)
    picking = scores if fault == "bias_ignored" else scores + bias
    _, top_e = jax.lax.top_k(picking, 2 * top_k if fault == "top_2k"
                             else top_k)
    weighing = scores + bias if fault == "bias_in_weights" else scores
    top_s = jnp.take_along_axis(weighing, top_e, -1)
    ex = p["experts"]
    held = ex["gate"].shape[0]
    denom = top_s
    if fault == "held_denominator":
        denom = jnp.where((top_e >= first_expert)
                          & (top_e < first_expert + held), top_s, 0.0)
    # norm_topk_prob, then routed_scaling_factor (released code)
    top_w = top_s / (denom.sum(-1, keepdims=True) + NORM_EPS) * route_scale
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
    return out, chosen.sum(0).astype(jnp.int32)


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


def expert_ffn(p_moe, h, *, top_k: int, route_scale: float = 1.0,
               first_expert: int = 0, operand_bits=None, fault=None):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router over all its columns, its bias, top-k, the held
    experts from ``first_expert`` on), float32."""
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


# ----------------------------------------------------------------------
# the stack
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind",) + _ATTN_STATIC)
def _mixer_block(p, x, kind, **kw):
    """``(x + mixer, the normalised input of the layer's mixer, the
    normalised input of its FFN)``."""
    eps, bits = kw["eps"], kw["bits"]
    with jax.default_matmul_precision(_HI):
        h_mix = _rms_norm(x, p["input_norm"]["scale"], eps)
        if kind == CONV:
            x = x + _short_conv(p["conv"], h_mix, bits, None)
        else:
            x = x + _attention(p["self_attn"], h_mix, **kw)
        return x, h_mix, _rms_norm(x, p["post_attention_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("vocab_size", "eps", "bits"))
def _head(norm, table, x, vocab_size: int, eps: float, bits=None):
    with jax.default_matmul_precision(_HI):
        # the head is the table (released code: tie_word_embeddings)
        logits = _mm(_rms_norm(x, norm["scale"], eps), _f32(table).T, bits)
        # departure 2: padded vocabulary rows
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: the blocks differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


def forward(params, input_ids, *, n_layer: int, n_head: int, n_kv_head: int,
            head_dim: int, vocab_size: int, top_k: int, layer_types,
            num_dense_layers: int, route_scale: float = 1.0,
            rope_theta: float = 1e6, eps: float = 1e-5,
            routed_experts=None, first_expert: int = 0, operand_bits=None,
            ffn_inputs=None, mixer_inputs=None, counts=None):
    """Logits (B, S, padded vocab) in float32; lists given as
    ``mixer_inputs`` / ``ffn_inputs`` receive each layer's normalised
    hidden states (B, S, E) before its mixer / FFN (dense layers too), and
    ``counts`` each sparse layer's pairs an expert."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    for i, p in enumerate(layers(params, n_layer)):
        x, h_mix, h = _mixer_block(
            p, x, kind=layer_types[i], n_head=n_head, n_kv_head=n_kv_head,
            head_dim=head_dim, rope_theta=float(rope_theta), eps=eps,
            bits=operand_bits, fault=None)
        if i < num_dense_layers:
            ff = dense_ffn(p, h, operand_bits=operand_bits)
        else:
            assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
            ff, c = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                               float(route_scale), first_expert, operand_bits)
            ff = ff.reshape(x.shape)
            if counts is not None:
                counts.append(c)
        x = x + ff
        if mixer_inputs is not None:
            mixer_inputs.append(h_mix)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    return _head(params["norm"], params["embed_tokens"], x,
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
