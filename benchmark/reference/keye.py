"""Plain Keye-VL-2.0 language model: forward, training loss and gradients
in float32 ``jax.numpy`` at "highest" matmul precision; no kernels, no
sort of rows, no grouped matmul, no chunked head.

Follows the model's public ``config.json`` (Kwai-Keye/Keye-VL-2.0-30B-A3B,
``model_type: KeyeVL2``: the Qwen3-MoE block its keys name, with
``sa_config``), the DeepSeek-V3.2-Exp report for what ``sa_config`` is
(DeepSeek-Sparse-Attention: a lightning indexer and a top-k selection) and
that model's released ``Indexer`` for the indexer's form.  Pre-norm residual
blocks, ``x += Attn(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``, RMSNorm eps
1e-6, no biases, untied head.  The vision tower is not part of this file:
text tokens have the same index on all three ``mrope_section`` axes, and
multi-axis rotary is then the plain rotary below.

*Attention*, ``h = RMSNorm(x)``, positions t and s of one row, ``g(i) = i
// (heads / kv heads)``:

- ``q = h W_q`` (32 x 128), ``k = h W_k``, ``v = h W_v`` (4 x 128); an
  RMSNorm over each head's 128 channels of q and of k (one scale each);
  half-split rotary on all 128 channels, theta 1e7.
- the indexer, from ``hd = stop_gradient(h)``: ``qI = hd W_qI`` (16 heads x
  64), ``kI = LayerNorm(hd W_kI)`` (ONE key head of 64; weight and bias,
  eps 1e-6), ``w = hd W_w`` (16); half-split rotary on all 64 channels of
  qI and kI at the same theta.  For s <= t::

      I[t, s] = sum_j w[t, j] * 16^-1/2 * 64^-1/2 * relu(qI[t, j] . kI[s])

  (the released code's Hadamard rotation of qI and kI is orthogonal and
  cancels in the product; its fp8 is an inference detail: both left out).
- the selection: ``S_t`` = the ``min(topk, t + 1)`` causal positions of
  largest ``I[t, .]``, ties to the lower position (``lax.top_k``'s rule),
  one set a query for all heads.  ``q_chunk_size`` / ``kv_chunk_size`` are
  the source's tiling and change nothing.
- ``o[t, i] = sum_{s in S_t} softmax_{s in S_t}(q[t, i] . k[s, g(i)] *
  128^-1/2) v[s, g(i)]``, then ``W_o``.
- the indexer's loss (the report's sparse training stage): ``pbar[t, s] =
  stop_gradient(mean_i p[t, i, s])``, ``L_I = mean_t KL(pbar[t] ||
  softmax_{s in S_t} I[t, s])``; ``loss = CE + indexer_loss_weight * sum
  over layers of L_I + router losses``.  By the two stop-gradients the
  indexer's leaves learn from ``L_I`` alone and every other leaf from the
  rest; the selection passes no gradient.

*FFN*: router logits over ALL routed experts, float32 softmax, top-k, the
chosen weights divided by their sum, expert e = ``(silu(x G_e) * x U_e)
D_e``.

Departures, each marked below:

1. the load-balancing loss is computed a layer and averaged over layers.
2. **the share**: ``first_expert`` says which contiguous run of the routed
   experts the leaves hold; routing, renormalisation and the router loss
   are over all routed experts, only the held experts' terms are summed
   (model-configs guide, section 4).
3. the vocabulary is the slice the head holds; padded columns are masked.
4. no document mask: rows are packed documents without padding.

So that a 32,768-position row fits beside a trainer's state, attention is
computed ``Q_BLOCK`` queries at a time under ``lax.map`` (a block's panels
rematerialised in the backward): the block's scores ``I`` against all keys,
``lax.top_k`` over them, a dense softmax over the kept pairs for all heads.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first: ``(4, 3)`` is "this forward in
fp8".  ``fault`` computes a named WRONG thing (:data:`FAULTS`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 128
# the selection ignored (every causal key kept); top-k over ALL keys, the
# future's too, before the causal mask; half as many keys; the indexer's
# ReLU, its head weights, its key's LayerNorm, its rotary left out; the
# indexer's loss over every causal key instead of the kept; key-value head
# h % kv heads
FAULTS = ("dense", "noncausal_topk", "half_topk", "no_relu", "no_w",
          "no_key_norm", "no_indexer_rope", "loss_all_causal", "kv_mod")
# the faults that move what the indexer selects
SELECTION_FAULTS = FAULTS[1:7]


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _mm(a, b, bits):
    """``a @ b`` with both operands rounded to ``bits`` (None: as they are)."""
    if bits is not None:
        a, b = (jax.lax.reduce_precision(t, *bits) for t in (a, b))
    return a @ b


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps=1e-6):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale) + _f32(bias)


def _rotary(x, theta):
    """Half-split rotation (HF ``rotate_half``) of x (B, S, H, D) at
    positions 0 .. S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(p, x, n_head, n_kv_head, head_dim, rope_theta, eps, bits):
    """q, k, v of normalised hidden states x (B, S, E)."""
    B, S, _ = x.shape
    D = head_dim
    q = _mm(x, _f32(p["q_proj_kernel"]), bits).reshape(B, S, n_head, D)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    return _rotary(q, rope_theta), _rotary(k, rope_theta), v


def _indexer(p, x, n_index_head, rope_theta, bits, fault):
    """``(qI (B, S, heads, channels), kI (B, S, channels), w (B, S, heads))``
    of the indexer's leaves ``p`` on x (B, S, E), which passes no gradient."""
    x = jax.lax.stop_gradient(x)
    B, S, _ = x.shape
    qi = _mm(x, _f32(p["wq_kernel"]), bits).reshape(B, S, n_index_head, -1)
    ki = _mm(x, _f32(p["wk_kernel"]), bits)
    if fault != "no_key_norm":
        ki = _layer_norm(ki, p["k_norm_scale"], p["k_norm_bias"])
    ki = ki[:, :, None]
    if fault != "no_indexer_rope":
        qi, ki = _rotary(qi, rope_theta), _rotary(ki, rope_theta)
    w = _mm(x, _f32(p["weights_proj_kernel"]), bits) \
        * (n_index_head * qi.shape[-1]) ** -0.5
    if fault == "no_w":
        w = jnp.full_like(w, (n_index_head * qi.shape[-1]) ** -0.5)
    return qi, ki[:, :, 0], w


def _scores(qi, ki, w, bits, fault):
    """``I`` (B, T, S) of a block's qI (B, T, heads, channels) and w (B, T,
    heads) against every kI (B, S, channels)."""
    z = _mm(qi.transpose(0, 2, 1, 3), ki[:, None].transpose(0, 1, 3, 2),
            bits)                                           # (B, heads, T, S)
    if fault != "no_relu":
        z = jax.nn.relu(z)
    scores = (w.transpose(0, 2, 1)[..., None] * z).sum(1)
    return jnp.where(scores == 0.0, 0.0, scores)


def _kept(scores, causal, topk, fault):
    """The selection of a block: bool (B, T, S)."""
    if fault == "dense":
        return jnp.broadcast_to(causal, scores.shape)
    B, T, S = scores.shape
    if fault == "half_topk":
        topk //= 2
    ranked = scores if fault == "noncausal_topk" \
        else jnp.where(causal, scores, -jnp.inf)
    _, idx = jax.lax.top_k(ranked, min(topk, S))
    kept = jnp.zeros(scores.shape, bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None],
        idx].set(True) & causal
    if fault == "noncausal_topk":   # a query whose best keys all lie ahead
        kept |= _own_position(causal)   # keeps itself, so the row is finite
    return kept


def _own_position(causal):
    """The pair (t, t) of each query of a block, from its causal mask."""
    return causal & ~jnp.pad(causal, ((0, 0), (0, 1)))[:, 1:]


def _blocks(S):
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    return qb


def _core(q, k, v, qi, ki, w, topk, bits, fault, selection=None):
    """``(o (B, S, H, D), kl (B, S), kept (B, S, S) or None)``: the
    selection (or one given) and the softmax over it, ``Q_BLOCK`` queries
    at a time."""
    B, S, n_head, D = q.shape
    n_kv_head = k.shape[2]
    kv_of = np.arange(n_head) % n_kv_head if fault == "kv_mod" \
        else np.arange(n_head) // (n_head // n_kv_head)
    kt = k.transpose(0, 2, 3, 1)[:, kv_of]                  # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)[:, kv_of]                  # (B, H, S, D)
    qb = _blocks(S)
    pos = jnp.arange(S)

    @jax.checkpoint         # the backward recomputes a block's panels
    def block(args):
        t0, q_blk, qi_blk, w_blk, given = args
        causal = pos[None, :] <= (t0 + jnp.arange(qb))[:, None]
        scores = _scores(qi_blk, ki, w_blk, bits, fault)        # (B, qb, S)
        # a selection given from outside stands, but for the fault that
        # ignores every selection
        kept = _kept(scores, causal, topk, fault) \
            if given is None or fault == "dense" else given & causal
        s = _mm(q_blk.transpose(0, 2, 1, 3), kt, bits) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(kept[:, None], s, -jnp.inf), -1)
        out = _mm(p, vt, bits).transpose(0, 2, 1, 3)        # (B, qb, H, D)
        pbar = jax.lax.stop_gradient(p.mean(1))
        over = causal if fault == "loss_all_causal" else kept
        log_soft = jax.nn.log_softmax(jnp.where(over, scores, -jnp.inf), -1)
        live = kept & (pbar > 0.0)
        kl = jnp.sum(jnp.where(live, pbar * (
            jnp.log(jnp.where(live, pbar, 1.0))
            - jnp.where(live, log_soft, 0.0)), 0.0), -1)
        return out, kl

    def cut(x):             # (B, S, ...) -> (S / qb, B, qb, ...)
        return jnp.moveaxis(x.reshape(B, S // qb, qb, *x.shape[2:]), 1, 0)

    out, kl = jax.lax.map(block, (
        jnp.arange(0, S, qb), cut(q), cut(qi), cut(w),
        None if selection is None else cut(selection)))
    return (jnp.moveaxis(out, 0, 1).reshape(B, S, n_head, D),
            jnp.moveaxis(kl, 0, 1).reshape(B, S))


# One jitted function a stage, shared by everything that runs the stage:
# the whole forward, a layer alone and the core alone compile a stage once
# a shape.
_QKV_STATIC = ("n_head", "n_kv_head", "head_dim", "rope_theta", "eps", "bits")


@functools.partial(jax.jit, static_argnames=_QKV_STATIC)
def _qkv_stage(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _qkv(p, x, **kw)


@functools.partial(jax.jit, static_argnames=("n_index_head", "rope_theta",
                                             "bits", "fault"))
def _indexer_stage(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _indexer(p, x, **kw)


@functools.partial(jax.jit, static_argnames=("topk", "bits", "fault"))
def _core_stage(q, k, v, qi, ki, w, topk, bits, fault):
    with jax.default_matmul_precision(_HI):
        return _core(q, k, v, qi, ki, w, topk, bits, fault)


@functools.partial(jax.jit, static_argnames=("bits",))
def _out_stage(p, a, bits):
    with jax.default_matmul_precision(_HI):
        B, S = a.shape[:2]
        return _mm(a.reshape(B, S, -1), _f32(p["o_proj_kernel"]), bits)


def _attention(p, x, n_head, n_kv_head, head_dim, n_index_head, topk,
               rope_theta, eps, bits, fault):
    """``(the layer's output (B, S, E), L_I)``."""
    q, k, v = _qkv_stage(p, x, n_head=n_head, n_kv_head=n_kv_head,
                         head_dim=head_dim, rope_theta=rope_theta, eps=eps,
                         bits=bits)
    qi, ki, w = _indexer_stage(p["indexer"], x, n_index_head=n_index_head,
                               rope_theta=rope_theta, bits=bits, fault=fault)
    a, kl = _core_stage(q, k, v, qi, ki, w, topk, bits, fault)
    return _out_stage(p, a, bits), kl.mean()


def _attn_kw(kw):
    return {k: kw[k] for k in ("n_head", "n_kv_head", "head_dim",
                               "n_index_head", "topk", "rope_theta", "eps")}


def attention(p_attn, h, *, n_head, n_kv_head, head_dim, n_index_head, topk,
              rope_theta=1e7, eps=1e-6, operand_bits=None, fault=None):
    """One attention layer alone: normalised hidden states ``h`` (B, S, E)
    through the layer's ``self_attn`` leaves, float32: ``(out (B, S, E),
    L_I)``."""
    assert fault is None or fault in FAULTS, fault
    return _attention(p_attn, _f32(h), n_head, n_kv_head, head_dim,
                      n_index_head, topk, float(rope_theta), eps,
                      operand_bits, fault)


def qkv(p_attn, h, *, n_head, n_kv_head, head_dim, rope_theta=1e7, eps=1e-6,
        **_):
    """The q, k, v that layer's attention core reads, float32."""
    return _qkv_stage(p_attn, _f32(h), n_head=n_head, n_kv_head=n_kv_head,
                      head_dim=head_dim, rope_theta=float(rope_theta),
                      eps=eps, bits=None)


def indexer(p_attn, h, *, n_index_head, rope_theta=1e7, operand_bits=None,
            fault=None, **_):
    """``(qI, kI, w)`` of that layer's indexer, float32."""
    assert fault is None or fault in FAULTS, fault
    return _indexer_stage(p_attn["indexer"], _f32(h),
                          n_index_head=n_index_head,
                          rope_theta=float(rope_theta), bits=operand_bits,
                          fault=fault)


@functools.partial(jax.jit, static_argnames=("rows", "bits", "fault"))
def _score_rows(qi, ki, w, t0, rows, bits, fault):
    with jax.default_matmul_precision(_HI):
        return _scores(jax.lax.dynamic_slice_in_dim(qi, t0, rows, 1), ki,
                       jax.lax.dynamic_slice_in_dim(w, t0, rows, 1), bits,
                       fault)


def indexer_scores(qi, ki, w, t0, rows: int, *, operand_bits=None,
                   fault=None):
    """``I`` (B, rows, S) of the queries ``t0 .. t0 + rows - 1`` against
    every key (the causal part is what attention reads), float32."""
    return _score_rows(_f32(qi), _f32(ki), _f32(w), t0, rows, operand_bits,
                       fault)


@functools.partial(jax.jit, static_argnames=("topk", "bits", "fault"))
def _selection(qi, ki, w, topk, bits, fault):
    with jax.default_matmul_precision(_HI):
        B, S = w.shape[:2]
        qb = _blocks(S)
        pos = jnp.arange(S)

        def block(t0):
            causal = pos[None, :] <= (t0 + jnp.arange(qb))[:, None]
            scores = _scores(
                jax.lax.dynamic_slice_in_dim(qi, t0, qb, 1), ki,
                jax.lax.dynamic_slice_in_dim(w, t0, qb, 1), bits, fault)
            return _kept(scores, causal, topk, fault)

        kept = jax.lax.map(block, jnp.arange(0, S, qb))
        return jnp.moveaxis(kept, 0, 1).reshape(B, S, S)


def selection(qi, ki, w, *, topk, operand_bits=None, fault=None):
    """The kept pairs as a mask, bool (B, S, S)."""
    assert fault is None or fault in FAULTS, fault
    return _selection(_f32(qi), _f32(ki), _f32(w), topk, operand_bits, fault)


@functools.partial(jax.jit, static_argnames=("topk", "bits", "fault"))
def _core_vjp(q, k, v, qi, ki, w, given, cotangent, topk, bits, fault):
    with jax.default_matmul_precision(_HI):
        (out, kl), vjp = jax.vjp(lambda *ops: _core(
            *ops, topk, bits, fault, selection=given), q, k, v, qi, ki, w)
        # the indexer's loss of the row is the mean over its queries
        grads = vjp((cotangent, jnp.full_like(kl, 1.0 / kl.shape[1])))
        return (out,) + grads[:3] + (kl.mean(),) + grads[3:]


def attention_core(q, k, v, qi, ki, w, cotangent, *, topk, selection=None,
                   operand_bits=None, fault=None):
    """``(out, dq, dk, dv, L_I, dqI, dkI, dw)`` of the attention over the
    selection alone (the indexer's, or the mask ``selection`` given), float32:
    the output and the gradients of ``sum(out * cotangent)`` (``cotangent``
    as ``out``), and the indexer's loss ``mean_t kl[t]`` with its gradients,
    which are the only ones the indexer's operands get.  What a system's
    kernels are held to on the same operands."""
    assert fault is None or fault in FAULTS, fault
    return _core_vjp(_f32(q), _f32(k), _f32(v), _f32(qi), _f32(ki), _f32(w),
                     selection, _f32(cotangent), topk, operand_bits, fault)


def _sparse_ffn(p, h, top_k, norm_topk_prob, first_expert, bits):
    """(out, load-balancing loss, z-loss) of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    probs = jax.nn.softmax(logits, -1)
    routed = probs.shape[-1]
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    # the weight of every routed expert for every token, 0 if not chosen
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_e].set(top_p)
    ex = p["experts"]
    held = ex["gate"].shape[0]

    def one(out, leaf):          # departure 2: the held experts alone
        gate, up, down, e = leaf
        y = _mm(jax.nn.silu(_mm(h, _f32(gate), bits)) * _mm(h, _f32(up), bits),
                _f32(down), bits)
        w = jax.lax.dynamic_index_in_dim(weight, first_expert + e, 1)  # (T, 1)
        return out + jnp.where(w > 0, y * w, 0.0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    # departure 1: this layer's own f_e and P_e, over all routed experts
    share = (weight > 0).astype(jnp.float32).sum(0) / (h.shape[0] * top_k)
    balance = routed * jnp.sum(share * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, balance, z


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm_stage(x, scale, eps):
    return _rms_norm(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk_prob",
                                             "first_expert", "bits"))
def _ffn_block(p_moe, h, top_k: int, norm_topk_prob: bool, first_expert: int,
               bits=None):
    with jax.default_matmul_precision(_HI):
        return _sparse_ffn(p_moe, h, top_k, norm_topk_prob, first_expert, bits)


def expert_ffn(p_moe, h, *, top_k: int, norm_topk_prob: bool = True,
               first_expert: int = 0, operand_bits=None):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router over all its columns, top-k, the held experts
    from ``first_expert`` on, the weighted partial sum), float32."""
    h = _f32(h)
    return _ffn_block(p_moe, h.reshape(-1, h.shape[-1]), top_k,
                      norm_topk_prob, first_expert,
                      operand_bits)[0].reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("vocab_size", "eps", "bits"))
def _head(params, x, vocab_size: int, eps: float, bits=None):
    with jax.default_matmul_precision(_HI):
        logits = _mm(_rms_norm(x, params["norm"]["scale"], eps),
                     _f32(params["lm_head"]), bits)
        # departure 3: padded vocabulary columns
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def layers(params, n_layer):
    """Each layer's leaves, of an unrolled or a scanned (stacked) stack."""
    if "layers" in params:      # scanned stack: leading layer axis
        for i in range(n_layer):
            yield jax.tree_util.tree_map(lambda a: a[i], params["layers"])
    else:
        for i in range(n_layer):
            yield params[f"layers_{i}"]


def forward(params, input_ids, *, n_layer: int, n_head: int, n_kv_head: int,
            head_dim: int, n_index_head: int, topk: int, vocab_size: int,
            top_k: int, rope_theta: float = 1e7, norm_topk_prob: bool = True,
            eps: float = 1e-6, routed_experts=None, first_expert: int = 0,
            operand_bits=None, fault=None, ffn_inputs=None, attn_inputs=None):
    """``(logits (B, S, padded vocab), balance (L,), z (L,), L_I (L,))`` in
    float32; lists given as ``attn_inputs`` / ``ffn_inputs`` receive each
    layer's normalised hidden states (B, S, E) before its attention / its
    sparse FFN.  ``routed_experts`` is checked against the router's width."""
    assert fault is None or fault in FAULTS, fault
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    balance, z, indexer_loss = [], [], []
    for p in layers(params, n_layer):
        assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        h_attn = _norm_stage(x, p["input_norm"]["scale"], eps)
        a, l_i = _attention(p["self_attn"], h_attn, n_head, n_kv_head,
                            head_dim, n_index_head, topk, float(rope_theta),
                            eps, operand_bits, fault)
        x = x + a
        h = _norm_stage(x, p["post_attention_norm"]["scale"], eps)
        ff, b_l, z_l = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                                  norm_topk_prob, first_expert, operand_bits)
        x = x + ff.reshape(x.shape)
        balance.append(b_l)
        z.append(z_l)
        indexer_loss.append(l_i)
        if attn_inputs is not None:
            attn_inputs.append(h_attn)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    lg = _head({"norm": params["norm"], "lm_head": params["lm_head"]}, x,
               vocab_size=vocab_size, eps=eps, bits=operand_bits)
    return lg, jnp.stack(balance), jnp.stack(z), jnp.stack(indexer_loss)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)[0]


def loss_parts(params, input_ids, *, aux_loss_weight: float = 0.001,
               z_loss_weight: float = 0.0, indexer_loss_weight: float = 1.0,
               **kw):
    """``(next-token cross-entropy, weighted router losses, the weighted
    SUM over the layers of the indexer's loss)``."""
    lg, balance, z, l_i = forward(params, input_ids, **kw)
    lg = lg[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return (nll.mean(),
            aux_loss_weight * balance.mean() + z_loss_weight * z.mean(),
            indexer_loss_weight * l_i.sum())


def training_loss(params, input_ids, **kw):
    """CE + the router losses + the indexer's loss."""
    return sum(loss_parts(params, input_ids, **kw))


def loss_and_grads(params, input_ids, **kw):
    """``(loss, d loss / d params)`` by ``jax.grad`` of
    :func:`training_loss`."""
    return jax.value_and_grad(
        lambda p: training_loss(p, input_ids, **kw))(params)
