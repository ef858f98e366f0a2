"""Plain Ling-3.0-flash forward and training loss: float32 ``jax.numpy`` at
"highest" matmul precision; no kernels, no chunks, no sort, no grouped
matmul, no cache, no chunked head.  Gradients are ``jax.grad`` of
:func:`training_loss`.

Follows the model's public ``config.json`` (inclusionAI/Ling-3.0-flash,
``model_type: bailing_hybrid``) and, where the config gives a key and no
form, the published form named beside it (marked (assumed) below; the
configuration file lists each under ``assumed``).

``N(x) = x * rsqrt(mean(x^2) + eps) * w``.  *Block* ``l`` (no bias
anywhere)::

    x <- x + mixer_l(N_in(x));  x <- x + ffn_l(N_post(x))
    logits = N_final(x) @ W_head                      # untied

*mixer_l* where ``layer_types[l] == "kda_attention"`` (Kimi Delta
Attention, arXiv:2510.26692; H heads of d channels, keys and values alike;
h the normed input)::

    q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
                        # depthwise, L taps, causal, the LAST tap is the
                        # current position; the taps are ONE leaf (3 H d, L):
                        # q's rows, then k's, then v's
    q <- q * rsqrt(sum(q^2) + 1e-6) * d^-1/2;  k <- k * rsqrt(sum(k^2) + 1e-6)
    g = lower_bound * sigmoid(exp(A_log_head) * (h W_f + dt_bias))
                        # (B, S, H, d) in (lower_bound, 0): a log-decay a KEY
                        # CHANNEL (assumed: fla's gate under kda_safe_gate)
    beta = sigmoid(h W_b)                                       # a head
    a head, S (d x d, keys x values) from zeros at every row:
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    y = (o * rsqrt(mean(o^2) + eps) * w_o) * sigmoid(h W_g)     # a head
    out = y W_o

**the recurrence is a ``lax.scan`` over POSITIONS**, one token a step: the
system under test runs it in chunks of 64 whose decays it forms 16
positions at a time, and that is what is tested.

*mixer_l* where ``full_attention`` (latent attention, DeepSeek-V2's,
WITHOUT a query latent and WITH a gate a head)::

    [q_nope | q_rope] = h W_q               # H*nope | H*rope columns
    [c_kv | k_rope] = h W_kva;  c_kv <- N(c_kv);  [k_nope | v] = c_kv W_kvb
    rotary over interleaved pairs (2i, 2i+1) of q_rope and of k_rope, ONE
    rope key for all heads; scores over nope + rope channels at scale
    (nope + rope)^-1/2, causal softmax, values v_dim wide
    out = (attn_head * sigmoid(h W_gate)_head) W_o      # W_gate (E, H)
                                                        # (assumed: head_wise)

*ffn_l*: the dense SwiGLU for ``l < num_dense_layers``; else s =
sigmoid(h W_r) over ALL routed experts; selection on ``s + bias``: the
experts lie in ``n_group`` groups of neighbours, a group scores the sum of
its two best ``s + bias``, the ``topk_group`` best groups stay, top-k among
their experts; weights are the chosen ``s`` over their sum (+ 1e-20) times
``route_scale``; plus the shared SwiGLU for every token.

*prediction block* (``mtp_layers`` 1): DeepSeek-V3's, with a
latent-attention block of the sparse kind whatever the stack ends on.

Departures, each marked below:

1. **the share**: the leaves hold ``first_expert .. first_expert + held -
   1`` of the routed experts; routing, groups and renormalisation are over
   all of them; only the held experts' terms are computed.  The shared
   expert is whole and counted once.
2. the vocabulary is the slice the head holds; padded columns are masked.
3. no attention mask and no state reset between packed documents.
4. no auxiliary loss (``seq_aux`` has no coefficient in the config).

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication, the filter's products and the recurrence's q, k, v to that
float format first.  ``fault`` makes :func:`kda` (:data:`KDA_FAULTS`),
:func:`attention` (:data:`FAULTS`), :func:`sparse_ffn`
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
KDA, FULL = "kda_attention", "full_attention"
L2_EPS = 1e-6           # the l2-norm of q and k (fla's l2norm)
FAULT_CHUNK = 64        # where "chunk_reset" forgets the state
SEGMENT = 64            # positions whose states the backward recomputes together
KDA_FAULTS = ("decay_head_mean", "no_decay", "beta_one", "no_l2norm",
              "q_unscaled", "taps_reversed", "no_silu", "gate_silu",
              "gate_before_norm", "no_dt_bias", "softplus_gate",
              "chunk_reset")
FAULTS = ("no_gate", "gate_before_softmax_scale", "rope_on_nope",
          "halves_on_q", "scale_nope", "k_rope_next_position",
          "no_kv_latent_norm")
EXPERT_FAULTS = ("no_groups", "group_max", "bias_ignored", "bias_in_weights",
                 "no_scale", "no_shared")
DENSE_FAULTS = ("gate_up_swapped",)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _round(t, bits):
    return t if bits is None else jax.lax.reduce_precision(t, *bits)


def _mm(a, b, bits):
    return _round(a, bits) @ _round(b, bits)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _swiglu(x, gate, up, down, bits):
    return _mm(jax.nn.silu(_mm(x, _f32(gate), bits)) * _mm(x, _f32(up), bits),
               _f32(down), bits)


# ----------------------------------------------------------------------
# Kimi Delta Attention
# ----------------------------------------------------------------------
def _conv(x, w, bits, fault):
    """``c_t = sum_j w[:, j] x_{t-(L-1)+j}``: a loop over the taps of the
    zero-padded sequence."""
    S, L = x.shape[1], w.shape[1]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    x_, w_ = _round(jnp.pad(x, ((0, 0), (L - 1, 0), (0, 0))), bits), \
        _round(w, bits)
    c = jnp.zeros_like(x)
    for j in range(L):
        c = c + w_[:, j] * x_[:, j:j + S]
    return c


def kda_rule(q, k, v, g, beta, *, fault=None):
    """The recurrence itself, one position a step: q, k, v (B, S, H, d), g
    (B, S, H, d) a log-decay a key channel, beta (B, S, H), float32;
    returns o (B, S, H, d)."""
    B, S, H, d = v.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs
        if fault == "chunk_reset":
            state = jnp.where(t % FAULT_CHUNK == 0, 0.0, state)
        state = state * jnp.exp(g_t)[..., :, None]      # Diag(exp(g)) S
        kv = (state * k_t[..., :, None]).sum(-2)        # S^T k
        delta = (v_t - kv) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, (state * q_t[..., :, None]).sum(-2)   # S^T q

    def row_major(x):               # positions lead
        return jnp.moveaxis(x, 1, 0)

    seg = SEGMENT if S % SEGMENT == 0 else S
    xs = tuple(row_major(x) for x in (q, k, v, g, beta)) + (jnp.arange(S),)
    # a segment at a time: the same steps in the same order; a segment's
    # states are recomputed in the backward (8192 states of 32 heads would
    # be 16 GB kept)
    xs = jax.tree_util.tree_map(
        lambda x: x.reshape((S // seg, seg) + x.shape[1:]), xs)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)),
        jnp.zeros((B, H, k.shape[-1], d), jnp.float32), xs)
    return row_major(o.reshape((S,) + o.shape[2:]))


def _kda(p, h, n_head, lower_bound, eps, bits, fault):
    B, S, _ = h.shape
    H = n_head
    q, k, v = (_mm(h, _f32(p[n + "_proj_kernel"]), bits)
               for n in ("q", "k", "v"))
    W = q.shape[-1]
    d = W // H
    qkv = _conv(jnp.concatenate([q, k, v], -1), _f32(p["conv_kernel"]), bits,
                fault)
    if fault != "no_silu":
        qkv = jax.nn.silu(qkv)
    q, k, v = (qkv[..., i * W:(i + 1) * W].reshape(B, S, H, d)
               for i in range(3))
    if fault != "no_l2norm":
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    if fault != "q_unscaled":
        q = q * d ** -0.5
    f = _mm(h, _f32(p["f_proj_kernel"]), bits)
    if fault != "no_dt_bias":
        f = f + _f32(p["dt_bias"])
    a = jnp.exp(_f32(p["A_log"]))[:, None]              # a head
    f = f.reshape(B, S, H, d)
    g = -a * jax.nn.softplus(f) if fault == "softplus_gate" \
        else lower_bound * jax.nn.sigmoid(a * f)        # (assumed)
    if fault == "decay_head_mean":      # one decay a head: Gated DeltaNet's
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    b = _mm(h, _f32(p["b_proj_kernel"]), bits)
    beta = jnp.ones_like(b) if fault == "beta_one" else jax.nn.sigmoid(b)
    o = kda_rule(_round(q, bits), _round(k, bits), _round(v, bits), g, beta,
                 fault=fault)
    z = _mm(h, _f32(p["g_proj_kernel"]), bits).reshape(B, S, H, d)
    gate = jax.nn.silu(z) if fault == "gate_silu" else jax.nn.sigmoid(z)
    if fault == "gate_before_norm":
        y = _rms_norm(o * gate, p["o_norm"], eps)
    else:                               # norm first, gate second (assumed)
        y = _rms_norm(o, p["o_norm"], eps) * gate
    return _mm(y.reshape(B, S, W), _f32(p["o_proj_kernel"]), bits)


_KDA_STATIC = ("n_head", "lower_bound", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_KDA_STATIC)
def _kda_alone(p, h, **kw):
    with jax.default_matmul_precision(_HI):
        return _kda(p, h, **kw)


def _kda_kw(n_head, lower_bound=-5.0, eps=1e-6, operand_bits=None,
            fault=None, **_):
    assert fault is None or fault in KDA_FAULTS, fault
    return dict(n_head=n_head, lower_bound=float(lower_bound), eps=eps,
                bits=operand_bits, fault=fault)


def kda(p_kda, h, **kw):
    """One Kimi-Delta-Attention mixer alone: normalised hidden states ``h``
    (B, S, E) through the layer's ``kda_attn`` leaves, float32."""
    return _kda_alone(p_kda, _f32(h), **_kda_kw(**kw))


@functools.partial(jax.jit, static_argnames=_KDA_STATIC)
def _kda_grads(p, h, probe, **kw):
    with jax.default_matmul_precision(_HI):
        y, pull = jax.vjp(lambda h, p: _kda(p, h, **kw), h, p)
        return (y,) + pull(probe)


def kda_grads(p_kda, h, probe, **kw):
    """``(y, dh, {leaf: d leaf})``: the mixer's output and the gradients of
    ``sum(y * probe)`` with respect to ``h`` and every leaf, from one
    compiled function."""
    return _kda_grads({k: _f32(v) for k, v in p_kda.items()}, _f32(h),
                      _f32(probe), **_kda_kw(**kw))


# ----------------------------------------------------------------------
# latent attention, no query latent, a gate a head
# ----------------------------------------------------------------------
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


def _attention(p, x, n_head, kv_lora_rank, nope, rope, v_dim, rope_theta,
               eps, bits, fault):
    B, S, E = x.shape
    H = n_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits)          # no query latent
    q_nope = q[..., :H * nope].reshape(B, S, H, nope)
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
    k_rope = _rotary(k_rope, rope_theta,
                     offset=1 if fault == "k_rope_next_position" else 0)
    if fault == "rope_on_nope":
        q_nope, k_nope = (_rotary(t, rope_theta) for t in (q_nope, k_nope))
    scale = 1.0 / np.sqrt(nope if fault == "scale_nope" else nope + rope)
    gate = jax.nn.sigmoid(_mm(x, _f32(p["gate_proj_kernel"]), bits))  # (B,S,H)
    qh = jnp.concatenate([q_nope, q_rope], -1)               # (B, S, H, 192)
    if fault == "gate_before_softmax_scale":    # the gate on q: a wrong place
        qh = qh * gate[..., None]
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
        keep = (i0 + jnp.arange(qb))[:, None] - j >= 0
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), vt, bits)          # (B, H, qb, Dv)

    a = jax.lax.map(block, (jnp.moveaxis(qs_, 2, 0), jnp.arange(0, S, qb)))
    a = jnp.moveaxis(a, 0, 2).reshape(B, H, S, v_dim).transpose(0, 2, 1, 3)
    if fault not in ("no_gate", "gate_before_softmax_scale"):
        a = a * gate[..., None]                 # a gate a head (assumed)
    return _mm(a.reshape(B, S, H * v_dim), _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("n_head", "kv_lora_rank", "nope", "rope", "v_dim",
                "rope_theta", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
             v_head_dim, rope_theta, eps=1e-6, operand_bits=None, fault=None,
             **_):
    assert fault is None or fault in FAULTS, fault
    return dict(n_head=n_head, kv_lora_rank=kv_lora_rank,
                nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                v_dim=v_head_dim, rope_theta=float(rope_theta), eps=eps,
                bits=operand_bits, fault=fault)


def attention(h_normed, p_attn, **kw):
    """One latent-attention layer alone: normalised hidden states (B, S, E)
    through the layer's ``self_attn`` leaves, float32."""
    return _attention_alone(p_attn, _f32(h_normed), **_attn_kw(**kw))


# ----------------------------------------------------------------------
# the FFNs
# ----------------------------------------------------------------------
def group_allowed(picking, n_group: int, topk_group: int, fault=None):
    """(T, routed) bool: the experts of each token's ``topk_group`` best
    groups; a group (``routed / n_group`` neighbours) scores the sum of its
    two best ``picking`` (score + bias)."""
    T, E = picking.shape
    per = picking.reshape(T, n_group, E // n_group)
    score = per.max(-1) if fault == "group_max" \
        else jnp.sort(per, -1)[..., -2:].sum(-1)
    _, best = jax.lax.top_k(score, topk_group)
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    return jnp.repeat(kept, E // n_group, axis=1)


def _route(p, h, top_k, route_scale, n_group, topk_group, bits, fault):
    """``(chosen (T, routed) bool, weight (T, routed), changed)``: the
    routing of tokens ``h`` (T, E); ``changed`` the share of the (token,
    choice) pairs of the top-k WITHOUT groups that the group limit moved."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    scores = jax.nn.sigmoid(logits)
    bias = _f32(p["gate"]["expert_bias"])
    picking = scores if fault == "bias_ignored" else scores + bias
    rows = jnp.arange(h.shape[0])[:, None]
    free = jnp.zeros(scores.shape, bool).at[
        rows, jax.lax.top_k(picking, top_k)[1]].set(True)
    if n_group > 1 and fault != "no_groups":
        picking = jnp.where(group_allowed(picking, n_group, topk_group,
                                          fault), picking, -jnp.inf)
    _, top_e = jax.lax.top_k(picking, top_k)
    weighing = scores + bias if fault == "bias_in_weights" else scores
    top_s = jnp.take_along_axis(weighing, top_e, -1)
    top_w = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_scale":
        top_w = top_w * route_scale
    chosen = jnp.zeros(scores.shape, bool).at[rows, top_e].set(True)
    weight = jnp.zeros_like(scores).at[rows, top_e].set(top_w)
    changed = (free & ~chosen).sum() / (h.shape[0] * top_k)
    return chosen, weight, changed


def _sparse_ffn(p, h, top_k, route_scale, first_expert, n_group, topk_group,
                bits, fault):
    """``(out, counts (routed experts,), changed)`` of tokens ``h`` (T, E)."""
    chosen, weight, changed = _route(p, h, top_k, route_scale, n_group,
                                     topk_group, bits, fault)
    ex = p["experts"]
    held = ex["gate"].shape[0]

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
    return out, chosen.sum(0).astype(jnp.int32), changed


_FFN_STATIC = ("top_k", "route_scale", "first_expert", "n_group",
               "topk_group", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_FFN_STATIC)
def _ffn_block(p_moe, h, top_k: int, route_scale: float, first_expert: int,
               n_group: int = 1, topk_group: int = 1, bits=None, fault=None):
    with jax.default_matmul_precision(_HI):
        return _sparse_ffn(p_moe, h, top_k, route_scale, first_expert,
                           n_group, topk_group, bits, fault)


def sparse_ffn(p_moe, h, *, top_k: int, route_scale: float,
               first_expert: int = 0, n_group: int = 1, topk_group: int = 1,
               operand_bits=None, fault=None, with_changed: bool = False):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router over all its columns, its bias, the group limit,
    top-k, the held experts from ``first_expert`` on, the shared expert),
    float32.  ``with_changed``: ``(out, share of the pairs the group limit
    moved)``."""
    assert fault is None or fault in EXPERT_FAULTS, fault
    h = _f32(h)
    out, _, changed = _ffn_block(
        p_moe, h.reshape(-1, h.shape[-1]), top_k, float(route_scale),
        first_expert, n_group, topk_group, operand_bits, fault)
    out = out.reshape(h.shape)
    return (out, float(changed)) if with_changed else out


@functools.partial(jax.jit, static_argnames=("bits",))
def _dense_block(p, h, bits=None):
    with jax.default_matmul_precision(_HI):
        return _swiglu(h, p["gate_proj_kernel"], p["up_proj_kernel"],
                       p["down_proj_kernel"], bits)


def dense_ffn(p_layer, h, *, operand_bits=None, fault=None):
    """The leading dense layer's SwiGLU alone, float32."""
    assert fault is None or fault in DENSE_FAULTS, fault
    gate, up = ("up", "gate") if fault == "gate_up_swapped" else ("gate", "up")
    return _dense_block({"gate_proj_kernel": p_layer[gate + "_proj_kernel"],
                         "up_proj_kernel": p_layer[up + "_proj_kernel"],
                         "down_proj_kernel": p_layer["down_proj_kernel"]},
                        _f32(h), operand_bits)


def bias_update(counts, b, rate: float):
    """``b + rate * sign(mean(counts) - counts)`` in float32 (DeepSeek-V3
    report, section 2.1.2: the bias has no gradient and no optimizer
    state)."""
    c = np.asarray(counts).astype(np.float32)
    return (np.asarray(b, np.float32)
            + np.float32(rate) * np.sign(c.mean(dtype=np.float32) - c))


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
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


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _eh_proj(p, e, h, eps, bits=None):
    with jax.default_matmul_precision(_HI):
        return _mm(jnp.concatenate(
            [_rms_norm(e, p["enorm"]["scale"], eps),
             _rms_norm(h, p["hnorm"]["scale"], eps)], -1),
            _f32(p["eh_proj_kernel"]), bits)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: the blocks differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _residual_norms(x, mix, p, eps):
    """``(x + mix, N_post(x + mix))``."""
    x = x + mix
    return x, _rms_norm(x, p["post_attention_norm"]["scale"], eps)


def _block(p, x, kind, sparse, kw, counts=None, mixer_inputs=None,
           ffn_inputs=None):
    eps, bits = kw.get("eps", 1e-6), kw.get("operand_bits")
    h_mix = _normed(x, p["input_norm"]["scale"], eps)
    if kind == KDA:
        mix = _kda_alone(p["kda_attn"], h_mix, **_kda_kw(**kw))
    else:
        mix = _attention_alone(p["self_attn"], h_mix, **_attn_kw(**kw))
    x, h = _residual_norms(x, mix, p, eps)
    if sparse:
        ff, c, _ = _ffn_block(
            p["moe"], h.reshape(-1, h.shape[-1]), kw["top_k"],
            float(kw["route_scale"]), kw.get("first_expert", 0),
            kw.get("n_group", 1), kw.get("topk_group", 1), bits)
        ff = ff.reshape(x.shape)
        if counts is not None:
            counts.append(c)
    else:
        ff = dense_ffn(p, h, operand_bits=bits)
    if mixer_inputs is not None:
        mixer_inputs.append(h_mix)
    if ffn_inputs is not None:
        ffn_inputs.append(h)
    return x + ff


def hidden(params, input_ids, *, n_layer: int, layer_types,
           num_dense_layers: int, routed_experts=None, ffn_inputs=None,
           mixer_inputs=None, counts=None, **kw):
    """The stack's output (B, S, E) BEFORE the final norm.  ``kw``: the
    widths and routing under the configuration file's ``reference_args``
    names (``n_head``, ``lower_bound``, ``kv_lora_rank``, ``qk_nope_head_
    dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``, ``eps``,
    ``top_k``, ``route_scale``, ``n_group``, ``topk_group``,
    ``first_expert``, ``operand_bits``).  Lists given as ``mixer_inputs`` /
    ``ffn_inputs`` receive each layer's normalised hidden states before its
    mixer / FFN, ``counts`` each sparse layer's pairs an expert."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    for i, p in enumerate(layers(params, n_layer)):
        sparse = i >= num_dense_layers
        if sparse:
            assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        x = _block(p, x, layer_types[i], sparse, kw, counts, mixer_inputs,
                   ffn_inputs)
    return x


def main_nll(h, input_ids, params, *, vocab_size, eps=1e-6,
             operand_bits=None, **_):
    """Per-position negative log-likelihood (B, S - 1) of token ``i + 1``
    at position ``i`` from the stack's output ``h``: the final norm, the
    head."""
    ids = jnp.asarray(input_ids)
    return _nll(_normed(_f32(h), params["norm"]["scale"], eps)[:, :-1],
                params["lm_head"], ids[:, 1:], vocab_size=vocab_size,
                bits=operand_bits)


def mtp(h, input_ids, params, *, vocab_size, eps=1e-6, operand_bits=None,
        **kw):
    """The multi-token-prediction block alone: per-position negative
    log-likelihood (B, S - 2) of token ``i + 2`` at position ``i``, through
    the MAIN model's table and head, from the stack's output ``h`` (before
    the final norm): ``x_i = [N_e(E[t_{i+1}]) ; N_h(h_i)] W_eh``, one
    latent-attention block of the sparse kind, ``N_shared_head``."""
    p = params["mtp_0"]
    ids = jnp.asarray(input_ids)
    S = ids.shape[1]
    x = _eh_proj(p, _f32(params["embed_tokens"])[ids[:, 1:]],
                 _f32(h)[:, :-1], eps, operand_bits)
    # one row short of the others: pad to the block size and cut again (the
    # pad is a LATER position, which no causal position reads)
    x = jnp.pad(x, ((0, 0), (0, 1), (0, 0)))
    x = _block(p["block"], x, FULL, True,
               dict(kw, eps=eps, operand_bits=operand_bits))
    x = _normed(x, p["shared_head_norm"]["scale"], eps)[:, :S - 2]
    return _nll(x, _f32(params["lm_head"]), ids[:, 2:],
                vocab_size=vocab_size, bits=operand_bits)


def loss_parts(params, input_ids, *, vocab_size, mtp_layers: int = 0, **kw):
    """``(CE_main, CE_mtp)``: next-token cross-entropy over positions 0 ..
    S-2 and the prediction block's over 0 .. S-3 (0.0 without one)."""
    ids = jnp.asarray(input_ids)
    h = hidden(params, ids, **kw)
    main = main_nll(h, ids, params, vocab_size=vocab_size, **kw).mean()
    if not mtp_layers:
        return main, jnp.float32(0.0)
    skip = ("ffn_inputs", "mixer_inputs", "counts", "n_layer", "layer_types",
            "num_dense_layers", "routed_experts")
    return main, mtp(h, ids, params, vocab_size=vocab_size,
                     **{k: v for k, v in kw.items() if k not in skip}).mean()


def training_loss(params, input_ids, *, mtp_weight: float = 0.0, **kw):
    """CE_main + ``mtp_weight`` * CE_mtp (departure 4: no router loss)."""
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
