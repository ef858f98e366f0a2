"""Plain Qwen3-Next forward and training loss: float32 ``jax.numpy`` at
"highest" matmul precision; no kernels, no chunks, no sort, no grouped
matmul, no cache, no chunked head.  Gradients are ``jax.grad`` of
:func:`training_loss`.

Follows the model's public ``config.json`` (Qwen/Qwen3-Next-80B-A3B-
Instruct, ``model_type: qwen3_next``) and, where the config has no key,
the family's released modeling code (``transformers``,
``models/qwen3_next``); those places are marked (released code) below.

``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``: every RMSNorm of the
model is ZERO-CENTRED (released code: ``Qwen3NextRMSNorm``), but the gated
one after the delta rule, which multiplies by ``w`` (``Qwen3NextRMSNorm
Gated``).  *Block* ``l`` (every projection without bias)::

    x <- x + mixer_l(N_in(x));  x <- x + moe_l(N_post(x));
    logits = N_final(x) @ W_head                      # untied

*mixer_l* where ``layer_types[l] == "linear_attention"`` (Gated DeltaNet;
h the normed input, Hk key heads, Hv value heads of d channels)::

    [q ; k ; v ; z] = h W_qkvz          # contiguous: Hk*d | Hk*d | Hv*d | Hv*d
    [b ; a]         = h W_ba            # Hv | Hv
    [q ; k ; v]    <- silu(conv([q ; k ; v]))     # depthwise, L taps, causal,
                                        # the LAST tap is the current position
    beta = sigmoid(b);   g = -exp(A_log) * softplus(a + dt_bias)
    q, k: key head j serves value heads j*r .. j*r + r - 1 (r = Hv / Hk)
    q <- q * rsqrt(sum(q^2) + 1e-6) * d^-1/2;  k <- k * rsqrt(sum(k^2) + 1e-6)
    a value head, S (d x d, keys x values) from zeros at every row:
        S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
        o_t = S_t^T q_t
    y = (o * rsqrt(mean(o^2) + eps) * w_o) * silu(z)     # a head's d channels
    out = y W_out

**the recurrence is a ``lax.scan`` over POSITIONS**, one token a step: the
system under test runs it in chunks, and the chunking is what is tested.

*mixer_l* where ``full_attention``: q = h W_q, gate = h W_gate (the
source writes both as one projection, split a head: a column permutation),
k, v = h W_k, h W_v; N over each head's channels of q and of k (one w
each); half-split rotary over the FIRST ``rotary_dim = head_dim *
partial_rotary_factor`` channels of each head, pairs ``(i, i +
rotary_dim/2)``, the rest pass; causal softmax attention, key-value head
``h // (heads / kv heads)``; ``out = (attn * sigmoid(gate)) W_o``.

*moe_l*: p = softmax(h W_r) over ALL routed experts; top-k of p; weights
renormalised to sum 1; routed = sum of weight * SwiGLU_e(h); ``out =
routed + sigmoid(h . w_g) * SwiGLU_shared(h)``.

Departures, each marked below:

1. **the share**: the leaves hold ``first_expert .. first_expert + held -
   1`` of the routed experts; routing, renormalisation and the auxiliary
   loss are over all of them; only the held experts' terms are computed
   (model-configs guide, section 4).  The shared expert and its gate are
   whole and counted once.
2. the load-balancing loss (``routed * sum_e f_e P_e``) a layer, averaged
   over the layers (as ``reference/mellum2.py``).
3. the vocabulary is the slice the head holds; padded columns are masked.
4. no attention mask and no state reset between packed documents.
5. no multi-token-prediction block: the config has no key for one.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication, the filter's products and the recurrence's q, k, v to that
float format first.  ``fault`` makes :func:`linear_attention`
(:data:`LINEAR_FAULTS`), :func:`attention` (:data:`FAULTS`) or
:func:`expert_ffn` (:data:`EXPERT_FAULTS`) compute a named WRONG thing, to
read what a tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6           # the l2-norm of q and k (released code)
FAULT_CHUNK = 64        # where "chunk_reset" forgets the state
SEGMENT = 64            # positions whose states the backward recomputes together
LINEAR_FAULTS = ("no_decay", "beta_one", "no_l2norm", "q_unscaled",
                 "taps_reversed", "no_silu", "gate_sigmoid",
                 "gate_before_norm", "norm_zero_centred", "k_head_mod",
                 "chunk_reset", "row_leak")
FAULTS = ("rope_all", "rope_last", "no_gate", "norm_plain", "kv_mod")
EXPERT_FAULTS = ("top_8", "no_renorm", "no_shared_gate",
                 "shared_per_expert")


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _round(t, bits):
    return t if bits is None else jax.lax.reduce_precision(t, *bits)


def _mm(a, b, bits):
    return _round(a, bits) @ _round(b, bits)


def _norm(x, w, eps, centred=True):
    """``N(x)``; ``centred=False``: times ``w`` itself."""
    scale = 1.0 + _f32(w) if centred else _f32(w)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, gate, up, down, bits):
    return _mm(jax.nn.silu(_mm(x, _f32(gate), bits)) * _mm(x, _f32(up), bits),
               _f32(down), bits)


# ----------------------------------------------------------------------
# Gated DeltaNet
# ----------------------------------------------------------------------
def _conv(x, w, bits, fault):
    """``c_t = sum_j w[:, j] x_{t-(L-1)+j}``: a loop over the taps of the
    zero-padded sequence (released code: a depthwise Conv1d with padding
    L - 1 cut to the sequence)."""
    S, L = x.shape[1], w.shape[1]
    if fault == "taps_reversed":
        w = w[:, ::-1]
    x_, w_ = _round(jnp.pad(x, ((0, 0), (L - 1, 0), (0, 0))), bits), \
        _round(w, bits)
    c = jnp.zeros_like(x)
    for j in range(L):
        c = c + w_[:, j] * x_[:, j:j + S]
    return c


def delta_rule(q, k, v, g, beta, *, fault=None):
    """The recurrence itself, one position a step: q, k, v (B, S, H, d), g
    and beta (B, S, H), float32; returns o (B, S, H, d)."""
    B, S, H, d = v.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs
        if fault == "chunk_reset":
            state = jnp.where(t % FAULT_CHUNK == 0, 0.0, state)
        state = state * jnp.exp(g_t)[..., None, None]
        kv = (state * k_t[..., :, None]).sum(-2)            # S^T k
        delta = (v_t - kv) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        return state, (state * q_t[..., :, None]).sum(-2)   # S^T q

    def row_major(x):               # positions lead
        return jnp.moveaxis(x, 1, 0)

    seg = SEGMENT if S % SEGMENT == 0 else S

    def run(state, xs):
        """All positions of ``xs``, a segment at a time: the same steps in
        the same order; a segment's states are recomputed in the backward
        (8192 states of 32 heads would be 16 GB kept)."""
        xs = jax.tree_util.tree_map(
            lambda x: x.reshape((S // seg, seg) + x.shape[1:]), xs)
        state, o = jax.lax.scan(
            jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)), state, xs)
        return state, o.reshape((S,) + o.shape[2:])

    xs = tuple(row_major(x) for x in (q, k, v, g, beta)) + (jnp.arange(S),)
    zeros = jnp.zeros((B, H, d, d), jnp.float32)
    if fault != "row_leak":
        return row_major(run(zeros, xs)[1])
    # a row starts from the state the row before it ended in
    outs, state = [], zeros[:1]
    for b in range(B):
        state, o = run(state, tuple(x[:, b:b + 1] for x in xs[:-1])
                       + (xs[-1],))
        outs.append(o)
    return row_major(jnp.concatenate(outs, axis=1))


def _linear_attention(p, h, n_k_heads, n_v_heads, eps, bits, fault):
    B, S, _ = h.shape
    Hk, Hv = n_k_heads, n_v_heads
    qkvz = _mm(h, _f32(p["in_proj_qkvz_kernel"]), bits)
    ba = _mm(h, _f32(p["in_proj_ba_kernel"]), bits)
    w = _f32(p["conv_kernel"])
    conv_dim = w.shape[0]
    d = (qkvz.shape[-1] - conv_dim) // Hv
    qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
    qkv = _conv(qkv, w, bits, fault)
    if fault != "no_silu":      # (released code: the filter's activation)
        qkv = jax.nn.silu(qkv)
    q = qkv[..., :Hk * d].reshape(B, S, Hk, d)
    k = qkv[..., Hk * d:2 * Hk * d].reshape(B, S, Hk, d)
    v = qkv[..., 2 * Hk * d:].reshape(B, S, Hv, d)
    b, a = ba[..., :Hv], ba[..., Hv:]
    beta = jnp.ones_like(b) if fault == "beta_one" else jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(a + _f32(p["dt_bias"]))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    # key head of each value head (released code: repeat_interleave)
    k_of = np.arange(Hv) % Hk if fault == "k_head_mod" \
        else np.arange(Hv) // (Hv // Hk)
    q, k = q[:, :, k_of], k[:, :, k_of]
    if fault != "no_l2norm":
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    if fault != "q_unscaled":
        q = q * d ** -0.5
    o = delta_rule(_round(q, bits), _round(k, bits), _round(v, bits), g,
                   beta, fault=fault)
    z = z.reshape(B, S, Hv, d)
    gate = jax.nn.sigmoid(z) if fault == "gate_sigmoid" else jax.nn.silu(z)
    if fault == "gate_before_norm":
        y = _norm(o * gate, p["o_norm"], eps, centred=False)
    else:       # norm first, gate second; w from ones, not zero-centred
        y = _norm(o, p["o_norm"], eps,
                  centred=fault == "norm_zero_centred") * gate
    return _mm(y.reshape(B, S, Hv * d), _f32(p["out_proj_kernel"]), bits)


_LIN_STATIC = ("n_k_heads", "n_v_heads", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_LIN_STATIC)
def _linear_alone(p, h, **kw):
    with jax.default_matmul_precision(_HI):
        return _linear_attention(p, h, **kw)


def linear_attention(p_lin, h, *, n_k_heads, n_v_heads, eps=1e-6,
                     operand_bits=None, fault=None):
    """One Gated DeltaNet mixer alone: normalised hidden states ``h`` (B,
    S, E) through the layer's ``linear_attn`` leaves, float32."""
    assert fault is None or fault in LINEAR_FAULTS, fault
    return _linear_alone(p_lin, _f32(h), n_k_heads=n_k_heads,
                         n_v_heads=n_v_heads, eps=eps, bits=operand_bits,
                         fault=fault)


def linear_attention_grads(p_lin, h, probe, **kw):
    """``(y, dh, {leaf: d leaf})``: the mixer's output and the gradients of
    ``sum(y * probe)`` with respect to ``h`` and every leaf, from one
    compiled function."""
    assert kw.get("fault") is None or kw["fault"] in LINEAR_FAULTS, kw
    return _linear_grads(
        {k: _f32(v) for k, v in p_lin.items()}, _f32(h), _f32(probe),
        n_k_heads=kw["n_k_heads"], n_v_heads=kw["n_v_heads"],
        eps=kw.get("eps", 1e-6), bits=kw.get("operand_bits"),
        fault=kw.get("fault"))


@functools.partial(jax.jit, static_argnames=_LIN_STATIC)
def _linear_grads(p, h, probe, **kw):
    with jax.default_matmul_precision(_HI):
        y, pull = jax.vjp(lambda h, p: _linear_attention(p, h, **kw), h, p)
        return (y,) + pull(probe)


# ----------------------------------------------------------------------
# gated attention
# ----------------------------------------------------------------------
def _rotary(x, theta, rotary_dim, last=False):
    """Half-split rotation (HF ``rotate_half``) of the first (``last``:
    the last) ``rotary_dim`` channels of x (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    r = rotary_dim
    inv_freq = theta ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    rot, rest = (x[..., D - r:], x[..., :D - r]) if last \
        else (x[..., :r], x[..., r:])
    x1, x2 = rot[..., : r // 2], rot[..., r // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rest, rot] if last else [rot, rest], -1)


def _attention(p, x, n_head, n_kv_head, head_dim, rope_theta, rotary_dim,
               eps, bits, fault):
    B, S, E = x.shape
    D, group = head_dim, n_head // n_kv_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits).reshape(B, S, n_head, D)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    # each head's channels, zero-centred, BEFORE the rotation (released code)
    centred = fault != "norm_plain"
    q = _norm(q, p["q_norm"]["scale"], eps, centred)
    k = _norm(k, p["k_norm"]["scale"], eps, centred)
    r = D if fault == "rope_all" else rotary_dim
    q, k = (_rotary(t, rope_theta, r, last=fault == "rope_last")
            for t in (q, k))
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
    a = a.reshape(B, S, n_head * D)
    if fault != "no_gate":
        a = a * jax.nn.sigmoid(_mm(x, _f32(p["gate_proj_kernel"]), bits))
    return _mm(a, _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("n_head", "n_kv_head", "head_dim", "rope_theta",
                "rotary_dim", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(layer_type, p_attn, h, *, n_head, n_kv_head, head_dim,
              rope_theta=1e7, partial_rotary_factor=0.25, eps=1e-6,
              operand_bits=None, fault=None):
    """One gated attention layer alone: normalised hidden states ``h`` (B,
    S, E) through the layer's ``self_attn`` leaves, float32."""
    assert layer_type == FULL, layer_type
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(
        p_attn, _f32(h), n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, rope_theta=float(rope_theta),
        rotary_dim=int(head_dim * partial_rotary_factor), eps=eps,
        bits=operand_bits, fault=fault)


# ----------------------------------------------------------------------
# the sparse feed-forward
# ----------------------------------------------------------------------
def _sparse_ffn(p, h, top_k, first_expert, bits, fault):
    """``(out, load-balancing loss)`` of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)         # (T, routed experts)
    probs = jax.nn.softmax(logits, -1)
    routed = probs.shape[-1]
    k = 8 if fault == "top_8" else top_k
    top_p, top_e = jax.lax.top_k(probs, k)
    if fault != "no_renorm":        # norm_topk_prob
        top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    chosen = jnp.zeros(probs.shape, bool).at[rows, top_e].set(True)
    weight = jnp.zeros_like(probs).at[rows, top_e].set(top_p)
    ex = p["experts"]
    held = ex["gate"].shape[0]
    sh = p["shared"]

    def one(out, leaf):          # departure 1: the held experts alone
        gate, up, down, e = leaf
        y = _swiglu(h, gate, up, down, bits)
        w = jax.lax.dynamic_index_in_dim(weight, first_expert + e, 1)  # (T, 1)
        c = jax.lax.dynamic_index_in_dim(chosen, first_expert + e, 1)
        return out + jnp.where(c, y * w, 0.0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    # one shared expert under a scalar sigmoid gate a token (released code),
    # whole on every share and counted once
    shared = _swiglu(h, sh["gate"], sh["up"], sh["down"], bits)
    if fault != "no_shared_gate":
        shared = shared * jax.nn.sigmoid(
            _mm(h, _f32(sh["token_gate"])[:, None], bits))
    if fault == "shared_per_expert":
        shared = shared * held
    # departure 2: this layer's own f_e and P_e, over all routed experts
    share = chosen.astype(jnp.float32).sum(0) / (h.shape[0] * k)
    balance = routed * jnp.sum(share * probs.mean(0))
    return out + shared, balance


@functools.partial(jax.jit, static_argnames=("top_k", "first_expert", "bits",
                                             "fault"))
def _ffn_block(p_moe, h, top_k: int, first_expert: int, bits=None,
               fault=None):
    with jax.default_matmul_precision(_HI):
        return _sparse_ffn(p_moe, h, top_k, first_expert, bits, fault)


def expert_ffn(p_moe, h, *, top_k: int, first_expert: int = 0,
               operand_bits=None, fault=None):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router over all its columns, top-k, the held experts
    from ``first_expert`` on, the gated shared expert), float32."""
    assert fault is None or fault in EXPERT_FAULTS, fault
    h = _f32(h)
    return _ffn_block(p_moe, h.reshape(-1, h.shape[-1]), top_k, first_expert,
                      operand_bits, fault)[0].reshape(h.shape)


# ----------------------------------------------------------------------
# the stack
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "n_k_heads", "n_v_heads")
                   + _ATTN_STATIC)
def _mixer_block(p, x, kind, n_k_heads, n_v_heads, **kw):
    """``(x + mixer, the normalised input of the layer's mixer, the
    normalised input of its FFN)``."""
    eps, bits = kw["eps"], kw["bits"]
    with jax.default_matmul_precision(_HI):
        h_mix = _norm(x, p["input_norm"]["scale"], eps)
        if kind == LINEAR:
            x = x + _linear_attention(p["linear_attn"], h_mix, n_k_heads,
                                      n_v_heads, eps, bits, None)
        else:
            x = x + _attention(p["self_attn"], h_mix, **kw)
        return x, h_mix, _norm(x, p["post_attention_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("vocab_size", "eps", "bits"))
def _head(norm, lm_head, x, vocab_size: int, eps: float, bits=None):
    with jax.default_matmul_precision(_HI):
        logits = _mm(_norm(x, norm["scale"], eps), _f32(lm_head), bits)
        # departure 3: padded vocabulary columns
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: the blocks differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


def forward(params, input_ids, *, n_layer: int, n_head: int, n_kv_head: int,
            head_dim: int, vocab_size: int, top_k: int, layer_types,
            n_k_heads: int, n_v_heads: int, rope_theta: float = 1e7,
            partial_rotary_factor: float = 0.25, eps: float = 1e-6,
            routed_experts=None, first_expert: int = 0, operand_bits=None,
            ffn_inputs=None, mixer_inputs=None):
    """``(logits (B, S, padded vocab), balance (L,))`` in float32; lists
    given as ``mixer_inputs`` / ``ffn_inputs`` receive each layer's
    normalised hidden states (B, S, E) before its mixer / FFN."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    balance = []
    for i, p in enumerate(layers(params, n_layer)):
        assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        x, h_mix, h = _mixer_block(
            p, x, kind=layer_types[i], n_k_heads=n_k_heads,
            n_v_heads=n_v_heads, n_head=n_head, n_kv_head=n_kv_head,
            head_dim=head_dim, rope_theta=float(rope_theta),
            rotary_dim=int(head_dim * partial_rotary_factor), eps=eps,
            bits=operand_bits, fault=None)
        ff, b_l = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                             first_expert, operand_bits)
        x = x + ff.reshape(x.shape)
        balance.append(b_l)
        if mixer_inputs is not None:
            mixer_inputs.append(h_mix)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    lg = _head(params["norm"], params["lm_head"], x, vocab_size=vocab_size,
               eps=eps, bits=operand_bits)
    return lg, jnp.stack(balance)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)[0]


def loss_parts(params, input_ids, *, aux_loss_weight: float = 0.001,
               z_loss_weight: float = 0.0, **kw):
    """``(next-token cross-entropy, weighted load-balancing loss)``: labels
    are the inputs shifted by one, the last position of each row left out;
    the router loss is the layers' mean times its weight (no z-loss)."""
    assert not z_loss_weight, z_loss_weight
    lg, balance = forward(params, input_ids, **kw)
    lg = lg[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return nll.mean(), aux_loss_weight * balance.mean()


def training_loss(params, input_ids, **kw):
    ce, aux = loss_parts(params, input_ids, **kw)
    return ce + aux
