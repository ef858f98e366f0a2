"""Plain Olmo-Hybrid forward and training loss: float32 ``jax.numpy`` at
"highest" matmul precision; no kernels, no chunks, no lane slots, no cache,
no chunked head.  Gradients are ``jax.grad`` of :func:`training_loss`.

Follows the model's public ``config.json`` (allenai/Olmo-Hybrid-7B,
``model_type: olmo_hybrid``) and, where the config has no key, the OLMo 2 /
OLMo 3 family's convention (arXiv:2501.00656) and fla's ``GatedDeltaNet``
(arXiv:2412.06464; ``use_gate``, ``use_short_conv``, ``expand_v`` 2,
``allow_neg_eigval``); those places are marked (assumed) below and listed
under ``assumed`` in ``benchmark/configs/olmo-hybrid-7b-z3-8bit.json``.

``N(x) = x * rsqrt(mean(x^2) + eps) * w``, ``w`` from ones (plain, not
zero-centred).  *Block* ``l``, both kinds, under the family's REORDERED
norm (assumed: nothing normalises a branch's input)::

    x <- x + N_attn(mixer_l(x));  x <- x + N_mlp(SwiGLU_l(x))
    logits = N_final(x) @ W_head                      # untied

*mixer_l* where ``layer_types[l] == "linear_attention"`` (Gated DeltaNet;
h the block's input, H key heads of dk channels and H value heads of dv)::

    [q ; k ; v ; z] = h W_qkvz          # contiguous: Hk*dk | Hk*dk | Hv*dv | Hv*dv
    [b ; a]         = h W_ba            # Hv | Hv
    [q ; k ; v]    <- silu(conv([q ; k ; v]))     # depthwise, 4 taps, causal,
                                        # the LAST tap is the current position
    beta = 2 * sigmoid(b)               # linear_allow_neg_eigval: in (0, 2)
    g = -exp(A_log) * softplus(a + dt_bias)
    q <- q * rsqrt(sum(q^2) + 1e-6) * dk^-1/2;  k <- k * rsqrt(sum(k^2) + 1e-6)
    a value head, S (dk x dv, keys x values) from zeros at every row:
        S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
        o_t = S_t^T q_t
    y = (o * rsqrt(mean(o^2) + eps) * w_o) * silu(z)     # a head's dv channels
    out = y W_out

(the released leaves ``q_proj``, ``k_proj``, ``v_proj``, ``g_proj``,
``a_proj``, ``b_proj`` and the three filters are column splits of ``W_qkvz``,
``W_ba`` and the one filter over ``[q ; k ; v]``, which a loader makes.)
**The recurrence is a ``lax.scan`` over POSITIONS**, one token a step: the
system under test runs it in chunks over lane slots, and that is what is
tested.

*mixer_l* where ``full_attention``: ``q = N(h W_q)``, ``k = N(h W_k)`` (ONE
norm over the whole projection, a weight as wide as it; assumed: OLMo 2's),
``v = h W_v``; causal softmax attention at ``head_dim^-1/2``, key-value
head ``h // (heads / kv heads)``; **no rotation and no other positional
encoding** (``rope_parameters.rope_theta`` null); no gate; ``out = attn
W_o``.

Departures, each marked below:

1. the vocabulary is the slice the head holds; padded columns are masked.
2. no attention mask and no state reset between packed documents.
3. cross-entropy alone: no auxiliary loss (a dense model has none).

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication, the filter's products and the recurrence's q, k, v to that
float format first.  ``fault`` makes :func:`linear_attention`
(:data:`LINEAR_FAULTS`), :func:`attention` (:data:`FAULTS`) or
:func:`block` (:data:`BLOCK_FAULTS`) compute a named WRONG thing, to read
what a tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6           # the l2-norm of q and k (assumed: fla's)
FAULT_CHUNK = 64        # where "chunk_reset" forgets the state
SEGMENT = 64            # positions whose states the backward recomputes together
ROPE_THETA = 500000.0   # what "rope_on" rotates by (OLMo 3's base)
LINEAR_FAULTS = ("beta_no_two", "scale_dv", "gate_before_norm", "chunk_reset",
                 "row_leak", "state_bf16", "no_decay", "no_l2norm",
                 "taps_reversed", "no_silu", "gate_sigmoid")
FAULTS = ("rope_on", "norm_per_head", "no_norm")
BLOCK_FAULTS = ("pre_norm",)


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _round(t, bits):
    return t if bits is None else jax.lax.reduce_precision(t, *bits)


def _mm(a, b, bits):
    return _round(a, bits) @ _round(b, bits)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _swiglu(x, gate, up, down, bits):
    return _mm(jax.nn.silu(_mm(x, _f32(gate), bits)) * _mm(x, _f32(up), bits),
               _f32(down), bits)


# ----------------------------------------------------------------------
# Gated DeltaNet
# ----------------------------------------------------------------------
def _conv(x, w, bits, fault):
    """``c_t = sum_j w[:, j] x_{t-(L-1)+j}``: a loop over the taps of the
    zero-padded sequence (assumed: a depthwise Conv1d with padding L - 1
    cut to the sequence)."""
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
    """The recurrence itself, one position a step: q, k (B, S, H, dk), v
    (B, S, H, dv), g and beta (B, S, H), float32; returns o (B, S, H, dv)."""
    B, S, H, dv = v.shape
    dk = k.shape[-1]

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t, t = xs
        if fault == "chunk_reset":
            state = jnp.where(t % FAULT_CHUNK == 0, 0.0, state)
        state = state * jnp.exp(g_t)[..., None, None]
        kv = (state * k_t[..., :, None]).sum(-2)            # S^T k
        delta = (v_t - kv) * b_t[..., None]
        state = state + k_t[..., :, None] * delta[..., None, :]
        if fault == "state_bf16":       # a state kept in the compute type
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, (state * q_t[..., :, None]).sum(-2)   # S^T q

    def row_major(x):               # positions lead
        return jnp.moveaxis(x, 1, 0)

    seg = SEGMENT if S % SEGMENT == 0 else S

    def run(state, xs):
        """All positions of ``xs``, a segment at a time: the same steps in
        the same order; a segment's states are recomputed in the backward
        (8192 states of 30 heads would be 18 GB kept)."""
        xs = jax.tree_util.tree_map(
            lambda x: x.reshape((S // seg, seg) + x.shape[1:]), xs)
        state, o = jax.lax.scan(
            jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)), state, xs)
        return state, o.reshape((S,) + o.shape[2:])

    xs = tuple(row_major(x) for x in (q, k, v, g, beta)) + (jnp.arange(S),)
    zeros = jnp.zeros((B, H, dk, dv), jnp.float32)
    if fault != "row_leak":
        return row_major(run(zeros, xs)[1])
    # a row starts from the state the row before it ended in
    outs, state = [], zeros[:1]
    for b in range(B):
        state, o = run(state, tuple(x[:, b:b + 1] for x in xs[:-1])
                       + (xs[-1],))
        outs.append(o)
    return row_major(jnp.concatenate(outs, axis=1))


def _linear_attention(p, h, n_k_heads, n_v_heads, key_dim, eps, bits, fault):
    B, S, _ = h.shape
    Hk, Hv, dk = n_k_heads, n_v_heads, key_dim
    qkvz = _mm(h, _f32(p["in_proj_qkvz_kernel"]), bits)
    ba = _mm(h, _f32(p["in_proj_ba_kernel"]), bits)
    w = _f32(p["conv_kernel"])
    conv_dim = w.shape[0]
    dv = (conv_dim - 2 * Hk * dk) // Hv
    assert qkvz.shape[-1] == conv_dim + Hv * dv, (qkvz.shape, conv_dim, dv)
    qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
    qkv = _conv(qkv, w, bits, fault)
    if fault != "no_silu":      # (assumed: the filter's activation)
        qkv = jax.nn.silu(qkv)
    q = qkv[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = qkv[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = qkv[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    b, a = ba[..., :Hv], ba[..., Hv:]
    beta = jax.nn.sigmoid(b)
    if fault != "beta_no_two":  # linear_allow_neg_eigval
        beta = 2.0 * beta
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(a + _f32(p["dt_bias"]))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    k_of = np.arange(Hv) // (Hv // Hk)      # key head of each value head
    q, k = q[:, :, k_of], k[:, :, k_of]
    if fault != "no_l2norm":
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    q = q * (dv if fault == "scale_dv" else dk) ** -0.5
    o = delta_rule(_round(q, bits), _round(k, bits), _round(v, bits), g,
                   beta, fault=fault)
    z = z.reshape(B, S, Hv, dv)
    gate = jax.nn.sigmoid(z) if fault == "gate_sigmoid" else jax.nn.silu(z)
    if fault == "gate_before_norm":
        y = _norm(o * gate, p["o_norm"], eps)
    else:       # (assumed) norm first, gate second
        y = _norm(o, p["o_norm"], eps) * gate
    return _mm(y.reshape(B, S, Hv * dv), _f32(p["out_proj_kernel"]), bits)


_LIN_STATIC = ("n_k_heads", "n_v_heads", "key_dim", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_LIN_STATIC)
def _linear_alone(p, h, **kw):
    with jax.default_matmul_precision(_HI):
        return _linear_attention(p, h, **kw)


def linear_attention(p_lin, h, *, n_k_heads, n_v_heads, key_dim, eps=1e-6,
                     operand_bits=None, fault=None):
    """One Gated DeltaNet mixer alone: the block's input ``h`` (B, S, E)
    through the layer's ``linear_attn`` leaves, float32."""
    assert fault is None or fault in LINEAR_FAULTS, fault
    return _linear_alone(p_lin, _f32(h), n_k_heads=n_k_heads,
                         n_v_heads=n_v_heads, key_dim=key_dim, eps=eps,
                         bits=operand_bits, fault=fault)


def linear_attention_grads(p_lin, h, probe, **kw):
    """``(y, dh, {leaf: d leaf})``: the mixer's output and the gradients of
    ``sum(y * probe)`` with respect to ``h`` and every leaf, from one
    compiled function."""
    assert kw.get("fault") is None or kw["fault"] in LINEAR_FAULTS, kw
    return _linear_grads(
        {k: _f32(v) for k, v in p_lin.items()}, _f32(h), _f32(probe),
        n_k_heads=kw["n_k_heads"], n_v_heads=kw["n_v_heads"],
        key_dim=kw["key_dim"], eps=kw.get("eps", 1e-6),
        bits=kw.get("operand_bits"), fault=kw.get("fault"))


@functools.partial(jax.jit, static_argnames=_LIN_STATIC)
def _linear_grads(p, h, probe, **kw):
    with jax.default_matmul_precision(_HI):
        y, pull = jax.vjp(lambda h, p: _linear_attention(p, h, **kw), h, p)
        return (y,) + pull(probe)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def _rotary(x, theta):
    """Half-split rotation (HF ``rotate_half``) of x (B, S, H, D): what
    the fault "rope_on" leaves on."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, n_head, n_kv_head, head_dim, eps, bits, fault):
    B, S, E = x.shape
    D, group = head_dim, n_head // n_kv_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    wq, wk = _f32(p["q_norm"]["scale"]), _f32(p["k_norm"]["scale"])
    if fault == "norm_per_head":    # each head's channels their own mean
        q = _norm(q.reshape(B, S, n_head, D), wq.reshape(n_head, D), eps)
        k = _norm(k.reshape(B, S, n_kv_head, D), wk.reshape(n_kv_head, D),
                  eps)
    elif fault != "no_norm":        # (assumed) the whole projection, one mean
        q, k = _norm(q, wq, eps), _norm(k, wk, eps)
    q, k = q.reshape(B, S, n_head, D), k.reshape(B, S, n_kv_head, D)
    if fault == "rope_on":          # the model carries no position
        q, k = _rotary(q, ROPE_THETA), _rotary(k, ROPE_THETA)
    kv_of = np.arange(n_head) // group
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


_ATTN_STATIC = ("n_head", "n_kv_head", "head_dim", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(layer_type, p_attn, h, *, n_head, n_kv_head, head_dim,
              eps=1e-6, operand_bits=None, fault=None):
    """One attention layer alone: the block's input ``h`` (B, S, E) through
    the layer's ``self_attn`` leaves, float32."""
    assert layer_type == FULL, layer_type
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(
        p_attn, _f32(h), n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, eps=eps, bits=operand_bits, fault=fault)


# ----------------------------------------------------------------------
# the dense feed-forward and the block
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bits",))
def _dense_alone(p, h, bits=None):
    with jax.default_matmul_precision(_HI):
        return _swiglu(h, p["gate_proj_kernel"], p["up_proj_kernel"],
                       p["down_proj_kernel"], bits)


def dense_ffn(p_layer, h, *, operand_bits=None):
    """The SwiGLU of one block alone: ``h`` (..., E) through the block's
    own ``gate_proj`` / ``up_proj`` / ``down_proj`` leaves, float32."""
    return _dense_alone({k: p_layer[k] for k in (
        "gate_proj_kernel", "up_proj_kernel", "down_proj_kernel")}, _f32(h),
        operand_bits)


_BLOCK_STATIC = ("kind", "n_k_heads", "n_v_heads", "key_dim", "n_head",
                 "n_kv_head", "head_dim", "eps", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_BLOCK_STATIC)
def _block(p, x, kind, n_k_heads, n_v_heads, key_dim, n_head, n_kv_head,
           head_dim, eps, bits=None, fault=None):
    def mixer(h):
        if kind == LINEAR:
            return _linear_attention(p["linear_attn"], h, n_k_heads,
                                     n_v_heads, key_dim, eps, bits, None)
        return _attention(p["self_attn"], h, n_head, n_kv_head, head_dim,
                          eps, bits, None)

    def ffn(h):
        return _swiglu(h, p["gate_proj_kernel"], p["up_proj_kernel"],
                       p["down_proj_kernel"], bits)

    w_attn = p["post_attention_norm"]["scale"]
    w_mlp = p["post_mlp_norm"]["scale"]
    with jax.default_matmul_precision(_HI):
        if fault == "pre_norm":     # a norm before each branch, not after
            x = x + mixer(_norm(x, w_attn, eps))
            return x + ffn(_norm(x, w_mlp, eps)), x
        x = x + _norm(mixer(x), w_attn, eps)    # (assumed) the reordered norm
        return x + _norm(ffn(x), w_mlp, eps), x


def block(p_layer, x, *, kind, n_k_heads, n_v_heads, key_dim, n_head,
          n_kv_head, head_dim, eps=1e-6, operand_bits=None, fault=None):
    """One whole block from its input ``x`` (B, S, E), float32."""
    assert fault is None or fault in BLOCK_FAULTS, fault
    return _block(p_layer, _f32(x), kind=kind, n_k_heads=n_k_heads,
                  n_v_heads=n_v_heads, key_dim=key_dim, n_head=n_head,
                  n_kv_head=n_kv_head, head_dim=head_dim, eps=eps,
                  bits=operand_bits, fault=fault)[0]


# ----------------------------------------------------------------------
# the stack
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("vocab_size", "eps", "bits"))
def _head(norm, lm_head, x, vocab_size: int, eps: float, bits=None):
    with jax.default_matmul_precision(_HI):
        logits = _mm(_norm(x, norm["scale"], eps), _f32(lm_head), bits)
        # departure 1: padded vocabulary columns
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def layers(params, n_layer):
    """Each layer's leaves (the stack is unrolled: the blocks differ)."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


def forward(params, input_ids, *, n_layer: int, n_head: int, n_kv_head: int,
            head_dim: int, vocab_size: int, layer_types, n_k_heads: int,
            n_v_heads: int, key_dim: int, eps: float = 1e-6,
            operand_bits=None, block_inputs=None, ffn_inputs=None):
    """Logits (B, S, padded vocab) in float32; lists given as
    ``block_inputs`` / ``ffn_inputs`` receive each layer's residual stream
    (B, S, E) before its mixer / before its FFN (under the reordered norm
    the branches read the stream itself)."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    for i, p in enumerate(layers(params, n_layer)):
        if block_inputs is not None:
            block_inputs.append(x)
        x, mid = _block(p, x, kind=layer_types[i], n_k_heads=n_k_heads,
                        n_v_heads=n_v_heads, key_dim=key_dim, n_head=n_head,
                        n_kv_head=n_kv_head, head_dim=head_dim, eps=eps,
                        bits=operand_bits)
        if ffn_inputs is not None:
            ffn_inputs.append(mid)
    return _head(params["norm"], params["lm_head"], x, vocab_size=vocab_size,
                 eps=eps, bits=operand_bits)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)


def loss_parts(params, input_ids, **kw):
    """``(next-token cross-entropy, 0.0)``: labels are the inputs shifted
    by one, the last position of each row left out; departure 3."""
    lg = forward(params, input_ids, **kw)[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return nll.mean(), jnp.zeros((), jnp.float32)


def training_loss(params, input_ids, **kw):
    return loss_parts(params, input_ids, **kw)[0]
