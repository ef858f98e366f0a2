"""Plain Ouro forward and training loss: float32 ``jax.numpy`` at "highest"
matmul precision; no kernels, no remat, no chunked head.  The loop is a
Python ``for`` over the SAME parameter dictionary.  Gradients are
``jax.grad`` of :func:`training_loss`.

Follows the model's public ``config.json`` (ByteDance/Ouro-2.6B,
``model_type: ouro``) and the family's report (arXiv:2510.25741); where the
config has no key the place is marked (assumed) below and listed under
``assumed`` in ``benchmark/configs/ouro-2.6b-z3-8bit.json``.

``N(x) = x * rsqrt(mean(x^2) + eps) * w``, ``w`` from ones.  *Block* ``l``
(assumed: the sandwich norm, four norms a block; no bias anywhere)::

    x <- x + N2(Attn(N1(x)));  x <- x + N4(SwiGLU(N3(x)))
    Attn: q, k, v = u Wq, u Wk, u Wv, H heads of D channels each
          rotary on all D channels of q and k, theta ``rope_theta``,
          half-split pairs (assumed: HF rotate_half), positions 0..S-1
          softmax(q k^T D^-1/2 + causal) v, then Wo

*The loop*, T = ``total_ut_steps`` (assumed: the norm sits inside it)::

    h_0 = Table[ids]
    for t = 1..T:  h_t = Nf(Block_L(... Block_1(h_{t-1}) ...))   # same leaves
                   g_t = sigmoid(h_t . w_gate + b_gate)
                   logits_t = h_t W_head                         # untied

*The exit distribution and the loss* (the report's first-stage objective)::

    p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j);  p_T = prod_{j<T} (1 - g_j)
    nll_t(i) = -log softmax(logits_t(i))[label(i)]
    loss = mean_i [sum_t p_t(i) nll_t(i) - beta H(p(i))]
    H(p) = -sum_t p_t log(p_t + 1e-20)

Departures, each marked below:

1. the vocabulary is the slice the head holds; padded columns are masked.
2. no attention mask between packed documents.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first.  ``fault`` (:data:`FAULTS`)
makes the forward or the loss compute a named WRONG thing, to read what a
tolerance must refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256           # queries whose scores exist together
ROW_BLOCK = 2048        # rows whose logits exist together
LOG_EPS = 1e-20
FAULTS = ("no_norm_between", "gate_on_raw", "one_pass_short",
          "last_exit_only", "uniform_exits", "last_mass_times_gate",
          "entropy_sign", "no_post_norms", "theta_1e4", "interleaved_rope")
BLOCK_FAULTS = ("no_post_norms", "theta_1e4", "interleaved_rope")


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


def _rotary(x, theta, interleaved=False):
    """x (B, S, H, D) turned by its position: pairs (i, i + D/2) (assumed:
    HF ``rotate_half``), or (2i, 2i + 1) under the fault."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, n_head, head_dim, rope_theta, bits, fault):
    B, S, E = x.shape
    D = head_dim
    q = _mm(x, _f32(p["q_proj_kernel"]), bits).reshape(B, S, n_head, D)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits).reshape(B, S, n_head, D)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_head, D)
    theta = 1e4 if fault == "theta_1e4" else rope_theta
    q = _rotary(q, theta, fault == "interleaved_rope")
    k = _rotary(k, theta, fault == "interleaved_rope")
    kt = k.transpose(0, 2, 3, 1)                            # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)                            # (B, H, S, D)
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs_ = q.transpose(0, 2, 1, 3).reshape(B, n_head, S // qb, qb, D)
    j = jnp.arange(S)[None, :]

    def block(args):
        q_blk, i0 = args                                    # (B, H, qb, D)
        s = _mm(q_blk, kt, bits) / np.sqrt(D)               # (B, H, qb, S)
        keep = (i0 + jnp.arange(qb))[:, None] - j >= 0      # departure 2
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), vt, bits)         # (B, H, qb, D)

    a = jax.lax.map(block, (jnp.moveaxis(qs_, 2, 0),
                            jnp.arange(0, S, qb)))          # (nb, B, H, qb, D)
    a = jnp.moveaxis(a, 0, 2).reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return _mm(a.reshape(B, S, n_head * D), _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("n_head", "head_dim", "rope_theta", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(p_attn, h, *, n_head, head_dim, rope_theta, operand_bits=None,
              fault=None):
    """One attention layer alone: its normalised input ``h`` (B, S, E)
    through the layer's ``self_attn`` leaves, float32."""
    assert fault is None or fault in BLOCK_FAULTS, fault
    return _attention_alone(p_attn, _f32(h), n_head=n_head,
                            head_dim=head_dim, rope_theta=float(rope_theta),
                            bits=operand_bits, fault=fault)


@functools.partial(jax.jit, static_argnames=("bits",))
def _dense_alone(p, h, bits=None):
    with jax.default_matmul_precision(_HI):
        return _swiglu(h, p["gate_proj_kernel"], p["up_proj_kernel"],
                       p["down_proj_kernel"], bits)


def dense_ffn(p_layer, h, *, operand_bits=None):
    """The SwiGLU of one block alone: its normalised input ``h`` through the
    block's own ``gate_proj`` / ``up_proj`` / ``down_proj`` leaves."""
    return _dense_alone({k: p_layer[k] for k in (
        "gate_proj_kernel", "up_proj_kernel", "down_proj_kernel")}, _f32(h),
        operand_bits)


_BLOCK_STATIC = ("n_head", "head_dim", "eps", "rope_theta", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_BLOCK_STATIC)
def _block(p, x, n_head, head_dim, eps, rope_theta, bits=None, fault=None):
    """``(x after the block, the attention's input, the FFN's input)``."""
    post = fault != "no_post_norms"     # (assumed) the sandwich norm
    with jax.default_matmul_precision(_HI):
        u = _norm(x, p["input_norm"]["scale"], eps)
        a = _attention(p["self_attn"], u, n_head, head_dim, rope_theta, bits,
                       fault)
        x = x + (_norm(a, p["post_attention_norm"]["scale"], eps) if post
                 else a)
        m = _norm(x, p["pre_mlp_norm"]["scale"], eps)
        f = _swiglu(m, p["gate_proj_kernel"], p["up_proj_kernel"],
                    p["down_proj_kernel"], bits)
        return x + (_norm(f, p["post_mlp_norm"]["scale"], eps) if post
                    else f), u, m


def block(p_layer, x, *, n_head, head_dim, eps, rope_theta,
          operand_bits=None, fault=None):
    """One whole block from its input ``x`` (B, S, E), float32."""
    assert fault is None or fault in BLOCK_FAULTS, fault
    return _block(p_layer, _f32(x), n_head=n_head, head_dim=head_dim,
                  eps=eps, rope_theta=float(rope_theta), bits=operand_bits,
                  fault=fault)[0]


def layers(params, n_layer):
    """Each layer's leaves, the same dictionaries every pass."""
    for i in range(n_layer):
        yield params[f"layers_{i}"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(norm, x, eps):
    return _norm(x, norm["scale"], eps)


def passes(params, input_ids, *, n_layer: int, n_head: int, head_dim: int,
           eps: float, rope_theta: float, ut_steps: int, operand_bits=None,
           fault=None, block_inputs=None, attn_inputs=None, ffn_inputs=None,
           copies=None, **_):
    """``(h, raw)``: the normed stream after each pass, ``ut_steps`` of
    (B, S, E), and the stream before that pass's norm.  Lists given as
    ``block_inputs`` / ``attn_inputs`` / ``ffn_inputs`` receive, block
    application by application (pass-major), a block's input and its two
    branches' normalised inputs.  ``copies`` (a list of ``ut_steps``
    parameter dictionaries) gives every pass leaves of its own: what the
    sharing is tested against."""
    fault_b = fault if fault in BLOCK_FAULTS else None
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    hs, raws = [], []
    for t in range(ut_steps):
        own = params if copies is None else copies[t]
        if not (fault == "one_pass_short" and t == ut_steps - 1):
            for p in layers(own, n_layer):
                if block_inputs is not None:
                    block_inputs.append(x)
                x, u, m = _block(p, x, n_head=n_head, head_dim=head_dim,
                                 eps=eps, rope_theta=float(rope_theta),
                                 bits=operand_bits, fault=fault_b)
                if attn_inputs is not None:
                    attn_inputs.append(u)
                if ffn_inputs is not None:
                    ffn_inputs.append(m)
            raws.append(x)
            h = _final_norm(own["norm"], x, eps)
            if not (fault == "no_norm_between" and t < ut_steps - 1):
                x = h       # (assumed) the next pass reads the NORMED stream
        else:               # the last pass left out: its exit reads the
            raws.append(raws[-1])   # one before
        hs.append(h)
    return hs, raws


def exit_distribution(gate_logits, fault=None):
    """``p`` (T, ...) from every pass's gate logit (T, ...)."""
    g = jax.nn.sigmoid(gate_logits)
    T = g.shape[0]
    if fault == "uniform_exits":
        return jnp.full_like(g, 1.0 / T)
    left = jnp.cumprod(1.0 - g, axis=0)             # prod_{j<=t} (1 - g_j)
    before = jnp.concatenate([jnp.ones_like(g[:1]), left[:-1]])
    p = g * before
    if fault == "last_mass_times_gate":
        return p
    return p.at[-1].set(before[-1])     # the last pass takes what is left


def gate_logits(gate, hs):
    """(T, B, S) from the passes' streams: ``h_t . w + b``."""
    with jax.default_matmul_precision(_HI):
        return jnp.stack(hs) @ _f32(gate["kernel"])[:, 0] \
            + _f32(gate["bias"])[0]


def loss_of_logits(logits, nll, beta, fault=None):
    """``(loss, p)`` from every pass's gate logit and each token's ``nll``,
    both (T, B, S')."""
    p = exit_distribution(logits, fault)
    if fault == "last_exit_only":
        return nll[-1].mean(), p
    entropy = -(p * jnp.log(p + LOG_EPS)).sum(0)
    sign = 1.0 if fault == "entropy_sign" else -1.0
    return ((p * nll).sum(0) + sign * beta * entropy).mean(), p


def exit_loss(gate, hs, nll, beta, fault=None):
    """The loss from the passes' gate inputs ``hs`` and each token's
    ``nll`` (T, B, S'): neither depends on the gate, so ``jax.grad`` of
    this over ``gate`` is the gate's whole gradient."""
    return loss_of_logits(gate_logits(gate, hs)[..., :nll.shape[-1]], nll,
                          beta, fault)


@functools.partial(jax.jit, static_argnames=("vocab_size", "bits"))
def _nll_rows(lm_head, h, tgt, vocab_size, bits=None):
    """Each row's negative log-likelihood, the logits in blocks of rows."""
    N, E = h.shape
    rb = min(ROW_BLOCK, N)
    pad = (-N) % rb
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rb, E)
    tgt = jnp.pad(tgt, (0, pad)).reshape(-1, rb)

    def rows(args):
        hb, tb = args
        lg = _mm(hb, _f32(lm_head), bits)
        # departure 1: padded vocabulary columns
        lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab_size, lg, -jnp.inf)
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    with jax.default_matmul_precision(_HI):
        return jax.lax.map(rows, (h, tgt)).reshape(-1)[:N]


def forward(params, input_ids, *, vocab_size: int, ut_steps: int,
            operand_bits=None, fault=None, **kw):
    """``(hs, gate_in, nll)``: the passes' normed streams, what the gate
    reads of each, and each token's nll at each exit (T, B, S - 1): labels
    are the inputs shifted by one, the last position of a row left out."""
    hs, raws = passes(params, input_ids, ut_steps=ut_steps,
                      operand_bits=operand_bits, fault=fault, **kw)
    ids = jnp.asarray(input_ids)
    B, S = ids.shape
    tgt = ids[:, 1:].reshape(-1)
    nll = jnp.stack([_nll_rows(
        params["lm_head"], h[:, :-1].reshape(B * (S - 1), -1), tgt,
        vocab_size=vocab_size, bits=operand_bits).reshape(B, S - 1)
        for h in hs])
    return hs, (raws if fault == "gate_on_raw" else hs), nll


def logits(params, input_ids, *, vocab_size: int, operand_bits=None, **kw):
    """The LAST pass's logits (B, S, padded vocab): what inference reads at
    ``early_exit_threshold`` 1.0, where every token runs all the passes."""
    hs, _ = passes(params, input_ids, operand_bits=operand_bits, **kw)
    with jax.default_matmul_precision(_HI):
        lg = _mm(hs[-1], _f32(params["lm_head"]), operand_bits)
    # departure 1: padded vocabulary columns
    return jnp.where(jnp.arange(lg.shape[-1]) < vocab_size, lg, -jnp.inf)


def loss_parts(params, input_ids, *, beta: float, **kw):
    """``{loss, exit_p (T,), exit_nll (T,), nll (T, B, S - 1), gate_in}``."""
    fault = kw.get("fault")
    hs, gate_in, nll = forward(params, input_ids, **kw)
    loss, p = exit_loss(params["exit_gate"], gate_in, nll, beta, fault)
    return {"loss": loss, "exit_p": p.mean((1, 2)),
            "exit_nll": nll.mean((1, 2)), "nll": nll, "gate_in": gate_in,
            "hs": hs}


def gate_grads(params, parts, *, beta: float, fault=None):
    """d loss / d (``kernel``, ``bias``) of ``exit_gate`` from a forward's
    parts (:func:`loss_parts`): the streams and the nll do not depend on the
    gate, so nothing of the stack is walked back: with ``dl`` = d loss / d
    the gate's logits (T, B, S'), ``sum dl h`` and ``sum dl``.  Under
    ``"scale"``, a leaf each, what those sums would come to if every token's
    term pulled one way, ``sum |dl| |h|`` and ``sum |dl|``: the measure a
    comparison in a lower precision can be held to (the sums themselves
    cancel to anything between that and nothing, by the seed)."""
    gate = jax.tree_util.tree_map(_f32, dict(params["exit_gate"]))
    nll = parts["nll"]
    hs = jnp.stack(parts["gate_in"])[:, :, :nll.shape[-1]]
    dl = jax.grad(lambda lg: loss_of_logits(lg, nll, beta, fault)[0])(
        gate_logits(gate, list(hs)))
    with jax.default_matmul_precision(_HI):
        kernel = jnp.einsum("tbs,tbse->e", dl, hs)[:, None]
    return {"kernel": kernel, "bias": dl.sum()[None], "scale": {
        "kernel": (jnp.abs(dl) * jnp.linalg.norm(hs, axis=-1)).sum(),
        "bias": jnp.abs(dl).sum()}}


def training_loss(params, input_ids, **kw):
    return loss_parts(params, input_ids, **kw)["loss"]
