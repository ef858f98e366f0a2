"""Plain Xing4.0-29B-A4B forward, both training losses and the bias update:
float32 ``jax.numpy`` at "highest" matmul precision; no kernels, no sort, no
grouped matmul, no cache, no chunked head, nothing of ``deepspeed_tpu``.

Follows the model's public ``config.json`` (XingChen-AGI/Xing4.0-29B-A4B,
``model_type: xing4_0``): the DeepSeek-V3 family's block (latent attention,
sigmoid-routed experts under a selection bias, one shared expert, one
multi-token-prediction block; ``benchmark/reference/joyai.py`` states those
equations and this file computes them the same way) on a residual stream of
``hc_mult`` lanes mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880 section 4, over Hyper-Connections, arXiv:2409.19606), with
YaRN on the latent attention's rope channels.  Where the config has no key
the papers and the family's released code decide; those places are marked
(assumed) below and listed in the configuration file.

*The stream.*  ``X_0`` = the table's row copied into all ``n`` lanes
(assumed: HC section 3).  After the last block ``h = sum over lanes of X_L``,
then the final RMSNorm and the head (assumed, as HC).

*A sublayer* (attention and FFN each have their own maps; ``F`` includes its
pre-norm), a token's stream ``X`` (n x E), ``x = vec(X)``::

    r = rsqrt(mean(x^2) + rms_norm_eps);  m = r * (x @ phi)     (n^2 + 2n)
    H_pre  = sigmoid(a_pre * m[:n] + b_pre)
    H_post = 2 sigmoid(a_post * m[n:2n] + b_post)
    M      = exp(clip(a_res * mat(m[2n:]) + b_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M = M / (rowsum(M) + hc_eps);
                              M = M / (colsum(M) + hc_eps)
    u = H_pre @ X;  y = F(u);  X' = M @ X + H_post^T y

(assumed: rows before columns; ``hc_eps`` in the denominators; scalar gains;
``mat`` row-major, so ``M[j, i]`` carries lane ``i`` to lane ``j``).  The
Sinkhorn is a Python loop over the ``(n, n)`` matrix of each token, tokens
leading.

*Latent attention*: as JoyAI's, with the rope channels turned by the YaRN
frequencies of ``rope_scaling`` (Peng et al., arXiv:2309.00071: pairs that
turn more than ``beta_fast`` times over the original context keep their
frequency, those under ``beta_slow`` are slowed by ``factor``, a linear ramp
between), cos and sin times ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)`` and the softmax scale ``(nope + rope)^-1/2 x mscale(factor,
mscale_all_dim)^2``, ``mscale(f, m) = 0.1 m ln f + 1`` (the DeepSeek-V2/V3
released modelling code's rule).

*Multi-token prediction*: JoyAI's, with ``h`` the lane SUM before the final
norm, ``x = [RMSNorm_e(E[t+1]) ; RMSNorm_h(h)] W_eh`` copied into ``n``
lanes, one sparse block with its own hyper-connection maps, the lane sum,
``RMSNorm``, the MAIN model's table and head (assumed: how the block meets
the lanes has no key).

Departures: the share (``first_expert``), the sliced vocabulary and no
attention mask, as ``reference/joyai.py`` lists them.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first (``(4, 3)``: float8 e4m3), and for
the hyper-connections also the lanes every mix reads.  ``fault`` makes
:func:`attention` (:data:`FAULTS`), :func:`sparse_ffn`
(:data:`EXPERT_FAULTS`), :func:`dense_ffn` (:data:`DENSE_FAULTS`),
:func:`mtp` (:data:`MTP_FAULTS`) or :func:`hyper_connection`
(:data:`MHC_FAULTS`) compute a named WRONG thing, to read what a tolerance
must refuse.
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
          "bf16_accumulation",
          # YaRN's: the Hugging Face default (0.1 ln factor + 1 on cos and
          # sin, a plain softmax scale), the scale without mscale^2, and
          # rope_theta's own frequencies under the right scale
          "factor_on_cos_sin", "scale_without_mscale", "plain_theta")
MHC_FAULTS = ("one_sweep", "rows_only", "post_without_2", "softmax_pre",
              "res_transposed", "no_rsqrt", "no_clamp")
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


def mscale(factor, m):
    """``0.1 m ln factor + 1``: YaRN's magnitude in the DeepSeek family's
    form."""
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, yarn):
    """The angle a position advances in each of ``dim / 2`` channel pairs:
    ``theta^(-2i/dim)``, under ``yarn`` (the config's ``rope_scaling``)
    slowed by ``factor`` where a pair turns fewer than ``beta_slow`` times
    over the original context, kept where it turns more than ``beta_fast``
    times, a linear ramp over the pairs between (bounds floored / ceiled)."""
    i = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / dim)
    if yarn is None:
        return freq
    yarn = dict(yarn)
    ctx = yarn["original_max_position_embeddings"]

    def pair_that_turns(times):
        return dim * np.log(ctx / (times * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair_that_turns(yarn["beta_fast"])), 0)
    high = min(np.ceil(pair_that_turns(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    slowed = np.clip((i - low) / (high - low), 0.0, 1.0)
    return freq / yarn["factor"] * slowed + freq * (1.0 - slowed)


def _rotary(x, theta, interleaved=True, offset=0, yarn=None, magnitude=1.0):
    """Rotate x (B, S, H, D) by position (+ ``offset``): pairs ``(2i,
    2i+1)`` when ``interleaved``, halves ``(i, i + D/2)`` otherwise; cos
    and sin times ``magnitude``."""
    S, D = x.shape[1], x.shape[-1]
    inv_freq = yarn_inv_freq(D, theta, yarn)
    ang = (np.arange(S, dtype=np.float64) + offset)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(ang) * magnitude, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * magnitude, jnp.float32)[None, :, None, :]
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
               eps, bits, fault, yarn=None):
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
    # YaRN, the DeepSeek rule: mscale / mscale_all_dim on cos and sin,
    # mscale_all_dim^2 on the softmax scale
    table = dict(yarn) if yarn else None
    magnitude = scaled = 1.0
    if table is not None:
        f = table["factor"]
        all_dim = mscale(f, table.get("mscale_all_dim", 0))
        magnitude = mscale(f, table.get("mscale", 1)) / all_dim
        scaled = all_dim * all_dim
        if fault == "factor_on_cos_sin":
            magnitude, scaled = mscale(f, 1.0), 1.0
        if fault == "scale_without_mscale":
            scaled = 1.0
        if fault == "plain_theta":
            table = None
    turn = dict(yarn=table, magnitude=magnitude)
    q_rope = _rotary(q_rope, rope_theta, fault != "halves_on_q", **turn)
    k_rope = _rotary(k_rope, rope_theta, fault != "halves_on_k",
                     offset=1 if fault == "k_rope_next_position" else 0,
                     **turn)
    if fault == "rope_on_nope":
        q_nope, k_nope = (_rotary(t, rope_theta, **turn)
                          for t in (q_nope, k_nope))
    scale = scaled / np.sqrt(nope if fault == "scale_nope" else nope + rope)
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
                "rope_theta", "eps", "bits", "fault", "yarn")


def _frozen(yarn):
    """``rope_scaling`` as a hashable (a jit's static argument)."""
    return None if not yarn else tuple(sorted(
        (k, v) for k, v in dict(yarn).items()
        if not isinstance(v, (list, dict))))


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
             v_head_dim, rope_theta, eps, operand_bits=None, fault=None,
             rope_scaling=None):
    return dict(n_head=n_head, kv_lora_rank=kv_lora_rank,
                nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                v_dim=v_head_dim, rope_theta=float(rope_theta), eps=eps,
                bits=operand_bits, fault=fault, yarn=_frozen(rope_scaling))


def attention(h_normed, p_attn, *, n_head, kv_lora_rank, qk_nope_head_dim,
              qk_rope_head_dim, v_head_dim, rope_theta, eps=1e-6,
              rope_scaling=None, operand_bits=None, fault=None):
    """One latent-attention layer alone: normalised hidden states (B, S, E)
    through the layer's ``self_attn`` leaves, float32."""
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(p_attn, _f32(h_normed), **_attn_kw(
        n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
        rope_theta, eps, operand_bits, fault, rope_scaling))


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
def _attn_sublayer(p, u, **kw):
    """``(the normalised input of the layer's attention, its output)`` of
    what the sublayer reads, ``u`` (B, S, E)."""
    with jax.default_matmul_precision(_HI):
        h_attn = _rms_norm(u, p["input_norm"]["scale"], kw["eps"])
        return h_attn, _attention(p["self_attn"], h_attn, **kw)


_HC_STATIC = ("n", "iters", "hc_eps", "clamp", "eps", "bits", "fault")


def _hc_maps(p, X, n, iters, hc_eps, clamp, eps, bits, fault):
    """``(H_pre (.., n), H_post (.., n), H_res (.., n, n))`` of streams ``X``
    (.., n, E): the matrices of each token, tokens leading."""
    x = X.reshape(*X.shape[:-2], -1)                        # vec(X)
    m = _mm(x, _f32(p["phi"]), bits)
    if fault != "no_rsqrt":
        m = m * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    a_pre, a_post, a_res = (_f32(p[k]).reshape(())
                            for k in ("a_pre", "a_post", "a_res"))
    pre_raw = a_pre * m[..., :n] + _f32(p["b_pre"])
    h_pre = jax.nn.softmax(pre_raw, -1) if fault == "softmax_pre" \
        else jax.nn.sigmoid(pre_raw)
    h_post = (1.0 if fault == "post_without_2" else 2.0) * jax.nn.sigmoid(
        a_post * m[..., n:2 * n] + _f32(p["b_post"]))
    raw = a_res * m[..., 2 * n:].reshape(*m.shape[:-1], n, n) \
        + _f32(p["b_res"])
    if fault != "no_clamp":
        raw = jnp.clip(raw, *clamp)
    M = jnp.exp(raw)
    for _ in range(1 if fault == "one_sweep" else iters):   # (assumed) rows
        M = M / (M.sum(-1, keepdims=True) + hc_eps)         # before columns
        if fault != "rows_only":
            M = M / (M.sum(-2, keepdims=True) + hc_eps)
    if fault == "res_transposed":
        M = jnp.swapaxes(M, -1, -2)
    return h_pre, h_post, M


@functools.partial(jax.jit, static_argnames=_HC_STATIC)
def _hc_read(p, X, **kw):
    """``(H_pre, H_post, H_res, u)``: the maps and ``u = H_pre @ X``."""
    with jax.default_matmul_precision(_HI):
        h_pre, h_post, h_res = _hc_maps(p, X, **kw)
        if kw["bits"] is not None:
            X = jax.lax.reduce_precision(X, *kw["bits"])
        return h_pre, h_post, h_res, jnp.einsum("...n,...ne->...e", h_pre, X)


@functools.partial(jax.jit, static_argnames=("bits",))
def _hc_write(X, y, h_post, h_res, bits=None):
    """``X' = H_res @ X + H_post^T y``."""
    with jax.default_matmul_precision(_HI):
        if bits is not None:
            X, y = (jax.lax.reduce_precision(t, *bits) for t in (X, y))
        return jnp.einsum("...ji,...ie->...je", h_res, X) \
            + h_post[..., :, None] * y[..., None, :]


def _hc_kw(hc_mult, hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min,
           mhc_h_res_clamp_max, eps, operand_bits=None, fault=None):
    return dict(n=int(hc_mult), iters=int(hc_sinkhorn_iters),
                hc_eps=float(hc_eps), clamp=(float(mhc_h_res_clamp_min),
                                             float(mhc_h_res_clamp_max)),
                eps=eps, bits=operand_bits, fault=fault)


def hyper_connection(p_hc, X, y=None, *, hc_mult, hc_sinkhorn_iters, hc_eps,
                     mhc_h_res_clamp_min, mhc_h_res_clamp_max, eps=1e-6,
                     operand_bits=None, fault=None, **_) -> dict:
    """One sublayer's hyper-connection alone, float32: the stream ``X`` (B,
    S, n, E), or flat (B, S, n * E), through the sublayer's leaves.  Returns
    ``pre`` (B, S, n), ``post`` (B, S, n), ``res`` (B, S, n, n), ``u`` (B,
    S, E) and, given the sublayer's output ``y`` (B, S, E), ``out`` (B, S,
    n, E)."""
    assert fault is None or fault in MHC_FAULTS, fault
    X = _f32(X)
    if X.ndim == 3:
        X = X.reshape(*X.shape[:2], int(hc_mult), -1)
    kw = _hc_kw(hc_mult, hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min,
                mhc_h_res_clamp_max, eps, operand_bits, fault)
    pre, post, res, u = _hc_read(p_hc, X, **kw)
    out = {"pre": pre, "post": post, "res": res, "u": u}
    if y is not None:
        out["out"] = _hc_write(X, _f32(y), post, res, bits=operand_bits)
    return out


@functools.partial(jax.jit, static_argnames=("n",))
def _widen(x, n):
    return jnp.broadcast_to(x[..., None, :], (*x.shape[:-1], n, x.shape[-1]))


@jax.jit
def _lane_sum(X):
    return X.sum(-2)


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


def _block(p, X, sparse, attn_kw, hc_kw, top_k, route_scale, first_expert,
           bits, counts=None, attn_inputs=None, ffn_inputs=None,
           hc_inputs=None):
    """A block on the stream ``X`` (B, S, n, E).  ``hc_inputs`` receives, a
    sublayer, ``(the stream it met, its output y)``."""
    eps = attn_kw["eps"]
    h_pre, h_post, h_res, u = _hc_read(p["attn_hc"], X, **hc_kw)
    h_attn, attn = _attn_sublayer(p, u, **attn_kw)
    X1 = _hc_write(X, attn, h_post, h_res, bits=bits)
    h_pre, h_post, h_res, u = _hc_read(p["mlp_hc"], X1, **hc_kw)
    h = _normed(u, p["post_attention_norm"]["scale"], eps)
    if sparse:
        ff, c = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                           float(route_scale), first_expert, bits)
        ff = ff.reshape(h.shape)
        if counts is not None:
            counts.append(c)
    else:
        ff = dense_ffn(p, h, operand_bits=bits)
    if attn_inputs is not None:
        attn_inputs.append(h_attn)
    if ffn_inputs is not None:
        ffn_inputs.append(h)
    if hc_inputs is not None:
        hc_inputs += [(X, attn), (X1, ff)]
    return _hc_write(X1, ff, h_post, h_res, bits=bits)


def hidden(params, input_ids, *, n_layer: int, n_head: int,
           kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int,
           v_head_dim: int, top_k: int, num_dense_layers: int,
           route_scale: float, rope_theta: float, hc_mult: int,
           hc_sinkhorn_iters: int, hc_eps: float, mhc_h_res_clamp_min: float,
           mhc_h_res_clamp_max: float, rope_scaling=None, eps: float = 1e-6,
           routed_experts=None, first_expert: int = 0, operand_bits=None,
           ffn_inputs=None, attn_inputs=None, hc_inputs=None, counts=None,
           **_):
    """The lanes' SUM after the last block, (B, S, E), BEFORE the final
    norm; lists given as ``attn_inputs`` / ``ffn_inputs`` receive each
    layer's normalised hidden states before its attention / FFN,
    ``hc_inputs`` each sublayer's ``(stream, output)``, ``counts`` each
    sparse layer's pairs an expert."""
    kw = _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim, rope_theta, eps, operand_bits,
                  rope_scaling=rope_scaling)
    hc = _hc_kw(hc_mult, hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min,
                mhc_h_res_clamp_max, eps, operand_bits)
    # (assumed) the table's row in every lane
    X = _widen(_f32(params["embed_tokens"])[jnp.asarray(input_ids)],
               n=int(hc_mult))
    for i, p in enumerate(layers(params, n_layer)):
        sparse = i >= num_dense_layers
        if sparse:
            assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        X = _block(p, X, sparse, kw, hc, top_k, route_scale, first_expert,
                   operand_bits, counts, attn_inputs, ffn_inputs, hc_inputs)
    return _lane_sum(X)


def mtp_hidden(h, input_ids, params, *, n_head, kv_lora_rank,
               qk_nope_head_dim, qk_rope_head_dim, v_head_dim, top_k,
               route_scale, rope_theta, hc_mult, hc_sinkhorn_iters, hc_eps,
               mhc_h_res_clamp_min, mhc_h_res_clamp_max, rope_scaling=None,
               eps=1e-6, first_expert=0, operand_bits=None, fault=None,
               attn_inputs=None, ffn_inputs=None, hc_inputs=None, **_):
    """The prediction block's output after its own norm, (B, S - 1, E):
    position i (< S - 1) combines ``h_i`` with the embedding of token
    ``i + 1`` (DeepSeek-V3 report, eq. 21-22).  ``h`` is the stack's lane
    sum before the final norm; the block runs on lanes of its own
    (assumed)."""
    assert fault is None or fault in MTP_FAULTS, fault
    p = params["mtp_0"]
    ids = jnp.asarray(input_ids)
    table = _f32(params["embed_tokens"])
    if fault == "other_table":      # a table that is not the main model's
        table = jnp.roll(table, 1, axis=0)
    x = _eh_proj(p, table[ids[:, 1:]], _f32(h)[:, :-1], eps, operand_bits,
                 fault == "h_then_e")
    kw = _attn_kw(n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                  v_head_dim, rope_theta, eps, operand_bits,
                  rope_scaling=rope_scaling)
    hc = _hc_kw(hc_mult, hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min,
                mhc_h_res_clamp_max, eps, operand_bits)
    # one row short of the others: pad to the block size and cut again (the
    # pad is a LATER position, which no causal position reads)
    S = ids.shape[1]
    X = _widen(jnp.pad(x, ((0, 0), (0, 1), (0, 0))), n=int(hc_mult))
    X = _block(p["block"], X, True, kw, hc, top_k, route_scale, first_expert,
               operand_bits, None, attn_inputs, ffn_inputs, hc_inputs)
    return _normed(_lane_sum(X), p["shared_head_norm"]["scale"],
                   eps)[:, :S - 1]


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
           if k not in ("ffn_inputs", "attn_inputs", "hc_inputs", "counts")}
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
