"""Plain OLMoE forward and training loss: float32 ``jax.numpy`` at
"highest" matmul precision; no kernels, no sort, no grouped matmul, no
cache, no chunked head.  The sparse FFN is a Python loop over the experts
with a boolean mask a token.

Follows Muennighoff et al. 2024 (OLMoE, arXiv:2409.02060) and the Hugging
Face ``modeling_olmoe.py``: pre-norm residual blocks; RMSNorm; q and k
normalised over the WHOLE projection width before the split into heads
(``q_norm`` / ``k_norm``); half-split rotary; causal softmax attention;
router ``softmax`` in float32 over all experts, top-k by probability,
weights not renormalised unless ``norm_topk_prob``; SwiGLU experts; untied
head.  Departures from ``modeling_olmoe.py``, each marked below:

1. the load-balancing loss is computed a layer and averaged over the
   layers (the paper's training code); HF concatenates every layer's
   router logits and takes the two means over layers and tokens together
   before their product.
2. the router z-loss (paper section 3, weight 0.001) is part of the
   training loss; HF's model has none.
3. the head's vocabulary rows may be padded by the system under test (to a
   multiple of 128; 50304 already is one): padded columns are masked out
   exactly as the model masks them.
4. no attention mask: rows are packed documents without padding, and
   attention runs across document boundaries as in the system.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first (float32 products and sums
after): ``(4, 3)`` is how PERF.md reads "this forward in fp8", the precision
below the bf16 the configurations compute in, to set ``loss_abs_tol``.

The parameter tree is the program's (``embed_tokens``, ``layers_<i>`` or a
stacked ``layers``, ``norm``, ``lm_head``), read, never copied whole: one
jitted attention half and one jitted FFN half are called once a layer on
that layer's leaves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"


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
    inv_freq = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    ang = np.arange(S, dtype=np.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, n_head, eps, theta, bits):
    B, S, E = x.shape
    q = _rms_norm(_mm(x, _f32(p["q_proj_kernel"]), bits), p["q_norm"]["scale"], eps)
    k = _rms_norm(_mm(x, _f32(p["k_proj_kernel"]), bits), p["k_norm"]["scale"], eps)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits)
    D = q.shape[-1] // n_head
    q, k, v = (t.reshape(B, S, -1, D) for t in (q, k, v))
    q, k = _rotary(q, theta), _rotary(k, theta)
    rep = n_head // k.shape[2]           # grouped-query: repeat the KV heads
    if rep > 1:
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))      # (B, H, S, D)
    s = _mm(q, k.transpose(0, 1, 3, 2), bits) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, -1), v, bits).transpose(0, 2, 1, 3)
    return _mm(a.reshape(B, S, -1), _f32(p["o_proj_kernel"]), bits)


def _sparse_ffn(p, h, top_k, norm_topk_prob, bits):
    """(out, load-balancing loss, z-loss) of tokens ``h`` (T, E)."""
    logits = _mm(h, _f32(p["gate"]["wg"]), bits)             # (T, experts)
    probs = jax.nn.softmax(logits, -1)
    n_exp = probs.shape[-1]
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    ex = p["experts"]
    out = jnp.zeros_like(h)
    chosen = jnp.zeros(probs.shape, bool)
    for e in range(n_exp):
        mine = top_e == e                                    # (T, k)
        weight = (top_p * mine).sum(-1)                      # 0 if not chosen
        y = _mm(jax.nn.silu(_mm(h, _f32(ex["gate"][e]), bits))
                * _mm(h, _f32(ex["up"][e]), bits), _f32(ex["down"][e]), bits)
        out = out + jnp.where(mine.any(-1)[:, None], y * weight[:, None], 0.0)
        chosen = chosen.at[:, e].set(mine.any(-1))
    # departure 1: this layer's own f_e and P_e
    share = chosen.astype(jnp.float32).sum(0) / (h.shape[0] * top_k)
    balance = n_exp * jnp.sum(share * probs.mean(0))
    # departure 2: ST-MoE's router z-loss, mean over tokens
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, balance, z


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta", "bits"))
def _attn_block(p, x, n_head: int, eps: float, theta: float, bits=None):
    """``(x + attention, the normalised input of the layer's sparse FFN)``."""
    with jax.default_matmul_precision(_HI):
        x = x + _attention(p["self_attn"],
                           _rms_norm(x, p["input_norm"]["scale"], eps),
                           n_head, eps, theta, bits)
        return x, _rms_norm(x, p["post_attention_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk_prob", "bits"))
def _ffn_block(p_moe, h, top_k: int, norm_topk_prob: bool, bits=None):
    """:func:`_sparse_ffn` of float32 tokens ``h`` (T, E), as one executable
    that the forward and :func:`expert_ffn` share."""
    with jax.default_matmul_precision(_HI):
        return _sparse_ffn(p_moe, h, top_k, norm_topk_prob, bits)


def expert_ffn(p_moe, h, *, top_k: int, norm_topk_prob: bool = False,
               operand_bits=None):
    """The sparse FFN alone: tokens ``h`` (..., E) through one layer's
    ``moe`` leaves (router, top-k, the masked loop over experts, the
    weighted sum), float32.  What a system's MoE layer is held to on the
    same ``h``, where the experts are all of the output and not 3% of a
    residual stream."""
    h = _f32(h)
    return _ffn_block(p_moe, h.reshape(-1, h.shape[-1]), top_k,
                      norm_topk_prob, operand_bits)[0].reshape(h.shape)


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


def forward(params, input_ids, *, n_layer: int, n_head: int, vocab_size: int,
            top_k: int, norm_topk_prob: bool = False, eps: float = 1e-5,
            theta: float = 10000.0, operand_bits=None, ffn_inputs=None):
    """``(logits (B, S, padded vocab), balance (L,), z (L,))`` in float32;
    a list given as ``ffn_inputs`` receives each layer's normalised hidden
    states (B, S, E), the input of its sparse FFN."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    balance, z = [], []
    for p in layers(params, n_layer):
        x, h = _attn_block(p, x, n_head=n_head, eps=eps, theta=theta,
                           bits=operand_bits)
        ff, b_l, z_l = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                                  norm_topk_prob, operand_bits)
        x = x + ff.reshape(x.shape)
        balance.append(b_l)
        z.append(z_l)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    lg = _head({"norm": params["norm"], "lm_head": params["lm_head"]}, x,
               vocab_size=vocab_size, eps=eps, bits=operand_bits)
    return lg, jnp.stack(balance), jnp.stack(z)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)[0]


def loss_parts(params, input_ids, *, aux_loss_weight: float = 0.01,
               z_loss_weight: float = 0.001, **kw):
    """``(next-token cross-entropy, weighted router losses)``: labels are
    the inputs shifted by one, the last position of each row left out; the
    router losses are the layer means times their weights."""
    lg, balance, z = forward(params, input_ids, **kw)
    lg = lg[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return nll.mean(), aux_loss_weight * balance.mean() + z_loss_weight * z.mean()


def training_loss(params, input_ids, **kw):
    """CE + 0.01 x load-balancing loss + 0.001 x router z-loss."""
    ce, aux = loss_parts(params, input_ids, **kw)
    return ce + aux
