"""Plain Mellum 2 forward and training loss: float32 ``jax.numpy`` at
"highest" matmul precision; no kernels, no sort, no grouped matmul, no
cache, no chunked head.

Follows the model's public ``config.json`` (JetBrains/Mellum2-12B-A2.5B,
``model_type: mellum``) and the Hugging Face conventions its key names
come from.  28 pre-norm residual blocks, ``x += Attn(RMSNorm(x))``, ``x +=
MoE(RMSNorm(x))``, RMSNorm eps 1e-6, no biases, untied head.

*Attention*: q = x W_q (hidden -> heads x head_dim), k, v = x W_k, x W_v
(hidden -> kv heads x head_dim), half-split rotary over all of head_dim,
scores q k^T / sqrt(head_dim), query head h reads key-value head
``h // (heads / kv heads)``, causal.  ``layer_types`` names each layer
``sliding_attention`` (key j kept for query i iff ``0 <= i - j <
sliding_window``; rotary ``theta^(-2m/d)``) or ``full_attention`` (every
``j <= i``; YaRN: channel pairs below ``low = floor(c(beta_fast))`` keep
their frequency, those above ``high = ceil(c(beta_slow))`` are slowed by
``factor``, a linear ramp between, with ``c(r) = d ln(L / 2 pi r) / (2 ln
theta)`` and L the original context; cos and sin times
``attention_factor``).

*FFN*: router logits x W_r over ALL routed experts, float32 softmax, top-k,
weights renormalised to sum 1 (``norm_topk_prob``), expert e =
``(silu(x G_e) * x U_e) D_e``, output the weighted sum over the chosen.

Departures, each marked below:

1. no QK-norm and no multi-token-prediction head: the config has a key for
   neither.
2. the load-balancing loss (``num_experts * sum_e f_e P_e``) is computed a
   layer and averaged over the layers; HF concatenates the layers' router
   logits first.  No router z-loss (``z_loss_weight`` defaults to 0).
3. **the share**: with ``routed_experts`` more than the experts the leaves
   hold, this is one chip of an expert-parallel layer.  ``first_expert``
   says which contiguous run the leaves are.  Routing, renormalisation and
   the loss are over all routed experts; only the held experts' terms of
   the weighted sum are computed, what the others would add is left out,
   and that partial sum goes on to the next layer
   (model-configs guide, section 4).
4. the vocabulary is the slice the head holds; padded columns (none at
   24,576) are masked as the model masks them.
5. no attention mask: rows are packed documents without padding.

So that an 8192-token row fits beside a trainer's state and compiles fast,
attention is computed in blocks of ``Q_BLOCK`` queries under ``lax.map``
and the held experts are walked by ``lax.scan`` over their stacked leaves:
the same sums in the same float32, no Python loop to unroll.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first: ``(4, 3)`` is "this forward in
fp8", the precision below the bf16 the configuration computes in.
``fault`` makes :func:`attention` compute a named WRONG thing, to read what
a tolerance must refuse: ``window+1``, ``no_window``, ``default_rope``
(on a full layer), ``no_attention_factor``, ``kv_mod`` (key-value head
``h % kv heads``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
SLIDING, FULL = "sliding_attention", "full_attention"
FAULTS = ("window+1", "no_window", "default_rope", "no_attention_factor",
          "kv_mod")


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _mm(a, b, bits):
    """``a @ b`` with both operands rounded to ``bits`` (None: as they are)."""
    if bits is not None:
        a, b = (jax.lax.reduce_precision(t, *bits) for t in (a, b))
    return a @ b


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _freeze(x):
    """json's dicts and lists as hashable tuples (static jit arguments)."""
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    return tuple(_freeze(v) for v in x) if isinstance(x, (list, tuple)) else x


def rotary_table(kind, rope_parameters, head_dim):
    """``(inv_freq (head_dim/2,) float64, factor on cos and sin)`` of a layer
    type, from the config's ``rope_parameters`` entry for it."""
    entry = dict(dict(_freeze(rope_parameters))[kind])
    theta = float(entry["rope_theta"])
    m = np.arange(head_dim // 2, dtype=np.float64)
    extrap = theta ** (-2.0 * m / head_dim)
    if entry["rope_type"] == "default":
        return extrap, 1.0
    assert entry["rope_type"] == "yarn", entry
    factor, orig = float(entry["factor"]), entry["original_max_position_embeddings"]

    def c(rotations):
        return head_dim * math.log(orig / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(c(entry["beta_fast"])), 0)
    high = min(math.ceil(c(entry["beta_slow"])), head_dim - 1)
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    inv_freq = extrap / factor * ramp + extrap * (1.0 - ramp)
    return inv_freq, float(entry.get("attention_factor",
                                     0.1 * math.log(factor) + 1.0))


def _rotary(x, inv_freq, factor):
    """Half-split rotation (HF ``rotate_half``) of x (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    ang = np.arange(S, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, kind, n_head, n_kv_head, head_dim, sliding_window,
               rope_parameters, bits, fault):
    B, S, E = x.shape
    D, group = head_dim, n_head // n_kv_head
    q = _mm(x, _f32(p["q_proj_kernel"]), bits).reshape(B, S, n_head, D)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    # departure 1: no q / k norm
    inv_freq, factor = rotary_table(
        SLIDING if fault == "default_rope" else kind, rope_parameters, D)
    if fault == "no_attention_factor":
        factor = 1.0
    q, k = _rotary(q, inv_freq, factor), _rotary(k, inv_freq, factor)
    window = sliding_window if kind == SLIDING else None
    if fault == "window+1":
        window += 1
    elif fault == "no_window":
        window = None
    # key-value head of each query head
    kv_of = np.arange(n_head) % n_kv_head if fault == "kv_mod" \
        else np.arange(n_head) // group
    kt = k.transpose(0, 2, 3, 1)[:, kv_of]                  # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)[:, kv_of]                  # (B, H, S, D)
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs = q.transpose(0, 2, 1, 3).reshape(B, n_head, S // qb, qb, D)
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

    a = jax.lax.map(block, (jnp.moveaxis(qs, 2, 0),
                            jnp.arange(0, S, qb)))          # (nb, B, H, qb, D)
    a = jnp.moveaxis(a, 0, 2).reshape(B, n_head, S, D).transpose(0, 2, 1, 3)
    return _mm(a.reshape(B, S, n_head * D), _f32(p["o_proj_kernel"]), bits)


_ATTN_STATIC = ("kind", "n_head", "n_kv_head", "head_dim", "sliding_window",
                "rope_parameters", "bits", "fault")


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC)
def _attention_alone(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _attention(p, x, **kw)


def attention(layer_type, p_attn, h, *, n_head, n_kv_head, head_dim,
              sliding_window, rope_parameters, operand_bits=None, fault=None):
    """One attention layer alone: normalised hidden states ``h`` (B, S, E)
    through the layer's ``self_attn`` leaves (projections, the layer
    type's rotary table, grouped causal or windowed softmax attention, the
    output projection), float32.  What a system's attention layer of that
    type is held to on the same ``h``."""
    assert fault is None or fault in FAULTS, fault
    return _attention_alone(
        p_attn, _f32(h), kind=layer_type, n_head=n_head, n_kv_head=n_kv_head,
        head_dim=head_dim, sliding_window=sliding_window,
        rope_parameters=_freeze(rope_parameters), bits=operand_bits,
        fault=fault)


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

    def one(out, leaf):          # departure 3: the held experts alone
        gate, up, down, e = leaf
        y = _mm(jax.nn.silu(_mm(h, _f32(gate), bits)) * _mm(h, _f32(up), bits),
                _f32(down), bits)
        w = jax.lax.dynamic_index_in_dim(weight, first_expert + e, 1)  # (T, 1)
        return out + jnp.where(w > 0, y * w, 0.0), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (ex["gate"], ex["up"], ex["down"], jnp.arange(held)))
    # departure 2: this layer's own f_e and P_e, over all routed experts
    share = (weight > 0).astype(jnp.float32).sum(0) / (h.shape[0] * top_k)
    balance = routed * jnp.sum(share * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, balance, z


@functools.partial(jax.jit, static_argnames=_ATTN_STATIC + ("eps",))
def _attn_block(p, x, eps, **kw):
    """``(x + attention, the normalised input of the layer's attention, the
    normalised input of its sparse FFN)``."""
    with jax.default_matmul_precision(_HI):
        h_attn = _rms_norm(x, p["input_norm"]["scale"], eps)
        x = x + _attention(p["self_attn"], h_attn, **kw)
        return x, h_attn, _rms_norm(x, p["post_attention_norm"]["scale"], eps)


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
        # departure 4: padded vocabulary columns
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
            head_dim: int, vocab_size: int, top_k: int, layer_types,
            sliding_window: int, rope_parameters, norm_topk_prob: bool = True,
            eps: float = 1e-6, routed_experts=None, first_expert: int = 0,
            operand_bits=None, ffn_inputs=None, attn_inputs=None):
    """``(logits (B, S, padded vocab), balance (L,), z (L,))`` in float32;
    lists given as ``attn_inputs`` / ``ffn_inputs`` receive each layer's
    normalised hidden states (B, S, E) before its attention / sparse FFN.
    ``routed_experts`` is checked against the router's width."""
    x = _f32(params["embed_tokens"])[jnp.asarray(input_ids)]
    balance, z = [], []
    for i, p in enumerate(layers(params, n_layer)):
        assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        x, h_attn, h = _attn_block(
            p, x, eps=eps, kind=layer_types[i], n_head=n_head,
            n_kv_head=n_kv_head, head_dim=head_dim,
            sliding_window=sliding_window,
            rope_parameters=_freeze(rope_parameters), bits=operand_bits,
            fault=None)
        ff, b_l, z_l = _ffn_block(p["moe"], h.reshape(-1, h.shape[-1]), top_k,
                                  norm_topk_prob, first_expert, operand_bits)
        x = x + ff.reshape(x.shape)
        balance.append(b_l)
        z.append(z_l)
        if attn_inputs is not None:
            attn_inputs.append(h_attn)
        if ffn_inputs is not None:
            ffn_inputs.append(h)
    lg = _head({"norm": params["norm"], "lm_head": params["lm_head"]}, x,
               vocab_size=vocab_size, eps=eps, bits=operand_bits)
    return lg, jnp.stack(balance), jnp.stack(z)


def logits(params, input_ids, **kw):
    return forward(params, input_ids, **kw)[0]


def loss_parts(params, input_ids, *, aux_loss_weight: float = 0.001,
               z_loss_weight: float = 0.0, **kw):
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
    """Cross-entropy + 0.001 x load-balancing loss."""
    ce, aux = loss_parts(params, input_ids, **kw)
    return ce + aux
