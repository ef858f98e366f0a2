"""Plain SDAR (block-diffusion) forward, training loss and gradients:
float32 ``jax.numpy`` at "highest" matmul precision; no kernels, no sort,
no grouped matmul, no chunked head, nothing of a tile schedule.

Follows the model's public ``config.json`` (JetLM/SDAR-30B-A3B-Chat,
``model_type: sdar_moe``), the family's paper (arXiv:2510.06303) and the
block-diffusion objective it adapts an autoregressive model to
(arXiv:2503.09573).  Pre-norm residual blocks, ``x += W_o Attn(...)``,
``x += MoE(RMSNorm(x))``, RMSNorm eps 1e-6, no biases, untied head.

*Attention*: q = x W_q (hidden -> heads x head_dim), k, v = x W_k, x W_v
(hidden -> kv heads x head_dim); an RMSNorm over each head's channels of q
and of k (one scale of head_dim each: the family's released block);
half-split rotary, theta 1e6, no scaling; scores q k^T / sqrt(head_dim);
query head h reads key-value head ``h // (heads / kv heads)``.

*FFN*: router logits x W_r over ALL routed experts, float32 softmax, top-k,
the chosen weights divided by their sum (``norm_topk_prob``), expert e =
``(silu(x G_e) * x U_e) D_e``, output the weighted sum over the chosen.

*The objective* (what makes it SDAR).  A row ``x`` of L tokens is cut into
blocks of g tokens, ``b(i) = i // g``.  A block has a noise level t in
(0, 1]; ``mask`` says which tokens were replaced by the mask id, giving
x~.  The model runs on the 2L positions ``[x~ ; x]``, both halves with
position ids 0 .. L-1, and keeps, for query i and key j
(:func:`attention_mask`, built from half flags and block ids alone):

- noisy query, noisy key:  b(j) = b(i)   (its own block, both directions)
- noisy query, clean key:  b(j) < b(i)   (earlier blocks)
- clean query, clean key:  b(j) <= b(i)  (its own and earlier blocks)
- clean query, noisy key:  never

The loss is ``sum_i mask_i (1 / t_b(i)) nll_i / (B L)`` with ``nll_i =
-log p(x_i | .)`` read at the noisy half's own position i (no shift); the
clean half has no loss.

Departures, each marked below:

1. the load-balancing loss (``routed * sum_e f_e P_e``) is computed a layer,
   over all 2L rows of a data row, and averaged over the layers; HF
   concatenates the layers' router logits first.
2. **the share**: with more routed experts than the leaves hold, this is
   one chip of an expert-parallel layer.  ``first_expert`` says which
   contiguous run the leaves are.  Routing, the renormalisation and the
   loss are over all routed experts; only the held experts' terms of the
   weighted sum are computed, what the others would add is left out, and
   that partial sum goes on to the next layer (model-configs guide,
   section 4).
3. the vocabulary is the slice the head holds; padded columns are masked
   as the model masks them.
4. no document mask: rows are packed documents without padding, and a
   block may span two documents.
5. the noise is an ARGUMENT (``mask`` (B, L) bool, ``t`` (B, L / g)): how
   it is drawn is the trainer's business (U(t_min, 1] a block).

So that a 16,384-position row fits beside a trainer's state, attention is
computed in blocks of ``Q_BLOCK`` queries under ``lax.map`` (rematerialised
in the backward) and the held experts are walked by ``lax.scan``.

``operand_bits=(exponent, mantissa)`` rounds both operands of every matrix
multiplication to that float format first: ``(4, 3)`` is "this forward in
fp8", the precision below the bf16 the configuration computes in.
``fault`` computes a named WRONG thing, to read what a tolerance must
refuse (:data:`FAULTS`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
Q_BLOCK = 256
# the clean half made plain causal; the noisy half shown its own block's
# clean keys; the weight 1/t dropped; the loss read from the clean half;
# key-value head h % kv heads
FAULTS = ("clean_causal", "noisy_sees_own_clean", "no_weight", "clean_loss",
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


def attention_mask(length: int, block_length: int, fault=None):
    """The ``(2L, 2L)`` boolean mask over ``[noisy ; clean]``, True = keep,
    from the four sentences of the module's docstring."""
    pos = np.arange(2 * length)
    noisy = pos < length                    # half flag
    blk = (pos % length) // block_length    # block id within the row
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    keep = (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))
    if fault == "clean_causal":
        i, j = (pos % length)[:, None], (pos % length)[None, :]
        keep = np.where(~qn & ~kn, j <= i, keep)
    elif fault == "noisy_sees_own_clean":
        keep = keep | (qn & ~kn & (kb == qb))
    return keep


def _rotary(x, positions, theta):
    """Half-split rotation (HF ``rotate_half``) of x (B, S, H, D) at
    ``positions`` (S,)."""
    D = x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _core(q, k, v, block_length, bits, fault):
    """Masked softmax attention over ``[noisy ; clean]``: q (B, 2L, H, D),
    k and v (B, 2L, KV, D) -> (B, 2L, H, D), dense scores a query block."""
    B, S, n_head, D = q.shape
    n_kv_head = k.shape[2]
    keep = jnp.asarray(attention_mask(S // 2, block_length, fault))
    kv_of = np.arange(n_head) % n_kv_head if fault == "kv_mod" \
        else np.arange(n_head) // (n_head // n_kv_head)
    kt = k.transpose(0, 2, 3, 1)[:, kv_of]                  # (B, H, D, S)
    vt = v.transpose(0, 2, 1, 3)[:, kv_of]                  # (B, H, S, D)
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs = q.transpose(0, 2, 1, 3).reshape(B, n_head, S // qb, qb, D)

    @jax.checkpoint         # the backward recomputes a block's scores
    def block(args):
        q_blk, rows = args                                  # (B, H, qb, D)
        s = _mm(q_blk, kt, bits) / np.sqrt(D)               # (B, H, qb, S)
        s = jnp.where(rows[None, None], s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), vt, bits)         # (B, H, qb, D)

    a = jax.lax.map(block, (jnp.moveaxis(qs, 2, 0),
                            keep.reshape(S // qb, qb, S)))  # (nb, B, H, qb, D)
    return jnp.moveaxis(a, 0, 2).reshape(B, n_head, S, D).transpose(0, 2, 1, 3)


def _qkv(p, x, n_head, n_kv_head, head_dim, rope_theta, eps, bits):
    """The layer's q, k, v from normalised hidden states x (B, 2L, E):
    projections, the per-head norm, rotary at positions 0 .. L-1 twice."""
    B, S, E = x.shape
    D = head_dim
    q = _mm(x, _f32(p["q_proj_kernel"]), bits).reshape(B, S, n_head, D)
    k = _mm(x, _f32(p["k_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    v = _mm(x, _f32(p["v_proj_kernel"]), bits).reshape(B, S, n_kv_head, D)
    # each head's channels (the family's released block)
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    positions = np.tile(np.arange(S // 2), 2)   # both halves 0 .. L-1
    return _rotary(q, positions, rope_theta), _rotary(k, positions, rope_theta), v


# One jitted function a stage, shared by everything that runs the stage: the
# whole forward, a layer alone and the core alone compile the dense scores
# once a shape (on the chip at 16,384 positions that compile is most of what
# a comparison costs).
_QKV_STATIC = ("n_head", "n_kv_head", "head_dim", "rope_theta", "eps", "bits")


@functools.partial(jax.jit, static_argnames=_QKV_STATIC)
def _qkv_stage(p, x, **kw):
    with jax.default_matmul_precision(_HI):
        return _qkv(p, x, **kw)


@functools.partial(jax.jit, static_argnames=("block_length", "bits", "fault"))
def _core_stage(q, k, v, block_length, bits, fault):
    with jax.default_matmul_precision(_HI):
        return _core(q, k, v, block_length, bits, fault)


@functools.partial(jax.jit, static_argnames=("bits",))
def _out_stage(p, a, bits):
    with jax.default_matmul_precision(_HI):
        B, S = a.shape[:2]
        return _mm(a.reshape(B, S, -1), _f32(p["o_proj_kernel"]), bits)


def _attention(p, x, n_head, n_kv_head, head_dim, block_length, rope_theta,
               eps, bits, fault):
    q, k, v = _qkv_stage(p, x, n_head=n_head, n_kv_head=n_kv_head,
                         head_dim=head_dim, rope_theta=rope_theta, eps=eps,
                         bits=bits)
    return _out_stage(p, _core_stage(q, k, v, block_length, bits, fault),
                      bits)


def attention(p_attn, h, *, n_head, n_kv_head, head_dim, block_length,
              rope_theta=1e6, eps=1e-6, operand_bits=None, fault=None):
    """One attention layer alone: normalised hidden states ``h`` (B, 2L, E)
    of ``[noisy ; clean]`` through the layer's ``self_attn`` leaves,
    float32: (B, 2L, E)."""
    assert fault is None or fault in FAULTS, fault
    return _attention(p_attn, _f32(h), n_head, n_kv_head, head_dim,
                      block_length, float(rope_theta), eps, operand_bits,
                      fault)


def qkv(p_attn, h, *, n_head, n_kv_head, head_dim, rope_theta=1e6, eps=1e-6):
    """The q, k, v that layer's attention core reads, float32."""
    return _qkv_stage(p_attn, _f32(h), n_head=n_head, n_kv_head=n_kv_head,
                      head_dim=head_dim, rope_theta=float(rope_theta),
                      eps=eps, bits=None)


@functools.partial(jax.jit, static_argnames=("block_length", "bits", "fault"))
def _core_vjp(q, k, v, cotangent, block_length, bits, fault):
    with jax.default_matmul_precision(_HI):
        out, vjp = jax.vjp(
            lambda q, k, v: _core(q, k, v, block_length, bits, fault),
            q, k, v)
        return (out,) + vjp(cotangent)


def attention_core(q, k, v, cotangent, *, block_length, operand_bits=None,
                   fault=None):
    """``(out, dq, dk, dv)`` of the masked softmax attention alone over
    ``[noisy ; clean]`` under ``cotangent`` (as ``out``), float32: what a
    system's kernels are held to on the same q, k, v."""
    assert fault is None or fault in FAULTS, fault
    return _core_vjp(_f32(q), _f32(k), _f32(v), _f32(cotangent),
                     block_length, operand_bits, fault)


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


def _attn_block(p, x, **kw):
    """``(x + attention, the normalised input of the layer's attention, the
    normalised input of its sparse FFN)``."""
    eps = kw["eps"]
    h_attn = _norm_stage(x, p["input_norm"]["scale"], eps)
    x = x + _attention(p["self_attn"], h_attn, **kw)
    return x, h_attn, _norm_stage(x, p["post_attention_norm"]["scale"], eps)


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


def noisy_ids(input_ids, mask, mask_token_id: int):
    """x~: the row with its masked tokens replaced by the mask id."""
    return jnp.where(jnp.asarray(mask, bool), mask_token_id,
                     jnp.asarray(input_ids))


@functools.partial(jax.jit, static_argnames=("eps",))
def _embed_norm(table, scale, both, eps):
    return _rms_norm(_f32(table)[both], scale, eps)


def first_attention_input(params, input_ids, mask, *, mask_token_id: int,
                          eps: float = 1e-6):
    """The normalised hidden states the FIRST layer's attention reads,
    (B, 2L, E) over ``[noisy ; clean]``: the embedding of both copies
    through that layer's input norm."""
    ids = jnp.asarray(input_ids)
    both = jnp.concatenate([noisy_ids(ids, mask, mask_token_id), ids], axis=1)
    first = next(iter(layers(params, 1)))
    return _embed_norm(params["embed_tokens"], first["input_norm"]["scale"],
                       both, eps)


def forward(params, input_ids, mask, *, n_layer: int, n_head: int,
            n_kv_head: int, head_dim: int, vocab_size: int, top_k: int,
            block_length: int, mask_token_id: int, rope_theta: float = 1e6,
            norm_topk_prob: bool = True, eps: float = 1e-6,
            routed_experts=None, first_expert: int = 0, operand_bits=None,
            fault=None, ffn_inputs=None, attn_inputs=None):
    """``(logits (B, 2L, padded vocab) over [noisy ; clean], balance (L,),
    z (L,))`` in float32 for the noise ``mask`` (B, L); lists given as
    ``attn_inputs`` / ``ffn_inputs`` receive each layer's normalised hidden
    states (B, 2L, E) before its attention / sparse FFN.
    ``routed_experts`` is checked against the router's width."""
    assert fault is None or fault in FAULTS, fault
    ids = jnp.asarray(input_ids)
    both = jnp.concatenate([noisy_ids(ids, mask, mask_token_id), ids], axis=1)
    x = _f32(params["embed_tokens"])[both]
    balance, z = [], []
    for p in layers(params, n_layer):
        assert routed_experts in (None, p["moe"]["gate"]["wg"].shape[1])
        x, h_attn, h = _attn_block(
            p, x, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
            block_length=block_length, rope_theta=float(rope_theta), eps=eps,
            bits=operand_bits, fault=fault)
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


def logits(params, input_ids, mask, **kw):
    return forward(params, input_ids, mask, **kw)[0]


def loss_parts(params, input_ids, mask, t, *, aux_loss_weight: float = 0.001,
               z_loss_weight: float = 0.0, **kw):
    """``(the weighted cross-entropy, weighted router losses)`` for the
    noise ``mask`` (B, L) and the blocks' levels ``t`` (B, L / g):
    ``sum_i mask_i / t_b(i) * nll_i / (B L)`` over the noisy half, read at
    position i itself; the router losses are the layer means times their
    weights."""
    fault = kw.get("fault")
    lg, balance, z = forward(params, input_ids, mask, **kw)
    ids = jnp.asarray(input_ids)
    B, L = ids.shape
    half = lg[:, L:] if fault == "clean_loss" else lg[:, :L]
    nll = jax.nn.logsumexp(half, -1) - jnp.take_along_axis(
        half, ids[..., None], -1)[..., 0]
    weight = jnp.asarray(mask, jnp.float32)
    if fault != "no_weight":
        weight = weight / jnp.repeat(_f32(t), kw["block_length"], axis=1)
    return (weight * nll).sum() / (B * L), \
        aux_loss_weight * balance.mean() + z_loss_weight * z.mean()


def training_loss(params, input_ids, mask, t, **kw):
    """The weighted cross-entropy + the router losses."""
    ce, aux = loss_parts(params, input_ids, mask, t, **kw)
    return ce + aux


def loss_and_grads(params, input_ids, mask, t, **kw):
    """``(loss, d loss / d params)`` by ``jax.grad`` of :func:`training_loss`."""
    return jax.value_and_grad(
        lambda p: training_loss(p, input_ids, mask, t, **kw))(params)
