"""Plain GPT-2 forward: float32 ``jax.numpy`` at "highest" matmul
precision, no kernels, no cache, no batching tricks.

Follows Radford et al. 2019 / the ``openai-community/gpt2*`` checkpoints:
learned positions, pre-norm blocks, fused QKV projection split in thirds,
``gelu_new`` (tanh approximation), 4E MLP, tied output head.  One
departure: the head's vocabulary rows are padded (to 128) by the system
under test, and the reference masks the padding columns exactly as the
model does.  The parameter tree is the program's (``wte``, ``wpe``,
``h_<i>`` or a stacked ``h``, ``ln_f``), read, never copied whole: one
jitted block is called once a layer on that layer's leaves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(p, x, n_head: int, eps: float):
    with jax.default_matmul_precision(_HI):
        B, S, E = x.shape
        D = E // n_head
        h = _layer_norm(x, p["ln_1"], eps)
        qkv = h @ _f32(p["attn"]["c_attn_kernel"]) + _f32(p["attn"]["c_attn_bias"])
        q, k, v = (t.reshape(B, S, n_head, D) for t in jnp.split(qkv, 3, -1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(B, S, E) @ _f32(p["attn"]["c_proj_kernel"]) \
            + _f32(p["attn"]["c_proj_bias"])
        h = _layer_norm(x, p["ln_2"], eps)
        h = h @ _f32(p["mlp"]["c_fc_kernel"]) + _f32(p["mlp"]["c_fc_bias"])
        h = 0.5 * h * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (h + 0.044715 * h ** 3)))
        return x + h @ _f32(p["mlp"]["c_proj_kernel"]) + _f32(p["mlp"]["c_proj_bias"])


@functools.partial(jax.jit, static_argnames=("vocab_size", "eps"))
def _head(params, x, vocab_size: int, eps: float):
    with jax.default_matmul_precision(_HI):
        h = _layer_norm(x, params["ln_f"], eps)
        logits = h @ _f32(params["wte"]).T
        pad = jnp.arange(logits.shape[-1]) < vocab_size
        return jnp.where(pad, logits, -jnp.inf)


def _layers(params, n_layer):
    if "h" in params:       # scanned stack: leading layer axis
        for i in range(n_layer):
            yield jax.tree_util.tree_map(lambda a: a[i], params["h"])
    else:
        for i in range(n_layer):
            yield params[f"h_{i}"]


def logits(params, input_ids, *, n_layer: int, n_head: int, vocab_size: int,
           eps: float = 1e-5):
    """(B, S, padded vocab) float32 logits of ``input_ids`` (B, S)."""
    ids = jnp.asarray(input_ids)
    x = _f32(params["wte"])[ids] + _f32(params["wpe"])[jnp.arange(ids.shape[1])][None]
    for p in _layers(params, n_layer):
        x = _block(p, x, n_head=n_head, eps=eps)
    return _head({"ln_f": params["ln_f"], "wte": params["wte"]}, x,
                 vocab_size=vocab_size, eps=eps)


def next_token_loss(params, input_ids, **kw):
    """Mean next-token cross-entropy of ``input_ids`` (labels = inputs
    shifted by one, the last position of each row left out)."""
    lg = logits(params, input_ids, **kw)[:, :-1]
    tgt = jnp.asarray(input_ids)[:, 1:]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, tgt[..., None], -1)[..., 0]
    return nll.mean()
