"""Drop-in fused transformer layer — the ``DeepSpeedTransformerLayer`` API.

Reference: ``deepspeed/ops/transformer/transformer.py`` —
``DeepSpeedTransformerConfig`` (:39) carries the kernel knobs and
``DeepSpeedTransformerLayer`` (:460) is a user-facing BERT-style encoder
layer backed by the fused CUDA kernel (``csrc/transformer/``); users swap
it into their models layer-by-layer (e.g. the BingBert recipe).

TPU-native: the layer is a flax module whose hot ops dispatch to the
Pallas kernel set (``ops/pallas``) on TPU and to XLA-fused jnp elsewhere.
The config keeps the reference's field names so existing integration code
ports by renaming the import.  ``normalize_invertible`` /
``attn_dropout_checkpoint`` / ``gelu_checkpoint`` (memory knobs that
discard and recompute intermediates) map onto ``jax.checkpoint`` over the
layer — on TPU rematerialization is a compiler policy, not hand-written
kernel variants; ``stochastic_mode`` (the reference's speed-over-
reproducibility trade) has no analog because XLA programs are
deterministic at no cost.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .attention import dot_product_attention, on_tpu


@dataclasses.dataclass(frozen=True)
class DeepSpeedTransformerConfig:
    """Field-compatible with reference ``transformer.py:39``."""

    batch_size: int = -1                 # accepted; shapes are dynamic here
    hidden_size: int = 768
    intermediate_size: int = 3072
    heads: int = 12
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    local_rank: int = -1                 # accepted for parity; unused (SPMD)
    seed: int = -1
    fp16: bool = False
    pre_layer_norm: bool = True
    normalize_invertible: bool = False   # → remat
    gelu_checkpoint: bool = False        # → remat
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False  # → remat
    stochastic_mode: bool = False        # no-op: XLA is deterministic
    fused_mlp: bool = False              # opt-in Pallas FFN (measured slower
                                         # e2e than XLA's scheduling on the
                                         # bench chip; see models/gpt2.py)
    return_tuple: bool = False      # True → layer returns (out,)

    @property
    def dtype(self):
        return jnp.float16 if self.fp16 else jnp.bfloat16

    @property
    def use_remat(self) -> bool:
        return (self.normalize_invertible or self.gelu_checkpoint
                or self.attn_dropout_checkpoint)


class DeepSpeedTransformerLayer(nn.Module):
    """BERT-style encoder layer (pre- or post-LN), fused-kernel backed.

    Call: ``layer(hidden_states, attention_mask)`` with
    ``hidden_states (B, S, H)`` and optional additive or boolean mask
    broadcastable to ``(B, 1, S, S)``; returns ``(B, S, H)``.
    """

    config: DeepSpeedTransformerConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None):
        cfg = self.config

        def body(mod, x):
            return _layer_body(mod, cfg, x, attention_mask,
                               self.deterministic)

        if cfg.use_remat:
            out = nn.remat(lambda m, x: body(m, x))(self, hidden_states)
        else:
            out = body(self, hidden_states)
        return (out,) if cfg.return_tuple else out


def _layer_body(mod: nn.Module, cfg: DeepSpeedTransformerConfig, x,
                attention_mask, deterministic: bool):
    H = cfg.hidden_size
    heads = cfg.heads
    head_dim = H // heads
    dtype = cfg.dtype
    B, S, _ = x.shape
    x = x.astype(dtype)

    def dense_params(name, in_features, features, names, std=None):
        kernel = mod.param(
            name + "_kernel",
            nn.with_partitioning(
                nn.initializers.normal(std or cfg.initializer_range), names),
            (in_features, features), jnp.float32)
        bias = mod.param(name + "_bias",
                         nn.with_partitioning(nn.initializers.zeros,
                                              (names[-1],)),
                         (features,), jnp.float32)
        return kernel, bias

    def dense(name, inp, features, names, std=None):
        kernel, bias = dense_params(name, inp.shape[-1], features, names, std)
        return jnp.dot(inp, kernel.astype(dtype)) + bias.astype(dtype)

    def layer_norm(name, inp):
        scale = mod.param(name + "_scale",
                          nn.with_partitioning(nn.initializers.ones, ("embed",)),
                          (inp.shape[-1],), jnp.float32)
        bias = mod.param(name + "_bias",
                         nn.with_partitioning(nn.initializers.zeros, ("embed",)),
                         (inp.shape[-1],), jnp.float32)
        xf = inp.astype(jnp.float32)
        mean = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.layer_norm_eps)
        return (y * scale + bias).astype(dtype)

    mask = None
    if attention_mask is not None:
        if attention_mask.dtype == bool:
            mask = attention_mask                 # True = attend
        elif jnp.issubdtype(attention_mask.dtype, jnp.floating):
            # BERT-style extended additive mask: 0 = keep, large negative =
            # masked; bool(-10000.) would INVERT it
            mask = attention_mask > -0.5
        else:                                     # int {0, 1} padding mask
            mask = attention_mask != 0
        while mask.ndim < 4:
            mask = mask[:, None]

    # --- attention block ---
    attn_in = layer_norm("attn_ln", x) if cfg.pre_layer_norm else x
    qkv = dense("attn_qkv", attn_in, 3 * H, ("embed", "qkv"))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    drop_rng = None
    if cfg.attn_dropout_ratio > 0.0 and not deterministic:
        drop_rng = mod.make_rng("dropout")
    ctx = dot_product_attention(
        q.reshape(B, S, heads, head_dim), k.reshape(B, S, heads, head_dim),
        v.reshape(B, S, heads, head_dim), causal=False, mask=mask,
        dropout_rate=0.0 if deterministic else cfg.attn_dropout_ratio,
        dropout_rng=drop_rng).reshape(B, S, H)
    attn_out = dense("attn_out", ctx, H, ("heads", "embed"))
    if cfg.hidden_dropout_ratio > 0.0 and not deterministic:
        attn_out = nn.Dropout(cfg.hidden_dropout_ratio)(
            attn_out, deterministic=False, rng=mod.make_rng("dropout"))
    x = x + attn_out
    if not cfg.pre_layer_norm:
        x = layer_norm("attn_ln", x)

    # --- FFN block ---
    ffn_in = layer_norm("ffn_ln", x) if cfg.pre_layer_norm else x
    w1, b1 = dense_params("inter", H, cfg.intermediate_size, ("embed", "mlp"))
    w2, b2 = dense_params("output", cfg.intermediate_size, H,
                          ("mlp", "embed"))
    out = None
    if cfg.fused_mlp and on_tpu():
        from .pallas.fused_mlp import fits_vmem, fused_mlp_spmd

        # fit-gate BEFORE dispatch: a Mosaic VMEM overflow surfaces at the
        # user's outer jit compile
        if fits_vmem(H, cfg.intermediate_size, 128,
                     jnp.dtype(dtype).itemsize):
            out = fused_mlp_spmd(ffn_in, w1.astype(dtype), b1.astype(dtype),
                                 w2.astype(dtype), b2.astype(dtype))
    if out is None:
        h = nn.gelu(jnp.dot(ffn_in, w1.astype(dtype)) + b1.astype(dtype),
                    approximate=True)
        out = jnp.dot(h, w2.astype(dtype)) + b2.astype(dtype)
    if cfg.hidden_dropout_ratio > 0.0 and not deterministic:
        out = nn.Dropout(cfg.hidden_dropout_ratio)(
            out, deterministic=False, rng=mod.make_rng("dropout"))
    x = x + out
    if not cfg.pre_layer_norm:
        x = layer_norm("ffn_ln", x)
    return x
