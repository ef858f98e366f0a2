"""LLaMA model family, TPU-native.

Beyond the reference's 2022 policy list — added because a modern user of
the framework expects the dominant open-model family.  Architecture:
RMSNorm, SwiGLU MLP, full rotary, grouped-query attention
(``num_key_value_heads``), untied LM head.  Shares the logical-axis
vocabulary, scan/remat/decode support of the other zoo families.

OLMoE (arXiv:2409.02060) is this block with fields, not a file of its own:
``moe`` puts a sparse SwiGLU-expert FFN (``parallel/moe.py``) in every
block, ``qk_norm`` an RMSNorm over the whole q and k projections before
the split into heads, and ``loss_chunk`` the chunked head.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.rotary import apply_rotary_pos_emb
from ..telemetry import trace
from .common import ModelOutput, cross_entropy_loss, resolve_remat_policy, shift_labels


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_position_embeddings: int = 2048
    # decode KV-cache length override: serving with a short
    # generation limit must not pay full-context cache traffic
    # every tick (the cache, not the weights, dominated decode
    # bandwidth at 760M/1024-ctx).  None: the position field.
    cache_len: Optional[int] = None
    hidden_size: int = 2048
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: Optional[int] = None   # None → MHA
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "auto"
    vocab_pad_multiple: int = 128
    # sparse FFN: a parallel.moe.MoEConfig replaces the dense SwiGLU MLP of
    # EVERY block with experts of width ``intermediate_size``
    moe: Optional[Any] = None
    # RMSNorm over the whole q / k projection, before heads and rotary
    qk_norm: bool = False
    # > 0 with labels: chunked cross-entropy head, logits never materialize
    # (common.chunked_lm_loss); the output then carries no ``logits``
    loss_chunk: int = 0
    decode: bool = False
    # weight-only int8 serving (ops/w8.py W8A16); set by init_inference
    w8: bool = False
    w8_group: int = 128
    # fused decode-tick megakernels (ops/pallas/decode_layer.py); see
    # GPT2Config.decode_fused.  DS_TPU_DECODE_FUSED env-overrides;
    # None = ON on TPU hardware (round-8 e2e sweep), OFF elsewhere.
    decode_fused: Optional[bool] = None

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads


PRESETS = {
    "llama-tiny": dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       intermediate_size=128, max_position_embeddings=128),
    "llama-1b": dict(hidden_size=2048, num_hidden_layers=22,
                     num_attention_heads=32, num_key_value_heads=4,
                     intermediate_size=8192),
    "llama-7b": dict(hidden_size=4096, num_hidden_layers=32,
                     num_attention_heads=32, intermediate_size=11008),
}


def llama_config(preset: str = "llama-tiny", **overrides) -> LlamaConfig:
    if preset not in PRESETS:
        raise ValueError(f"unknown preset {preset!r}; valid: {sorted(PRESETS)}")
    return LlamaConfig(**{**PRESETS[preset], **overrides})


def _dense(x, features, names, *, cfg, name, module):
    if cfg.w8:
        # int8 codes + grouped scales (ops/w8.py; names match
        # quantize_dense_tree's output from a trained checkpoint)
        from ..ops.w8 import declare_w8_dense, w8a16_matmul

        codes, scale = declare_w8_dense(module, name, names, x.shape[-1],
                                        features, cfg.w8_group)
        return w8a16_matmul(x, codes, scale)
    kernel = module.param(
        name + "_kernel",
        nn.with_partitioning(nn.initializers.normal(cfg.initializer_range), names),
        (x.shape[-1], features), cfg.param_dtype)
    return jnp.dot(x, kernel.astype(cfg.dtype))


class RMSNorm(nn.Module):
    cfg: LlamaConfig
    axis: str = "embed"             # logical axis of the normalised width

    @nn.compact
    def __call__(self, x, params_only: bool = False):
        scale = self.param("scale", nn.with_partitioning(nn.initializers.ones,
                                                         (self.axis,)),
                           (x.shape[-1],), self.cfg.param_dtype)
        if params_only:
            return scale
        from .common import rms_norm

        return rms_norm(x, scale, self.cfg.rms_norm_eps)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    def _cache_append(self, k, v):
        from .common import append_kv_cache

        cfg = self.cfg
        return append_kv_cache(self, k, v,
                               cfg.cache_len or cfg.max_position_embeddings,
                               cfg.dtype)

    def _fused_decode(self, x, position_ids, attn_mask, fused_norm):
        """Megakernel prologue: RMSNorm folded into each of the split
        q/k/v projection kernels (GQA keeps KV panels narrow); rotary and
        the decode-attention kernel run between the fusion groups."""
        cfg = self.cfg
        B, S, E = x.shape
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        ns, interp = fused_norm
        from .common import fused_decode_qkv

        from .common import declare_fused_proj

        def proj(name, names, feat):
            w = declare_fused_proj(self, cfg, name, names, E, feat)
            return fused_decode_qkv(x, ns, None, w, None, rms=True,
                                    eps=cfg.rms_norm_eps, interpret=interp)

        q = proj("q_proj", ("embed", "qkv"), H * D).reshape(B, S, H, D)
        k = proj("k_proj", ("embed", "kv"), KV * D).reshape(B, S, KV, D)
        v = proj("v_proj", ("embed", "kv"), KV * D).reshape(B, S, KV, D)
        q, k = apply_rotary_pos_emb(q, k, position_ids, rotary_dim=D,
                                    theta=cfg.rope_theta)
        kc, vc, cur = self._cache_append(k, v)
        from ..ops.attention import cached_decode_attention

        y = cached_decode_attention(q, kc, vc, cur, attn_mask)
        y = y.reshape(B, S, H * D)
        wo = declare_fused_proj(self, cfg, "o_proj", ("heads", "embed"),
                                H * D, E)
        return y, wo

    @nn.compact
    def __call__(self, x, position_ids, attn_mask, fused_norm=None):
        cfg = self.cfg
        B, S, E = x.shape
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        if fused_norm is not None:
            return self._fused_decode(x, position_ids, attn_mask,
                                      fused_norm)
        q = _dense(x, H * D, ("embed", "qkv"), cfg=cfg, name="q_proj",
                   module=self)
        k = _dense(x, KV * D, ("embed", "kv"), cfg=cfg, name="k_proj",
                   module=self)
        if cfg.qk_norm:
            q = RMSNorm(cfg, axis="qkv", name="q_norm")(q)
            k = RMSNorm(cfg, axis="kv", name="k_norm")(k)
        q, k = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        v = _dense(x, KV * D, ("embed", "kv"), cfg=cfg, name="v_proj",
                   module=self).reshape(B, S, KV, D)
        q, k = apply_rotary_pos_emb(q, k, position_ids, rotary_dim=D,
                                    theta=cfg.rope_theta)
        if cfg.decode:
            kc, vc, cur = self._cache_append(k, v)
            # shared fused-or-fallback dispatch; GQA-aware (KV panels stay
            # at KV heads on the kernel path — no repeat materialized)
            from ..ops.attention import cached_decode_attention

            y = cached_decode_attention(q, kc, vc, cur, attn_mask)
            y = y.reshape(B, S, H * D)
            return _dense(y, E, ("heads", "embed"), cfg=cfg,
                          name="o_proj", module=self)
        k_full, v_full = k, v
        if KV != H:  # GQA: repeat kv heads
            rep = H // KV
            k_full = jnp.repeat(k_full, rep, axis=2)
            v_full = jnp.repeat(v_full, rep, axis=2)
        y = dot_product_attention(q, k_full, v_full, causal=True,
                                  mask=attn_mask, impl=cfg.attn_impl)
        y = y.reshape(B, S, H * D)
        return _dense(y, E, ("heads", "embed"), cfg=cfg, name="o_proj", module=self)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, inputs):
        position_ids, attn_mask = inputs
        cfg = self.cfg
        if cfg.decode and x.shape[1] == 1 and cfg.moe is None \
                and not cfg.qk_norm:
            from .common import decode_fused_plan, fused_decode_post_attn

            H, KV, D = (cfg.num_attention_heads, cfg.kv_heads,
                        cfg.head_dim)
            E, I = cfg.hidden_size, cfg.intermediate_size
            plan = decode_fused_plan(cfg, x.shape[0] * x.shape[1], E,
                                     (H * D, KV * D, KV * D), I,
                                     swiglu=True)
            if plan is not None:
                from .common import declare_fused_proj

                interp = plan["interpret"]
                attn = LlamaAttention(cfg, name="self_attn")
                ns1 = RMSNorm(cfg, name="input_norm")(x, params_only=True)
                y, wo = attn(x, position_ids, attn_mask,
                             fused_norm=(ns1, interp))
                ns2 = RMSNorm(cfg, name="post_attention_norm")(
                    x, params_only=True)
                wg = declare_fused_proj(self, cfg, "gate_proj",
                                        ("embed", "mlp"), E, I)
                wu = declare_fused_proj(self, cfg, "up_proj",
                                        ("embed", "mlp"), E, I)
                wd = declare_fused_proj(self, cfg, "down_proj",
                                        ("mlp", "embed"), I, E)
                x = fused_decode_post_attn(
                    y, x, wo, None, ns2, None, (wg, wu, wd), swiglu=True,
                    rms=True, eps=cfg.rms_norm_eps, interpret=interp)
                return x, None
        x = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_norm")(x), position_ids, attn_mask)
        h = RMSNorm(cfg, name="post_attention_norm")(x)
        if cfg.moe is not None:
            from ..parallel.moe import MoELayer

            ff, aux, stats = MoELayer(
                cfg.moe, model_dim=cfg.hidden_size,
                hidden_dim=cfg.intermediate_size, dtype=cfg.dtype,
                name="moe")(h, train=not self.deterministic,
                            return_stats=True)
            return x + ff, dict(stats, aux_loss=aux)
        gate = _dense(h, cfg.intermediate_size, ("embed", "mlp"), cfg=cfg,
                      name="gate_proj", module=self)
        up = _dense(h, cfg.intermediate_size, ("embed", "mlp"), cfg=cfg,
                    name="up_proj", module=self)
        ff = _dense(nn.silu(gate) * up, cfg.hidden_size, ("mlp", "embed"),
                    cfg=cfg, name="down_proj", module=self)
        return x + ff, None


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 labels=None, deterministic: bool = True, shift: bool = True):
        cfg = self.cfg
        B, S = input_ids.shape
        embed = self.param("embed_tokens", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")),
            (cfg.padded_vocab_size, cfg.hidden_size), cfg.param_dtype)
        if position_ids is None:
            if cfg.decode:
                raise ValueError("decode mode requires explicit position_ids")
            position_ids = jnp.arange(S)[None, :]
        h = embed.astype(cfg.dtype)[input_ids]
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(
                LlamaBlock, policy=resolve_remat_policy(cfg.remat_policy),
                prevent_cse=False)
        if cfg.scan_layers:
            stack = nn.scan(block_cls,
                            variable_axes={"params": 0, "cache": 0},
                            split_rngs={"params": True, "dropout": True,
                                        "gating": True, "pld": True},
                            length=cfg.num_hidden_layers,
                            in_axes=nn.broadcast,
                            metadata_params={nn.meta.PARTITION_NAME: "layers"})
            h, per_layer = stack(cfg, deterministic, name="layers")(
                h, (position_ids, mask))
        else:
            per_layer = []
            for i in range(cfg.num_hidden_layers):
                h, ys = block_cls(cfg, deterministic, name=f"layers_{i}")(
                    h, (position_ids, mask))
                per_layer.append(ys)
            if cfg.moe is not None:
                per_layer = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *per_layer)

        out = ModelOutput()
        aux_loss = None
        if cfg.moe is not None:
            # the auxiliary-loss weights are the paper's, set over the MEAN
            # of the layers' losses (the depth of a cut model leaves the
            # scale alone); ``stats`` rides out with the loss for the
            # registry (record_step_stats), stacked over layers
            stats = dict(per_layer)
            aux_loss = out["aux_loss"] = stats.pop("aux_loss").mean()
            out["stats"] = stats

        h = RMSNorm(cfg, name="norm")(h)
        lm_head = self.param("lm_head", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("embed", "vocab")),
            (cfg.hidden_size, cfg.padded_vocab_size), cfg.param_dtype)
        tgt = None
        if labels is not None:
            tgt = shift_labels(labels) if shift else labels
        if cfg.loss_chunk and tgt is not None:
            from .common import chunked_lm_loss

            with trace.device_span("loss_head"):
                loss = chunked_lm_loss(
                    h, lm_head.T, tgt, vocab_size=cfg.vocab_size,
                    padded_vocab_size=cfg.padded_vocab_size,
                    chunk=cfg.loss_chunk, dtype=cfg.dtype)
        else:
            with trace.device_span("loss_head"):
                logits = jnp.dot(h, lm_head.astype(cfg.dtype))
                if cfg.padded_vocab_size != cfg.vocab_size:
                    pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
                    logits = jnp.where(pad_mask, logits,
                                       jnp.finfo(logits.dtype).min)
                out["logits"] = logits
                loss = None if tgt is None else cross_entropy_loss(logits, tgt)
        if loss is not None:
            out["loss"] = loss if aux_loss is None else loss + aux_loss
        return out

    @staticmethod
    def record_step_stats(stats) -> None:
        """The engine hands back the host copy of ``out["stats"]`` of each
        finished step; the routing counters live with the MoE layer."""
        from ..parallel.moe import record_stats

        record_stats(stats)

    def dummy_inputs(self, batch_size: int = 2, seq_len: Optional[int] = None):
        S = seq_len or min(self.cfg.max_position_embeddings, 128)
        ids = jnp.zeros((batch_size, S), jnp.int32)
        return {"input_ids": ids, "labels": ids}

    def flops_per_token(self) -> float:
        cfg = self.cfg
        E, L = cfg.hidden_size, cfg.num_hidden_layers
        D = cfg.head_dim
        # a sparse FFN multiplies by top_k of its experts, and its router
        ffn = 3 * E * cfg.intermediate_size
        if cfg.moe is not None:
            ffn = ffn * cfg.moe.top_k + E * cfg.moe.num_experts
        n = (2 * cfg.padded_vocab_size * E
             + L * (E * E + 2 * E * cfg.kv_heads * D + E * E + ffn))
        return 6.0 * n + 12 * L * E * cfg.max_position_embeddings
