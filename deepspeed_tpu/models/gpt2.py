"""GPT-2 model family, TPU-native.

This is the flagship training model (BASELINE.json configs #1/#2/#5:
GPT-2-125M DP smoke, GPT-2-1.5B ZeRO-2/3, GPT-2-XL 3D).  The reference has
no model zoo for training — users bring torch models and DeepSpeed injects
kernels (``module_inject/replace_policy.py:284`` ``HFGPT2LayerPolicy``
records the q/k/v/mlp layout used here).  TPU-native, the model IS the
integration point: parameters carry logical axis names (see
``models/common.py``) so TP/FSDP fall out of a rules table, layers can be
``nn.scan``-stacked (one compiled block, O(1) compile time in depth), and
activation checkpointing is a ``jax.checkpoint`` policy on the block.

Architecture parity: GPT-2 (pre-LN, gelu_new ≈ tanh-gelu, learned absolute
positions, tied LM head, residual init scaled 1/√(2·n_layer)).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import dot_product_attention, on_tpu
from ..telemetry import trace
from ..utils import compat as _compat
from .common import ModelOutput, cross_entropy_loss, resolve_remat_policy, shift_labels


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    # decode KV-cache length override: serving with a short
    # generation limit must not pay full-context cache traffic
    # every tick (the cache, not the weights, dominated decode
    # bandwidth at 760M/1024-ctx).  None: the position field.
    cache_len: Optional[int] = None
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    embd_pdrop: float = 0.0
    attn_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16          # compute dtype
    param_dtype: Any = jnp.float32     # storage dtype (master copy lives fp32)
    scan_layers: bool = True           # nn.scan over blocks (fast compile)
    remat: bool = False                # activation checkpointing per block
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "auto"            # auto | jnp | flash | ring
    fused_mlp: bool = False            # opt-in Pallas FFN kernel: measured
                                       # SLOWER e2e than XLA's scheduling on
                                       # the bench chip once attention is
                                       # tuned (XLA overlaps the unfused
                                       # pair; the opaque kernel is a
                                       # scheduling barrier)
    vocab_pad_multiple: int = 128      # MXU/TP-friendly vocab padding
    decode: bool = False               # KV-cache autoregressive mode
    # flash-kernel tiling (autotuner search space; None = kernel
    # default, see ops/pallas/flash_attention.py)
    flash_block: Optional[tuple] = None          # (block_q, block_k)
    # Mixture-of-Experts FFN (reference deepspeed/moe usage: MoE replaces
    # the MLP).  With scan_layers the stack is homogeneous, so MoE applies
    # to EVERY block (use use_residual=True for the PR-MoE dense+MoE mix).
    moe: Optional[Any] = None          # parallel.moe.MoEConfig
    # weight-only int8 serving (ops/w8.py): dense kernels stored as int8
    # codes + grouped fp32 scales, consumed by a dequant-fused matmul
    # (reference pt_binding.cpp:622 int8 GEMMs).  Set by init_inference.
    w8: bool = False
    w8_group: int = 128
    # fused decode-tick megakernels (ops/pallas/decode_layer.py): the
    # per-layer decode chain collapses to LN->QKV and o-proj->LN->MLP
    # Pallas launches around decode_attention; DS_TPU_DECODE_FUSED
    # env-overrides.  None = ON on TPU hardware (flipped after the
    # round-8 e2e sweep), OFF elsewhere (the CPU interpreter runs the
    # same kernels orders of magnitude slower — tests opt in with True).
    decode_fused: Optional[bool] = None
    # chunked tied-head loss (common.chunked_lm_loss): token rows per
    # chunk; None = dense logits.  Saves the (B,S,V) fp32 logits+cotangent
    # at large micro sizes; the model output then carries no "logits".
    # Each chunk's logits are multiplied out once: the loss's forward rule
    # makes dh and dW from them and its backward scales the two (see
    # models/common.py _fused_ce), so there is neither a second product
    # nor O(N·V) of saved logits to choose between.
    loss_chunk: Optional[int] = None

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def head_dim(self) -> int:
        assert self.n_embd % self.n_head == 0
        return self.n_embd // self.n_head


# Model sizes from the GPT-2/GPT-3 papers; XL(1.5B) is the north-star model.
PRESETS = {
    "gpt2-tiny": dict(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=2),
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-350m": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-760m": dict(n_embd=1536, n_layer=24, n_head=16),
    "gpt2-1.5b": dict(n_embd=1600, n_layer=48, n_head=25),
}
PRESETS["gpt2-xl"] = PRESETS["gpt2-1.5b"]


def gpt2_config(preset: str = "gpt2-125m", **overrides) -> GPT2Config:
    if preset not in PRESETS:
        raise ValueError(f"unknown GPT-2 preset {preset!r}; valid: {sorted(PRESETS)}")
    return GPT2Config(**{**PRESETS[preset], **overrides})


def _dense_params(in_features, features, names, *, cfg: GPT2Config, name: str,
                  module: nn.Module, init_std: Optional[float] = None,
                  use_bias: bool = True):
    """Create an annotated (kernel, bias) pair — the single source of truth
    for dense-layer naming/partitioning/init, shared by the XLA and fused
    dispatch paths (checkpoint + HF-policy name compatibility)."""
    std = cfg.initializer_range if init_std is None else init_std
    kernel = module.param(
        name + "_kernel",
        nn.with_partitioning(nn.initializers.normal(std), names),
        (in_features, features), cfg.param_dtype)
    bias = None
    if use_bias:
        bias = module.param(name + "_bias",
                            nn.with_partitioning(nn.initializers.zeros, (names[-1],)),
                            (features,), cfg.param_dtype)
    return kernel, bias


def _dense(x, features, names, *, cfg: GPT2Config, name: str, module: nn.Module,
           init_std: Optional[float] = None, use_bias: bool = True):
    """Annotated dense layer: kernel gets logical axis names ``names``."""
    if cfg.w8:
        # int8 codes + grouped scales declared IN PLACE of the fp kernel
        # (ops/w8.py W8A16 path); names line up with what
        # quantize_dense_tree emits from a trained checkpoint
        from ..ops.w8 import declare_w8_dense, w8a16_matmul

        codes, scale = declare_w8_dense(module, name, names, x.shape[-1],
                                        features, cfg.w8_group)
        y = w8a16_matmul(x, codes, scale)
        bias = module.param(
            name + "_bias",
            nn.with_partitioning(nn.initializers.zeros, (names[-1],)),
            (features,), cfg.param_dtype) if use_bias else None
    else:
        kernel, bias = _dense_params(
            x.shape[-1], features, names, cfg=cfg, name=name, module=module,
            init_std=init_std, use_bias=use_bias)
        y = jnp.dot(x, kernel.astype(cfg.dtype))
    if bias is not None:
        y = y + bias.astype(cfg.dtype)
    return y


class LayerNorm(nn.Module):
    """fp32 layernorm with annotated scale/bias (reference fuses this in
    ``csrc/transformer/normalize_kernels.cu``; XLA fuses it for us).
    ``params_only=True`` declares and returns (scale, bias) without
    normalizing — the fused decode path folds the norm into its Pallas
    kernel but must keep this module's param names/shapes."""

    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, params_only: bool = False):
        scale = self.param("scale", nn.with_partitioning(nn.initializers.ones, ("embed",)),
                           (x.shape[-1],), self.cfg.param_dtype)
        bias = self.param("bias", nn.with_partitioning(nn.initializers.zeros, ("embed",)),
                          (x.shape[-1],), self.cfg.param_dtype)
        if params_only:
            return scale, bias
        from .common import layer_norm

        return layer_norm(x, scale, bias, self.cfg.layer_norm_epsilon)


class SelfAttention(nn.Module):
    cfg: GPT2Config

    def _cache_append(self, k, v):
        from .common import append_kv_cache

        cfg = self.cfg
        return append_kv_cache(self, k, v,
                               cfg.cache_len or cfg.n_positions, cfg.dtype)

    def _fused_decode(self, x, attn_mask, fused_ln):
        """Megakernel decode prologue: LN folded into the QKV projection
        kernel (``x`` is the RAW residual stream).  Returns the
        PRE-o-proj head mix plus the declared o-proj params — the o-proj
        runs inside the fused post-attention kernel at the Block level."""
        cfg = self.cfg
        B, S, E = x.shape
        H, D = cfg.n_head, cfg.head_dim
        ns, nb, interp = fused_ln
        from .common import declare_fused_proj, fused_decode_qkv

        w, b = declare_fused_proj(self, cfg, "c_attn", ("embed", "qkv"),
                                  E, 3 * E, bias=True)
        qkv = fused_decode_qkv(x, ns, nb, w, b, rms=False,
                               eps=cfg.layer_norm_epsilon,
                               interpret=interp)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        kc, vc, cur = self._cache_append(k.reshape(B, S, H, D),
                                         v.reshape(B, S, H, D))
        from ..ops.attention import cached_decode_attention

        y = cached_decode_attention(q.reshape(B, S, H, D), kc, vc, cur,
                                    attn_mask)
        y = y.reshape(B, S, E)
        proj_std = cfg.initializer_range / (2 * cfg.n_layer) ** 0.5
        wo, bo = declare_fused_proj(self, cfg, "c_proj",
                                    ("heads", "embed"), E, E,
                                    init_std=proj_std, bias=True)
        return y, (wo, bo)

    @nn.compact
    def __call__(self, x, attn_mask, deterministic: bool, fused_ln=None):
        cfg = self.cfg
        B, S, E = x.shape
        H, D = cfg.n_head, cfg.head_dim
        if fused_ln is not None:
            return self._fused_decode(x, attn_mask, fused_ln)
        qkv = _dense(x, 3 * E, ("embed", "qkv"), cfg=cfg, name="c_attn", module=self)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, H, D)
        k = k.reshape(B, S, H, D)
        v = v.reshape(B, S, H, D)
        if cfg.decode:
            kc, vc, cur = self._cache_append(k, v)
            # fused-or-fallback dispatch shared by all decoder families
            # (the softmax_context analog, ops/pallas/decode_attention.py)
            from ..ops.attention import cached_decode_attention

            y = cached_decode_attention(q, kc, vc, cur, attn_mask)
            y = y.reshape(B, S, E)
            out = _dense(y, E, ("heads", "embed"), cfg=cfg, name="c_proj", module=self,
                         init_std=cfg.initializer_range / (2 * cfg.n_layer) ** 0.5)
            return out
        dropout_rng = None
        if cfg.attn_pdrop > 0.0 and not deterministic:
            dropout_rng = self.make_rng("dropout")
        flash_opts = {}
        if cfg.flash_block is not None:
            flash_opts["block_q"], flash_opts["block_k"] = cfg.flash_block
        y = dot_product_attention(
            q, k, v, causal=True, mask=attn_mask,
            dropout_rate=0.0 if deterministic else cfg.attn_pdrop,
            dropout_rng=dropout_rng, impl=cfg.attn_impl,
            flash_opts=flash_opts or None)
        y = y.reshape(B, S, E)
        out = _dense(y, E, ("heads", "embed"), cfg=cfg, name="c_proj", module=self,
                     init_std=cfg.initializer_range / (2 * cfg.n_layer) ** 0.5)
        if cfg.resid_pdrop > 0.0 and not deterministic:
            out = nn.Dropout(cfg.resid_pdrop)(out, deterministic=False)
        return out


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool, params_only: bool = False):
        cfg = self.cfg
        E, F = cfg.n_embd, 4 * cfg.n_embd
        proj_std = cfg.initializer_range / (2 * cfg.n_layer) ** 0.5
        if params_only:
            # declare (identically to the compute path) and hand the
            # arrays to the fused decode-tick kernel at the Block level
            from .common import declare_fused_proj

            w1, b1 = declare_fused_proj(self, cfg, "c_fc", ("embed", "mlp"),
                                        E, F, bias=True)
            w2, b2 = declare_fused_proj(self, cfg, "c_proj",
                                        ("mlp", "embed"), F, E,
                                        init_std=proj_std, bias=True)
            return w1, b1, w2, b2
        if self._use_fused():
            # single-kernel FFN: hidden tile never leaves VMEM (the
            # bandwidth hot spot — see ops/pallas/fused_mlp.py)
            from ..ops.pallas.fused_mlp import fused_mlp_spmd

            w1, b1 = _dense_params(E, F, ("embed", "mlp"), cfg=cfg,
                                   name="c_fc", module=self)
            w2, b2 = _dense_params(F, E, ("mlp", "embed"), cfg=cfg,
                                   name="c_proj", module=self,
                                   init_std=proj_std)
            y = fused_mlp_spmd(x, w1.astype(cfg.dtype), b1.astype(cfg.dtype),
                               w2.astype(cfg.dtype), b2.astype(cfg.dtype),
                               block_rows=128)
            if y is not None:
                return y
            h = nn.gelu(jnp.dot(x, w1.astype(cfg.dtype)) + b1.astype(cfg.dtype),
                        approximate=True)
            return jnp.dot(h, w2.astype(cfg.dtype)) + b2.astype(cfg.dtype)
        h = _dense(x, F, ("embed", "mlp"), cfg=cfg, name="c_fc", module=self)
        h = nn.gelu(h, approximate=True)  # gelu_new
        out = _dense(h, E, ("mlp", "embed"), cfg=cfg, name="c_proj", module=self,
                     init_std=proj_std)
        if cfg.resid_pdrop > 0.0 and not deterministic:
            out = nn.Dropout(cfg.resid_pdrop)(out, deterministic=False)
        return out

    def _use_fused(self) -> bool:
        cfg = self.cfg
        if not cfg.fused_mlp or cfg.resid_pdrop > 0.0 or cfg.w8 \
                or not on_tpu():
            return False
        from ..ops.pallas.fused_mlp import fits_vmem

        return fits_vmem(cfg.n_embd, 4 * cfg.n_embd, 128,
                         jnp.dtype(cfg.dtype).itemsize)


class Block(nn.Module):
    """Pre-LN transformer block; scan-compatible signature (carry, bcast).

    ``deterministic`` is a static module attribute (not a traced input) so
    remat/scan see a fixed program.
    """

    cfg: GPT2Config
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, inputs):
        attn_mask, pld_theta = inputs if isinstance(inputs, tuple) else (inputs, None)
        cfg = self.cfg

        if cfg.decode and x.shape[1] == 1 and cfg.moe is None \
                and pld_theta is None:
            # single-token tick: try the decode-row megakernel pair
            # (common.decode_fused_plan mirrors decode_supported — None
            # keeps the stock XLA chain below and says why in the
            # dispatch report)
            from .common import decode_fused_plan, fused_decode_post_attn

            plan = decode_fused_plan(cfg, x.shape[0] * x.shape[1],
                                     cfg.n_embd, (3 * cfg.n_embd,),
                                     4 * cfg.n_embd)
            if plan is not None:
                interp = plan["interpret"]
                ns1, nb1 = LayerNorm(cfg, name="ln_1")(x, params_only=True)
                y, (wo, bo) = SelfAttention(cfg, name="attn")(
                    x, attn_mask, True, fused_ln=(ns1, nb1, interp))
                ns2, nb2 = LayerNorm(cfg, name="ln_2")(x, params_only=True)
                mlp_w = MLP(cfg, name="mlp")(x, True, params_only=True)
                x = fused_decode_post_attn(
                    y, x, wo, bo, ns2, nb2, mlp_w, rms=False,
                    eps=cfg.layer_norm_epsilon, exact_gelu=False,
                    parallel_residual=False, interpret=interp)
                return x, jnp.zeros((), jnp.float32)

        def survive(branch):
            # stochastic depth (PLD, reference progressive_layer_drop.py):
            # keep residual branch with prob theta, rescale to keep E[x]
            if pld_theta is None or self.deterministic:
                return branch
            keep = jax.random.bernoulli(self.make_rng("pld"), pld_theta)
            scaled = branch / pld_theta.astype(branch.dtype)
            return jnp.where(keep, scaled, jnp.zeros_like(branch))

        x = x + survive(SelfAttention(self.cfg, name="attn")(
            LayerNorm(self.cfg, name="ln_1")(x), attn_mask, self.deterministic))
        h = LayerNorm(self.cfg, name="ln_2")(x)
        if self.cfg.moe is not None:
            from ..parallel.moe import MoELayer

            ff, aux = MoELayer(self.cfg.moe, model_dim=self.cfg.n_embd,
                               hidden_dim=4 * self.cfg.n_embd,
                               dtype=self.cfg.dtype, w8=self.cfg.w8,
                               w8_group=self.cfg.w8_group, name="moe")(
                h, train=not self.deterministic)
            x = x + survive(ff)
            return x, aux
        x = x + survive(MLP(self.cfg, name="mlp")(h, self.deterministic))
        return x, jnp.zeros((), jnp.float32)


class GPT2LMHeadModel(nn.Module):
    """Causal-LM GPT-2 with tied embeddings.

    ``__call__(input_ids, labels=None, ...)`` returns a :class:`ModelOutput`
    with ``logits`` (+ ``loss`` when labels given).  When ``labels`` is the
    input shifted by the caller, pass it; otherwise pass
    ``labels=input_ids`` and set ``shift=True`` (default) to compute
    next-token loss.
    """

    cfg: GPT2Config

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 labels=None, deterministic: bool = True, shift: bool = True,
                 layer_drop_theta=None):
        cfg = self.cfg
        B, S = input_ids.shape

        wte = self.param("wte", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")),
            (cfg.padded_vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("pos", "embed")),
            (cfg.n_positions, cfg.n_embd), cfg.param_dtype)

        if position_ids is None:
            if cfg.decode:
                raise ValueError("decode mode requires explicit position_ids "
                                 "(the inference engine tracks them)")
            position_ids = jnp.arange(S)[None, :]
        with trace.device_span("embed"):
            h = (wte.astype(cfg.dtype)[input_ids]
                 + wpe.astype(cfg.dtype)[position_ids])
        if cfg.embd_pdrop > 0.0 and not deterministic:
            h = nn.Dropout(cfg.embd_pdrop)(h, deterministic=False)

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        if cfg.scan_layers:
            block_cls = Block
            if cfg.remat:
                block_cls = nn.remat(
                    Block, policy=resolve_remat_policy(cfg.remat_policy),
                    prevent_cse=False, static_argnums=())
            stack = nn.scan(
                block_cls,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True, "gating": True,
                            "pld": True},
                length=cfg.n_layer,
                in_axes=nn.broadcast,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )
            h, layer_aux = stack(cfg, deterministic, name="h")(
                h, (mask, layer_drop_theta))
            aux_loss = layer_aux.sum()
        else:
            aux_loss = jnp.zeros((), jnp.float32)
            for i in range(cfg.n_layer):
                block_cls = Block
                if cfg.remat:
                    block_cls = nn.remat(
                        Block, policy=resolve_remat_policy(cfg.remat_policy),
                        prevent_cse=False)
                h, aux = block_cls(cfg, deterministic, name=f"h_{i}")(
                    h, (mask, layer_drop_theta))
                aux_loss = aux_loss + aux

        h = LayerNorm(cfg, name="ln_f")(h)
        if cfg.loss_chunk and labels is not None:
            # memory-bounded head: logits never fully materialize
            from .common import chunked_lm_loss

            tgt = shift_labels(labels) if shift else labels
            with trace.device_span("loss_head"):
                loss = chunked_lm_loss(
                    h, wte, tgt, vocab_size=cfg.vocab_size,
                    padded_vocab_size=cfg.padded_vocab_size,
                    chunk=cfg.loss_chunk, dtype=cfg.dtype)
            out = ModelOutput(loss=loss)
            if cfg.moe is not None:
                out["aux_loss"] = aux_loss
                out["loss"] = loss + aux_loss
            return out
        with trace.device_span("loss_head"):
            logits = jnp.dot(h, wte.astype(cfg.dtype).T)
            if cfg.padded_vocab_size != cfg.vocab_size:
                # mask padded vocab columns out of the softmax
                pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
                logits = jnp.where(pad_mask, logits,
                                   jnp.finfo(logits.dtype).min)

        out = ModelOutput(logits=logits)
        if cfg.moe is not None:
            out["aux_loss"] = aux_loss
        if labels is not None:
            tgt = shift_labels(labels) if shift else labels
            with trace.device_span("loss_head"):
                loss = cross_entropy_loss(logits, tgt)
            if cfg.moe is not None:
                loss = loss + aux_loss  # load-balancing loss (engine.py:2154 analog)
            out["loss"] = loss
        return out

    # -- pipeline decomposition (parallel/pipeline.py contract) --------
    @nn.nowrap
    def pipeline_layout(self, n_stages: int, method: str = "uniform"):
        """Layer→stage placement (reference ``pipe/module.py:363``
        ``_partition_layers``).  ``method='parameters'`` balances the
        homogeneous block weights against the embed load on stage 0 and
        the tied E×V head load on the last stage; ``type:<regex>``
        weighs layers whose type name matches."""
        from ..parallel.partition import make_layout

        cfg = self.cfg
        block_w = float(12 * cfg.n_embd ** 2 + 13 * cfg.n_embd)
        extras = [0.0] * n_stages
        extras[0] += float((cfg.padded_vocab_size + cfg.n_positions)
                           * cfg.n_embd)              # wte + wpe
        extras[-1] += float(cfg.padded_vocab_size * cfg.n_embd)  # tied head
        return make_layout(
            cfg.n_layer, n_stages, method,
            layer_weights=[block_w] * cfg.n_layer,
            layer_types=["Block"] * cfg.n_layer,
            stage_extras=extras if method == "parameters" else None)

    @nn.nowrap
    def pipeline_fns(self, n_stages: int, method: str = "uniform"):
        """Split the forward pass into (embed, stage, loss) closures.

        The stage function re-binds the same scanned ``Block`` stack over a
        ``n_layer/n_stages``-slice of the ``h`` params, so PP reuses the
        exact single-path math (no drift between PP and non-PP).

        Heterogeneous/balanced partitioning (reference pipe/module.py:363
        ``partition_layers``): n_layer need not divide n_stages, and
        ``method`` picks the placement (see :meth:`pipeline_layout`).  The
        stack is zero-PADDED to ``local·n_stages`` slots — a zero-weight
        pre-LN block is an exact identity (both residual branches end in
        a zero-weight projection, so forward adds 0 and the cotangent
        through the branch is 0).  ``split_params`` pads+places a
        canonical stack (idempotent: an already-stored stack passes
        through) and ``merge_params`` inverts it; the engine stores the
        stack in placed order so neither costs anything per step.  With a
        non-trivial placement the stage executor cond-gates each slot on
        its real-layer count, so a stage whose slack is pad slots SKIPS
        that compute at run time (the balancing actually lands).
        """
        cfg = self.cfg
        if not cfg.scan_layers:
            raise ValueError("pipeline parallelism requires scan_layers=True")
        if cfg.moe is not None:
            raise NotImplementedError(
                "MoE + pipeline parallelism: the aux loss does not flow "
                "through the pipeline loop yet; use ep with dp/fsdp/tp")
        layout = self.pipeline_layout(n_stages, method)
        local_layers = layout.local_layers
        padded_layers = layout.padded_layers
        n_pad = padded_layers - cfg.n_layer
        trivial = layout.trivial

        stage_stack = nn.scan(
            Block,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            length=local_layers,
            in_axes=nn.broadcast,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )(cfg, True)
        ln_f = LayerNorm(cfg)

        def split_params(params):
            shared = {k: v for k, v in params.items() if k != "h"}
            stage = params["h"]
            shape = np.shape(jax.tree_util.tree_leaves(stage)[0])
            lead = shape[0] if shape else None
            if lead == cfg.n_layer and (n_pad or not trivial):
                stage = jax.tree_util.tree_map(layout.place, stage)
            return shared, stage

        def merge_params(shared, stage, keep_layout: bool = False):
            shape = np.shape(jax.tree_util.tree_leaves(stage)[0])
            lead = shape[0] if shape else None
            if not keep_layout and lead == padded_layers != cfg.n_layer:
                stage = jax.tree_util.tree_map(layout.unplace, stage)
            return {**shared, "h": stage}

        def embed_fn(shared, mb):
            ids = mb["input_ids"]
            S = ids.shape[1]
            pos = jnp.arange(S)[None, :]
            return (shared["wte"].astype(cfg.dtype)[ids]
                    + shared["wpe"].astype(cfg.dtype)[pos])

        if trivial:
            def stage_fn(stage_params, h):
                h, _ = stage_stack.apply({"params": stage_params}, h, None)
                return h
        else:
            # placed layout: cond-gate each local slot on this stage's
            # real-layer count so pad slots SKIP their compute at run
            # time (lax.cond executes one branch; reverse-differentiable,
            # unlike a dynamic-bound fori_loop).  Must run under the
            # manual ``pp`` shard_map (the pipeline loops' contract).
            block = Block(cfg, True)
            counts = tuple(layout.stage_counts())

            def stage_fn(stage_params, h, chunk_slot=None):
                sid = jax.lax.axis_index("pp")
                g = sid if chunk_slot is None \
                    else chunk_slot * _compat.axis_size("pp") + sid
                n_real = jnp.asarray(counts, jnp.int32)[g]

                def body(carry, xs):
                    v, params_v = xs

                    def run():
                        out, _ = block.apply({"params": params_v}, carry,
                                             None)
                        return out

                    return jax.lax.cond(v < n_real, run, lambda: carry), None

                h, _ = jax.lax.scan(
                    body, h, (jnp.arange(local_layers), stage_params))
                return h

            stage_fn.takes_slot = True

        def loss_fn(shared, h, mb):
            h = ln_f.apply({"params": shared["ln_f"]}, h)
            logits = jnp.dot(h, shared["wte"].astype(cfg.dtype).T)
            if cfg.padded_vocab_size != cfg.vocab_size:
                pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
                logits = jnp.where(pad_mask, logits, jnp.finfo(logits.dtype).min)
            return cross_entropy_loss(logits, shift_labels(mb["labels"]))

        return embed_fn, stage_fn, loss_fn, split_params, merge_params

    # -- engine integration hooks ------------------------------------
    def dummy_inputs(self, batch_size: int = 2, seq_len: Optional[int] = None):
        S = seq_len or min(self.cfg.n_positions, 128)
        ids = jnp.zeros((batch_size, S), jnp.int32)
        return {"input_ids": ids, "labels": ids}

    def flops_per_token(self) -> float:
        """6·N_params + attention flops, for MFU accounting."""
        cfg = self.cfg
        n_params = (cfg.padded_vocab_size * cfg.n_embd
                    + cfg.n_positions * cfg.n_embd
                    + cfg.n_layer * (12 * cfg.n_embd ** 2 + 13 * cfg.n_embd)
                    + 2 * cfg.n_embd)
        attn = 12 * cfg.n_layer * cfg.n_embd * cfg.n_positions
        return 6.0 * n_params + attn
