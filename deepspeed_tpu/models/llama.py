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

Mellum 2 (JetBrains, 2026; ``model_type: mellum``) is it with more fields:
``head_dim`` apart from ``hidden_size / heads``, ``layer_types`` that mix
``sliding_attention`` (the last ``sliding_window`` keys) and
``full_attention`` blocks in one stack, ``rope_parameters`` with a rotary
table a layer type (YaRN on the full layers), and experts of
``moe_intermediate_size``, of which this instance may hold a contiguous
share (``MoEConfig.first_expert``).  Keys and values reach attention at
their own ``num_key_value_heads``.

AFMoE (Arcee Trinity, 2025; ``model_type: afmoe``) is it with more again:
``num_dense_layers`` leading blocks keep the dense SwiGLU of
``intermediate_size`` and the rest take ``moe`` (sigmoid scores, a
selection bias that the step moves and no gradient does, a shared expert:
``parallel/moe.py``), ``mup_enabled`` multiplies the embedding by
``sqrt(hidden_size)``, and four fields the family's released code has and
its config has no key for: ``qk_norm="head"`` (an RMSNorm over each head's
channels), ``attn_gate`` (the attention output times the sigmoid of a
fourth projection, before ``o_proj``), ``rope_layer_types`` (the layer
types that rotate q and k: the others carry no position at all) and
``sandwich_norm`` (a norm after each branch as well as before it).

The DeepSeek-V3 family (JoyAI-LLM-Flash, 2026; ``model_type:
joyai_llm_flash``) is it with two more modules.  **Latent attention**
(``kv_lora_rank`` and its five sister fields, under their ``config.json``
names): queries and keys-and-values are projected down to a latent,
normalised and projected up again; each head has ``qk_nope_head_dim``
channels of its own and ``qk_rope_head_dim`` rotated ones, the rotated KEY
is one for all heads, and values are ``v_head_dim`` wide
(:class:`LlamaLatentAttention`; the score is a sum of two products,
``ops/attention.py``).  **Multi-token prediction**
(``num_nextn_predict_layers``): after the stack, a projection of [the next
token's embedding ; the stack's output], one more whole block and a norm
predict the token two ahead through the model's own table and head; the
loss is ``lm_loss + mtp_loss_weight * mtp_loss`` (:class:`MTPModule`,
arXiv:2412.19437 section 2.2).

SDAR (JetLM, 2025; ``model_type: sdar_moe``, arXiv:2510.06303) is the
sparse block with ``qk_norm="head"`` under another OBJECTIVE: block
diffusion (arXiv:2503.09573), the ``diffusion`` section
(:class:`BlockDiffusionConfig`).  With it and ``labels`` the model draws a
noise level a block of ``block_length`` tokens, replaces each token by the
mask id with that probability, runs the stack ONCE over ``[noisy ; clean]``
(``2L`` positions, both halves at positions ``0 .. L-1``) under the mask
of ``ops/attention.py block_diffusion_mask``, and takes a cross-entropy
weighted by ``1 / t`` from the masked positions of the noisy half, read at
their own position (no shift); the clean half has no loss and exists to
give keys and values.

LFM2 (Liquid AI, 2025; ``model_type: lfm2_moe``) is the first stack whose
layers are not all attention: ``layer_types`` holds ``"conv"`` where the
token mixer is the family's double-gated short convolution
(:class:`ShortConv`, ``ops/short_conv.py``: one projection to three
thirds, a causal depthwise filter of ``conv_L_cache`` taps between two
elementwise gates, one projection back) and ``"full_attention"`` where it
is grouped-query attention with ``qk_norm="head"``.  Everything after the
mixer is the block that stands.  A window, a rotary table, the per-head
norm and the output gate are asked of the attention layers alone; a conv
layer has no positional encoding and no cache leaf yet, so ``decode=True``
with one raises.  ``tie_word_embeddings`` makes the head read
``embed_tokens`` (no ``lm_head`` leaf; the table's gradient is the sum of
both uses).

Qwen3-Next (Qwen, 2025; ``model_type: qwen3_next``) is the first stack
with a state carried ALONG the sequence: ``layer_types`` holds
``"linear_attention"`` where the token mixer is a Gated DeltaNet
(:class:`GatedDeltaNet`: one projection to ``[q | k | v | z]`` and one to
``[b | a]``, a causal depthwise filter of ``linear_conv_kernel_dim`` taps
with SiLU over ``[q | k | v]``, the gated delta rule of
``ops/gated_delta.py`` over ``linear_num_value_heads`` states of
``linear_key_head_dim x linear_value_head_dim``, a per-head RMSNorm gated
by ``silu(z)``, one projection back) and ``"full_attention"`` where it is
grouped-query attention with ``qk_norm="head"``, ``attn_gate`` (the
source writes q and the gate as ONE projection of ``2 H D`` columns split a
head; here they are the two leaves ``q_proj`` and ``gate_proj``, a column
permutation a loader makes) and ``partial_rotary_factor``: the rotation
turns the first ``head_dim * factor`` channels of a head and the rest
pass.  ``norm_zero_centered`` makes every RMSNorm multiply by ``1 + w``
with ``w`` from zeros (the gated norm inside the DeltaNet layer alone
keeps ``w`` from ones).  ``MoEConfig.shared_expert_gate`` is the family's
scalar sigmoid gate on the shared expert.  No cache leaf holds the state
or the filter's tail yet: ``decode=True`` with such a layer raises.

Olmo-Hybrid (Ai2, 2026; ``model_type: olmo_hybrid``) is that stack with no
experts at all (``moe=None`` beside ``layer_types``: every block keeps the
dense SwiGLU of ``intermediate_size``) under the OLMo 2 / 3 family's
**reordered norm** (``reordered_norm``: ``x + Norm(mixer(x))``, ``x +
Norm(FFN(x))``, nothing before a branch; arXiv:2501.00656).  Its Gated
DeltaNet is fla's at ``expand_v`` 2: ``linear_key_head_dim`` 96 and
``linear_value_head_dim`` 192, a state of 96 x 192 a head, and
``linear_allow_neg_eigval`` (``beta = 2 sigmoid(b)``, so the transition ``I
- beta k k^T`` has the eigenvalue ``1 - beta`` in (-1, 1)); no output gate
on attention, ``qk_norm=True`` (OLMoE's, over the whole projection) and
``rope_layer_types=()``: no layer carries a position.

Keye-VL-2.0 (Kwai-Keye, 2026; ``model_type: KeyeVL2``; the language model
alone) is the sparse block with ``qk_norm="head"`` and, on every attention
layer, a **learned sparse selection** (``sa_config``,
:class:`SparseAttentionConfig`: DeepSeek-Sparse-Attention over grouped
queries).  An :class:`Indexer` beside q, k and v reads the layer's
normalised input under a stop-gradient, scores every causal key of a query
(``indexer_num_heads`` heads of ``indexer_head_dim`` channels against ONE
key head, a learned weight a head, ReLU between) and attention keeps the
``topk`` best (``ops/indexed_attention.py``).  The indexer learns from a
loss of its own, ``KL(mean-over-heads attention probabilities || softmax of
its scores)`` over the kept keys, which joins the model's loss under
``indexer_loss_weight`` summed over the layers; the selection passes no
gradient.  No cache leaf holds the indexer's keys: ``decode=True`` raises.

Ling 3.0 (inclusionAI, 2026; ``model_type: bailing_hybrid``) is the first
stack in which latent attention stands BESIDE linear-state layers:
``layer_types`` holds ``"kda_attention"`` where the token mixer is Kimi
Delta Attention (:class:`KimiDeltaAttention`, arXiv:2510.26692: the delta
rule under a log-decay a KEY CHANNEL, ``ops/gated_delta.py`` with ``g`` of
``(B, S, H, dk)``; ``num_attention_heads`` states of ``head_dim x
head_dim``, a filter of ``short_conv_kernel_size`` taps, the decay's gate
bounded below by ``kda_lower_bound`` under ``kda_safe_gate``) and
``"full_attention"`` where it is :class:`LlamaLatentAttention`, here
without a query latent (``q_lora_rank=None``: one projection to ``[q_nope |
q_rope]``) and with ``attn_gate="head"`` (the output of each head times the
sigmoid of ONE scalar a head, a projection of ``num_attention_heads``
columns, before ``o_proj``).  ``partial_rotary_factor`` there only restates
``qk_rope_head_dim / head_dim``.  The experts route under a group limit
(``MoEConfig.n_group`` / ``topk_group``, ``parallel/moe.py``); the
prediction block's mixer is latent attention whatever the stack ends on.
``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list`` name a
clamp whose form the config does not give: a non-zero entry within the
depth built raises.  ``decode=True`` raises with any of it.

**The Xing 4.0 family** (``Xing4.0-29B-A4B``) is the DeepSeek-V3 block above
on a residual stream of ``hc_mult`` lanes (manifold-constrained
hyper-connections, arXiv:2512.24880; :class:`HyperConnection`,
``ops/hyper_connection.py``): the table's row is copied into every lane,
each sublayer reads ``H_pre @ X`` and the stream becomes ``H_res @ X +
H_post^T y`` under per-token maps (``H_res`` made doubly stochastic by
``hc_sinkhorn_iters`` Sinkhorn sweeps), the lanes are summed before the
final norm, and the prediction block widens and sums its own.  The stream
is carried flat, ``(B, S, hc_mult * E)``.  Its latent attention turns its
rope channels by the YaRN table of ``rope_scaling`` and scales the softmax
by ``mscale^2`` (:attr:`LlamaConfig.latent_rotary`).  ``decode=True``,
``scan_layers``, ``sa_config``, ``diffusion`` and sequence parallelism raise
with more than one lane.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import dot_product_attention
from ..ops.rotary import apply_rotary_pos_emb, rotate_rows, rows_plan
from ..telemetry import trace
from .common import ModelOutput, cross_entropy_loss, resolve_remat_policy, shift_labels


SLIDING, FULL_ATTENTION = "sliding_attention", "full_attention"
CONV = "conv"       # a layer whose token mixer is ShortConv, not attention
LINEAR = "linear_attention"     # ... is GatedDeltaNet
KDA = "kda_attention"           # ... is KimiDeltaAttention
MIXERS = (CONV, LINEAR, KDA)    # the layer types that are no attention
# the widths latent attention takes together (their config.json names);
# ``q_lora_rank`` beside them is None where the queries have no latent
_MLA_WIDTHS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """The objective of block-diffusion training (arXiv:2503.09573, which
    the SDAR family adapts an autoregressive model to).  A row of L tokens
    is cut into blocks of ``block_length``; a block draws ``t ~ U(t_min,
    1]`` and each of its tokens becomes ``mask_token_id`` with probability
    ``t``; the loss is ``sum_i 1[masked_i] w(t_b(i)) nll_i / (B L)`` with
    ``w = 1 / t`` (``loss_weight="inv_t"``, the linear schedule's) or 1
    (``"one"``)."""
    block_length: int = 4
    mask_token_id: int = 0
    t_min: float = 1e-3
    loss_weight: str = "inv_t"


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """``sa_config`` under its ``config.json`` names: an indexer of
    ``indexer_num_heads`` heads of ``indexer_head_dim`` channels on
    ``indexer_num_kv_heads`` (1) key heads picks ``topk`` causal keys a
    query.  The source's ``q_chunk_size`` and ``kv_chunk_size`` are its
    tiling of that computation: no result depends on them, the kernels tile
    by their own blocks, and a dict that carries them loads without them."""
    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    indexer_num_kv_heads: int = 1
    topk: int = 2048


# what an indexed attention layer hands up beside its output, a layer
_INDEXER_STATS = ("indexer_loss", "indexer_kept_share",
                  "indexer_live_tile_share")
# what a block under hyper-connections hands up of its two sublayers' maps
# (ops/hyper_connection.py gauges)
_MHC_STATS = ("mhc_res_marginal_err", "mhc_res_offdiag", "mhc_pre_mean",
              "mhc_post_mean")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Fields under their Hugging Face ``config.json`` names are what
    ``benchmark/drivers/train_lm.py`` passes through from a configuration
    file as they stand: ``vocab_size``, ``max_position_embeddings``,
    ``hidden_size``, ``num_hidden_layers``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
    ``moe_intermediate_size``, ``rms_norm_eps``, ``rope_theta``,
    ``rope_parameters``, ``sliding_window``, ``layer_types``,
    ``initializer_range``, ``num_dense_layers``, ``mup_enabled``,
    ``conv_L_cache``, ``conv_bias``, ``tie_word_embeddings``,
    ``linear_num_key_heads``, ``linear_num_value_heads``,
    ``linear_key_head_dim``, ``linear_value_head_dim``,
    ``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``,
    ``partial_rotary_factor``, ``sa_config``, ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``rope_interleave``, ``num_nextn_predict_layers``,
    ``kda_lower_bound``, ``kda_safe_gate``, ``short_conv_kernel_size``,
    ``expert_swiglu_limit_list``, ``share_expert_swiglu_limit_list``,
    ``rope_scaling``, ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``, ``total_ut_steps``.
    The rest are this program's own."""
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
    # None → hidden_size // num_attention_heads (resolved at construction)
    head_dim: Optional[int] = None
    intermediate_size: int = 5632
    # width of one expert of ``moe``; None → intermediate_size
    moe_intermediate_size: Optional[int] = None
    # one entry a layer (more are ignored: a model cut in depth keeps its
    # source's list), "sliding_attention" | "full_attention" | "conv" (a
    # short-convolution mixer in attention's place) | "linear_attention" (a
    # Gated DeltaNet there) | "kda_attention" (Kimi Delta Attention there);
    # None → all full
    layer_types: Optional[tuple] = None
    # a "linear_attention" layer: key heads (q and k), value heads (v, the
    # states, the output; a multiple of the key heads), the channels of
    # each (a state is key x value channels), the taps of the filter over
    # [q | k | v] (the last is the current position)
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # beta = 2 sigmoid(b) instead of sigmoid(b): the state's transition
    # I - beta k k^T may flip a direction (eigenvalue 1 - beta in (-1, 1))
    linear_allow_neg_eigval: bool = False
    # positions the delta rule solves together (ops/gated_delta.py), of a
    # "linear_attention" and of a "kda_attention" layer
    linear_chunk_size: int = 64
    # a "kda_attention" layer (num_attention_heads states of head_dim x
    # head_dim): the taps of its three filters, and the decay's gate, a
    # log-decay a key channel: kda_lower_bound * sigmoid(exp(A_log) (h W_f +
    # dt_bias)) in (kda_lower_bound, 0) under kda_safe_gate, else Gated
    # DeltaNet's -exp(A_log) softplus(.), unbounded below (the chunked rule
    # forms its decays 16 positions at a time and overflows float32 below
    # -5.5 a position: ops/gated_delta.py)
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    # the share of a head's channels, from the first on, that the rotation
    # turns; the rest pass
    partial_rotary_factor: float = 1.0
    # every RMSNorm multiplies by 1 + w, w from zeros (the gated norm of a
    # "linear_attention" layer alone keeps w from ones)
    norm_zero_centered: bool = False
    # taps of a "conv" layer's causal depthwise filter (the family's name:
    # the positions its serving cache would hold); the last is the current
    # position
    conv_L_cache: int = 3
    # a bias on the conv layer's two projections and its filter: not written
    conv_bias: bool = False
    # the head reads ``embed_tokens``: no ``lm_head`` leaf
    tie_word_embeddings: bool = False
    # keys a "sliding_attention" layer keeps: 0 <= q_pos - k_pos < window
    sliding_window: Optional[int] = None
    # {layer type: {rope_type, rope_theta, factor, ...}} (ops/rotary.py
    # rotary_table's arguments), or one such dict for every layer;
    # None → rope_theta alone
    rope_parameters: Optional[Any] = None
    # the DeepSeek-V2/V3 family's scaling entry, read by LATENT attention
    # alone ({"type": "yarn", factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim}: :attr:`latent_rotary`);
    # None, or type "default", rotates by rope_theta alone.  Grouped-query
    # attention reads ``rope_parameters``
    rope_scaling: Optional[Any] = None
    # manifold-constrained hyper-connections (ops/hyper_connection.py): the
    # residual stream has ``hc_mult`` lanes, every sublayer reads a learned
    # mix of them and writes back through a learned map beside a lane-to-
    # lane map that ``hc_sinkhorn_iters`` sweeps (``hc_eps`` in their
    # denominators) make doubly stochastic, its logits clamped first.  None
    # or 1: one lane, ``x + f(x)``, and nothing of this is traced
    hc_mult: Optional[int] = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # a looped stack (the Ouro family's key): the SAME blocks and the same
    # final norm are applied ``total_ut_steps`` times over the same leaves,
    # the normed stream of a pass feeding the next; after every pass a gate
    # (``exit_gate``, hidden_size -> 1 with a bias) and the one head.  With
    # labels the loss is the expectation of the passes' cross-entropies under
    # the gates' exit distribution less ``exit_entropy_weight`` times that
    # distribution's entropy (this program's name: the config has no key).
    # 1: one pass, no gate, and nothing of this is traced
    total_ut_steps: int = 1
    exit_entropy_weight: float = 0.05
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    # False lets XLA merge a block's recomputation with its forward pass
    # wherever memory allows (it then keeps what it would recompute); True
    # fences the two apart, for a stack whose kept values do not fit
    remat_prevent_cse: bool = False
    attn_impl: str = "auto"
    vocab_pad_multiple: int = 128
    # sparse FFN: a parallel.moe.MoEConfig replaces the dense SwiGLU MLP of
    # every block after the first ``num_dense_layers`` with experts of
    # width ``expert_size``; those first keep ``intermediate_size``
    moe: Optional[Any] = None
    num_dense_layers: int = 0
    # the embedding times sqrt(hidden_size) (the one muP multiplier of
    # AFMoE's forward)
    mup_enabled: bool = False
    # True: RMSNorm over the whole q / k projection, before heads and
    # rotary (OLMoE); "head": over each head's channels, one scale of
    # head_dim for q and one for k (AFMoE)
    qk_norm: Any = False
    # attention's output times sigmoid(x W_g) before o_proj: True, W_g as
    # wide as q (a gate a channel); "head", W_g of num_attention_heads
    # columns (a gate a head; written for latent attention)
    attn_gate: Any = False
    # the layer types whose q and k are rotated; None: all.  A type left
    # out attends with no positional encoding
    rope_layer_types: Optional[tuple] = None
    # x += Norm(Attn(Norm(x))); x += Norm(FFN(Norm(x))): ``post_attention_
    # norm`` then normalises the attention branch's OUTPUT and the FFN
    # reads ``pre_mlp_norm``, its output through ``post_mlp_norm``
    sandwich_norm: bool = False
    # x += Norm(mixer(x)); x += Norm(FFN(x)), nothing before a branch (the
    # OLMo 2 / 3 family's): ``post_attention_norm`` normalises the mixer's
    # OUTPUT and ``post_mlp_norm`` the FFN's; no ``input_norm`` leaf
    reordered_norm: bool = False
    # latent attention (DeepSeek-V2/V3's MLA), set by ``kv_lora_rank``:
    # c_q = Norm(x W_qa) (q_lora_rank wide), [q_nope | q_rope] = c_q W_qb;
    # [c_kv | k_rope] = x W_kva, c_kv = Norm(c_kv) (kv_lora_rank wide),
    # [k_nope | v] = c_kv W_kvb; rotary on q_rope and on k_rope, ONE key
    # of qk_rope_head_dim for all heads; scores over qk_nope_head_dim +
    # qk_rope_head_dim channels, values v_head_dim wide.  ``head_dim`` and
    # ``num_key_value_heads`` say nothing there
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # rotary over channel pairs (2i, 2i+1) instead of (i, i + d/2);
    # written for the latent attention's rope channels alone
    rope_interleave: bool = False
    # a clamp inside the routed / the shared experts' SwiGLU, one entry a
    # layer (the Ling 3.0 family's keys); the config gives its size and not
    # its form, so only zeros (no clamp) within the depth built are taken
    expert_swiglu_limit_list: Optional[tuple] = None
    share_expert_swiglu_limit_list: Optional[tuple] = None
    # multi-token prediction: this many extra blocks after the stack (1 is
    # written), each predicting one token further ahead through the
    # model's own embedding table and head; their cross-entropy joins the
    # loss under ``mtp_loss_weight`` (this program's name: the DeepSeek-V3
    # family's config has no key for it; Ling 3.0's is
    # ``mtp_loss_scaling_factor``).  At weight 0 the loss and every gradient
    # of the main model are those without the blocks and the blocks' own
    # gradients are zero: none is built, and no leaf of one is declared
    # (:attr:`mtp_blocks`)
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # block-diffusion training: a BlockDiffusionConfig, or a dict of its
    # fields (a configuration file's section); None: next-token training,
    # and nothing of the section is traced
    diffusion: Optional[Any] = None
    # a learned sparse selection on every attention layer: a
    # SparseAttentionConfig, or a dict of its fields (the source's
    # ``sa_config``); None: every causal key.  Its loss joins the model's
    # under ``indexer_loss_weight`` (this program's name), summed over layers
    sa_config: Optional[Any] = None
    indexer_loss_weight: float = 1.0
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

    def __post_init__(self):
        def frozen(x):      # json's lists and dicts, hashable
            if isinstance(x, dict):
                return tuple(sorted((k, frozen(v)) for k, v in x.items()))
            return tuple(frozen(v) for v in x) if isinstance(x, list) else x

        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_attention_heads)
        for name in ("layer_types", "rope_parameters", "rope_layer_types",
                     "rope_scaling", "sa_config", "expert_swiglu_limit_list",
                     "share_expert_swiglu_limit_list"):
            object.__setattr__(self, name, frozen(getattr(self, name)))
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm is False, True (the whole projection)"
                             f" or 'head', got {self.qk_norm!r}")
        if self.reordered_norm and self.sandwich_norm:
            raise ValueError(
                "reordered_norm (a norm after each branch alone) beside "
                "sandwich_norm (one before and one after): a block has one "
                "placement")
        if self.reordered_norm and self.decode:
            raise NotImplementedError(
                "decode=True with reordered_norm: the fused decode kernels "
                "fold a norm BEFORE each branch into its projections")
        if self.num_dense_layers and self.moe is None:
            raise ValueError("num_dense_layers counts the blocks that moe "
                             "leaves dense; there is no moe")
        for t in self.layer_types or ():
            if t not in (SLIDING, FULL_ATTENTION) + MIXERS:
                raise ValueError(f"layer_types holds {t!r}; {SLIDING!r}, "
                                 f"{FULL_ATTENTION!r}, {KDA!r}, {CONV!r} and "
                                 f"{LINEAR!r} are written")
        if self.layer_types is not None:
            if len(self.layer_types) < self.num_hidden_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers of "
                    f"{self.num_hidden_layers}")
            if SLIDING in self.kinds and not self.sliding_window:
                raise ValueError(f"{SLIDING} layers need sliding_window")
        if self.conv_bias:
            raise NotImplementedError(
                "conv_bias=True: the short convolution's projections and "
                "filter are written without a bias")
        if CONV in self.kinds:
            if self.conv_L_cache < 1:
                raise ValueError(f"conv_L_cache {self.conv_L_cache}: a conv "
                                 f"layer's filter has at least one tap")
            if self.decode:
                raise NotImplementedError(
                    "decode=True with a conv layer (layer_types): the cache "
                    "holds keys and values, and a short convolution's state "
                    "(its last conv_L_cache - 1 inputs) is no leaf of it yet")
            if self.diffusion is not None:
                raise NotImplementedError(
                    "diffusion (block-diffusion training) with a conv "
                    "layer: the filter would run across the two halves "
                    "[noisy ; clean] and no block mask is written for it")
        if LINEAR in self.kinds:
            Hk, Hv = self.linear_num_key_heads, self.linear_num_value_heads
            if Hk < 1 or Hv % Hk:
                raise ValueError(
                    f"linear_num_value_heads {Hv} is no multiple of "
                    f"linear_num_key_heads {Hk}")
            if self.linear_key_head_dim < 1 or self.linear_value_head_dim < 1:
                raise ValueError(
                    f"linear_key_head_dim {self.linear_key_head_dim} and "
                    f"linear_value_head_dim {self.linear_value_head_dim}: "
                    f"at least one channel a head")
            if self.linear_conv_kernel_dim < 1 or self.linear_chunk_size < 1:
                raise ValueError(
                    f"linear_conv_kernel_dim {self.linear_conv_kernel_dim} "
                    f"and linear_chunk_size {self.linear_chunk_size}: at "
                    f"least one tap and one position a chunk")
        if KDA in self.kinds:
            if self.short_conv_kernel_size < 1 or self.linear_chunk_size < 1:
                raise ValueError(
                    f"short_conv_kernel_size {self.short_conv_kernel_size} "
                    f"and linear_chunk_size {self.linear_chunk_size}: at "
                    f"least one tap and one position a chunk")
            if not -5.5 <= self.kda_lower_bound < 0.0:
                raise ValueError(
                    f"kda_lower_bound {self.kda_lower_bound}: a log-decay a "
                    f"position in [-5.5, 0), what the chunked rule's blocks "
                    f"of 16 positions hold in float32")
            if not self.kda_safe_gate:
                raise NotImplementedError(
                    "kda_safe_gate=False (the decay -exp(A_log) softplus(.), "
                    "unbounded below) with a kda_attention layer: the "
                    "chunked rule under a decay a key channel is written "
                    "for a bounded gate (kda_lower_bound)")
            if self.attn_impl in ("ring", "ulysses"):
                raise NotImplementedError(
                    f"a kda_attention layer under sequence parallelism "
                    f"(attn_impl {self.attn_impl!r}): the delta rule's state "
                    f"runs along a whole row, on one device")
        # what neither linear-state mixer has yet
        for kind, state in ((LINEAR, "a Gated DeltaNet's recurrent state "
                             "and its filter's tail"),
                            (KDA, "Kimi Delta Attention's recurrent state "
                             "and its filters' tails")):
            if kind not in self.kinds:
                continue
            if self.decode:
                raise NotImplementedError(
                    f"decode=True with a {kind} layer (layer_types): the "
                    f"cache holds keys and values, and {state} are no "
                    f"leaves of it yet")
            if self.diffusion is not None:
                raise NotImplementedError(
                    f"diffusion (block-diffusion training) with a {kind} "
                    f"layer: the state would run across the two halves "
                    f"[noisy ; clean] and no block mask is written for it")
            if self.scan_layers:
                raise NotImplementedError(
                    f"scan_layers=True with a {kind} layer (layer_types): "
                    f"scan_layers scans one kind of block; set "
                    f"scan_layers=False (the stack is then unrolled)")
        clamped = sorted(
            (i, name) for name in ("expert_swiglu_limit_list",
                                   "share_expert_swiglu_limit_list")
            for i, x in enumerate((getattr(self, name) or ())[
                :self.num_hidden_layers]) if x)
        if clamped:
            i, name = clamped[0]
            raise NotImplementedError(
                f"{name}[{i}] = {getattr(self, name)[i]}: a clamp inside the "
                f"experts' SwiGLU of layer {i} whose form the config does "
                f"not give; only zeros within the {self.num_hidden_layers} "
                f"layers built are taken")
        if not 0.0 < self.partial_rotary_factor <= 1.0 \
                or self.rotary_dim % 2:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim}: a share in (0, 1] that leaves "
                f"an even number of channels")
        # under latent attention the factor can only restate the rope
        # channels' share of head_dim: nothing partial is left to do
        if self.partial_rotary_factor != 1.0 and (
                self.rope_interleave if not self.kv_lora_rank
                else self.rotary_dim != self.qk_rope_head_dim):
            raise NotImplementedError(
                "partial_rotary_factor with rope_interleave, or with latent "
                "attention where it is not qk_rope_head_dim / head_dim: the "
                "partial rotation is written for half-split pairs of "
                "grouped-query attention")
        if self.mla_fields and not all(getattr(self, f) for f in _MLA_WIDTHS):
            raise ValueError(
                f"latent attention takes {', '.join(_MLA_WIDTHS)} together "
                f"(q_lora_rank beside them, or None for no query latent); "
                f"set: {', '.join(self.mla_fields)}")
        if self.mla_fields and (
                self.qk_norm or self.attn_gate is True
                or SLIDING in self.kinds or self.rope_parameters is not None
                or self.rope_layer_types is not None):
            raise NotImplementedError(
                "latent attention with qk_norm, a gate a channel "
                "(attn_gate=True; 'head' is written), a sliding window, "
                "rope_parameters or rope_layer_types: none of them is "
                "written for it")
        if self.rope_scaling is not None and not self.kv_lora_rank \
                and self._rope_scaling_type != "default":
            raise NotImplementedError(
                f"rope_scaling of type {self._rope_scaling_type!r} without "
                f"latent attention: grouped-query attention's scaled tables "
                f"are rope_parameters'")
        if self.kv_lora_rank and self._rope_scaling_type not in (
                "default", "yarn"):
            raise NotImplementedError(
                f"rope_scaling of type {self._rope_scaling_type!r}: "
                f"'default' and 'yarn' are written")
        if self.lanes > 1:
            if self.hc_sinkhorn_iters < 1 or self.hc_eps < 0.0 or not \
                    self.mhc_h_res_clamp_min < self.mhc_h_res_clamp_max:
                raise ValueError(
                    f"hc_sinkhorn_iters {self.hc_sinkhorn_iters}, hc_eps "
                    f"{self.hc_eps}, mhc_h_res_clamp_min / _max "
                    f"{self.mhc_h_res_clamp_min} / "
                    f"{self.mhc_h_res_clamp_max}: at least one sweep, a "
                    f"denominator's eps >= 0 and a clamp that leaves room")
            for on, what in (
                    (self.decode, "decode=True: the fused decode path and "
                     "the cache's blocks take a (B, 1, E) stream"),
                    (self.scan_layers, "scan_layers=True: the scanned "
                     "carry is one lane; set scan_layers=False"),
                    (self.sa_config is not None, "sa_config"),
                    (self.diffusion is not None, "diffusion (block-"
                     "diffusion training)"),
                    (self.attn_impl in ("ring", "ulysses"),
                     f"attn_impl {self.attn_impl!r} (sequence "
                     f"parallelism)"),
                    (self.sandwich_norm or self.reordered_norm,
                     "sandwich_norm / reordered_norm: a sublayer under "
                     "hyper-connections carries its own norm BEFORE it")):
                if on:
                    raise NotImplementedError(
                        f"hc_mult {self.hc_mult} (a residual stream of "
                        f"several lanes) with {what}: the lanes are written "
                        f"for the unrolled training stack alone (pipeline "
                        f"stages: this family has no pipeline_fns at all)")
        if self.total_ut_steps < 1 or self.exit_entropy_weight < 0.0:
            raise ValueError(
                f"total_ut_steps {self.total_ut_steps}, exit_entropy_weight "
                f"{self.exit_entropy_weight}: at least one pass and a weight "
                f">= 0")
        if self.total_ut_steps > 1:
            for on, what in (
                    (self.decode, "decode=True: the cache would hold a key "
                     "and a value a pass a layer, and early exit by the gate "
                     "is not written"),
                    (self.moe is not None, "moe: the routing statistics and "
                     "the selection bias's update would count a layer once a "
                     "pass"),
                    (self.mtp_blocks, "a multi-token-prediction block "
                     "(num_nextn_predict_layers)"),
                    (self.diffusion is not None, "diffusion (block-"
                     "diffusion training)"),
                    (self.sa_config is not None, "sa_config: the indexer's "
                     "loss would be gathered once a pass"),
                    (self.lanes > 1, f"hc_mult {self.hc_mult} (a residual "
                     f"stream of several lanes)"),
                    (self.attn_impl in ("ring", "ulysses"),
                     f"attn_impl {self.attn_impl!r} (sequence "
                     f"parallelism)")):
                if on:
                    raise NotImplementedError(
                        f"total_ut_steps {self.total_ut_steps} (a looped "
                        f"stack) with {what}: the loop is written for "
                        f"training a dense stack on whole rows (pipeline "
                        f"stages, where the loop is a ring: this family has "
                        f"no pipeline_fns at all)")
        if self.attn_gate not in (False, True, "head"):
            raise ValueError(f"attn_gate is False, True (a gate a channel) "
                             f"or 'head', got {self.attn_gate!r}")
        if self.attn_gate == "head" and not self.kv_lora_rank:
            raise NotImplementedError(
                "attn_gate='head' (one gate a head) without latent "
                "attention: grouped-query attention's gate is as wide as q")
        if self.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError(
                f"num_nextn_predict_layers "
                f"{self.num_nextn_predict_layers}: one multi-token-"
                f"prediction block is written")
        if isinstance(self.diffusion, dict):
            object.__setattr__(self, "diffusion",
                               BlockDiffusionConfig(**self.diffusion))
        if self.diffusion is not None:
            dif = self.diffusion
            if dif.block_length < 1 or 128 % dif.block_length:
                raise ValueError(
                    f"diffusion.block_length {dif.block_length} does not "
                    f"divide 128, the tile the attention schedule is cut in")
            if not 0 <= dif.mask_token_id < self.vocab_size:
                raise ValueError(
                    f"diffusion.mask_token_id {dif.mask_token_id} is no id "
                    f"of a vocabulary of {self.vocab_size}")
            if not 0.0 < dif.t_min <= 1.0 \
                    or dif.loss_weight not in ("inv_t", "one"):
                raise ValueError(
                    f"diffusion: t_min in (0, 1] and loss_weight 'inv_t' or "
                    f"'one', got {dif.t_min!r} and {dif.loss_weight!r}")
            if self.decode:
                raise NotImplementedError(
                    "decode=True with diffusion (block-diffusion training): "
                    "generation by denoising a block is not written; the "
                    "cache and the batcher yield one token a sequence a step")
            if self.mla_fields or self.num_nextn_predict_layers \
                    or SLIDING in self.kinds:
                raise NotImplementedError(
                    "diffusion (block-diffusion training) with latent "
                    "attention, a multi-token-prediction block or a sliding "
                    "window: the block mask is written for full grouped-"
                    "query attention alone")
        if isinstance(self.sa_config, (dict, tuple)):
            object.__setattr__(self, "sa_config", SparseAttentionConfig(**{
                k: v for k, v in dict(self.sa_config).items()
                if k not in ("q_chunk_size", "kv_chunk_size")}))
        if self.sa_config is not None:
            sa = self.sa_config
            if sa.indexer_num_kv_heads != 1 or sa.topk < 1 \
                    or sa.indexer_num_heads < 1 or sa.indexer_head_dim % 2:
                raise ValueError(
                    f"sa_config: one indexer key head, topk >= 1 and an "
                    f"even indexer_head_dim are written, got {sa}")
            if self.decode:
                raise NotImplementedError(
                    "decode=True with sa_config (a learned sparse "
                    "selection): the cache holds keys and values, and the "
                    "indexer's keys are no leaf of it yet")
            if self.mla_fields:
                raise NotImplementedError(
                    "sa_config with latent attention: the indexer is "
                    "written beside grouped-query attention alone")
            if self.sliding_window or SLIDING in self.kinds:
                raise NotImplementedError(
                    "sa_config with a sliding window: the selection is "
                    "written over every causal key")
            if self.diffusion is not None:
                raise NotImplementedError(
                    "sa_config with diffusion (block-diffusion training): "
                    "the selection is written under the causal mask alone")
            if self.attn_impl in ("ring", "ulysses"):
                raise NotImplementedError(
                    f"sa_config under sequence parallelism (attn_impl "
                    f"{self.attn_impl!r}): a query's top keys are chosen "
                    f"over its whole row, on one device")
            if any(t in MIXERS for t in self.kinds) \
                    or self.num_nextn_predict_layers:
                raise NotImplementedError(
                    "sa_config beside a conv or linear_attention layer or "
                    "a multi-token-prediction block: the indexer's loss is "
                    "gathered from a stack of attention layers alone")
        if self.decode and (self.mla_fields
                            or self.num_nextn_predict_layers):
            raise NotImplementedError(
                f"decode=True with "
                f"{', '.join(self.mla_fields + self.mtp_fields)}: the cache "
                f"holds keys and values a head, not a latent and a rope "
                f"key, and no decode path drafts with a prediction block")
        if self.decode and self.per_layer_type:
            raise NotImplementedError(
                "decode=True with a sliding window (layer_types) or a "
                "per-layer-type rotary table (rope_parameters): the cache "
                "and the decode kernels know neither, and would run full "
                "attention with one table")
        if self.decode and self.moe is not None and not self.moe.holds_all:
            raise NotImplementedError(
                "decode=True with a share of the experts "
                "(MoEConfig.routed_experts): the serving path has no "
                "partial expert sum")
        if self.decode and self.afmoe_fields:
            raise NotImplementedError(
                f"decode=True with {', '.join(self.afmoe_fields)}: the "
                f"cache, the fused decode kernels and the capacity gate "
                f"know none of them")

    @property
    def rotary_dim(self) -> int:
        """Channels of a head, from the first on, that the rotation turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def lanes(self) -> int:
        """Lanes of the residual stream (``hc_mult``; 1 without)."""
        return int(self.hc_mult or 1)

    @property
    def _rope_scaling_type(self) -> str:
        entry = dict(self.rope_scaling or ())
        return entry.get("rope_type", entry.get("type", "default"))

    @property
    def latent_rotary(self) -> tuple:
        """``(table, factor)`` of latent attention under ``rope_scaling``:
        the ``ops.rotary.RotaryTable`` its rope channels turn by (None:
        ``rope_theta`` alone) and what multiplies the softmax scale.  The
        DeepSeek-V2/V3 released code's rule: YaRN's frequencies, cos and
        sin times ``mscale(factor, mscale) / mscale(factor,
        mscale_all_dim)``, the softmax scale times ``mscale(factor,
        mscale_all_dim)^2``."""
        if self._rope_scaling_type == "default":
            return None, 1.0
        from ..ops.rotary import rotary_table, yarn_mscale

        rs = dict(self.rope_scaling)
        factor = float(rs["factor"])
        all_dim = yarn_mscale(factor, float(rs.get("mscale_all_dim", 0.0)))
        table = rotary_table(
            self.qk_rope_head_dim, "yarn", rope_theta=float(self.rope_theta),
            factor=factor, original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs.get("beta_fast", 32.0)),
            beta_slow=float(rs.get("beta_slow", 1.0)),
            attention_factor=yarn_mscale(factor, float(rs.get("mscale", 1.0)))
            / all_dim)
        return table, all_dim * all_dim

    @property
    def expert_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def kinds(self) -> tuple:
        """The layer types of the stack, one a layer; () without any."""
        return (self.layer_types or ())[:self.num_hidden_layers]

    @property
    def per_layer_type(self) -> bool:
        return bool(self.kinds) or self.rope_parameters is not None

    @property
    def mla_fields(self) -> tuple:
        """The latent attention's fields that are set, by name."""
        return tuple(f for f in ("q_lora_rank",) + _MLA_WIDTHS
                     + ("rope_interleave",) if getattr(self, f))

    @property
    def mtp_blocks(self) -> int:
        """Prediction blocks that are built: those the loss weighs."""
        return self.num_nextn_predict_layers if self.mtp_loss_weight else 0

    @property
    def mtp_fields(self) -> tuple:
        return ("num_nextn_predict_layers",) \
            if self.num_nextn_predict_layers else ()

    @property
    def afmoe_fields(self) -> tuple:
        """AFMoE's fields that are set, by name (``moe.`` for the routing's)."""
        mine = tuple(name for name, on in (
            ("num_dense_layers", self.num_dense_layers),
            ("mup_enabled", self.mup_enabled),
            ("qk_norm", self.qk_norm == "head"),
            ("attn_gate", self.attn_gate),
            ("rope_layer_types", self.rope_layer_types is not None),
            ("sandwich_norm", self.sandwich_norm)) if on)
        routing = () if self.moe is None else self.moe.afmoe_fields
        return mine + tuple("moe." + f for f in routing)

    def sparse(self, layer: int) -> bool:
        """Whether block ``layer``'s FFN is ``moe``."""
        return self.moe is not None and layer >= self.num_dense_layers

    def rotates(self, kind: Optional[str]) -> bool:
        return self.rope_layer_types is None or kind in self.rope_layer_types

    def window(self, kind: Optional[str]) -> Optional[int]:
        return self.sliding_window if kind == SLIDING else None

    def rotary(self, kind: Optional[str]):
        """The ``ops.rotary.RotaryTable`` of a layer type, made once a
        type; None where ``rope_theta`` alone says it."""
        if self.rope_parameters is None:
            return None
        from ..ops.rotary import rotary_table

        entry = dict(self.rope_parameters)
        if "rope_type" not in entry:        # keyed by layer type
            entry = dict(entry[kind or FULL_ATTENTION])
        entry.setdefault("rope_theta", self.rope_theta)
        return rotary_table(self.rotary_dim, **entry)


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
        centred = self.cfg.norm_zero_centered
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.zeros if centred else nn.initializers.ones,
            (self.axis,)), (x.shape[-1],), self.cfg.param_dtype)
        if centred:         # the multiplier reaches every reader as data
            scale = 1.0 + scale
        if params_only:
            return scale
        from .common import rms_norm

        return rms_norm(x, scale, self.cfg.rms_norm_eps)


class Indexer(nn.Module):
    """``sa_config``'s scorer (released code: DeepSeek-V3.2-Exp's
    ``Indexer``, without its Hadamard rotation, which is orthogonal and
    cancels in the product, and without its fp8, an inference detail)::

        qI = rotary(h W_q)                  # heads x channels
        kI = rotary(LayerNorm(h W_k))       # ONE key head; weight and bias
        w  = (h W_w) * heads^-1/2 * channels^-1/2       # float32

    on ``h`` under a stop-gradient: these leaves learn from the indexer's
    own loss alone.  Rotary turns all channels, by halves, at the model's
    ``rope_theta``."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, h, position_ids):
        cfg, sa = self.cfg, self.cfg.sa_config
        B, S, _ = h.shape
        NI, DI = sa.indexer_num_heads, sa.indexer_head_dim
        qi = _dense(h, NI * DI, ("embed", "qkv"), cfg=cfg, name="wq",
                    module=self).reshape(B, S, NI, DI)
        ki = _dense(h, DI, ("embed", None), cfg=cfg, name="wk", module=self)
        scale = self.param("k_norm_scale", nn.with_partitioning(
            nn.initializers.ones, (None,)), (DI,), cfg.param_dtype)
        bias = self.param("k_norm_bias", nn.with_partitioning(
            nn.initializers.zeros, (None,)), (DI,), cfg.param_dtype)
        ki = ki.astype(jnp.float32)
        ki = ki - ki.mean(-1, keepdims=True)
        ki = (ki * jax.lax.rsqrt((ki * ki).mean(-1, keepdims=True) + 1e-6)
              * scale + bias).astype(cfg.dtype)
        qi, ki = apply_rotary_pos_emb(qi, ki[:, :, None], position_ids,
                                      theta=cfg.rope_theta)
        w = _dense(h, NI, ("embed", None), cfg=cfg, name="weights_proj",
                   module=self).astype(jnp.float32) * (NI * DI) ** -0.5
        return qi, ki[:, :, 0], w


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    kind: Optional[str] = None      # the layer's type; None: full, one table
    # the rows are [noisy ; clean] of block-diffusion training: attention
    # under ops/attention.py block_diffusion_mask
    blockdiff: bool = False

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
        q, k = apply_rotary_pos_emb(q, k, position_ids,
                                    rotary_dim=cfg.rotary_dim,
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
        """Which shapes take which path between the projections and the
        attention call.  The projections write q and k as ``(B, S, H*D)``
        rows and the flash kernels read exactly those, so wherever
        ``ops/rotary.py rows_plan`` allows - ``head_dim`` a multiple of
        128 (OLMoE, Mellum 2, Trinity), no decode cache, a TPU, one
        device's own operands - the per-head norm of ``qk_norm="head"``
        and the rotation are one pass over the rows
        (``ops/pallas/qk_rows.py``) and the reshapes to ``(B, S, H, D)``
        that ``dot_product_attention``'s signature asks for fold away
        between two row-major kernels.  On the chip that view is no
        bitcast of the rows: formed for the rotation it cost a copy each
        way, forward, recomputation and backward (PERF.md section 6,
        PR 34).  Every other shape - head_dim 64 / 80 / 96, ``decode``
        (the cache append and ``_fused_decode`` keep ``(B, S, KV, D)``),
        heads split over ``tp``, the CPU - rotates and normalises in the
        ``(B, S, H, D)`` view as before;
        ``kernel_dispatch_total{site="qk_rows"}`` says which, and why.
        ``qk_norm=True`` (over the whole projection) is flat already."""
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
        if cfg.qk_norm is True:
            with trace.device_span("attn/qk_norm"):
                q = RMSNorm(cfg, axis="qkv", name="q_norm")(q)
                k = RMSNorm(cfg, axis="kv", name="k_norm")(k)
        rotates, head_norm = cfg.rotates(self.kind), cfg.qk_norm == "head"
        plan = rows_plan(q, k, D, rotary_dim=cfg.rotary_dim,
                         decode=cfg.decode, norm=head_norm) \
            if rotates or head_norm else None
        # a layer type's own table and device scopes only where the
        # configuration names layer types
        typed = self.kind is not None or cfg.rope_parameters is not None
        rope = "rope/" + (self.kind or FULL_ATTENTION) if typed else "rope"
        if plan is not None:
            scales = [RMSNorm(cfg, axis="head_dim", name=name)(
                jax.ShapeDtypeStruct((D,), cfg.dtype), params_only=True)
                for name in ("q_norm", "k_norm")] if head_norm else [None] * 2
            with trace.device_span(rope if rotates else "attn/qk_norm"):
                q, k = rotate_rows(
                    q, k, position_ids if rotates else None, D, plan,
                    theta=cfg.rope_theta, table=cfg.rotary(self.kind),
                    q_scale=scales[0], k_scale=scales[1],
                    eps=cfg.rms_norm_eps)
        q, k = q.reshape(B, S, H, D), k.reshape(B, S, KV, D)
        v = _dense(x, KV * D, ("embed", "kv"), cfg=cfg, name="v_proj",
                   module=self).reshape(B, S, KV, D)
        if head_norm and plan is None:
            with trace.device_span("attn/qk_norm"):
                q = RMSNorm(cfg, axis="head_dim", name="q_norm")(q)
                k = RMSNorm(cfg, axis="head_dim", name="k_norm")(k)
        if rotates and plan is None:
            with trace.device_span(rope):
                q, k = apply_rotary_pos_emb(q, k, position_ids,
                                            rotary_dim=cfg.rotary_dim,
                                            theta=cfg.rope_theta,
                                            table=cfg.rotary(self.kind))
        if cfg.decode:
            kc, vc, cur = self._cache_append(k, v)
            # shared fused-or-fallback dispatch; GQA-aware (KV panels stay
            # at KV heads on the kernel path — no repeat materialized)
            from ..ops.attention import cached_decode_attention

            y = cached_decode_attention(q, kc, vc, cur, attn_mask)
            y = y.reshape(B, S, H * D)
            return _dense(y, E, ("heads", "embed"), cfg=cfg,
                          name="o_proj", module=self)
        # k and v go at their own heads: the kernel and the XLA path read
        # key-value head h // (H // KV) for query head h.  The scope names
        # a layer type's kernels in the device trace
        window = cfg.window(self.kind)
        scope = "self_attn_window" if window else "self_attn_full"
        ys = None
        if cfg.sa_config is not None:
            from ..ops.indexed_attention import impl_of, indexed_attention

            with trace.device_span("attn/indexer"):
                qi, ki, w = Indexer(cfg, name="indexer")(
                    jax.lax.stop_gradient(x), position_ids)
            y, kl, counts = indexed_attention(
                q, k, v, qi, ki, w, topk=cfg.sa_config.topk,
                impl=impl_of(cfg.attn_impl))
            with trace.device_span("attn/indexer_loss"):
                tiles = counts.shape[1]
                ys = {
                    "indexer_loss": kl.mean(),
                    "indexer_kept_share": counts.sum() / (B * S * (S + 1) / 2),
                    "indexer_live_tile_share": (counts > 0).sum() / (
                        B * tiles * (tiles + 1) / 2)}
        elif self.blockdiff:
            from ..ops.attention import block_diffusion_attention

            with trace.device_span("self_attn_blockdiff"):
                y = block_diffusion_attention(
                    q, k, v, block=cfg.diffusion.block_length,
                    impl=cfg.attn_impl)
        else:
            with trace.device_span(scope) if self.kind is not None \
                    else contextlib.nullcontext():
                y = dot_product_attention(q, k, v, causal=True,
                                          mask=attn_mask, window=window,
                                          impl=cfg.attn_impl)
        y = y.reshape(B, S, H * D)
        if cfg.attn_gate:
            # elementwise in the kernels' own (B, S, H*D) layout: XLA may
            # fuse it into o_proj's operand
            gate = _dense(x, H * D, ("embed", "qkv"), cfg=cfg,
                          name="gate_proj", module=self)
            with trace.device_span("attn/gate"):
                y = y * jax.nn.sigmoid(gate)
        y = _dense(y, E, ("heads", "embed"), cfg=cfg, name="o_proj",
                   module=self)
        # with sa_config: the layer's _INDEXER_STATS ride beside its output
        return y if ys is None else (y, ys)


class LlamaLatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1; the leaves carry the released weights' names).  The up-projections'
    columns are laid out for the kernels that read them: ``q_b_proj``'s are
    all heads' nope channels, then all heads' rope channels (``H·nope |
    H·rope``), ``kv_b_proj``'s all heads' keys, then all heads' values - a
    fixed permutation of the released ``(H, nope + rope)`` and ``(H, nope +
    v)`` orders.  So q_nope, k_nope and v leave their matmuls as the ``(B,
    S, H·128)`` rows the two-product flash kernels read, one head a lane
    block, and nothing is split, transposed or padded in between.

    ``q_lora_rank=None`` (Ling 3.0): the queries have no latent, ``[q_nope
    | q_rope] = x W_q`` is the one leaf ``q_proj`` in the same column
    order.  ``attn_gate="head"``: each head's output times ``sigmoid(x
    W_gate)`` of its own column of ``gate_proj`` (E, H), before
    ``o_proj``."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, position_ids, attn_mask):
        from ..ops.rotary import rotate_rope_rows

        cfg = self.cfg
        B, S, E = x.shape
        H = cfg.num_attention_heads
        Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        init = nn.initializers.normal(cfg.initializer_range)

        def weight(name, names, shape):
            return self.param(name + "_kernel", nn.with_partitioning(
                init, names), shape, cfg.param_dtype).astype(cfg.dtype)

        with trace.device_span("attn/mla_q"):
            if cfg.q_lora_rank:
                c_q = RMSNorm(cfg, axis="latent", name="q_a_layernorm")(
                    jnp.dot(x, weight("q_a_proj", ("embed", "latent"),
                                      (E, cfg.q_lora_rank))))
                w_qb = weight("q_b_proj", ("latent", "qkv"),
                              (cfg.q_lora_rank, H * (Dn + Dr)))
            else:
                c_q = x
                w_qb = weight("q_proj", ("embed", "qkv"), (E, H * (Dn + Dr)))
            # two products of one leaf: each lands where its reader wants it
            q_nope = jnp.dot(c_q, w_qb[:, :H * Dn])
            q_rope = jnp.dot(c_q, w_qb[:, H * Dn:])
        with trace.device_span("attn/mla_kv"):
            kv_a = jnp.dot(x, weight("kv_a_proj_with_mqa", ("embed", "latent"),
                                     (E, cfg.kv_lora_rank + Dr)))
            c_kv = RMSNorm(cfg, axis="latent", name="kv_a_layernorm")(
                kv_a[..., :cfg.kv_lora_rank])
            k_rope = kv_a[..., cfg.kv_lora_rank:]          # (B, S, Dr)
            w_kvb = weight("kv_b_proj", ("latent", "kv"),
                           (cfg.kv_lora_rank, H * (Dn + Dv)))
            k_nope = jnp.dot(c_kv, w_kvb[:, :H * Dn])
            v = jnp.dot(c_kv, w_kvb[:, H * Dn:])
        table, scaled = cfg.latent_rotary
        yarn = {} if table is None else {"table": table}
        with trace.device_span("rope/mla"):
            q_rope, k_rope = (rotate_rope_rows(
                t, position_ids, Dr, theta=cfg.rope_theta,
                interleaved=cfg.rope_interleave, **yarn)
                for t in (q_rope, k_rope))
        with trace.device_span("self_attn_mla"):
            y = dot_product_attention(
                q_nope.reshape(B, S, H, Dn), k_nope.reshape(B, S, H, Dn),
                v.reshape(B, S, H, Dv), causal=True, mask=attn_mask,
                scale=(Dn + Dr) ** -0.5 * scaled, impl=cfg.attn_impl,
                q_rope=q_rope.reshape(B, S, H, Dr),
                k_rope=k_rope.reshape(B, S, 1, Dr))
        if cfg.attn_gate == "head":
            with trace.device_span("attn/mla_gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    x, weight("gate_proj", ("embed", None), (E, H)),
                    preferred_element_type=jnp.float32))
                y = (y.reshape(B, S, H, Dv) * gate[..., None]).astype(y.dtype)
        return jnp.dot(y.reshape(B, S, H * Dv),
                       weight("o_proj", ("heads", "embed"), (H * Dv, E)))


class ShortConv(nn.Module):
    """The LFM2 family's token mixer of a ``"conv"`` layer (released code:
    ``Lfm2MoeShortConv``): ``[B ; C ; u] = h W_in`` (in this order),
    ``y = (C * filter(B * u)) W_out`` with a causal depthwise filter of
    ``conv_L_cache`` taps a channel whose last tap is the current position
    (``ops/short_conv.py``).  No bias, no activation, no position.  The
    channels shard as the attention projections' do: ``in_proj`` like the
    fused q, k, v, the taps and ``out_proj`` like ``o_proj``'s input."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.short_conv import short_conv_rows

        cfg = self.cfg
        E = cfg.hidden_size
        with trace.device_span("short_conv/in_proj"):
            bcu = _dense(x, 3 * E, ("embed", "qkv"), cfg=cfg, name="in_proj",
                         module=self)
        taps = self.param("conv_kernel", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("heads", None)),
            (E, cfg.conv_L_cache), cfg.param_dtype)
        with trace.device_span("short_conv/filter"):
            y = short_conv_rows(bcu, taps)
        with trace.device_span("short_conv/out_proj"):
            return _dense(y, E, ("heads", "embed"), cfg=cfg, name="out_proj",
                          module=self)


class GatedDeltaNet(nn.Module):
    """The Qwen3-Next and Olmo-Hybrid families' token mixer of a
    ``"linear_attention"`` layer (released code: ``Qwen3NextGatedDeltaNet``;
    fla's ``GatedDeltaNet``), Hk key heads of dk channels and Hv value heads
    of dv (128 and 128; 96 and 192), the delta rule under ONE log-decay a
    value head a position (the Ling 3.0 family's decay a key channel is
    :class:`KimiDeltaAttention`'s)::

        [q | k | v | z] = h W_qkvz      # Hk dk | Hk dk | Hv dv | Hv dv, contiguous
        [b | a]         = h W_ba        # Hv | Hv
        [q | k | v]    <- silu(filter([q | k | v]))     # ops/short_conv.py
        beta = sigmoid(b)  (x 2 under linear_allow_neg_eigval)
        g = -exp(A_log) * softplus(a + dt_bias)
        q <- q / |q| * dk^-1/2;  k <- k / |k|           # a head, eps 1e-6
        o = gated_delta_rule(q, k, v, g, beta)          # ops/gated_delta.py
        y = (o * rsqrt(mean(o^2) + eps) * w_o) * silu(z)    # a head
        out = y W_out

    The source's ``in_proj_qkvz`` orders its columns a key-head group
    (``[q ; k ; v ; z]`` of group 0, then of group 1, ...) and
    ``in_proj_ba`` likewise; here each part is contiguous, a column
    permutation a loader makes, so that the filter and the rule read rows
    as the projection wrote them.  ``in_proj_qkvz`` is one leaf read by
    two products (``[q | k | v]`` for the filter, ``z`` for the gate): each
    lands where its reader wants it.  No positional encoding, no bias;
    every row of a batch starts from a zero state and an empty filter.
    The channels shard as the attention projections' do.

    Where the normalised heads lie between the filter and ``out_proj`` -
    rows at whole lane tiles (128 x 128), lane slots (96 x 192) or the
    ``(B, S, H, d)`` float32 view - is the kernels' matter and is read from
    the shapes, the device and the mesh by ``ops/gated_delta.py
    normalised_heads`` / ``heads_rule`` / ``gated_norm``, whose text says
    which runs where; ``kernel_dispatch_total`` says which ran, and why."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops import gated_delta
        from ..ops.short_conv import causal_conv_rows

        cfg = self.cfg
        B, S, E = x.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, d = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        conv_dim = 2 * Hk * dk + Hv * d
        init = nn.initializers.normal(cfg.initializer_range)
        f32 = jnp.float32
        with trace.device_span("linear_attn/in_proj"):
            w_in = self.param("in_proj_qkvz_kernel", nn.with_partitioning(
                init, ("embed", "qkv")), (E, conv_dim + Hv * d),
                cfg.param_dtype).astype(cfg.dtype)
            qkv = jnp.dot(x, w_in[:, :conv_dim])
            z = jnp.dot(x, w_in[:, conv_dim:])
            ba = _dense(x, 2 * Hv, ("embed", "qkv"), cfg=cfg,
                        name="in_proj_ba", module=self)
        taps = self.param("conv_kernel", nn.with_partitioning(
            init, ("heads", None)), (conv_dim, cfg.linear_conv_kernel_dim),
            cfg.param_dtype)
        with trace.device_span("linear_attn/conv"):
            qkv = causal_conv_rows(qkv, taps, activation="silu")
        # A_log = log(U(0, 16)), dt_bias ones (released code)
        a_log = self.param("A_log", nn.with_partitioning(
            lambda key, shape, dtype: jnp.log(jax.random.uniform(
                key, shape, dtype, 1e-3, 16.0)), ("heads",)), (Hv,), f32)
        dt_bias = self.param("dt_bias", nn.with_partitioning(
            nn.initializers.ones, ("heads",)), (Hv,), f32)
        with trace.device_span("linear_attn/delta_rule"):
            heads = gated_delta.normalised_heads(qkv, Hk, dk, Hv, d,
                                                 cfg.linear_chunk_size)
            beta = jax.nn.sigmoid(ba[..., :Hv].astype(f32))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log) * jax.nn.softplus(
                ba[..., Hv:].astype(f32) + dt_bias)
            o = gated_delta.heads_rule(heads, g, beta)
        w_o = self.param("o_norm", nn.with_partitioning(
            nn.initializers.ones, ("head_dim",)), (d,), cfg.param_dtype)
        with trace.device_span("linear_attn/gated_norm"):
            # norm first, gate second; w from ones whatever the other
            # norms of the model are
            y = gated_delta.gated_norm(heads, o, z, w_o, cfg.rms_norm_eps)
        with trace.device_span("linear_attn/out_proj"):
            return _dense(y.reshape(B, S, Hv * d), E, ("heads", "embed"),
                          cfg=cfg, name="out_proj", module=self)


class KimiDeltaAttention(nn.Module):
    """The Ling 3.0 / Kimi Linear families' token mixer of a
    ``"kda_attention"`` layer (arXiv:2510.26692; fla's
    ``KimiDeltaAttention`` under ``no_kda_lora``: both gates' projections
    full rank), H = ``num_attention_heads`` heads of d = ``head_dim``
    channels, keys and values alike (a state is d x d)::

        [q | k | v] = silu(filter(h [W_q | W_k | W_v]))     # ops/short_conv.py
        q <- q / |q| * d^-1/2;  k <- k / |k|                # a head, eps 1e-6
        g = kda_lower_bound * sigmoid(exp(A_log) (h W_f + dt_bias))
        beta = sigmoid(h W_b)                               # a head
        o = gated_delta_rule(q, k, v, g, beta)          # ops/gated_delta.py
        y = (o * rsqrt(mean(o^2) + eps) * w_o) * sigmoid(h W_g)     # a head
        out = y W_o

    ``g`` is a log-decay a KEY CHANNEL, (B, S, H, d) in (kda_lower_bound,
    0), float32: ``A_log`` is a number a head, ``dt_bias`` one a channel.
    What it shares with :class:`GatedDeltaNet`: the filter
    (``causal_conv_rows``), the heads' norms (``ops/gated_delta.py
    normalised_heads`` / ``gated_norm``) and the rule.  ``q_proj``,
    ``k_proj`` and ``v_proj`` are three leaves under the released names,
    read as ONE product (their columns side by side) so that the filter and
    the rule read ``[q | k | v]`` rows as GatedDeltaNet's do; the three
    filters' taps are the one leaf ``conv_kernel`` (3 H d, taps), q's rows,
    then k's, then v's: a concatenation a loader makes.  No positional
    encoding, no bias; every row of a batch starts from a zero state and
    empty filters.  On a TPU in bf16 at heads of whole lane tiles (128) the
    rule runs as the kernels of a decay a key channel
    (``ops/pallas/gated_delta.py channel_forward`` / ``channel_backward``,
    PR 60), reading ``q``, ``k``, ``v`` as the filter and the row norm wrote
    them and gamma as float32 rows beside ``k``; elsewhere as XLA's program
    (``kernel_dispatch_total{site="gated_delta"}`` says which and why).
    The sigmoid gate keeps the output norm on XLA's lines
    (the row kernel multiplies by ``silu``):
    ``kernel_dispatch_total{site="gated_norm_rows"}`` says so."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops import gated_delta
        from ..ops.short_conv import causal_conv_rows

        cfg = self.cfg
        B, S, E = x.shape
        H, d = cfg.num_attention_heads, cfg.head_dim
        W = H * d
        init = nn.initializers.normal(cfg.initializer_range)
        f32 = jnp.float32

        def weight(name, names, shape):
            return self.param(name + "_kernel", nn.with_partitioning(
                init, names), shape, cfg.param_dtype).astype(cfg.dtype)

        with trace.device_span("linear_attn/in_proj"):
            qkv = jnp.dot(x, jnp.concatenate(
                [weight(n, ("embed", "qkv"), (E, W))
                 for n in ("q_proj", "k_proj", "v_proj")], axis=1))
            z = jnp.dot(x, weight("g_proj", ("embed", "qkv"), (E, W)))
            b = jnp.dot(x, weight("b_proj", ("embed", None), (E, H)),
                        preferred_element_type=f32)
        taps = self.param("conv_kernel", nn.with_partitioning(
            init, ("heads", None)), (3 * W, cfg.short_conv_kernel_size),
            cfg.param_dtype)
        with trace.device_span("linear_attn/conv"):
            qkv = causal_conv_rows(qkv, taps, activation="silu")
        # the released layer's: A_log = log(U(1, 16)) a head; dt_bias the
        # inverse softplus of dt ~ logU(1e-3, 1e-1) a channel
        a_log = self.param("A_log", nn.with_partitioning(
            lambda key, shape, dtype: jnp.log(jax.random.uniform(
                key, shape, dtype, 1.0, 16.0)), ("heads",)), (H,), f32)

        def dt_init(key, shape, dtype):
            dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                         * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))

        dt_bias = self.param("dt_bias", nn.with_partitioning(
            dt_init, ("heads",)), (W,), f32)
        with trace.device_span("linear_attn/decay_gate"):
            f = jnp.dot(x, weight("f_proj", ("embed", "qkv"), (E, W)))
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.repeat(jnp.exp(a_log), d)
                * (f.astype(f32) + dt_bias)).reshape(B, S, H, d)
        with trace.device_span("linear_attn/delta_rule"):
            heads = gated_delta.normalised_heads(qkv, H, d, H, d,
                                                 cfg.linear_chunk_size)
            o = gated_delta.heads_rule(heads, g, jax.nn.sigmoid(b))
        w_o = self.param("o_norm", nn.with_partitioning(
            nn.initializers.ones, ("head_dim",)), (d,), cfg.param_dtype)
        with trace.device_span("linear_attn/gated_norm"):
            y = gated_delta.gated_norm(heads, o, z, w_o, cfg.rms_norm_eps,
                                       gate="sigmoid")
        with trace.device_span("linear_attn/out_proj"):
            return jnp.dot(y.reshape(B, S, W),
                           weight("o_proj", ("heads", "embed"), (W, E)))


class HyperConnection(nn.Module):
    """One sublayer's manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880 section 4; ``ops/hyper_connection.py`` has the
    equations): called on the stream ``(B, S, hc_mult * E)`` it returns what
    the sublayer reads, ``(B, S, E)``, and the token's maps; :meth:`post`
    writes the sublayer's output back.  Leaves: ``phi`` (n E, n^2 + 2n), the
    gains ``a_pre``, ``a_post``, ``a_res`` (one number each) and the biases
    ``b_pre``, ``b_post`` (n,), ``b_res`` (n, n), from the paper's
    near-identity start: ``H_pre`` = 1/n, ``H_post`` = 1, ``H_res`` ~ I."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from ..ops import hyper_connection as mhc

        cfg, n = self.cfg, self.cfg.lanes
        const = nn.initializers.constant

        def leaf(name, init, shape, names=None):
            init = init if names is None else nn.with_partitioning(init, names)
            return self.param(name, init, shape, cfg.param_dtype)

        phi = leaf("phi", nn.initializers.normal(cfg.initializer_range),
                   (x.shape[-1], n * n + 2 * n), ("embed", None))
        gains = tuple(leaf(a, const(0.01), (1,))
                      for a in ("a_pre", "a_post", "a_res"))
        biases = (leaf("b_pre", const(-math.log(n - 1.0)), (n,)),
                  leaf("b_post", const(0.0), (n,)),
                  leaf("b_res", lambda key, shape, dtype: 8.0 * jnp.eye(
                      n, dtype=dtype), (n, n)))
        return mhc.read(
            x, phi, gains, biases, n=n, iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps, rms_eps=cfg.rms_norm_eps,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))

    @staticmethod
    def post(x, y, maps):
        from ..ops import hyper_connection as mhc

        return mhc.write(x, y, maps)


def _mhc_stats(attn_maps, mlp_maps) -> dict:
    """A block's ``_MHC_STATS``: the worse marginal of its two sublayers,
    the others' mean."""
    from ..ops.hyper_connection import gauges

    a, b = gauges(attn_maps), gauges(mlp_maps)
    return {k: jnp.maximum(a[k], b[k]) if k == _MHC_STATS[0]
            else 0.5 * (a[k] + b[k]) for k in _MHC_STATS}


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    deterministic: bool = True
    kind: Optional[str] = None      # this layer's entry of cfg.layer_types
    sparse: bool = True             # False: dense FFN although cfg.moe is set
    blockdiff: bool = False         # LlamaAttention's

    def _dense_ffn(self, h):
        cfg = self.cfg
        gate = _dense(h, cfg.intermediate_size, ("embed", "mlp"), cfg=cfg,
                      name="gate_proj", module=self)
        up = _dense(h, cfg.intermediate_size, ("embed", "mlp"), cfg=cfg,
                    name="up_proj", module=self)
        return _dense(nn.silu(gate) * up, cfg.hidden_size, ("mlp", "embed"),
                      cfg=cfg, name="down_proj", module=self)

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
        stream = attn_maps = mlp_maps = None
        if cfg.lanes > 1:       # x: the stream's lanes; the sublayer reads u
            stream = x
            x, attn_maps = HyperConnection(cfg, name="attn_hc")(stream)
        # under the reordered norm a branch reads the residual stream itself
        h = x if cfg.reordered_norm else RMSNorm(cfg, name="input_norm")(x)
        if self.kind == CONV:       # the mixer reads no position and no mask
            attn = ShortConv(cfg, name="conv")(h)
        elif self.kind == LINEAR:
            attn = GatedDeltaNet(cfg, name="linear_attn")(h)
        elif self.kind == KDA:
            attn = KimiDeltaAttention(cfg, name="kda_attn")(h)
        else:
            self_attn = LlamaLatentAttention(cfg, name="self_attn") \
                if cfg.kv_lora_rank \
                else LlamaAttention(cfg, self.kind, self.blockdiff,
                                    name="self_attn")
            attn = self_attn(h, position_ids, attn_mask)
        indexed = None
        if cfg.sa_config is not None:
            attn, indexed = attn
        if stream is not None:
            stream = HyperConnection.post(stream, attn, attn_maps)
            x, mlp_maps = HyperConnection(cfg, name="mlp_hc")(stream)
            h = RMSNorm(cfg, name="post_attention_norm")(x)
        elif cfg.reordered_norm:
            h = x = x + RMSNorm(cfg, name="post_attention_norm")(attn)
        elif cfg.sandwich_norm:
            x = x + RMSNorm(cfg, name="post_attention_norm")(attn)
            h = RMSNorm(cfg, name="pre_mlp_norm")(x)
        else:
            x = x + attn
            h = RMSNorm(cfg, name="post_attention_norm")(x)
        ys = None
        if cfg.moe is not None and self.sparse:
            from ..parallel.moe import MoELayer

            ff, aux, stats = MoELayer(
                cfg.moe, model_dim=cfg.hidden_size,
                hidden_dim=cfg.expert_size, dtype=cfg.dtype,
                name="moe")(h, train=not self.deterministic,
                            return_stats=True)
            ys = dict(stats, aux_loss=aux)
        else:
            with trace.device_span("mlp_dense"):
                ff = self._dense_ffn(h)
        if cfg.sandwich_norm or cfg.reordered_norm:
            ff = RMSNorm(cfg, name="post_mlp_norm")(ff)
        if indexed is not None:
            ys = dict(ys or {}, **indexed)
        if stream is not None:
            return HyperConnection.post(stream, ff, mlp_maps), dict(
                ys or {}, **_mhc_stats(attn_maps, mlp_maps))
        return x + ff, ys


class MTPModule(nn.Module):
    """One multi-token-prediction depth (DeepSeek-V3, arXiv:2412.19437
    section 2.2, eq. 21-23; leaves under the released weights' names):
    ``x_i = [enorm(E[t_{i+1}]) ; hnorm(h_i)] eh_proj``, one whole block of
    the model's sparse kind, ``shared_head_norm``.  The table that embeds
    ``t_{i+1}`` and the head that reads the result are the CALLER's: the
    main model's own leaves, whose gradient is the sum of both uses.
    Under hyper-connections ``h`` is the stack's lane sum, ``x`` is copied
    into the block's own lanes and the block's lanes are summed again."""
    cfg: LlamaConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, h, next_embed, inputs):
        cfg = self.cfg
        with trace.device_span("mtp/embed_proj"):
            x = jnp.concatenate(
                [RMSNorm(cfg, name="enorm")(next_embed),
                 RMSNorm(cfg, name="hnorm")(h)], axis=-1)
            x = _dense(x, cfg.hidden_size, ("mlp", "embed"), cfg=cfg,
                       name="eh_proj", module=self)
        with trace.device_span("mtp/block"):
            if cfg.lanes > 1:
                from ..ops import hyper_connection as mhc

                x = mhc.widen(x, cfg.lanes)
            x, ys = LlamaBlock(cfg, self.deterministic, name="block")(
                x, inputs)
            if cfg.lanes > 1:
                x = mhc.collapse(x, cfg.lanes)
        return RMSNorm(cfg, name="shared_head_norm")(x), ys


_EXIT_LOG_EPS = 1e-20      # inside the exit distribution's entropy's log


class ExitGate(nn.Module):
    """A looped stack's exit gate: the logit of ``g = sigmoid(h . w + b)``,
    one float32 number a token, from a pass's normed stream ``(B, S, E)``."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        kernel = self.param("kernel", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("embed", None)),
            (h.shape[-1], 1), cfg.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (1,),
                          cfg.param_dtype)
        return (h.astype(jnp.float32) * kernel[:, 0].astype(jnp.float32)
                ).sum(-1) + bias.astype(jnp.float32)


def _exit_distribution(gate_logits):
    """``p`` (T, ...) from the first T - 1 passes' gate logits (T - 1, ...):
    ``p_t = g_t prod_{j<t} (1 - g_j)``, and the last pass takes what is
    left, ``prod_{j<T} (1 - g_j)``."""
    g = jax.nn.sigmoid(gate_logits)
    left = jnp.cumprod(1.0 - g, axis=0)         # after pass t
    return jnp.concatenate([g[:1], g[1:] * left[:-1], left[-1:]])


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig

    @property
    def rng_streams(self) -> tuple:
        """The random streams the engine folds for this model beside its
        own three (``runtime/engine.py _loss_and_stats``)."""
        return ("diffusion",) if self.cfg.diffusion is not None else ()

    def _diffusion_noise(self, input_ids, diffusion_mask, diffusion_t):
        """``(masked (B, L) bool, t (B, L / block_length))``: as given, or
        drawn from the ``diffusion`` stream (the engine folds it from the
        step and the micro-batch, beside ``dropout`` and ``gating``)."""
        dif = self.cfg.diffusion
        B, L = input_ids.shape
        g = dif.block_length
        if L % g:
            raise ValueError(f"rows of {L} tokens are no whole blocks of {g}")
        if (diffusion_mask is None) != (diffusion_t is None):
            raise ValueError(
                "diffusion_mask (B, L) and diffusion_t (B, L / "
                "block_length) come together: the weight reads the levels")
        if diffusion_mask is not None:
            return diffusion_mask.astype(bool), diffusion_t.astype(jnp.float32)
        if self.has_rng("diffusion"):
            key = self.make_rng("diffusion")
        elif self.is_initializing():    # shapes alone: any noise will do
            key = jax.random.PRNGKey(0)
        else:
            raise ValueError(
                "block-diffusion training draws its noise from the "
                "'diffusion' random stream, which only a training step has: "
                "give diffusion_mask and diffusion_t in the batch instead")
        key_t, key_mask = jax.random.split(key)
        t = 1.0 - jax.random.uniform(key_t, (B, L // g)) * (1.0 - dif.t_min)
        masked = jax.random.uniform(key_mask, (B, L)) \
            < jnp.repeat(t, g, axis=1)
        return masked, t

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 labels=None, deterministic: bool = True, shift: bool = True,
                 diffusion_mask=None, diffusion_t=None):
        """With ``cfg.diffusion``: ``labels`` (the clean tokens; -100 takes
        a position out of the loss) makes the training loss over the noisy
        half under drawn or given noise; ``diffusion_mask`` and
        ``diffusion_t`` without labels return the logits of both halves,
        ``(B, 2L, V)``, noisy first."""
        cfg = self.cfg
        B, S = input_ids.shape
        embed = self.param("embed_tokens", nn.with_partitioning(
            nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")),
            (cfg.padded_vocab_size, cfg.hidden_size), cfg.param_dtype)
        dif = cfg.diffusion
        if dif is not None:
            if labels is None and diffusion_mask is None:
                raise NotImplementedError(
                    "a model with the diffusion section and neither labels "
                    "nor diffusion_mask: a plain forward over a clean "
                    "context (generation by denoising a block) is not "
                    "written")
            if attention_mask is not None or position_ids is not None:
                raise NotImplementedError(
                    "block-diffusion training with an attention_mask or "
                    "position_ids of the caller's: the two halves' "
                    "positions and mask are the objective's own")
            with trace.device_span("diffusion/noise"):
                masked, t = self._diffusion_noise(input_ids, diffusion_mask,
                                                  diffusion_t)
            with trace.device_span("diffusion/halves"):
                input_ids = jnp.concatenate(
                    [jnp.where(masked, dif.mask_token_id, input_ids),
                     input_ids], axis=1)                    # [noisy ; clean]
                position_ids = jnp.concatenate(
                    [jnp.arange(S), jnp.arange(S)])[None, :]
        if position_ids is None:
            if cfg.decode:
                raise ValueError("decode mode requires explicit position_ids")
            position_ids = jnp.arange(S)[None, :]
        h = embed.astype(cfg.dtype)[input_ids]
        if cfg.mup_enabled:
            h = h * (cfg.hidden_size ** 0.5)
        if cfg.lanes > 1:       # the table's row in every lane
            from ..ops import hyper_connection as mhc

            h = mhc.widen(h, cfg.lanes)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)

        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(
                LlamaBlock, policy=resolve_remat_policy(cfg.remat_policy),
                prevent_cse=cfg.remat_prevent_cse)
        kinds = cfg.kinds
        indexed = []        # the layers' _INDEXER_STATS, then stacked
        lane_stats = []     # the blocks' _MHC_STATS, the prediction's last
        # the stack is built once; a looped stack calls it once a pass
        if cfg.scan_layers:
            if len(set(kinds)) > 1 or cfg.num_dense_layers:
                raise NotImplementedError(
                    f"scan_layers=True scans one kind of block, and "
                    f"layer_types mixes {sorted(set(kinds))} with "
                    f"{cfg.num_dense_layers} leading dense blocks: set "
                    f"scan_layers=False (the stack is then unrolled)")
            scanned = nn.scan(
                block_cls, variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True,
                            "gating": True, "pld": True},
                length=cfg.num_hidden_layers, in_axes=nn.broadcast,
                metadata_params={nn.meta.PARTITION_NAME: "layers"})
            stack = scanned(cfg, deterministic, *kinds[:1], name="layers",
                            blockdiff=dif is not None)
        else:
            stack = [block_cls(cfg, deterministic, *kinds[i:i + 1],
                               name=f"layers_{i}",
                               **({} if cfg.sparse(i) or cfg.moe is None
                                  else {"sparse": False}),
                               blockdiff=dif is not None)
                     for i in range(cfg.num_hidden_layers)]
        norm = RMSNorm(cfg, name="norm")

        def one_pass(h):
            """``(the stream after the stack, what its layers report)``."""
            if cfg.scan_layers:
                return stack(h, (position_ids, mask))
            reports = []
            for block in stack:
                h, ys = block(h, (position_ids, mask))
                reports.append(ys)
            return h, reports

        exits = None
        if cfg.total_ut_steps > 1:
            # h_t = norm(blocks(h_{t-1})): the normed stream is what the next
            # pass, the gate and the head read
            exits = []
            for t in range(cfg.total_ut_steps):
                with trace.device_span(f"ut/pass_{t}"):
                    h = norm(one_pass(h)[0])
                exits.append(h)
            with trace.device_span("ut/exit_gate"):
                # the last pass takes what is left: its gate is never read
                gate = ExitGate(cfg, name="exit_gate")
                exit_p = _exit_distribution(
                    jnp.stack([gate(x) for x in exits[:-1]]))
        elif cfg.scan_layers:
            h, per_layer = one_pass(h)
            if cfg.sa_config is not None:
                per_layer = dict(per_layer)
                indexed = {k: per_layer.pop(k) for k in _INDEXER_STATS}
        else:
            h, reports = one_pass(h)
            per_layer = []
            for ys in reports:
                if cfg.sa_config is not None:   # every layer has these
                    ys = dict(ys)
                    indexed.append({k: ys.pop(k) for k in _INDEXER_STATS})
                    ys = ys or None
                if cfg.lanes > 1:
                    ys = dict(ys)
                    lane_stats.append({k: ys.pop(k) for k in _MHC_STATS})
                    ys = ys or None
                per_layer.append(ys)
            if indexed:
                indexed = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                 *indexed)
            if cfg.moe is not None:     # stacked over the MoE layers alone
                per_layer = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *per_layer[cfg.num_dense_layers:])

        if cfg.lanes > 1:       # the lanes' sum: what both heads read
            h = mhc.collapse(h, cfg.lanes)
        h_mtp = None
        if cfg.mtp_blocks and labels is not None:
            # the stack's output BEFORE the final norm, beside the next
            # token's embedding (the last position has none: its label is
            # ignored below, so what it embeds is never read)
            mtp_cls = MTPModule
            if cfg.remat:
                mtp_cls = nn.remat(
                    MTPModule, policy=resolve_remat_policy(cfg.remat_policy),
                    prevent_cse=cfg.remat_prevent_cse)
            next_ids = jnp.concatenate(
                [input_ids[:, 1:], jnp.zeros_like(input_ids[:, :1])], axis=1)
            h_mtp, ys = mtp_cls(cfg, deterministic, name="mtp_0")(
                h, embed.astype(cfg.dtype)[next_ids], (position_ids, mask))
            if cfg.lanes > 1:
                ys = dict(ys)
                lane_stats.append({k: ys.pop(k) for k in _MHC_STATS})
                ys = ys or None
            if cfg.moe is not None:     # the block's row follows the stack's
                ys = jax.tree_util.tree_map(lambda x: x[None], ys)
                per_layer = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b]), per_layer, ys) \
                    if cfg.num_dense_layers < cfg.num_hidden_layers else ys

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
        if cfg.sa_config is not None:
            # the paper's sum over the layers, under one weight; the two
            # shares are the stack's means
            indexer_loss = out["indexer_loss"] = indexed["indexer_loss"].sum()
            out["stats"] = dict(
                out.get("stats") or {}, indexer_loss=indexer_loss,
                **{k: indexed[k].mean() for k in _INDEXER_STATS[1:]})

        if lane_stats:      # a value a block, the prediction block's last
            out["stats"] = dict(
                out.get("stats") or {}, **jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *lane_stats))

        weighted = {}
        if dif is not None and labels is not None:
            # the head reads the noisy half alone; the clean half has
            # passed every layer held, as in an inner stage of a pipeline
            with trace.device_span("diffusion/halves"):
                h = h[:, :S]
                weight = (masked & (labels != -100)).astype(jnp.float32)
                if dif.loss_weight == "inv_t":
                    weight = weight / jnp.repeat(t, dif.block_length, axis=1)
            weighted = {"weights": weight, "denominator": float(B * S)}
            shift = False       # read at the noisy half's own position
            out["stats"] = dict(
                out.get("stats") or {},
                diffusion_masked=masked.sum().astype(jnp.int32),
                diffusion_kept=(~masked).sum().astype(jnp.int32),
                diffusion_t_mean=t.mean())
        if exits is None:
            h = norm(h)
        if cfg.tie_word_embeddings:     # the head reads the table
            lm_head = embed.T
        else:
            lm_head = self.param("lm_head", nn.with_partitioning(
                nn.initializers.normal(cfg.initializer_range),
                ("embed", "vocab")),
                (cfg.hidden_size, cfg.padded_vocab_size), cfg.param_dtype)
        tgt = None
        if labels is not None:
            tgt = shift_labels(labels) if shift else labels

        def head(h):
            logits = jnp.dot(h, lm_head.astype(cfg.dtype))
            if cfg.padded_vocab_size != cfg.vocab_size:
                pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
                logits = jnp.where(pad_mask, logits,
                                   jnp.finfo(logits.dtype).min)
            return logits

        if exits is not None:
            with trace.device_span("loss_head"):
                if tgt is None:
                    loss, out["logits"] = None, head(h)     # the last pass's
                else:
                    loss = self._exit_loss(exits, exit_p, lm_head, tgt, head,
                                           out)
        elif cfg.loss_chunk and tgt is not None:
            from .common import chunked_lm_loss

            with trace.device_span("loss_head"):
                loss = chunked_lm_loss(
                    h, lm_head.T, tgt, vocab_size=cfg.vocab_size,
                    padded_vocab_size=cfg.padded_vocab_size,
                    chunk=cfg.loss_chunk, dtype=cfg.dtype, **weighted)
            if h_mtp is not None:
                # the same head, on labels one further ahead: positions
                # without a label two ahead are left out of the mean
                with trace.device_span("mtp/loss_head"):
                    mtp_loss = chunked_lm_loss(
                        h_mtp, lm_head.T, shift_labels(tgt),
                        vocab_size=cfg.vocab_size,
                        padded_vocab_size=cfg.padded_vocab_size,
                        chunk=cfg.loss_chunk, dtype=cfg.dtype)
        else:
            with trace.device_span("loss_head"):
                logits = out["logits"] = head(h)
                loss = None if tgt is None \
                    else cross_entropy_loss(logits, tgt, **weighted)
            if h_mtp is not None and tgt is not None:
                with trace.device_span("mtp/loss_head"):
                    mtp_loss = cross_entropy_loss(head(h_mtp),
                                                  shift_labels(tgt))
        if h_mtp is not None and loss is not None:
            out["lm_loss"], out["mtp_loss"] = loss, mtp_loss
            out["stats"] = dict(out.get("stats") or {}, lm_loss=loss,
                                mtp_loss=mtp_loss)
            loss = loss + cfg.mtp_loss_weight * mtp_loss
        if loss is not None:
            if cfg.sa_config is not None:
                loss = loss + cfg.indexer_loss_weight * indexer_loss
            out["loss"] = loss if aux_loss is None else loss + aux_loss
        return out

    def _exit_loss(self, exits, p, lm_head, tgt, head, out):
        """A looped stack's loss: over the labelled tokens, the mean of
        ``sum_t p_t nll_t - exit_entropy_weight H(p)``, the passes' streams
        ``exits`` (T of (B, S, E)) through the ONE head, ``p`` (T, B, S) the
        exit distribution; its parts go into ``out["stats"]``.

        Under ``loss_chunk`` the T B S rows pass the chunked head once, each
        weighted by its ``p`` held constant (one ``dW``, no logits kept), and
        ``p``'s gradient, each row's own nll, is added at value 0; without
        it the last pass's logits go into ``out["logits"]``."""
        cfg = self.cfg
        T, (B, S) = len(exits), tgt.shape
        valid = tgt != -100
        count = jnp.maximum(valid.sum(), 1).astype(jnp.float32)
        if cfg.loss_chunk:
            from .common import chunked_lm_loss

            held = jax.lax.stop_gradient(p)
            expected, nll = chunked_lm_loss(
                jnp.concatenate(exits), lm_head.T, jnp.tile(tgt, (T, 1)),
                vocab_size=cfg.vocab_size,
                padded_vocab_size=cfg.padded_vocab_size,
                chunk=cfg.loss_chunk, dtype=cfg.dtype,
                weights=held.reshape(T * B, S), denominator=count, rows=True)
            nll = nll.reshape(T, B, S)
            expected = expected + ((p - held) * nll).sum() / count
        else:
            each = [head(x) for x in exits]
            out["logits"] = each[-1]
            lg = jnp.stack(each).astype(jnp.float32)
            label = jnp.take_along_axis(
                lg, jnp.where(valid, tgt, 0)[None, ..., None], -1)[..., 0]
            nll = jnp.where(valid, jax.nn.logsumexp(lg, -1) - label, 0.0)
            expected = (p * nll).sum() / count
        p = p * valid
        entropy = -(p * jnp.log(p + _EXIT_LOG_EPS)).sum() / count
        exit_p = p.sum((1, 2)) / count
        exit_nll = jax.lax.stop_gradient(nll).sum((1, 2)) / count
        out["stats"] = dict(
            out.get("stats") or {}, exit_p=exit_p, exit_nll=exit_nll,
            exit_step_mean=(exit_p * jnp.arange(1, T + 1)).sum(),
            exit_entropy=entropy, lm_loss=exit_nll[-1])
        return expected - cfg.exit_entropy_weight * entropy

    @staticmethod
    def record_step_stats(stats) -> None:
        """The engine hands back the host copy of ``out["stats"]`` of each
        finished step; the routing counters live with the MoE layer."""
        if "exit_p" in stats:
            from ..telemetry import registry

            for name, what in (
                    ("exit_p", "share of the exit distribution on a pass of "
                     "a looped stack, mean over the labelled tokens"),
                    ("exit_nll", "next-token cross-entropy of a pass's exit "
                     "through the one head")):
                gauge = registry.gauge(
                    "ut_" + name, what + ", last finished step", ("step",))
                for t, v in enumerate(np.asarray(stats[name]).ravel()):
                    gauge.labels(str(t)).set(float(v))
            registry.gauge(
                "ut_exit_step_mean", "mean pass a token exits a looped "
                "stack at under the exit distribution (1 is the first), "
                "last finished step").set(float(stats["exit_step_mean"]))
            registry.gauge(
                "ut_exit_entropy", "entropy of the exit distribution, mean "
                "over the labelled tokens, last finished step").set(
                float(stats["exit_entropy"]))
        if "lm_loss" in stats:      # beside a prediction block's or the exits'
            from ..telemetry import registry

            registry.gauge("lm_loss", "next-token cross-entropy of the main "
                           "head, last finished step").set(
                float(stats["lm_loss"]))
        if "mtp_loss" in stats:
            from ..telemetry import registry

            registry.gauge(
                "mtp_loss", "cross-entropy of a multi-token-prediction "
                "block (depth d predicts the token d + 1 ahead), last "
                "finished step", ("depth",)).labels("1").set(
                float(stats["mtp_loss"]))
        if "diffusion_masked" in stats:
            from ..telemetry import registry

            tokens = registry.counter(
                "diffusion_tokens_total", "data tokens of block-diffusion "
                "training by what the step's noise made of them: masked "
                "(replaced by the mask id; the loss reads these) or kept",
                ("kind",))
            tokens.labels("masked").inc(float(stats["diffusion_masked"]))
            tokens.labels("kept").inc(float(stats["diffusion_kept"]))
            registry.gauge(
                "diffusion_t_mean", "mean noise level drawn over the "
                "blocks of the last finished step").set(
                float(stats["diffusion_t_mean"]))
        if "indexer_loss" in stats:
            from ..telemetry import registry

            registry.gauge(
                "indexer_loss", "KL(attention's head-mean probabilities || "
                "softmax of the indexer's scores) over the kept keys, summed "
                "over the layers, last finished step").set(
                float(stats["indexer_loss"]))
            registry.gauge(
                "sparse_attention_kept_share", "kept (query, key) pairs "
                "over the causal pairs, mean of the layers, last finished "
                "step").set(float(stats["indexer_kept_share"]))
            registry.gauge(
                "sparse_attention_live_tile_share", "512 x 512 causal score "
                "tiles that hold at least one kept pair over all of them "
                "(what block skipping could skip is the rest), mean of the "
                "layers, last finished step").set(
                float(stats["indexer_live_tile_share"]))
        if _MHC_STATS[0] in stats:
            from ..telemetry import registry

            for name, what in zip(_MHC_STATS, (
                    "largest |row sum - 1| and |column sum - 1| of a "
                    "hyper-connection's lane-to-lane map over the tokens",
                    "1 - trace(H_res) / n, the share of a lane that comes "
                    "from other lanes (0: a plain residual), mean over the "
                    "tokens",
                    "mean of H_pre, what a sublayer reads of a lane",
                    "mean of H_post, what a lane takes of a sublayer's "
                    "output")):
                gauge = registry.gauge(
                    name, what + "; a block's two sublayers together (the "
                    "prediction block's row last), last finished step",
                    ("layer",))
                for i, v in enumerate(np.asarray(stats[name]).ravel()):
                    gauge.labels(str(i)).set(float(v))
        if "tokens_per_expert" in stats:
            from ..parallel.moe import record_stats

            record_stats(stats)

    @property
    def is_undecayed_leaf(self):
        """``path -> bool`` over a leaf's path of dict keys: the leaves
        weight decay leaves alone (``runtime/optimizers.py decay_mask``), a
        hyper-connection's gains and biases, a few numbers each whose rest
        values are not 0 (``b_res`` starts at 8 I), and a looped stack's
        ``exit_gate`` bias.  None with one lane and one pass: every leaf
        decays, as it always did."""
        if self.cfg.total_ut_steps > 1:     # a looped stack: the gate's bias
            return lambda path: path[-2:] == ("exit_gate", "bias")
        if self.cfg.lanes == 1:
            return None
        return lambda path: len(path) > 1 and path[-1] != "phi" \
            and path[-2] in ("attn_hc", "mlp_hc")

    @staticmethod
    def is_state_leaf(path: tuple) -> bool:
        """Leaves of ``params`` that are state and no parameter (the
        engine keeps them from the optimizer and hands them, with the
        step's statistics, to :meth:`update_state_leaves`)."""
        from ..parallel.moe import STATE_LEAF

        return path[-1] == STATE_LEAF

    def update_state_leaves(self, held: dict, stats: dict) -> dict:
        """Each MoE layer's selection bias after a step that routed
        ``stats["tokens_per_expert"]`` (MoE layers, experts), summed over
        the step's micro-batches: traced inside the compiled step."""
        from ..parallel.moe import STATE_LEAF, bias_update

        cfg = self.cfg
        counts = stats["tokens_per_expert"]
        rate = cfg.moe.bias_update_rate
        with trace.device_span("moe/bias_update"):
            new = {}
            for name, sub in held.items():
                if name == "layers":    # a scanned stack: leading layer axis
                    gate = sub["moe"]["gate"]
                    n = gate[STATE_LEAF].shape[0]
                    new[name] = {"moe": {"gate": {STATE_LEAF: jax.vmap(
                        lambda c, b: bias_update(c, b, rate))(
                            counts[:n], gate[STATE_LEAF])}}}
                elif name.startswith("mtp_"):   # its row follows the stack's
                    new[name] = {"block": {"moe": {"gate": {
                        STATE_LEAF: bias_update(
                            counts[-1],
                            sub["block"]["moe"]["gate"][STATE_LEAF], rate)}}}}
                else:
                    new[name] = {"moe": {"gate": {STATE_LEAF: bias_update(
                        counts[int(name.rsplit("_", 1)[1])
                               - cfg.num_dense_layers],
                        sub["moe"]["gate"][STATE_LEAF], rate)}}}
            return new

    def dummy_inputs(self, batch_size: int = 2, seq_len: Optional[int] = None):
        S = seq_len or min(self.cfg.max_position_embeddings, 128)
        ids = jnp.zeros((batch_size, S), jnp.int32)
        return {"input_ids": ids, "labels": ids}

    def flops_per_token(self) -> float:
        cfg = self.cfg
        E, L = cfg.hidden_size, cfg.num_hidden_layers
        D, H = cfg.head_dim, cfg.num_attention_heads
        # a sparse FFN multiplies by the experts of a token's top_k that
        # live here (all of them unless this instance holds a share), and
        # its router
        dense = ffn = 3 * E * cfg.intermediate_size
        if cfg.moe is not None:
            ffn = (3 * E * cfg.expert_size
                   * (cfg.moe.top_k * cfg.moe.num_experts / cfg.moe.routed
                      + cfg.moe.num_shared_experts)
                   + E * cfg.moe.routed)
        attn = (3 if cfg.attn_gate else 2) * E * H * D \
            + 2 * E * cfg.kv_heads * D
        score = 2 * D               # channels a kept key costs a head
        if cfg.kv_lora_rank:        # latent attention: five projections
            Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            Rq = cfg.q_lora_rank
            attn = ((E * Rq + Rq * H * (Dn + Dr)) if Rq
                    else E * H * (Dn + Dr)) + E * (cfg.kv_lora_rank + Dr) \
                + cfg.kv_lora_rank * H * (Dn + Dv) + H * Dv * E \
                + (E * H if cfg.attn_gate == "head" else 0)
            score = Dn + Dr + Dv
        mtp = cfg.mtp_blocks        # a block, eh_proj, the head
        # a conv layer's mixer: in_proj to three thirds, out_proj, the taps
        convs = cfg.kinds.count(CONV)
        conv = 3 * E * E + E * E + E * cfg.conv_L_cache
        # a linear_attention layer's mixer: two projections in, the taps,
        # one projection out; its recurrence a token a value head is three
        # products of dk x dv forward (S^T k, k (x) delta, S^T q)
        linears = cfg.kinds.count(LINEAR)
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, d = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        conv_dim = 2 * Hk * dk + Hv * d
        linear = (E * (conv_dim + Hv * d) + E * 2 * Hv
                  + conv_dim * cfg.linear_conv_kernel_dim + Hv * d * E)
        # a kda_attention layer's: q, k, v, both gates' and the output
        # projection, beta's, the taps; the recurrence as above
        kdas = cfg.kinds.count(KDA)
        kda = (6 * E * H * D + E * H + 3 * H * D * cfg.short_conv_kernel_size
               + 3 * H * D * D)
        table = (1 if cfg.tie_word_embeddings else 2) * cfg.padded_vocab_size
        n = (table * E + (L - convs - linears - kdas) * attn + convs * conv
             + linears * (linear + 3 * Hv * dk * d) + kdas * kda
             + cfg.num_dense_layers * dense
             + (L - cfg.num_dense_layers) * ffn
             + mtp * (attn + ffn + 2 * E * E + cfg.padded_vocab_size * E))
        # QK^T and AV over the keys an attention layer keeps: all
        # positions, or the window where that is shorter
        S = cfg.max_position_embeddings
        keys = sum(min(S, cfg.window(k) or S) for k in cfg.kinds
                   if k not in MIXERS) if cfg.kinds else (L + mtp) * S
        return 6.0 * n + 6 * H * score * keys
